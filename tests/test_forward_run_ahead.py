"""``JaxTrainEngine.forward`` runs ahead of its own results: every
micro-batch of a call is uploaded (one ``device_put``) and dispatched
before the first result is fetched, the results come back in order, and
the numbers are those of the loop that waited for each result before the
next upload. How many results may wait is read off their size: a pass
that returns logits keeps the serial order."""

import dataclasses
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.algorithms.fused import FusedForwardInterface
from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.api.model import Model, ModelInterface, register_interface
from areal_tpu.api.train_config import TelemetryConfig
from areal_tpu.backend import jax_train
from areal_tpu.backend import microbatch as mbu
from areal_tpu.base import telemetry
from areal_tpu.models.config import MoEConfig
from areal_tpu.ops.attention import dispatch_label

from test_compute_copy import _lp_hook
from test_compute_copy import _engine as copy_engine
from test_remat_plan import _sample as remat_sample

MOE = dict(moe=MoEConfig(num_experts=4, top_k=2))
STEPS = ("infer/upload", "infer/dispatch", "infer/fetch")


def _engine(**cfg_kw):
    return copy_engine(opt=False, **cfg_kw)


def _sample(seed=0, n=10):
    return remat_sample(np.random.RandomState(seed), n=n)


# micro-batches the packer makes → (sequences, max_tokens_per_mb) of a
# sample and a spec that give them at the engine's buckets
PACKS = {1: (10, None), 2: (10, 64), 3: (9, 32), 4: (10, 48), 5: (15, 32)}


def _case(eng, n_mbs):
    n_seqs, max_tokens = PACKS[n_mbs]
    sample, spec = _sample(n=n_seqs), MicroBatchSpec(
        max_tokens_per_mb=max_tokens)
    assert len(_pack(eng, sample, spec)) == n_mbs
    return sample, spec


def _pack(eng, sample, spec):
    return mbu.split_into_microbatches(
        sample, spec, length_bucket=eng.length_bucket,
        rows_bucket=eng.rows_bucket, seqs_bucket=eng.seqs_bucket,
        fill_bucket=eng.fill_bucket, rows_multiple=eng.rows_multiple)


def _serial_forward(eng, sample, spec, post_hook):
    """The reference: the loop ``forward`` was — an array at a time up, one
    program, its result fetched, and only then the next micro-batch — on
    the engine's own program (``eng.forward`` ran once before)."""
    fn = eng._fwd_fns[(id(post_hook), eng._use_chunked_logprobs(post_hook))]
    mbs, outs = _pack(eng, sample, spec), []
    for mb in mbs:
        db = {k: jnp.asarray(v) for k, v in (
            *mb.grids.items(), *mb.scalars.items(),
            ("seq_rows", mb.seq_rows),
            ("seq_first_cols", mb.seq_first_cols),
            ("seq_last_cols", mb.seq_last_cols),
            ("seq_mask", mb.seq_mask))}
        with eng._mesh_ctx(), dispatch_label("forward"):
            outs.append(np.asarray(fn(eng.compute_params(), db)))
    return mbu.scatter_back(mbs, outs, sample.bs)


@pytest.fixture()
def registry():
    tel = telemetry.configure("run_ahead", "t", "trainer",
                              cfg=TelemetryConfig(enabled=True), push=False)
    yield tel.registry
    telemetry.shutdown()


def _call(reg, fn):
    """What the registry recorded of ``fn()``: the ``infer/*`` spans in the
    order they were ENTERED (span ids are handed out at entry), and how far
    the call moved the two counters and set the gauge."""
    before = reg.snapshot(reset=True)["counters"]
    result = fn()
    snap = reg.snapshot(reset=True)
    spans = sorted((s for s in snap["spans"] if s["name"].startswith("infer/")),
                   key=lambda s: s["span_id"])
    moved = {k: snap["counters"].get(k, 0.0) - before.get(k, 0.0)
             for k in ("infer/mbs", "infer/mbs_run_ahead")}
    return result, spans, moved, snap["gauges"].get("infer/inflight_peak")


@pytest.mark.parametrize("hooked", [True, False], ids=["hook", "logits"])
@pytest.mark.parametrize("n_mbs", [1, 2, 5])
@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_results_are_the_serial_loops(kind, n_mbs, hooked):
    eng = _engine(**(MOE if kind == "moe" else {}))
    sample, spec = _case(eng, n_mbs)
    hook = _lp_hook if hooked else None
    got = eng.forward(sample, spec, post_hook=hook)
    want = _serial_forward(eng, sample, spec, hook)
    assert len(got) == len(want) == sample.bs
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g.view(np.uint8), w.view(np.uint8))
    assert eng.infer_run_ahead() == (n_mbs - 1) / n_mbs


@pytest.mark.parametrize("n_mbs", [1, 4])
def test_every_dispatch_precedes_the_first_fetch(registry, monkeypatch, n_mbs):
    eng = _engine()
    sample, spec = _case(eng, n_mbs)
    assert eng.infer_run_ahead() is None
    eng.forward(sample, spec, post_hook=_lp_hook)  # compile
    puts = []
    orig = jax.device_put
    monkeypatch.setattr(jax, "device_put",
                        lambda x, *a, **kw: puts.append(x) or orig(x, *a, **kw))
    _, spans, moved, peak = _call(
        registry, lambda: eng.forward(sample, spec, post_hook=_lp_hook))
    monkeypatch.undo()
    names = [s["name"] for s in spans]
    assert names == (["infer/split_pack"]
                     + ["infer/upload", "infer/dispatch"] * n_mbs
                     + ["infer/fetch"] * n_mbs + ["infer/scatter_back"])
    # one upload a micro-batch: the whole host dict in one device_put
    assert len(puts) == n_mbs and all(
        set(p) >= {"tokens", "segment_ids", "positions", "seq_mask"}
        for p in puts)
    assert moved == {"infer/mbs": n_mbs, "infer/mbs_run_ahead": n_mbs - 1}
    assert peak == n_mbs
    fetches = [s for s in spans if s["name"] == "infer/fetch"]
    assert [s["attrs"] for s in fetches] == (
        [{}] * (n_mbs - 1) + [{"run_ahead": n_mbs - 1}])
    assert eng.infer_run_ahead() == (n_mbs - 1) / n_mbs


@pytest.mark.parametrize("room, peak", [(0, 1), (2, 3)],
                         ids=["a_result_is_over_the_bound",
                              "two_results_fit"])
def test_the_bound_is_read_off_the_results_bytes(registry, monkeypatch,
                                                 room, peak):
    """The logits case without allocating logits: with the bound under ONE
    result's size every result is fetched before the next upload — the
    serial order; with room for two, the oldest leaves when a third is
    dispatched."""
    eng, n = _engine(), 5
    sample, spec = _case(eng, n)
    want = eng.forward(sample, spec, post_hook=_lp_hook)
    R, L = _pack(eng, sample, spec)[0].layout.shape
    one = R * L * 4  # a hooked result: [R, L] float32
    monkeypatch.setattr(jax_train, "_INFLIGHT_RESULT_BYTES",
                        room * one if room else one - 1)
    got, spans, moved, seen_peak = _call(
        registry, lambda: eng.forward(sample, spec, post_hook=_lp_hook))
    names = [s["name"] for s in spans if s["name"] in STEPS]
    if room == 0:
        assert names == list(STEPS) * n
        assert moved["infer/mbs_run_ahead"] == 0
    else:
        up_dispatch = list(STEPS[:2])
        assert names == (up_dispatch * 3 + ["infer/fetch"]
                         + (up_dispatch + ["infer/fetch"]) * (n - 3)
                         + ["infer/fetch"] * 2)
        assert moved["infer/mbs_run_ahead"] == n - 1
    assert seen_peak == peak
    last = [s for s in spans if s["name"] == "infer/fetch"][-1]
    assert last["attrs"] == {"run_ahead": moved["infer/mbs_run_ahead"]}
    for g, w in zip(got, want):  # oldest first: the order of the results
        np.testing.assert_array_equal(g, w)


def test_a_dispatch_that_raises_leaves_nothing_behind(registry):
    eng, n, fail_at = _engine(), 5, 3
    sample, spec = _case(eng, n)
    want = eng.forward(sample, spec, post_hook=_lp_hook)
    key = (id(_lp_hook), True)
    fn, results = eng._fwd_fns[key], []

    def failing(params, batch):
        if len(results) == fail_at:
            raise RuntimeError("dispatch failed")
        out = fn(params, batch)
        results.append(weakref.ref(out))
        return out

    eng._fwd_fns[key] = failing
    counted = eng.infer_mbs
    with pytest.raises(RuntimeError, match="dispatch failed") as held:
        eng.forward(sample, spec, post_hook=_lp_hook)
    # The traceback still holds the call's frame, and with it the one
    # result the loop's own name refers to; the queue let go of the rest.
    gc.collect()
    assert len(results) == fail_at
    assert [r() is None for r in results] == [True] * (fail_at - 1) + [False]
    del held
    gc.collect()
    assert all(r() is None for r in results)
    assert eng.infer_mbs == counted  # a call that failed counts nothing
    # ... and the next call starts clean
    eng._fwd_fns[key] = fn
    got, spans, moved, peak = _call(
        registry, lambda: eng.forward(sample, spec, post_hook=_lp_hook))
    assert moved == {"infer/mbs": n, "infer/mbs_run_ahead": n - 1}
    assert peak == n
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@dataclasses.dataclass
class _ScaledLogprobs(ModelInterface):
    """A child of the fused interface with a result of its own."""

    key: str = "scaled"
    scale: float = 1.0

    def __post_init__(self):
        def hook(logprobs, batch):
            return logprobs * self.scale

        hook.wants_token_logprobs = True
        self._hook = hook

    def inference(self, model, data, mb_spec):
        per_sample = model.module.forward(data, mb_spec, post_hook=self._hook)
        return SequenceSample(
            ids=list(data.ids), keys={self.key},
            seqlens={self.key: [list(s) for s in
                                data.seqlens["packed_input_ids"]]},
            data={self.key: np.concatenate(per_sample)})


register_interface("test_scaled_logprobs", _ScaledLogprobs)


def test_two_threads_get_their_own_results():
    eng, n = _engine(), 5
    model = Model("actor", eng)
    sample, spec = _case(eng, n)
    children = {f"x{s}": ("test_scaled_logprobs",
                          {"key": f"x{s}", "scale": float(s)})
                for s in (1, 2, 3)}
    fused = FusedForwardInterface(interfaces=children)
    alone = {k: fused._children[k].inference(model, sample, spec).data[k]
             for k in children}
    assert not np.array_equal(alone["x1"], alone["x2"])
    counted = eng.infer_mbs
    for _ in range(3):
        out = fused.inference(model, sample, spec)
        for k in children:
            np.testing.assert_array_equal(out.data[k], alone[k])
    # every call of every thread counted, none lost
    assert eng.infer_mbs - counted == 3 * len(children) * n
    assert eng.infer_run_ahead() == (n - 1) / n


def test_upload_span_carries_the_packers_counts(registry):
    """What ``infer_pack_fill_span_pct`` reads: real and padded tokens of
    each micro-batch on its own ``infer/upload`` span."""
    eng = _engine()
    sample, spec = _case(eng, 3)
    _, spans, _, _ = _call(
        registry, lambda: eng.forward(sample, spec, post_hook=_lp_hook))
    mbs = _pack(eng, sample, spec)
    R, L = mbs[0].layout.shape
    assert [s["attrs"] for s in spans if s["name"] == "infer/upload"] == [
        {"real_tokens": mb.n_tokens, "padded_tokens": R * L,
         "n_mbs": len(mbs), "grid": f"{R}x{L}"} for mb in mbs]
