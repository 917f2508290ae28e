"""Attention under a learned selection of keys (models/dsa.py, ops/pallas/
sparse_attention.py) on the CPU: the kernels in Pallas's interpreter
against the XLA form of the same entry, the selection against a sort on
the host (ties to the earlier key), the backward's selection against the
forward's under every remat entry, and what the engine does with the
indexer's parameters (no gradient, no weight decay: bit for bit)."""

import dataclasses
import sys
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import dsa, transformer
from areal_tpu.models.config import FULL, SparseAttnConfig
from areal_tpu.ops import attention
from areal_tpu.ops.pallas import sparse_attention as sk

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SA = SparseAttnConfig(n_heads=4, head_dim=16, top_k=32)


def rows(lens_by_row, T):
    seg = np.zeros((len(lens_by_row), T), np.int32)
    for r, lens in enumerate(lens_by_row):
        at = 0
        for i, n in enumerate(lens):
            seg[r, at:at + n] = i + 1
            at += n
    return jnp.asarray(seg)


def inputs(B, T, seed=0, ties=False, Hq=4, Hkv=2, D=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, T, Hq, D))
    k = jax.random.normal(ks[1], (B, T, Hkv, D))
    v = jax.random.normal(ks[2], (B, T, Hkv, D))
    qi = jax.random.normal(ks[3], (B, T, SA.q_dim))
    ki = jax.random.normal(ks[4], (B, T, SA.head_dim))
    w = jax.random.normal(ks[5], (B, T, SA.n_heads))
    if ties:  # forty keys with ONE indexer key: forty equal scores a query
        ki = ki.at[:, 100:140].set(ki[:, 100:101])
    return q, k, v, qi, ki, w


def host_selection(scores, seg, top_k):
    """bool [T, S] by a stable sort on the host: the top_k largest scores
    among a query's causal same-document keys, ties to the earlier key."""
    scores, seg = np.asarray(scores), np.asarray(seg)
    T = len(seg)
    out = np.zeros((T, T), bool)
    for t in range(T):
        if seg[t] == 0:
            continue
        keys = [s for s in range(t + 1) if seg[s] == seg[t]]
        order = sorted(keys, key=lambda s: (-scores[t, s], s))
        out[t, order[:top_k]] = True
    return out


# ---- the selection ----

@pytest.mark.parametrize("lens,T,ties", [
    ([[300, 350], [20, 670]], 700, True),
    ([[513, 100]], 640, False),  # a document boundary inside a key tile
])
def test_the_selected_set_is_the_top_k_with_ties_to_the_earlier_key(
        lens, T, ties):
    _, _, _, qi, ki, w = inputs(len(lens), T, ties=ties)
    seg = rows(lens, T)
    scores = dsa.scores_xla(qi, ki, w, SA.n_heads)
    meta = dsa.select_xla(scores, seg, SA.top_k)
    mask = np.asarray(dsa.mask_xla(scores, meta, seg))
    for r in range(len(lens)):
        want = host_selection(scores[r], seg[r], SA.top_k)
        np.testing.assert_array_equal(mask[r], want)
    # |S_t| = min(p + 1, top_k) for every real token, exactly
    assert int(mask.sum()) == dsa.host_selected_pairs(
        [n for row in lens for n in row], SA.top_k)
    if ties:  # the cut fell inside a run of equal scores somewhere
        on_tau = (np.asarray(sk.sortable(scores)) == np.asarray(
            meta[..., 0:1])) & np.asarray(dsa._valid_xla(seg))
        assert (on_tau.sum(-1) > 1).any()
        assert (on_tau & ~mask).any()


@pytest.mark.parametrize("lens,T,ties", [
    ([[300, 350], [20, 670]], 700, True),
    ([[1000]], 1000, False),
])
def test_the_kernels_selection_is_the_xla_forms(lens, T, ties):
    """The kernel ``dsa_select`` (interpreted): a bisection on the scores'
    32 bits and one on the row index give the (tau, cut) that ``lax.top_k``
    gives. The kernel makes its scores a tile at a time and XLA a row at a
    time, so a score may differ in its last bits between them (the CPU's
    matmul sums in another order, and a score is a signed sum of sixteen
    terms): the thresholds agree to under 1,024 units in the last place
    and the masks on all but the pairs that close to a threshold (a run
    of forty EQUAL scores crosses the other form's threshold whole: a few
    hundredths of the pairs with the ties, under one without). That the kernels' OWN selection is exact — min(p + 1,
    top_k) pairs a query — is the next tests' count."""
    _, _, _, qi, ki, w = inputs(len(lens), T, ties=ties)
    seg = rows(lens, T)
    more = dsa.padded_len(T) - T

    def pad(a):
        return jnp.pad(a, [(0, 0), (0, more)] + [(0, 0)] * (a.ndim - 2))

    qi, ki, w, seg = pad(qi), pad(ki), pad(w), pad(seg)
    got = sk.select(qi, sk.tiled_key(ki), w, seg, SA.top_k, SA.n_heads,
                    interpret=True)
    scores = dsa.scores_xla(qi, ki, w, SA.n_heads)
    want = dsa.select_xla(scores, seg, SA.top_k)
    tau_k, tau_x = (np.asarray(m[..., 0], np.int64) for m in (got, want))
    selects = tau_x > sk.INT_MIN
    np.testing.assert_array_equal(tau_k > sk.INT_MIN, selects)
    assert np.abs(tau_k - tau_x)[selects].max() < 1024
    m_k, m_x = (np.asarray(dsa.mask_xla(scores, m, seg)) for m in (got, want))
    assert (m_k != m_x).sum() < (5e-2 if ties else 1e-2) * m_x.sum()
    if ties:
        assert int((got[..., 1] < 2 ** 31 - 1).sum()) > 0


def test_tile_ranges_hold_every_pair_and_skip_other_documents():
    seg = rows([[300, 350], [20, 670], [0]], 1024)
    lo, hi, qlo, qhi = (np.asarray(a) for a in sk.tile_ranges(seg))
    valid = np.asarray(dsa._valid_xla(seg))
    nq, nk = 1024 // sk.BQ, 1024 // sk.BKV
    for b in range(3):
        tiles = valid[b].reshape(nq, sk.BQ, nk, sk.BKV).any((1, 3))
        for i in range(nq):
            js = np.flatnonzero(tiles[i])
            if len(js):
                assert lo[b, i] <= js[0] and js[-1] <= hi[b, i]
            else:
                assert hi[b, i] < lo[b, i]
        for j in range(nk):
            qs = np.flatnonzero(tiles[:, j])
            if len(qs):
                assert qlo[b, j] <= qs[0] and qs[-1] <= qhi[b, j]
            else:
                assert qhi[b, j] < qlo[b, j]
    # the second row's first document ends in the first key tile: the
    # query tiles of the second document still start there
    assert lo[1].tolist() == [0, 0, 0, 0][:nq]


# ---- the kernels against the XLA form ----

@pytest.mark.parametrize("lens,T,ties", [
    ([[300, 350], [20, 670]], 700, True),
    ([[513, 100]], 640, False),
])
def test_the_kernels_interpreted_equal_the_xla_form(lens, T, ties):
    """Output, the pairs a query attended and all three gradients; a
    padding query selects nothing and returns zeros."""
    q, k, v, qi, ki, w = inputs(len(lens), T, ties=ties)
    seg = rows(lens, T)

    def run(interpret):
        def f(q, k, v):
            o, n = dsa.sparse_attention(q, k, v, qi, ki, w, seg, SA,
                                        impl="reference", interpret=interpret)
            return jnp.sum(o * jnp.cos(o)), (o, n)

        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))(q, k, v)

    (_, (o_x, n_x)), g_x = run(False)
    (_, (o_k, n_k)), g_k = run(True)
    np.testing.assert_array_equal(n_k, n_x)
    np.testing.assert_allclose(o_k, o_x, atol=2e-5)
    for a, b in zip(g_k, g_x):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-4)
    pad = np.asarray(seg) == 0
    assert pad.any() and not np.asarray(n_k)[pad].any()
    assert not np.asarray(o_k)[pad].any() and not np.asarray(o_x)[pad].any()
    assert int(n_k.sum()) == dsa.host_selected_pairs(
        [n for row in lens for n in row], SA.top_k)


def test_the_indexer_takes_no_gradient():
    q, k, v, qi, ki, w = inputs(1, 200)
    seg = rows([[200]], 200)

    def f(qi, ki, w):
        o, _ = dsa.sparse_attention(q, k, v, qi, ki, w, seg, SA,
                                    impl="reference")
        return jnp.sum(o ** 2)

    for g in jax.grad(f, argnums=(0, 1, 2))(qi, ki, w):
        assert not np.any(np.asarray(g))


def test_the_calls_are_counted_under_a_name_of_their_own():
    q, k, v, qi, ki, w = inputs(1, 200, seed=3)
    seg = rows([[200]], 200)
    before_g, before_i = dsa.geometry_counts(), dsa.impl_counts()
    with attention.dispatch_label("dsa-test"):
        jax.eval_shape(lambda: dsa.sparse_attention(
            q, k, v, qi, ki, w, seg, SA, impl="reference"))
        jax.eval_shape(lambda: dsa.sparse_attention(
            q, k, v, qi, ki, w, seg, SA, impl="pallas"))
    assert attention.dispatch_counts()["dsa-test"] == {"sparse": 2}
    geo, impl = dsa.geometry_counts(), dsa.impl_counts()
    assert geo[(200, 200, sk.BQ, sk.BKV, 32)] - before_g.get(
        (200, 200, sk.BQ, sk.BKV, 32), 0) == 1
    assert geo[(200, 512, sk.BQ, sk.BKV, 32)] - before_g.get(
        (200, 512, sk.BQ, sk.BKV, 32), 0) == 1
    assert impl["xla"] - before_i.get("xla", 0) == 1
    assert impl["kernel"] - before_i.get("kernel", 0) == 1
    assert dsa.kernel_padded_len("pallas", 10752) == 10752
    assert dsa.kernel_padded_len("pallas", 7552) == 7680
    assert dsa.kernel_padded_len("reference", 7552) is None


# ---- through the model ----

@pytest.mark.parametrize("remat", [False, "full", "attention", "matmuls"])
def test_the_backward_selects_the_pairs_the_forward_did(remat):
    """Every mask a backward used is, bit for bit, the mask the FIRST
    forward of its layer made — under every entry the engine can pick —
    and every one holds ``sum(min(p + 1, top_k))`` pairs."""
    import test_keye_vl2_parity as t

    cfg, params = t.model()
    row, seg, pos, _ = t.packed_row((70, 83), 160)
    log = []
    dsa.RECORD_MASKS = log
    try:
        def loss(p):
            out, _ = transformer.forward(
                p, cfg, row, pos, segment_ids=seg, attn_impl="reference",
                return_kv=False, remat=remat)
            return jnp.sum(jnp.sin(out))

        jax.block_until_ready(jax.jit(jax.grad(loss))(params))
        jax.effects_barrier()
    finally:
        dsa.RECORD_MASKS = None
    first = [m for which, m in log if which == "fwd"][:cfg.n_layers]
    back = [m for which, m in log if which == "bwd"]
    assert len(first) == len(back) == cfg.n_layers
    want = dsa.host_selected_pairs((70, 83), cfg.dsa.top_k)
    for layer, bwd in enumerate(reversed(back)):
        np.testing.assert_array_equal(bwd, first[layer])
        assert int(bwd.sum()) == want
    # the layers select differently (their own activations decide)
    assert (first[0] != first[1]).any()


def test_what_the_scan_keeps_of_a_selection_is_reckoned():
    import test_keye_vl2_parity as t

    cfg, _ = t.model()
    per = dsa.selection_kept_bytes(cfg.dsa, 4)
    assert per == (32 + 8) * 4 + 4 * 4 + 16
    plain = dataclasses.replace(cfg, dsa=None)
    kept, base = (transformer.remat_kept_bytes(c, 1000, 4)
                  for c in (cfg, plain))
    assert kept["full"] - base["full"] == cfg.n_layers * 1000 * per
    assert kept["attention"] - base["attention"] == kept["full"] - base["full"]
    widths = dsa.matmul_widths(cfg.dsa)
    assert (kept["matmuls"] - base["matmuls"]
            == cfg.n_layers * 1000 * (per + 4 * widths))
    assert transformer.attention_kept_bytes_per_token(
        cfg, "full", 4, kernel=True) == per
    assert transformer.attention_kept_bytes_per_token(
        cfg, False, 4, kernel=True) == 0


def _engine(weight_decay=0.0):
    import test_keye_vl2_parity as t
    from areal_tpu.api.model import FinetuneSpec
    from areal_tpu.api.train_config import OptimizerConfig
    from areal_tpu.backend.jax_train import JaxTrainEngine

    cfg, params = t.model()
    # a copy: the engine donates its weights, ``t.model()`` caches its own
    return cfg, params, JaxTrainEngine(
        cfg, jax.tree.map(jnp.array, params), OptimizerConfig(type="adamw", lr=1e-2,
                                     weight_decay=weight_decay),
        FinetuneSpec(1, 8, 4), compute_dtype="float32", length_bucket=16,
        rows_bucket=1, seqs_bucket=4)


def test_a_step_under_weight_decay_leaves_the_indexer_bit_for_bit():
    """The engine's grad program gives the indexer exactly zero, and a
    step with weight decay > 0 moves every other matrix and leaves the
    indexer's where the seed drew them; the step's exact counts equal the
    host's from the batch's document lengths alone."""
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.api.train_config import TelemetryConfig
    from areal_tpu.base import telemetry

    cfg, params, eng = _engine(weight_decay=0.1)
    lens = [40, 75, 23, 64]
    rng = np.random.RandomState(0)
    sample = SequenceSample.from_default(
        ids=[f"s{i}" for i in range(len(lens))],
        data={"packed_input_ids": rng.randint(
            2, 67, sum(lens)).astype(np.int32),
            "loss_mask": np.ones(sum(lens), np.float32)},
        seqlens=lens)

    def sq_loss(logits, batch):
        w = (batch["segment_ids"] > 0).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return jnp.sum(jnp.sum(lp * lp, axis=-1) * w), {"n": jnp.sum(w)}

    before = jax.tree.map(np.asarray, params)
    telemetry.configure("t", "t", "trainer", 0,
                        TelemetryConfig(enabled=True), push=False)
    try:
        stats = eng.train_batch(sample, MicroBatchSpec(max_tokens_per_mb=128),
                                sq_loss, lambda mb: mb.n_tokens)
        snap = telemetry.get().snapshot()
    finally:
        telemetry.shutdown()
    after = jax.tree.map(np.asarray, eng.params)
    for name, a in after["layers"][dsa.INDEXER].items():
        np.testing.assert_array_equal(
            a, before["layers"][dsa.INDEXER][name], err_msg=name)
    for name in ("wq", "wk", "wo", "router", "e_gate", "ln1"):
        assert (after["layers"][name] != before["layers"][name]).any(), name
    top_k = cfg.dsa.top_k
    assert stats["dsa_selected_pairs"] == dsa.host_selected_pairs(lens, top_k)
    assert stats["dsa_causal_pairs"] == dsa.host_causal_pairs(lens)
    assert stats["dsa_queries"] == sum(lens)
    assert stats["dsa_selecting_queries"] == sum(
        max(n - top_k, 0) for n in lens)
    gauges = snap["gauges"]
    assert gauges["train/dsa_selected_pairs"] == stats["dsa_selected_pairs"]
    assert gauges["train/dsa_selecting_query_frac"] == pytest.approx(
        stats["dsa_selecting_queries"] / sum(lens))
    spans = [s for s in snap["spans"] if s["name"] == "train/finish_stats"]
    assert spans[-1]["attrs"]["dsa_selected_pairs"] == stats[
        "dsa_selected_pairs"]


def test_a_step_whose_int32_counts_would_wrap_reports_none(monkeypatch):
    """The device adds the exact counts in int32: 43 documents of 9,919
    tokens a step fit, 44 do not — and a step past the bound reports no
    ``dsa_*`` count (and sets no gauge) rather than a wrapped one."""
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample

    assert dsa.counts_fit([9919] * 43) and not dsa.counts_fit([9919] * 44)
    cfg, params, eng = _engine()
    lens = [40, 75]
    sample = SequenceSample.from_default(
        ids=["s0", "s1"],
        data={"packed_input_ids": np.arange(
            2, 2 + sum(lens)).astype(np.int32) % 60 + 2,
            "loss_mask": np.ones(sum(lens), np.float32)},
        seqlens=lens)

    def loss(logits, batch):
        w = (batch["segment_ids"] > 0).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return jnp.sum(lp[..., 0] * w), {"n": jnp.sum(w)}

    spec = MicroBatchSpec(max_tokens_per_mb=128)
    monkeypatch.setattr(dsa, "COUNT_MAX", dsa.host_causal_pairs(lens))
    assert eng.train_batch(sample, spec, loss, lambda mb: mb.n_tokens)[
        "dsa_causal_pairs"] == dsa.host_causal_pairs(lens)
    monkeypatch.setattr(dsa, "COUNT_MAX", dsa.host_causal_pairs(lens) - 1)
    stats = eng.train_batch(sample, spec, loss, lambda mb: mb.n_tokens)
    assert not any(k.startswith("dsa_") for k in stats) and "n" in stats


def test_the_optimizer_passes_over_a_buffer_subtree():
    from areal_tpu.backend.jax_train import add_decayed_weights

    params = {"layers": {"wq": jnp.ones((2, 3)),
                         dsa.INDEXER: {"wq": jnp.ones((2, 3)),
                                       "k_norm": jnp.ones((2,))}}}
    grads = jax.tree.map(jnp.zeros_like, params)
    tx = add_decayed_weights(0.5)
    updates, _ = tx.update(grads, tx.init(params), params)
    assert np.all(np.asarray(updates["layers"]["wq"]) == 0.5)
    for leaf in jax.tree.leaves(updates["layers"][dsa.INDEXER]):
        assert not np.any(np.asarray(leaf))


def test_the_scopes_the_benchmark_reads_are_the_programs():
    import test_keye_vl2_parity as t
    from areal_tpu.base import telemetry

    cfg, params = t.model()
    text = t.system_logits.lower(params, cfg, t.tokens(1, 31)).as_text(
        debug_info=True)
    for scope in telemetry.DSA_SCOPES + ("attention", "qkv_proj", "o_proj",
                                         "moe_router", "moe_experts"):
        assert scope in text, scope
    assert "attention/dsa_select" in text  # nested inside "attention"
    assert cfg.rope_of(FULL).base == 10000000
