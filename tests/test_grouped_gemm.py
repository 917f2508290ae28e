"""The experts' grouped GEMM: jax's Pallas kernel (``megablox`` ``gmm`` /
``tgmm`` under ``moe._gmm_rows``' own VJP) in its interpreter against
``jax.lax.ragged_dot`` and against a float32 loop over the groups, the
rule that reads its tile off the operands' shapes, and the one rule that
chooses between the two paths: the kernel where a pass is bounded."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import moe, transformer
from areal_tpu.models.config import MoEConfig, tiny_config


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


def _close(got, want, what, steps=1):
    """bf16 roundings of float32 sums taken in another order: ``steps``
    rounding steps of the larger magnitudes apart at most."""
    got, want = _f32(got), _f32(want)
    scale = max(float(np.abs(want).max()), 1e-6)
    assert np.abs(got - want).max() <= steps * 2 ** -7 * scale, what


def _loop(xs, w, ct, sizes):
    """The grouped GEMM and both gradients as a float32 loop over the
    groups; rows past the groups are zero and take no gradient."""
    xs, w, ct = (np.asarray(a.astype(jnp.float32)) for a in (xs, w, ct))
    out, d_xs, d_w = np.zeros_like(ct), np.zeros_like(xs), np.zeros_like(w)
    start = 0
    for g, size in enumerate(np.asarray(sizes)):
        rows = slice(start, start + size)
        out[rows] = xs[rows] @ w[g]
        d_xs[rows] = ct[rows] @ w[g].T
        d_w[g] = xs[rows].T @ ct[rows]
        start += size
    return out, d_xs, d_w


# (rows, group sizes), three groups that can expect 1024 rows each (the
# least the tile rule takes): rows past the groups, empty groups,
# everything in one group, boundaries on and off the 512-row tile, a tile
# that no group owns, and live rows 0 / R-1 / R.
R = 3072
SIZES = {
    "rows-past-the-groups": [300, 612, 400],
    "an-empty-group": [700, 0, 863],
    "all-rows-in-one-group": [0, R, 0],
    "boundaries-on-the-tile": [512, 0, 1536],
    "a-tile-nobody-owns": [5, 6, 7],
    "live-rows-0": [0, 0, 0],
    "live-rows-R-1": [1023, 1024, 1024],
    "live-rows-R": [1024, 1023, 1025],
}
# (K, N): widths that are whole lanes and nothing more — 896 = 7 x 128
# (Mellum 2's experts), 2688 = 21 x 128 (Nemotron 3's) — and a tile that
# splits the contraction.
WIDTHS = {"128-256": (128, 256), "896-256": (896, 256), "256-896": (256, 896),
          "2688-128": (2688, 128)}


def _operands(sizes, K, N, nan_tail=True):
    G = len(sizes)
    keys = jax.random.split(jax.random.PRNGKey(K + N + sum(sizes)), 3)
    xs = jax.random.normal(keys[0], (R, K), jnp.bfloat16)
    ct = jax.random.normal(keys[2], (R, N), jnp.bfloat16)
    if nan_tail:  # what the TPU leaves in rows nobody wrote
        live = sum(sizes)
        xs, ct = xs.at[live:].set(jnp.nan), ct.at[live:].set(jnp.nan)
    w = jax.random.normal(keys[1], (G, K, N), jnp.bfloat16) * 0.1
    return xs, w, ct, jnp.asarray(sizes, jnp.int32)


def _run(xs, w, ct, sizes, kernel):
    out, vjp = jax.vjp(lambda xs, w: moe._grouped_matmul(
        xs, w, sizes, kernel=kernel, interpret=True), xs, w)
    return (out, *vjp(ct))


@pytest.mark.parametrize("case", SIZES)
def test_kernel_equals_ragged_dot_and_the_loop(case):
    """Outputs and both gradients of one grouped GEMM, NaN in every row
    past ``sum(group_sizes)``: zero there and zero gradient either way, an
    expert with no row takes no gradient."""
    sizes = SIZES[case]
    K, N, G = 256, 128, len(sizes)
    xs, w, ct, gs = _operands(sizes, K, N)
    want = _run(xs, w, ct, gs, kernel=False)
    assert moe.gemm_counts()[(R, K, N, G)] == "ragged_dot"
    got = _run(xs, w, ct, gs, kernel=True)
    assert moe.gemm_counts()[(R, K, N, G)] == "gmm"
    live = sum(sizes)
    loop = _loop(jnp.nan_to_num(xs), w, jnp.nan_to_num(ct), sizes)
    for g, r, f, what in zip(got, want, loop, ("out", "d_xs", "d_w")):
        assert g.dtype == r.dtype and g.shape == r.shape
        assert not np.isnan(_f32(g)).any(), what
        _close(g, r, what)
        _close(g, jnp.asarray(f), what + " against the float32 loop")
    assert not _f32(got[0])[live:].any() and not _f32(got[1])[live:].any()
    assert not _f32(got[2])[np.asarray(sizes) == 0].any()


@pytest.mark.parametrize("widths", WIDTHS)
def test_kernel_at_widths_that_are_whole_lanes_only(widths):
    K, N = WIDTHS[widths]
    sizes = SIZES["rows-past-the-groups"]
    xs, w, ct, gs = _operands(sizes, K, N)
    assert moe.gemm_tiling(R, K, N, 3) is not None
    assert moe.gemm_tiling(R, N, K, 3) is not None  # the rows' gradient
    got = _run(xs, w, ct, gs, kernel=True)
    assert moe.gemm_counts()[(R, K, N, 3)] == "gmm"
    for g, r, what in zip(got, _run(xs, w, ct, gs, kernel=False),
                          ("out", "d_xs", "d_w")):
        _close(g, r, what)


# ---- the pass around the GEMMs ----

# gated silu and ungated relu2 experts; bounded (rows < entries: the kernel
# under the cond, ``ragged_dot`` in its whole-buffer branch) and whole.
PASSES = {
    "gated-bounded": (True, 2, 8),
    "gated-whole": (True, 4, 4),
    "ungated-bounded": (False, 2, 8),
    "ungated-whole": (False, 4, 4),
}


def _pass_operands(gated, held, routed, N=1024, k=4, D=128, F=256):
    keys = jax.random.split(jax.random.PRNGKey(held), 6)
    xf = jax.random.normal(keys[0], (N, D), jnp.bfloat16)
    top_i = jax.random.randint(keys[1], (N, k), 0, routed)
    gates = jax.random.uniform(keys[2], (N * k,), jnp.float32)
    w = [jax.random.normal(kk, s, jnp.bfloat16) * 0.1 for kk, s in zip(
        keys[3:], ((held, D, F), (held, D, F), (held, F, D)))]
    eid = moe._held_eid(top_i, jnp.ones((N,)), 0, held)
    return xf, gates, w, eid, k


@pytest.mark.parametrize("case", PASSES)
def test_expert_pass_equals_ragged_dot(case):
    """``_sorted_expert_ffn`` asked for the kernel: the per-token sums and
    every gradient (tokens, gates, each weight matrix) against the same
    pass on ``ragged_dot``; ``gemm_counts()`` says ``gmm`` for ``rows <
    M`` and ``ragged_dot`` for ``rows == M``."""
    gated, held, routed = PASSES[case]
    xf, gates, w, eid, k = _pass_operands(gated, held, routed)
    M = eid.shape[0]
    rows = moe.sorted_rows(M, held, routed)
    assert (rows < M) == (held < routed)
    act = jax.nn.silu if gated else moe.relu2

    def run(gemm):
        def loss(xf, gates, gate_w, up_w, down_w):
            y, kept, _ = moe._sorted_expert_ffn(
                xf, eid, gates, None, gate_w if gated else None, up_w,
                down_w, rows, act, k, gemm)
            return jnp.sum(y.astype(jnp.float32) ** 2), y

        moe._GEMMS.clear()
        jax.clear_caches()  # the pass is jitted: traced once a shape
        (_, y), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4) if gated else (0, 1, 3, 4),
            has_aux=True)(xf, gates, *w)
        return (y, *grads), moe.gemm_counts()

    want, how = run("ragged_dot")
    assert set(how.values()) == {"ragged_dot"}
    got, how = run("gmm_interpret")
    assert {r for (r, *_), h in how.items() if h == "gmm"} == (
        {rows} if rows < M else set())
    assert {r for (r, *_), h in how.items() if h == "ragged_dot"} == {M}
    for i, (g, r) in enumerate(zip(got, want)):
        _close(g, r, f"output {i}", steps=2)


@pytest.mark.parametrize("live", ["fits", "one-row-too-many"])
def test_bounded_pass_with_the_kernel_equals_the_whole_pass(live):
    """``_bounded_pass``: the bounded branch (the kernel) where the live
    rows fit, the whole buffer (``ragged_dot``) where they do not, against
    the whole pass — exact both, within bf16 rounding of each other, and
    no longer bit-identical where the branch taken is the kernel's (on the
    CPU path, both ``ragged_dot``, they are: tests/test_moe_dispatch.py)."""
    xf, gates, w, eid, k = _pass_operands(True, 2, 8)
    M = eid.shape[0]
    R_ = moe.sorted_rows(M, 2, 8)
    if live == "one-row-too-many":  # R + 1 live rows: the fallback
        eid = jnp.where(jnp.arange(M) <= R_, jnp.arange(M) % 2, 2)
    assert (int(jnp.sum(eid < 2)) <= R_) == (live == "fits")

    def run(rows, gemm):
        def loss(xf, gates, *w):
            y, _, counts = moe._sorted_expert_ffn(
                xf, eid, gates, None, *w, rows, jax.nn.silu, k, gemm)
            return jnp.sum(y.astype(jnp.float32) ** 2), (y, counts)

        (_, (y, counts)), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(xf, gates, *w)
        return (y, *grads), counts

    whole, _ = run(M, "ragged_dot")
    bounded, counts = run(R_, "gmm_interpret")
    assert float(counts["full_passes"]) == (live != "fits")
    for i, (g, r) in enumerate(zip(bounded, whole)):
        _close(g, r, f"output {i}", steps=2)
    if live != "fits":  # the fallback IS the whole pass on ragged_dot
        plain, _ = run(R_, "ragged_dot")
        for g, r in zip(bounded, plain):
            assert np.array_equal(_f32(g), _f32(r))


# ---- the rule ----

# The bounded passes of the benchmark's expert cells: (rows, the tokens'
# width, the experts' width, groups). OLMoE's are what its ``ep`` shards
# would run were the bound engaged there.
MELLUM = [(r, 2304, 896, 16) for r in (24064, 26624)]
OLMOE_BOUNDED = [(r, 2048, 1024, 16) for r in (14848, 15872)]
NEMOTRON = [(2560, 1024, 2688, 8)]


def test_benchmark_rows_are_the_bounded_passes():
    assert [moe.sorted_rows(t * 8, 16, 64) for t in (6016, 6656)] == [
        24064, 26624]
    assert [moe.sorted_rows(t * 8, 16, 64) for t in (3712, 3968)] == [
        14848, 15872]
    assert {moe.sorted_rows(t * 22, 8, 512) for t in (3072, 3456, 3712)
            } == {2560}


@pytest.mark.parametrize("shape", MELLUM + [(32768, 2048, 1024, 16)],
                         ids=lambda s: "x".join(map(str, s)))
def test_the_benchmarks_long_passes_have_a_tile(shape):
    """Both orientations (gate / up, and down) and the rows' gradient of
    each: the row tile divides the rows, the other two divide the widths
    in whole lanes, and a grid step of either kernel fits the VMEM the
    rule allows."""
    rows, D, F, G = shape
    for K, N in ((D, F), (F, D)):
        tm, tk, tn = moe.gemm_tiling(rows, K, N, G)
        assert rows % tm == 0 and K % tk == 0 and N % tn == 0
        assert tk % 128 == 0 and tn % 128 == 0
        assert moe._tile_bytes(tm, tk, tn) <= moe._GEMM_VMEM_BYTES
    assert moe.gemm_tiling(26624, 2304, 896, 16) == (512, 384, 896)
    assert moe.gemm_tiling(26624, 896, 2304, 16) == (512, 896, 384)


@pytest.mark.parametrize("shape,why", [
    ((2048, 64, 128, 4), "a contraction under a lane"),
    ((2048, 128, 192, 4), "a width that is no whole lane"),
    ((2000, 128, 128, 2), "rows that are no whole row tile"),
    ((64 * 8, 2048, 1024, 64), "a decode step's buffer: 8 rows a group"),
    (NEMOTRON[0], "Nemotron's bounded pass: 320 rows a group"),
    (OLMOE_BOUNDED[0], "OLMoE's bounded pass: 928 rows a group"),
    (OLMOE_BOUNDED[1], "OLMoE's bounded pass: 992 rows a group"),
    ((16384, 2048, 1024, 17), "963 rows a group: under two row tiles"),
])
def test_shapes_the_rule_refuses(shape, why):
    assert moe.gemm_tiling(*shape) is None, why


def test_refused_shapes_and_other_dtypes_stay_on_ragged_dot():
    """Asked for the kernel, a buffer the rule refuses and float32
    operands still run ``ragged_dot``."""
    sizes = jnp.asarray([10, 20], jnp.int32)
    for (rows, K, N), dtype in (((64, 128, 128), jnp.bfloat16),
                                ((1024, 128, 128), jnp.float32)):
        out = moe._grouped_matmul(
            jnp.ones((rows, K), dtype), jnp.ones((2, K, N), dtype), sizes,
            kernel=True, interpret=True)
        assert moe.gemm_counts()[(rows, K, N, 2)] == "ragged_dot"
        assert float(out[29, 0]) == K and not _f32(out)[30:].any()


def _layer(held, routed, D=128, F=128):
    cfg = MoEConfig(num_experts=held, router_experts=routed, first_expert=0,
                    top_k=2, capacity_factor=None) if held < routed else (
        MoEConfig(num_experts=held, top_k=2, capacity_factor=None))
    lp = jax.tree.map(lambda x: x[0], moe.init_moe_params(
        dataclasses.replace(tiny_config(), hidden_dim=D, intermediate_dim=F,
                            moe=cfg), jax.random.PRNGKey(0), jnp.bfloat16,
        n=1))
    return cfg, lp


@pytest.mark.parametrize("impl,how", [
    ("auto", {"ragged_dot"}), ("reference", {"ragged_dot"}),
    ("pallas", {"gmm", "ragged_dot"})])
def test_the_layer_takes_the_kernel_as_attention_takes_its_kernels(impl,
                                                                   how):
    """``moe_mlp(impl=...)`` is the transformer's ``attn_impl``: on the
    CPU ``"auto"`` and ``"reference"`` trace what tier-1 always ran,
    ``"pallas"`` (a compile for a described TPU) the kernel — in the
    bounded branch of a share's pass and nowhere else."""
    cfg, lp = _layer(1, 4)
    x = jnp.ones((1, 2048, 128), jnp.bfloat16)
    moe._GEMMS.clear()
    jax.clear_caches()
    text = str(jax.make_jaxpr(lambda lp: moe.moe_mlp(
        x, lp, cfg, impl=impl)[0])(lp))
    counts = moe.gemm_counts()
    assert set(counts.values()) == how
    assert ("pallas_call" in text) == ("gmm" in how)
    M = 2048 * 2
    assert {r for (r, *_), h in counts.items() if h == "ragged_dot"} >= {M}
    assert all(r < M for (r, *_), h in counts.items() if h == "gmm")


def test_a_layer_that_holds_every_expert_keeps_ragged_dot():
    """``rows == M``: no bound, no ``cond``, no kernel, whatever is
    asked."""
    cfg, lp = _layer(4, 4)
    x = jnp.ones((2, 1024, 128), jnp.bfloat16)
    moe._GEMMS.clear()
    text = str(jax.make_jaxpr(lambda lp: moe.moe_mlp(
        x, lp, cfg, impl="pallas")[0])(lp))
    assert "pallas_call" not in text and "ragged_dot" in text
    assert set(moe.gemm_counts().values()) == {"ragged_dot"}


def test_under_ep_the_gemms_are_ragged_dot():
    """``_dispatch_ep`` runs every source's pass on the whole buffer (the
    row bound is held back there), so asked for the kernel, at shapes the
    rule has tiles for, the traced layer holds ``ragged_dot`` and no
    Pallas kernel."""
    from areal_tpu.parallel import mesh as pmesh

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    mesh = pmesh.make_mesh(pmesh.ParallelSpec(ep=2))
    cfg, lp = _layer(4, 4)
    B, T = 2, 2048
    x = jnp.ones((B, T, 128), jnp.bfloat16)
    assert moe.ep_eligible(mesh, cfg, B, T)
    assert moe.gemm_tiling(T * cfg.top_k, 128, 128, 2) is not None
    moe._GEMMS.clear()
    text = str(jax.make_jaxpr(lambda lp: moe.moe_mlp(
        x, lp, cfg, mesh=mesh, impl="pallas")[0])(lp))
    assert "ragged_dot" in text and "pallas_call" not in text
    assert set(moe.gemm_counts().values()) == {"ragged_dot"}


def test_the_layer_with_the_kernel_equals_the_layer_without():
    """A share's layer end to end (router, bounded pass, combine), output
    and gradients, the kernel in its interpreter against ``ragged_dot``."""
    cfg, lp = _layer(1, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 2048, 128),
                          jnp.bfloat16)

    def run(**how):
        def loss(lp, x):
            y, aux = moe.moe_mlp(x, lp, cfg, **how)
            return jnp.sum(y.astype(jnp.float32) ** 2), (y, aux)

        jax.clear_caches()
        (_, (y, aux)), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(lp, x)
        return y, grads, aux

    y0, g0, _ = run()
    y1, g1, aux = run(impl="pallas", interpret=True)
    assert float(aux["passes"]) == 1 and float(aux["full_passes"]) == 0
    _close(y1, y0, "output", steps=2)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        _close(a, b, "gradient", steps=4)


# ---- the remat plans do not start keeping the expert layer ----

MOE_WIDTHS = dict(
    vocab_size=512, n_layers=3, hidden_dim=256, n_q_heads=2, n_kv_heads=2,
    head_dim=128, intermediate_dim=128)
SHARE = MoEConfig(num_experts=2, router_experts=8, first_expert=0, top_k=2,
                  capacity_factor=None, routed_intermediate_dim=128)
_MOE_MLP = moe.moe_mlp


def _kept(cfg, entry, attn_impl, monkeypatch):
    """(shape, dtype) of every residual the layer scan stacks."""
    from jax._src.ad_checkpoint import saved_residuals

    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), shapes)
    tok = jax.ShapeDtypeStruct((2, 4096), jnp.int32)
    # the attention kernels either way: only the experts' GEMMs differ
    monkeypatch.setattr(moe, "moe_mlp", lambda *a, impl="auto", **k: (
        _MOE_MLP(*a, impl=attn_impl, **k)))

    def loss(p, tokens, pos, seg):
        y, _ = transformer.forward(p, cfg, tokens, pos, segment_ids=seg,
                                   attn_impl="pallas", remat=entry,
                                   return_kv=False, return_hidden=True)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    jax.clear_caches()
    return sorted(
        (tuple(aval.shape), str(aval.dtype))
        for aval, src in saved_residuals(loss, params, tok, tok, tok)
        if "output of scan" in src)


@pytest.mark.parametrize("entry", transformer.REMAT_ENTRIES)
def test_the_kernel_adds_no_residual(entry, monkeypatch):
    """What an expert layer keeps between its forward and its backward
    under each entry is what it keeps with ``ragged_dot``: the kernel is
    a ``pallas_call`` under a ``custom_vjp`` like the flash kernel, whose
    outputs the plans do keep — but inside the pass's own function."""
    cfg = dataclasses.replace(tiny_config(), moe=SHARE, **MOE_WIDTHS)
    want = _kept(cfg, entry, "reference", monkeypatch)
    moe._GEMMS.clear()
    got = _kept(cfg, entry, "pallas", monkeypatch)
    assert "gmm" in moe.gemm_counts().values()
    assert got == want


def test_the_trainers_device_report_says_which_gemm_ran(monkeypatch):
    """``moe_gemm`` beside ``moe_combine``: ``{"rows x K x N/groups":
    "gmm" | "ragged_dot"}`` of every grouped GEMM the process traced."""
    import types

    from areal_tpu.base import monitor
    from areal_tpu.system import trainer_worker

    sizes = jnp.asarray([600, 424], jnp.int32)
    moe._GEMMS.clear()
    for rows, kernel in ((2048, True), (4096, False)):
        moe._grouped_matmul(jnp.ones((rows, 128), jnp.bfloat16),
                            jnp.ones((2, 128, 256), jnp.bfloat16), sizes,
                            kernel=kernel, interpret=True)
    seen = {}
    monkeypatch.setattr(monitor, "log_device_report",
                        lambda logger, worker, **extra: seen.update(extra))
    worker = types.SimpleNamespace(cfg=types.SimpleNamespace(dist_rank=0),
                                   models={})
    trainer_worker.TrainerWorker._log_device_report(worker, "setup")
    assert seen["moe_gemm"] == {"2048x128x256/2": "gmm",
                                "4096x128x256/2": "ragged_dot"}
    assert "moe_combine" in seen
