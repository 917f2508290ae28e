"""Pallas flash attention vs XLA reference parity (the reference repo's
tests/cpp_extensions kernel-parity pattern, on the Pallas TPU interpreter),
plus the block-size autotuning table and the counted reference fallback
for non-128-divisible shapes (both CPU-only — no interpreter needed).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.models import packing
from areal_tpu.ops import attention as attn
from areal_tpu.ops.pallas import flash_attention as fa


def _packed_case(seqlens, Hq=4, Hkv=2, D=128, row_len=None, seed=0):
    rng = np.random.RandomState(seed)
    layout = packing.plan_packing(seqlens, length_bucket=128, row_len=row_len)
    grid = packing.make_grid(layout)
    B, L = layout.shape
    q = rng.randn(B, L, Hq, D).astype(np.float32) * 0.3
    k = rng.randn(B, L, Hkv, D).astype(np.float32) * 0.3
    v = rng.randn(B, L, Hkv, D).astype(np.float32) * 0.3
    return layout, grid, jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


# Row lengths with no divisor above 128 (5 x 128, 7 x 128) -> the tile the
# wrapper pads them to (640 -> 768, 896 -> 1024) before it slices back.
PADDED_ROWS = {640: 384, 896: 512}


def _check_row(layout, row_len):
    """The case packs into rows of the length it is meant to exercise, and
    a PADDED_ROWS length runs the tile recorded there."""
    L = layout.shape[1]
    assert row_len in (None, L)
    if L in PADDED_ROWS:
        assert fa.pick_block_sizes(L, L) == (PADDED_ROWS[L],) * 2


@pytest.mark.parametrize(
    "seqlens,row_len",
    [([128], None), ([60, 68], None), ([100, 20, 120, 9], None),
     ([300, 340], None),  # two rows of 384
     ([300, 330], 640), ([500, 60, 300], 896)],
)
@pytest.mark.parametrize("D", [64, 128])
def test_flash_matches_reference(seqlens, row_len, D):
    layout, grid, q, k, v = _packed_case(seqlens, D=D, row_len=row_len)
    _check_row(layout, row_len)
    seg = jnp.asarray(grid["segment_ids"])
    pos = jnp.asarray(grid["positions"])

    ref = attn.packed_attention(q, k, v, seg, seg, q_positions=pos,
                                kv_positions=pos, causal=True,
                                impl="reference")
    with pltpu.force_tpu_interpret_mode():
        out = fa.flash_attention(q, k, v, seg, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)
    # padding query rows are exactly zero
    pad = np.asarray(seg) == 0
    assert (np.asarray(out)[pad] == 0).all()


@pytest.mark.parametrize(
    "seqlens,row_len,D",
    [([96, 32], None, 128), ([90, 30], None, 128),
     ([300, 330], 640, 64), ([300, 330], 640, 128),
     ([500, 60, 300], 896, 64), ([500, 60, 300], 896, 128)],
)
def test_flash_backward_matches_reference(seqlens, row_len, D):
    layout, grid, q, k, v = _packed_case(seqlens, Hq=2, Hkv=2, D=D,
                                         row_len=row_len)
    _check_row(layout, row_len)
    seg = jnp.asarray(grid["segment_ids"])
    pos = jnp.asarray(grid["positions"])

    def loss_ref(q, k, v):
        o = attn.packed_attention(q, k, v, seg, seg, q_positions=pos,
                                  kv_positions=pos, impl="reference")
        return jnp.sum(o * o)

    def loss_flash(q, k, v):
        o = fa.flash_attention(q, k, v, seg, seg)
        return jnp.sum(o * o)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    with pltpu.force_tpu_interpret_mode():
        g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_fl, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-2,
            err_msg=f"grad mismatch for {name}",
        )
        # pad tokens get exactly nothing: as queries (dq) and as keys (the
        # only queries that see them are pad queries, whose dO is zero)
        pad = np.asarray(seg) == 0
        assert (np.asarray(a)[pad] == 0).all(), name


# four rows of each length
MESH_ROWS = {128: [100, 20, 120, 9, 68, 60], 640: [300, 330, 600, 610, 620, 10]}


@pytest.mark.parametrize(
    "spec,row_len", [("f2", 128), ("d2t2", 128), ("t4", 128), ("d2t2", 640)])
def test_flash_on_mesh_matches_reference(spec, row_len):
    """GSPMD cannot partition a Mosaic kernel, so under a mesh the call is
    wrapped in a shard_map: rows over the data axes, heads over tp where
    they divide (t4 with 2 kv heads does not — it computes redundantly).
    The pad of a 640-token row to its tile happens inside the body."""
    from areal_tpu.parallel import mesh as pmesh
    from areal_tpu.parallel import sharding as psh

    layout, grid, q, k, v = _packed_case(MESH_ROWS[row_len], D=64,
                                         row_len=row_len)
    _check_row(layout, row_len)
    seg = jnp.asarray(grid["segment_ids"])
    assert q.shape[:2] == (4, row_len)
    ref = attn.packed_attention(q, k, v, seg, seg, impl="reference")
    mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse(spec))
    with pltpu.force_tpu_interpret_mode(), psh.activation_sharding(mesh):
        out = jax.jit(
            lambda q, k, v: fa.flash_attention_on_mesh(mesh, q, k, v, seg,
                                                       seg)
        )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


@pytest.mark.parametrize(
    "spec,row_len", [("f2", 128), ("d2t2", 128), ("t4", 128), ("d2t2", 640)])
def test_the_dispatch_on_a_mesh_matches_reference(spec, row_len, monkeypatch):
    """The same through ``packed_attention``: a causal call of a row over
    itself takes the grouped-head kernel inside the same shard_map (rows
    over the data axes, key/value heads over tp where they divide), K/V
    at their 2 heads."""
    import functools

    from areal_tpu.ops.pallas import window_attention as wa
    from areal_tpu.parallel import mesh as pmesh
    from areal_tpu.parallel import sharding as psh

    monkeypatch.setattr(wa, "CAUSAL_TILE_COST", {128: 1.0, 256: 0.5})
    monkeypatch.setattr(wa, "window_attention", functools.partial(
        wa.window_attention, interpret=True))
    layout, grid, q, k, v = _packed_case(MESH_ROWS[row_len], D=64,
                                         row_len=row_len)
    seg = jnp.asarray(grid["segment_ids"])
    assert wa.padded_len(row_len) == {128: 128, 640: 768}[row_len]
    ref = attn.packed_attention(q, k, v, seg, seg, impl="reference")
    mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse(spec))
    with attn.dispatch_label(f"mesh-{spec}-{row_len}"), \
            psh.activation_sharding(mesh):
        out = jax.jit(
            lambda q, k, v: attn.packed_attention(q, k, v, seg, seg,
                                                  impl="pallas")
        )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)
    assert attn.kernel_counts()[f"mesh-{spec}-{row_len}"] == {"causal": 1}


# ---------------- tile selection (CPU, no interpreter) ------------


@pytest.mark.parametrize("T,S,want", [
    # the tile that is cheapest once the dim is padded to it
    (1024, 1024, (512, 512)),
    (640, 640, (384, 384)),  # padded to 768
    (384, 768, (384, 384)),
    # not a multiple of 128 -> None (callers fall back)
    (192, 1024, None),
    (1024, 100, None),
])
def test_pick_block_sizes_heuristic(T, S, want):
    assert fa.pick_block_sizes(T, S) == want


# The row lengths the benchmark's two train cells produce -> (tile, padded
# length), as PERF.md records them.
CELL_GEOMETRY = {512: (512, 512), 2688: (512, 3072), 3072: (512, 3072),
                 6016: (512, 6144), 7296: (512, 7680)}


@pytest.mark.parametrize("L", range(128, 8192 + 1, 128))
def test_pick_tile_rule(L):
    tile = fa.pick_tile(L)
    assert fa.pick_block_sizes(L, L) == (tile, tile)
    L_pad = fa._round_up(L, tile)
    assert tile in fa.TILE_COST and L_pad % tile == 0 and L <= L_pad < L + tile
    if L >= 512:
        assert tile > 128
    # the padding never costs more than the tile saves over blocks of 128
    assert L_pad ** 2 / L ** 2 <= fa.TILE_COST[128] / fa.TILE_COST[tile]
    if L in CELL_GEOMETRY:
        assert (tile, L_pad) == CELL_GEOMETRY[L]


def test_geometry_counts_under_the_active_label():
    """A 6016-token row (47 x 128) is traced at 6144 with blocks of 512,
    counted beside — not in — the dispatch counts: by the flash kernel
    for a non-causal call, by the grouped-head kernel (its own tile rule,
    its own count) for a causal one."""
    from areal_tpu.ops.pallas import window_attention as wa

    q = jax.ShapeDtypeStruct((1, 6016, 14, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 6016, 2, 64), jnp.bfloat16)
    seg = jax.ShapeDtypeStruct((1, 6016), jnp.int32)
    for causal in (False, True):
        with attn.dispatch_label(f"t6016-{causal}"):
            out = jax.eval_shape(
                lambda q, k, v, s: attn.packed_attention(
                    q, k, v, s, s, causal=causal, impl="pallas"),
                q, kv, kv, seg)
        assert out.shape == q.shape
        assert attn.dispatch_counts()[f"t6016-{causal}"] == {"pallas": 1}
    assert fa.geometry_counts()["t6016-False"] == {(6016, 6144, 512): 1}
    assert "t6016-True" not in fa.geometry_counts()
    tile = wa.pick_tile(6016)
    assert wa.causal_geometry_counts()["t6016-True"] == {
        (6016, -(-6016 // tile) * tile, tile): 1}
    assert "t6016-False" not in wa.causal_geometry_counts()


def test_non_divisible_shape_falls_back_to_reference():
    """T=192 has no 128-multiple divisor: the kernel wrapper refuses it,
    and the dispatcher — asked for the kernel — runs the reference and
    COUNTS the fallback under the active label."""
    seqlens = [100, 92]  # packs to one 192-col row with row_len=192
    layout = packing.plan_packing(seqlens, length_bucket=64, row_len=192)
    grid = packing.make_grid(layout)
    B, L = layout.shape
    assert L == 192
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(B, L, 4, 64).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(B, L, 2, 64).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(B, L, 2, 64).astype(np.float32) * 0.3)
    seg = jnp.asarray(grid["segment_ids"])
    pos = jnp.asarray(grid["positions"])

    with pytest.raises(ValueError, match="no 128-multiple block"):
        fa.flash_attention(q, k, v, seg, seg)
    with attn.dispatch_label("t192"):
        out = attn.packed_attention(q, k, v, seg, seg, q_positions=pos,
                                    kv_positions=pos, impl="pallas")
        ref = attn.packed_attention(q, k, v, seg, seg, q_positions=pos,
                                    kv_positions=pos, impl="reference")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    assert attn.dispatch_counts()["t192"] == {"fallback": 1, "reference": 1}
