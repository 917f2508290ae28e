"""``packed_attention``'s dispatch onto the Pallas kernel vs the XLA
reference (the reference repo's tests/cpp_extensions kernel-parity pattern,
in Pallas's interpreter): packed layouts at head sizes 64 and 128 with GQA,
forward and backward, alone and on a mesh — plus the gate (which calls a
kernel takes), the padded length the remat plan is sized by, and the
counted reference fallback (CPU-only — no interpreter needed).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import packing
from areal_tpu.ops import attention as attn
from areal_tpu.ops.pallas import window_attention as wa


def _packed_case(seqlens, Hq=4, Hkv=2, D=128, row_len=None, seed=0):
    rng = np.random.RandomState(seed)
    layout = packing.plan_packing(seqlens, length_bucket=128, row_len=row_len)
    grid = packing.make_grid(layout)
    B, L = layout.shape
    q = rng.randn(B, L, Hq, D).astype(np.float32) * 0.3
    k = rng.randn(B, L, Hkv, D).astype(np.float32) * 0.3
    v = rng.randn(B, L, Hkv, D).astype(np.float32) * 0.3
    return layout, grid, jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


# Row lengths that no tile above 128 divides (5 x 128, 7 x 128) -> the
# (tile, padded length) the kernel runs them at before it slices back.
PADDED_ROWS = {640: (768, 768), 896: (512, 1024)}


def _check_row(layout, row_len):
    """The case packs into rows of the length it is meant to exercise, and
    a PADDED_ROWS length runs the tile recorded there."""
    L = layout.shape[1]
    assert row_len in (None, L)
    if L in PADDED_ROWS:
        assert (wa.pick_tile(L), wa.padded_len(L)) == PADDED_ROWS[L]


@pytest.fixture
def interpreted(monkeypatch):
    """The dispatch's kernel runs in Pallas's interpreter."""
    monkeypatch.setattr(wa, "window_attention", functools.partial(
        wa.window_attention, interpret=True))


@pytest.mark.parametrize(
    "seqlens,row_len",
    [([128], None), ([60, 68], None), ([100, 20, 120, 9], None),
     ([300, 340], None),  # two rows of 384
     ([300, 330], 640), ([500, 60, 300], 896)],
)
@pytest.mark.parametrize("D", [64, 128])
def test_the_dispatch_matches_reference(seqlens, row_len, D, interpreted):
    """Heads of 64 are padded to the 128 lanes; K/V stay at their 2 heads."""
    layout, grid, q, k, v = _packed_case(seqlens, D=D, row_len=row_len)
    _check_row(layout, row_len)
    seg = jnp.asarray(grid["segment_ids"])
    pos = jnp.asarray(grid["positions"])

    ref = attn.packed_attention(q, k, v, seg, seg, q_positions=pos,
                                kv_positions=pos, causal=True,
                                impl="reference")
    with attn.dispatch_label(f"parity-{seqlens}-{D}"):
        out = attn.packed_attention(q, k, v, seg, seg, impl="pallas")
    assert attn.dispatch_counts()[f"parity-{seqlens}-{D}"] == {"pallas": 1}
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)
    # padding query rows are exactly zero
    pad = np.asarray(seg) == 0
    assert (np.asarray(out)[pad] == 0).all()


@pytest.mark.parametrize(
    "seqlens,row_len,D",
    [([96, 32], None, 128), ([90, 30], None, 128),
     ([300, 330], 640, 64), ([300, 330], 640, 128),
     ([500, 60, 300], 896, 64), ([500, 60, 300], 896, 128)],
)
def test_the_dispatchs_backward_matches_reference(seqlens, row_len, D,
                                                  interpreted):
    layout, grid, q, k, v = _packed_case(seqlens, Hq=2, Hkv=2, D=D,
                                         row_len=row_len)
    _check_row(layout, row_len)
    seg = jnp.asarray(grid["segment_ids"])
    pos = jnp.asarray(grid["positions"])

    def loss(impl):
        def f(q, k, v):
            o = attn.packed_attention(q, k, v, seg, seg, q_positions=pos,
                                      kv_positions=pos, impl=impl)
            return jnp.sum(o * o)
        return f

    g_ref = jax.grad(loss("reference"), argnums=(0, 1, 2))(q, k, v)
    g_ker = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ker, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4,
            err_msg=f"grad mismatch for {name}",
        )
        # pad tokens get exactly nothing: as queries (dq) and as keys (the
        # only queries that see them are pad queries, whose dO is zero)
        pad = np.asarray(seg) == 0
        assert (np.asarray(a)[pad] == 0).all(), name


# four rows of each length
MESH_ROWS = {128: [100, 20, 120, 9, 68, 60], 640: [300, 330, 600, 610, 620, 10]}
MESH_CASES = [("f2", 128), ("d2t2", 128), ("t4", 128), ("d2t2", 640)]


def _on_mesh(spec, row_len, monkeypatch, window):
    """GSPMD cannot partition a Mosaic kernel, so under a mesh the call is
    wrapped in a shard_map: rows over the data axes, key/value heads over
    tp where they divide (t4 with 2 kv heads does not — it computes
    redundantly). The pad of a 640-token row to its tile happens inside
    the body. -> (the dispatch's output, the reference's, its counts)"""
    from areal_tpu.parallel import mesh as pmesh
    from areal_tpu.parallel import sharding as psh

    for table in ("TILE_COST", "CAUSAL_TILE_COST"):
        monkeypatch.setattr(wa, table, {128: 1.0, 256: 0.4})
    layout, grid, q, k, v = _packed_case(MESH_ROWS[row_len], D=64,
                                         row_len=row_len)
    seg = jnp.asarray(grid["segment_ids"])
    assert q.shape[:2] == (4, row_len)
    assert wa.padded_len(row_len, window) == {128: 128, 640: 768}[row_len]
    ref = attn.packed_attention(q, k, v, seg, seg, sliding_window=window,
                                impl="reference")
    mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse(spec))
    label = f"mesh-{spec}-{row_len}-{window}"
    with attn.dispatch_label(label), psh.activation_sharding(mesh):
        out = jax.jit(
            lambda q, k, v: attn.packed_attention(
                q, k, v, seg, seg, sliding_window=window, impl="pallas")
        )(q, k, v)
    return out, ref, attn.dispatch_counts()[label]


@pytest.mark.parametrize("spec,row_len", MESH_CASES)
def test_the_windowed_dispatch_on_a_mesh_matches_reference(
        spec, row_len, monkeypatch, interpreted):
    """A sliding-window layer's call inside the kernel's shard_map."""
    out, ref, counts = _on_mesh(spec, row_len, monkeypatch, window=100)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)
    assert counts == {"window": 1}


@pytest.mark.parametrize("spec,row_len", MESH_CASES)
def test_the_dispatch_on_a_mesh_matches_reference(spec, row_len, monkeypatch,
                                                  interpreted):
    """A full layer's: a causal call of a row over itself takes the
    grouped-head kernel inside the same shard_map, K/V at their 2 heads."""
    out, ref, counts = _on_mesh(spec, row_len, monkeypatch, window=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)
    assert counts == {"pallas": 1}


# ---------------- the gate and the tile (CPU, no interpreter) ------------


def _traced(T, S, label, **kw):
    """Trace one call of q [1, T] over k [1, S] under ``label``."""
    q = jax.ShapeDtypeStruct((1, T, 14, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, S, 2, 64), jnp.bfloat16)
    with attn.dispatch_label(label):
        out = jax.eval_shape(
            lambda q, k, v, qs, ks: attn.packed_attention(
                q, k, v, qs, ks, impl="pallas", **kw),
            q, kv, kv, jax.ShapeDtypeStruct((1, T), jnp.int32),
            jax.ShapeDtypeStruct((1, S), jnp.int32))
    assert out.shape == q.shape
    return attn.dispatch_counts()[label]


@pytest.mark.parametrize("T,S,want", [
    # a row over itself on the lane grid: the kernel, padded to its tile
    (1024, 1024, "pallas"),
    (640, 640, "pallas"),  # padded to 768
    # queries and keys of different lengths: no kernel takes them
    (384, 768, "fallback"),
    # not a multiple of 128
    (192, 1024, "fallback"),
    (1024, 100, "fallback"),
])
def test_which_calls_a_kernel_takes(T, S, want):
    assert _traced(T, S, f"gate-{T}-{S}") == {want: 1}


@pytest.mark.parametrize("L", range(128, 8192 + 1, 128))
def test_the_padded_length_is_the_kernels(L):
    """What ``jax_train`` sizes its remat plan by is what the kernel runs:
    at every row length the dispatch's padded length is the tile rule's,
    and a traced causal call of that length records it."""
    tile, L_pad = wa.pick_tile(L), wa.padded_len(L)
    assert attn.kernel_padded_len("pallas", L) == L_pad
    assert attn.kernel_padded_len("auto", L) is None  # the CPU's reference
    assert L_pad % tile == 0 and L <= L_pad < L + tile
    assert _traced(L, L, f"tile-{L}") == {"pallas": 1}
    assert wa.causal_geometry_counts()[f"tile-{L}"] == {
        (L, L_pad, wa.geometry(L)): 1}
    assert wa.geometry(L).tile == tile
    assert f"tile-{L}" not in wa.geometry_counts()


def test_non_divisible_shape_falls_back_to_reference():
    """T=192 is off the 128-token lane grid: the dispatcher — asked for
    the kernel — runs the reference and COUNTS the fallback under the
    active label."""
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

    with attn.dispatch_label("t192"):
        out = attn.packed_attention(q, k, v, seg, seg, q_positions=pos,
                                    kv_positions=pos, impl="pallas")
        ref = attn.packed_attention(q, k, v, seg, seg, q_positions=pos,
                                    kv_positions=pos, impl="reference")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    assert attn.dispatch_counts()["t192"] == {"fallback": 1, "reference": 1}
