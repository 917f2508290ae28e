"""The windowed attention kernel (ops/pallas/window_attention.py) against
the XLA reference under ``segment_mask(..., sliding_window=w)``, run in
Pallas's interpreter on the CPU: forward and gradients on packed rows with
documents shorter and longer than the window, a document boundary inside a
tile, a row that is no multiple of the tile, GQA — and its tile rule, its
trace-time count and its place in ``packed_attention``'s dispatch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_mask as mask_lib,
)
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_mask_info as mask_info_lib,
)

from areal_tpu.ops import attention
from areal_tpu.ops.attention import attention_reference, segment_mask
from areal_tpu.ops.pallas import window_attention as wa

# (row length, [document lengths] per row (the rest is padding), window,
# q heads, kv heads, head size)
CASES = {
    # documents shorter and longer than the window; a boundary inside a
    # 128-token tile (50, 300); tail padding
    "mixed_docs": (384, [[50, 250, 70], [384]], 100, 4, 2, 16),
    # a row that is no multiple of its 256-token tile: padded to 512
    "padded_row": (384, [[384], [200, 100]], 100, 4, 2, 16),
    # MHA, a lane-wide head, the window wider than every document
    "short_docs": (256, [[90, 90], [60, 120, 40]], 128, 2, 2, 128),
    # one query head group of four on one key/value head, window of one tile
    "mqa_group": (256, [[256], [130, 126]], 128, 4, 1, 32),
}
TILES = {"mixed_docs": {128: 1.0}, "padded_row": {256: 1.0},
         "short_docs": {128: 1.0}, "mqa_group": {128: 1.0}}


def make(case, seed=0):
    T, docs, W, Hq, Hkv, D = CASES[case]
    B = len(docs)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, T, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)
    seg = np.zeros((B, T), np.int32)
    pos = np.zeros((B, T), np.int32)
    for b, lens in enumerate(docs):
        at = 0
        for i, n in enumerate(lens):
            seg[b, at:at + n] = i + 1
            pos[b, at:at + n] = np.arange(n)  # positions INSIDE the document
            at += n
    w = jax.random.normal(ks[3], (B, T, Hq, D), jnp.float32)
    return q, k, v, jnp.asarray(seg), jnp.asarray(pos), W, w


@pytest.fixture(autouse=True)
def tiles(request, monkeypatch):
    """The cases run at toy tiles (the measured table starts at 256)."""
    params = getattr(getattr(request.node, "callspec", None), "params", {})
    if "case" in params:
        monkeypatch.setattr(wa, "TILE_COST", TILES[params["case"]])


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_matches_the_reference(case):
    q, k, v, seg, pos, W, _ = make(case)
    want = attention_reference(
        q, k, v, segment_mask(seg, seg, pos, pos, True, sliding_window=W))
    got = wa.window_attention(q, k, v, seg, seg, window=W, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # padding queries come back as exact zeros
    assert float(jnp.abs(jnp.where((seg > 0)[..., None, None], 0, got)).max()
                 ) == 0.0


@pytest.mark.parametrize("case", sorted(CASES))
def test_gradients_match_the_reference(case):
    q, k, v, seg, pos, W, w = make(case, seed=1)
    mask = segment_mask(seg, seg, pos, pos, True, sliding_window=W)
    want = jax.grad(lambda *a: jnp.sum(attention_reference(*a, mask) * w),
                    argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(lambda *a: jnp.sum(wa.window_attention(
        *a, seg, seg, window=W, interpret=True) * w), argnums=(0, 1, 2))(
            q, k, v)
    for g, r, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5, err_msg=name)


def test_the_window_edge():
    """A query sees the key ``window - 1`` back and not the one ``window``
    back: moving that key's value changes nothing."""
    T, W = 256, 128
    q, k, v, seg, pos, _, _ = make("mqa_group")
    seg = jnp.ones_like(seg)
    out = wa.window_attention(q, k, v, seg, seg, window=W, interpret=True)
    for back, seen in ((W - 1, True), (W, False)):
        v2 = v.at[:, 200 - back].add(10.0)
        out2 = wa.window_attention(q, k, v2, seg, seg, window=W,
                                   interpret=True)
        moved = float(jnp.abs(out2[:, 200] - out[:, 200]).max())
        assert (moved > 1e-4) == seen, (back, moved)


def test_a_row_off_the_lane_grid_is_refused():
    q, k, v, seg, _, W, _ = make("mixed_docs")
    with pytest.raises(ValueError, match="multiple of 128"):
        wa.window_attention(q[:, :200], k[:, :200], v[:, :200], seg[:, :200],
                            seg[:, :200], window=W, interpret=True)
    assert wa.padded_len(200, W) is None


@pytest.mark.parametrize("n", range(128, 8193, 128))
def test_pick_tile_rule(n):
    """The tile is the cheapest by visited blocks at the padded length,
    and the measured costs put every multiple of 512 on tile 512."""
    tile = wa.pick_tile(n, 1024)
    n_pad = wa.padded_len(n, 1024)
    assert tile in wa.TILE_COST and n_pad % tile == 0 and n <= n_pad < n + tile

    def cost(t):
        p = -(-n // t) * t
        return wa.blocks_visited(p, t, 1024)[0] * t * t * wa.TILE_COST[t]

    assert cost(tile) == min(cost(t) for t in wa.TILE_COST)
    if n % 512 == 0:
        assert tile == 512


@pytest.mark.parametrize("n_pad,tile,window", [
    (8192, 512, 1024), (8192, 256, 1024), (8192, 1024, 1024),
    (6144, 512, 1024), (1024, 512, 1024), (3072, 512, 700), (2048, 256, 1)])
def test_blocks_visited_is_what_the_kernels_grid_holds(n_pad, tile, window):
    """The arithmetic of the trace-time count against the kernel's own
    mask: the non-empty blocks of the LocalMask at that tile."""
    visited, causal = wa.blocks_visited(n_pad, tile, window)
    info, _ = mask_info_lib.process_mask(
        mask_lib.MultiHeadMask(
            [mask_lib.LocalMask((n_pad, n_pad), (window - 1, 0), 0)]),
        (tile, tile))
    n = n_pad // tile
    dense = np.zeros((n, n), bool)
    for i in range(n):
        lo = max(i * tile - window + 1, 0) // tile
        dense[i, lo:i + 1] = True
    assert visited == int(dense.sum())
    assert causal == n * (n + 1) // 2
    # the kernel's grid is as wide as its busiest query block's row
    assert info.block_mask.shape[-1] == int(dense.sum(1).max())
    assert int((np.asarray(info.block_mask) > 0).sum()) == visited


def test_the_published_geometry_skips_two_thirds_of_an_8k_row():
    visited, causal = wa.blocks_visited(8192, 512, 1024)
    assert (visited, causal) == (45, 136)


def test_geometry_counts_under_the_active_label(monkeypatch):
    monkeypatch.setattr(wa, "TILE_COST", {128: 1.0})
    q, k, v, seg, _, W, _ = make("mixed_docs")
    with attention.dispatch_label("test-window"):
        jax.eval_shape(lambda *a: wa.window_attention(
            *a, seg, seg, window=W, interpret=True), q, k, v)
    got = wa.geometry_counts()["test-window"]
    assert got == {(384, 384, 128, 100): {
        "calls": 1, "blocks_visited": 5, "blocks_causal": 6}}


def test_packed_attention_counts_the_windowed_kernel(monkeypatch):
    """impl="pallas" with a window runs the windowed kernel and counts it
    as "window" — never "fallback"; off the lane grid both kernels give
    way to the reference, which is counted as "fallback"; on the CPU
    ("auto") the reference is the path."""
    q, k, v, seg, pos, W, _ = make("mixed_docs")
    seen = {}

    def fake(q, k, v, qs, ks, window=0, scale=None):
        seen["window"] = window
        return jnp.zeros_like(q)

    monkeypatch.setattr(wa, "window_attention", fake)
    with attention.dispatch_label("test-dispatch"):
        attention.packed_attention(q, k, v, seg, seg, pos, pos,
                                   sliding_window=W, impl="pallas")
        attention.packed_attention(
            q[:, :200], k[:, :200], v[:, :200], seg[:, :200], seg[:, :200],
            pos[:, :200], pos[:, :200], sliding_window=W, impl="pallas")
        attention.packed_attention(q, k, v, seg, seg, pos, pos,
                                   sliding_window=W, impl="auto")
    assert seen == {"window": W}
    assert attention.dispatch_counts()["test-dispatch"] == {
        "window": 1, "fallback": 1, "reference": 1}
    assert attention.kernel_padded_len("pallas", 384, W) == wa.padded_len(
        384, W)
    assert attention.kernel_padded_len("auto", 384, W) is None


def test_the_window_scope_is_on_the_compiled_ops():
    from areal_tpu.base import telemetry

    q, k, v, seg, pos, W, _ = make("mixed_docs")
    text = jax.make_jaxpr(lambda *a: attention.packed_attention(
        *a, seg, seg, pos, pos, sliding_window=W, impl="pallas"))(
            q, k, v).pretty_print(name_stack=True)
    assert telemetry.WINDOW_SCOPES == (wa.SCOPE,)
    assert wa.SCOPE not in telemetry.DEVICE_SCOPES
    assert f"{wa.SCOPE}/pallas_window_attention" in text
