"""The grouped-head attention kernel (ops/pallas/window_attention.py)
against the XLA reference under ``segment_mask(..., sliding_window=w)`` —
windowed, and full causal (no window) — run in Pallas's interpreter on the
CPU: forward and gradients on packed rows with documents shorter and
longer than the window, a document boundary inside a tile, a row that is
no multiple of the tile, GQA — and its tile rules, its trace-time counts
and its place in ``packed_attention``'s dispatch."""

import functools

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

# (row length, [document lengths] per row (the rest is padding), window
# (None: full causal), q heads, kv heads, head size)
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
    # full causal: 14 query heads on 2 key/value heads of 64, several
    # documents with a boundary inside a tile, tail padding
    "causal_gqa": (384, [[50, 250, 70], [384]], None, 14, 2, 64),
    # full causal, MHA (groups of one), a lane-wide head
    "causal_mha": (256, [[90, 90], [60, 120, 40]], None, 4, 4, 128),
    # full causal, a row that is no multiple of its 256-token tile
    "causal_padded_row": (384, [[384], [200, 100]], None, 4, 2, 16),
}
TILES = {"mixed_docs": {128: 1.0}, "padded_row": {256: 1.0},
         "short_docs": {128: 1.0}, "mqa_group": {128: 1.0},
         "causal_gqa": {128: 1.0}, "causal_mha": {128: 1.0},
         "causal_padded_row": {256: 1.0}}


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
        monkeypatch.setattr(wa, "CAUSAL_TILE_COST", TILES[params["case"]])


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


# The row lengths of the benchmark's cells -> (tile, padded length) of a
# full layer's call, as PERF.md §5 (PR 45) records them.
CAUSAL_GEOMETRY = {512: (512, 512), 2688: (1024, 3072), 3072: (1024, 3072),
                   3712: (768, 3840), 3968: (1024, 4096),
                   6016: (1024, 6144), 6656: (1024, 7168),
                   7296: (1024, 8192), 8192: (1024, 8192)}


@pytest.mark.parametrize("n", range(128, 8193, 128))
def test_pick_tile_rule_without_a_window(n):
    """The causal table: cheapest by the causal blocks at the padded
    length; the long rows of the benchmark's cells run blocks of 1024
    (one of 3712 tokens blocks of 768: 3840 against 4096), and a short row
    is not padded past the next multiple of 512."""
    tile = wa.pick_tile(n)
    n_pad = wa.padded_len(n)
    assert tile in wa.CAUSAL_TILE_COST
    assert n_pad % tile == 0 and n <= n_pad < n + tile

    def cost(t):
        blocks = -(-n // t)
        return blocks * (blocks + 1) // 2 * t * t * wa.CAUSAL_TILE_COST[t]

    assert cost(tile) == min(cost(t) for t in wa.CAUSAL_TILE_COST)
    assert wa.blocks_visited(n_pad, tile, None) == (
        (n_pad // tile) * (n_pad // tile + 1) // 2,) * 2
    if n <= 512:
        assert n_pad <= 512
    if n in CAUSAL_GEOMETRY:
        assert (tile, n_pad) == CAUSAL_GEOMETRY[n]
    # the blocks are those the table was measured at
    sizes = wa._block_sizes(tile, None)
    assert sizes.use_fused_bwd_kernel and sizes.block_q == sizes.block_kv
    assert sizes.block_kv_compute == (512 if tile == 1024 else tile)
    assert not wa._block_sizes(tile, 1024).use_fused_bwd_kernel


def test_the_shipped_blocks_of_a_long_row(monkeypatch):
    """Blocks of 1024 whose keys are computed 512 at a time, and the fused
    backward whose dQ is summed over key blocks outside the kernel:
    forward and gradients of a 2048-token row of two documents."""
    monkeypatch.setattr(wa, "CAUSAL_TILE_COST", {1024: 1.0})
    T = 2048
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (1, T, 2, 8), jnp.float32)
    k, v = (jax.random.normal(kk, (1, T, 1, 8), jnp.float32)
            for kk in ks[1:3])
    w = jax.random.normal(ks[3], q.shape, jnp.float32)
    seg = np.ones((1, T), np.int32)
    seg[0, 1300:2000], seg[0, 2000:] = 2, 0
    seg = jnp.asarray(seg)
    mask = segment_mask(seg, seg, causal=True)
    want = jax.value_and_grad(lambda *a: jnp.sum(
        attention_reference(*a, mask) * w), argnums=(0, 1, 2))(q, k, v)
    got = jax.value_and_grad(lambda *a: jnp.sum(wa.window_attention(
        *a, seg, seg, interpret=True) * w), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, r, name in zip(got[1], want[1], "qkv"):
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5, err_msg=name)


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


def test_causal_calls_have_a_geometry_count_of_their_own(monkeypatch):
    """A full-causal call is counted by (length, padded length, tile) —
    and NOT among the windowed calls, which readers take for a sliding
    layer's."""
    monkeypatch.setattr(wa, "CAUSAL_TILE_COST", {256: 1.0})
    q, k, v, seg, _, _, _ = make("causal_padded_row")
    with attention.dispatch_label("test-causal"):
        jax.eval_shape(lambda *a: wa.window_attention(
            *a, seg, seg, interpret=True), q, k, v)
    assert wa.causal_geometry_counts()["test-causal"] == {(384, 512, 256): 1}
    assert "test-causal" not in wa.geometry_counts()


def test_a_long_row_that_the_tile_pads(monkeypatch):
    """6016 = 47 x 128 tokens run 512-blocks at 6144; the 128 tokens added
    carry segment id 0, and the output is sliced back."""
    monkeypatch.setattr(wa, "CAUSAL_TILE_COST", {512: 1.0})
    T = 6016
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, T, 2, 8), jnp.float32)
    k, v = (jax.random.normal(kk, (1, T, 1, 8), jnp.float32)
            for kk in ks[1:])
    seg = np.zeros((1, T), np.int32)
    seg[0, :3000], seg[0, 3000:5900] = 1, 2
    seg = jnp.asarray(seg)
    assert wa.padded_len(T) == 6144
    got = wa.window_attention(q, k, v, seg, seg, interpret=True)
    want = attention_reference(q, k, v, segment_mask(seg, seg, causal=True))
    assert got.shape == q.shape
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(got[:, 5900:]).max()) == 0.0


def _interpreted(monkeypatch):
    monkeypatch.setattr(wa, "window_attention", functools.partial(
        wa.window_attention, interpret=True))


def test_a_value_wider_than_q_and_k(monkeypatch):
    """Differential attention's call: the value twice as wide as q / k.
    ``packed_attention`` pads q and k with zeros in front of the kernel
    and keeps q's own scale; forward and gradients match the reference."""
    monkeypatch.setattr(wa, "CAUSAL_TILE_COST", {128: 1.0})
    _interpreted(monkeypatch)
    q, k, v, seg, pos, _, w = make("causal_gqa", seed=3)
    v = jnp.concatenate([v, v[..., ::-1] * 0.5], axis=-1)  # [B, T, 2, 128]
    w = jnp.concatenate([w, w], axis=-1)

    def run(impl):
        return jax.value_and_grad(lambda *a: jnp.sum(
            attention.packed_attention(*a, seg, seg, pos, pos, impl=impl)
            * w), argnums=(0, 1, 2))(q, k, v)

    (want, want_g), (got, got_g) = run("reference"), run("pallas")
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, r, name in zip(got_g, want_g, "qkv"):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5, err_msg=name)


def test_packed_attention_picks_the_kernel_by_what_it_is_handed(monkeypatch):
    """impl="pallas": a causal call of a row over itself runs the grouped
    kernel — with a window counted as "window", without as "pallas", kernel
    "causal"; a non-causal call or T != S runs the flash kernel ("pallas",
    kernel "flash"); off the lane grid every kernel gives way to the
    reference, counted as "fallback"; on the CPU ("auto") the reference
    is the path. No call adds a label to ``dispatch_counts`` beyond the
    four the checks of a step's kernels know."""
    from areal_tpu.ops.pallas import flash_attention as fa

    q, k, v, seg, pos, W, _ = make("mixed_docs")
    seen = []

    def fake(q, k, v, qs, ks, window=None, scale=None):
        seen.append(window)
        return jnp.zeros_like(q)

    def fake_flash(q, k, v, qs, ks, causal=True, scale=None):
        seen.append(("flash", causal, q.shape[1], k.shape[1]))
        return jnp.zeros_like(q)

    monkeypatch.setattr(wa, "window_attention", fake)
    monkeypatch.setattr(fa, "flash_attention", fake_flash)
    short = [x[:, :200] for x in (q, k, v, seg, seg, pos, pos)]
    with attention.dispatch_label("test-dispatch"):
        attention.packed_attention(q, k, v, seg, seg, pos, pos,
                                   sliding_window=W, impl="pallas")
        attention.packed_attention(q, k, v, seg, seg, pos, pos,
                                   impl="pallas")
        attention.packed_attention(q, k, v, seg, seg, pos, pos,
                                   causal=False, impl="pallas")
        attention.packed_attention(q[:, :128], k, v, seg[:, :128], seg,
                                   pos[:, :128], pos, impl="pallas")
        attention.packed_attention(*short, sliding_window=W, impl="pallas")
        attention.packed_attention(*short, impl="pallas")
        attention.packed_attention(q, k, v, seg, seg, pos, pos,
                                   sliding_window=W, impl="auto")
        attention.packed_attention(q, k, v, seg, seg, pos, pos, impl="auto")
    assert seen == [W, None, ("flash", False, 384, 384),
                    ("flash", True, 128, 384)]
    assert attention.dispatch_counts()["test-dispatch"] == {
        "window": 1, "pallas": 3, "fallback": 2, "reference": 2}
    assert attention.kernel_counts()["test-dispatch"] == {
        "window": 1, "causal": 1, "flash": 2}
    for w in (W, None):
        assert attention.kernel_padded_len("pallas", 384, w) == wa.padded_len(
            384, w)
        assert attention.kernel_padded_len("auto", 384, w) is None
        assert attention.kernel_padded_len("pallas", 200, w) is None


@pytest.mark.parametrize("window,scope", [(100, wa.SCOPE),
                                          (None, wa.CAUSAL_SCOPE)])
def test_the_kernels_scope_is_on_the_compiled_ops(window, scope):
    from areal_tpu.base import telemetry

    q, k, v, seg, pos, _, _ = make("mixed_docs")
    text = jax.make_jaxpr(lambda *a: attention.packed_attention(
        *a, seg, seg, pos, pos, sliding_window=window, impl="pallas"))(
            q, k, v).pretty_print(name_stack=True)
    assert telemetry.WINDOW_SCOPES == (wa.SCOPE, wa.CAUSAL_SCOPE)
    assert scope not in telemetry.DEVICE_SCOPES
    assert f"{scope}/pallas_window_attention" in text
    other = ({wa.SCOPE, wa.CAUSAL_SCOPE} - {scope}).pop()
    assert f"{other}/pallas_window_attention" not in text
