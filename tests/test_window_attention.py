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
    blocks = wa.geometry(n)
    sizes = blocks.sizes()
    assert blocks.tile == tile
    assert sizes.use_fused_bwd_kernel and sizes.block_q == sizes.block_kv
    assert sizes.block_kv_compute == (512 if tile == 1024 else tile)
    assert not wa.geometry(n, 1024).sizes().use_fused_bwd_kernel


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
    """A full-causal call is counted by (length, padded length, the
    blocks that ran) — and NOT among the windowed calls, which readers
    take for a sliding layer's."""
    monkeypatch.setattr(wa, "CAUSAL_TILE_COST", {256: 1.0})
    q, k, v, seg, _, _, _ = make("causal_padded_row")
    with attention.dispatch_label("test-causal"):
        jax.eval_shape(lambda *a: wa.window_attention(
            *a, seg, seg, interpret=True), q, k, v)
    blocks = wa.Blocks((256, 256, 256), (256, 256, 256), None)
    assert wa.causal_geometry_counts()["test-causal"] == {
        (384, 512, blocks): 1}
    assert blocks.label() == "f256x256x256.kv256x256x256.fused"
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
    """impl="pallas": a causal call of a row over itself runs the kernel —
    with a window counted as "window", without as "pallas"; every other
    call (non-causal, T != S, off the lane grid) gives way to the
    reference, counted as "fallback"; on the CPU ("auto") the reference
    is the path. No call adds a label to ``dispatch_counts`` beyond the
    four the checks of a step's kernels know."""
    q, k, v, seg, pos, W, _ = make("mixed_docs")
    seen = []

    def fake(q, k, v, qs, ks, window=None, scale=None):
        seen.append(window)
        return jnp.zeros_like(q)

    monkeypatch.setattr(wa, "window_attention", fake)
    whole = (q, k, v, seg, seg, pos, pos)
    short = [x[:, :200] for x in whole]
    # what the kernel does not take: non-causal, T != S, off the lane grid
    others = [(whole, dict(causal=False)),
              ((q[:, :128], k, v, seg[:, :128], seg, pos[:, :128], pos), {}),
              (short, dict(sliding_window=W)), (short, {})]
    with attention.dispatch_label("test-dispatch"):
        attention.packed_attention(*whole, sliding_window=W, impl="pallas")
        attention.packed_attention(*whole, impl="pallas")
        fell_back = [attention.packed_attention(*args, **kw, impl="pallas")
                     for args, kw in others]
        attention.packed_attention(*whole, sliding_window=W, impl="auto")
        attention.packed_attention(*whole, impl="auto")
    assert seen == [W, None]
    assert attention.dispatch_counts()["test-dispatch"] == {
        "window": 1, "pallas": 1, "fallback": 4, "reference": 2}
    for got, (args, kw) in zip(fell_back, others):
        np.testing.assert_array_equal(got, attention.packed_attention(
            *args, **kw, impl="reference"))
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


# ---- blocks skipped by the row's segment ids ----

# (row length, [document lengths] per row, tile): what the kernel's
# schedule is narrowed by — all at a toy tile of 128
LAYOUTS = {
    # (a) five documents in a multi-block row, boundaries on a block edge
    # (128, 384) and off it (228, 520); 20 tokens of tail padding
    "five_docs": (640, [[128, 100, 156, 136, 100]]),
    # (b) a tail of two WHOLE blocks of padding, and of one
    "whole_pad_blocks": (512, [[200, 56], [200, 184]]),
    # (c) a short document padded inside the last real block, then a
    # block of nothing but padding
    "short_doc_padded": (384, [[128, 30]]),
    # (d) two rows with different layouts in one call
    "two_rows": (384, [[128, 100, 100], [380]]),
    # (e) a one-block row: the static schedule
    "one_block": (128, [[50, 60], [128]]),
}
HEADS = {"14q2kv64": (14, 2, 64), "4q2kv128": (4, 2, 128)}


def _row(T, docs):
    """Segment ids of a row of T tokens: the documents, then padding."""
    return np.pad(np.repeat(np.arange(1, len(docs) + 1), docs),
                  (0, T - sum(docs))).astype(np.int32)


def make_layout(layout, heads, seed=0, dv=None):
    T, docs = LAYOUTS[layout]
    Hq, Hkv, D = HEADS[heads]
    B = len(docs)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, T, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, dv or D), jnp.float32)
    w = jax.random.normal(ks[3], (B, T, Hq, dv or D), jnp.float32)
    seg = np.stack([_row(T, lens) for lens in docs])
    return q, k, v, jnp.asarray(seg), w


def _out_and_grads(attend, q, k, v, w):
    """(out, dq, dk, dv) of sum(out * w), in ONE jitted program (an
    interpreted kernel compiles a second a program)."""
    out, grads = jax.jit(jax.value_and_grad(
        lambda *a: (lambda o: (jnp.sum(o * w), o))(attend(*a)),
        argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return (out[1], *grads)


@pytest.mark.parametrize("heads", sorted(HEADS))
@pytest.mark.parametrize("window", [None, 160])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_skipped_blocks_change_nothing(monkeypatch, layout, window, heads):
    """Forward AND gradients of the narrowed kernel: the reference's under
    ``segment_mask``, finite everywhere, zero in padding rows, and EQUAL to
    what the same kernel gives under the static schedule."""
    monkeypatch.setattr(wa, "TILE_COST", {128: 1.0})
    monkeypatch.setattr(wa, "CAUSAL_TILE_COST", {128: 1.0})
    q, k, v, seg, w = make_layout(layout, heads)
    mask = segment_mask(seg, seg, causal=True, sliding_window=window)
    want = _out_and_grads(
        lambda *a: attention_reference(*a, mask), q, k, v, w)

    def kernel(*a):
        return wa.window_attention(*a, seg, seg, window=window,
                                   interpret=True)

    got = _out_and_grads(kernel, q, k, v, w)
    monkeypatch.setattr(wa, "_narrowed",
                        lambda seg, *geometry: wa._kernel(*geometry)[0])
    static = _out_and_grads(kernel, q, k, v, w)
    real = np.asarray(seg > 0)
    for g, r, s, name in zip(got, want, static, ("out", "dq", "dk", "dv")):
        assert bool(jnp.isfinite(g).all()), name
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5, err_msg=name)
        np.testing.assert_array_equal(g, s, err_msg=name)
        assert float(jnp.abs(g)[~real].max(initial=0.0)) == 0.0, name


# Every KIND of geometry the wide-head table picks (window_attention.
# WIDE_BLOCKS, heads of 256) or its measurement timed
# (tools/window_tile_sweep.py --fused 1), at toy tiles: (blocks, query
# heads, key/value heads) — groups of one (GLM's latent attention) and of
# eight (Qwen3-Next).
WIDE_KINDS = {
    # a query block of two key blocks, each kernel a shape of its own
    "nonsquare_pair_g1": (wa.Blocks(
        (256, 128, 128), (128, 256, 128), (256, 128)), 2, 2),
    # ... and a dQ key block of two query blocks
    "nonsquare_pair_g8": (wa.Blocks(
        (256, 128, 128), (128, 128, 128), (128, 256)), 8, 1),
    # the key block fetched computed half at a time
    "subblocked_pair_g1": (wa.Blocks(
        (256, 256, 128), (256, 256, 128), (256, 256)), 2, 2),
    "subblocked_pair_g8": (wa.Blocks(
        (128, 256, 128), (128, 256, 128), (128, 128)), 8, 1),
    # the fused backward, dQ a partial sum a key block, added up outside:
    # no entry has it (its partial sums do not fit the cells' rows), the
    # sweep timed it through these same Blocks
    "fused_g1": (wa.Blocks((256, 256, 128), (256, 128, 128), None), 4, 4),
    "fused_g8": (wa.Blocks((256, 256, 128), (128, 256, 128), None), 16, 2),
    # today's fallback: square, computed whole, dKV and dQ kernels
    "square_pair_g1": (wa._square(128), 2, 2),
}


# (row, head_dim, group, window) -> what the call runs: the entry
# (head_dim, group) of WIDE_BLOCKS, or the fallback's square tile
WIDE_PICKS = {
    "glm_14336": ((14336, 256, 1, None), (256, 1)),
    "glm_13440": ((13440, 256, 1, None), (256, 1)),
    "qnext_14336": ((14336, 256, 8, None), (256, 8)),
    "qnext_8704": ((8704, 256, 8, None), (256, 8)),
    "glm_4096": ((4096, 256, 1, None), (256, 1)),
    # unmeasured: another group, another head size, a short row, a
    # window — the square pair
    "group_2": ((8192, 256, 2, None), 512),
    "group_4_of_192": ((8192, 192, 4, None), 512),
    "group_16": ((8192, 256, 16, None), 512),
    "short_row": ((1920, 256, 1, None), 512),
    "heads_of_512": ((8192, 512, 1, None), 512),
    "windowed": ((8192, 256, 8, 1024), 512),
}


@pytest.mark.parametrize("name", sorted(WIDE_PICKS))
def test_the_wide_head_table_is_picked_by_what_the_call_sees(name):
    """Heads wider than the lanes: the entry measured at exactly this
    head size and group — the dKV + dQ pair — and the square pair of PR 52
    where nothing was measured; the padded length the engine's remat plan
    reads is the pick's."""
    (n, head_dim, group, window), want = WIDE_PICKS[name]
    got = wa.geometry(n, window, head_dim, group)
    assert got == (wa._square(want) if isinstance(want, int)
                   else wa.WIDE_BLOCKS[want])
    assert got.dq is not None
    got.sizes()  # the blocks are ones the kernels take
    assert wa.padded_len(n, window, head_dim, group) == -(-n // got.tile) * (
        got.tile)
    assert attention.kernel_padded_len(
        "pallas", n, window, head_dim, group) == wa.padded_len(
            n, window, head_dim, group)


def test_a_backward_query_block_lies_inside_a_forward_one():
    """What the forward leaves of a query block of nothing but padding
    (-inf) is read by no backward block that runs: no geometry may give a
    backward kernel a query block that spans two of the forward's."""
    with pytest.raises(AssertionError):
        wa.Blocks((128, 256, 128), (256, 256, 128), None).sizes()
    with pytest.raises(AssertionError):
        wa.Blocks((512, 512, 512), (512, 512, 512), (1024, 512)).sizes()


@pytest.mark.parametrize("kind", sorted(WIDE_KINDS))
def test_wide_heads_at_every_kind_of_geometry(monkeypatch, kind):
    """Heads of 256 over a 512-token row of two documents and padding:
    outputs, dQ, dK and dV are the reference's — at the tolerance of the
    narrow heads' fused cases — finite, zero in padding rows, and EQUAL
    bit for bit to what the same blocks give under the static schedule
    (non-square blocks narrow by ``blocks_needed`` at their own shape)."""
    blocks, Hq, Hkv = WIDE_KINDS[kind]
    monkeypatch.setattr(wa, "_wide_blocks", lambda *a: blocks)
    T, D = 512, 256
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v, w = (jax.random.normal(kk, (1, T, h, D), jnp.float32)
                  for kk, h in zip(ks, (Hq, Hkv, Hkv, Hq)))
    seg = jnp.asarray(_row(T, [150, 210])[None])
    mask = segment_mask(seg, seg, causal=True)
    want = _out_and_grads(
        lambda *a: attention_reference(*a, mask), q, k, v, w)

    def kernel(*a):
        return wa.window_attention(*a, seg, seg, interpret=True)

    with attention.dispatch_label(f"wide-{kind}"):
        got = _out_and_grads(kernel, q, k, v, w)
    assert wa.causal_geometry_counts()[f"wide-{kind}"] == {
        (T, T, blocks): 1}
    monkeypatch.setattr(wa, "_narrowed",
                        lambda seg, *geometry: wa._kernel(*geometry)[0])
    static = _out_and_grads(kernel, q, k, v, w)
    real = np.asarray(seg > 0)
    for g, r, s, name in zip(got, want, static, ("out", "dq", "dk", "dv")):
        assert bool(jnp.isfinite(g).all()), name
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5, err_msg=name)
        np.testing.assert_array_equal(g, s, err_msg=name)
        assert float(jnp.abs(g)[~real].max(initial=0.0)) == 0.0, name


@pytest.mark.parametrize("layout", ["five_docs", "whole_pad_blocks"])
def test_skipped_blocks_under_a_value_wider_than_q_and_k(monkeypatch,
                                                         layout):
    """Differential attention's call (a value of 128 over q / k of 64)
    through ``packed_attention``, on rows with blocks to skip."""
    monkeypatch.setattr(wa, "CAUSAL_TILE_COST", {128: 1.0})
    _interpreted(monkeypatch)
    q, k, v, seg, w = make_layout(layout, "14q2kv64", seed=4, dv=128)

    def run(impl):
        return _out_and_grads(lambda *a: attention.packed_attention(
            *a, seg, seg, impl=impl), q, k, v, w)

    for g, r, name in zip(run("pallas"), run("reference"),
                          ("out", "dq", "dk", "dv")):
        assert bool(jnp.isfinite(g).all()), name
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5, err_msg=name)


def _allowed_blocks(seg, tile, window):
    """The blocks that hold a (query, key) pair ``segment_mask`` allows."""
    mask = np.asarray(segment_mask(seg[None], seg[None], causal=True,
                                   sliding_window=window))[0, 0]
    n = len(seg) // tile
    return mask.reshape(n, tile, n, tile).any(axis=(1, 3))


def _random_row(rng, n_blocks, tile):
    """Contiguous documents of random lengths, ids in a random order,
    then padding (sometimes none, sometimes whole blocks)."""
    T = n_blocks * tile
    real = int(rng.integers(1, T + 1))
    cuts = np.sort(rng.choice(np.arange(1, real), size=min(
        int(rng.integers(0, 7)), real - 1), replace=False))
    lens = np.diff(np.concatenate([[0], cuts, [real]]))
    ids = rng.permutation(len(lens)) + 1
    return np.pad(np.repeat(ids, lens), (0, T - real)).astype(np.int32)


@pytest.mark.parametrize("seed", range(24))
def test_blocks_needed_leaves_out_no_pair_the_mask_allows(seed):
    """On random contiguous layouts: every (query, key) pair that
    ``segment_mask`` allows lies in a needed block, a needed block is one
    of the static mask's, under a causal mask every needed block holds
    such a pair, and a traced row gives what a numpy row gives."""
    rng = np.random.default_rng(seed)
    tile = int(rng.choice([8, 16, 32]))
    n = int(rng.integers(2, 10))
    window = [None, int(rng.integers(1, 3 * tile))][seed % 2]
    seg = _random_row(rng, n, tile)
    needed = wa.blocks_needed(seg, tile, window)
    allowed = _allowed_blocks(seg, tile, window)
    assert needed.shape == (n, n) and needed.dtype == bool
    assert not (allowed & ~needed).any(), (seg, tile, window)
    i, j = np.indices((n, n))
    static = j <= i
    if window is not None:
        static &= j >= np.maximum(i * tile - window + 1, 0) // tile
    assert int(static.sum()) == wa.blocks_visited(n * tile, tile, window)[0]
    assert not (needed & ~static).any()
    if window is None:
        np.testing.assert_array_equal(needed, allowed)
    traced = jax.jit(lambda s: wa.blocks_needed(s, tile, window))(seg)
    np.testing.assert_array_equal(np.asarray(traced), needed)


@pytest.mark.parametrize("seed", range(12))
def test_blocks_needed_with_key_blocks_of_their_own(seed):
    """Non-square blocks under a causal mask, on random contiguous
    layouts: the needed blocks are EXACTLY those that hold a (query, key)
    pair ``segment_mask`` allows, each one of the static mask's, and a
    traced row gives what a numpy row gives."""
    rng = np.random.default_rng(100 + seed)
    block_q, block_kv = [(16, 8), (8, 16), (32, 8), (8, 32)][seed % 4]
    big = max(block_q, block_kv)
    n = int(rng.integers(2, 8))
    seg = _random_row(rng, n, big)
    nq, nk = n * big // block_q, n * big // block_kv
    needed = wa.blocks_needed(seg, block_q, None, block_kv)
    mask = np.asarray(segment_mask(seg[None], seg[None], causal=True))[0, 0]
    allowed = mask.reshape(nq, block_q, nk, block_kv).any(axis=(1, 3))
    assert needed.shape == (nq, nk) and needed.dtype == bool
    np.testing.assert_array_equal(needed, allowed)
    static = np.tril(np.ones((n * big,) * 2, bool)).reshape(
        nq, block_q, nk, block_kv).any(axis=(1, 3))
    assert not (needed & ~static).any()
    assert wa.blocks_visited(n * big, block_q, None, block_kv) == (
        int(static.sum()),) * 2
    traced = jax.jit(
        lambda s: wa.blocks_needed(s, block_q, None, block_kv))(seg)
    np.testing.assert_array_equal(np.asarray(traced), needed)


# (row length, documents, window): blocks needed / the static mask's at
# the tile and padded length the kernel runs (ISSUE 46's hand counts)
HAND_COUNTS = {
    # a 5,930-token trajectory in train-long's 7,296-row, padded to 8,192
    "long_5930_of_7296": (7296, [5930], None, (21, 36)),
    # two 2,356-token documents in its 6,016-row, padded to 6,144
    "long_2x2356_of_6016": (6016, [2356, 2356], None, (11, 21)),
    # Phi's 3,140 + 4,197 in a 7,424-row, padded to 8,192
    "phi_3140_4197_of_7424": (7424, [3140, 4197], None, (24, 36)),
    # Mellum's 4,931-token trajectory in a 6,656-row: a full layer (padded
    # to 7,168 at tile 1024) and a window-1024 layer (tile 512)
    "mellum_4931_of_6656_full": (6656, [4931], None, (15, 28)),
    "mellum_4931_of_6656_window": (6656, [4931], 1024, (27, 36)),
    # a trajectory that fills its row: nothing to skip
    "trinity_8717_of_8832": (8832, [8717], None, (45, 45)),
    # train-packed's 8 x 512 grid: one block a row, the static schedule
    "packed_one_block": (512, [100, 100], None, (1, 1)),
}


@pytest.mark.parametrize("name", sorted(HAND_COUNTS))
def test_count_needed_at_the_cells_layouts(name):
    L, docs, window, want = HAND_COUNTS[name]
    seg = _row(L, docs)[None]
    before = dict(wa.needed_counts().get(
        (1, L, wa.padded_len(L, window), wa.pick_tile(L, window),
         window or 0), {"grids": 0}))
    assert wa.count_needed(seg, window) == want
    after = wa.needed_counts()[
        (1, L, wa.padded_len(L, window), wa.pick_tile(L, window),
         window or 0)]
    assert after["grids"] == before["grids"] + 1
    assert after["blocks_needed"] - before.get("blocks_needed", 0) == want[0]
    assert after["blocks_static"] - before.get("blocks_static", 0) == want[1]


@pytest.mark.parametrize("window", [None, 160, 300])
@pytest.mark.parametrize("layout", ["five_docs", "whole_pad_blocks",
                                    "short_doc_padded"])
def test_the_narrowed_schedule_runs_the_needed_blocks_and_no_other(
        layout, window):
    """Each kernel's ``block_mask`` after narrowing: non-zero exactly at
    the needed blocks (forward and dQ by query block, dKV by key block),
    and every step's ``data_next`` is the block of the next step that
    runs, in the order the grid walks."""
    T, docs = LAYOUTS[layout]
    tile, n = 128, T // 128
    seg = _row(T, docs[0])
    needed = wa.blocks_needed(seg, tile, window)
    geometry = (T, window, 1,
                wa._square(tile, fused=window is None).sizes(), True)
    static, _ = wa._kernel(*geometry)
    narrow = wa._narrowed(jnp.asarray(seg), *geometry)
    infos = [(static.fwd_mask_info, narrow.fwd_mask_info, False),
             (static.dkv_mask_info, narrow.dkv_mask_info, True)]
    if window is not None:
        infos.append((static.dq_mask_info, narrow.dq_mask_info, False))
    else:
        assert narrow.dq_mask_info is None  # the fused backward
    for was, now, by_key in infos:
        block, data = (np.asarray(x)[0] for x in (now.block_mask,
                                                  now.data_next))
        was_block, was_data = (np.asarray(x)[0] for x in (was.block_mask,
                                                          was.data_next))
        assert block.dtype == was_block.dtype and data.dtype == was_data.dtype
        runs = np.zeros((n, n), bool)
        r, c = np.nonzero(block)
        qi, ki = (was_data[r, c], c) if by_key else (r, was_data[r, c])
        runs[qi, ki] = True
        np.testing.assert_array_equal(runs, needed)
        assert len(r) == int(needed.sum())  # no block twice
        np.testing.assert_array_equal(block[r, c], was_block[r, c])
        np.testing.assert_array_equal(data[r, c], was_data[r, c])
        walk = (lambda x: x.T.reshape(-1)) if by_key else (
            lambda x: x.reshape(-1))
        live, nxt, src = walk(block) > 0, walk(data), walk(was_data)
        at = np.flatnonzero(live)
        for step in range(block.size):
            later = at[at >= step]
            assert nxt[step] == src[later[0] if len(later) else at[0]]


@pytest.mark.parametrize("pattern,want", [
    # every layer full causal: 2 layers x (3 + 6) of 2 x (6 + 6) blocks
    (None, 18 / 24),
    # one sliding layer (window 100: 5 static blocks a row, 3 + 5 needed)
    # beside one full layer (3 + 6 of 6 + 6)
    (("sliding", "full"), 17 / 22),
])
def test_the_engines_gauge_is_the_kernels_count(monkeypatch, pattern, want):
    """``train/attn_blocks_needed_frac`` over the layers' windows, from
    ``count_needed`` on the packer's grids — and no gauge where the XLA
    reference runs the rows (the CPU's "auto")."""
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model import FinetuneSpec
    from areal_tpu.backend import microbatch as mbu
    from areal_tpu.backend.jax_train import JaxTrainEngine, OptimizerConfig
    from areal_tpu.base import telemetry
    from areal_tpu.models import transformer
    from areal_tpu.models.config import tiny_config

    monkeypatch.setattr(wa, "TILE_COST", {128: 1.0})
    monkeypatch.setattr(wa, "CAUSAL_TILE_COST", {128: 1.0})
    kw = {} if pattern is None else {"layer_types": pattern,
                                     "sliding_window": 100}
    cfg = tiny_config(vocab_size=64, n_layers=2, **kw)
    assert cfg.attention_windows() == (
        {None: 2} if pattern is None else {100: 1, None: 1})
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    lens = [130, 120, 384]  # rows of 384: two documents + padding, one
    sample = SequenceSample.from_default(
        ids=["a", "b", "c"],
        data={"packed_input_ids": np.ones(sum(lens), np.int32)},
        seqlens=lens)
    telemetry.configure("t", "t0", "trainer", 0, push=False)
    try:
        for impl, gauged in (("auto", False), ("pallas", True)):
            eng = JaxTrainEngine(
                cfg, params, opt_cfg=OptimizerConfig(lr=1e-4),
                ft_spec=FinetuneSpec(1, 8, 4), compute_dtype="float32",
                length_bucket=128, rows_bucket=1, attn_impl=impl)
            mbs = mbu.split_into_microbatches(
                sample, MicroBatchSpec(max_tokens_per_mb=384),
                length_bucket=128, rows_bucket=1)
            assert sorted(mb.grids["segment_ids"].shape for mb in mbs) == [
                (1, 384), (1, 384)]
            eng._gauge_blocks_needed("train", mbs)
            gauges = telemetry.get().snapshot(reset=True)["gauges"]
            if gauged:
                assert gauges["train/attn_blocks_needed_frac"] == (
                    pytest.approx(want))
            else:
                assert "train/attn_blocks_needed_frac" not in gauges
    finally:
        telemetry.shutdown()


@pytest.mark.parametrize("widths", [(192, 128), (64, 32)])
def test_a_value_narrower_than_its_key_keeps_its_own_lanes(monkeypatch,
                                                          widths):
    """Latent attention's call without a query latent (a key of 192 over a
    value of 128; and 64 over 32 inside one lane tile): the key is padded
    to whole lanes, the value to ITS own, outputs and gradients are the
    reference's at the value's width, and ``head_width_counts`` says what
    the kernel was handed and ran."""
    D, Dv = widths
    T, H = 256, 2
    monkeypatch.setattr(wa, "_wide_blocks",
                        lambda *a: wa._square(256))
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k = (jax.random.normal(kk, (1, T, H, D), jnp.float32)
            for kk in ks[:2])
    v, w = (jax.random.normal(kk, (1, T, H, Dv), jnp.float32)
            for kk in ks[2:])
    seg = jnp.asarray(_row(T, [100, 120])[None])
    mask = segment_mask(seg, seg, causal=True)
    want = _out_and_grads(
        lambda *a: attention_reference(*a, mask), q, k, v, w)
    label = f"narrow-value-{D}"
    with attention.dispatch_label(label):
        got = _out_and_grads(
            lambda *a: wa.window_attention(*a, seg, seg, interpret=True),
            q, k, v, w)
    assert got[0].shape == (1, T, H, Dv) and got[3].shape == v.shape
    for g, r, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5, err_msg=name)
    (seen,) = wa.head_width_counts()[label].values()
    lanes = -(-D // 128) * 128
    assert seen == (D, lanes, Dv, 128)
