"""The gated delta rule's Pallas kernels (``ops/pallas/gated_delta_rule.py``)
in Pallas's interpreter on the CPU, at the widths the Qwen3-Next cell runs
(heads of 128, two value heads a key head, chunks of 64): the forward and
every gradient against the token-by-token float32 recurrence
(``benchmark/reference_qwen3_next.delta_rule``, a document at a time) and
against the XLA form of ``models/gdn.gated_delta_rule``; documents that
start inside a chunk, on the chunk grid, in a grid step's second chunk and
twice in one chunk; a row that is no whole number of chunks; trailing
padding; ``A_log`` drawn LOW, so that the state is remembered across
chunks and grid steps; the dispatch's three answers and its counters; and
the control no cell's ``correct`` sees but ``rule_error`` — the carried
state narrowed to bfloat16 — which this file must refuse. And the kernels
WITH the mixer's two norms inside (``gdn.rule_with_norms``: raw q and k
in, the gated, normed ``y`` out) against the XLA mixer's ends — l2 norm,
rule, gated RMS norm — output and the gradients of q, k, v, g, β, z and
the norm's weight.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import gdn
from areal_tpu.models.config import GDNConfig
from areal_tpu.ops.pallas import gated_delta_rule as kernel
from benchmark import reference_qwen3_next as ref

D, Q, R = 128, 64, 2  # head size, chunk, value heads a key head
GRADS = ("q", "k", "v", "g", "beta")


def inputs(T, G, seed=0, dtype=jnp.float32, low=True, r=R, alike=0.0):
    """One row. ``low``: ``A_log`` drawn low (g of -0.01 to -0.4 a token:
    the state lasts hundreds of tokens); else as the model draws it.
    ``alike``: the share of a direction all keys of a head have in common."""
    H = G * r
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = gdn.l2_normalize(jax.random.normal(ks[0], (1, T, G, D))) * D ** -0.5
    k = gdn.l2_normalize(
        (1 - alike) * jax.random.normal(ks[1], (1, T, G, D))
        + alike * jax.random.normal(ks[6], (1, 1, G, D)))
    v = jax.random.normal(ks[2], (1, T, H, D))
    lo, hi = (0.01, 0.3) if low else (1.0, 16.0)
    g = -jax.random.uniform(ks[3], (H,), minval=lo, maxval=hi
                            ) * jax.nn.softplus(
        jax.random.normal(ks[4], (1, T, H)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (1, T, H)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def layout(which, T):
    """Segment ids [1, T]: the second document starts (a) inside the first
    chunk, (b) inside the second chunk — a step's second where a step
    holds more than one —, (c) on the chunk grid, (d) and a third one in
    the same chunk; (e) trailing padding (segment 0) from inside a chunk."""
    cuts = {"first": [23, T], "second": [Q + 6, T], "grid": [2 * Q, T],
            "twice": [Q + 5, Q + 40, T], "padding": [Q - 9, T - Q // 2]
            }[which]
    seg = np.zeros((1, T), np.int32)
    a = 0
    for i, b in enumerate(cuts):
        seg[0, a:b] = i + 1
        a = b
    return seg


def sequential(q, k, v, g, beta, seg, w):
    """(Σ w · o, o) of the recurrence, a document at a time, float32; o is
    0 on padding. ``seg`` a numpy array (the documents are sliced)."""
    f32 = jnp.float32
    R = v.shape[2] // q.shape[2]
    o = jnp.zeros(v.shape, f32)
    for s in np.unique(seg[0]):
        if s:
            idx = np.nonzero(seg[0] == s)[0]
            a, e = idx[0], idx[-1] + 1
            o = o.at[0, a:e].set(ref.delta_rule(
                jnp.repeat(q[0, a:e].astype(f32), R, axis=1),
                jnp.repeat(k[0, a:e].astype(f32), R, axis=1),
                v[0, a:e].astype(f32), g[0, a:e], beta[0, a:e]))
    return jnp.sum(o * w), o


@functools.partial(jax.jit, static_argnums=0)
def chunked(impl, q, k, v, g, beta, seg, w):
    """((Σ w · o, o), the five gradients) of ``gdn.gated_delta_rule``."""
    def loss(q, k, v, g, beta):
        o = gdn.gated_delta_rule(q, k, v, g, beta, seg, Q, impl)
        o = o * (seg > 0)[..., None, None]
        return jnp.sum(o * w), o

    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(
        q, k, v, g, beta)


def worst(got, want):
    """max |got - want| over max |want|, float32."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# layout, row length (256: one step of 4 chunks; 384: 3 steps of 2; 320: 5
# steps of 1; 300: no whole number of chunks; 1024: 2 steps of 8, the state
# and its cotangent carried from one whole step into the next), key heads
CASES = [("first", 256, 1), ("second", 256, 1), ("grid", 256, 1),
         ("twice", 384, 2), ("padding", 320, 1), ("padding", 300, 2),
         ("twice", 1024, 1)]


@pytest.mark.parametrize("which,T,G", CASES)
def test_the_kernel_equals_the_recurrence_in_float32(which, T, G):
    """Forward and the five gradients, compute dtype float32, a state that
    lasts: against the recurrence and against the XLA form, at float32's
    own distance."""
    args = inputs(T, G, seed=T + G)
    seg = layout(which, T)
    w = jax.random.normal(jax.random.PRNGKey(9), (1, T, G * R, D))
    before = gdn.rule_impl_counts().get("pallas_interpret", 0)
    with jax.default_matmul_precision("highest"):
        (_, o), got = chunked("pallas_interpret", *args, jnp.asarray(seg), w)
        (_, o_xla), xla = chunked("xla", *args, jnp.asarray(seg), w)
        (_, o_seq), want = jax.jit(jax.value_and_grad(
            lambda *a: sequential(*a, seg, w), argnums=(0, 1, 2, 3, 4),
            has_aux=True))(*args)
    assert gdn.rule_impl_counts()["pallas_interpret"] >= before
    assert worst(o, o_seq) < 2e-5 and worst(o, o_xla) < 2e-5
    for name, a, x_, s in zip(GRADS, got, xla, want):
        assert worst(a, s) < 1e-4, (name, worst(a, s))
        assert worst(a, x_) < 1e-4, (name, worst(a, x_))


@pytest.mark.parametrize("which,T,G,low", [
    ("second", 256, 1, True), ("padding", 300, 2, False)])
def test_the_kernel_in_bfloat16_is_as_near_as_the_xla_form(which, T, G, low):
    """q, k, v bfloat16: forward and gradients are as near the float32
    recurrence (on the same rounded operands) as the XLA form's — within
    twice its distance and a bfloat16 ulp of room."""
    args = inputs(T, G, seed=T, dtype=jnp.bfloat16, low=low)
    seg = layout(which, T)
    w = jax.random.normal(jax.random.PRNGKey(9), (1, T, G * R, D))
    (_, o), got = chunked("pallas_interpret", *args, jnp.asarray(seg), w)
    (_, o_xla), xla = chunked("xla", *args, jnp.asarray(seg), w)
    with jax.default_matmul_precision("highest"):
        (_, o_seq), want = jax.jit(jax.value_and_grad(
            lambda *a: sequential(*a, seg, w), argnums=(0, 1, 2, 3, 4),
            has_aux=True))(*args)
    assert o.dtype == jnp.float32 and got[0].dtype == jnp.bfloat16
    assert worst(o, o_seq) < 2 * worst(o_xla, o_seq) + 2 ** -8
    for name, a, x_, s in zip(GRADS, got, xla, want):
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        assert worst(a, s) < 2 * worst(x_, s) + 2 ** -8, (
            name, worst(a, s), worst(x_, s))


ENDS_GRADS = GRADS + ("z", "w")
EPS = 1e-6  # the model's rms_norm_eps


def ends_inputs(T, G, r, seed, dtype, low=True):
    """The mixer's arrays between its convolution and its output
    projection, one row: q, k RAW (silu of a normal draw, as the
    convolution leaves them; token 70's q and k and token 7's k all zero:
    the epsilon), v, g, β as :func:`inputs`, the gate z and the norm's
    weight."""
    _, _, v, g, beta = inputs(T, G, seed=seed, dtype=dtype, low=low, r=r)
    ks = jax.random.split(jax.random.PRNGKey(seed + 100), 4)
    q, k = (jax.nn.silu(2.0 * jax.random.normal(kk, (1, T, G, D)))
            for kk in ks[:2])
    q = q.at[0, 70].set(0.0)
    k = k.at[0, 70].set(0.0).at[0, 7].set(0.0)
    z = 2.0 * jax.random.normal(ks[2], (1, T, G * r * D))
    w = 1.0 + 0.3 * jax.random.normal(ks[3], (D,))
    return (q.astype(dtype), k.astype(dtype), v, z.astype(dtype),
            w.astype(dtype), g, beta)


def xla_ends(q, k, v, z, w, g, beta, seg, impl="xla"):
    """``gdn.gdn_mixer``'s text between ``gdn_conv`` and ``gdn_out_proj``."""
    f32, cd = jnp.float32, v.dtype
    q = (gdn.l2_normalize(q) * D ** -0.5).astype(cd)
    k = gdn.l2_normalize(k).astype(cd)
    o = gdn.gated_delta_rule(q, k, v, g, beta, seg, Q, impl)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + EPS)
    y = (o * w.astype(f32)).astype(cd)
    return (y.reshape(z.shape).astype(f32)
            * jax.nn.silu(z.astype(f32))).astype(cd)


@functools.partial(jax.jit, static_argnums=0)
def ends(how, q, k, v, z, w, g, beta, seg, wt):
    """((Σ wt · y, y), the seven gradients) of the mixer's ends: the
    kernels with their norms (``how`` "pallas_interpret"), the XLA text
    around the XLA rule ("xla") or around the un-normed kernels
    ("xla+pallas_interpret")."""
    def loss(q, k, v, g, beta, z, w):
        if how == "pallas_interpret":
            y = gdn.rule_with_norms(q, k, v, z, w, g, beta, seg, Q, EPS, how)
        else:
            y = xla_ends(q, k, v, z, w, g, beta, seg, how.split("+")[-1])
        y = y.astype(jnp.float32) * (seg > 0)[..., None]
        return jnp.sum(y * wt), y

    return jax.value_and_grad(loss, argnums=tuple(range(7)), has_aux=True)(
        q, k, v, g, beta, z, w)


# layout, row length, key heads, value heads a key head
ENDS_CASES = [("first", 256, 1, 2), ("twice", 384, 2, 2),
              ("padding", 300, 2, 2), ("second", 256, 1, 1),
              ("twice", 200, 2, 1)]


@pytest.mark.parametrize("which,T,G,r", ENDS_CASES)
def test_the_norms_inside_the_kernels_equal_the_mixers_ends_in_float32(
        which, T, G, r):
    """Raw q, k in and the gated, normed y out: the output and the seven
    gradients at float32's own distance from the XLA text around the XLA
    rule; an all-zero q / k row leaves through the epsilons alone."""
    args = ends_inputs(T, G, r, seed=T + G + r, dtype=jnp.float32)
    seg = jnp.asarray(layout(which, T))
    wt = jax.random.normal(jax.random.PRNGKey(9), (1, T, G * r * D))
    with jax.default_matmul_precision("highest"):
        (_, y), got = ends("pallas_interpret", *args, seg, wt)
        (_, y_xla), want = ends("xla", *args, seg, wt)
    assert y.shape == (1, T, G * r * D)
    assert not np.asarray(y[0, 70]).any()  # q = 0: o = 0, whatever rstd
    assert worst(y, y_xla) < 2e-5
    for name, a, x_ in zip(ENDS_GRADS, got, want):
        assert a.shape == x_.shape and a.dtype == x_.dtype, name
        assert np.isfinite(np.asarray(a)).all(), name
        assert worst(a, x_) < 2e-4, (name, worst(a, x_))


def sequential_ends(q, k, v, z, w, g, beta, seg, wt):
    """(Σ wt · y, y) of the mixer's ends around the token-by-token
    recurrence (:func:`sequential`), float32; ``seg`` a numpy array."""
    _, o = sequential(gdn.l2_normalize(q) * D ** -0.5, gdn.l2_normalize(k),
                      v, g, beta, seg, 0.0)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + EPS)
    y = (o * w).reshape(z.shape) * jax.nn.silu(z)
    return jnp.sum(y * wt), y


# layout, row length, key heads, value heads a key head (2: the cell's 16 /
# 32 grouping), the backward's chunks a step at most — (256, 8): one step of
# 4 chunks; (512, 8): one step of 8; (1024, 8): 2 steps of 8, dS carried from
# a whole step into the one before it; (384, 8): 3 steps of 2; (300, 8): no
# whole number of chunks, 5 steps of 1; (512, 1) and (512, 2): the backward
# walks 8 and 4 steps where the forward that left the states took one
BWD_CASES = [("first", 256, 1, 2, 8), ("second", 512, 1, 2, 8),
             ("twice", 1024, 1, 2, 8), ("twice", 384, 2, 2, 8),
             ("padding", 300, 2, 2, 8), ("second", 512, 2, 2, 1),
             ("twice", 512, 1, 2, 2), ("twice", 200, 2, 1, 8)]


@pytest.mark.parametrize("which,T,G,r,most", BWD_CASES)
def test_the_backward_equals_the_recurrences_in_float32(
        which, T, G, r, most, monkeypatch):
    """The backward kernel against the float32 token-by-token recurrence
    inside the mixer's ends, not only against the chunked XLA form: every
    cotangent — dq, dk, dv, dg, dβ, the output gate's and the norm
    weight's — where a document begins inside a chunk, the row is no whole
    number of steps of 8 chunks, a step holds one chunk or several, and the
    backward's steps are not the forward's."""
    monkeypatch.setattr(kernel, "BWD_CHUNKS_PER_STEP", most)
    assert kernel.chunks_per_step(-(-T // Q), backward=True) == min(
        most, kernel.chunks_per_step(-(-T // Q)))
    args = ends_inputs(T, G, r, seed=T + G + r + most, dtype=jnp.float32)
    seg = layout(which, T)
    wt = jax.random.normal(jax.random.PRNGKey(9), (1, T, G * r * D))
    jax.clear_caches()  # ``ends`` was traced at another chunks a step
    with jax.default_matmul_precision("highest"):
        (_, y), got = ends("pallas_interpret", *args, jnp.asarray(seg), wt)
        q, k, v, z, w, g, beta = args
        (_, y_seq), want = jax.jit(jax.value_and_grad(
            lambda q, k, v, g, beta, z, w: sequential_ends(
                q, k, v, z, w, g, beta, seg, wt),
            argnums=tuple(range(7)), has_aux=True))(q, k, v, g, beta, z, w)
    jax.clear_caches()
    assert worst(y, y_seq) < 2e-5
    for name, a, s_ in zip(ENDS_GRADS, got, want):
        assert a.shape == s_.shape and a.dtype == s_.dtype, name
        assert worst(a, s_) < 2e-4, (name, worst(a, s_))


@pytest.mark.parametrize("which,T,G,r,low", [
    ("second", 256, 1, 2, True), ("padding", 300, 2, 1, False)])
def test_the_norms_inside_the_kernels_in_bfloat16_are_as_near_as_the_xla_text(
        which, T, G, r, low):
    """bfloat16 operands: y and the gradients are as near the float32 ends
    (XLA, "highest", the same rounded operands) as the XLA text around
    the un-normed kernels is — within twice its distance and a bfloat16
    ulp of room —, and y leaves in bfloat16."""
    bf, f32 = jnp.bfloat16, jnp.float32
    args = ends_inputs(T, G, r, seed=T, dtype=bf, low=low)
    seg = jnp.asarray(layout(which, T))
    wt = jax.random.normal(jax.random.PRNGKey(9), (1, T, G * r * D))
    (_, y), got = ends("pallas_interpret", *args, seg, wt)
    (_, y_xla), xla = ends("xla+pallas_interpret", *args, seg, wt)
    q, k, v, z, w, g, beta = args
    with jax.default_matmul_precision("highest"):
        (_, y_32), want = ends("xla", *(a.astype(f32) for a in args), seg, wt)
    assert gdn.rule_with_norms(q, k, v, z, w, g, beta, seg, Q, EPS,
                               "pallas_interpret").dtype == bf
    assert worst(y, y_32) < 2 * worst(y_xla, y_32) + 2 ** -8
    for name, a, x_, s in zip(ENDS_GRADS, got, xla, want):
        assert a.dtype == x_.dtype, name
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        assert worst(a, s) < 2 * worst(x_, s) + 2 ** -8, (
            name, worst(a, s), worst(x_, s))


def test_one_value_head_a_key_head_rides_half_a_pair():
    """r = 1: the second head's lanes of every pair are empty."""
    T, G = 200, 2
    args = inputs(T, G, seed=3, r=1)
    seg = layout("twice", T)
    w = jax.random.normal(jax.random.PRNGKey(9), (1, T, G, D))
    with jax.default_matmul_precision("highest"):
        (_, o), got = chunked("pallas_interpret", *args, jnp.asarray(seg), w)
        (_, o_seq), want = jax.jit(jax.value_and_grad(
            lambda *a: sequential(*a, seg, w), argnums=(0, 1, 2, 3, 4),
            has_aux=True))(*args)
    assert worst(o, o_seq) < 2e-5
    for name, a, s in zip(GRADS, got, want):
        assert worst(a, s) < 1e-4, (name, worst(a, s))


def test_keys_that_resemble_each_other_do_not_break_the_inverse():
    """A chunk whose keys share most of a direction (cosines of ~0.97)
    under a slow decay: ``A`` is nearly full at β k·k ≈ β, the powers of
    ``−A`` grow with the binomials, and their sum — the XLA form's
    inverse — cancels what float32 cannot hold; the kernel's inverse by
    blocks multiplies factors no larger than the inverse's own and stays
    at the recurrence."""
    T = 256
    args = inputs(T, 1, seed=11, alike=0.8)
    seg = np.ones((1, T), np.int32)
    w = jax.random.normal(jax.random.PRNGKey(9), (1, T, R, D))
    with jax.default_matmul_precision("highest"):
        (_, o), got = chunked("pallas_interpret", *args, jnp.asarray(seg), w)
        (_, o_xla), _ = chunked("xla", *args, jnp.asarray(seg), w)
        (_, o_seq), want = jax.jit(jax.value_and_grad(
            lambda *a: sequential(*a, seg, w), argnums=(0, 1, 2, 3, 4),
            has_aux=True))(*args)
    assert worst(o, o_seq) < 2e-5
    for name, a, s in zip(GRADS, got, want):
        assert worst(a, s) < 2e-4, (name, worst(a, s))
    assert worst(o_xla, o_seq) > 10 * worst(o, o_seq)


def test_a_bfloat16_state_between_chunks_is_refused():
    """The control: the recurrence with its state rounded to bfloat16 after
    each chunk's last token — what a kernel with a bfloat16 carry computes
    —, at a state that lasts, fails the tolerance that the kernel passes;
    and a float32 run's kept states hold bits bfloat16 has not."""
    T, G = 384, 1
    q, k, v, g, beta = inputs(T, G, seed=5)
    bf, f32 = jnp.bfloat16, jnp.float32
    qh, kh = (jnp.repeat(a[0], R, axis=1) for a in (q, k))

    @functools.partial(jax.jit, static_argnums=0)
    def recurrence(narrow):
        def step(S, inp):
            t, q_t, k_t, v_t, g_t, b_t = inp
            S = jnp.exp(g_t)[:, None, None] * S
            d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t,
                                                 precision=ref.HI))
            S = S + k_t[:, :, None] * d[:, None, :]
            if narrow:
                S = jnp.where(t % Q == Q - 1, S.astype(bf).astype(f32), S)
            return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=ref.HI)

        return jax.lax.scan(step, jnp.zeros((G * R, D, D), f32),
                            (jnp.arange(T), qh, kh, v[0], g[0], beta[0]))[1]

    want = recurrence(False)
    with jax.default_matmul_precision("highest"):
        o, states = kernel.rule_fwd(q, k, v, g, beta,
                                    jnp.ones((1, T), jnp.int32), Q, keep=True,
                                    interpret=True)
    assert worst(o[0], want) < 2e-5
    assert worst(recurrence(True), want) > 2e-4
    assert states.dtype == f32 and states.shape == (1, T // Q, G, D, R * D)
    assert worst(states.astype(bf), states) > 2 ** -10


def test_what_the_kernel_takes_and_the_dispatchs_three_answers(monkeypatch):
    bf = jnp.bfloat16
    assert kernel.supported(64, 16, 32, 128, 128, bf)  # the cell's
    assert kernel.supported(64, 1, 2, 128, 128, jnp.float32)
    assert kernel.supported(64, 4, 4, 128, 128, bf)
    assert not kernel.supported(64, 4, 16, 128, 128, bf)  # r = 4: no tile
    assert not kernel.supported(64, 2, 4, 16, 32, bf)  # the tiny models
    assert not kernel.supported(128, 16, 32, 128, 128, bf)
    assert not kernel.supported(64, 16, 32, 128, 128, jnp.float16)
    assert [kernel.chunks_per_step(z) for z in (224, 136, 6, 5)] == [
        8, 8, 2, 1]
    assert [kernel.chunks_per_step(z, backward=True)
            for z in (224, 136, 6, 5)] == [
        min(n, kernel.BWD_CHUNKS_PER_STEP) for n in (8, 8, 2, 1)]
    cell = (64, 16, 32, 128, 128, bf)
    assert gdn._rule_impl("pallas_interpret", *cell) == "pallas_interpret"
    assert gdn._rule_impl("pallas", *cell) == "pallas"
    assert gdn._rule_impl("auto", *cell) == "xla"  # no TPU here
    assert gdn._rule_impl("reference", *cell) == "xla"
    assert gdn._rule_impl("pallas", 64, 2, 4, 16, 32, bf) == "xla"
    # a chip without the VMEM the kernels ask for runs the XLA form
    monkeypatch.setattr(kernel, "fits_device", lambda: False)
    assert gdn._rule_impl("pallas", *cell) == "xla"
    assert gdn._rule_impl("pallas_interpret", *cell) == "pallas_interpret"


def test_the_mixer_runs_the_kernel_all_heads_at_once_and_counts_it():
    """``gdn_mixer`` under ``pallas_interpret``: the same output and
    gradients as under the XLA form (which, at four key heads or more,
    runs a group of heads at a time under a checkpoint), one geometry
    count a traced mixer, the kernels' ops under scope ``gdn_rule`` by
    their names."""
    cfg = GDNConfig(n_k_heads=1, n_v_heads=2, k_head_dim=D, v_head_dim=D,
                    conv_kernel=4, chunk_size=Q)
    hidden, T = 64, 140
    lp = {name: w[0] for name, w in gdn.init_gdn_params(
        cfg, 1, hidden, jax.random.PRNGKey(0), jnp.float32).items()}
    lp["gdn_A_log"] = lp["gdn_A_log"] - 4.0  # a state that lasts
    u = jax.random.normal(jax.random.PRNGKey(1), (1, T, hidden))
    seg = jnp.asarray(layout("padding", T))

    def loss(impl, u, lp):
        return jnp.sum(gdn.gdn_mixer(u, lp, cfg, 1e-6, seg, impl) ** 2)

    geometry = dict(gdn.geometry_counts())
    counts = dict(gdn.rule_impl_counts())
    norms = gdn.mixer_norm_counts()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(
            functools.partial(loss, "pallas_interpret"), argnums=(0, 1)))(
                u, lp)
        key = (1, T, Q, 1, 2, D, D)
        assert gdn.geometry_counts()[key] == geometry.get(key, 0) + 1
        assert gdn.rule_impl_counts()["pallas_interpret"] == counts.get(
            "pallas_interpret", 0) + 1
        assert gdn.rule_impl_counts().get("xla", 0) == counts.get("xla", 0)
        # ... with both norms inside the kernels, and says so
        assert gdn.mixer_norm_counts()["kernel"] == norms.get("kernel", 0) + 1
        assert gdn.mixer_norm_counts().get("xla", 0) == norms.get("xla", 0)
        want = jax.jit(jax.value_and_grad(
            functools.partial(loss, "reference"), argnums=(0, 1)))(u, lp)
    assert gdn.mixer_norm_counts()["xla"] == norms.get("xla", 0) + 1
    assert 0.0 < gdn.rule_kernel_frac() <= 1.0
    assert 0.0 < gdn.norms_in_kernel_frac() < 1.0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert worst(a, b) < 1e-4
    # ... one forward and one backward kernel under scope ``gdn_rule``:
    # all heads at once, and nothing of the mixer runs twice
    text = str(jax.make_jaxpr(jax.grad(functools.partial(
        loss, "pallas_interpret")))(u, lp))
    assert text.count("name=gdn_rule_fwd") == 1
    assert text.count("name=gdn_rule_bwd") == 1
    lowered = jax.jit(functools.partial(loss, "pallas_interpret")).lower(
        u, lp).as_text(debug_info=True)
    assert "gdn_rule" in lowered
