"""The channel-decay delta rule's Pallas kernels (``ops/pallas/kda_rule.py``)
in Pallas's interpreter on the CPU, at the widths the Kimi-Linear cell runs
(heads of 128, chunks of 64): the forward and every gradient against the
token-by-token float32 recurrence (``benchmark/reference_kimi_linear.
delta_rule``, a document at a time) and against the XLA form
(``models/kda._rule_xla``); documents that
start inside a chunk, on the chunk grid, in a grid step's second chunk and
twice in one chunk; a row that is no whole number of chunks; rows of 11 and
17 chunks at 8 a grid step (a last step of 3 chunks and of 1, a document
that starts inside it and one that ends where a step ends; what the step's
blocks hold past the row's end must reach nothing); trailing
padding; decays drawn LOW, so that the state is remembered across chunks and
grid steps, and decays so STRONG that ``k ⊙ e^{-c}`` — the factor a
reference outside the chunk's sub-blocks would need — overflows float32;
the dispatch's answers and its counters; a Gated DeltaNet rule (the decay
averaged over a head's channels), which this file must refuse; and that the
scalar-decay rule's traced program is what it was. And the MIXER'S entry of
the same two kernels (``kda.rule_with_ends``: the SiLU, both l2 norms, the
decay's activation, β's products and the gated norm inside them) against
the mixer's XLA text between its convolution and its out-projection:
forward and the gradient of every operand and parameter, float32 and
bfloat16, on the same layouts; and where ``kda_mixer`` takes which.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import gdn, kda
from areal_tpu.models.config import KDAConfig
from areal_tpu.ops.pallas import kda_rule as kernel
from benchmark import reference_kimi_linear as ref

D, Q = 128, 64  # head size, chunk
GRADS = ("q", "k", "v", "g", "beta")


def inputs(T, H, seed=0, dtype=jnp.float32, strong=1.0):
    """One row. Decays of -0.002 to -0.7 a token and channel (the state
    lasts hundreds of tokens on some channels and a few on others), times
    ``strong``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = gdn.l2_normalize(jax.random.normal(ks[0], (1, T, H, D))) * D ** -0.5
    k = gdn.l2_normalize(jax.random.normal(ks[1], (1, T, H, D)))
    v = jax.random.normal(ks[2], (1, T, H, D))
    rate = jnp.exp(jax.random.uniform(ks[3], (H, D), minval=np.log(0.002),
                                      maxval=np.log(0.5)))
    g = -rate * jax.nn.softplus(
        jax.random.normal(ks[4], (1, T, H, D)) + 1.0) * strong
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (1, T, H)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def layout(which, T):
    """Segment ids [1, T] (tests/test_gdn_rule_kernel.py's layouts)."""
    cuts = {"first": [23, T], "second": [Q + 6, T], "grid": [2 * Q, T],
            "twice": [Q + 5, Q + 40, T], "padding": [Q - 9, T - Q // 2]}[which]
    seg = np.zeros(T, np.int32)
    start = 0
    for i, end in enumerate(cuts):
        seg[start:end] = i + 1
        start = end
    return jnp.asarray(seg)[None]


def by_document(q, k, v, g, beta, seg):
    """The reference's recurrence on each document alone (float32)."""
    out = np.zeros(v.shape, np.float32)
    ids = np.asarray(seg[0])
    with jax.default_matmul_precision("highest"):
        for s in sorted(set(ids[ids > 0])):
            at = np.where(ids == s)[0]
            lo, hi = at[0], at[-1] + 1
            out[0, lo:hi] = np.asarray(ref.delta_rule(
                *(a[0, lo:hi].astype(jnp.float32)
                  for a in (q, k, v, g, beta))))
    return jnp.asarray(out)


def rule(how):
    return lambda q, k, v, g, b, seg: kda.channel_decay_rule(
        q, k, v, g, b, seg, Q, how)


def real(seg):
    return (seg > 0)[..., None, None]


@pytest.mark.parametrize("which", ["first", "second", "grid", "twice",
                                   "padding"])
def test_forward_against_the_token_scan(which):
    T = 4 * Q
    a = inputs(T, 2, seed=1)
    seg = layout(which, T)
    want = by_document(*a, seg)
    for how in ("pallas_interpret", "xla"):
        got = rule(how)(*a, seg)
        assert float(jnp.max(jnp.abs((got - want) * real(seg)))) < 2e-6, how


def test_a_row_that_is_no_whole_number_of_chunks_and_several_steps():
    """9 chunks and a half: more than one grid step, the last one short."""
    T = 9 * Q + 31
    a = inputs(T, 1, seed=2)
    seg = jnp.ones((1, T), jnp.int32).at[0, 300:].set(2)
    want = by_document(*a, seg)
    got = rule("pallas_interpret")(*a, seg)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6


@pytest.mark.parametrize("chunks,strong", [(11, 1.0), (17, 1.0), (11, 200.0)])
def test_a_short_last_step_against_the_token_scan(chunks, strong):
    """Two rows of ``chunks`` chunks at 8 a grid step: the last step holds 3
    chunks of the row (or 1) and reaches 5 (or 7) past its end. Row 0: a
    document that ends exactly where the first step ends and one that
    starts inside the short step; row 1: a document's start inside the
    first step's chunks and trailing padding. The forward and the gradient
    of all five operands against the token scan, and the counter says 8."""
    T, step = chunks * Q, 8 * Q
    rows = [inputs(T, 1, seed=10 + r, strong=strong) for r in range(2)]
    a = tuple(jnp.concatenate(x) for x in zip(*rows))
    last = T - (T - 1) % step - 1  # the short step's first token
    seg = np.zeros((2, T), np.int32)
    seg[0, :step], seg[0, step:last + 20], seg[0, last + 20:] = 1, 2, 3
    seg[1, :300], seg[1, 300:T - 40] = 1, 2
    seg = jnp.asarray(seg)
    before = dict(kernel.step_counts())

    def loss(fn):
        def f(*x):
            o = fn(*x)
            return jnp.sum(jnp.sin(o) * real(seg)), o
        return jax.jit(jax.value_and_grad(f, argnums=range(5), has_aux=True))

    def scan(*x):
        return jnp.concatenate([
            _scan_row(*(b[r:r + 1] for b in x), seg[r:r + 1])
            for r in range(2)])

    with jax.default_matmul_precision("highest"):
        (_, want), gwant = loss(scan)(*a)
    (_, got), grads = loss(lambda *x: rule("pallas_interpret")(*x, seg))(*a)
    assert float(jnp.max(jnp.abs((got - want) * real(seg)))) < 2e-6
    for name, x, w in zip(GRADS, grads, gwant):
        assert bool(jnp.isfinite(x).all()), name
        scale = max(float(jnp.max(jnp.abs(w))), 1.0)
        assert float(jnp.max(jnp.abs(x - w))) < 2e-5 * scale, name
    after = kernel.step_counts()
    ran = {k: n - before.get(k, 0) for k, n in after.items()
           if n > before.get(k, 0)}
    assert ran == {(2, T, 1, 8, kernel.BWD_CHUNKS_PER_STEP): 2}, ran
    assert kernel.steps_of(chunks) == (8, kernel.BWD_CHUNKS_PER_STEP)
    assert kernel.steps_of(3) == (2, 2) and kernel.steps_of(1) == (1, 1)


@pytest.mark.parametrize("strong", [1.0, 200.0])
def test_gradients_against_the_token_scan(strong):
    """Every gradient; at ``strong`` 200 a chunk's cumulated decay passes
    -3000 on some channels: ``e^{+3000}`` is what a factor ``k ⊙ e^{-c}``
    would be, and every number here must stay finite."""
    T = 3 * Q
    a = inputs(T, 2, seed=3, strong=strong)
    seg = layout("second", T)
    assert float(jnp.min(jnp.cumsum(a[3], axis=1))) < (
        -3000 if strong > 1 else -1)

    def loss(fn):
        return lambda *x: jnp.sum(jnp.sin(fn(*x)) * real(seg))

    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss(lambda *x: _scan_row(*x, seg)),
                        argnums=range(5))(*a)
    for how in ("pallas_interpret", "xla"):
        got = jax.grad(loss(lambda *x: rule(how)(*x, seg)),
                       argnums=range(5))(*a)
        for name, x, w in zip(GRADS, got, want):
            assert bool(jnp.isfinite(x).all()), (how, name)
            scale = float(jnp.max(jnp.abs(w))) + 1e-6
            assert float(jnp.max(jnp.abs(x - w))) < 2e-5 * max(scale, 1.0), (
                how, name)


def _scan_row(q, k, v, g, beta, seg):
    """The recurrence with the state zeroed at a document's first token,
    differentiable (the reference's, a row at a time)."""
    H = q.shape[2]

    def step(carry, x):
        S, prev = carry
        q, k, v, g, b, s = x
        S = jnp.where(s != prev, 0.0, S)
        S = jnp.exp(g)[:, :, None] * S
        d = b[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k))
        S = S + k[:, :, None] * d[:, None, :]
        return (S, s), jnp.einsum("hkv,hk->hv", S, q)

    _, o = jax.lax.scan(step, (jnp.zeros((H, D, D)), jnp.int32(-1)),
                        (q[0], k[0], v[0], g[0], beta[0], seg[0]))
    return o[None]


def test_bfloat16_operands():
    T = 4 * Q
    a = inputs(T, 2, seed=4, dtype=jnp.bfloat16)
    seg = layout("first", T)
    want = by_document(*a, seg)
    got = rule("pallas_interpret")(*a, seg)
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs((got - want) * real(seg)))) < 4e-3
    grads = jax.grad(lambda *x: jnp.sum(rule("pallas_interpret")(*x, seg)),
                     argnums=range(5))(*a)
    assert [x.dtype for x in grads[:3]] == [jnp.bfloat16] * 3
    assert all(bool(jnp.isfinite(x.astype(jnp.float32)).all()) for x in grads)


def test_a_decay_averaged_over_a_heads_channels_is_refused():
    """A Gated DeltaNet rule in KDA's place: the same inputs with ``g``
    averaged over a head's channels move the output by orders more than
    the tolerance above."""
    T = 4 * Q
    q, k, v, g, beta = inputs(T, 2, seed=5)
    seg = layout("grid", T)
    want = by_document(q, k, v, g, beta, seg)
    flat = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    got = rule("pallas_interpret")(q, k, v, flat, beta, seg)
    assert float(jnp.max(jnp.abs(got - want))) > 1e-2


def test_dispatch_and_counters():
    cfg = KDAConfig(n_heads=4, head_dim=128)
    assert kda._rule_impl("auto", cfg, jnp.bfloat16) == "xla"  # a CPU
    assert kda._rule_impl("pallas_interpret", cfg, jnp.float32) == (
        "pallas_interpret")
    assert kda._rule_impl("pallas_interpret",
                          KDAConfig(n_heads=4, head_dim=64),
                          jnp.float32) == "xla"
    assert kda._rule_impl("pallas_interpret",
                          KDAConfig(n_heads=4, head_dim=128, chunk_size=32),
                          jnp.float32) == "xla"
    assert not kernel.supported(64, 4, 128, 128, jnp.float16)
    before = dict(kda.rule_impl_counts())
    a = inputs(Q, 1, seed=6)
    rule("pallas_interpret")(*a, jnp.ones((1, Q), jnp.int32))
    after = kda.rule_impl_counts()
    assert after["pallas_interpret"] == before.get("pallas_interpret", 0) + 1
    assert 0.0 < kda.rule_kernel_frac() <= 1.0


def test_the_kernels_names_and_scope():
    """The benchmark reads the rule by scope ``kda_rule`` and the kernels
    by ``kda_rule_fwd`` / ``kda_rule_bwd``."""
    a = inputs(Q, 1, seed=7)
    seg = jnp.ones((1, Q), jnp.int32)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *x: jnp.sum(rule("pallas_interpret")(*x, seg)),
        argnums=range(5)))(*a))
    assert "kda_rule_fwd" in text and "kda_rule_bwd" in text
    assert (kernel.FWD_NAME, kernel.BWD_NAME) == ("kda_rule_fwd",
                                                  "kda_rule_bwd")


ENDS = ("x", "a", "gate", "beta", "A_log", "dt_bias", "norm")
EPS = 1e-5


def ends_inputs(R, T, H, seg, seed=0, strong=1.0):
    """The mixer's arrays behind its convolution and its gates' matmuls
    (a head's [q | k | v] side by side), zero where the row is padding;
    decays of -0.001 to -1.6 a token, times ``strong``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = 2.0 * jax.random.normal(ks[0], (R, T, H * 3 * D))
    a = jax.random.normal(ks[1], (R, T, H * D))
    gate = 2.0 * jax.random.normal(ks[2], (R, T, H * D))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (R, T, H)))
    p = kda.init_kda_params(KDAConfig(n_heads=H, head_dim=D), 1, 8, ks[4],
                            jnp.float32)
    A_log = p["kda_A_log"][0] + np.log(strong)
    norm = 1.0 + 0.3 * jax.random.normal(ks[5], (D,))
    live = (seg > 0)[..., None]
    return (x * live, a * live, gate * live, beta, A_log,
            p["kda_dt_bias"][0], norm)


def xla_ends(x, a, gate, beta, A_log, dt_bias, norm, seg):
    """``kda_mixer``'s XLA text between ``kda_conv`` and ``kda_out_proj``,
    all heads at once, in the operands' dtype."""
    R, T, _ = x.shape
    H = beta.shape[2]
    cd, f32 = x.dtype, jnp.float32
    q, k, v = (jax.nn.silu(x).reshape(R, T, H, 3, D)[:, :, :, i]
               for i in range(3))
    g = -jnp.exp(A_log.astype(f32))[:, None] * jax.nn.softplus(
        a.astype(f32).reshape(R, T, H, D) + dt_bias.astype(f32).reshape(H, D))
    q = (gdn.l2_normalize(q) * D ** -0.5).astype(cd)
    k = gdn.l2_normalize(k).astype(cd)
    o = kda.channel_decay_rule(q, k, v, g, beta, seg, Q, "xla")
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + EPS)
    y = (o * norm.astype(f32)).astype(cd)
    return (y.reshape(R, T, H * D).astype(f32)
            * jax.nn.sigmoid(gate.astype(f32))).astype(cd)


def fused_ends(*ops_and_seg):
    *ops, seg = ops_and_seg
    return kda.rule_with_ends(*ops, seg, Q, EPS, "pallas_interpret")


def ends_grads(fn, ops, seg, dtype):
    """(y, the gradients of Σ sin(y) over the row's tokens) in float32."""
    ops = tuple(o.astype(dtype) if i < 3 else o for i, o in enumerate(ops))

    def loss(*xs):
        y = fn(*xs, seg).astype(jnp.float32)
        return jnp.sum(jnp.sin(y) * (seg > 0)[..., None]), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=range(7), has_aux=True))(*ops)
    return [np.asarray(v, np.float32) for v in (y, *grads)]


def ends_layout(which):
    """(rows, tokens, heads, segment ids) of a case."""
    if which == "packed":  # a document's start inside the second chunk
        return 1, 4 * Q, 2, layout("second", 4 * Q)
    T = 11 * Q  # 8 chunks a grid step: a last step of 3
    seg = np.zeros((2, T), np.int32)
    seg[0, :8 * Q], seg[0, 8 * Q:9 * Q + 20], seg[0, 9 * Q + 20:] = 1, 2, 3
    seg[1, :300], seg[1, 300:T - 40] = 1, 2
    if which == "short":
        return 1, T, 1, jnp.asarray(seg[:1])
    return 2, T, 1, jnp.asarray(seg)


@pytest.mark.parametrize("which,strong", [
    ("packed", 1.0), ("short", 1.0), ("rows", 1.0), ("rows", 200.0)])
def test_the_mixers_entry_against_its_xla_text(which, strong):
    """The fused entry in float32: y and the gradient of each of its
    seven operands (the parameters' through ``mixer_parameters``) equal
    the XLA text's, the short last step and the strongest decays
    included."""
    R, T, H, seg = ends_layout(which)
    ops = ends_inputs(R, T, H, seg, seed=20, strong=strong)
    before = dict(kernel.step_counts())
    got = ends_grads(fused_ends, ops, seg, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ends_grads(xla_ends, ops, seg, jnp.float32)
    for name, x, w in zip(("y",) + ENDS, got, want):
        assert np.isfinite(x).all(), name
        scale = max(float(np.max(np.abs(w))), 1.0)
        assert float(np.max(np.abs(x - w))) < 5e-5 * scale, name
    after = kernel.step_counts()
    ran = {k: n - before.get(k, 0) for k, n in after.items()
           if n > before.get(k, 0)}
    nc = min(8, T // Q)
    assert ran == {(R, T, H, nc, nc): 2}, ran


def test_the_mixers_entry_in_bfloat16():
    """bfloat16 operands: y and the operands' cotangents leave in
    bfloat16, β's and the parameters' in float32, and every one is as
    close to the float32 text as the XLA text in bfloat16 is (max |a − b| /
    max |b|; the two forms read 0.003-0.03 on these)."""
    R, T, H, seg = ends_layout("rows")
    ops = ends_inputs(R, T, H, seg, seed=21)
    with jax.default_matmul_precision("highest"):
        want = ends_grads(xla_ends, ops, seg, jnp.float32)
    got = ends_grads(fused_ends, ops, seg, jnp.bfloat16)
    xla = ends_grads(xla_ends, ops, seg, jnp.bfloat16)
    for name, x, other, w in zip(("y",) + ENDS, got, xla, want):
        assert np.isfinite(x).all(), name
        far = np.max(np.abs(x - w)) / np.max(np.abs(w))
        assert far < max(0.05, 2 * np.max(np.abs(other - w))
                         / np.max(np.abs(w))), (name, far)
    cot = jax.grad(lambda *xs: jnp.sum(fused_ends(*xs, seg).astype(
        jnp.float32)), argnums=range(7))(
            *(o.astype(jnp.bfloat16) for o in ops[:3]), *ops[3:])
    assert [c.dtype for c in cot] == [jnp.bfloat16] * 3 + [jnp.float32] * 4


def test_a_token_of_zeros_norms_to_zero_and_writes_nothing():
    """A row that is no whole number of chunks (the entry pads it with
    zeros) with trailing padding: the padded tokens' y is exactly 0 and
    the real ones' is the XLA text's."""
    T = 2 * Q + 31
    seg = jnp.ones((1, T), jnp.int32).at[0, T - 17:].set(0)
    ops = ends_inputs(1, T, 1, seg, seed=22)
    got = fused_ends(*ops, seg)
    assert got.shape == (1, T, D)
    assert float(jnp.max(jnp.abs(got[0, T - 17:]))) == 0.0
    want = xla_ends(*ops, seg)
    assert float(jnp.max(jnp.abs((got - want) * (seg > 0)[..., None]))) < 5e-6


def test_where_the_mixer_runs_its_ends():
    """``kda_mixer`` on the kernel path runs the fused entry, all heads
    at once (``mixer_norm_counts`` ``kernel``, ONE kernel call at the
    model's heads); on the XLA path the grouped text (``xla``)."""
    cfg = KDAConfig(n_heads=2, head_dim=D)
    lp = jax.tree.map(lambda a: a[0], kda.init_kda_params(
        cfg, 1, 16, jax.random.PRNGKey(0), jnp.float32))
    u = jax.random.normal(jax.random.PRNGKey(1), (1, Q, 16))
    norms, steps = dict(kda.mixer_norm_counts()), dict(kernel.step_counts())
    y = kda.kda_mixer(u, lp, cfg, EPS, None, "pallas_interpret")
    y2 = kda.kda_mixer(u, lp, cfg, EPS, None, "reference")
    np.testing.assert_allclose(y, y2, rtol=1e-4, atol=1e-6)
    after = kda.mixer_norm_counts()
    assert {k: after[k] - norms.get(k, 0) for k in after} == {
        "kernel": 1, "xla": 1}
    assert kernel.step_counts()[(1, Q, 2, 1, 1)] == steps.get(
        (1, Q, 2, 1, 1), 0) + 1
    assert 0.0 < kda.norms_in_kernel_frac() < 1.0


def test_the_scalar_decay_rules_traced_program_is_unchanged():
    """A ``gdn`` model's rule (one decay a value head) traces to the
    equations it traced to before the decay could be a channel's (the
    parent's tree gives the same jaxpr text for these shapes: 91
    equations)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    T, G, H, d = 128, 2, 4, 16
    args = (jax.random.normal(ks[0], (1, T, G, d)),
            jax.random.normal(ks[1], (1, T, G, d)),
            jax.random.normal(ks[2], (1, T, H, d)),
            -jax.nn.softplus(jax.random.normal(ks[3], (1, T, H))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (1, T, H))),
            jnp.ones((1, T), jnp.int32))
    jaxpr = jax.make_jaxpr(
        lambda *a: gdn.gated_delta_rule(*a, 64, "reference"))(*args)
    assert len(jaxpr.jaxpr.eqns) == 91
