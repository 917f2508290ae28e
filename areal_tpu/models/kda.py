"""The Kimi Delta Attention mixer of a ``kda`` block (kimi_linear's
``kda_layers``) — packed rows, the delta rule with a decay a key CHANNEL in
chunks: on a TPU, at heads of 128, the Pallas kernel pair of
``ops/pallas/kda_rule.py`` (:func:`_rule_impl` chooses, by what it can see;
:func:`rule_impl_counts` says what it chose); elsewhere the XLA form,
:func:`_rule_xla` — ``gdn.gated_delta_rule``'s inverse and chunk-to-chunk
scan under blocks of its own (a decay a channel does not leave the chunk's
products as a factor).

Where the kernels run, the mixer between its convolution and its
out-projection IS the kernel pair (:func:`rule_with_ends`, all heads at
once, no kernel run twice: :func:`_mixer_in_kernels`): the SiLU, β's
products, both l2 norms, the decay's activation and the gated norm run
inside ``kda_rule_fwd`` / ``kda_rule_bwd`` on a head's 128 lanes, and XLA
holds no array by head, no float32 g or o (:func:`mixer_norm_counts` says
which mixers did). Elsewhere the XLA text below, a group of heads at a
time under a checkpoint of its own. :func:`channel_decay_rule` is the RULE
alone either way (l2-normed operands in, float32 o out): what the tests
and the benchmark's ``rule_error`` hold to the token scan.

One mixer, ``u = norm(h)`` [B, T, D] (models/transformer.py adds the
residual and the block's FFN); ``H`` heads, key and value both ``dh``; the
two gates come through a bottleneck of ``gate_rank``:

    [q | k | v] = u · kda_qkv                    H·dh each
    [b | f | z] = u · kda_gates_a                H | gate_rank | gate_rank
    [q | k | v] = silu(conv1d([q | k | v]))      depthwise, causal, K taps, no bias
    β = sigmoid(b)                               a head
    g = -exp(A_log) ⊙ softplus(f · kda_f_b + dt_bias)     a key CHANNEL (A_log a head)
    q̂ = q · rsqrt(Σ q² + 1e-6) · dh^-1/2;  k̂ = k · rsqrt(Σ k² + 1e-6)
    S ← Diag(e^{g_t}) S;  δ_t = β_t (v_t − Sᵀ k̂_t);  S ← S + k̂_t δ_tᵀ;  o_t = Sᵀ q̂_t
    y = rms(o) · kda_norm ⊙ sigmoid(z · kda_g_b)          over each head's dh channels
    out = y · kda_out

The three projections ride ONE matrix, as their convolutions one, and the
three narrow ones another; models/hf.py splits them into the publisher's
names (``q_proj`` .. ``g_b_proj``) and back. Gates, decays and the carried
state are float32. Packed rows as models/gdn.py: the state is ZERO before a
document's first token and a convolution tap that would read across a
document's start reads 0, both masks on what is multiplied.

Device scopes (base/telemetry.KDA_SCOPES): ``kda_in_proj``, ``kda_conv``,
``kda_gates``, ``kda_rule``, ``kda_gate_norm``, ``kda_out_proj`` (on the
kernel path nothing runs under ``kda_gates`` / ``kda_gate_norm``: the
gates' two expansions are matmuls of ``kda_in_proj``, the rest is inside
the kernels under ``kda_rule``).
:func:`geometry_counts` is the trace-time count of the rules a compiled
program holds.
"""

from __future__ import annotations

import collections
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.models.config import KDAConfig
from areal_tpu.models.gdn import (
    _HEAD_GROUPS,
    L2_EPS,
    _carry_states,
    _unit_lower_inverse,
    l2_normalize,
)
from areal_tpu.models.ssm import _masked_exp, causal_conv

# Why a model with these blocks is not decoded here (models/generate.py
# and transformer.forward refuse it by this name).
DECODE_REFUSAL = (
    "channel_decay_rule_decode_state: a delta-rule block with a decay a key "
    "channel decodes from a recurrent matrix a head (S [dk, dv]) and its "
    "three convolutions' last taps, which no cache here holds")

# The range ``init_kda_params`` draws a channel's step from.
DT_MIN, DT_MAX = 1e-3, 0.1

# Rules per compiled program, counted where they are traced: {(rows,
# length, chunk, heads, head width, gate rank): calls}.
_GEOMETRY: collections.Counter = collections.Counter()
# Which form each traced rule took: "pallas" | "pallas_interpret" | "xla".
_RULE_IMPL: collections.Counter = collections.Counter()


def geometry_counts() -> Dict[Tuple[int, int, int, int, int, int], int]:
    return dict(_GEOMETRY)


def rule_impl_counts() -> Dict[str, int]:
    return dict(_RULE_IMPL)


def rule_kernel_frac() -> Optional[float]:
    """Of the rules traced so far, the share that took the Pallas kernels;
    None before the first trace."""
    total = sum(_RULE_IMPL.values())
    return (total - _RULE_IMPL["xla"]) / total if total else None


# Where each traced mixer's per-head work ran (:func:`kda_mixer`): "kernel"
# — β, the l2 norms, the decay's activation and the gated norm inside the
# rule's kernels, all heads at once (:func:`rule_with_ends`) — or "xla".
_MIXER_NORMS: collections.Counter = collections.Counter()


def mixer_norm_counts() -> Dict[str, int]:
    return dict(_MIXER_NORMS)


def norms_in_kernel_frac() -> Optional[float]:
    """Of the mixers traced so far, the share whose ends ran inside the
    rule's kernels; None before the first trace."""
    total = sum(_MIXER_NORMS.values())
    return _MIXER_NORMS["kernel"] / total if total else None


def param_shapes(kda: KDAConfig, hidden_dim: int,
                 ) -> Dict[str, Tuple[int, ...]]:
    """{name: shape} of one mixer (the norm in front is the block's)."""
    width = kda.n_heads * kda.head_dim
    return {
        "kda_qkv": (hidden_dim, 3 * width),
        "kda_gates_a": (hidden_dim, kda.gates_a_dim),
        "kda_conv": (kda.conv_kernel, 3 * width),
        "kda_f_b": (kda.gate_rank, width),
        "kda_g_b": (kda.gate_rank, width),
        "kda_A_log": (kda.n_heads,),
        "kda_dt_bias": (width,),
        "kda_norm": (kda.head_dim,),
        "kda_out": (width, hidden_dim),
    }


def init_kda_params(kda: KDAConfig, n: int, hidden_dim: int, key: jax.Array,
                    dtype) -> Dict[str, jnp.ndarray]:
    """``n`` stacked mixers: ``A_log = log U(1, 16)`` a head; ``dt_bias``
    the inverse softplus of a step drawn log-uniform in [``DT_MIN``,
    ``DT_MAX``] a CHANNEL (Mamba's draw: a mixer's channels then forget at
    rates two orders apart, -g of 1e-3 to 1.6 a token, and a head's
    channels do not share one); the convolutions U(±1/2), the gated norm's
    weight 1, every matrix N(0, 0.02)."""
    shapes = param_shapes(kda, hidden_dim)
    ks = dict(zip(shapes, jax.random.split(key, len(shapes))))
    bound = kda.conv_kernel ** -0.5
    out = {name: (jax.random.normal(ks[name], (n,) + shape) * 0.02
                  ).astype(dtype)
           for name, shape in shapes.items() if len(shape) == 2}
    out["kda_conv"] = jax.random.uniform(
        ks["kda_conv"], (n,) + shapes["kda_conv"], minval=-bound,
        maxval=bound).astype(dtype)
    out["kda_A_log"] = jnp.log(jax.random.uniform(
        ks["kda_A_log"], (n, kda.n_heads), minval=1.0, maxval=16.0)
    ).astype(dtype)
    dt = jnp.exp(jax.random.uniform(
        ks["kda_dt_bias"], (n,) + shapes["kda_dt_bias"],
        minval=math.log(DT_MIN), maxval=math.log(DT_MAX)))
    out["kda_dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    out["kda_norm"] = jnp.ones((n, kda.head_dim), dtype)
    return out


def kda_param_count(kda: KDAConfig, hidden_dim: int) -> int:
    """Parameters of one mixer, the norm in front not counted."""
    return sum(math.prod(s) for s in param_shapes(kda, hidden_dim).values())


def matmul_widths(kda: KDAConfig) -> int:
    """Widths of the mixer's matmul outputs that its backward reads (the
    out-projection's is the block's): both in-projections and both gates'
    expansions."""
    return 5 * kda.n_heads * kda.head_dim + kda.gates_a_dim


def _rule_impl(impl: str, kda: KDAConfig, dtype) -> str:
    """"pallas" | "pallas_interpret" | "xla" (as ``gdn._rule_impl``)."""
    from areal_tpu.ops.attention import _wants_kernel
    from areal_tpu.ops.pallas import kda_rule as kernel

    if not kernel.supported(kda.chunk_size, kda.n_heads, kda.head_dim,
                            kda.head_dim, dtype):
        return "xla"
    if impl == "pallas_interpret":
        return impl
    return "pallas" if _wants_kernel(impl) and kernel.fits_device() else "xla"


def channel_decay_rule(q: jnp.ndarray,  # [B, T, H, dk], l2-normed and scaled
                       k: jnp.ndarray,  # [B, T, H, dk], l2-normed
                       v: jnp.ndarray,  # [B, T, H, dv]
                       g: jnp.ndarray,  # [B, T, H, dk] float32 log-decay, <= 0
                       beta: jnp.ndarray,  # [B, T, H] float32 in (0, 1)
                       seg: jnp.ndarray,  # [B, T] int; 0 = padding
                       chunk: int, how: str) -> jnp.ndarray:
    """The rule of the module's docstring in chunks of ``chunk`` tokens.
    Returns o [B, T, H, dv] float32. ``how`` as :func:`_rule_impl`
    answered: the kernels, or the XLA form."""
    _RULE_IMPL[how] += 1
    if how == "xla":
        return _rule_xla(q, k, v, g, beta, seg, chunk)
    T = q.shape[1]
    cd, f32 = v.dtype, jnp.float32
    b = beta.astype(f32)[..., None]
    # β rides k and v into the kernels (its gradient is these products')
    kb = (k.astype(f32) * b).astype(cd)
    vb = (v.astype(f32) * b).astype(cd)
    q, k, g = q.astype(cd), k.astype(cd), g.astype(f32)
    pad = -T % chunk
    if pad:  # a padded token: its row's padding, kb = vb = 0 writes nothing
        q, k, kb, vb, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                           for a in (q, k, kb, vb, g))
        seg = jnp.pad(seg, ((0, 0), (0, pad)))
    return _rule_kernel(q, k, kb, vb, g, seg, chunk, how)[:, :T]


def _rule_xla(q, k, v, g, beta, seg, chunk: int) -> jnp.ndarray:
    """The XLA form of :func:`channel_decay_rule`: ``gdn.gated_delta_rule``'s
    chunks with ``A_ij = β_i Σ_d k_id k_jd e^{c_id − c_jd}`` — the decay no
    longer leaves the product as a factor, and ``(k_i e^{c_i})·(k_j
    e^{−c_j})`` would take a positive exponent. Every exponent stays that of a
    NON-POSITIVE difference by a reference inside the chunk, by sub-blocks
    of ``SUB`` tokens: a sub-block's rows against the EARLIER sub-blocks'
    columns are one product of ``x_i e^{c_i − c_ref}`` and ``k_j e^{c_ref −
    c_j}``, ``c_ref`` the sub-block's first row (``j < ref <= i``); inside
    a sub-block the sum over channels is made element by element. Then
    the scalar rule's ``M``, ``U``, ``W`` with ``e``, ``t`` and ``κ`` a
    channel, its inverse and its scan (``gdn._unit_lower_inverse``,
    ``gdn._carry_states``: both exist once)."""
    from areal_tpu.ops.pallas.kda_rule import SUB

    B_, T, G, dk = q.shape
    H, dv = v.shape[2:]
    r = H // G
    Q = chunk
    pad = -T % Q
    if pad:  # a padded token is its row's padding: beta = 0 writes nothing
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
        seg = jnp.pad(seg, ((0, 0), (0, pad)))
    padded = T + pad
    sub = math.gcd(Q, SUB)
    nb = Q // sub
    Z = padded // Q
    cd, f32 = v.dtype, jnp.float32
    seg = seg.reshape(B_, Z, Q)
    # q, k [B, Z, G, 1, Q, dk]; v [B, Z, G, r, Q, dv]; beta [B, Z, G, r, Q];
    # c [B, Z, G, r, Q, dk]
    qv = jnp.moveaxis(q.astype(f32).reshape(B_, Z, Q, G, dk), 2, 3)[:, :, :, None]
    kv = jnp.moveaxis(k.astype(f32).reshape(B_, Z, Q, G, dk), 2, 3)[:, :, :, None]
    v = jnp.moveaxis(v.reshape(B_, Z, Q, G, r, dv), 2, 4)
    beta = jnp.moveaxis(beta.astype(f32).reshape(B_, Z, Q, G, r), 2, -1)
    c = jnp.cumsum(jnp.moveaxis(
        g.astype(f32).reshape(B_, Z, Q, G, r, dk), 2, 4), axis=-2)

    def blocks(a):  # [..., Q, w] -> [..., sub-blocks, sub, w]
        return a.reshape(a.shape[:-2] + (nb, sub, a.shape[-1]))

    # ---- a sub-block's rows against the earlier sub-blocks' columns
    cb = blocks(c)
    ref = cb[..., :1, :]  # [.., nb, 1, dk]: each sub-block's first row
    up = jnp.exp(cb - ref)
    earlier = (jnp.arange(Q)[None, :] < (jnp.arange(nb) * sub)[:, None])
    down = (kv[..., None, :, :] * _masked_exp(
        earlier[:, :, None], ref - c[..., None, :, :])).astype(cd)
    kk, qk = (jnp.einsum(
        "...nid,...njd->...nij", (blocks(a) * up).astype(cd), down,
        preferred_element_type=f32).reshape(a.shape[:3] + (r, Q, Q))
        for a in (kv, qv))
    # ---- inside a sub-block, element by element
    tri = jnp.tril(jnp.ones((sub, sub), bool))
    inner = _masked_exp(tri[:, :, None],
                        cb[..., :, None, :] - cb[..., None, :, :])
    eye = jnp.eye(nb, dtype=f32)[:, None, :, None]

    def diagonal(a):  # -> [.., Q, Q], zero outside the diagonal sub-blocks
        d = jnp.einsum("...nid,...njd,...nijd->...nij", blocks(a), blocks(kv),
                       inner)
        return (d[..., :, :, None, :] * eye).reshape(d.shape[:-3] + (Q, Q))

    same = (seg[:, :, :, None] == seg[:, :, None, :])[:, :, None, None]
    low = jnp.tril(jnp.ones((Q, Q), bool))
    A = jnp.where(same & jnp.tril(low, -1),
                  beta[..., None] * (kk + diagonal(kv)), 0.0)
    P = jnp.where(same & low, qk + diagonal(qv), 0.0).astype(cd)
    M = _unit_lower_inverse(A).astype(cd)
    # ---- what reads the entering state, and what the chunk leaves
    last = seg[:, :, -1]
    prev = jnp.pad(last, ((0, 0), (1, 0)), constant_values=-1)[:, :Z]
    enters = _masked_exp(
        (seg == prev[..., None])[:, :, None, None, :, None], c)
    to_end = _masked_exp(
        (seg == last[..., None])[:, :, None, None, :, None],
        c[..., -1:, :] - c)
    keeps = _masked_exp((last == prev)[:, :, None, None, None],
                        c[..., -1, :])  # [B, Z, G, r, dk]
    U = jnp.einsum("bzgrij,bzgrjv->bzgriv", M,
                   (v.astype(f32) * beta[..., None]).astype(cd),
                   preferred_element_type=f32)
    W = jnp.einsum("bzgrij,bzgrjk->bzgrik", M,
                   (kv * beta[..., None] * enters).astype(cd),
                   preferred_element_type=f32).astype(cd)
    return _carry_states(U, W, (qv * enters).astype(cd),
                         (kv * to_end).astype(cd), P, keeps, T)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _rule_kernel(q, k, kb, vb, g, seg, chunk, how):
    from areal_tpu.ops.pallas import kda_rule as kernel

    with jax.named_scope("kda_rule"):
        return kernel.rule_fwd(q, k, kb, vb, g, seg, chunk,
                               interpret=how == "pallas_interpret")[0]


def _rule_kernel_fwd(q, k, kb, vb, g, seg, chunk, how):
    from areal_tpu.ops.pallas import kda_rule as kernel

    with jax.named_scope("kda_rule"):
        o, states = kernel.rule_fwd(q, k, kb, vb, g, seg, chunk, keep=True,
                                    interpret=how == "pallas_interpret")
    return o, (q, k, kb, vb, g, seg, states)


def _rule_kernel_bwd(chunk, how, res, do):
    from areal_tpu.ops.pallas import kda_rule as kernel

    q, k, kb, vb, g, seg, states = res
    with jax.named_scope("kda_rule"):
        return kernel.rule_bwd(q, k, kb, vb, g, seg, states, do, chunk,
                               interpret=how == "pallas_interpret") + (None,)


_rule_kernel.defvjp(_rule_kernel_fwd, _rule_kernel_bwd)


def rule_with_ends(x: jnp.ndarray,  # [B, T, H · 3 dh]: a head's [q | k | v]
                   a: jnp.ndarray,  # [B, T, H · dh]: f · kda_f_b
                   gate: jnp.ndarray,  # [B, T, H · dh]: z · kda_g_b
                   beta: jnp.ndarray,  # [B, T, H] float32
                   A_log: jnp.ndarray, dt_bias: jnp.ndarray,  # [H], [H · dh]
                   norm: jnp.ndarray,  # [dh] the gated norm's weight
                   seg: jnp.ndarray, chunk: int, eps: float,
                   how: str) -> jnp.ndarray:
    """The mixer between its convolution and its out-projection as the ONE
    kernel pair (``kda_rule.mixer_fwd`` / ``mixer_bwd``): the SiLU behind
    the convolution, the two l2 norms, the decay's activation, β's two
    products, the rule, and ``y = rms(o) · norm ⊙ sigmoid(gate)`` [B, T, H ·
    dh] in the compute dtype — the arithmetic and the rounding points of
    :func:`kda_mixer`'s XLA text with no array by head, no float32 g or o
    and no ``kb`` / ``vb`` outside the kernels. ``how``: "pallas" |
    "pallas_interpret", as :func:`_rule_impl` answered."""
    return _ends((x, a, gate), _as_given, beta, A_log, dt_bias, norm, seg,
                 chunk, eps, how)


def _ends(ops, make, beta, A_log, dt_bias, norm, seg, chunk: int, eps: float,
          how: str) -> jnp.ndarray:
    """:func:`rule_with_ends` on the x, a and gate that ``make(*ops, seg)``
    makes — in the forward and AGAIN in the backward: what is kept between
    the passes is ``ops`` (:func:`_conv_and_gates`)."""
    from areal_tpu.ops.pallas import kda_rule as kernel

    _RULE_IMPL[how] += 1
    with jax.named_scope("kda_rule"):
        par = kernel.mixer_parameters(A_log, dt_bias, norm)
    y = _ends_kernel(ops, beta.astype(jnp.float32), par, seg, make, chunk,
                     (L2_EPS, float(eps)), how)
    return y[:, :seg.shape[1]]


def _as_given(x, a, gate, seg):
    return x, a, gate


def _kernel_operands(make, ops, beta, seg, chunk: int):
    """(x, a, gate, β) as the kernels take them: made of ``ops``, the row a
    whole number of chunks (a token of zeros norms to zero and writes
    nothing); and the row's segment ids likewise."""
    x, a, gate = make(*ops, seg)
    pad = -x.shape[1] % chunk
    if pad:
        x, a, gate, beta = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                            for v in (x, a, gate, beta))
        seg = jnp.pad(seg, ((0, 0), (0, pad)))
    return (x, a, gate, beta), seg


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _ends_kernel(ops, beta, par, seg, make, chunk, eps, how):
    from areal_tpu.ops.pallas import kda_rule as kernel

    made, segs = _kernel_operands(make, ops, beta, seg, chunk)
    with jax.named_scope("kda_rule"):
        return kernel.mixer_fwd(*made, par, segs, chunk, eps,
                                interpret=how == "pallas_interpret")[0]


def _ends_kernel_fwd(ops, beta, par, seg, make, chunk, eps, how):
    from areal_tpu.ops.pallas import kda_rule as kernel

    made, segs = _kernel_operands(make, ops, beta, seg, chunk)
    with jax.named_scope("kda_rule"):
        y, states = kernel.mixer_fwd(*made, par, segs, chunk, eps, keep=True,
                                     interpret=how == "pallas_interpret")
    return y, (ops, beta, par, seg, states)


def _ends_kernel_bwd(make, chunk, eps, how, res, dy):
    from areal_tpu.ops.pallas import kda_rule as kernel

    ops, beta, par, seg, states = res
    # as ``jax.checkpoint`` does: made again only once dy is there (else
    # XLA finds the forward's own and keeps them alive in between)
    ops, dy = jax.lax.optimization_barrier((ops, dy))
    made, pull, segs = jax.vjp(
        lambda ops, beta: _kernel_operands(make, ops, beta, seg, chunk),
        ops, beta, has_aux=True)
    with jax.named_scope("kda_rule"):
        *d_made, d_par = kernel.mixer_bwd(
            *made, par, segs, states, dy, chunk, eps,
            interpret=how == "pallas_interpret")
    return pull(tuple(d_made)) + (d_par, None)


_ends_kernel.defvjp(_ends_kernel_fwd, _ends_kernel_bwd)


def _heads_together(w: jnp.ndarray, H: int) -> jnp.ndarray:
    """A WEIGHT's columns [.., q | k | v] (H · dh each) -> a head's [q | k |
    v] side by side, [.., H · 3 dh]: what it multiplies then comes out in
    the kernels' layout (a head's three 128-lane blocks are ONE block of
    an operand and of its cotangent), and no activation is moved."""
    by = w.reshape(w.shape[:-1] + (3, H, w.shape[-1] // (3 * H)))
    return jnp.swapaxes(by, -3, -2).reshape(w.shape)


def _conv_and_gates(x, conv, f, z, f_b, g_b, seg):
    """The kernels' three wide operands from what the in-projections leave:
    the convolution over all of [q | k | v] (its SiLU is the kernels') and
    the two gates' expansions. :func:`_ends` runs this in BOTH passes: of
    a mixer's [T, 5 · H · dh] of kernel operands only the projection ([T, 3
    · H · dh], which the convolution's backward reads anyway) is alive
    while the block's FFN runs its backward — with the operands kept the 2
    x 7,552 grid's carried grad program booked 6.47 GB where the head
    groups' checkpoint had it at 5.58, made again 5.61 (PERF.md §6, PR
    65)."""
    with jax.named_scope("kda_conv"):
        x = causal_conv(x, conv, 0.0, seg)
    with jax.named_scope("kda_in_proj"):
        return x, f @ f_b, z @ g_b


def _mixer_in_kernels(u, lp, kda: KDAConfig, eps: float, seg, how: str):
    """:func:`kda_mixer` where the rule runs as the kernel pair: all heads
    at once, no kernel run twice and nothing by head outside the kernels —
    two in-projections, ONE convolution over [B, T, 3 · H · dh], the two
    gates' expansions as two matmuls, :func:`rule_with_ends`' kernels, the
    out-projection. Which kernel a block's backward re-runs is its remat
    entry's to say (the head groups of the XLA form bound float32 arrays
    that do not exist here; of XLA's own only the convolution and the
    gates' expansions run once more: :func:`_conv_and_gates`)."""
    H, rank = kda.n_heads, kda.gate_rank
    with jax.named_scope("kda_in_proj"):
        x = u @ _heads_together(lp["kda_qkv"], H)
        b, f, z = jnp.split(u @ lp["kda_gates_a"], [H, H + rank], axis=-1)
    with jax.named_scope("kda_rule"):
        beta = jax.nn.sigmoid(b.astype(jnp.float32))
    y = _ends((x, _heads_together(lp["kda_conv"], H), f, z, lp["kda_f_b"],
               lp["kda_g_b"]), _conv_and_gates, beta, lp["kda_A_log"],
              lp["kda_dt_bias"], lp["kda_norm"], seg, kda.chunk_size, eps,
              how)
    with jax.named_scope("kda_out_proj"):
        return y @ lp["kda_out"]


def kda_mixer(u: jnp.ndarray,  # [B, T, D] the normed residual stream
              lp: Dict[str, jnp.ndarray],  # this layer's parameters
              kda: KDAConfig, eps: float,
              segment_ids: Optional[jnp.ndarray],  # None = one document a row
              impl: str = "auto",  # the model's ``attn_impl``
              ) -> jnp.ndarray:
    B_, T, _ = u.shape
    H, dh, rank = kda.n_heads, kda.head_dim, kda.gate_rank
    width = H * dh
    f32 = jnp.float32
    seg = (jnp.ones((B_, T), jnp.int32) if segment_ids is None
           else segment_ids)
    _GEOMETRY[(B_, T, kda.chunk_size, H, dh, rank)] += 1
    how = _rule_impl(impl, kda, u.dtype)
    _MIXER_NORMS["xla" if how == "xla" else "kernel"] += 1
    if how != "xla":
        return _mixer_in_kernels(u, lp, kda, eps, seg, how)
    # The XLA form's per-head work runs a group of heads at a time, each
    # under its own checkpoint (models/gdn.py, ``_HEAD_GROUPS``): a decay a
    # CHANNEL makes g, its pre-activation, the float32 o and the rule's
    # operands and cotangents [T, H · dh] arrays each, 3.6 GB of them a
    # mixer at a 16,384-token row where all 32 heads run at once (PERF.md
    # §6, PR 63); a group at a time they are a quarter, at the price of one
    # more forward of the group in the backward pass.
    n = math.gcd(H, _HEAD_GROUPS)
    Hn = H // n

    def by_group(a, parts: int = 1):
        """[..., parts · n · w] -> [n, ..., parts · w]: each part's heads
        of a group side by side."""
        w = a.shape[-1] // (parts * n)
        a = a.reshape(a.shape[:-1] + (parts, n, w))
        return jnp.moveaxis(a, -2, 0).reshape((n,) + a.shape[:-3]
                                              + (parts * w,))

    with jax.named_scope("kda_in_proj"):
        qkv = u @ lp["kda_qkv"]
        b, f, z = jnp.split(u @ lp["kda_gates_a"], [H, H + rank], axis=-1)
        xs = (by_group(qkv, 3), by_group(lp["kda_conv"], 3), by_group(b),
              by_group(lp["kda_f_b"]), by_group(lp["kda_g_b"]),
              by_group(lp["kda_A_log"]), by_group(lp["kda_dt_bias"]))

    def heads(xs):
        qkv, conv, b, f_b, g_b, A_log, dt_bias = xs
        with jax.named_scope("kda_conv"):
            q, k, v = (a.reshape(B_, T, Hn, dh) for a in jnp.split(
                jax.nn.silu(causal_conv(qkv, conv, 0.0, seg)), 3, axis=-1))
        with jax.named_scope("kda_gates"):
            beta = jax.nn.sigmoid(b.astype(f32))
            g = -jnp.exp(A_log.astype(f32))[:, None] * jax.nn.softplus(
                (f @ f_b).astype(f32).reshape(B_, T, Hn, dh)
                + dt_bias.astype(f32).reshape(Hn, dh))
            q = (l2_normalize(q) * dh ** -0.5).astype(u.dtype)
            k = l2_normalize(k).astype(u.dtype)
        with jax.named_scope("kda_rule"):
            o = channel_decay_rule(q, k, v, g, beta, seg, kda.chunk_size, how)
        with jax.named_scope("kda_gate_norm"):
            o = o * jax.lax.rsqrt(
                jnp.mean(o * o, axis=-1, keepdims=True) + eps)
            y = (o * lp["kda_norm"].astype(f32)).astype(u.dtype)
            return (y.reshape(B_, T, Hn * dh).astype(f32)
                    * jax.nn.sigmoid((z @ g_b).astype(f32))).astype(u.dtype)

    y = jax.lax.map(jax.checkpoint(heads), xs)
    with jax.named_scope("kda_out_proj"):
        return jnp.moveaxis(y, 0, 2).reshape(B_, T, width) @ lp["kda_out"]
