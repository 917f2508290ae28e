"""Mixture-of-Experts layer — TPU-first, sort-based grouped expert compute.

Parity target: ``realhf/impl/model/modules/moe/`` — ``TopKRouter``
(router.py:24; aux-loss load balancing :78, z-loss :146, input jitter
:170), token dispatcher (token_dispatcher.py: permute + capacity drop) and
``GroupedMLP`` (experts.py:99, grouped_gemm). What runs here:

 - **grouped dispatch** (the default on one shard): flatten the (token,
   choice) entries, stable-argsort them by expert id, and run the expert
   MLPs as grouped GEMMs over the contiguous per-expert segments
   (``jax.lax.ragged_dot``). Work scales with the rows actually routed;
   there is no ``[E, C]`` capacity buffer on the compute path;
 - **capacity or none** (``MoEConfig.capacity_factor``): a number keeps
   the Switch-style drop of the reference (an entry past its expert's
   ``capacity`` slots contributes nothing; priority = token order, then
   choice order); ``None`` is a dropless model (olmoe): every chosen
   (token, expert) pair is computed, ``dropped_frac`` is 0 by
   construction and no path builds a keep mask;
 - **expert parallelism** over the mesh's "ep" axis (parallel/mesh.py),
   whose shards own ``E/ep`` experts each (parallel/sharding.py) and a
   slice of the batch: :func:`_dispatch_ep` all-gathers a micro-batch's
   tokens and routing over "ep"; each shard, one source shard's tokens at
   a time, sorts the entries that chose ITS experts to the front and runs
   the grouped GEMMs over them, and the gate-weighted per-token sums are
   reduce-scattered back. Shapes are static under any routing skew (the
   row bound is the worst case, every entry local) and nothing of shape
   ``[N, E, C]`` is built. With a capacity the drop is decided at the
   shard boundary (per source shard); a dropless model drops nothing;
 - **a share** (``MoEConfig.router_experts`` / ``first_expert``): one
   rank's part of such a group run alone, on a mesh with no "ep" axis.
   The router scores all the published experts and normalises the gates
   over all the chosen ones; the held experts' rows are sorted to the
   front exactly as an ep shard sorts its own (:func:`_held_eid`), and a
   pair that chose an expert held elsewhere is the sentinel: it adds
   nothing, and no collective or stand-in for one is set up;
 - the GShard one-hot-einsum dispatch is kept as the parity ORACLE behind
   ``AREAL_MOE_DISPATCH=einsum`` (same contract as ``AREAL_RING_SCHEDULE``
   / ``AREAL_PP_SCHEDULE``); it shares the router and the drop policy;
 - sinkhorn routing is not implemented (the reference defaults to aux-loss
   balancing for its shipped configs).

Weights per layer (stacked on the leading layer axis by the transformer):
``router [D, E]``, ``e_gate/e_up [E, D, F]``, ``e_down [E, F, D]``, and an
optional always-on shared expert ``s_gate/s_up [D, Fs]``, ``s_down [Fs, D]``.

Device scopes inside the transformer's ``moe`` scope
(base/telemetry.MOE_SCOPES): ``moe_router`` (matmul, softmax, top-k, the
balancing statistics), ``moe_dispatch`` (sort, gather, un-permute,
combine), ``moe_exchange`` (the collectives over "ep"), ``moe_experts``
(the grouped GEMMs).

Routing-health aux (exported as ``train/moe_*`` telemetry by
backend/jax_train.py; docs/observability.md): ``dropped_frac``,
``routed_rows`` (the (token, expert) pairs routed, over all experts),
on a share ``local_rows`` (those of them that chose an expert held
here), ``expert_load`` ([E] fraction of routed assignments per expert,
pre-drop) and
``expert_load_ratio`` (max/mean of that — 1.0 is perfectly balanced,
→ E is total collapse; the sentinel ``expert_collapse`` rule baselines it).
"""

from __future__ import annotations

import math
import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from areal_tpu.models.config import MoEConfig

DISPATCH_METHODS = ("grouped", "einsum")
# Aux entries that add up over micro-batches and optimizer steps; every
# other scalar is a mean (backend/jax_train.py, algorithms/ppo.py).
SUMMED_AUX = ("routed_rows", "local_rows")


def resolve_dispatch(method: Optional[str] = None) -> str:
    """The dispatch actually run: explicit arg > ``AREAL_MOE_DISPATCH`` >
    "grouped". "einsum" is the GShard one-hot oracle kept for parity."""
    if method is None:
        method = os.environ.get("AREAL_MOE_DISPATCH", "").strip() or "grouped"
    if method not in DISPATCH_METHODS:
        raise ValueError(
            f"unknown MoE dispatch {method!r} (one of {DISPATCH_METHODS})"
        )
    return method


def capacity(n_tokens: int, moe: MoEConfig) -> int:
    """Slots per expert for ``n_tokens`` tokens. A dropless model has no
    capacity: an expert can be chosen by every token, once."""
    if moe.capacity_factor is None:
        return n_tokens
    c = math.ceil(moe.top_k * n_tokens * moe.capacity_factor / moe.num_experts)
    return max(int(c), 1)


def ep_eligible(mesh: Optional[Mesh], moe: Optional[MoEConfig],
                batch: int, seq_len: int = 1) -> bool:
    """Whether the expert-parallel path can run: a real "ep"
    mesh axis, experts dividing over it, and batch/seq dims that divide
    their mesh axes (the full-manual shard_map needs exact blocks — e.g.
    generate()'s unbucketed batch dim does not divide, mirroring
    ring_eligible)."""
    if mesh is None or moe is None or moe.is_share:
        return False
    ep = dict(mesh.shape).get("ep", 1)
    if ep <= 1 or moe.num_experts % ep:
        return False
    return (
        batch % (mesh.shape["dp"] * mesh.shape["fsdp"] * ep) == 0
        and seq_len % mesh.shape["sp"] == 0
    )


# ---------------- router + balancing stats (shared by all paths) ----------------

def _routing(
    xf: jnp.ndarray,  # [N, D]
    lp: Dict[str, jnp.ndarray],
    moe: MoEConfig,
    rng: Optional[jnp.ndarray],
    valid: jnp.ndarray,  # [N] float 0/1
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Returns (top_p [N, k] post-norm gates, top_i [N, k], onehot
    [N, k, E] with padding rows zeroed, aux dict sans dropped_frac)."""
    E, k = moe.n_routed, moe.top_k
    n_valid = jnp.maximum(jnp.sum(valid), 1.0)

    router_in = xf
    if moe.input_jitter_eps > 0 and rng is not None:
        # Router input jitter (reference router.py:170): train steps thread
        # a per-micro-batch key down through transformer.forward(rng=...);
        # inference passes rng=None and routes on the clean input — jitter
        # is a training-only regulariser, never a serving behaviour.
        eps = moe.input_jitter_eps
        router_in = xf * jax.random.uniform(
            rng, xf.shape, minval=1 - eps, maxval=1 + eps, dtype=xf.dtype
        )
    logits = (router_in @ lp["router"]).astype(jnp.float32)  # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)  # [N, k]
    if moe.norm_topk_prob:
        top_p = top_p / jnp.maximum(
            jnp.sum(top_p, axis=-1, keepdims=True), 1e-9
        )

    # ---- balancing losses (reference router.py:78,146) ----
    # f_e: fraction of (real) tokens routed to expert e; P_e: mean prob.
    onehot = jax.nn.one_hot(top_i, E, dtype=jnp.float32)  # [N, k, E]
    onehot = onehot * valid[:, None, None]  # padding routes nowhere
    routed = jnp.sum(onehot, axis=1)  # [N, E] 0/1 counts
    counts_e = jnp.sum(routed, axis=0)  # [E] routed assignments per expert
    f = counts_e / n_valid * E / k
    Pm = jnp.sum(probs * valid[:, None], axis=0) / n_valid
    load_balance = jnp.sum(f * Pm)
    z = jnp.sum((jax.nn.logsumexp(logits, axis=-1) ** 2) * valid) / n_valid
    aux_total = moe.aux_loss_coeff * load_balance + moe.z_loss_coeff * z

    # Routing-health stats (pre-drop): per-expert share of assignments,
    # and its max/mean ratio (1 = balanced, E = collapse onto one expert).
    expert_load = counts_e / jnp.maximum(n_valid * k, 1.0)  # [E], sums to 1
    load_ratio = jnp.max(expert_load) / jnp.maximum(
        jnp.mean(expert_load), 1e-9
    )
    aux = {
        "aux_total": aux_total,
        "load_balance_loss": load_balance,
        "z_loss": z,
        "routed_rows": jnp.sum(valid) * k,
        "expert_load": expert_load,
        "expert_load_ratio": load_ratio,
    }
    return top_p, top_i, onehot, aux


def _capacity_keep(onehot: jnp.ndarray, C: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Switch-style slot assignment: position of each (token, choice)
    within its expert's capacity buffer — priority is token order then
    choice order (same as the reference's dispatcher); padding tokens have
    zeroed onehot and consume no slots. Returns (pos [N, k], keep [N, k])."""
    N, k, E = onehot.shape
    flat_oh = onehot.reshape(N * k, E)
    pos = (jnp.cumsum(flat_oh, axis=0) - flat_oh).reshape(N, k, E)
    pos = jnp.sum(pos * onehot, axis=-1)  # [N, k] slot per choice
    keep = (pos < C) & (jnp.sum(onehot, axis=-1) > 0)
    return pos, keep


def _expert_ffn(xe, gate_w, up_w, down_w):
    """Batched silu-gated expert MLP over [E, rows, D] capacity buffers."""
    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", xe, gate_w)
    ) * jnp.einsum("ecd,edf->ecf", xe, up_w)
    return jnp.einsum("ecf,efd->ecd", h, down_w)  # [E, rows, D]


# ---------------- einsum dispatch (GShard oracle) ----------------

def _dispatch_einsum(
    xf: jnp.ndarray,  # [N, D]
    top_p: jnp.ndarray,  # [N, k]
    onehot: jnp.ndarray,  # [N, k, E]
    lp: Dict[str, jnp.ndarray],
    moe: MoEConfig,
    n_valid: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The original one-hot capacity-buffer dispatch — every op a
    static-shape batched matmul, FLOPs/HBM scale with E × capacity.
    Kept as the parity oracle (``AREAL_MOE_DISPATCH=einsum``)."""
    N, D = xf.shape
    k = moe.top_k
    C = capacity(N, moe)
    pos, keep = _capacity_keep(onehot, C)
    gate = top_p * keep  # dropped tokens contribute nothing
    dropped_frac = 1.0 - jnp.sum(keep) / jnp.maximum(n_valid * k, 1.0)

    # combine [N, E, C] — sparse; also serves (as booleans) for dispatch.
    slot_oh = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=jnp.float32)
    combine = jnp.einsum("nke,nkc,nk->nec", onehot, slot_oh, gate)
    dispatch = (combine > 0).astype(xf.dtype)

    xe = jnp.einsum("nec,nd->ecd", dispatch, xf)  # [E, C, D]
    ye = _expert_ffn(xe, lp["e_gate"], lp["e_up"], lp["e_down"])
    y = jnp.einsum("nec,ecd->nd", combine.astype(ye.dtype), ye)
    return y, dropped_frac


# ---------------- grouped dispatch (sorted segments, the default) ----------------

def _grouped_matmul(xs: jnp.ndarray,  # [M, K] rows sorted by group
                    w: jnp.ndarray,  # [G, K, F]
                    group_sizes: jnp.ndarray,  # [G] int32
                    ) -> jnp.ndarray:
    """Grouped GEMM over contiguous row segments: row m multiplies
    ``w[g]`` where m falls in group g's segment. Rows beyond
    ``sum(group_sizes)`` — the sentinel-sorted entries — come back as
    zeros, and take no gradient: the TPU's ``ragged_dot`` leaves those
    output rows unwritten (NaN among them, measured on a v5e), in the
    backward pass as in the forward, so both ends are selected, never
    multiplied, to zero. The select on the input is what zeroes the
    cotangent of the rows on the way back."""
    live = (jnp.arange(xs.shape[0]) < jnp.sum(group_sizes))[:, None]
    out = jax.lax.ragged_dot(jnp.where(live, xs, 0), w, group_sizes)
    return jnp.where(live, out, 0)


def _sorted_expert_ffn(
    xf: jnp.ndarray,  # [N, D] tokens
    eid: jnp.ndarray,  # [N·k] group of each (token, choice) entry; G = none
    gates: jnp.ndarray,  # [N·k] gate of each entry
    cap: Optional[int],  # slots per group; None = dropless
    gate_w: jnp.ndarray,  # [G, D, F]
    up_w: jnp.ndarray,  # [G, D, F]
    down_w: jnp.ndarray,  # [G, F, D]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sort-based grouped expert compute over the ``G`` experts whose
    weights are given: one stable argsort of the ``M = N·k`` entries by
    group id makes each expert's rows contiguous, so the expert MLP is
    three grouped GEMMs over ``[M, D]``. Entries with the sentinel id
    ``G`` (padding tokens; under expert parallelism, another shard's
    experts) sort to the tail beyond ``sum(group_sizes)`` and come back as
    zero rows. Returns (the gate-weighted sum per token [N, D], the number
    of entries kept).

    With a capacity the drop matches the einsum oracle structurally: a
    stable sort preserves flat (token-major, then choice) order within
    each expert, so an entry's position inside its segment IS the oracle's
    capacity slot — entries at ``pos >= cap`` keep a zero gate (their rows
    are computed and contribute nothing). Dropless (``cap=None``) builds
    no positions and no keep mask."""
    N, D = xf.shape
    M = eid.shape[0]
    k = M // N
    G = gate_w.shape[0]
    with jax.named_scope("moe_dispatch"):
        order = jnp.argsort(eid)  # jnp argsort is stable
        counts = jnp.bincount(eid, length=G + 1)  # sentinel bin last
        group_sizes = counts[:G].astype(jnp.int32)
        gate = jnp.take(gates, order)
        if cap is None:
            kept = jnp.sum(group_sizes)
        else:
            sorted_eid = jnp.take(eid, order)
            starts = jnp.cumsum(counts) - counts
            pos = jnp.arange(M) - jnp.take(starts, sorted_eid)
            keep = (pos < cap) & (sorted_eid < G)
            kept = jnp.sum(keep)
            gate = gate * keep
        xs = jnp.take(xf, order // k, axis=0)  # [M, D] sorted expert inputs
    with jax.named_scope("moe_experts"):
        h = jax.nn.silu(
            _grouped_matmul(xs, gate_w, group_sizes)
        ) * _grouped_matmul(xs, up_w, group_sizes)
        ys = _grouped_matmul(h, down_w, group_sizes)  # [M, D]
    with jax.named_scope("moe_dispatch"):
        ys = ys * gate.astype(ys.dtype)[:, None]
        inv = jnp.argsort(order)  # inverse permutation
        y = jnp.sum(jnp.take(ys, inv, axis=0).reshape(N, k, D), axis=1)
    return y, kept.astype(jnp.float32)


def _dispatch_grouped(
    xf: jnp.ndarray,  # [N, D]
    top_p: jnp.ndarray,  # [N, k]
    top_i: jnp.ndarray,  # [N, k]
    valid: jnp.ndarray,  # [N]
    lp: Dict[str, jnp.ndarray],
    moe: MoEConfig,
    n_valid: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The one-shard path: padding entries are the sentinel
    (:func:`_sorted_expert_ffn`), and on a share so are the entries of
    experts held elsewhere. Returns (y, dropped_frac, on a share the
    entries that chose an expert held here — else None: all of them)."""
    N = xf.shape[0]
    E, k = moe.num_experts, moe.top_k
    if moe.is_share:
        with jax.named_scope("moe_dispatch"):
            eid = _held_eid(top_i, valid, moe.first_expert, E)
            local = jnp.sum(eid < E).astype(jnp.float32)
    else:
        eid = jnp.where(valid.reshape(N, 1) > 0, top_i, E).reshape(N * k)
        local = None
    dropless = moe.capacity_factor is None
    y, kept = _sorted_expert_ffn(
        xf, eid, top_p.reshape(N * k), None if dropless else capacity(N, moe),
        lp["e_gate"], lp["e_up"], lp["e_down"],
    )
    if dropless:
        return y, jnp.zeros((), jnp.float32), local
    pairs = n_valid * k if local is None else local
    return y, 1.0 - kept / jnp.maximum(pairs, 1.0), local


def _held_eid(ids: jnp.ndarray,  # [N, k] expert chosen by each entry
              valid: jnp.ndarray,  # [N]
              first,  # index of the first expert held here
              held: int) -> jnp.ndarray:
    """The group of each (token, choice) entry among the ``held`` experts
    from ``first`` on: 0..held-1, or the sentinel ``held`` for a padding
    token and for an expert held elsewhere. Shared by an ep shard (its
    ``first`` is its index on the axis) and a share run alone."""
    local = ids - first
    mine = (local >= 0) & (local < held) & (valid[:, None] > 0)
    return jnp.where(mine, local, held).reshape(ids.shape[0] * ids.shape[1])


# ---------------- expert-parallel dispatch (gather, sort, reduce-scatter over "ep") ----------------

def _dispatch_ep(
    x: jnp.ndarray,  # [B, T, D] global
    top_p: jnp.ndarray,  # [N, k]
    top_i: jnp.ndarray,  # [N, k]
    valid: jnp.ndarray,  # [N]
    lp: Dict[str, jnp.ndarray],
    moe: MoEConfig,
    mesh: Mesh,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert parallelism over the mesh's "ep" axis: each ep shard holds
    ``E/ep`` experts and a slice of the tokens. The shards all-gather
    their tokens, expert choices and gates over "ep" (16k tokens of width
    2048 in bf16 are 67 MB). Each then takes the gathered tokens one
    source shard at a time: it marks the (token, choice) entries that
    chose one of ITS experts, sorts those to the front and runs the
    grouped GEMMs over them (:func:`_sorted_expert_ffn`; an entry of
    another shard's expert is the sentinel and costs a zero row). The
    gate-weighted per-token sums — each shard's partial over its own
    experts — are reduce-scattered back over "ep", which both adds the
    shards' parts and returns every token to its owner.

    Shapes are static under any routing skew: a pass's sorted buffer has
    all ``N_local·k`` rows, the worst case in which every entry of that
    source is local, and the grouped GEMM does the work of the rows that
    are. Nothing of shape ``[N, E, C]`` is built. With a capacity, the
    drop is decided per source shard (``capacity(N_local)`` slots, that
    shard's token order) — the shard-boundary rule the exchange has
    always had; without one nothing drops.

    Full-manual shard_map (the ring_attention pattern): tokens split over
    DATA_AXES × sp, expert weights over ep with their ffn dim over tp
    (Megatron column→row: the ``e_down`` partial sums psum over "tp",
    after the combine); the ZeRO-3 fsdp shard of the weights all-gathers
    at the region boundary, exactly what GSPMD does for the dense
    paths."""
    from areal_tpu.parallel.mesh import DATA_AXES

    B, T, D = x.shape
    E, k = moe.num_experts, moe.top_k
    ep = mesh.shape["ep"]
    E_l = E // ep
    tok_axes = DATA_AXES + ("sp",)
    dropless = moe.capacity_factor is None

    def body(xl, gl, il, vl, gate_w, up_w, down_w):
        # Local shapes: xl [B/(dp·fsdp·ep), T/sp, D], gl/il [..., Tl, k],
        # vl [..., Tl]; weights [E/ep, D, F/tp] / [E/ep, F/tp, D].
        Bl, Tl = xl.shape[0], xl.shape[1]
        Nl = Bl * Tl
        cap = None if dropless else capacity(Nl, moe)
        with jax.named_scope("moe_exchange"):
            gathered = tuple(
                jax.lax.all_gather(a, "ep", axis=0)  # [ep, Nl, ...] by source
                for a in (xl.reshape(Nl, D), gl.reshape(Nl, k),
                          il.reshape(Nl, k), vl.reshape(Nl))
            )
        first = jax.lax.axis_index("ep") * E_l  # this shard's experts

        def one_source(src):
            xs, gs, ids, vs = src
            with jax.named_scope("moe_dispatch"):
                eid = _held_eid(ids, vs, first, E_l)
            return _sorted_expert_ffn(
                xs, eid, gs.reshape(Nl * k), cap, gate_w, up_w, down_w)

        # One source shard's tokens at a time, each pass recomputed in the
        # backward pass: the sorted buffers of a pass (Nl·k rows) are the
        # most that is ever alive, not the whole exchange's.
        y, kept = jax.lax.map(jax.checkpoint(one_source), gathered)
        with jax.named_scope("moe_exchange"):
            y = jax.lax.psum(y, "tp")  # row-parallel e_down partial sums
            y = jax.lax.psum_scatter(y.reshape(ep * Nl, D), "ep",
                                     scatter_dimension=0, tiled=True)
            if dropless:
                dropped = jnp.zeros((), jnp.float32)
            else:
                kept = jax.lax.psum(jnp.sum(kept), tok_axes)
                nv = jax.lax.psum(jnp.sum(vl.astype(jnp.float32)), tok_axes)
                dropped = 1.0 - kept / jnp.maximum(nv * k, 1.0)
        return y.reshape(Bl, Tl, D), dropped

    tok_spec = P(DATA_AXES, "sp")
    y, dropped_frac = jax.shard_map(
        body,
        mesh=mesh,
        check_vma=False,
        in_specs=(P(DATA_AXES, "sp", None), tok_spec, tok_spec, tok_spec,
                  P("ep", None, "tp"), P("ep", None, "tp"),
                  P("ep", "tp", None)),
        out_specs=(P(DATA_AXES, "sp", None), P()),
    )(
        x,
        top_p.reshape(B, T, k),
        top_i.reshape(B, T, k),
        valid.reshape(B, T),
        lp["e_gate"], lp["e_up"], lp["e_down"],
    )
    return y.reshape(B * T, D), dropped_frac


# ---------------- the layer ----------------

def moe_mlp(
    x: jnp.ndarray,  # [B, T, D]
    lp: Dict[str, jnp.ndarray],  # this layer's params
    moe: MoEConfig,
    rng: jnp.ndarray = None,  # jitter noise (training only); None = off
    mask: jnp.ndarray = None,  # [B, T] bool/int — True for real tokens
    dispatch: Optional[str] = None,  # None → AREAL_MOE_DISPATCH → "grouped"
    mesh: Optional[Mesh] = None,  # a mesh with ep > 1 → the EP path
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Returns (output [B, T, D], aux dict with load_balance_loss / z_loss /
    aux_total / dropped_frac / expert_load / expert_load_ratio).

    ``mask`` excludes grid-padding tokens from routing entirely: they take
    no expert-capacity slots and do not enter the balancing/z statistics
    (the reference runs on unpadded packed tokens, so padding never exists
    there; with [B, T] grids it must be masked out explicitly).

    ``mesh``: pass the active mesh to take the expert-parallel path;
    callers must gate on :func:`ep_eligible` (and must NOT pass a
    mesh from inside an already-manual shard_map region — the pipeline
    stages fall back to the single-shard paths with GSPMD handling the
    ep-sharded weights)."""
    B, T, D = x.shape
    N = B * T
    xf = x.reshape(N, D)
    valid = (
        jnp.ones((N,), jnp.float32) if mask is None
        else mask.reshape(N).astype(jnp.float32)
    )
    n_valid = jnp.maximum(jnp.sum(valid), 1.0)

    with jax.named_scope("moe_router"):
        top_p, top_i, onehot, aux = _routing(xf, lp, moe, rng, valid)

    if mesh is not None and ep_eligible(mesh, moe, B, T):
        y, dropped_frac = _dispatch_ep(x, top_p, top_i, valid, lp, moe, mesh)
    elif resolve_dispatch(dispatch) == "einsum":
        if moe.is_share:
            raise NotImplementedError(
                "the einsum oracle holds every expert; a share runs the "
                "grouped dispatch")
        y, dropped_frac = _dispatch_einsum(xf, top_p, onehot, lp, moe, n_valid)
    else:
        y, dropped_frac, local_rows = _dispatch_grouped(
            xf, top_p, top_i, valid, lp, moe, n_valid
        )
        if moe.is_share:
            aux = dict(aux, local_rows=local_rows)

    if "s_gate" in lp:  # always-on shared expert (qwen-moe)
        y = y + (jax.nn.silu(xf @ lp["s_gate"]) * (xf @ lp["s_up"])) @ lp["s_down"]

    aux = dict(aux)
    aux["dropped_frac"] = dropped_frac
    return y.reshape(B, T, D).astype(x.dtype), aux


def init_moe_params(cfg, key: jnp.ndarray, dtype) -> Dict[str, jnp.ndarray]:
    """Per-layer-stacked MoE weights ([n_layers, ...])."""
    moe = cfg.moe
    n, d = cfg.n_layers, cfg.hidden_dim
    f = moe.routed_intermediate_dim or cfg.intermediate_dim
    E = moe.num_experts  # held; the router scores all of ``n_routed``
    # One key per weight actually initialized — adding a weight grows the
    # split instead of silently reusing a neighbour's key.
    names = ["router", "e_gate", "e_up", "e_down"]
    if moe.shared_intermediate_dim:
        names += ["s_gate", "s_up", "s_down"]
    ks = dict(zip(names, jax.random.split(key, len(names))))

    def nrm(k, shape, scale=0.02):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    out = {
        "router": nrm(ks["router"], (n, d, moe.n_routed)),
        "e_gate": nrm(ks["e_gate"], (n, E, d, f)),
        "e_up": nrm(ks["e_up"], (n, E, d, f)),
        "e_down": nrm(ks["e_down"], (n, E, f, d)),
    }
    if moe.shared_intermediate_dim:
        fs = moe.shared_intermediate_dim
        out["s_gate"] = nrm(ks["s_gate"], (n, d, fs))
        out["s_up"] = nrm(ks["s_up"], (n, d, fs))
        out["s_down"] = nrm(ks["s_down"], (n, fs, d))
    return out
