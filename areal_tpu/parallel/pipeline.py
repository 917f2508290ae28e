"""Pipeline parallelism — micro-batch streaming over the mesh's "pp" axis.

Parity target: ``realhf/impl/model/parallelism/pipeline_parallel/`` (the
PipeInstruction VM + static GPipe/1F1B schedules) and its executor
``realhf/impl/model/backend/pipe_runner.py:148``. TPU-first re-design: no
instruction VM, no p2p send/recv threads — the schedule IS a ``lax.scan``
over pipeline steps inside a ``shard_map`` that is *manual over "pp" only*
(``axis_names={"pp"}``): each stage holds ``n_layers/pp`` layers of the
stacked param tree (the "pp"-sharded leading axis, parallel/sharding.py),
runs them on its resident micro-batch, and hands the activation to the next
stage with a nearest-neighbour ``lax.ppermute`` riding the ICI ring. The
dp/fsdp/tp/sp shardings of everything INSIDE a stage stay automatic
(GSPMD) — stages compose with tensor/data parallelism without any manual
collectives.

Two schedules share the step equation (at step ``s`` stage ``k`` processes
micro-batch ``s - k``; ``steps = n_micro + pp - 1``; bubble fraction
``(pp-1)/steps``):

``"gpipe"`` — the original formulation and the parity ORACLE. Backward
needs no schedule code: ``ppermute`` has a transpose rule, so ``jax.grad``
of the scan IS the reverse pipeline. Memory cost: autodiff saves residuals
for every scan step and the per-step outputs stack to ``[steps, mb, T, D]``
per stage, so live activations scale with ``steps = n_micro + pp - 1`` —
the extra ``(pp-1)/n_micro`` factor is exactly what blocked larger token
caps under PP (VERDICT round-5 "known memory cost").

``"1f1b"`` (default) — the memory-bounded rewrite, mirroring why the
reference runs a one-forward-one-backward schedule (SURVEY §2.4): a
``jax.custom_vjp`` whose forward keeps ONLY each stage's ``n_micro``
micro-batch inputs (a carry buffer written by masked dynamic-update — no
``[steps, ...]`` stacking anywhere), and whose backward is a hand-written
reverse carry: at backward step ``t`` stage ``k`` re-runs its layers on
saved input ``t + k - (pp-1)`` (rematerialization, the same trade the
reference's 1F1B+checkpointing makes), vjp's them against the cotangent
arriving from its successor, and ppermutes the input-cotangent to its
predecessor — the grad of ``ppermute`` stays the transposed ``ppermute``,
written explicitly. Live activations therefore scale with ``n_micro``, not
``steps``, which is what unlocks cap-4096+ under PP (and, once ring-SP
composes into the manual-pp region, PP∘SP at long context).

The 1F1B backward declares ZERO cotangents for cos/sin: rope tables are
pure functions of integer positions (models/transformer.rope_tables), so
their upstream cotangent dead-ends at an int cast in every caller.

Generation (decode mode) intentionally does NOT pipeline: the decode hot
loop is latency-bound and the generation fleet runs on its own mesh without
a "pp" axis (SURVEY §2.4 note; the reference's GenerateSchedule exists
because its trainer must also generate — our async design moves that to
the server).
"""

from __future__ import annotations

from contextlib import nullcontext
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from areal_tpu.base import logging, telemetry
from areal_tpu.models.config import (
    CONV, GDN, KDA, SLIDING, TransformerConfig,
)
from areal_tpu.parallel import ring as ring_mod
from areal_tpu.parallel import sharding as psh

logger = logging.getLogger("parallel.pipeline")

# One-time-per-reason WARN dedup for the GSPMD fallback (process-global:
# the gate runs per trace, the operator needs the reason once).
_WARNED_FALLBACKS: set = set()

_FALLBACK_HINTS = {
    "layers_indivisible": "n_layers must divide the pp axis",
    "batch_too_small": "batch has no divisor in [pp, 2*pp]",
    "requested_indivisible": "requested micro-batch count must divide batch",
    "sp_seq_indivisible": "seq_len must divide the sp axis to ring",
    "sp_sliding_window": "sliding-window attention is not ring-expressible",
    "layer_pattern": "layers of more than one attention kind are not "
                     "pipelined (a stage takes one RoPE table)",
    "cross_layer_state": "a layer reads a tensor another layer made (a "
                         "memory, one layer's K/V): it would have to "
                         "travel with the micro-batch from stage to stage",
    "gated_delta_rule": "Gated DeltaNet blocks beside attention blocks: "
                        "a tree per kind has no one stacked axis to split "
                        "over pp, and a period's stages cost unequally",
    "channel_decay_rule": "delta-rule blocks with a decay a key channel "
                          "beside latent-attention blocks, a dense block "
                          "before expert blocks: a tree per kind has no "
                          "one stacked axis to split over pp, and a "
                          "period's stages cost unequally",
    "learned_sparse_attention": "attention under a learned selection "
                                "reports exact counts a layer, which no "
                                "stage's aux carries",
    "short_convolution": "short-convolution blocks beside attention "
                         "blocks, dense blocks before expert blocks: a tree "
                         "per kind has no one stacked axis to split over "
                         "pp, and a period's stages cost unequally",
    "mixer_layers": "layers that are one mixer each (state-space, expert, "
                    "attention) make stages of unequal cost and have no "
                    "one stacked tree to split over pp",
}


def _fallback(reason: str) -> None:
    """GSPMD-fallback bookkeeping: a counter per reason plus a one-time
    WARN naming the failed gate (ROADMAP item 2 — the silent fallback)."""
    telemetry.inc(f"parallel/pp_fallback{{reason={reason}}}")
    if reason not in _WARNED_FALLBACKS:
        _WARNED_FALLBACKS.add(reason)
        logger.warning(
            "pipeline disengaged, falling back to GSPMD layer sharding: "
            "%s (%s)", reason, _FALLBACK_HINTS.get(reason, "")
        )
    return None


def pick_pp_microbatches(
    mesh: Optional[Mesh],
    cfg: TransformerConfig,
    batch: int,
    requested: Optional[int] = None,
    seq_len: Optional[int] = None,
) -> Optional[int]:
    """The pipeline-eligibility gate: returns the micro-batch count, or
    None when the GSPMD scan path should run instead.

    Requirements: a "pp" axis > 1, layers divisible across stages, and a
    batch divisible into >= pp micro-batches. Meshes with sp > 1 pipeline
    too (PP∘SP): ring attention runs *inside* each stage, manual over
    {"pp","sp"}, which additionally needs the sequence to shard over the
    ring (``seq_len % sp == 0``) and a ring-expressible attention pattern
    (no sliding-window layer). Layers of more than one attention kind are
    not pipelined. Every fallback WARNs once and bumps the
    ``parallel/pp_fallback{reason=...}`` counter.
    """
    if mesh is None:
        return None
    pp = mesh.shape.get("pp", 1)
    if pp <= 1:
        return None  # no pipeline requested — not a fallback
    if cfg.cross_layer_reads:
        return _fallback("cross_layer_state")
    if GDN in cfg.layer_kinds:
        return _fallback("gated_delta_rule")
    if cfg.has_mixer(KDA):
        return _fallback("channel_decay_rule")
    if cfg.has_mixer(CONV):
        return _fallback("short_convolution")
    if cfg.dsa is not None:  # the selection's counts ride no stage's aux
        return _fallback("learned_sparse_attention")
    if cfg.is_hybrid:
        return _fallback("mixer_layers")
    sp = mesh.shape.get("sp", 1)
    if sp > 1:
        if seq_len is None or seq_len % sp != 0:
            return _fallback("sp_seq_indivisible")
        if SLIDING in cfg.layer_kinds:
            return _fallback("sp_sliding_window")
    if len(set(cfg.layer_kinds)) > 1:
        return _fallback("layer_pattern")
    if cfg.n_layers % pp != 0:
        return _fallback("layers_indivisible")
    if requested is not None:
        n_micro = requested
        if batch % n_micro != 0:
            return _fallback("requested_indivisible")
        return n_micro
    # Auto: the largest divisor of the batch in [pp, 2*pp] — >= pp keeps
    # the bubble <= 1/2; > 2*pp only shrinks it further at more dispatch.
    for n_micro in range(min(2 * pp, batch), 0, -1):
        if batch % n_micro == 0 and n_micro >= pp:
            return n_micro
    return _fallback("batch_too_small")


def pp_engagement(
    mesh: Optional[Mesh],
    cfg: TransformerConfig,
    batch: int,
    seq_len: int,
    requested: Optional[int] = None,
) -> Tuple[float, float]:
    """(pp_engaged, ring_engaged) as 0/1 gauge values for this shape —
    the same gates the forward path applies, evaluated outside the jit so
    backend/jax_train.py can export ``train/pp_engaged`` /
    ``train/ring_engaged`` without tracing anything."""
    n_micro = pick_pp_microbatches(mesh, cfg, batch, requested,
                                   seq_len=seq_len)
    pp_on = n_micro is not None
    if pp_on:
        ring_on = mesh.shape.get("sp", 1) > 1
    else:
        ring_on = ring_mod.ring_eligible(mesh, cfg, batch, seq_len)
    return float(pp_on), float(ring_on)


def _scale_aux(aux: Dict[str, jnp.ndarray], cfg: TransformerConfig,
               n_micro: int) -> Dict[str, jnp.ndarray]:
    """Per-stage aux sums -> the apply_layer_stack contract: aux_total =
    total over layers (averaged over micro-batches), others = layer means
    (averaged over micro-batches)."""
    if not aux:
        return aux
    n_layers = float(cfg.n_layers)
    return {
        k: v / n_micro if k == "aux_total" else v / (n_layers * n_micro)
        for k, v in aux.items()
    }


def pipeline_apply_layers(
    cfg: TransformerConfig,
    layer_params: Dict[str, jnp.ndarray],  # stacked [L, ...], "pp"-sharded
    h: jnp.ndarray,  # [B, T, D]
    cos: jnp.ndarray,  # [B, T, dh]
    sin: jnp.ndarray,
    segment_ids: Optional[jnp.ndarray],  # [B, T]
    positions: Optional[jnp.ndarray],  # [B, T]
    mesh: Mesh,
    n_micro: int,
    attn_impl: str = "auto",
    remat: bool = False,
    schedule: str = "1f1b",  # | "gpipe" (the oracle)
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Run the stacked layers as a ``pp``-stage pipeline.

    Returns (h, aux) matching apply_layer_stack: aux values are reduced so
    that downstream's sum/mean post-processing is an identity.

    ``schedule`` selects the memory-bounded 1F1B custom-vjp path (default)
    or the GPipe scan oracle.

    PP∘SP: on meshes with sp > 1 the stages are manual over {"pp","sp"}
    and run ring attention inline (ring_mod.ring_attention_inline). The
    zig-zag ring layout is applied here — a static gather on the global
    sequence dim, inverted on the way out — so the stage bodies see the
    striped shard order while callers keep natural-order semantics.
    """
    if schedule not in ("1f1b", "gpipe"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    sp = mesh.shape.get("sp", 1)
    ring_schedule, inv = None, None
    if sp > 1:
        B, T, _ = h.shape
        ring_schedule = ring_mod.resolve_schedule(None, T, sp, causal=True)
        if segment_ids is None:
            # The ring body masks by segment; "everything is one document"
            # reproduces plain causal attention.
            segment_ids = jnp.ones((B, T), jnp.int32)
        if ring_schedule == "zigzag":
            fwd_p = ring_mod.zigzag_permutation(T, sp)
            inv = jnp.asarray(ring_mod.inverse_permutation(fwd_p))
            fwd_p = jnp.asarray(fwd_p)
            take = lambda x: None if x is None else jnp.take(x, fwd_p, axis=1)
            h, cos, sin = take(h), take(cos), take(sin)
            segment_ids, positions = take(segment_ids), take(positions)
    fn = _gpipe_apply_layers if schedule == "gpipe" else _1f1b_apply_layers
    # Inside a manual-{"pp","sp"} region a with_sharding_constraint must
    # not name the manual axes — push rules with them stripped for the
    # duration of the (trace-time) stage bodies.
    ctx = (psh.activation_sharding(mesh, psh.rules_without_axes(("pp", "sp")))
           if sp > 1 else nullcontext())
    with ctx:
        out, aux = fn(cfg, layer_params, h, cos, sin, segment_ids, positions,
                      mesh, n_micro, attn_impl, remat, ring_schedule)
    if inv is not None:
        out = jnp.take(out, inv, axis=1)
    return out, aux


# ---------------- GPipe scan (the parity oracle) ----------------


def _stage_specs(layer_params, sp_manual):
    """(manual_axes, in_spec pieces) shared by the three shard_maps: the
    layer stack, [n_micro, mb, T, ...] activations and [n_micro, mb, T]
    token arrays. With sp manual the sequence dim shards over the ring;
    otherwise the specs are exactly the pp-only originals."""
    layer_specs = jax.tree.map(lambda _: P("pp"), layer_params)
    if sp_manual:
        return (frozenset({"pp", "sp"}), layer_specs,
                P(None, None, "sp", None), P(None, None, "sp"))
    return (frozenset({"pp"}), layer_specs, P(), P())


def _ring_ctx(sp, ring_schedule):
    """RingCtx of a stage body (None when sp is not manual)."""
    if sp <= 1:
        return None
    return ring_mod.RingCtx("sp", sp, ring_schedule)


def _gpipe_apply_layers(
    cfg, layer_params, h, cos, sin, segment_ids, positions,
    mesh, n_micro, attn_impl, remat, ring_schedule=None,
):
    from areal_tpu.models import transformer as tfm

    pp = mesh.shape["pp"]
    sp = mesh.shape.get("sp", 1)
    B, T, D = h.shape
    assert B % n_micro == 0 and cfg.n_layers % pp == 0
    mb = B // n_micro
    steps = n_micro + pp - 1

    def to_mbs(x):
        return x.reshape((n_micro, mb) + x.shape[1:]) if x is not None else None

    h_mbs = to_mbs(h)
    cos_mbs, sin_mbs = to_mbs(cos), to_mbs(sin)
    seg_mbs = to_mbs(segment_ids)
    pos_mbs = to_mbs(positions)

    def stage_body(local_layers, h_mbs, cos_mbs,
                   sin_mbs, seg_mbs, pos_mbs):
        stage = jax.lax.axis_index("pp")
        ring_ctx = _ring_ctx(sp, ring_schedule)
        fwd_perm = [(k, k + 1) for k in range(pp - 1)]
        Tl = h_mbs.shape[2]  # local sequence shard (T/sp when sp manual)

        def step(carry, s):
            state, aux_acc = carry
            # Stage 0 ingests micro-batch s; others consume the activation
            # permuted from their predecessor at the previous step.
            mb_idx = jnp.clip(s - stage, 0, n_micro - 1)
            take = lambda x: (
                jax.lax.dynamic_index_in_dim(x, mb_idx, 0, keepdims=False)
                if x is not None else None
            )
            inp = jax.lax.dynamic_index_in_dim(
                h_mbs, jnp.clip(s, 0, n_micro - 1), 0, keepdims=False
            )
            x = jnp.where(stage == 0, inp, state)
            y, aux = tfm.apply_layer_stack(
                cfg, x, local_layers, take(cos_mbs), take(sin_mbs),
                take(seg_mbs), take(pos_mbs), attn_impl=attn_impl,
                remat=remat, allow_ring=True, ring_ctx=ring_ctx,
                allow_ep=False,  # no nested shard_map inside the pp stages
            )
            # Bubble steps run garbage (their ys are never sliced out);
            # MoE aux must not count them.
            valid = ((s - stage >= 0) & (s - stage < n_micro)).astype(
                jnp.float32
            )
            # Index by aux_acc's (scalar) keys: aux may carry extra
            # vector-valued stats the pipeline cannot accumulate.
            aux_acc = {
                k: aux_acc[k] + valid * jnp.sum(aux[k].astype(jnp.float32))
                for k in aux_acc
            } if aux else aux_acc
            state = jax.lax.ppermute(y, "pp", fwd_perm)
            return (state, aux_acc), y

        aux0 = {k: jnp.zeros((), jnp.float32) for k in _aux_keys(cfg)}
        state0 = jnp.zeros((mb, Tl, D), h_mbs.dtype)
        (_, aux_acc), ys = jax.lax.scan(
            step, (state0, aux0), jnp.arange(steps)
        )
        aux_out = {
            k: jax.lax.psum(v, ("pp", "sp") if sp > 1 else "pp")
            for k, v in aux_acc.items()
        }
        # KNOWN COST (why this schedule is only the oracle): ys stacks each
        # stage's per-step outputs ([steps, mb, T, D] per device ≈
        # (1 + (pp-1)/n_micro)·[B, T, D]) although only the last stage's
        # n_micro blocks are consumed, and scan autodiff saves residuals
        # for all ``steps`` iterations. The 1F1B path below fixes both.
        return ys, aux_out

    # Manual over the pipeline axes only: layer stacks arrive as local
    # [L/pp, ...] slices (and activations as T/sp sequence shards when sp
    # rings); dp/fsdp/tp inside each stage stay automatic (GSPMD).
    manual, layer_specs, act_spec, tok_spec = _stage_specs(
        layer_params, sp > 1
    )
    ys_spec = P("pp", None, "sp", None) if sp > 1 else P("pp")
    ys, aux = jax.shard_map(
        stage_body,
        mesh=mesh,
        check_vma=False,
        in_specs=(layer_specs, act_spec, act_spec,
                  act_spec, tok_spec, tok_spec),
        out_specs=(ys_spec, P()),
        axis_names=manual,
    )(layer_params, h_mbs, cos_mbs, sin_mbs, seg_mbs, pos_mbs)

    # ys is the per-stage step outputs concatenated over "pp":
    # [pp*steps, mb, T, D]; the finished micro-batch i left the LAST stage
    # at step (pp-1) + i.
    last = (pp - 1) * steps + (pp - 1)
    out = jax.lax.dynamic_slice_in_dim(ys, last, n_micro, axis=0)
    out = out.reshape(B, T, D)
    return out, _scale_aux(aux, cfg, n_micro)


# ---------------- 1F1B custom-vjp (memory-bounded, the default) ----------


def _aux_keys(cfg) -> Tuple[str, ...]:
    """The SCALAR MoE aux keys the pipeline carries (accumulated across
    micro-batches and psummed across stages). Vector-valued aux — the
    per-expert ``expert_load`` histogram — is deliberately absent: the
    pipeline's aux plumbing (scan carries, 1F1B cotangents) is
    scalar-only, and the engine recomputes nothing it can't carry."""
    return (("aux_total", "load_balance_loss", "z_loss", "dropped_frac",
             "expert_load_ratio")
            if cfg.moe is not None else ())


def _make_stage_fn(cfg, attn_impl, remat):
    """One stage's layer application, shared VERBATIM by the 1F1B forward
    and its hand-written backward (the backward re-runs it under jax.vjp):
    any drift between the two would break gradient parity silently, so
    there is exactly one definition."""

    def stage_fn(local_layers, x, cos_j, sin_j, seg_j, pos_j,
                 ring_ctx=None):
        from areal_tpu.models import transformer as tfm

        # Stage bodies trace inside a shard_map manual over {"pp"} or
        # {"pp","sp"}, but the trace POINT varies: the 1F1B custom-vjp
        # backward traces after pipeline_apply_layers' stripped-rules
        # context has popped, leaving whatever outer activation_sharding
        # the engine holds (full rules naming "sp") innermost — strip the
        # manual axes here, at the constrain calls themselves.
        with psh.strip_manual_axes(("pp", "sp")):
            y, aux = tfm.apply_layer_stack(
                cfg, x, local_layers, cos_j, sin_j, seg_j, pos_j,
                attn_impl=attn_impl, remat=remat, allow_ring=True,
                ring_ctx=ring_ctx,
                allow_ep=False,  # no nested shard_map inside the pp stages
            )
        # Only the scalar keys: the 1F1B backward builds cotangents from
        # _aux_keys, and vector stats (expert_load) don't pipeline.
        aux_sums = {k: jnp.sum(aux[k].astype(jnp.float32))
                    for k in _aux_keys(cfg)} if aux else {}
        return y, aux_sums

    return stage_fn


def _1f1b_parts(cfg, mesh, n_micro, attn_impl, remat,
                layer_params, h_mbs, cos_mbs, sin_mbs, seg_mbs, pos_mbs,
                ring_schedule=None):
    """The 1F1B forward: returns (out_blocks, aux, saved_x) where
    ``saved_x`` — each stage's n_micro micro-batch INPUTS, ``[pp*n_micro,
    mb, T, D]`` sharded P("pp") — is the complete activation residual set
    the backward needs (everything else is rematerialized per stage-step;
    under PP∘SP that includes the stage's ring steps)."""
    pp = mesh.shape["pp"]
    sp = mesh.shape.get("sp", 1)
    n_micro_, mb, T, D = h_mbs.shape
    assert n_micro_ == n_micro
    steps = n_micro + pp - 1
    aux_keys = _aux_keys(cfg)
    stage_fn = _make_stage_fn(cfg, attn_impl, remat)

    def fwd_body(local_layers, h_mbs, cos_mbs,
                 sin_mbs, seg_mbs, pos_mbs):
        stage = jax.lax.axis_index("pp")
        ring_ctx = _ring_ctx(sp, ring_schedule)
        fwd_perm = [(k, k + 1) for k in range(pp - 1)]
        Tl = h_mbs.shape[2]

        def step(carry, s):
            state, aux_acc, saved_x, out_buf = carry
            mb_idx = jnp.clip(s - stage, 0, n_micro - 1)
            take = lambda a: (
                jax.lax.dynamic_index_in_dim(a, mb_idx, 0, keepdims=False)
                if a is not None else None
            )
            inp = jax.lax.dynamic_index_in_dim(
                h_mbs, jnp.clip(s, 0, n_micro - 1), 0, keepdims=False
            )
            x = jnp.where(stage == 0, inp, state)
            valid = (s - stage >= 0) & (s - stage < n_micro)
            # Guarded writes: tail-bubble steps clip mb_idx onto slot
            # n_micro-1, which holds real data — keep it.
            prev_x = jax.lax.dynamic_index_in_dim(
                saved_x, mb_idx, 0, keepdims=False
            )
            saved_x = jax.lax.dynamic_update_index_in_dim(
                saved_x, jnp.where(valid, x, prev_x), mb_idx, 0
            )
            y, aux_sums = stage_fn(local_layers, x, take(cos_mbs),
                                   take(sin_mbs), take(seg_mbs),
                                   take(pos_mbs), ring_ctx)
            vf = valid.astype(jnp.float32)
            aux_acc = {
                k: aux_acc[k] + vf * aux_sums[k] for k in aux_acc
            } if aux_acc else aux_acc
            write = valid & (stage == pp - 1)
            prev_o = jax.lax.dynamic_index_in_dim(
                out_buf, mb_idx, 0, keepdims=False
            )
            out_buf = jax.lax.dynamic_update_index_in_dim(
                out_buf, jnp.where(write, y, prev_o), mb_idx, 0
            )
            state = jax.lax.ppermute(y, "pp", fwd_perm)
            return (state, aux_acc, saved_x, out_buf), None

        aux0 = {k: jnp.zeros((), jnp.float32) for k in aux_keys}
        state0 = jnp.zeros((mb, Tl, D), h_mbs.dtype)
        saved0 = jnp.zeros((n_micro, mb, Tl, D), h_mbs.dtype)
        out0 = jnp.zeros((n_micro, mb, Tl, D), h_mbs.dtype)
        (_, aux_acc, saved_x, out_buf), _ = jax.lax.scan(
            step, (state0, aux0, saved0, out0), jnp.arange(steps)
        )
        aux_out = {k: jax.lax.psum(v, ("pp", "sp") if sp > 1 else "pp")
                   for k, v in aux_acc.items()}
        return out_buf, aux_out, saved_x

    manual, layer_specs, act_spec, tok_spec = _stage_specs(
        layer_params, sp > 1
    )
    buf_spec = P("pp", None, "sp", None) if sp > 1 else P("pp")
    return jax.shard_map(
        fwd_body,
        mesh=mesh,
        check_vma=False,
        in_specs=(layer_specs, act_spec, act_spec,
                  act_spec, tok_spec, tok_spec),
        out_specs=(buf_spec, P(), buf_spec),
        axis_names=manual,
    )(layer_params, h_mbs, cos_mbs, sin_mbs, seg_mbs, pos_mbs)


def _1f1b_bwd_impl(cfg, mesh, n_micro, attn_impl, remat,
                   layer_params, saved_x, cos_mbs, sin_mbs, seg_mbs,
                   pos_mbs, d_out, d_aux, ring_schedule=None):
    """Hand-written reverse pipeline: at backward step ``t`` stage ``k``
    rematerializes micro-batch ``j = t + k - (pp-1)`` from its saved input
    and vjp's it (under PP∘SP the re-run includes the stage's ring steps —
    ppermute has a transpose rule, so the vjp is exact); the
    input-cotangent rides the transposed ppermute to the predecessor while
    param-cotangents accumulate in place."""
    pp = mesh.shape["pp"]
    sp = mesh.shape.get("sp", 1)
    steps = n_micro + pp - 1
    aux_keys = _aux_keys(cfg)
    stage_fn = _make_stage_fn(cfg, attn_impl, remat)

    def bwd_body(local_layers, saved_x, cos_mbs,
                 sin_mbs, seg_mbs, pos_mbs, d_out, d_aux):
        stage = jax.lax.axis_index("pp")
        ring_ctx = _ring_ctx(sp, ring_schedule)
        bwd_perm = [(k, k - 1) for k in range(1, pp)]
        _, mb, Tl, D = saved_x.shape

        def step(carry, t):
            dstate, dtheta, d_h_buf = carry
            j = t + stage - (pp - 1)
            valid = (j >= 0) & (j < n_micro)
            jc = jnp.clip(j, 0, n_micro - 1)
            take = lambda a: (
                jax.lax.dynamic_index_in_dim(a, jc, 0, keepdims=False)
                if a is not None else None
            )
            x = jax.lax.dynamic_index_in_dim(saved_x, jc, 0, keepdims=False)
            # The last stage reads its cotangent from the output buffer's
            # cotangent (its local d_out block); inner stages receive it
            # from their successor over the reverse ring.
            dy_tail = jax.lax.dynamic_index_in_dim(
                d_out, jc, 0, keepdims=False
            )
            dy = jnp.where(stage == pp - 1, dy_tail, dstate)
            dy = jnp.where(valid, dy, jnp.zeros_like(dy))
            cos_j, sin_j, seg_j, pos_j = (take(cos_mbs), take(sin_mbs),
                                          take(seg_mbs), take(pos_mbs))
            fn = lambda p, xx: stage_fn(p, xx, cos_j, sin_j, seg_j, pos_j,
                                        ring_ctx)
            _, vjp_fn = jax.vjp(fn, local_layers, x)
            vf = valid.astype(jnp.float32)
            d_aux_t = {k: d_aux[k].astype(jnp.float32) * vf
                       for k in aux_keys}
            dp, dx = vjp_fn((dy, d_aux_t))
            # vjp is linear in the cotangent: the masked (zero) dy/d_aux of
            # bubble steps yields exactly-zero dp/dx, so plain accumulation
            # is already bubble-safe.
            dtheta = jax.tree.map(jnp.add, dtheta, dp)
            w0 = valid & (stage == 0)
            prev = jax.lax.dynamic_index_in_dim(
                d_h_buf, jc, 0, keepdims=False
            )
            d_h_buf = jax.lax.dynamic_update_index_in_dim(
                d_h_buf, jnp.where(w0, dx, prev), jc, 0
            )
            dstate = jax.lax.ppermute(dx, "pp", bwd_perm)
            return (dstate, dtheta, d_h_buf), None

        dstate0 = jnp.zeros((mb, Tl, D), saved_x.dtype)
        dtheta0 = jax.tree.map(jnp.zeros_like, local_layers)
        dh0 = jnp.zeros((n_micro, mb, Tl, D), saved_x.dtype)
        (_, dtheta, d_h_buf), _ = jax.lax.scan(
            step, (dstate0, dtheta0, dh0), jnp.arange(steps)
        )
        if sp > 1:
            # Layer params are replicated over the ring: each sp shard's
            # dtheta covers only its sequence shard's tokens — the total
            # is their sum. This backward is hand-written (no shard_map
            # transpose runs), so the psum must be explicit here.
            dtheta = jax.tree.map(
                lambda g: jax.lax.psum(g, "sp"), dtheta
            )
        return dtheta, d_h_buf

    manual, layer_specs, act_spec, tok_spec = _stage_specs(
        layer_params, sp > 1
    )
    buf_spec = P("pp", None, "sp", None) if sp > 1 else P("pp")
    d_layers, d_h_blocks = jax.shard_map(
        bwd_body,
        mesh=mesh,
        check_vma=False,
        in_specs=(layer_specs, buf_spec, act_spec,
                  act_spec, tok_spec, tok_spec, buf_spec, P()),
        out_specs=(P("pp"), buf_spec),
        axis_names=manual,
    )(layer_params, saved_x, cos_mbs, sin_mbs, seg_mbs, pos_mbs, d_out,
      d_aux)
    # d_h_blocks concatenates per-stage buffers over "pp"; only stage 0
    # ingests h, so its block (the first) is the input cotangent — a lazy
    # slice, no collective.
    d_h_mbs = jax.lax.slice_in_dim(d_h_blocks, 0, n_micro, axis=0)
    return d_layers, d_h_mbs


def _zero_cotangent(x):
    """Symbolic-zero cotangent: float0 for int leaves (jax's tangent type
    for non-differentiable dtypes), zeros for float leaves, None for None."""
    if x is None:
        return None
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.zeros_like(x)
    return np.zeros(x.shape, jax.dtypes.float0)


def _1f1b_apply_layers(
    cfg, layer_params, h, cos, sin, segment_ids, positions,
    mesh, n_micro, attn_impl, remat, ring_schedule=None,
):
    pp = mesh.shape["pp"]
    B, T, D = h.shape
    assert B % n_micro == 0 and cfg.n_layers % pp == 0
    mb = B // n_micro

    def to_mbs(x):
        return x.reshape((n_micro, mb) + x.shape[1:]) if x is not None else None

    @jax.custom_vjp
    def run(layer_params, h_mbs, cos_mbs, sin_mbs, seg_mbs, pos_mbs):
        out, aux, _ = _1f1b_parts(
            cfg, mesh, n_micro, attn_impl, remat,
            layer_params, h_mbs, cos_mbs, sin_mbs, seg_mbs, pos_mbs,
            ring_schedule,
        )
        return out, aux

    def run_fwd(layer_params, h_mbs, cos_mbs, sin_mbs, seg_mbs, pos_mbs):
        out, aux, saved_x = _1f1b_parts(
            cfg, mesh, n_micro, attn_impl, remat,
            layer_params, h_mbs, cos_mbs, sin_mbs, seg_mbs, pos_mbs,
            ring_schedule,
        )
        res = (layer_params, saved_x, cos_mbs, sin_mbs, seg_mbs, pos_mbs)
        return (out, aux), res

    def run_bwd(res, cts):
        layer_params, saved_x, cos_mbs, sin_mbs, seg_mbs, pos_mbs = res
        d_out, d_aux = cts
        d_layers, d_h_mbs = _1f1b_bwd_impl(
            cfg, mesh, n_micro, attn_impl, remat,
            layer_params, saved_x, cos_mbs, sin_mbs, seg_mbs, pos_mbs,
            d_out, d_aux, ring_schedule,
        )
        return (d_layers, d_h_mbs, _zero_cotangent(cos_mbs),
                _zero_cotangent(sin_mbs), _zero_cotangent(seg_mbs),
                _zero_cotangent(pos_mbs))

    run.defvjp(run_fwd, run_bwd)

    out_blocks, aux = run(layer_params, to_mbs(h), to_mbs(cos), to_mbs(sin),
                          to_mbs(segment_ids), to_mbs(positions))
    # Only the last stage's output buffer holds the pipeline output.
    out = jax.lax.slice_in_dim(
        out_blocks, (pp - 1) * n_micro, pp * n_micro, axis=0
    )
    return out.reshape(B, T, D), _scale_aux(aux, cfg, n_micro)


def backward_residual_bytes(
    cfg: TransformerConfig,
    layer_params,
    h: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    segment_ids: Optional[jnp.ndarray],
    positions: Optional[jnp.ndarray],
    mesh: Mesh,
    n_micro: int,
    attn_impl: str = "auto",
    remat: bool = False,
) -> int:
    """PER-STAGE bytes of activation residuals the 1F1B backward keeps live
    between forward and backward, measured from the ABSTRACT shapes of the
    actual forward (``jax.eval_shape`` of ``_1f1b_parts``) — not a formula
    that can drift from the implementation. Excludes layer params (shared
    with forward, schedule-independent).

    The GPipe oracle has no comparable hook (its residuals are implicit in
    scan autodiff): its per-stage cost is the same set of per-step inputs
    PLUS the ``[steps, mb, T, D]`` stacked output and its cotangent —
    ``>= (steps / n_micro)`` times this number; tests assert the scaling.
    """
    pp = mesh.shape["pp"]
    sp = mesh.shape.get("sp", 1)
    B = h.shape[0]
    mb = B // n_micro
    ring_schedule = (
        ring_mod.resolve_schedule(None, h.shape[1], sp) if sp > 1 else None
    )
    if sp > 1 and segment_ids is None:
        segment_ids = jnp.ones(h.shape[:2], jnp.int32)

    def to_mbs(x):
        return x.reshape((n_micro, mb) + x.shape[1:]) if x is not None else None

    def fwd(lp, h_mbs, cos_mbs, sin_mbs, seg_mbs, pos_mbs):
        _, _, saved_x = _1f1b_parts(
            cfg, mesh, n_micro, attn_impl, remat,
            lp, h_mbs, cos_mbs, sin_mbs, seg_mbs, pos_mbs, ring_schedule,
        )
        return saved_x

    saved = jax.eval_shape(
        fwd, layer_params, to_mbs(h), to_mbs(cos), to_mbs(sin),
        to_mbs(segment_ids), to_mbs(positions),
    )
    total = sum(
        int(np.prod(s.shape)) * s.dtype.itemsize
        for s in jax.tree.leaves(saved)
    )
    return total // pp  # global [pp*n_micro, ...] -> one stage's share
