"""The optax/GSPMD training engine — areal_tpu's Megatron-backend equivalent.

Parity target: ``realhf/impl/model/backend/megatron.py`` (ReaLMegatronEngine:
microbatched train_batch/forward/generate with global token normalization,
grad-norm stats, lr scheduling) and ``inference.py`` (PipelinableInference-
Engine). TPU-first differences:

 - No DDP/ZeRO wrapper classes: params/opt-state sharding IS the
   PartitionSpec tree (parallel/sharding.py); XLA emits the reduce-scatters
   Megatron's DistributedOptimizer hand-codes.
 - No pipeline-schedule VM (instruction.py/pipe_runner.py): micro-batches
   exist only to bound activation HBM; each one is a full jitted step and
   gradients accumulate across them on device.
 - Mixed precision: params live in f32 (or cfg dtype); the programs compute
   with a ``compute_dtype`` copy (bf16 on the MXU) that the optimizer step
   writes once per weights version; no loss scaling needed.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.api.model import (
    FinetuneSpec,
    GenerationHyperparameters,
    Model,
    ModelBackend,
    TrainableEngine,
    register_backend,
)
from areal_tpu.backend import microbatch as mbu
from areal_tpu.base import compile_watch, logging, telemetry
from areal_tpu.models import generate as genmod
from areal_tpu.models import dsa as dsa_mod
from areal_tpu.models import moe as moe_mod
from areal_tpu.models import transformer
from areal_tpu.models.config import TransformerConfig, has_dense_ffn
from areal_tpu.ops.attention import dispatch_label, kernel_padded_len
from areal_tpu.parallel import pipeline as ppl
from areal_tpu.parallel import sharding as psh
from areal_tpu.system import memwatch

logger = logging.getLogger("backend.jax")

# Canonical home is the dependency-free api.train_config; re-exported here
# because this module historically defined it.
from areal_tpu.api.train_config import OptimizerConfig  # noqa: E402,F401


def build_lr_schedule(cfg: OptimizerConfig, total_steps: int):
    """Warmup + {constant,cosine,linear} decay to min_lr_ratio·lr (parity:
    thirdparty/megatron lr_schduler.py used by the reference backend)."""
    total_steps = max(total_steps, 1)
    warmup = int(cfg.warmup_steps_proportion * total_steps)
    floor = cfg.lr * cfg.min_lr_ratio
    if cfg.lr_scheduler_type == "cosine":
        decay = optax.cosine_decay_schedule(
            cfg.lr, max(total_steps - warmup, 1), alpha=cfg.min_lr_ratio
        )
    elif cfg.lr_scheduler_type == "linear":
        decay = optax.linear_schedule(
            cfg.lr, floor, max(total_steps - warmup, 1)
        )
    else:
        decay = optax.constant_schedule(cfg.lr)
    if warmup > 0:
        return optax.join_schedules(
            [optax.linear_schedule(0.0, cfg.lr, warmup), decay], [warmup]
        )
    return decay


def scale_by_adam_mixed(
    b1: float, b2: float, eps: float,
    mu_dtype: Optional[str] = None, nu_dtype: Optional[str] = None,
) -> optax.GradientTransformation:
    """optax.scale_by_adam with BOTH moment storage dtypes configurable
    (optax only exposes mu_dtype). The moment math always runs in f32 —
    only the carried state is cast — so bf16 storage adds rounding noise
    to the state, not to any single update's arithmetic. Reuses optax's
    ScaleByAdamState so checkpointed optimizer trees stay compatible."""

    def _cast(tree, dtype):
        if dtype is None:
            return tree
        dt = jnp.dtype(dtype)
        return jax.tree.map(lambda x: x.astype(dt), tree)

    def init(params):
        mu = jax.tree.map(
            lambda p: jnp.zeros_like(p, dtype=mu_dtype or p.dtype), params
        )
        nu = jax.tree.map(
            lambda p: jnp.zeros_like(p, dtype=nu_dtype or p.dtype), params
        )
        return optax.ScaleByAdamState(
            count=jnp.zeros([], jnp.int32), mu=mu, nu=nu
        )

    def update(updates, state, params=None):
        del params
        f32 = jnp.float32
        mu = jax.tree.map(
            lambda g, m: b1 * m.astype(f32) + (1 - b1) * g.astype(f32),
            updates, state.mu,
        )
        nu = jax.tree.map(
            lambda g, n: b2 * n.astype(f32) + (1 - b2) * g.astype(f32) ** 2,
            updates, state.nu,
        )
        count = state.count + 1
        bc1 = 1 - b1 ** count.astype(f32)
        bc2 = 1 - b2 ** count.astype(f32)
        out = jax.tree.map(
            lambda m, n: (m / bc1) / (jnp.sqrt(n / bc2) + eps), mu, nu
        )
        return out, optax.ScaleByAdamState(
            count=count, mu=_cast(mu, mu_dtype), nu=_cast(nu, nu_dtype)
        )

    return optax.GradientTransformation(init, update)


def add_decayed_weights(weight_decay: float) -> optax.GradientTransformation:
    """``optax.add_decayed_weights`` — its (empty) state, its arithmetic —
    that passes over a model's buffers (``moe.BUFFER_LEAVES``: the
    router's choice bias takes no gradient and is the publisher's to
    move; ``dsa.BUFFER_SUBTREES``: a learned selection's indexer, whole
    matrices under a subtree, takes none either), so that what the
    optimizer adds to such a leaf is exactly 0."""

    def update(updates, state, params):
        def one(path, g, p):
            if (getattr(path[-1], "key", None) in moe_mod.BUFFER_LEAVES
                    or dsa_mod.is_buffer(path)):
                return g
            return g + weight_decay * p

        return jax.tree_util.tree_map_with_path(one, updates, params), state

    return optax.GradientTransformation(
        lambda params: optax.AddDecayedWeightsState(), update)


def _scoped(name: str, tx: optax.GradientTransformation):
    """``tx`` with its update under ``jax.named_scope(name)`` (metadata
    only; the state tree is ``tx``'s own)."""

    def update(updates, state, params=None):
        with jax.named_scope(name):
            return tx.update(updates, state, params)

    return optax.GradientTransformation(tx.init, update)


def build_optimizer(
    cfg: OptimizerConfig, total_steps: int
) -> Tuple[optax.GradientTransformation, Callable]:
    sched = build_lr_schedule(cfg, total_steps)
    assert cfg.type in ("adamw", "sgd"), cfg.type
    if cfg.type == "adamw":
        opt = optax.chain(
            scale_by_adam_mixed(
                cfg.beta1, cfg.beta2, cfg.eps,
                mu_dtype=getattr(cfg, "mu_dtype", None),
                nu_dtype=getattr(cfg, "nu_dtype", None),
            ),
            add_decayed_weights(cfg.weight_decay),
            optax.scale_by_learning_rate(sched),
        )
    else:
        opt = optax.sgd(sched)
    chain = [_scoped("adam", opt)]
    if cfg.gradient_clipping and cfg.gradient_clipping > 0:
        chain = [_scoped(
            "grad_clip", optax.clip_by_global_norm(cfg.gradient_clipping)
        )] + chain
    return optax.chain(*chain), sched


# Loss functions receive (logits, batch) and return (loss_sum, stats-sums).
LossFn = Callable[[jnp.ndarray, Dict[str, jnp.ndarray]], Tuple[jnp.ndarray, Dict]]


def _pack_attrs(mbs: List[mbu.MicroBatch],
                n_mbs: Optional[int] = None) -> Dict[str, Any]:
    """What the packer decided, as the attributes of the upload span that
    follows it: counted once, where the trace and the registry both see
    it. ``mbs`` are the micro-batches this span uploads, ``n_mbs`` how
    many the packer made in all (default: these)."""
    R, L = mbs[0].layout.shape
    return {
        "real_tokens": sum(mb.n_tokens for mb in mbs),
        "padded_tokens": len(mbs) * R * L,
        "n_mbs": n_mbs or len(mbs),
        "grid": f"{R}x{L}",
    }


def _accumulate(loss, stats, grads, scale, carry):
    """Tail of both grad programs: scale this micro-batch's loss and grads
    and add them to the carry of the micro-batches before it."""
    loss = loss * scale
    with jax.named_scope("grad_accum"):
        # Cast the scale into each leaf's dtype: a f32 scalar would
        # silently promote bf16 grads to f32 (2x grad + carry HBM).
        grads = jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)
        if carry is not None:
            c_loss, c_stats, c_grads = carry
            loss = loss + c_loss
            stats = {
                k: stats[k] + c_stats[k] if k in c_stats else stats[k]
                for k in stats
            }
            grads = jax.tree.map(jnp.add, grads, c_grads)
    return loss, stats, grads


# "moe_" statistics that add up over a step's micro-batches; the others
# are per-micro-batch means carried as sums.
_MOE_SUMMED = tuple(f"moe_{k}" for k in moe_mod.SUMMED_AUX)
# A learned selection's exact counts (models/dsa.py): int32, summed over a
# step's micro-batches under their own names.
_DSA_GAUGES = tuple((k, f"train/{k}") for k in dsa_mod.SUMMED_AUX)


def _moe_step_stats(fetched: Dict[str, Any], n_mbs: int) -> Dict[str, float]:
    """A step's routing health out of its fetched statistics: the (token,
    expert) pairs routed per layer over the step (on a share of an expert
    layer also those that chose an expert held here), where the sorted
    pass is bounded (models/moe.sorted_rows) its passes per layer, those
    of them that ran on the whole buffer, the rows they ran on and the
    rows their gathers and combines walked (models/moe.walked_rows), and
    the micro-batch means
    of the load ratio and the dropped share. {} for a dense model."""
    n = max(n_mbs, 1)
    return {
        k: float(fetched[k]) / (1 if k in _MOE_SUMMED else n)
        for k in ("moe_routed_rows", "moe_local_rows", "moe_passes",
                  "moe_full_passes", "moe_bound_rows", "moe_walked_rows",
                  "moe_expert_load_ratio", "moe_dropped_frac")
        if k in fetched
    }


# The constants of the remat budget (JaxTrainEngine._remat_budget_bytes),
# calibrated on the chip compiler's ``memory_analysis()`` of the
# benchmark's seven grids under every entry (PERF.md §5, PR 28).
# Of the chip's limit, kept free: the allocator wants a program's
# temporaries in one piece beside what is resident.
_LIMIT_MARGIN = 0.05
# Bytes of program heap a kept byte costs (the compiler reports 28 % of
# fragmentation; measured 1.2-2.6, 2.0 at the grids that decide).
_HEAP_PER_KEPT_BYTE = 2.0
# Bytes a logit of the head's chunk costs (compute-dtype logits, their
# float32 softmax, the cotangent): measured 2.8-4.3.
_HEAD_BYTES_PER_LOGIT = 4.3
# Copies of a layer's widest activation that its backward holds: dense
# (measured 8.7), through the expert exchange (22.9, one configuration),
# and of an expert layer whose experts are all on the chip that runs it —
# a share, or no "ep" axis: no gathered tokens (6.0: the compiler's 1.59 GB
# for Mellum 2's share at 1 x 6656 under "full", PERF.md §5, PR 32).
_LAYER_COPIES = 9
_MOE_LAYER_COPIES = 24
_MOE_LOCAL_LAYER_COPIES = 7
# ... and of an expert layer whose experts work in a LATENT width, in
# copies of top_k x latent: the router scores 512 experts for 22 choices a
# token, so the sorted buffers are long and narrow and the routing's own
# arrays weigh beside them (measured 10.3 and 10.7: the compiler's 4.53 /
# 2.34 GB for the Nemotron 3 share's forward + backward at 1 x 8192 /
# 1 x 4096 under "full", less the kept layer inputs; PERF.md §5, PR 34).
_LATENT_MOE_LAYER_COPIES = 11
# The engine's side of a grid's plan that a grad program's label carries
# into the compile ledger, beside the compiler's side.
_PLAN_LABEL = ("kept_bytes_estimate", "budget_bytes", "reckoned_heap_bytes")


# Bytes of inference results that may wait on the device for their fetch
# (JaxTrainEngine.forward). A post-hooked pass returns [R, L] float32 a
# micro-batch, 0.1-0.2 MB a whole step: every micro-batch is in flight and
# the device never waits for the host. A pass without a hook returns
# logits ([1, 4096, 151936] = 1.2 GB in bfloat16, 2.5 GB in float32): ONE
# such result is over the bound, so it is fetched before the next
# dispatch. Three orders above the first case, most of one below the
# second.
_INFLIGHT_RESULT_BYTES = 256 << 20


def _bytes_on_chip(tree) -> int:
    """Bytes of a tree's arrays on one chip: each leaf's shard."""
    return sum(
        int(np.prod(x.sharding.shard_shape(x.shape))) * x.dtype.itemsize
        for x in jax.tree.leaves(tree) if isinstance(x, jax.Array))


def choose_remat(kept: Dict[str, int], budget: int) -> str:
    """The entry of transformer.REMAT_ENTRIES that re-runs least among
    those whose kept bytes fit ``budget``; "full" when none does (it is
    what a program must keep at the least)."""
    for entry in reversed(transformer.REMAT_ENTRIES):
        if kept[entry] <= budget:
            return entry
    return transformer.REMAT_ENTRIES[0]


@dataclasses.dataclass
class UniformBatch:
    """A whole batch resident on device as one [n_mbs·R, L] grid set.

    ``grids``: per-token keys (+ prep outputs); ``seq``: [n_mbs, S] stacked
    per-micro-batch sequence arrays (grid coordinates, masks, scalar keys).
    Host-side layouts stay in ``mbs`` for weights/scatter-back."""

    mbs: List[mbu.MicroBatch]
    R: int
    L: int
    S: int
    grids: Dict[str, jnp.ndarray]
    seq: Dict[str, jnp.ndarray]

    @property
    def n_mbs(self) -> int:
        return len(self.mbs)


class JaxTrainEngine(TrainableEngine):
    """Owns (params, opt_state) on an optional mesh and the jitted steps."""

    def __init__(
        self,
        cfg: TransformerConfig,
        params: Any,
        opt_cfg: Optional[OptimizerConfig] = None,
        ft_spec: Optional[FinetuneSpec] = None,
        mesh=None,
        compute_dtype: str = "bfloat16",
        length_bucket: int = 128,
        rows_bucket: int = 8,
        seqs_bucket: int = 8,
        attn_impl: str = "auto",
        remat: bool = False,
        logprob_chunk: Optional[int] = 512,
        fill_bucket: Optional[int] = None,
    ):
        self.cfg = cfg
        self.mesh = mesh
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.length_bucket = length_bucket
        self.rows_bucket = rows_bucket
        self.seqs_bucket = seqs_bucket
        # Candidate row-length granularity for the packer's fill sweep
        # (None = packer default, min(length_bucket, 128)).
        self.fill_bucket = fill_bucket
        self.attn_impl = attn_impl
        # False: the backward pass finds everything kept, unconditionally.
        # True: it re-runs what does not fit, chosen per packed grid from
        # the grid's tokens and the chip's free memory (_remat_for).
        self.remat = remat
        # Column-chunk size for the chunked-logprob head (None disables);
        # only used by losses/hooks that declare wants_token_logprobs.
        self.logprob_chunk = logprob_chunk
        # Rows of a micro-batch split over the mesh's data axes: the packer
        # makes their count a multiple of that degree, or the row-wise
        # shard_maps (the attention kernel, the expert-parallel MoE layer)
        # cannot split them and every chip computes every row.
        self.rows_multiple = 1
        if mesh is not None:
            from areal_tpu.parallel.mesh import DATA_AXES

            self.rows_multiple = int(np.prod(
                [mesh.shape[a] for a in DATA_AXES]))
            params = psh.shard_params(params, mesh, cfg)
        else:
            params = jax.tree.map(jnp.asarray, params)
        if opt_cfg is not None:
            # EXPLICIT f32 master params when training. Without this the
            # first optimizer step silently promotes bf16 params to f32
            # anyway (optax's f32 lr scalar infects the update), costing a
            # retrace and a failed-donation copy on step one — and hiding
            # the master-dtype decision. f32 masters are also the quality
            # choice: bf16's ~3 significant digits round away small
            # Adam updates (the reference's Megatron DistributedOptimizer
            # keeps f32 masters for the same reason). Compute still runs
            # in compute_dtype, on the copy (compute_params). (No buffer
            # donation here: the caller's tree must stay valid — callers
            # that need the transient peak gone should drop their
            # reference.)
            params = jax.tree.map(
                lambda x: x.astype(jnp.float32)
                if jnp.issubdtype(x.dtype, jnp.floating) else x,
                params,
            )
        self._compute = None  # the compute-dtype copy; None = to rebuild
        self._compute_shared = False
        self._cast_fn = None
        self.param_cast_rebuilds = 0
        # {"local" | "bound" | "walked": rows per layer since start}: the
        # bounded expert passes' rows (``moe_rows`` in the device_report)
        self.moe_rows: Dict[str, float] = {}
        # forward()'s micro-batches over the engine's life, and those of
        # them dispatched while an earlier one's result was unfetched;
        # under a lock: several threads call forward() (algorithms/fused).
        self._infer_lock = threading.Lock()
        self.infer_mbs = 0
        self.infer_mbs_run_ahead = 0
        self.params = params
        self.opt_cfg = opt_cfg
        self.tx = None
        self.opt_state = None
        self.lr_schedule = None
        self.opt_step_count = 0
        if opt_cfg is not None:
            total = ft_spec.total_train_steps if ft_spec is not None else 1000
            self.tx, self.lr_schedule = build_optimizer(opt_cfg, total)

            def opt_init(params):
                return self.tx.init(params)

            self.opt_state = compile_watch.watched_jit(
                "train/opt_init",
                jax.jit(opt_init, out_shardings=self._opt_shardings(opt_init)),
            )(self.params)
        self._grad_fns: Dict[int, Callable] = {}
        self._fwd_fns: Dict[int, Callable] = {}
        self._apply_fn = None
        # {(R, L): what the grad programs of that packed grid keep for
        # their backward pass, and why} — see _remat_for / remat_plan.
        self._remat_plan: Dict[Tuple[int, int], Dict[str, Any]] = {}
        # One period of the layer pattern, for the train/fwd_bwd span:
        # "full" for most families, "sliding,sliding,sliding,full" (mellum).
        self._layer_kinds = ",".join(self.cfg.period_kinds)
        # Static gate for MoE router input jitter: train steps thread a
        # per-micro-batch rng key through the batch dict iff this is set
        # (key presence is part of the jit trace, so the gate must not
        # flip per step — it is fixed by the model config).
        self._router_jitter = (
            cfg.moe is not None and cfg.moe.input_jitter_eps > 0
        )

    # -------------- internals --------------

    def _opt_shardings(self, opt_init: Callable):
        """Where the optimizer state is born: each moment like the
        parameter it belongs to, everything else replicated. The moments
        are zeros that depend on no input, so left to itself the
        partitioner puts every one of them whole on the first chip
        (RESOURCE_EXHAUSTED for a model that only fits sharded). None
        without a mesh."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec

        replicated = NamedSharding(self.mesh, PartitionSpec())
        return optax.tree_utils.tree_map_params(
            self.tx, lambda _, sharding: sharding,
            jax.eval_shape(opt_init, self.params),
            jax.tree.map(lambda x: x.sharding, self.params),
            transform_non_params=lambda _: replicated,
        )

    def _mesh_ctx(self):
        if self.mesh is not None:
            return psh.activation_sharding(self.mesh)
        import contextlib

        return contextlib.nullcontext()

    # -------------- the compute-dtype copy of the weights --------------
    #
    # The programs compute with the masters' floating leaves in
    # ``compute_dtype``. That tree has the lifetime of a weights version:
    # the program that changes the weights (train_apply) writes it beside
    # the new masters, and every grad, inference and generate call reads
    # it. Any other write of ``params`` drops it, and the next reader
    # rebuilds it with one ``param_cast`` program.

    @property
    def params(self):
        """The masters (float32 under an optimizer)."""
        return self._params

    @params.setter
    def params(self, tree):
        self._params = tree
        self._compute = None
        # Nothing to cast (float32 compute; bf16 weights without an
        # optimizer): the copy IS the masters' tree, read off the leaves.
        self._copy_is_params = all(
            x.dtype == self.compute_dtype for x in jax.tree.leaves(tree)
            if jnp.issubdtype(x.dtype, jnp.floating))

    def _to_compute(self, x):
        """One leaf in the compute dtype (a non-floating one as it is)."""
        cd = self.compute_dtype
        return x.astype(cd) if jnp.issubdtype(x.dtype, jnp.floating) else x

    def _cast(self, params):
        with jax.named_scope("param_cast"):
            return jax.tree.map(self._to_compute, params)

    def compute_params(self, share: bool = False):
        """The tree the programs compute with, sharded leaf by leaf like
        the masters. ``share``: the caller keeps it beyond this call (a
        weight publish gathers it in the background, the device transport
        hands its buffers to the decoders), so the next train_apply must
        not donate it and writes a new one instead."""
        if self._copy_is_params:
            return self._params
        if self._compute is None:
            if self._cast_fn is None:

                def param_cast(params):
                    return self._cast(params)

                self._cast_fn = compile_watch.watched_jit(
                    "train/param_cast", jax.jit(param_cast))
            self._compute = self._cast_fn(self._params)
            self._compute_shared = False
            self.param_cast_rebuilds += 1
            telemetry.inc("train/param_cast_rebuilds")
            logger.info("compute-dtype copy of the weights rebuilt outside "
                        f"train_apply (#{self.param_cast_rebuilds})")
        self._compute_shared |= share
        return self._compute

    def _value_and_grad(self, lf: Callable, compute_params):
        """``jax.value_and_grad(lf, has_aux=True)`` at the compute-dtype
        copy: the gradient of the copy IS the gradient of the masters (the
        cast's transpose only widens it), so it is produced in the compute
        dtype — half the bytes of a float32 tree between the backward pass
        and the accumulation — and widened to the masters' dtype leaf by
        leaf, where the add into the carry reads it. Only the masters'
        dtypes are read, at trace time: the float32 tree is no input."""
        out, grads = jax.value_and_grad(lf, has_aux=True)(compute_params)
        with jax.named_scope("grad_accum"):
            grads = jax.tree.map(
                lambda g, p: g.astype(p.dtype), grads, self._params)
        return out, grads

    def _model_forward(
        self, params, batch: Dict[str, jnp.ndarray], with_aux: bool = False,
        remat=False,
    ):
        """``params``: the compute-dtype copy (:meth:`compute_params`).
        ``remat``: what a grad program's backward re-runs (an entry of
        ``transformer.REMAT_ENTRIES``, see :meth:`_remat_for`); a program
        that is not differentiated keeps nothing either way."""
        out, _, aux = transformer.forward(
            params,
            self.cfg,
            batch["tokens"],
            batch["positions"],
            segment_ids=batch["segment_ids"],
            attn_impl=self.attn_impl,
            remat=remat,
            return_kv=False,
            return_aux=True,
            rng=batch.get("rng"),
        )
        # Critic values [B, L] are cheap in f32; lm logits [B, L, V] stay in
        # the compute dtype — loss fns upcast per-element inside fused
        # reductions (see ppo_functional.gather_logprobs).
        out = out.astype(jnp.float32) if self.cfg.is_critic else out
        return (out, aux) if with_aux else out

    def _forward_token_logprobs(self, params, batch: Dict[str, jnp.ndarray],
                                remat=False):
        """[R, L] per-token logprobs with a CHUNKED head: the [R, L, V]
        logits grid never materializes (at a 152k vocab it is the single
        biggest activation, ~2.4GB at [8,1024] incl. its cotangent).
        Each column-chunk computes its logits
        and gathers its scores under jax.checkpoint, so backward recomputes
        chunk logits instead of storing them — the head matmul is redone
        once (~25% of forward FLOPs at 0.5B) to free the grid; role parity:
        the reference's fused vocab-parallel cross entropy
        (tensor_parallel/modules.py:1060) exists for the same reason."""
        from areal_tpu.algorithms import ppo_functional as F

        h, _, aux = transformer.forward(
            params, self.cfg,
            batch["tokens"], batch["positions"],
            segment_ids=batch["segment_ids"],
            attn_impl=self.attn_impl, remat=remat,
            return_kv=False, return_aux=True, return_hidden=True,
            rng=batch.get("rng"),
        )
        R, L, D = h.shape
        C = self.logprob_chunk or L
        if L % C != 0:
            C = L  # bucketing guarantees divisibility in practice

        @jax.checkpoint
        def chunk_scores(h_c, lab_c):
            logits_c = transformer.apply_head(params, self.cfg, h_c)
            from areal_tpu.ops.xent import gather_logprobs

            return gather_logprobs(logits_c, lab_c)

        # "xent" names the label shifts and the chunking around the head
        # (apply_head's own "head" scope is the innermost inside it).
        with jax.named_scope("xent"):
            labels = F.next_token_labels(batch["tokens"])
            if C == L:
                s = chunk_scores(h, labels)
            else:
                n = L // C
                hs = h.reshape(R, n, C, D).transpose(1, 0, 2, 3)
                ls = labels.reshape(R, n, C).transpose(1, 0, 2)
                s = jax.lax.map(lambda args: chunk_scores(*args), (hs, ls))
                s = s.transpose(1, 0, 2).reshape(R, L)
            return F.shift_mask_scores(s, batch["segment_ids"]), aux

    def _use_chunked_logprobs(self, fn) -> bool:
        return (
            self.logprob_chunk is not None
            and not self.cfg.is_critic
            and bool(getattr(fn, "wants_token_logprobs", False))
        )

    def _loss_and_grads(self, loss_fn: LossFn, remat, params, batch,
                        denom, aux_scale):
        """((loss, stats), grads) of one micro-batch at ``params``, the
        compute-dtype copy."""
        use_lp = self._use_chunked_logprobs(loss_fn)

        def lf(p):
            if use_lp:
                out, aux = self._forward_token_logprobs(p, batch, remat)
            else:
                out, aux = self._model_forward(p, batch, with_aux=True,
                                               remat=remat)
            loss_sum, stats = loss_fn(out, batch)
            loss = loss_sum / jnp.maximum(denom, 1.0)
            if aux:
                # MoE balancing losses (reference utils/moe.py aux
                # tracker), surfaced under a reserved "moe_" prefix
                # (train_batch divides the stats by the mb count).
                if "aux_total" in aux:
                    loss = loss + aux["aux_total"] * aux_scale
                stats = dict(stats, **{
                    k if k in dsa_mod.SUMMED_AUX else f"moe_{k}": v
                    for k, v in aux.items()
                })
            return loss, stats

        return self._value_and_grad(lf, params)

    def _get_apply_fn(self, skip_rule) -> Callable:
        """Optimizer update with donated buffers and an optional on-device
        early-stop gate.

        ``skip_rule=(num_key, den_key)``: if given, the update is SKIPPED
        (params returned unchanged) when stats[num]/stats[den] > cap — the
        reference's early-stop checks the importance ratio BEFORE stepping
        (ppo_interface.py:735-760).

        Measured note (r2): a single-dispatch lax.scan over stacked
        micro-batches was tried here and LOST ~40% throughput on v5e — the
        param-sized grad carry through the while loop costs more than the
        per-micro-batch dispatches it saves. The per-micro-batch loop with
        async dispatch (no host syncs until the final stats fetch) is the
        fast path on TPU.
        """
        key = ("apply", skip_rule)
        if key in self._grad_fns:
            return self._grad_fns[key]

        def train_apply(params, opt_state, grads, stats, cap, old_copy):
            del old_copy  # donated: the new copy is written in its place
            with jax.named_scope("grad_clip"):
                gnorm = optax.global_norm(grads)
            # grad_clip and adam are scoped in build_optimizer's chain.
            updates, new_opt = self.tx.update(grads, opt_state, params)
            with jax.named_scope("param_update"):
                new_params = optax.apply_updates(params, updates)
                if skip_rule is not None:
                    num, den = skip_rule
                    ratio = stats[num] / jnp.maximum(stats[den], 1.0)
                    apply = (cap <= 0.0) | (ratio <= cap)
                    new_params = jax.tree.map(
                        lambda new, old: jnp.where(apply, new, old),
                        new_params, params,
                    )
                    new_opt = jax.tree.map(
                        lambda new, old: jnp.where(apply, new, old)
                        if hasattr(new, "dtype") else new,
                        new_opt, opt_state,
                    )
                else:
                    apply = jnp.asarray(True)
                # The next weights version's compute copy, cast where the
                # masters are written (of a skipped update: the old ones):
                # one more output of the fused update, which is ONE kernel
                # under one name — so it stays in this scope, and
                # "param_cast" names the program that only casts.
                new_copy = (None if self._copy_is_params else jax.tree.map(
                    self._to_compute, new_params))
            return new_params, new_opt, new_copy, gnorm, apply

        # Donate params + opt_state (aliased into new_params/new_opt) AND
        # grads: no output aliases the grad buffers (XLA warns they are
        # "not usable" as outputs), but donating them still lets the
        # optimizer's f32 transients reuse those 2 bytes/param in place —
        # measured on a 16 GB v5e with the 0.5B model, withdrawing the
        # grads donation OOMs the apply step. The previous compute copy is
        # donated so the new one takes its buffers (kept as an argument
        # though no op reads it); None where there is none to give.
        self._grad_fns[key] = compile_watch.watched_jit(
            "train/apply",
            jax.jit(train_apply, donate_argnums=(0, 1, 2, 5),
                    keep_unused=True),
        )
        return self._grad_fns[key]

    # -------------- what the backward pass re-runs --------------
    #
    # With ``remat`` on, a grad program keeps between its forward and its
    # backward pass the most that fits (an entry of
    # transformer.REMAT_ENTRIES), chosen once per packed grid by
    # arithmetic — no trial compile: tracing and lowering are most of a
    # warm start. The constants are calibrated against the chip compiler's
    # ``memory_analysis()`` of the benchmark's grids (PERF.md §5, PR 28).

    def _device_bytes_limit(self) -> Optional[int]:
        """The memory one chip gives this process; None where the device
        does not say (the CPU), and then nothing more than today is kept."""
        stats = jax.local_devices()[0].memory_stats() or {}
        return stats.get("bytes_limit")

    def _rows_on_chip(self, R: int) -> int:
        """Rows of an [R, L] grid that one chip holds (split over the data
        axes)."""
        return -(-R // self.rows_multiple)

    def _remat_kept_bytes(self, R: int, L: int) -> Dict[str, int]:
        """Estimated bytes each entry keeps on ONE chip for the grid
        [R, L]: its rows split over the data axes (an axis that splits
        the sequence or the widths is not counted: an over-estimate)."""
        rows = self._rows_on_chip(R)
        group = self.cfg.group_size
        if self.cfg.dsa is not None:  # the selection's kernels' own tile
            full = dsa_mod.kernel_padded_len(self.attn_impl, L)
        else:
            full = kernel_padded_len(self.attn_impl, L,
                                     head_dim=self.cfg.head_dim, group=group)
        window = kernel_padded_len(self.attn_impl, L, self.cfg.sliding_window,
                                   self.cfg.head_dim, group)
        return transformer.remat_kept_bytes(
            self.cfg, rows * L, self.compute_dtype.itemsize,
            full_tokens=rows * (full or 0),
            window_tokens=rows * (window or 0))

    def _remat_budget_bytes(self, R: int, L: int) -> int:
        """The bytes of kept activations one chip has room for: its limit
        less a margin, less the trees the engine holds there (masters,
        optimizer state, the gradient carry, the compute-dtype copy of the
        weights), less the rest of a grad program — over what the
        compiler's heap takes per kept byte."""
        limit = self._device_bytes_limit()
        if limit is None:
            return 0
        params = _bytes_on_chip(self.params)
        weights = self._weights_bytes()
        copy = 0 if self._copy_is_params else weights
        resident = 2 * params + _bytes_on_chip(self.opt_state) + copy
        free = limit * (1.0 - _LIMIT_MARGIN) - resident
        return int((free - self._remat_reserve_bytes(R, L, weights))
                   / _HEAP_PER_KEPT_BYTE)

    def _weights_bytes(self) -> int:
        """Bytes on one chip of a tree of the masters' shapes in the
        compute dtype: the copy (resident, unless the masters are it) and
        a program's gradient."""
        masters = jax.tree.leaves(self.params)[0].dtype.itemsize
        return (_bytes_on_chip(self.params) * self.compute_dtype.itemsize
                // masters)

    def _reckoned_heap_bytes(self, R: int, L: int, kept: int) -> int:
        """What the budget's arithmetic takes a grad program of the grid
        [R, L] that keeps ``kept`` bytes to need of the chip beside what
        is resident: the sum :meth:`_remat_budget_bytes` sets against the
        free bytes. It answers to the compiler's ``temp_bytes`` of that
        program (the gradient carry it returns is counted resident) — the
        compile ledger files both, ``remat_plan`` shows both."""
        return int(kept * _HEAP_PER_KEPT_BYTE + self._remat_reserve_bytes(
            R, L, self._weights_bytes()))

    def _remat_reserve_bytes(self, R: int, L: int, weights: int) -> int:
        """A grad program's temporaries besides the kept activations: the
        larger of its two phases — the head (one chunk's logits with their
        softmax, or the whole [rows, L, vocab] grid where the chunk does
        not divide L) or the layers' backward (the gradient in the compute
        dtype, ``weights`` bytes, and one layer's working set by its
        widest activation)."""
        cfg, size = self.cfg, self.compute_dtype.itemsize
        rows = self._rows_on_chip(R)
        chunk = self.logprob_chunk if (
            self.logprob_chunk and L % self.logprob_chunk == 0) else L
        head = rows * chunk * cfg.vocab_size * _HEAD_BYTES_PER_LOGIT
        if cfg.is_hybrid:
            layer = self._mixer_layer_width()
        elif cfg.moe is None:
            layer = (max(cfg.intermediate_dim, cfg.q_dim, cfg.hidden_dim)
                     * _LAYER_COPIES)
        else:  # a token's top_k rows, through the expert exchange or not
            exchange = (self.mesh is not None
                        and dict(self.mesh.shape).get("ep", 1) > 1
                        and not cfg.moe.is_share)
            layer = cfg.moe.top_k * cfg.hidden_dim * (
                _MOE_LAYER_COPIES if exchange else _MOE_LOCAL_LAYER_COPIES)
        return int(max(head, weights + rows * L * layer * size))

    def _mixer_layer_width(self) -> int:
        """Elements a token costs the backward of the costliest layer of a
        hybrid model — a layer is one mixer (or a whole block, costed by
        its wider half), so the widest of: a dense MLP's width, an expert
        layer's ``top_k`` rows at the width its experts read (the latent
        one where the model has it; never exchanged) or its shared
        expert's width, a Mamba-2 mixer's in-projection or its scan's
        [heads, chunk] decays (float32: two elements), attention's q."""
        cfg, widths = self.cfg, [self.cfg.q_dim * _LAYER_COPIES]
        if cfg.moe is None or any(has_dense_ffn(k) for k in cfg.layer_kinds):
            widths.append(cfg.intermediate_dim * _LAYER_COPIES)
        if cfg.s6 is not None:
            # an S6 mixer's in-projection; its scan's inputs and their
            # gradients in float32 (x, Δ, y and the three back: twelve
            # compute-dtype elements a channel)
            widths.append(max(2 * cfg.s6.d_inner * _LAYER_COPIES,
                              12 * cfg.s6.d_inner))
        if cfg.moe is not None:
            widths += [
                cfg.moe.top_k * (
                    cfg.moe.latent_dim * _LATENT_MOE_LAYER_COPIES
                    if cfg.moe.latent_dim
                    else cfg.hidden_dim * _MOE_LOCAL_LAYER_COPIES),
                (cfg.moe.shared_intermediate_dim or 0) * _LAYER_COPIES]
        if cfg.ssm is not None:
            widths.append(max(cfg.ssm.in_proj_dim,
                              2 * cfg.ssm.n_heads * cfg.ssm.chunk_size)
                          * _LAYER_COPIES)
        return max(widths)

    def _remat_for(self, R: int, L: int):
        """What the grad programs of the packed grid [R, L] keep for their
        backward pass: False (``remat`` off: everything), else the entry
        of transformer.REMAT_ENTRIES that re-runs least among those whose
        kept bytes fit the chip — decided at the grid's first dispatch and
        recorded in ``remat_plan``. Pipeline stages rematerialise by their
        own schedule and keep "full"."""
        if not self.remat:
            return False
        plan = self._remat_plan.get((R, L))
        if plan is None:
            kept = self._remat_kept_bytes(R, L)
            budget = self._remat_budget_bytes(R, L)
            pp_on, _ = ppl.pp_engagement(self.mesh, self.cfg, R, L)
            entry = "full" if pp_on else choose_remat(kept, budget)
            plan = self._remat_plan[(R, L)] = {
                "entry": entry, "kept_bytes_estimate": kept[entry],
                "budget_bytes": budget,
                "reckoned_heap_bytes": self._reckoned_heap_bytes(
                    R, L, kept[entry]),
                "fell_back": False,
            }
            logger.info(f"remat plan for grid {R}x{L}: {plan}")
        return plan["entry"]

    def _remat_fall_back(self, R: int, L: int) -> bool:
        """The estimate was wrong — a grad program of this grid did not
        fit: drop the grid one entry towards "full" and say so in the
        record. False where there is nothing left to drop."""
        plan = self._remat_plan.get((R, L))
        entries = transformer.REMAT_ENTRIES
        if plan is None or plan["entry"] == entries[0]:
            return False
        entry = entries[entries.index(plan["entry"]) - 1]
        logger.warning(
            f"grad program of grid {R}x{L} does not fit keeping "
            f"{plan['entry']!r}; falling back to {entry!r}")
        kept = self._remat_kept_bytes(R, L)[entry]
        # what was reckoned for the entry that did not fit stays on record
        failed = plan.get("failed", []) + [
            {k: plan[k] for k in ("entry",) + _PLAN_LABEL}]
        plan.update(entry=entry, fell_back=True, kept_bytes_estimate=kept,
                    reckoned_heap_bytes=self._reckoned_heap_bytes(R, L, kept),
                    failed=failed)
        return True

    def remat_plan(self) -> Dict[str, Dict[str, Any]]:
        """{"RxL": {entry, kept_bytes_estimate, budget_bytes,
        reckoned_heap_bytes, fell_back}} of every packed grid a grad
        program was dispatched for (read like
        window_attention.geometry_counts(); in the trainer worker's
        ``device_report``): the engine's side of the sum. Beside it, once
        the grid's grad programs have compiled, the compiler's:
        ``compiled`` = {temp_bytes, peak_bytes, cache} of the one that
        needs most, as the compile ledger filed it under this grid's label
        (absent on a backend that gives no statistics; engines of one
        process that share a grid and an entry share the records).
        ``failed``: the entries that did not fit, each with what was
        reckoned for it. Empty with ``remat`` off."""
        records = [r for r in compile_watch.executables("train_grad_sliced")
                   if r["temp_bytes"] is not None]
        out = {}
        for (R, L), plan in self._remat_plan.items():
            grid = out[f"{R}x{L}"] = dict(plan)
            mine = [r for r in records
                    if r["label"].get("grid") == f"{R}x{L}"
                    and r["label"].get("remat") == plan["entry"]]
            if mine:
                top = max(mine, key=lambda r: r["temp_bytes"])
                grid["compiled"] = {
                    "temp_bytes": top["temp_bytes"],
                    "peak_bytes": max(r["peak_bytes"] for r in mine),
                    "cache": top["cache"]}
        return out

    def _dispatch_grad(self, loss_fn: LossFn, args: list, carry,
                       R: int, L: int):
        """Run one grad program of the grid [R, L] on ``args`` and the
        carry of the micro-batches before it (None for the first).
        Compiling it can fail for memory — the estimate behind the grid's
        entry is arithmetic; then the grid falls back one entry and is
        traced again (only ever in warm-up: a grid compiles once)."""
        if carry is not None:
            args = args + [carry]
        while True:
            remat = self._remat_for(R, L)
            fn = self._get_sliced_grad_fn(loss_fn, carry is not None, R, remat)
            # which grid and entry this executable is, and what the
            # engine reckoned for it (read only if jax compiles it now)
            plan = self._remat_plan.get((R, L)) or {}
            compile_watch.label(
                "train_grad_sliced", grid=f"{R}x{L}", carry=carry is not None,
                remat=remat, **{k: plan[k] for k in _PLAN_LABEL if k in plan})
            try:
                with self._mesh_ctx(), dispatch_label("train"):
                    return fn(*args)
            except jax.errors.JaxRuntimeError as e:
                if ("RESOURCE_EXHAUSTED" not in str(e)
                        or not self._remat_fall_back(R, L)):
                    raise

    # -------------- upload-once uniform batches --------------
    #
    # Per-micro-batch h2d transfers and eager dispatches between them
    # stall the device queue (a PPO step made ~70 of them). The
    # uniform packer (backend/microbatch.py) makes every micro-batch the
    # same [R, L] shape, so the WHOLE batch uploads once as [n_mbs*R, L]
    # grids and each grad step slices its rows on device by a traced index.

    def _gauge_blocks_needed(self, role: str,
                             mbs: List[mbu.MicroBatch]) -> None:
        """``<role>/attn_blocks_needed_frac``: the share of the static
        mask's key blocks that the attention kernel runs on these packed
        grids, over every attention layer (1.0 = it skips nothing) — where
        the kernel runs them (not on the XLA reference)."""
        windows = self.cfg.attention_windows()
        grids = [mb.grids["segment_ids"] for mb in mbs]
        if not windows or any(
                kernel_padded_len(self.attn_impl, seg.shape[1], w) is None
                for seg in grids for w in windows):
            return
        from areal_tpu.ops.pallas import window_attention

        needed = static = 0
        for window, layers in windows.items():
            for seg in grids:
                n, s = window_attention.count_needed(
                    seg, window, self.cfg.head_dim, self.cfg.group_size)
                needed += layers * n
                static += layers * s
        telemetry.set_gauge(f"{role}/attn_blocks_needed_frac",
                            needed / static)

    def upload_uniform(
        self, input_: SequenceSample, mb_spec: MicroBatchSpec
    ) -> "UniformBatch":
        with telemetry.span("train/split_pack"):
            mbs = mbu.split_into_microbatches(
                input_, mb_spec, length_bucket=self.length_bucket,
                rows_bucket=self.rows_bucket, seqs_bucket=self.seqs_bucket,
                fill_bucket=self.fill_bucket, rows_multiple=self.rows_multiple,
            )
            telemetry.set_gauge("train/pack_fill", mbu.pack_fill(mbs))
            telemetry.set_gauge("train/docs_per_row", mbu.docs_per_row(mbs))
            if self.cfg.gdn is not None:
                telemetry.set_gauge(
                    "train/gdn_resets_in_chunk_per_row",
                    mbu.resets_in_chunk_per_row(mbs, self.cfg.gdn.chunk_size))
            if self.cfg.kda is not None:
                telemetry.set_gauge(
                    "train/kda_resets_in_chunk_per_row",
                    mbu.resets_in_chunk_per_row(mbs, self.cfg.kda.chunk_size))
            if self.cfg.shortconv is not None:
                telemetry.set_gauge("train/shortconv_resets_per_row",
                                    mbu.shortconv_resets_per_row(mbs))
            self._gauge_blocks_needed("train", mbs)
        R, L = mbs[0].layout.shape
        pp_on, ring_on = ppl.pp_engagement(self.mesh, self.cfg, R, L)
        telemetry.set_gauge("train/pp_engaged", pp_on)
        telemetry.set_gauge("train/ring_engaged", ring_on)
        telemetry.set_gauge("train/moe_ep_engaged",
                            self._ep_engagement(R, L, pp_on))
        S = max(len(mb.seq_mask) for mb in mbs)
        S = mbu.packing.round_up(S, self.seqs_bucket)
        grids: Dict[str, jnp.ndarray] = {}
        seq: Dict[str, jnp.ndarray] = {}

        def pad_stack(key, getter, dtype=None):
            rows = []
            for mb in mbs:
                v = np.asarray(getter(mb))
                pad = np.zeros((S,) + v.shape[1:], v.dtype)
                pad[: len(v)] = v
                rows.append(pad)
            seq[key] = jnp.asarray(np.stack(rows))

        with telemetry.span("train/upload", **_pack_attrs(mbs)):
            for k in mbs[0].grids:
                grids[k] = jnp.asarray(
                    np.concatenate([mb.grids[k] for mb in mbs], axis=0)
                )
            pad_stack("seq_rows", lambda mb: mb.seq_rows)
            pad_stack("seq_first_cols", lambda mb: mb.seq_first_cols)
            pad_stack("seq_last_cols", lambda mb: mb.seq_last_cols)
            pad_stack("seq_mask", lambda mb: mb.seq_mask)
            for k in mbs[0].scalars:
                pad_stack(k, lambda mb, k=k: mb.scalars[k])
        return UniformBatch(mbs=mbs, R=R, L=L, S=S, grids=grids, seq=seq)

    def run_prep(
        self,
        ub: "UniformBatch",
        prep_fn: Callable,
        prep_key: object,
        scalars: Optional[Dict[str, float]] = None,
    ) -> Dict[str, jnp.ndarray]:
        """Run a jitted full-batch preprocessing step on device:
        ``prep_fn(grids, seq, R, scalars) -> (extra_grids, out_scalars)``.
        The extra grids are merged into ``ub.grids`` (available to loss
        fns); the returned scalars stay on device for the end-of-step fetch.
        ``scalars`` are dynamic device args (e.g. an adaptive KL coef) so
        their drift never retraces."""
        key = ("prep", prep_key, ub.n_mbs, ub.R)
        if key not in self._grad_fns:
            R = ub.R

            def adv_prep(grids, seq, sc):
                return prep_fn(grids, seq, R, sc)

            self._grad_fns[key] = compile_watch.watched_jit(
                "train/prep", jax.jit(adv_prep)
            )
        with telemetry.span("train/adv_prep"):
            sc = {
                k: jnp.asarray(v, jnp.float32)
                for k, v in (scalars or {}).items()
            }
            compile_watch.label("adv_prep", grid=f"{ub.R}x{ub.L}",
                                n_mbs=ub.n_mbs)
            with self._mesh_ctx():
                extra, out_scalars = self._grad_fns[key](ub.grids, ub.seq, sc)
            ub.grids.update(extra)
        return out_scalars

    def _get_sliced_grad_fn(
        self, loss_fn: LossFn, with_carry: bool, R: int, remat=False,
    ) -> Callable:
        """The grad program: fused grad + accumulate of ONE micro-batch, one
        dispatch each. It takes the FULL uploaded batch and a traced
        micro-batch index and slices its rows/seq-entries on device.

        ``with_carry``: the (loss, stats, grads) accumulators from the
        previous micro-batch ride through the jit (donated) and the adds
        happen on device instead of as eager tree-map adds between
        dispatches.

        ``scale`` multiplies this micro-batch's loss/grads ("mb" normalize
        scope passes 1/n_mbs); ``aux_scale`` multiplies the MoE balancing
        loss so its total contribution over the whole batch equals one
        aux_total regardless of the micro-batch count.

        ``R`` (rows per micro-batch) is part of the cache key: two packings
        can share the total grid shape while slicing differently. So is
        ``remat``: what the backward pass re-runs, chosen per packed grid
        by :meth:`_remat_for` — a grid that changes its entry is traced
        again. Keyed by the function OBJECT (keeps it alive): an id() key
        could be reused by a new closure after GC and silently run stale
        code."""
        key = (loss_fn, with_carry, R, remat)
        if key not in self._grad_fns:
            micro = self._get_micro_grad_fn(loss_fn, R, remat)

            def train_grad_sliced(params, grids, seq, mb_idx, denom, scale,
                                  aux_scale, carry=None):
                (loss, stats), grads = micro(params, grids, seq, mb_idx,
                                             denom, aux_scale)
                return _accumulate(loss, stats, grads, scale, carry)

            donate = (7,) if with_carry else ()
            self._grad_fns[key] = compile_watch.watched_jit(
                "train/grad_sliced",
                jax.jit(train_grad_sliced, donate_argnums=donate),
            )
        return self._grad_fns[key]

    def _get_micro_grad_fn(self, loss_fn: LossFn, R: int, remat) -> Callable:
        """((loss, stats), grads) of micro-batch ``mb_idx`` of an uploaded
        batch, jitted by itself: what the two grad programs of a packed
        grid share. jax keeps a jitted function's trace by its arguments'
        shapes, so the program traced second finds the whole model's
        forward and backward — most of a warm start — traced already and
        traces its tail alone. ``inline``: the kept trace is copied into
        the program's own, which lowers to the text a single trace gave
        (tests/test_mellum_parity.py holds it to the commit before), so
        the programs that run, and their entries in the persistent cache,
        are the ones each variant had when it traced the model itself."""
        key = ("micro", loss_fn, R, remat)
        if key not in self._grad_fns:

            def train_grad_micro(params, grids, seq, mb_idx, denom,
                                 aux_scale):
                batch = {
                    k: jax.lax.dynamic_slice_in_dim(g, mb_idx * R, R, 0)
                    for k, g in grids.items()
                }
                for k, v in seq.items():
                    batch[k] = jax.lax.dynamic_index_in_dim(
                        v, mb_idx, 0, keepdims=False
                    )
                return self._loss_and_grads(
                    loss_fn, remat, params, batch, denom, aux_scale)

            self._grad_fns[key] = jax.jit(train_grad_micro, inline=True)
        return self._grad_fns[key]

    def train_uniform(
        self,
        ub: "UniformBatch",
        loss_fn: LossFn,
        loss_weight_fn: Callable[[mbu.MicroBatch], float],
        mb_indices: Optional[List[int]] = None,
        token_normalize_scope: str = "global",
        skip_update_rule: Optional[Tuple[str, str, float]] = None,
        extra_fetch: Optional[Dict[str, jnp.ndarray]] = None,
    ) -> Dict[str, float]:
        """One optimizer step over the micro-batches ``mb_indices`` (default
        all) of an uploaded batch: n_mbs grad dispatches + 1 apply + ONE
        host sync. See train_batch for semantics."""
        assert self.tx is not None, "engine built without an optimizer"
        idxs = list(mb_indices) if mb_indices is not None else list(range(ub.n_mbs))
        weights = [float(loss_weight_fn(ub.mbs[i])) for i in idxs]
        total_w = sum(weights)
        rule = None
        cap = 0.0
        if skip_update_rule is not None and skip_update_rule[2]:
            rule = (skip_update_rule[0], skip_update_rule[1])
            cap = float(skip_update_rule[2])
        glob = token_normalize_scope == "global"
        scale = 1.0 if glob else 1.0 / len(idxs)
        aux_scale = (1.0 / len(idxs)) if glob else 1.0
        carry = None
        seq = ub.seq
        if self._router_jitter:
            # Stacked per-mb jitter keys ride the seq dict: the sliced grad
            # fn's dynamic_index_in_dim over axis 0 hands each micro-batch
            # its own [2] key (one base key per optimizer step, the only
            # derivation). ub.seq itself stays untouched so the
            # run_prep jit (keyed on the seq structure) never retraces.
            seq = dict(
                ub.seq,
                rng=jax.random.split(
                    jax.random.PRNGKey(self.opt_step_count), ub.n_mbs
                ),
            )
        span_attrs = {}
        if self.cfg.ssm is not None or self.cfg.s6 is not None:
            # documents that begin inside a row: where the scans reset
            starts = sum(col > 0 for i in idxs
                         for _, col in ub.mbs[i].layout.placements)
            telemetry.set_gauge("train/ssm_segment_starts", starts)
            span_attrs["ssm_segment_starts"] = starts
        if self.cfg.ssm is not None:
            from areal_tpu.models import ssm as ssmmod

            # the share of the traced Mamba-2 scans that run the kernel
            # (models/ssm.scan_impl_counts; None before the first trace)
            frac = ssmmod.ssd_kernel_frac()
            if frac is not None:
                telemetry.set_gauge("train/ssd_kernel_frac", frac)
                span_attrs["ssd_kernel_frac"] = frac
        if self.cfg.kda is not None:
            from areal_tpu.models import kda as kdamod

            # the share of the traced channel-decay rules that run the
            # kernel pair (models/kda.rule_impl_counts)
            frac = kdamod.rule_kernel_frac()
            if frac is not None:
                telemetry.set_gauge("train/kda_kernel_frac", frac)
                span_attrs["kda_kernel_frac"] = frac
            # ... and of the traced mixers, those whose ends (β, the l2
            # norms, the decay's activation, the gated norm) ran inside
            # that pair, all heads at once (models/kda.mixer_norm_counts)
            frac = kdamod.norms_in_kernel_frac()
            if frac is not None:
                telemetry.set_gauge("train/kda_norms_in_kernel_frac", frac)
                span_attrs["kda_norms_in_kernel_frac"] = frac
        if self.cfg.gdn is not None:
            from areal_tpu.models import gdn as gdnmod

            # the share of the traced gated delta rules that run the
            # kernel pair (models/gdn.rule_impl_counts)
            frac = gdnmod.rule_kernel_frac()
            if frac is not None:
                telemetry.set_gauge("train/gdn_kernel_frac", frac)
                span_attrs["gdn_kernel_frac"] = frac
            # ... and of the traced mixers, those whose two norms ran
            # inside that pair (models/gdn.mixer_norm_counts)
            frac = gdnmod.norms_in_kernel_frac()
            if frac is not None:
                telemetry.set_gauge("train/gdn_norms_in_kernel_frac", frac)
                span_attrs["gdn_norms_in_kernel_frac"] = frac
        if self.cfg.mla is not None:
            # what this grid's remat entry keeps of one latent-attention
            # branch, a token (transformer.attention_kept_bytes_per_token)
            kept = transformer.attention_kept_bytes_per_token(
                self.cfg, self._remat_for(ub.R, ub.L),
                self.compute_dtype.itemsize,
                kernel=kernel_padded_len(
                    self.attn_impl, ub.L,
                    head_dim=self.cfg.head_dim) is not None)
            telemetry.set_gauge("train/mla_kept_bytes_per_token", kept)
            span_attrs["mla_kept_bytes_per_token"] = kept
        with telemetry.span("train/fwd_bwd", n_mbs=len(idxs),
                            grid=f"{ub.R}x{ub.L}",
                            remat=str(self._remat_for(ub.R, ub.L)),
                            layer_kinds=self._layer_kinds, **span_attrs), \
                memwatch.watermark("train/fwd_bwd"):
            for i, w in zip(idxs, weights):
                denom = total_w if glob else w
                args = [
                    self.compute_params(), ub.grids, seq,
                    jnp.asarray(i, jnp.int32),
                    jnp.asarray(denom, jnp.float32),
                    jnp.asarray(scale, jnp.float32),
                    jnp.asarray(aux_scale, jnp.float32),
                ]
                carry = self._dispatch_grad(loss_fn, args, carry,
                                            ub.R, ub.L)
        drop: Tuple[str, ...] = ()
        if self.cfg.dsa is not None and not dsa_mod.counts_fit(
                n for i in idxs for n in ub.mbs[i].layout.seqlens):
            # the int32 sums of this step wrapped: no count, not a wrong one
            logger.warning(
                "a step of %d tokens has more than %d causal pairs: its "
                "dsa_* counts are dropped (int32 on the device)",
                sum(ub.mbs[i].n_tokens for i in idxs), dsa_mod.COUNT_MAX)
            drop = dsa_mod.SUMMED_AUX
        return self._apply_and_fetch(
            carry, rule, cap, extra_fetch, n_mbs=len(idxs),
            total_tokens=float(sum(ub.mbs[i].n_tokens for i in idxs)),
            total_w=total_w, drop=drop,
        )

    def _apply_and_fetch(
        self, carry, rule, cap: float,
        extra_fetch: Optional[Dict[str, jnp.ndarray]],
        n_mbs: int, total_tokens: float, total_w: float,
        drop: Tuple[str, ...] = (),
    ) -> Dict[str, float]:
        """The end of an optimizer step, shared by train_uniform and
        train_batch: dispatch the apply, then the step's ONE blocking
        fetch of every scalar, then host-side stats. Nothing syncs between
        the last grad dispatch and the fetch — the device's own timeline
        (programs ``train_grad*`` / ``train_apply`` in a capture) gives
        the gradient / apply split. ``drop``: statistics the step must not
        report (sums that did not fit their type)."""
        loss_acc, stats_acc, grads_acc = carry
        with telemetry.span("train/optimizer"):
            with telemetry.span("train/apply_dispatch"), self._mesh_ctx():
                # A copy someone else holds (compute_params(share=True))
                # is theirs to keep: not donated.
                old_copy = None if self._compute_shared else self._compute
                compile_watch.label("train_apply", skip_rule=rule is not None)
                self._params, self.opt_state, self._compute, gnorm, \
                    applied = self._get_apply_fn(rule)(
                        self._params, self.opt_state, grads_acc,
                        dict(stats_acc), jnp.asarray(cap, jnp.float32),
                        old_copy,
                    )
                self._compute_shared = False
            with telemetry.span("train/fetch_stats"):
                # optax evaluated the schedule at the PRE-increment count.
                applied_lr = float(self.lr_schedule(self.opt_step_count))
                # ONE host round trip for all scalars (each float() would
                # be a separate device→host sync).
                fetched = jax.device_get({
                    **stats_acc, **(extra_fetch or {}), "loss": loss_acc,
                    "grad_norm": gnorm, "update_applied": applied,
                })
                for k in drop:
                    fetched.pop(k, None)
            # The span that closes the step carries the step's routing
            # health (MoE models; nothing for a dense one).
            with telemetry.span("train/finish_stats",
                                **_moe_step_stats(fetched, n_mbs),
                                **{k: float(fetched[k])
                                   for k in dsa_mod.SUMMED_AUX
                                   if k in fetched}):
                # A skipped (early-stopped) update must not advance the LR
                # schedule: optax's internal count is an array leaf and
                # was reverted by the gate; keep the host-side mirror in
                # lockstep (reference abandon-minibatch semantics).
                if bool(fetched["update_applied"]):
                    self.opt_step_count += 1
                # Engine bookkeeping keys are written AFTER the user stats
                # and would clobber same-named loss_fn stats — keep them
                # namespaced.
                out = self._finish_stats(fetched, n_mbs)
                out["lr"] = applied_lr
                out["total_tokens"] = total_tokens
                out["loss_weight"] = total_w
                telemetry.inc("train/tokens", total_tokens)
                telemetry.inc(
                    "train/optimizer_steps",
                    1.0 if bool(fetched["update_applied"]) else 0.0)
        return out

    def _ep_engagement(self, batch: int, seq_len: int, pp_on: float) -> float:
        """0/1 gauge: will the MoE expert-parallel path engage
        for this shape? Mirrors the forward gate (transformer._block):
        never inside pipeline stages (already-manual regions — there GSPMD
        alone handles the ep-sharded weights), otherwise moe.ep_eligible
        on the engine mesh."""
        if pp_on:
            return 0.0
        return float(moe_mod.ep_eligible(
            self.mesh, getattr(self.cfg, "moe", None), batch, seq_len
        ))

    # Per-expert routed-load shares cluster around 1/E — log-ish buckets.
    _EXPERT_LOAD_BUCKETS = (0.001, 0.002, 0.005, 0.01, 0.02, 0.05,
                            0.1, 0.2, 0.5, 1.0)

    def _finish_stats(self, fetched: Dict[str, Any],
                      n_mbs: int) -> Dict[str, float]:
        """Host-side stat post-processing shared by train_batch and the
        uniform path. Vector-valued stats — the [E] ``moe_expert_load``
        histogram — are split off BEFORE scalar conversion (float() on a
        vector raises) and published as a telemetry distribution; "moe_"
        stats are per-mb means accumulated as sums, so divide by the mb
        count (but for ``_MOE_SUMMED``); the routing-health scalars also
        land on the scrape as ``train/moe_*`` gauges
        (docs/observability.md; the sentinel ``expert_collapse`` rule
        baselines the load ratio)."""
        n_mbs = max(n_mbs, 1)
        vec = {k: v for k, v in fetched.items()
               if getattr(v, "ndim", 0) > 0 and np.size(v) > 1}
        out = {k: float(v) for k, v in fetched.items() if k not in vec}
        for k in out:
            if k.startswith("moe_") and k not in _MOE_SUMMED:
                out[k] /= n_mbs
        load = vec.get("moe_expert_load")
        if load is not None:
            for share in np.asarray(load, np.float64).reshape(-1) / n_mbs:
                telemetry.observe("train/moe_expert_load_dist",
                                  float(share),
                                  buckets=self._EXPERT_LOAD_BUCKETS)
        for stat, gauge in (
            ("moe_dropped_frac", "train/moe_dropped_frac"),
            ("moe_expert_load_ratio", "train/moe_expert_load_ratio"),
            ("moe_local_rows", "train/moe_local_rows"),
            ("moe_full_passes", "train/moe_full_passes"),
            ("moe_walked_rows", "train/moe_walked_rows"),
        ):
            if stat in out:
                telemetry.set_gauge(gauge, out[stat])
        if "dsa_queries" in out:
            for stat, gauge in _DSA_GAUGES:
                telemetry.set_gauge(gauge, out[stat])
            telemetry.set_gauge(
                "train/dsa_selecting_query_frac",
                out["dsa_selecting_queries"] / max(out["dsa_queries"], 1.0))
        if "moe_walked_rows" in out:
            for rows in ("local", "bound", "walked"):
                self.moe_rows[rows] = (self.moe_rows.get(rows, 0.0)
                                       + out.get(f"moe_{rows}_rows", 0.0))
        if out.get("moe_full_passes"):
            logger.warning(
                f"{out['moe_full_passes']:g} of {out['moe_passes']:g} expert "
                "passes a layer ran on the whole sorted buffer this step: "
                "the router sent a shard more rows than its bound holds "
                "(models/moe.sorted_rows); exact, only slower")
        return out

    # -------------- TrainableEngine API --------------

    def train_batch(
        self,
        input_: SequenceSample,
        mb_spec: MicroBatchSpec,
        loss_fn: LossFn,
        loss_weight_fn: Callable[[mbu.MicroBatch], float],
        token_normalize_scope: str = "global",
        version_steps: int = 0,
        skip_update_rule: Optional[Tuple[str, str, float]] = None,
    ) -> Dict[str, float]:
        """One optimizer step over the whole of ``input_``: pack it into
        micro-batches of one shape, upload them once, accumulate their
        gradients on device, apply — ``train_uniform(upload_uniform(...))``.

        ``loss_fn`` must return the SUM of per-token losses; it is divided by
        the total ``loss_weight_fn`` mass of the whole batch ("global" scope,
        reference megatron.py:410-494) or of each micro-batch ("mb").

        ``skip_update_rule=(num_key, den_key, cap)``: skip the optimizer
        update when stats[num]/stats[den] > cap (the reference's PPO
        early-stop checks the importance ratio BEFORE stepping). The
        returned stats carry ``update_applied`` ∈ {0.0, 1.0}."""
        return self.train_uniform(
            self.upload_uniform(input_, mb_spec), loss_fn, loss_weight_fn,
            token_normalize_scope=token_normalize_scope,
            skip_update_rule=skip_update_rule,
        )

    # -------------- train-state checkpointing --------------
    #
    # Parity: the reference saves optimizer shards alongside weights
    # (megatron.py:711-760) so a recovered run continues the SAME
    # optimization trajectory. Leaves are saved positionally (tree_flatten
    # order) — the restoring engine always has the identical structure.

    def save_train_state(self, ckpt_dir: str) -> None:
        from safetensors.numpy import save_file

        from areal_tpu.parallel import distributed as dist

        # Multi-host: every process joins the gather collective; only
        # process 0 touches the filesystem. safetensors (not npz): npz
        # cannot round-trip bf16 leaves (the mixed-dtype Adam moments).
        host_params = dist.allgather_params(self.params)
        host_opt = (
            dist.allgather_params(self.opt_state)
            if self.opt_state is not None else None
        )
        if jax.process_index() != 0:
            return
        os.makedirs(ckpt_dir, exist_ok=True)
        p_leaves = jax.tree_util.tree_leaves(host_params)
        save_file(
            {f"p{i}": np.ascontiguousarray(x) for i, x in
             enumerate(p_leaves)},
            os.path.join(ckpt_dir, "params.safetensors"),
        )
        if host_opt is not None:
            o_leaves = jax.tree_util.tree_leaves(host_opt)
            save_file(
                {
                    **{f"o{i}": np.ascontiguousarray(x)
                       for i, x in enumerate(o_leaves)},
                    "opt_step_count": np.asarray(self.opt_step_count),
                },
                os.path.join(ckpt_dir, "opt_state.safetensors"),
            )

    @staticmethod
    def _load_leaf_file(path: str) -> Dict[str, np.ndarray]:
        from safetensors.numpy import load_file

        if os.path.exists(path):
            return load_file(path)
        legacy = path.replace(".safetensors", ".npz")
        if os.path.exists(legacy):  # pre-r5 checkpoints
            with np.load(legacy) as z:
                return {k: z[k] for k in z.files}
        raise FileNotFoundError(path)

    @staticmethod
    def _restore_leaf(v, o):
        """Restore one checkpoint leaf in the live leaf's image: dtype,
        SHAPE (safetensors round-trips 0-d scalars as (1,)), and —
        critically — COMMITMENT. Live opt_state leaves are uncommitted
        (jit re-places them next to the sharded params); committing them
        to their current single device on restore pins them there, and
        the next meshed train step dies with "incompatible devices"
        (params on the whole mesh vs opt leaves on device 0)."""
        arr = np.asarray(v).astype(o.dtype).reshape(o.shape)
        if getattr(o, "_committed", True):
            return jax.device_put(arr, o.sharding)
        return jax.device_put(arr)  # device=None: stays uncommitted

    def load_train_state(self, ckpt_dir: str) -> None:
        z = self._load_leaf_file(os.path.join(ckpt_dir, "params.safetensors"))
        leaves = [z[f"p{i}"] for i in range(len(z))]
        treedef = jax.tree_util.tree_structure(self.params)
        old = jax.tree_util.tree_leaves(self.params)
        self.params = jax.tree_util.tree_unflatten(treedef, [
            self._restore_leaf(v, o) for v, o in zip(leaves, old)
        ])
        try:
            z = self._load_leaf_file(
                os.path.join(ckpt_dir, "opt_state.safetensors")
            )
        except FileNotFoundError:
            z = None
        if self.opt_state is not None and z is not None:
            self.opt_step_count = int(z.pop("opt_step_count"))
            o_leaves = [z[f"o{i}"] for i in range(len(z))]
            treedef = jax.tree_util.tree_structure(self.opt_state)
            old = jax.tree_util.tree_leaves(self.opt_state)
            assert len(old) == len(o_leaves), (
                f"optimizer state leaf count changed: ckpt {len(o_leaves)} "
                f"vs live {len(old)}"
            )
            self.opt_state = jax.tree_util.tree_unflatten(treedef, [
                self._restore_leaf(v, o) for v, o in zip(o_leaves, old)
            ])

    def forward(
        self,
        input_: SequenceSample,
        mb_spec: MicroBatchSpec,
        output_key: str = "logprobs",
        post_hook: Optional[Callable] = None,
    ) -> List[np.ndarray]:
        """Micro-batched inference. ``post_hook(out, batch) -> [B, L, ...]``
        maps raw model output (logits/values) to the per-token quantity —
        applied on device so [B, L, V] logits never reach the host. Returns
        per-sample packed arrays in input order."""
        with telemetry.span("infer/split_pack"):
            mbs = mbu.split_into_microbatches(
                input_, mb_spec, length_bucket=self.length_bucket,
                rows_bucket=self.rows_bucket, seqs_bucket=self.seqs_bucket,
                fill_bucket=self.fill_bucket, rows_multiple=self.rows_multiple,
            )
            telemetry.set_gauge("infer/pack_fill", mbu.pack_fill(mbs))
            self._gauge_blocks_needed("infer", mbs)
        use_lp = self._use_chunked_logprobs(post_hook)
        # use_lp is part of the key: id() of a GC'd hook can be reused by a
        # new hook with a different wants_token_logprobs, which would route
        # through the wrong logprob head via the stale cached jit.
        key = (id(post_hook), use_lp)
        if key not in self._fwd_fns:

            def infer_forward(params, batch):
                if use_lp:
                    out, _ = self._forward_token_logprobs(params, batch)
                else:
                    out = self._model_forward(params, batch)
                return (post_hook(out, batch)
                        if post_hook is not None else out)

            self._fwd_fns[key] = compile_watch.watched_jit(
                "train/forward", jax.jit(infer_forward)
            )
        fn = self._fwd_fns[key]
        hooked = post_hook is not None
        outs: List[np.ndarray] = []
        # Dispatched and not yet fetched, oldest first. A local of the
        # call: several threads run this method at once (algorithms/fused).
        pending: collections.deque = collections.deque()
        run_ahead = peak = 0

        def fetch_oldest():
            out = pending.popleft()
            # the call's last fetch says how far the call ran ahead
            last = len(outs) + 1 == len(mbs)
            with telemetry.span("infer/fetch",
                                **({"run_ahead": run_ahead} if last else {})):
                outs.append(np.asarray(out))

        # Upload, dispatch and fetch are a span each: they are the places
        # where the host can make the device wait. The host runs ahead of
        # the device's results: a micro-batch is uploaded and dispatched
        # while the ones before it still run, so the device's queue is
        # empty under the call's first upload only.
        try:
            for mb in mbs:
                with telemetry.span("infer/upload",
                                    **_pack_attrs([mb], len(mbs))):
                    db = jax.device_put({
                        **mb.grids, **mb.scalars,
                        "seq_rows": mb.seq_rows,
                        "seq_first_cols": mb.seq_first_cols,
                        "seq_last_cols": mb.seq_last_cols,
                        "seq_mask": mb.seq_mask})
                run_ahead += bool(pending)
                with telemetry.span("infer/dispatch"), self._mesh_ctx(), \
                        dispatch_label("forward"):
                    params = self.compute_params()
                    # which call-site state and grid this executable is
                    compile_watch.label(
                        "infer_forward", use_lp=use_lp, hook=hooked,
                        grid="%dx%d" % mb.layout.shape)
                    out = fn(params, db)
                out.copy_to_host_async()
                pending.append(out)
                peak = max(peak, len(pending))
                # nbytes is the aval's: no sync
                while sum(o.nbytes for o in pending) > _INFLIGHT_RESULT_BYTES:
                    fetch_oldest()
            while pending:
                fetch_oldest()
        finally:
            pending.clear()  # a dispatch that raised: its results go with it
        telemetry.inc("infer/mbs", len(mbs))
        telemetry.inc("infer/mbs_run_ahead", run_ahead)
        telemetry.set_gauge("infer/inflight_peak", peak)
        with self._infer_lock:
            self.infer_mbs += len(mbs)
            self.infer_mbs_run_ahead += run_ahead
        with telemetry.span("infer/scatter_back"):
            return mbu.scatter_back(mbs, outs, input_.bs)

    def infer_run_ahead(self) -> Optional[float]:
        """Share of forward()'s micro-batches that were dispatched while
        an earlier one's result was still unfetched: (n - 1) / n of a
        hooked pass of n micro-batches, 0 of a pass that returns logits
        (in the trainer worker's ``device_report``). None before the
        first call."""
        with self._infer_lock:
            n, m = self.infer_mbs, self.infer_mbs_run_ahead
        return m / n if n else None

    def generate(
        self,
        input_: SequenceSample,
        mb_spec: MicroBatchSpec,
        gconfig: GenerationHyperparameters,
        key: Optional[jax.Array] = None,
        prompt_key: str = "packed_prompts",
        eos_token_id: int = 1,
        pad_token_id: int = 0,
    ) -> Dict[str, np.ndarray]:
        """In-process generation (the reference's non-SGLang path). Groups of
        ``gconfig.n`` samples per prompt are produced by repeating prompts."""
        assert input_.data is not None
        if key is None:
            key = jax.random.PRNGKey(self.opt_step_count)
        offs = input_.offsets(prompt_key)
        lens = input_.total_lens(prompt_key)
        prompts = [
            input_.data[prompt_key][o : o + l] for o, l in zip(offs, lens)
        ]
        if gconfig.n > 1:
            prompts = [p for p in prompts for _ in range(gconfig.n)]
        padded, plens = genmod.pad_prompts(prompts, pad_token_id)
        with self._mesh_ctx(), dispatch_label("generate"):
            out = genmod.generate_batch(
                self.compute_params(),
                self.cfg,
                jnp.asarray(padded),
                jnp.asarray(plens),
                key,
                gconfig,
                max_new_tokens=gconfig.max_new_tokens,
                eos_token_id=eos_token_id,
                pad_token_id=pad_token_id,
                attn_impl=self.attn_impl,
            )
        return {k: np.asarray(v) for k, v in out.items()}


# ---------------- backend registration ----------------


@dataclasses.dataclass
class JaxTrainBackend(ModelBackend):
    """Builds a JaxTrainEngine for a Model whose ``module`` is a
    (TransformerConfig, params) pair (what models/hf.py loaders return)."""

    optimizer: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    mesh: Any = None
    # Picklable alternative to ``mesh`` for configs that cross process
    # boundaries (the experiments layer): a ParallelSpec string like
    # "d2f2t2"; the mesh is built lazily in the hosting process.
    parallel_spec: Optional[str] = None
    compute_dtype: str = "bfloat16"
    length_bucket: int = 128
    rows_bucket: int = 8
    seqs_bucket: int = 8
    attn_impl: str = "auto"
    remat: bool = False
    logprob_chunk: Optional[int] = 512
    fill_bucket: Optional[int] = None
    train: bool = True

    def initialize(self, model: Model, spec: FinetuneSpec) -> Model:
        if self.mesh is None and self.parallel_spec:
            from areal_tpu.parallel import mesh as pmesh

            ps = pmesh.ParallelSpec.parse(self.parallel_spec)
            if ps.world_size > 1:
                self.mesh = pmesh.make_mesh(ps)
        cfg, params = model.module
        engine = JaxTrainEngine(
            cfg,
            params,
            opt_cfg=self.optimizer if self.train else None,
            ft_spec=spec,
            mesh=self.mesh,
            compute_dtype=self.compute_dtype,
            length_bucket=self.length_bucket,
            rows_bucket=self.rows_bucket,
            seqs_bucket=self.seqs_bucket,
            attn_impl=self.attn_impl,
            remat=self.remat,
            logprob_chunk=self.logprob_chunk,
            fill_bucket=self.fill_bucket,
        )
        model.module = engine
        return model


register_backend("jax_train", JaxTrainBackend)
register_backend(
    "jax_inference",
    lambda **kw: JaxTrainBackend(train=False, **kw),
)
