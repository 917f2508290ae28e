"""Trainer worker — hosts model roles on one mesh and executes MFCs.

Parity target: ``realhf/system/model_worker.py:101``. TPU-first collapse:
JAX is single-controller SPMD, so the reference's one-process-per-GPU model
workers (with NCCL data redistribution between them, ``data_manager.py``,
``redistributor.py``) become ONE process driving the whole trainer mesh —
the DataManager shrinks to an in-process dict, and GSPMD handles every
intra-mesh reshard the reference planned centrally.

Serves the master's request stream with handlers:
 - ``fetch``          next dataset batch → store → metadata
 - ``mfc``            run one MFC (generate/inference/train_step) over
                      stored samples; store outputs; reply metadata
 - ``clear``          drop sample ids from the store
 - ``save`` / ``version`` / ``exit``  bookkeeping

Pre/post hooks on MFC payloads: ``weight_update`` publishes actor weights
for the generation fleet (disk path + names.model_version bump — §3.5 of
the survey), ``param_realloc`` does EMA role sync, ``save`` checkpoints.
"""

from __future__ import annotations

import dataclasses
import os
import queue
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.api.model import (
    FinetuneSpec,
    Model,
    make_backend,
    make_dataset,
    make_interface,
)
from areal_tpu.api.train_config import (
    CompileWatchConfig,
    DurabilityConfig,
    GoodputConfig,
    RewardServiceConfig,
    TelemetryConfig,
    WeightSyncConfig,
)
from areal_tpu.base import compile_watch, logging, name_resolve, names, \
    telemetry
from areal_tpu.system import goodput as goodput_mod
from areal_tpu.system import memwatch
from areal_tpu.system.sample_spool import (
    SPOOL_KEY,
    SpoolIngest,
    ack_channel_name,
)
from areal_tpu.system.streams import (
    Payload,
    WorkerRequestServer,
    ZmqPuller,
    ZmqPusher,
)

logger = logging.getLogger("system.trainer")


@dataclasses.dataclass
class ModelRoleConfig:
    """One model role (actor/critic/ref/reward) hosted by the trainer."""

    # model construction: "hf_dir" (path) or "init" (cfg dict) or "shared"
    init: Dict[str, Any] = dataclasses.field(default_factory=dict)
    backend: str = "jax_train"
    backend_args: Dict[str, Any] = dataclasses.field(default_factory=dict)
    train: bool = True


@dataclasses.dataclass
class MFCRuntimeConfig:
    """Interface binding for one MFC name."""

    interface: str = "ppo_actor"
    interface_args: Dict[str, Any] = dataclasses.field(default_factory=dict)
    model_name: str = "actor"
    method: str = "train_step"


@dataclasses.dataclass
class TrainerWorkerConfig:
    experiment: str = "exp"
    trial: str = "trial"
    handler: str = "trainer"
    models: Dict[str, ModelRoleConfig] = dataclasses.field(default_factory=dict)
    mfcs: Dict[str, MFCRuntimeConfig] = dataclasses.field(default_factory=dict)
    dataset: Optional[str] = None
    dataset_args: Dict[str, Any] = dataclasses.field(default_factory=dict)
    batch_size: int = 8
    ft_spec: FinetuneSpec = dataclasses.field(default_factory=FinetuneSpec)
    tokenizer: Any = None
    # async mode: pull trajectories from rollout workers instead of a dataset
    stream_dataset: bool = False
    realloc_dir: str = "/tmp/areal_tpu/realloc"
    # Weight publish transport. The worker-level default stays "disk" for
    # back-compat with directly constructed configs; the experiment config
    # tree (api.cli_args BaseExperimentConfig.weight_sync) defaults to the
    # streamed transport and threads it through here.
    weight_sync: WeightSyncConfig = dataclasses.field(
        default_factory=lambda: WeightSyncConfig(transport="disk")
    )
    # Unified telemetry (base/telemetry.py): step-phase spans, weight-sync
    # latency gauges, profiler trigger. Off by default — zero overhead.
    telemetry: TelemetryConfig = dataclasses.field(
        default_factory=TelemetryConfig
    )
    # Goodput ledger (system/goodput.py): compute/comm/data_wait/idle
    # time-in-state counters + live train/achieved_tflops + train/mfu
    # gauges. Off by default — the null ledger costs nothing.
    goodput: GoodputConfig = dataclasses.field(default_factory=GoodputConfig)
    # Sandbox reward fleet (docs/rewards.md): enabled, trainer-side
    # reward interfaces (sync-mode rw_math_code / fused) grade over HTTP
    # instead of executing verification in the trainer process. Off =
    # legacy local grading, bit-identical.
    reward_service: RewardServiceConfig = dataclasses.field(
        default_factory=RewardServiceConfig
    )
    # Durable sample delivery (system/sample_spool.py): knobs for the
    # trainer side of the at-least-once loop — the replay staleness gate
    # and ack-push budgets. Ingest/ack machinery itself is keyed off
    # arriving ``_spool`` metadata, so a worker/trainer config mismatch
    # still settles instead of resending forever.
    durability: DurabilityConfig = dataclasses.field(
        default_factory=DurabilityConfig
    )
    # Compile & HBM observatory (base/compile_watch.py +
    # system/memwatch.py): jit compile-event tracing over the train
    # engine's entry points, HBM gauges/watermarks around the big
    # allocators, and the compile-inflight heartbeat flag the sentinel's
    # trainer_stalled rule reads. Off by default — zero wrappers, zero
    # device polls, scrape bit-identical.
    compile_watch: CompileWatchConfig = dataclasses.field(
        default_factory=CompileWatchConfig
    )
    # Multi-host SPMD (reference global_comm.py:48): dist_world processes —
    # one per host — join one jax.distributed program; rank 0 owns every
    # control-plane socket and broadcasts (request, data) to the others,
    # which execute the same jitted steps in the same order.
    dist_rank: int = 0
    dist_world: int = 1
    # Virtual CPU devices per process for multi-process CPU testing.
    dist_local_devices: Optional[int] = None
    # TPU chip ids this worker may initialize (launcher-assigned partition
    # in decoupled async mode); None = all chips.
    chips: Optional[List[int]] = None


class TrainerWorker:
    def __init__(self, cfg: TrainerWorkerConfig, model_factory=None):
        """``model_factory(role, role_cfg) -> Model`` lets tests inject tiny
        models; the default builds from role_cfg.init (hf dir / config)."""
        self.cfg = cfg
        self.store: Dict[Any, SequenceSample] = {}
        self.models: Dict[str, Model] = {}
        self.interfaces: Dict[str, Any] = {}
        self._mfc_cfg = cfg.mfcs
        self._server: Optional[WorkerRequestServer] = None
        self._dataset = None
        self._data_iter: List[int] = []
        self._epoch = 0
        self._epoch_pos = 0
        self._puller: Optional[ZmqPuller] = None
        self._pull_q: "queue.Queue[SequenceSample]" = queue.Queue()
        self._pull_thread = None
        # Durable-delivery bookkeeping (rank 0, stream mode): idempotent
        # ingest + the per-worker ack pushers (created lazily on first
        # ack for a worker index). _ack_lock serializes the pull thread
        # (stale drops / re-acks) against the serve thread ("clear").
        self._ingest: Optional[SpoolIngest] = None
        self._ack_pushers: Dict[int, ZmqPusher] = {}
        self._ack_lock = threading.Lock()
        self._model_factory = model_factory or self._default_model_factory
        self._exiting = False
        self._weight_publishers: Dict[str, Any] = {}  # role -> publisher
        # Goodput accounting (null until setup() arms it on rank 0).
        self._ledger = goodput_mod.NULL_LEDGER
        self._mfu = None
        self._flops = None

    # ---------------- setup ----------------

    @staticmethod
    def _default_model_factory(role: str, rc: ModelRoleConfig) -> Model:
        from areal_tpu.models import hf as hfmod

        if "hf_dir" in rc.init:
            cfg, params, tok = hfmod.load_hf_model(rc.init["hf_dir"])
            return Model(role, (cfg, params), tokenizer=tok)
        if "ckpt_dir" in rc.init:
            cfg, params = hfmod.load_checkpoint_auto(rc.init["ckpt_dir"])
            return Model(role, (cfg, params))
        if "tiny" in rc.init:  # fabricated test model (reference testing.py)
            import jax

            from areal_tpu.models import transformer
            from areal_tpu.models.config import tiny_config

            kw = dict(rc.init["tiny"])
            seed = kw.pop("seed", 0)
            cfg = tiny_config(**kw)
            params = transformer.init_params(cfg, jax.random.PRNGKey(seed))
            return Model(role, (cfg, params))
        if rc.init.get("null"):  # tokenizer-only role (rule-based reward)
            return Model(role, None)
        raise ValueError(f"role {role}: no model source in init={rc.init}")

    @property
    def _rank0(self) -> bool:
        return self.cfg.dist_rank == 0

    def _bcast(self, obj):
        if self.cfg.dist_world > 1:
            from areal_tpu.parallel import distributed as dist

            return dist.broadcast_pyobj(obj)
        return obj

    def setup(self) -> None:
        cfg = self.cfg
        if cfg.dist_world > 1:
            from areal_tpu.parallel import distributed as dist

            dist.initialize(
                cfg.experiment, cfg.trial, cfg.dist_rank, cfg.dist_world,
                group="trainer", local_device_count=cfg.dist_local_devices,
            )
        for role, rc in cfg.models.items():
            model = self._model_factory(role, rc)
            if model.tokenizer is None:
                model.tokenizer = cfg.tokenizer
            if rc.backend == "null" or model.module is None:
                self.models[role] = model
                continue
            backend = make_backend(rc.backend, **{"train": rc.train,
                                                 **rc.backend_args})
            self.models[role] = backend.initialize(model, cfg.ft_spec)
        for mfc_name, mc in self._mfc_cfg.items():
            self.interfaces[mfc_name] = make_interface(
                mc.interface, **mc.interface_args
            )
        # Reward grading mode for THIS process (rewards/client.py): the
        # sync-mode rw interface's batch_reward calls fan out to the
        # sandbox fleet when the service is enabled; disabled keeps the
        # legacy in-process path bit-identical.
        from areal_tpu.rewards import client as reward_client

        reward_client.configure_service(
            cfg.reward_service, cfg.experiment, cfg.trial
        )
        # Rank 0 owns the data plane and the master's request socket; other
        # ranks receive everything via broadcast.
        if cfg.dataset is not None and self._rank0:
            self._dataset = make_dataset(
                cfg.dataset, tokenizer=cfg.tokenizer, **cfg.dataset_args
            )
            self._reshuffle()
        if cfg.stream_dataset and self._rank0:
            self._puller = ZmqPuller(cfg.experiment, cfg.trial, cfg.handler)
            self._ingest = SpoolIngest(
                staleness_limit=cfg.durability.replay_staleness_limit
            )
            self._pull_thread = threading.Thread(
                target=self._pull_loop, daemon=True
            )
            self._pull_thread.start()
        if self._rank0:
            self._server = WorkerRequestServer(
                cfg.experiment, cfg.trial, cfg.handler
            )
        # Telemetry + profiler trigger: rank 0 only (it owns the control
        # plane; follower ranks mirror its work anyway). With the config
        # absent/disabled, configure() installs the no-op sink and no
        # watcher is created — the serve loop pays nothing.
        self._profiler = None
        # Goodput ledger + live MFU (system/goodput.py): rank 0 only,
        # like the rest of the control plane. Disabled (the default):
        # the null ledger and no FLOPs math on any handler.
        self._ledger = goodput_mod.NULL_LEDGER
        self._mfu = None
        self._flops = None
        if cfg.telemetry.enabled and self._rank0:
            telemetry.configure(
                cfg.experiment, cfg.trial, "trainer", cfg.dist_rank,
                cfg.telemetry,
            )
            self._profiler = telemetry.ProfilerTriggerWatcher(
                cfg.experiment, cfg.trial
            )
            if cfg.goodput.enabled:
                import jax

                from areal_tpu.base import monitor

                self._ledger = goodput_mod.make_ledger(
                    cfg.goodput, telemetry.get()
                )
                self._mfu = goodput_mod.MfuEmitter(
                    telemetry.get(),
                    goodput_mod.resolve_peak_flops(
                        cfg.goodput, jax.devices()[0].device_kind
                    ),
                    tflops_name="train/achieved_tflops",
                    mfu_name="train/mfu", context="trainer",
                )
                self._flops = monitor.FlopsCounter()
            # Compile & HBM observatory: the module-global facades the
            # train engine's jit sites (backend/jax_train.py) and the
            # weight-publish paths below call through. Disabled config
            # keeps the NULL objects — the wrap/watermark calls resolve
            # to the raw fn / a no-op context.
            compile_watch.configure(cfg.compile_watch, telemetry.get())
            memwatch.configure(cfg.compile_watch, telemetry.get())
        logger.info(
            f"trainer up (rank {cfg.dist_rank}/{cfg.dist_world}): "
            f"models={list(self.models)} mfcs={list(self.interfaces)}"
        )
        self._log_device_report("setup")

    def _log_device_report(self, stage: str) -> None:
        """Device, HBM counters and the trace-time/host-side facts a chip
        run is judged by (base/monitor.log_device_report)."""
        from areal_tpu.base import monitor
        from areal_tpu.ops import attention, native
        from areal_tpu.models import dsa, gdn, kda, mla, moe, shortconv, ssm
        from areal_tpu.ops.pallas import window_attention

        widths = window_attention.head_width_counts()
        monitor.log_device_report(
            logger, f"trainer{self.cfg.dist_rank}", stage=stage,
            attention=attention.dispatch_counts(),
            # {label: {"length>padded/blocks": calls traced}}: the kernel's
            # full-causal calls (the "pallas" count above) and the
            # geometry each ran (window_attention.Blocks.label)
            # — with "/k<width>><lanes>.v<width>><lanes>" where the
            # value is narrower than the key and keeps its own lanes
            causal_geometry={
                label: {"%d>%d/%s" % (g[0], g[1], g[2].label()) + (
                    "/k%d>%d.v%d>%d" % widths[label][g]
                    if widths[label][g][0] != widths[label][g][2] else ""
                ): calls for g, calls in counts.items()}
                for label, counts in
                window_attention.causal_geometry_counts().items()
            },
            # {"rows x length>padded/tile/window": {grids, blocks_needed,
            # blocks_static}}: what the packed grids the engine ran needed
            # of the static mask's key blocks (the kernel skips the rest)
            attn_blocks_needed={
                "%dx%d>%d/%d/w%d" % grid: c for grid, c in
                window_attention.needed_counts().items()
            },
            # {label: {"length>padded/tile/window": {calls, blocks_visited,
            # blocks_causal}}}: the windowed kernel's calls, and the key
            # blocks they visit against a causal kernel's
            window_geometry={
                label: {"%d>%d/%d/w%d" % geom: c for geom, c in counts.items()}
                for label, counts in
                window_attention.geometry_counts().items()
            },
            # {"rows x length/chunk/hHgG": scans traced}: a hybrid model's
            # state-space layers
            ssm_geometry={"%dx%d/%d/h%dg%d" % geom: n
                          for geom, n in ssm.geometry_counts().items()},
            # {"rows x length/chunk/kG vH/dk x dv": rules traced}: a
            # model's Gated DeltaNet blocks (models/gdn.py)
            gdn_geometry={"%dx%d/%d/k%dv%d/%dx%d" % geom: n
                          for geom, n in gdn.geometry_counts().items()},
            # {"rows x length/chunk/hH/head width/gate rank": rules traced}:
            # a model's Kimi Delta Attention blocks (models/kda.py), and
            # what runs them (ops/pallas/kda_rule.py, or the XLA form)
            kda_geometry={"%dx%d/%d/h%d/%d/r%d" % geom: n
                          for geom, n in kda.geometry_counts().items()},
            kda_rule_impl=kda.rule_impl_counts(),
            # {"kernel" | "xla": mixers traced}: where their per-head ends
            # ran (inside the kernel pair, all heads at once; or XLA's
            # grouped text)
            kda_mixer_norms=kda.mixer_norm_counts(),
            # {"rows x length/channels/taps": convolutions traced}: a
            # model's short-convolution blocks (models/shortconv.py)
            shortconv_geometry={
                "%dx%d/c%d/k%d" % geom: n
                for geom, n in shortconv.geometry_counts().items()},
            # {"rows x length/heads/q latent (0: none, one full-rank
            # projection), kv latent/nope+rope/v": assemblies traced}:
            # latent attention (models/mla.py)
            mla_geometry={"%dx%d/h%d/q%dkv%d/%d+%d/v%d" % geom: n
                          for geom, n in mla.geometry_counts().items()},
            # {"row/padded row/q tile, kv tile/top-k": calls traced}:
            # attention under a learned selection (models/dsa.py), and
            # what runs it ("kernel": ops/pallas/sparse_attention.py; or
            # "xla")
            dsa_geometry={"%d/%d/q%dkv%d/k%d" % geom: n
                          for geom, n in dsa.geometry_counts().items()},
            dsa_impl=dsa.impl_counts(),
            # {"pallas" | "pallas_interpret" | "xla": scans traced}: what
            # runs them (the kernel of ops/pallas/ssd_scan.py, or einsums)
            ssm_scan_impl=ssm.scan_impl_counts(),
            # the same of the gated delta rules (ops/pallas/
            # gated_delta_rule.py, or the XLA form of models/gdn.py)
            gdn_rule_impl=gdn.rule_impl_counts(),
            # {"kernel" | "xla": mixers traced}: where a mixer's l2 norms
            # and gated RMS norm ran (inside the rule's kernels, or XLA's)
            gdn_mixer_norms=gdn.mixer_norm_counts(),
            # {"rows x length/dD nN/impl": scans traced}: a model's selective
            # scans (S6), and which form each runs as
            s6_geometry={"%dx%d/d%dn%d/%s" % geom: n
                         for geom, n in ssm.s6_geometry_counts().items()},
            # {model: {"memory" | "kv": layers that read what ONE earlier
            # layer made}}: empty for layers that read the stream alone
            cross_layer_reads={
                role: m.module.cfg.cross_layer_reads
                for role, m in self.models.items()
                if hasattr(getattr(m.module, "cfg", None),
                           "cross_layer_reads")
            },
            # {"entries>rows/token rows x width": "rows" | "entries"}: how
            # each expert pass traced adds its rows into their tokens
            moe_combine={"%d>%d/%dx%d" % key: how
                         for key, how in moe.combine_counts().items()},
            # {"rows x K x N/groups": "gmm" | "ragged_dot"}: which grouped
            # GEMM each one traced runs as (the Pallas kernel in a bounded
            # pass on a TPU; its tile is moe.gemm_tiling of the shape)
            moe_gemm={"%dx%dx%d/%d" % key: how
                      for key, how in moe.gemm_counts().items()},
            # {model: {"local" | "bound" | "walked": rows per layer since
            # start}}: of the rows a bounded expert pass runs on (bound),
            # those that landed here (local) and those its row gather and
            # its combine moved (walked: whole steps over the live head)
            moe_rows={
                role: m.module.moe_rows for role, m in self.models.items()
                if getattr(m.module, "moe_rows", None)
            },
            # {model: {"<attention or mixer kind>/<dense | experts | ->":
            # layers}}: the model's blocks by what they are made of
            blocks={
                role: m.module.cfg.block_counts()
                for role, m in self.models.items()
                if hasattr(getattr(m.module, "cfg", None), "block_counts")
            },
            # {model: {"RxL": {entry, kept_bytes_estimate, budget_bytes,
            # reckoned_heap_bytes, fell_back, compiled: {temp_bytes,
            # peak_bytes, cache}}}}: what each grid's backward pass
            # re-runs, the engine's reckoning of its grad program's heap
            # and, beside it, what the compiler says that program needs
            remat_plan={
                role: m.module.remat_plan() for role, m in self.models.items()
                if hasattr(m.module, "remat_plan")
            },
            # {model: casts of the whole tree outside train_apply}: one at
            # first use, one more per external write of the weights
            param_cast_rebuilds={
                role: m.module.param_cast_rebuilds
                for role, m in self.models.items()
                if hasattr(m.module, "param_cast_rebuilds")
            },
            # {model: share of forward()'s micro-batches dispatched while
            # an earlier one's result was unfetched}: (n - 1) / n where
            # the inference pass keeps the device fed, 0 where each result
            # is fetched before the next dispatch (logits)
            infer_run_ahead={
                role: m.module.infer_run_ahead()
                for role, m in self.models.items()
                if hasattr(m.module, "infer_run_ahead")
            },
            # the compile ledger less its ring of spans (a log line: the
            # per-program table says what start-up cost and, a record an
            # executable, what each program needs of the chip;
            # /metrics.json and telemetry.jsonl carry the spans)
            compile_cache={k: v for k, v in
                           (compile_watch.cache_stats() or {}).items()
                           if k != "spans"} or None,
            native_ops="g++" if native.available() else "numpy",
        )

    def _reshuffle(self):
        rng = np.random.RandomState(self._epoch + 1)
        self._data_iter = list(rng.permutation(len(self._dataset)))
        self._epoch_pos = 0

    def _pull_loop(self):
        while not self._exiting:
            obj = self._puller.pull(timeout_ms=200)
            if obj is not None:
                # Optional durable-spool framing (system/sample_spool.py):
                # popped like the trace key below, absent on non-durable
                # pushes (bit-identical legacy path).
                spool_meta = (
                    obj.pop(SPOOL_KEY, None) if isinstance(obj, dict)
                    else None
                )
                # Optional sample-lineage context pushed by the rollout
                # worker (streams.ZmqPusher): keep it in the sample's
                # METADATA — it survives the master's metadata buffer and
                # this store untouched, so the train step can close the
                # trace with a terminal span (docs/observability.md).
                trace = telemetry.extract_payload(obj)
                s = SequenceSample.from_json_compatible(obj)
                if trace is not None:
                    s.metadata["_trace"] = [trace.as_dict()]
                if spool_meta is not None and self._ingest is not None \
                        and not self._ingest_spooled(s, spool_meta):
                    continue
                self._pull_q.put(s)

    def _ingest_spooled(self, s: SequenceSample, meta: Dict) -> bool:
        """At-least-once ingest decision; False = drop (do not enqueue).

        Duplicates are a NORMAL event here (a resend racing its own ack,
        or a replay of an already-settled record after the ack was lost)
        — dropped idempotently, re-acked when already settled. Replays
        re-enter the staleness gate: the paper's bounded-off-policyness
        contract must hold across a trainer outage too, so a replay
        whose version lag exceeds the bound is durably dropped (counted
        + acked — a drop the worker knows about is not sample loss)."""
        sid = s.ids[0]
        cur = max(
            (m.version.global_step for m in self.models.values()),
            default=0,
        )
        sample_ver = None
        if "version_end" in s.data:
            sample_ver = float(
                np.asarray(s.data["version_end"]).reshape(-1)[0]
            )
        action, ackp = self._ingest.observe(sid, meta, cur, sample_ver)
        if action == "duplicate":
            telemetry.inc("spool/duplicate_dropped")
            if ackp is not None:
                self._send_acks({ackp[0]: [ackp[1]]})
            return False
        if action == "stale":
            telemetry.inc("spool/replay_stale_dropped")
            self._send_acks({ackp[0]: [ackp[1]]})
            return False
        return True

    def _send_acks(self, by_worker: Dict[int, List[int]]) -> None:
        """Push settled seqnos back to each worker's ack channel. Best
        effort by design: a lost ack is recovered by the worker's resend
        timer + this side's settled-duplicate re-ack, so failures are
        logged and dropped rather than retried here."""
        if not by_worker:
            return
        with self._ack_lock:
            for w, seqnos in by_worker.items():
                try:
                    pusher = self._ack_pushers.get(w)
                    if pusher is None:
                        pusher = ZmqPusher(
                            self.cfg.experiment, self.cfg.trial,
                            ack_channel_name(w), timeout=5.0,
                            block_secs=1.0,
                        )
                        self._ack_pushers[w] = pusher
                    pusher.push({"seqnos": [int(s) for s in seqnos]})
                except Exception as e:  # noqa: BLE001 — worker down/respawning
                    logger.warning(
                        f"ack push to rollout worker {w} failed ({e}); "
                        f"its resend timer will recover"
                    )
                    # Drop the pusher: a respawned worker binds a fresh
                    # address under the same key.
                    stale = self._ack_pushers.pop(w, None)
                    if stale is not None:
                        try:
                            stale.close()
                        except Exception:  # noqa: BLE001
                            pass

    # ---------------- handlers ----------------

    def _read_batch(self, n: int) -> Optional[SequenceSample]:
        """Rank-0-only data-plane read (dataset or rollout stream).

        Stream mode returns WHATEVER is available within the wait window —
        possibly fewer than ``n``, possibly None. The master accumulates
        across fetches until its step batch is full (master_worker
        _load_data); returning early keeps this serve loop responsive
        instead of blocking an entire rollout round inside one request.
        (A partial return that the master treated as complete was the
        r2-era hang: buffer gates wait for n_seqs forever.)"""
        if self.cfg.stream_dataset:
            out: List[SequenceSample] = []
            deadline = time.monotonic() + 0.5
            while len(out) < n and time.monotonic() < deadline:
                try:
                    out.append(self._pull_q.get(timeout=0.1))
                except queue.Empty:
                    if out:
                        break
            return SequenceSample.gather(out) if out else None
        idx = []
        while len(idx) < n and self._dataset is not None:
            if self._epoch_pos >= len(self._data_iter):
                self._epoch += 1
                self._reshuffle()
            idx.append(self._data_iter[self._epoch_pos])
            self._epoch_pos += 1
        return SequenceSample.gather([self._dataset[i] for i in idx])

    def _store_batch(self, batch: SequenceSample) -> None:
        for i in range(batch.bs):
            s = batch.select_idx([i])
            self.store[s.ids[0]] = s

    def _handle_fetch(self, p: Payload) -> Any:
        with telemetry.span("trainer/data_wait",
                            stream=self.cfg.stream_dataset) as attrs, \
                self._ledger.state("data_wait"):
            batch = self._read_batch(int(p.data or self.cfg.batch_size))
            attrs["n_seqs"] = batch.bs if batch is not None else 0
        telemetry.set_gauge("trainer/pull_queue_depth",
                            self._pull_q.qsize())
        if batch is not None:
            # Every rank stores the same batch (multi-host: the jitted
            # steps consume identical replicated host inputs per process).
            self._bcast(("fetch", batch))
            self._store_batch(batch)
        return {
            "meta": batch.meta() if batch is not None else None,
            "epoch": self._epoch,
            "epoch_pos": self._epoch_pos,
            "dataset_size": len(self._dataset) if self._dataset else -1,
        }

    def _gather_input(self, ids, input_keys, remap) -> SequenceSample:
        samples = [self.store[i] for i in ids]
        batch = SequenceSample.gather(samples)
        if remap:
            batch = SequenceSample(
                ids=list(batch.ids), keys=set(batch.keys),
                seqlens=dict(batch.seqlens), data=dict(batch.data),
                metadata=dict(batch.metadata),
            )
            batch.remap_keys_(remap)
        return batch

    def _handle_mfc(self, p: Payload) -> Any:
        req = p.data  # {"mfc": name, "ids": [...], "method": ...}
        if req.get("method") == "noop":
            # hook-only request (e.g. a save triggered by the master)
            for hook in p.pre_hooks + p.post_hooks:
                self._run_hook(hook)
            return {"stats": None, "meta": None}
        mfc_name = req["mfc"]
        mc = self._mfc_cfg[mfc_name]
        iface = self.interfaces[mfc_name]
        model = self.models[mc.model_name]
        batch = self._gather_input(req["ids"], req.get("input_keys"),
                                   req.get("input_remap"))
        mb_spec = p.mb_spec or MicroBatchSpec()
        method = req.get("method", mc.method)
        for hook in p.pre_hooks:
            self._run_hook(hook)
        trace_dir = os.environ.get("AREAL_DUMP_TRACE")
        t_mfc_wall = time.time()
        t_mfc = time.monotonic()
        with telemetry.span("trainer/mfc", mfc=mfc_name, method=method,
                            n_seqs=batch.bs), \
                self._ledger.state("compute"):
            if trace_dir:
                # Env-gated per-MFC profiler (reference REAL_DUMP_TRACE,
                # model_worker.py:829 __maybe_profile_rpc): one jax.profiler
                # trace per MFC invocation, viewable in tensorboard/xprof.
                import jax

                out_dir = os.path.join(
                    trace_dir, f"{mfc_name}_{model.version.global_step}"
                )
                with jax.profiler.trace(out_dir):
                    out = getattr(iface, method)(model, batch, mb_spec)
            else:
                out = getattr(iface, method)(model, batch, mb_spec)
        result: Dict[str, Any] = {"stats": None, "meta": None}
        if method == "train_step":
            result["stats"] = out
            self._export_train_stats(mfc_name, out)
            self._emit_mfu(mc.model_name, batch,
                           time.monotonic() - t_mfc)
            self._emit_terminal_spans(
                req["ids"], model, t_mfc_wall, time.monotonic() - t_mfc
            )
        elif out is not None:
            remap = req.get("output_remap") or {}
            if remap:
                out.remap_keys_(remap)
            if method == "generate":
                # Flattened trajectories REPLACE the prompt samples.
                for i in range(out.bs):
                    s = out.select_idx([i])
                    self.store[s.ids[0]] = s
                for old_id in req["ids"]:
                    self.store.pop(old_id, None)
            else:
                for i, sid in enumerate(out.ids):
                    self.store[sid].update_(out.select_idx([i]))
            result["meta"] = out.meta()
        for hook in p.post_hooks:
            self._run_hook(hook)
        return result

    # The divergence signatures that kill RL runs get a distribution view
    # on top of the last-value gauge (suffix _dist: a gauge and a
    # histogram cannot share one Prometheus family name).
    _TRAIN_DIST_KEYS = ("approx_kl", "entropy", "grad_norm",
                        "importance_weight", "clip_ratio")

    def _export_train_stats(self, mfc_name: str,
                            stats: Optional[Dict[str, Any]]) -> None:
        """First-class training-dynamics telemetry per train step
        (docs/observability.md): every train_step scalar becomes a
        ``train/<name>{mfc=...}`` gauge on the scrape — the sentinel's
        rule pack and any external Prometheus reader consume THESE, not
        the stats_tracker/tensorboard keys the master tabulates. No-op
        with telemetry disabled."""
        if not stats or not telemetry.enabled():
            return
        import math

        for k, v in stats.items():
            if isinstance(v, bool) or not isinstance(v, (int, float)) \
                    or not math.isfinite(v):
                continue
            telemetry.set_gauge(f"train/{k}{{mfc={mfc_name}}}", float(v))
            if k in self._TRAIN_DIST_KEYS:
                telemetry.observe(f"train/{k}_dist{{mfc={mfc_name}}}",
                                  float(v))

    def _emit_mfu(self, role: str, batch: SequenceSample,
                  dur_secs: float) -> None:
        """Live achieved-FLOP/s + MFU for one train MFC: packed token
        counts fed through the one set of analytic formulas
        (base/monitor.py FlopsCounter — the llama formula family
        with the engine's real remat factor), divided by the step's wall
        clock and the chip count. ``train/mfu`` degrades to
        achieved-TFLOP/s-only on unknown device kinds (MfuEmitter).
        No-op with goodput disabled."""
        if self._flops is None or self._mfu is None or dur_secs <= 0:
            return
        engine = self.models[role].module
        cfg = getattr(engine, "cfg", None)
        if cfg is None or not batch.seqlens:
            return
        import jax

        # The MAIN token key, not an arbitrary one: seqlens also carries
        # scalar keys (rewards: [[1]] per sample), and set-ordered
        # iteration could pick one of those — understating the gauges by
        # orders of magnitude, nondeterministically.
        lens = [float(v) for v in batch.total_lens()]
        n_tokens = sum(lens)
        if n_tokens <= 0:
            return
        self._flops.add_train(
            cfg, n_tokens, n_tokens / max(len(lens), 1),
            remat=bool(getattr(engine, "remat", False)),
        )
        self._mfu.emit(
            self._flops.pop() / dur_secs / max(jax.device_count(), 1)
        )

    def _emit_terminal_spans(self, ids, model, t_start: float,
                             dur_secs: float) -> None:
        """Close each traced sample's lineage: a terminal
        ``trainer/train_sample`` span recording WHICH weight version
        trained it — the stitcher (base/telemetry.TraceStitcher) keys the
        prompt→trained latency + stage breakdown off this span. The trace
        is CONSUMED from the stored sample's metadata on emit: several
        TRAIN_STEP MFCs may read the same sample ids in one step
        (actor_train + critic_train), and only the first to train it
        terminates the trace — otherwise every stitched metric would
        double per extra train MFC. No-op with telemetry disabled or for
        untraced samples."""
        if not telemetry.enabled():
            return
        version = model.version.global_step
        for sid in ids:
            s = self.store.get(sid)
            if s is None:
                continue
            tr = (s.metadata.pop("_trace", None) or [None])[0]
            if not isinstance(tr, dict):
                continue
            ctx = telemetry.TraceContext.from_dict(tr)
            if ctx is None:
                continue
            telemetry.add_span(
                "trainer/train_sample", t_start, dur_secs, trace=ctx,
                sample_id=str(sid), weight_version=version,
            )

    def _run_hook(self, hook: Dict) -> None:
        kind = hook.get("kind")
        if kind == "weight_update":
            self.publish_weights(hook.get("role", "actor"))
        elif kind == "save":
            role = hook.get("role", "actor")
            self._save_role(role, hook["path"])
        elif kind == "param_realloc":
            # EMA: target := eta*source + (1-eta)*target (reference ref-EMA)
            import jax

            from areal_tpu.parallel import reshard as rsh

            src = self.models[hook["source"]].module
            dst = self.models[hook["target"]].module
            eta = float(hook.get("eta", 1.0))
            # MFC-boundary reshard: under a heterogeneous per-MFC allocation
            # the source and target roles live on different meshes, so move
            # the source tree into the target's layout on device first — the
            # EMA math then runs entirely on the target's mesh. Same-layout
            # roles hit the zero-copy no-op path (plan.n_moved == 0).
            src_params, plan = rsh.reshard_pytree(
                src.params, rsh.shardings_of(dst.params)
            )
            if plan.n_moved:
                with self._ledger.state("comm"):
                    jax.block_until_ready(src_params)
                logger.info(
                    f"param_realloc reshard {hook['source']}→{hook['target']}: "
                    + plan.describe()
                )
            dst.params = jax.tree.map(
                lambda s, d: (eta * s.astype(np.float32)
                              + (1 - eta) * d.astype(np.float32)).astype(d.dtype),
                src_params, dst.params,
            )
        else:
            raise ValueError(f"unknown hook {hook}")

    def _save_role(self, role: str, path: str, fmt: str = "hf") -> None:
        from areal_tpu.models import hf as hfmod
        from areal_tpu.parallel import distributed as dist

        model = self.models[role]
        engine = model.module
        params = (self._compute_dtype_params(role) if fmt == "native"
                  else engine.params)
        host_params = dist.allgather_params(params)
        if not self._rank0:
            return
        saver = (hfmod.save_native_checkpoint if fmt == "native"
                 else hfmod.save_hf_checkpoint)
        saver(
            host_params, engine.cfg, path,
            meta={"version": model.version.global_step},
        )

    def _compute_dtype_params(self, role: str):
        """The role's compute-dtype copy of its weights, as the engine
        keeps it — weight-sync payloads travel in bf16: the generation
        fleet computes in bf16 anyway, and halves the transport bytes of
        the f32 masters. Shared: a publish reads it after this step ends,
        so the engine's next apply leaves it alone."""
        return self.models[role].module.compute_params(share=True)

    def publish_weights(self, role: str) -> None:
        """The §3.5 weight-sync path: make the role's weights visible to
        the generation fleet and bump names.model_version.

        Transport "stream" (docs/weight_sync.md) hands the tensors to a
        per-role WeightStreamPublisher: servers pull per-tensor chunks
        over ZMQ straight from this process's host cache — no checkpoint
        round-trip through the filesystem. Transport "device" never leaves
        the accelerator: the live params reshard into the generation
        fleet's layout on device (parallel/reshard.py) and servers swap
        them straight out of the publish registry. Transport "disk" is the
        legacy fallback: NATIVE pytree format under the realloc dir
        (models/hf.py save_native_checkpoint — skips HF layout conversion
        both ways; persistent "save" hooks stay HF)."""
        if self.cfg.weight_sync.transport == "stream":
            self._publish_weights_stream(role)
            return
        if self.cfg.weight_sync.transport == "device":
            self._publish_weights_device(role)
            return
        model = self.models[role]
        version = model.version.global_step
        path = os.path.join(self.cfg.realloc_dir, role, str(version))
        t0 = time.monotonic()
        with telemetry.span("trainer/weight_publish", role=role,
                            version=version, transport="disk"), \
                self._ledger.state("comm"), \
                memwatch.watermark("trainer/weight_publish"):
            self._save_role(role, path, fmt="native")
        save_secs = time.monotonic() - t0
        telemetry.set_gauge("trainer/weight_publish_secs", save_secs)
        telemetry.inc("trainer/weight_publishes")
        if not self._rank0:
            return
        # A crashed stream/device-mode predecessor may have left its
        # discovery keys in name_resolve; clear them so the manager's
        # transport auto-detection routes this publish (and all later
        # ones) at the disk checkpoint instead of a dead publisher.
        self._clear_stale_transport_keys(role, keep="disk")
        self._bump_version(role, version, save_secs)
        logger.info(
            f"published {role} weights v{version} -> {path} "
            f"(save {save_secs:.2f}s)"
        )

    def _publish_weights_stream(self, role: str) -> None:
        from areal_tpu.models.hf import flatten_pytree

        model = self.models[role]
        version = model.version.global_step
        t0 = time.monotonic()
        params = self._compute_dtype_params(role)
        if self.cfg.dist_world > 1:
            # Multi-host: every rank joins the gather; only rank 0 owns a
            # publisher, so the others contribute their shards and return.
            from areal_tpu.parallel import distributed as dist

            params = dist.allgather_params(params)
        if not self._rank0:
            return
        pub = self._weight_publishers.get(role)
        if pub is None:
            from areal_tpu.system.weight_stream import WeightStreamPublisher

            pub = WeightStreamPublisher(
                self.cfg.experiment, self.cfg.trial, role,
                chunk_bytes=self.cfg.weight_sync.chunk_mb << 20,
            )
            self._weight_publishers[role] = pub
        # publish() returns the moment the manifest is registered: the d2h
        # gather runs in the publisher's background thread, overlapping the
        # wire leg of tensors already gathered (and the servers' uploads).
        with telemetry.span("trainer/weight_publish", role=role,
                            version=version, transport="stream"), \
                self._ledger.state("comm"), \
                memwatch.watermark("trainer/weight_publish"):
            pub.publish(sorted(flatten_pytree(params).items()), version)
        publish_secs = time.monotonic() - t0
        telemetry.set_gauge("trainer/weight_publish_secs", publish_secs)
        telemetry.inc("trainer/weight_publishes")
        self._clear_stale_transport_keys(role, keep="stream")
        self._bump_version(role, version, publish_secs)
        logger.info(
            f"published {role} weights v{version} -> {pub.endpoint} "
            f"(stream publish {publish_secs:.2f}s; gather continues in "
            f"background)"
        )

    def _publish_weights_device(self, role: str) -> None:
        """Transport "device" (docs/weight_sync.md): reshard the live
        params into the generation fleet's layout ON DEVICE and register
        the result in the in-process publish registry — no d2h, no wire,
        no disk. The fanout payload carries the publication digest out of
        band, so the generation server's swap stays manifest/digest-gated
        exactly like the streamed path."""
        from areal_tpu.parallel import reshard as rsh

        model = self.models[role]
        version = model.version.global_step
        t0 = time.monotonic()
        params = self._compute_dtype_params(role)
        target = self._device_publish_shardings(role, params)
        with telemetry.span("trainer/weight_publish", role=role,
                            version=version, transport="device"), \
                self._ledger.state("comm"), \
                memwatch.watermark("trainer/weight_publish"):
            pub = rsh.publish_device(
                self.cfg.experiment, self.cfg.trial, role, params,
                target_shardings=target, version=version,
                group_mb=self.cfg.weight_sync.transfer_group_mb,
            )
        publish_secs = time.monotonic() - t0
        telemetry.set_gauge("trainer/weight_publish_secs", publish_secs)
        # First-class latency histogram: the device transport's whole
        # point is taking this from minutes to sub-second — the
        # distribution (not just the last value) is the acceptance metric.
        telemetry.observe("trainer/weight_publish_latency_secs",
                          publish_secs)
        telemetry.inc("trainer/weight_publishes")
        if not self._rank0:
            return
        self._clear_stale_transport_keys(role, keep="device")
        self._bump_version(role, version, publish_secs)
        logger.info(
            f"published {role} weights v{version} on device "
            f"({pub.plan.n_moved} leaves moved/"
            f"{len(pub.plan.identical)} zero-copy, "
            f"{publish_secs:.3f}s)"
        )

    def _device_publish_shardings(self, role: str, params):
        """Target layout for a device publish: the gen fleet's spec when
        configured (weight_sync.gen_parallel_spec — decoupled experiments
        thread AllocationMode.gen_spec through), else the ungridded
        single-device layout un-meshed generation servers hold."""
        from areal_tpu.parallel import mesh as pmesh
        from areal_tpu.parallel import reshard as rsh

        gen_spec = self.cfg.weight_sync.gen_parallel_spec
        engine = self.models[role].module
        model_cfg = getattr(engine, "cfg", None)
        if gen_spec and model_cfg is not None:
            mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse(gen_spec))
            return rsh.model_shardings(mesh, model_cfg)
        return rsh.shardings_like(params, rsh.model_shardings(None, None))

    def _clear_stale_transport_keys(self, role: str, keep: str) -> None:
        """Drop the OTHER transports' discovery keys so the manager's
        auto-detection can never steer a fanout at a transport this
        trainer is not publishing on (e.g. a crashed predecessor's dead
        stream endpoint, or a stale device registry descriptor)."""
        stale = {
            "stream": names.weight_stream,
            "device": names.weight_device,
        }
        stale.pop(keep, None)
        for fn in stale.values():
            try:
                name_resolve.delete(
                    fn(self.cfg.experiment, self.cfg.trial, role)
                )
            except Exception:  # noqa: BLE001 — normally absent
                pass

    def _bump_version(self, role: str, version: int,
                      publish_secs: float) -> None:
        # Publish time anchors the end-to-end weight-sync latency metric
        # (publish start → every server swapped; GserverManager reads it).
        name_resolve.add(
            names.model_version_time(
                self.cfg.experiment, self.cfg.trial, role
            ),
            repr(time.time() - publish_secs), replace=True,
        )
        name_resolve.add(
            names.model_version(self.cfg.experiment, self.cfg.trial, role),
            str(version), replace=True,
        )

    def _handle_model_info(self) -> Dict[str, Any]:
        """Model geometry + device info for the master's FLOPs/MFU logging
        (reference FlopsCounter inputs, flops_counter.py:15)."""
        import jax

        from areal_tpu.models.transformer import (
            activated_param_count,
            param_count,
        )

        info: Dict[str, Any] = {
            "n_devices": jax.device_count(),
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "roles": {},
        }
        for role, m in self.models.items():
            engine = m.module
            cfg = getattr(engine, "cfg", None)
            if cfg is None:
                continue
            info["roles"][role] = {
                "n_layers": cfg.n_layers, "hidden_dim": cfg.hidden_dim,
                "q_dim": cfg.q_dim, "kv_dim": cfg.kv_dim,
                "intermediate_dim": cfg.intermediate_dim,
                "vocab_size": cfg.vocab_size, "is_critic": cfg.is_critic,
                "n_params": param_count(cfg),
                # Activated params (per-token compute) — for MoE, only
                # top_k of num_experts FFNs run per token; the master's
                # MFU accounting must not count idle expert weights.
                "n_params_activated": activated_param_count(cfg),
                "moe": None if getattr(cfg, "moe", None) is None else {
                    "num_experts": cfg.moe.num_experts,
                    "top_k": cfg.moe.top_k,
                    "routed_intermediate_dim":
                        cfg.moe.routed_intermediate_dim,
                    "shared_intermediate_dim":
                        cfg.moe.shared_intermediate_dim,
                },
                # Remat recomputes activations in backward → 4× forward
                # FLOPs instead of 3× (reference checkpoint_activations
                # factor); the master's MFU math needs to know.
                "remat": bool(getattr(engine, "remat", False)),
            }
        return info

    def _handle_clear(self, p: Payload) -> Any:
        sids = list(p.data or [])
        for sid in sids:
            self.store.pop(sid, None)
        if self._ingest is not None and sids:
            # Freed ids are SETTLED samples (fully consumed by every MFC
            # after the optimizer step committed, or durably dropped by
            # the master's buffer) — the ack point of the at-least-once
            # delivery loop. Rank 0 only: followers replay "clear" for
            # the store pop, but _ingest exists only where the puller is.
            self._send_acks(self._ingest.pop_settled(sids))
        return {"n_stored": len(self.store)}

    # ---------------- checkpoint / restore ----------------
    #
    # Parity: the reference's recover checkpoints save optimizer shards +
    # interface state so a restarted run continues the same trajectory
    # (megatron.py:711-760, master_worker.py:585). One "ckpt" request saves
    # every trainable role's (params, opt_state, version) + per-MFC
    # interface state (kl controller, value RMS) + the dataset cursor.

    def _handle_ckpt(self, p: Payload) -> Any:
        import json

        ckpt_dir = p.data["dir"]
        if self._rank0:
            os.makedirs(ckpt_dir, exist_ok=True)
        meta: Dict[str, Any] = {
            "versions": {}, "epoch": self._epoch, "epoch_pos": self._epoch_pos,
        }
        for role, model in self.models.items():
            engine = model.module
            if hasattr(engine, "save_train_state"):
                # Multi-host: all ranks join the gather; rank 0 writes.
                engine.save_train_state(os.path.join(ckpt_dir, role))
            meta["versions"][role] = model.version.global_step
        if not self._rank0:
            return {"ok": True}
        iface_states = {}
        for mfc_name, iface in self.interfaces.items():
            if hasattr(iface, "state_dict"):
                iface_states[mfc_name] = iface.state_dict()
        # Atomic write: trainer_state.json doubles as the legacy
        # completeness signal (recover.ckpt_is_complete), so a crash
        # mid-dump must leave no torn file behind.
        path = os.path.join(ckpt_dir, "trainer_state.json")
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"meta": meta, "interfaces": iface_states}, f)
        os.replace(tmp, path)
        logger.info(f"checkpointed trainer state -> {ckpt_dir}")
        return {"ok": True}

    def _handle_restore(self, p: Payload) -> Any:
        import json

        ckpt_dir = p.data["dir"]
        with open(os.path.join(ckpt_dir, "trainer_state.json")) as f:
            d = json.load(f)
        meta = d["meta"]
        for role, model in self.models.items():
            engine = model.module
            role_dir = os.path.join(ckpt_dir, role)
            if hasattr(engine, "load_train_state") and os.path.isdir(role_dir):
                engine.load_train_state(role_dir)
            model.version.global_step = int(meta["versions"].get(role, 0))
        for mfc_name, st in d["interfaces"].items():
            iface = self.interfaces.get(mfc_name)
            if iface is not None and hasattr(iface, "load_state_dict"):
                iface.load_state_dict(st)
        self._epoch = int(meta["epoch"])
        self._epoch_pos = int(meta["epoch_pos"])
        if self._dataset is not None:
            # Same seed ⇒ same permutation; restoring (epoch, pos) resumes
            # the dataset exactly where the checkpoint left it, so consumed
            # samples are not retrained (reference hash_vals_to_ignore).
            rng = np.random.RandomState(self._epoch + 1)
            self._data_iter = list(rng.permutation(len(self._dataset)))
        logger.info(f"restored trainer state from {ckpt_dir}")
        return {"ok": True, "versions": meta["versions"]}

    # ---------------- loop ----------------

    def _dispatch(self, p: Payload) -> None:
        """Execute one request (all ranks run this identically)."""
        try:
            if p.handle_name == "fetch":
                p.output = self._handle_fetch(p)
            elif p.handle_name == "mfc":
                p.output = self._handle_mfc(p)
            elif p.handle_name == "clear":
                p.output = self._handle_clear(p)
            elif p.handle_name == "version":
                p.output = {
                    r: m.version.global_step for r, m in self.models.items()
                }
            elif p.handle_name == "model_info":
                p.output = self._handle_model_info()
            elif p.handle_name == "ckpt":
                p.output = self._handle_ckpt(p)
            elif p.handle_name == "restore":
                p.output = self._handle_restore(p)
            elif p.handle_name == "exit":
                # Before the reply: the launcher tears the fleet down as
                # soon as the master has its "bye".
                self._log_device_report("exit")
                p.output = "bye"
                self._exiting = True
            else:
                raise ValueError(f"unknown handle {p.handle_name}")
        except Exception as e:  # noqa: BLE001 — surfaced to the master
            import traceback

            p.exception = f"{e}\n{traceback.format_exc()}"
            logger.error(f"handler {p.handle_name} failed: {p.exception}")

    def serve_once(self, timeout_ms: int = 100) -> bool:
        p = self._server.poll(timeout_ms)
        if p is None:
            return False
        if p.handle_name != "fetch":
            # _handle_fetch broadcasts its own (request, batch) pair after
            # the rank-0-only data read; everything else replays verbatim.
            self._bcast(("cmd", p.handle_name, p.data, p.mb_spec,
                         p.pre_hooks, p.post_hooks))
        self._dispatch(p)
        self._server.reply(p)
        return True

    def _follow_once(self) -> None:
        """Rank > 0: receive one broadcast command and replay it."""
        from areal_tpu.parallel import distributed as dist

        msg = dist.broadcast_pyobj(None)
        if msg[0] == "fetch":
            self._store_batch(msg[1])
            return
        _, handle_name, data, mb_spec, pre, post = msg
        p = Payload(handler=self.cfg.handler, handle_name=handle_name,
                    data=data, mb_spec=mb_spec, pre_hooks=pre,
                    post_hooks=post)
        self._dispatch(p)
        if p.exception:
            # Deterministic errors fail identically on every rank; mirroring
            # rank 0 (catch, log, keep serving) keeps the group in lockstep.
            # But a rank-LOCAL failure of a state-mutating handler (mfc
            # optimizer step, restore, clear) means this rank's params/state
            # now diverge from the group — continuing would train silently
            # corrupted. Fail loudly instead; the launcher's child monitor
            # tears the run down.
            if handle_name in ("mfc", "restore", "clear"):
                raise RuntimeError(
                    f"rank {self.cfg.dist_rank} replay of state-mutating "
                    f"{handle_name} failed — exiting to avoid silent SPMD "
                    f"divergence: {p.exception}"
                )
            logger.error(
                f"rank {self.cfg.dist_rank} replay of {handle_name} failed "
                f"(read-only; continuing to stay in sync): {p.exception}"
            )

    def run(self) -> None:
        from areal_tpu.system.worker_base import WorkerControl

        self.setup()
        if self._rank0:
            # Lifecycle FSM endpoint (reference worker_base.py:474); only
            # rank 0 serves it — pausing rank 0 stalls the whole SPMD group
            # at the next broadcast, which is exactly pause semantics.
            # Compile-aware liveness: the heartbeat thread publishes
            # names.compile_inflight while a jit compile is in progress
            # so the sentinel's trainer_stalled rule can tell a warmup
            # compile from a wedge (the NULL watch's inflight() is a
            # constant False — zero traffic when disabled).
            ctrl = WorkerControl(
                self.cfg.experiment, self.cfg.trial, self.cfg.handler,
                inflight_fn=compile_watch.inflight,
            )
            # Liveness: the control heartbeat also keeps the trainer's
            # stream advertisements leased (request ROUTER + trajectory
            # puller) — a SIGKILLed trainer's stale addresses expire
            # instead of swallowing a recovered master's requests; the
            # value rides along so a lapsed lease re-registers.
            if self._server is not None:
                ctrl.lease(self._server._key, self._server._addr)
            if self._puller is not None:
                ctrl.lease(self._puller._key, self._puller._addr)
            while not self._exiting:
                ctrl.step(lambda: {"roles": sorted(self.models)})
                if ctrl.should_exit:
                    break
                if self._profiler is not None:
                    # Operator-requested jax.profiler capture (rate-limited
                    # name-resolve poll; docs/observability.md).
                    self._profiler.poll()
                self.serve_once(timeout_ms=100)
                # Accrue the in-progress state (idle between requests)
                # so the scrape moves even when no handler runs.
                self._ledger.poll()
                # HBM gauges piggyback on the serve cadence (rate-limited
                # inside the watch; the NULL watch is a no-op).
                memwatch.sample()
                telemetry.set_gauge("trainer/store_size", len(self.store))
            ctrl.close()
        else:
            while not self._exiting:
                self._follow_once()
        if self._server:
            self._server.close()
        if self._pull_thread is not None:
            # _exiting is set; the loop exits within one 200ms poll. Join
            # before close — destroying the socket under a live poll
            # raises ENOTSOCK in the thread.
            self._pull_thread.join(timeout=2.0)
        if self._puller:
            self._puller.close()
        for pusher in self._ack_pushers.values():
            pusher.close()
        for pub in self._weight_publishers.values():
            pub.close()
        self._ledger.flush()
        memwatch.shutdown()
        compile_watch.shutdown()
        telemetry.shutdown()  # final flush to the aggregator
