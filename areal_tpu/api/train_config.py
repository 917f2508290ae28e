"""Dependency-free leaf config dataclasses shared by the config tree.

These used to live in ``backend/jax_train.py`` and
``system/master_worker.py``, which made ``api.cli_args`` (and therefore
every process that merely parses configs — ``--help``, CPU-only manager /
rollout children) import jax+optax at startup (advisor r2). They are
re-exported from their original homes for compatibility.

Parity targets: reference ``cli_args.py:173`` (OptimizerConfig) and
``cli_args.py:702`` (ExperimentSaveEvalControl).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional


@dataclasses.dataclass
class OptimizerConfig:
    """Reference cli_args.py:173 (OptimizerConfig)."""

    type: str = "adamw"
    lr: float = 1e-5
    weight_decay: float = 0.05
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-5
    min_lr_ratio: float = 0.0
    warmup_steps_proportion: float = 0.02
    lr_scheduler_type: str = "constant"  # constant | cosine | linear
    gradient_clipping: float = 1.0
    # Adam moment storage dtypes (master params are always f32). bf16
    # moments halve optimizer HBM (the update math still runs in f32 per
    # step), but a bf16 default would silently lossy-cast f32 optimizer
    # states on resume — so BOTH default to exact f32; HBM-constrained
    # configs opt into bf16 explicitly.
    mu_dtype: Optional[str] = "float32"
    nu_dtype: Optional[str] = "float32"


@dataclasses.dataclass
class WeightSyncConfig:
    """Trainer→generation-fleet weight transport (docs/weight_sync.md).

    ``stream`` publishes per-tensor chunks over ZMQ straight from the
    trainer's host cache (system/weight_stream.py) — no checkpoint
    round-trip through the filesystem; ``disk`` is the legacy fallback
    (native-pytree checkpoint under the realloc dir); ``device`` keeps
    the weights on device end to end — the trainer reshards its live
    params into the generation fleet's layout (parallel/reshard.py) and
    servers swap them in with zero host hops. ``device`` requires the
    trainer and generation fleet to share one JAX runtime."""

    transport: str = "stream"  # stream | disk | device
    # Wire chunk size (MB) for the streamed transport; smaller chunks
    # pipeline finer, larger chunks amortize framing.
    chunk_mb: int = 32
    # In-flight chunk requests per consuming server.
    pipeline_depth: int = 4
    # Device transport: transfer-group byte budget (MB) for the mesh→mesh
    # reshard — peak extra HBM during a publish is ~one group of
    # target-layout leaves (docs/weight_sync.md §HBM headroom).
    transfer_group_mb: int = 64
    # Device transport: the generation fleet's ParallelSpec (e.g. "d4t2").
    # None publishes in the ungridded single-device layout — correct for
    # un-meshed generation servers; decoupled experiments thread
    # AllocationMode.gen_spec through here automatically.
    gen_parallel_spec: Optional[str] = None


@dataclasses.dataclass
class TelemetryConfig:
    """Unified telemetry layer (base/telemetry.py, docs/observability.md).

    Off by default: with ``enabled=False`` every instrumented call site
    routes to a shared no-op sink — no ZMQ sockets, no HTTP servers, no
    span allocation — so the hot paths carry no passive overhead."""

    enabled: bool = False
    # Worker→aggregator snapshot push cadence.
    flush_interval_secs: float = 2.0
    # Aggregated per-snapshot stream; defaults under the experiment log
    # dir (<log>/telemetry.jsonl) when the experiment tree wires it.
    jsonl_path: Optional[str] = None
    # >0: the master's aggregator serves the merged fleet state as
    # Prometheus text on this plain-HTTP port (GET /metrics).
    http_port: int = 0
    # Span buffer bound per process between flushes (oldest drop first).
    max_buffered_spans: int = 4096
    # ---- sample-lineage tracing + flight recorder ----
    # Stitched end-to-end traces (one JSON line per trained sample);
    # defaults next to telemetry.jsonl when unset.
    traces_path: Optional[str] = None
    # How long a terminal span waits for sibling workers' slower span
    # flushes before the trace is stitched. Should exceed
    # flush_interval_secs; lower it together with the flush interval.
    stitch_grace_secs: float = 5.0
    # Per-worker crash-evidence ring of recent span/event records
    # (0 disables the ring entirely).
    flight_recorder_len: int = 512
    # Where flight_<worker>.jsonl dumps land on crash/SIGTERM/eviction.
    # None: no crash hooks are installed (on-demand dumps still work —
    # the trigger request carries its own directory).
    flight_dir: Optional[str] = None


@dataclasses.dataclass
class GoodputConfig:
    """Goodput ledger (system/goodput.py, docs/observability.md §Goodput).

    Off by default: with ``enabled=False`` every instrumented worker gets
    the shared null ledger — no per-transition clock reads, no counters,
    no MFU math — so the hot paths carry zero new work and the Prometheus
    scrape is bit-identical to a build without the ledger. Enabled
    (requires ``telemetry.enabled``), each worker classifies its wall
    clock into ``compute / comm / data_wait / idle`` monotonic counters
    (``goodput_secs_total{state=...}`` on the scrape, so Prometheus
    ``rate()`` yields live utilization fractions), the trainer and
    generation servers export live achieved-TFLOP/s + MFU gauges against
    the per-generation peak table (``base/monitor.py``), and the master's
    TelemetryAggregator stitches fleet goodput (useful chip-seconds /
    total chip-seconds, split trainer vs generation side) onto the merged
    scrape and ``telemetry.jsonl``."""

    enabled: bool = False
    # Minimum interval between counter exports from a ledger into its
    # telemetry registry (transitions between exports only accrue
    # host-side floats).
    export_interval_secs: float = 1.0
    # Override the per-chip peak FLOP/s used for live MFU gauges; 0 =
    # look up the device kind (monitor.device_peak_flops). Off the TPU the
    # MFU gauges degrade to achieved-TFLOP/s-only with a one-time warning;
    # a TPU kind the table lacks is an error — set this for either (CPU
    # tests, unlisted hardware).
    peak_flops_override: float = 0.0


@dataclasses.dataclass
class CompileWatchConfig:
    """Compile & HBM observatory (base/compile_watch.py +
    system/memwatch.py, docs/observability.md §Compile & memory).

    Off by default: with ``enabled=False`` every ``watched_jit`` site
    gets the raw jitted function back (zero wrappers, zero per-call
    work), no device memory_stats poll ever runs, and the Prometheus
    scrape is bit-identical to a build without the observatory. Enabled
    (requires ``telemetry.enabled``), every chip-bearing worker records
    per-function compile events (trigger shapes, elapsed seconds,
    cumulative counts, a recompile-storm detector), publishes the
    compile-inflight flag its HeartbeatThread exports so sentinel absence
    rules become compile-aware, samples per-device HBM gauges with
    high-water marks around the big allocators, and the master derives
    fleet rollups plus the recompile_storm / hbm_pressure / compile_stall
    sentinel rules."""

    enabled: bool = False
    # Calls without a new compiled shape before a function counts as
    # shape-STABLE; a new shape after that is a storm event (the signal
    # the recompile_storm sentinel rule rates). Lower it in tests.
    storm_warmup_calls: int = 16
    # Min interval between device memory_stats polls (samples piggyback
    # on worker cadences — the trainer step loop, the generation
    # server's metrics endpoint — so this bounds poll cost, not wakeups).
    mem_sample_interval_secs: float = 10.0


@dataclasses.dataclass
class SentinelConfig:
    """Training-health sentinel (system/sentinel.py,
    docs/observability.md §Alerting).

    Off by default: nothing is constructed — zero threads, sockets, or
    allocations, and the merged Prometheus scrape is bit-identical to a
    build without the sentinel. Enabled (requires ``telemetry.enabled``),
    the master's TelemetryAggregator hosts a rule engine that evaluates a
    declarative rule pack (threshold / rate-of-change /
    rolling-baseline-deviation / absence-of-signal predicates, each with
    a ``for:`` hold duration, severity, and per-rule cooldown) over the
    merged fleet telemetry and the trainer's per-step training-dynamics
    series. Firing alerts land in ``alerts.jsonl``, export as
    ``areal_alerts_total{rule,severity}`` / ``areal_alert_active`` on the
    merged scrape, and capture evidence (fleet flight dumps, pinned trace
    ids, the triggering metric window, optional profiler capture) into
    ``evidence/<rule>-<ts>/`` while the anomaly is still live."""

    enabled: bool = False
    # Rule evaluation cadence inside the aggregator's ingest loop.
    eval_interval_secs: float = 1.0
    # Include the built-in divergence-signature rule pack
    # (system/sentinel.DEFAULT_RULES; table in docs/observability.md).
    default_rules: bool = True
    # Extra rules (dicts in the rule grammar; validated at parse time —
    # unknown metrics, non-positive durations, and duplicate ids are
    # rejected with an error naming the rule). Primarily set via YAML.
    rules: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    # A source (one worker's reading of a metric) that has not reported
    # a value within this window is dropped from rule aggregation — a
    # scaled-down/evicted worker's last gauge must not pin a max/sum
    # aggregate (and a false alert) forever.
    source_expiry_secs: float = 120.0
    # Alert stream; defaults next to telemetry.jsonl.
    alerts_path: Optional[str] = None
    # Per-alert evidence bundles; defaults to <log>/evidence.
    evidence_dir: Optional[str] = None
    # Hard cap on bundles per run (beyond it alerts still fire and
    # export, but capture is skipped and counted).
    max_evidence_bundles: int = 8
    # Critical alerts also request an on-demand jax.profiler capture on
    # the trainer into the bundle (off by default: a capture costs real
    # trainer time exactly when the run is struggling).
    profile_on_critical: bool = False
    profile_secs: float = 5.0
    # How many recent stitched trace ids to pin into each bundle.
    pinned_traces: int = 8
    # Rules with action=pause may command a master pause at the next
    # step boundary (WorkerControl panel). Off by default — an operator
    # must opt into the sentinel stopping a run.
    allow_pause: bool = False
    # Critical alerts publish an autoscale-inhibit hint so the fleet
    # does not scale up into a diverging run (system/autoscaler).
    autoscale_inhibit: bool = True
    inhibit_secs: float = 300.0


@dataclasses.dataclass
class ServingConfig:
    """Generation-fleet serving engine (system/serving.py, docs/serving.md).

    Off by default, like telemetry: with ``enabled=False`` the generation
    server behaves exactly like the legacy rollout-only decode loop — one
    FIFO queue without admission limits, no cross-request KV reuse, and
    the legacy unbounded ``kv_bucket``-multiple capacity rounding. The
    distinct-compiled-shapes gauge is tracked either way."""

    enabled: bool = False
    # ---- admission control (per request class; 0 = unbounded) ----
    # Bounded queues replace unbounded pending growth: a full class queue
    # rejects with HTTP 429 + a Retry-After hint instead of absorbing an
    # arbitrarily deep backlog the SLOs could never recover from.
    queue_limit_rollout: int = 512
    queue_limit_interactive: int = 64
    queue_limit_eval: int = 128
    retry_after_secs: float = 0.5
    # Fraction of each drained batch reserved for the lowest-priority
    # class (rollout) while it has waiters, clamped to [0, 1]. Strict
    # priority alone would let sustained interactive/eval load starve
    # rollouts indefinitely and stall training data production; 0
    # restores strict priority.
    min_rollout_share: float = 0.25
    # ---- cross-request prefix-reuse KV ----
    # Seed a new request's decode state from another request's retained
    # KV when their token prefixes overlap (system prompts, shared
    # few-shot preambles, group sampling over one prompt).
    prefix_reuse: bool = True
    # Shared prefixes shorter than this re-prefill: the clone/extend
    # dispatch costs more than the prefill it would save.
    min_prefix_tokens: int = 4
    # ---- bounded compile shapes (VERDICT #9) ----
    # Decode chunk lengths are rounded UP to one of these buckets (empty =
    # a factor-4 geometric ladder down from chunk_tokens, so small-budget
    # batches scan a small chunk); per-row budgets stop shorter requests
    # early so rounding up never over-generates.
    chunk_buckets: List[int] = dataclasses.field(default_factory=list)
    # Decode/prefill batch rows are padded up to one of these buckets
    # (empty = powers of two up to max_batch_size).
    row_buckets: List[int] = dataclasses.field(default_factory=list)
    # KV capacities are kv_bucket * 2^k up to this ceiling; prompts that
    # cannot fit are rejected at admission (HTTP 413) instead of minting a
    # fresh compiled shape per length.
    max_kv_capacity: int = 16384
    # Hard cap on the distinct-compiled-shapes gauge. The policy refuses
    # (at construction) bucket configs whose WORST-CASE shape count —
    # decode (rows x capacities x chunks) + prefill (rows x widths x
    # chunks) + suffix-extend (widths x capacities) — exceeds it, so the
    # gauge can never pass the cap at runtime. The default ladders
    # (geometric capacities/rows/widths, 4-bucket chunk ladder) come to
    # ~480 worst-case; observed counts run far lower.
    max_compiled_shapes: int = 512


@dataclasses.dataclass
class RewardServiceConfig:
    """Sandboxed reward service — the sixth worker kind
    (system/reward_worker.py + rewards/service.py, docs/rewards.md).

    Off by default: with ``enabled=False`` reward grading runs exactly the
    legacy local path (rewards/math_verify.py / rewards/code_verify.py on
    the calling worker's thread pool) — bit-identical outputs, no sockets.
    Enabled, the launcher spawns ``n_workers`` CPU reward workers; each
    hosts an HTTP sandbox fleet member that grades math/code tasks in
    rlimit-guarded subprocess pools, and the rollout/trainer reward paths
    fan out to them (rewards/client.py) with bounded in-flight
    concurrency, capped-exponential retry across surviving replicas, and
    partial-batch degradation to local grading when the fleet is
    unreachable (parity: the reference's 3k-LoC functioncall service,
    ``functioncall/base/call.py:81-235``)."""

    enabled: bool = False
    # Sandbox fleet size (one reward worker process each; CPU-only).
    n_workers: int = 1
    # Fixed port of worker 0 (workers i bind port+i); 0 = random ports,
    # discovered through name_resolve either way.
    port: int = 0
    # ---- worker-side grading ----
    # Concurrent grading slots per worker, clamped to pool_size at
    # runtime (an admitted task must start grading immediately so the
    # wall budget never times executor-queue wait).
    max_inflight: int = 16
    # Grader threads per worker; each code grade additionally runs its
    # own rlimit-guarded subprocess (rewards/code_verify.py).
    pool_size: int = 8
    # Server-side wall budget per task: a grade that overruns returns a
    # 0.0 verdict with verdict="timeout" and bumps reward_timeouts_total.
    # Bounds a WEDGED grader: code tasks floor at their legal worst case
    # (per-case timeout x sampled cases) so slow-but-correct programs
    # never get spurious timeout verdicts (rewards/service.py).
    grade_timeout_secs: float = 30.0
    # Languages this fleet will grade; tasks in other languages return a
    # 0.0 verdict with verdict="unsupported_language" (per-task dispatch:
    # rewards/code_verify.py GRADERS — C++/bash slot in there).
    languages: List[str] = dataclasses.field(
        default_factory=lambda: ["python"]
    )
    # ---- client-side fanout (rewards/client.py) ----
    # In-flight request cap across one batch fanout.
    max_concurrency: int = 64
    # Per-task HTTP timeout (covers queue wait + grading on the worker).
    request_timeout_secs: float = 120.0
    # Retries per task across surviving replicas before degrading.
    max_retries: int = 2
    retry_base_delay_secs: float = 0.2
    retry_max_delay_secs: float = 2.0
    # Degrade to local grading when the fleet is unreachable / a task's
    # retry budget is exhausted. False: failed tasks score 0.0 instead of
    # executing untrusted code in the calling process.
    local_fallback: bool = True


@dataclasses.dataclass
class AutoscaleConfig:
    """Elastic generation-fleet autoscaling (system/autoscaler.py,
    docs/fault_tolerance.md §Autoscaling).

    Off by default. Enabled, the gserver manager hosts a slow control
    loop that computes a target fleet size from live telemetry signals
    (rollout capacity utilization, per-server queue depth, staleness
    gate, time-to-first-chunk SLO misses, weight-fanout ack latency,
    heartbeat ages) with hysteresis + cooldown, publishes the plan
    through name_resolve, and the launcher-side executor spawns
    supervised single-server workers to meet it. Scale-down and
    straggler defense go through the manager's **cordon** state: the
    server stops receiving leases, inflight rollouts drain (or fail
    over), then a WorkerControl-commanded exit reaps the process."""

    enabled: bool = False
    # Fleet-size bounds on the ROUTABLE server count. min_servers should
    # not exceed the baseline fleet unless scale-up capacity exists.
    min_servers: int = 1
    max_servers: int = 4
    # Decision cadence of the manager-side control loop.
    interval_secs: float = 5.0
    # ---- scale-up / scale-down pressure thresholds ----
    # Rollout capacity utilization (running / max_concurrent_rollouts).
    up_utilization: float = 0.85
    down_utilization: float = 0.25
    # Mean per-server decode queue depth (reported by /health).
    queue_high: float = 8.0
    queue_low: float = 1.0
    # Time-to-first-chunk SLO: a server whose recent TTFC EWMA exceeds
    # this is an SLO miss; scale up when >= slo_miss_fraction of the
    # fleet misses. 0 disables the SLO signal.
    slo_ttfc_secs: float = 0.0
    slo_miss_fraction: float = 0.5
    # Weight-fanout ack latency high-water (0 disables): a fleet too
    # busy to ack weight pushes promptly needs more capacity.
    fanout_ack_high_secs: float = 0.0
    # ---- hysteresis + cooldown (both directions move 1 server/step) ----
    up_consecutive: int = 2
    down_consecutive: int = 5
    scale_up_cooldown_secs: float = 30.0
    scale_down_cooldown_secs: float = 120.0
    # ---- cordon-and-drain ----
    # How long a cordoned server may drain its inflight rollouts before
    # the exit proceeds anyway (clients fail over via chunk replay).
    drain_timeout_secs: float = 120.0
    # ---- straggler defense (per-server decode-latency EWMAs) ----
    straggler_defense: bool = True
    # A server is "slow" when its decode EWMA exceeds factor x the
    # median of its peers (self excluded) for consecutive sweeps:
    # deprioritized after straggler_slow_sweeps, cordoned after
    # straggler_cordon_sweeps. Samples below floor_secs are noise.
    straggler_factor: float = 3.0
    straggler_min_probes: int = 5
    straggler_slow_sweeps: int = 2
    straggler_cordon_sweeps: int = 6
    straggler_floor_secs: float = 0.002
    # ---- overload backpressure ----
    # When the fleet is pinned at max_servers and still saturated,
    # /allocate_rollout capacity denials carry this Retry-After hint so
    # rollout workers slow prompt admission instead of hammering the
    # gate every 0.5s.
    backpressure_retry_secs: float = 2.0


@dataclasses.dataclass
class FaultToleranceConfig:
    """Launcher-level supervision + liveness (system/supervisor.py,
    docs/fault_tolerance.md).

    The supervisor classifies child death by failure domain: stateless
    workers (rollout workers, the gen-fleet process) are respawned in
    place with exponential backoff behind a crash-loop circuit breaker;
    stateful workers (trainer) escalate to the whole-experiment
    ``recover_mode=auto`` relaunch. Liveness is grounded in name-resolve
    keepalive leases: supervised workers register their advertisements
    with ``keepalive_ttl_secs`` and heartbeat them from a dedicated
    thread, so a SIGKILLed worker's ghost keys expire instead of being
    addressed forever."""

    # False restores the legacy behavior: ANY child death tears the
    # experiment down (run_experiment's relaunch loop still applies).
    supervise: bool = True
    # Crash-loop circuit breaker: more than this many restarts of one
    # worker inside the rolling window escalates to a full relaunch.
    max_restarts: int = 3
    restart_window_secs: float = 300.0
    # Respawn backoff (per worker, reset outside the window).
    backoff_base_secs: float = 0.5
    backoff_max_secs: float = 30.0
    backoff_multiplier: float = 2.0
    # Liveness lease on worker/stream advertisements (0 disables leases;
    # heartbeats default to ttl/3).
    keepalive_ttl_secs: float = 15.0
    heartbeat_interval_secs: float = 0.0
    # Graceful drain (SIGTERM): budget for pause -> out-of-band recover
    # checkpoint -> orderly exits before falling back to terminate().
    drain_timeout_secs: float = 60.0
    # Backoff between whole-experiment relaunch attempts
    # (run_experiment's recover_mode=auto/fault loop).
    relaunch_backoff_secs: float = 5.0
    relaunch_backoff_max_secs: float = 60.0


@dataclasses.dataclass
class DurabilityConfig:
    """Durable rollout→trainer sample delivery (system/sample_spool.py,
    docs/fault_tolerance.md §Data durability).

    Enabled, every accepted trajectory is fsynced to a per-rollout-worker
    append-only spool BEFORE its prompt is marked consumed, pushes carry
    ``(worker_index, spool_seqno)``, and the trainer acks a seqno back
    only once the sample is trained (optimizer step committed → the
    master's freed-id "clear" forwarding) or durably dropped (too-stale
    replay). A trainer/master death therefore costs replay, not samples:
    the worker re-sends unacked records and the trainer ingests them
    idempotently (dedup by sample id).

    Off by default: no spool is created, no ``_spool`` key is injected,
    and the push wire bytes are bit-identical to the non-durable format
    (pinned by tests/test_sample_spool.py)."""

    enabled: bool = False
    # Spool segment roll size; acked prefixes are deleted whole-segment.
    spool_segment_bytes: int = 8 * 1024 * 1024
    # Total on-disk (and in-memory mirror) cap per worker. Appends past
    # it block the submitting rollout — backpressure, not sample loss.
    spool_max_bytes: int = 256 * 1024 * 1024
    # A record unacked this long after its last send is re-sent with the
    # replay flag (covers trainer restarts and lost acks).
    resend_timeout_secs: float = 30.0
    # Replayed samples re-enter a staleness gate at the trainer: a
    # replay whose version_end lags the current trained version by more
    # than this many versions is durably dropped (and acked), counted in
    # spool/replay_stale_dropped. Negative disables the gate.
    replay_staleness_limit: int = 8
    # On clean worker exit, wait this long for in-flight acks so the
    # spool drains instead of replaying next incarnation.
    drain_timeout_secs: float = 5.0
    # Bounded-retry budget for a blocked ZMQ push (streams.ZmqPusher);
    # with durability on only the background sender ever blocks.
    push_block_secs: float = 120.0


@dataclasses.dataclass
class ExperimentSaveEvalControl:
    """Reference cli_args.py:702."""

    total_train_epochs: int = 1
    benchmark_steps: Optional[int] = None  # stop after N train steps
    save_freq_steps: Optional[int] = None
    ckpt_freq_steps: Optional[int] = None
    ckpt_freq_secs: Optional[int] = None
    eval_freq_steps: Optional[int] = None
