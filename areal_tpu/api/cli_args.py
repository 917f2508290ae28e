"""Experiment configuration tree + CLI/YAML merge.

Parity target: ``realhf/api/cli_args.py`` (1558 LoC) — the single-file
dataclass config tree that hydra merges YAML and dotted CLI overrides onto.
We have no hydra in the TPU image, so this module also implements the merge
itself: :func:`apply_overrides` walks dotted ``a.b.c=value`` assignments
onto a (nested) dataclass instance with field-type coercion and typo-safe
errors, and :func:`load_yaml`/:func:`to_yaml_dict` round-trip configs the
way the reference dumps ``config.yaml`` next to each run
(``training/main_async_ppo.py:40-50``).

Field names deliberately mirror the reference so launch commands like
``examples/run_async_ppo.sh`` port verbatim (that IS the compatibility
contract): ``allocation_mode=...``, ``actor.type._class=qwen3``,
``dataset.train_bs_n_seqs=32``, ``ppo.gen.max_new_tokens=4096``,
``actor_train.mb_spec.max_tokens_per_mb=32768``,
``max_head_offpolicyness=4`` …
"""

from __future__ import annotations

import dataclasses
import difflib
import typing
from typing import Any, Dict, List, Optional

from areal_tpu.api.data import MicroBatchSpec
from areal_tpu.api.model import GenerationHyperparameters  # noqa: F401

# Re-exported so experiment configs can be built from this one module, the
# way everything in the reference imports from realhf.api.cli_args. These
# live in the dependency-free api.train_config so that parsing configs
# never drags in jax/optax (CPU-only children, `--help`).
from areal_tpu.api.train_config import (  # noqa: F401
    AutoscaleConfig,
    CompileWatchConfig,
    DurabilityConfig,
    ExperimentSaveEvalControl,
    FaultToleranceConfig,
    GoodputConfig,
    OptimizerConfig,
    RewardServiceConfig,
    SentinelConfig,
    ServingConfig,
    TelemetryConfig,
    WeightSyncConfig,
)


# --------------------------------------------------------------------------
# leaf config groups
# --------------------------------------------------------------------------


@dataclasses.dataclass
class ModelFamily:
    """Reference cli_args.py:99. ``_class`` picks the HF family converter
    (llama/qwen2/qwen3/...), or "tiny" for fabricated test models."""

    _class: str = "qwen3"
    size: int = 0
    is_critic: bool = False


@dataclasses.dataclass
class ModelTrainEvalConfig:
    """One model role (reference cli_args.py:433).

    TPU notes: ``backend`` is the jax train/inference engine for every
    trainable role; Megatron-only knobs (ddp, overlap_grad_reduce, ...)
    have no analogue under GSPMD and are intentionally absent.
    """

    type: ModelFamily = dataclasses.field(default_factory=ModelFamily)
    path: str = ""  # HF checkpoint dir (or empty with init_from_scratch)
    init_from_scratch: bool = False
    gradient_checkpointing: bool = True
    bf16: bool = True
    optimizer: OptimizerConfig = dataclasses.field(
        default_factory=OptimizerConfig
    )
    backend: str = "jax_train"
    # Fabricated tiny model for CPU tests (reference base/testing.py models):
    # e.g. actor.tiny.vocab_size=258. Empty = use `path`.
    tiny: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class MFCConfig:
    """Per-MFC runtime knobs (reference cli_args.py:496)."""

    mb_spec: MicroBatchSpec = dataclasses.field(default_factory=MicroBatchSpec)


@dataclasses.dataclass
class PromptOnlyDatasetConfig:
    """Reference cli_args.py:44 (PromptOnlyDatasetConfig)."""

    path: str = ""
    max_prompt_len: int = 1024
    train_bs_n_seqs: int = 256
    fill_to_max_length: bool = False


@dataclasses.dataclass
class PromptAnswerDatasetConfig:
    """SFT dataset (reference cli_args.py:58)."""

    path: str = ""
    max_seqlen: int = 1024
    train_bs_n_seqs: int = 256
    valid_bs_n_seqs: int = 256
    fill_to_max_length: bool = False


from areal_tpu.base.name_resolve import NameResolveConfig  # noqa: F401,E402


@dataclasses.dataclass
class ClusterSpecConfig:
    """Reference cli_args.py:896."""

    fileroot: str = "/tmp/areal_tpu/experiments"
    n_nodes: int = 1
    n_gpus_per_node: int = 8  # chips per host on TPU; name kept for parity
    name_resolve: NameResolveConfig = dataclasses.field(
        default_factory=NameResolveConfig
    )


@dataclasses.dataclass
class WandBConfig:
    """Reference cli_args.py:837 (subset; offline by default on TPU pods)."""

    mode: str = "disabled"
    entity: Optional[str] = None
    project: Optional[str] = None
    name: Optional[str] = None


@dataclasses.dataclass
class TensorBoardConfig:
    """Reference cli_args.py:863."""

    path: Optional[str] = None


@dataclasses.dataclass
class AutomaticEvaluatorConfig:
    """Reference cli_args.py:791 (AutomaticEvaluator)."""

    data_names: str = "aime24"
    max_gen_tokens: int = 32768
    max_concurrent_jobs: int = 1
    eval_job_image: Optional[str] = None
    initial_checkpoint_path: Optional[str] = None
    prompt_type: str = "math-cot"
    # pass@k sampling evaluation (apps/eval_ckpt.py, docs/rewards.md):
    # k>1 draws k temperature-sampled generations per prompt and the
    # evaluator publishes pass@1/pass@k/pass^k per task kind to
    # tensorboard for every saved checkpoint; k=1 keeps the legacy
    # greedy single-sample accuracy.
    eval_k: int = 1
    temperature: float = 0.6


# --------------------------------------------------------------------------
# experiment root
# --------------------------------------------------------------------------


@dataclasses.dataclass
class BaseExperimentConfig:
    """Reference cli_args.py:944 (BaseExperimentConfig).

    ``mode`` on TPU: "local" spawns every worker on this host (tests and
    single-host runs); "ray"/"slurm" are reserved words kept for CLI parity
    and raise until a cluster scheduler lands.
    """

    experiment_name: str = "areal-tpu"
    trial_name: str = ""
    mode: str = "local"
    backend: str = "tpu"  # accepted for parity with `--backend=tpu`
    debug: bool = True
    partition: str = "dev"
    schedule_strategy: str = "empty_first"
    recover_mode: str = "disabled"  # disabled | auto | resume | fault
    recover_retries: int = 1
    ignore_worker_error: bool = False
    allocation_mode: str = ""
    n_nodes: int = 1
    n_gpus_per_node: int = 8
    seed: int = 1
    cluster: ClusterSpecConfig = dataclasses.field(
        default_factory=ClusterSpecConfig
    )
    exp_ctrl: ExperimentSaveEvalControl = dataclasses.field(
        default_factory=ExperimentSaveEvalControl
    )
    wandb: WandBConfig = dataclasses.field(default_factory=WandBConfig)
    tensorboard: TensorBoardConfig = dataclasses.field(
        default_factory=TensorBoardConfig
    )
    auto_eval: bool = False
    auto_eval_config: AutomaticEvaluatorConfig = dataclasses.field(
        default_factory=AutomaticEvaluatorConfig
    )
    # Trainer→generation-fleet weight transport (docs/weight_sync.md):
    # `weight_sync.transport=disk` falls back to the checkpoint round-trip.
    weight_sync: WeightSyncConfig = dataclasses.field(
        default_factory=WeightSyncConfig
    )
    # Unified telemetry layer (docs/observability.md): off by default —
    # `telemetry.enabled=true` turns on cross-worker metric aggregation,
    # rollout trace spans, Prometheus /metrics, and profiler triggers.
    telemetry: TelemetryConfig = dataclasses.field(
        default_factory=TelemetryConfig
    )
    # Goodput ledger (docs/observability.md §Goodput): off by default —
    # `goodput.enabled=true` (with telemetry on) turns on per-worker
    # compute/comm/data_wait/idle time-in-state counters, live
    # achieved-TFLOP/s + MFU gauges on the trainer and generation
    # servers, and fleet-goodput stitching on the merged scrape.
    goodput: GoodputConfig = dataclasses.field(
        default_factory=GoodputConfig
    )
    # Training-health sentinel (docs/observability.md §Alerting): off by
    # default — `sentinel.enabled=true` (with telemetry on) arms the
    # master-hosted rule engine: streaming anomaly detection over fleet
    # telemetry + per-step training dynamics, alerts.jsonl +
    # areal_alerts_total on the merged scrape, automatic evidence capture
    # (flight dumps, pinned traces, optional profiler), autoscale-inhibit
    # on critical alerts, and opt-in master pause.
    sentinel: SentinelConfig = dataclasses.field(
        default_factory=SentinelConfig
    )
    # Compile & HBM observatory (docs/observability.md §Compile & memory):
    # off by default — `compile_watch.enabled=true` (with telemetry on)
    # wraps the fleet's jit entry points in compile-event tracing with
    # recompile-storm detection, samples per-device HBM gauges with
    # high-water marks around the big allocators, and arms the
    # recompile_storm / hbm_pressure / compile_stall sentinel rules.
    compile_watch: CompileWatchConfig = dataclasses.field(
        default_factory=CompileWatchConfig
    )
    # Generation-fleet serving engine (docs/serving.md): off by default —
    # `serving.enabled=true` turns on request-class admission control,
    # cross-request prefix-reuse KV, bounded compile-shape bucketing, and
    # per-class latency SLO histograms on the generation servers.
    serving: ServingConfig = dataclasses.field(default_factory=ServingConfig)
    # Launcher-level supervision + liveness leases (docs/fault_tolerance.md):
    # per-worker respawn with backoff + crash-loop circuit breaker for the
    # stateless domain, graceful SIGTERM drain, keepalive heartbeats.
    fault_tolerance: FaultToleranceConfig = dataclasses.field(
        default_factory=FaultToleranceConfig
    )
    # Elastic generation-fleet autoscaling (docs/fault_tolerance.md
    # §Autoscaling): off by default — `autoscale.enabled=true` turns on
    # the gserver manager's scaling loop (telemetry-driven target size,
    # cordon-and-drain scale-down, straggler defense, overload
    # backpressure) and the launcher-side spawn executor.
    autoscale: AutoscaleConfig = dataclasses.field(
        default_factory=AutoscaleConfig
    )
    # Durable trajectory spool (docs/fault_tolerance.md §Data durability):
    # off by default — `durability.enabled=true` turns on at-least-once
    # rollout→trainer delivery: per-worker fsynced spool written before
    # the prompt is marked consumed, trainer acks on optimizer-step
    # commit (or durable drop), crash-replay with idempotent ingest.
    # Disabled = today's fire-and-forget path, bit-identical wire bytes.
    durability: DurabilityConfig = dataclasses.field(
        default_factory=DurabilityConfig
    )
    # Sandboxed reward service (docs/rewards.md): off by default —
    # `reward_service.enabled=true` spawns the reward-worker fleet and
    # switches rollout/trainer reward grading to HTTP fanout with retry
    # and local-fallback degradation; disabled = exact legacy local
    # grading, bit-identical outputs.
    reward_service: RewardServiceConfig = dataclasses.field(
        default_factory=RewardServiceConfig
    )
    torch_cache_mysophobia: bool = False  # parity no-op (no torch allocator)
    cache_clear_freq: Optional[int] = 10
    # Test-only: use the deterministic mock tokenizer instead of HF.
    mock_tokenizer: bool = False
    # Multi-host trainer: one SPMD process per host via jax.distributed
    # (reference global_comm.py:48). >1 makes the launcher spawn that many
    # trainer processes; with trainer_dist_devices_per_proc they run on the
    # CPU platform with that many virtual devices each (multi-process CPU
    # testing, SURVEY §4).
    trainer_dist_procs: int = 1
    trainer_dist_devices_per_proc: Optional[int] = None

    def resolve_trial_name(self) -> str:
        if not self.trial_name:
            import datetime

            self.trial_name = (
                "run" + datetime.datetime.now().strftime("%Y%m%d-%H%M%S")
            )
        return self.trial_name


# --------------------------------------------------------------------------
# YAML + dotted-override machinery (the hydra replacement)
# --------------------------------------------------------------------------


def _field_map(obj) -> Dict[str, dataclasses.Field]:
    return {f.name: f for f in dataclasses.fields(obj)}


_HINT_CACHE: Dict[type, Dict[str, Any]] = {}


def _field_type(obj, name: str):
    """Resolved (non-string) annotation for a field — modules using
    ``from __future__ import annotations`` store them as strings."""
    cls = type(obj)
    if cls not in _HINT_CACHE:
        try:
            _HINT_CACHE[cls] = typing.get_type_hints(cls)
        except Exception:  # unresolvable forward refs: fall back per-field
            _HINT_CACHE[cls] = {}
    return _HINT_CACHE[cls].get(name, _field_map(obj)[name].type)


def _strip_optional(tp):
    origin = typing.get_origin(tp)
    if origin is typing.Union:
        args = [a for a in typing.get_args(tp) if a is not type(None)]
        if len(args) == 1:
            return args[0]
    return tp


def _coerce(value: str, tp) -> Any:
    """Parse a CLI string into the annotated field type."""
    tp = _strip_optional(tp)
    if value.lower() in ("null", "none"):
        return None
    if tp is bool or tp == "bool":
        if value.lower() in ("1", "true", "yes", "on"):
            return True
        if value.lower() in ("0", "false", "no", "off"):
            return False
        raise ValueError(f"cannot parse bool from {value!r}")
    if tp is int or tp == "int":
        return int(value)
    if tp is float or tp == "float":
        return float(value)
    if tp is str or tp == "str":
        return value
    origin = typing.get_origin(tp)
    if origin in (list, List):
        (etp,) = typing.get_args(tp) or (str,)
        if not value:
            return []
        return [_coerce(v.strip(), etp) for v in value.split(",")]
    if origin in (dict, Dict):
        import json

        return json.loads(value)
    if tp is Any:
        import json

        try:
            return json.loads(value)
        except (ValueError, TypeError):
            return value
    raise ValueError(f"don't know how to parse {value!r} as {tp}")


class ConfigError(ValueError):
    pass


def _safe_set(obj, key: str, val):
    """setattr that tolerates frozen dataclasses; returns the (possibly
    new) object holding the assignment."""
    try:
        setattr(obj, key, val)
        return obj
    except dataclasses.FrozenInstanceError:
        return dataclasses.replace(obj, **{key: val})


def _assign(obj, parts: List[str], value: str, path: str):
    fm = _field_map(obj)
    key = parts[0]
    if key not in fm:
        raise ConfigError(_unknown_key_msg(obj, key, path))
    if len(parts) == 1:
        return _safe_set(obj, key, _coerce(value, _field_type(obj, key)))
    child = getattr(obj, key)
    if dataclasses.is_dataclass(child):
        return _safe_set(obj, key, _assign(child, parts[1:], value, path))
    if isinstance(child, dict):
        # dict leaf: remaining path becomes a (typed-by-json) dict key
        child[".".join(parts[1:])] = _coerce(value, Any)
        return obj
    raise ConfigError(f"'{key}' is a leaf; cannot descend into '{path}'")


def _set_dotted(obj, path: str, value: str) -> None:
    if _assign(obj, path.split("."), value, path) is not obj:
        raise ConfigError(
            f"top-level config {type(obj).__name__} must not be frozen"
        )


def _unknown_key_msg(obj, key: str, path: str) -> str:
    names = [f.name for f in dataclasses.fields(obj)]
    close = difflib.get_close_matches(key, names, n=3)
    hint = f" (did you mean: {', '.join(close)}?)" if close else ""
    return (
        f"unknown config key '{path}' on {type(obj).__name__}{hint}; "
        f"valid keys: {', '.join(sorted(names))}"
    )


def apply_overrides(cfg, overrides: List[str]):
    """Apply ``a.b.c=value`` assignments in order. Mutates and returns cfg."""
    for ov in overrides:
        if "=" not in ov:
            raise ConfigError(f"override {ov!r} is not of the form key=value")
        key, _, value = ov.partition("=")
        _set_dotted(cfg, key.strip(), value.strip())
    return cfg


# Launch modes this framework implements. "ray" is descoped (VERDICT #10):
# Ray is not in the TPU image, and the scheduler surface is SlurmClient +
# LocalLauncher — see docs/operations.md §Launching.
VALID_MODES = ("local", "slurm")

# MFC names the PPO experiment graph can schedule (ppo_math_exp.py);
# per-MFC allocation entries must name one of these.
KNOWN_MFCS = (
    "actor_train", "actor_gen", "actor_inf",
    "critic_train", "critic_inf",
    "ref_inf", "rew_inf", "fused_rew_ref_inf",
)


def _actor_moe(cfg):
    """(MoEConfig, where it was read) of the actor model, or (None, None)
    for a dense one: ``actor.tiny.moe`` for a fabricated model, else the
    expert keys of the ``config.json`` beside ``actor.path`` through the
    family mapping a checkpoint takes (models/hf.config_from_hf)."""
    import json
    import os
    import types

    from areal_tpu.models.config import MoEConfig

    actor = getattr(cfg, "actor", None)
    tiny = getattr(actor, "tiny", None)
    if isinstance(tiny, dict) and tiny:
        moe = tiny.get("moe")
        return ((MoEConfig(**moe), "actor.tiny.moe")
                if isinstance(moe, dict) else (None, None))
    path = os.path.join(str(getattr(actor, "path", "") or ""), "config.json")
    if not os.path.isfile(path):
        return None, None
    from areal_tpu.models import hf

    with open(path) as f:
        hf_cfg = types.SimpleNamespace(**json.load(f))
    try:
        model_cfg = hf.config_from_hf(hf_cfg)
    except NotImplementedError:
        return None, None
    return model_cfg.moe, path


def validate_config(cfg) -> None:
    """Config-parse-time sanity checks, called right after overrides/YAML
    merge (training/_cli.py) and again by the launcher: a bad ``mode``
    must fail while the operator is still at the command line, not after
    workers have been spawned."""
    mode = getattr(cfg, "mode", "local")
    if mode == "ray":
        raise ConfigError(
            "mode='ray' is descoped: Ray is not in the TPU image and there "
            "is no Ray scheduler backend. Use mode=local (single host) or "
            "mode=slurm (cluster) — see docs/operations.md §Launching. A "
            "Ray backend would slot in at apps/launcher.py:run_experiment."
        )
    if mode not in VALID_MODES:
        raise ConfigError(
            f"mode={mode!r} is not supported: valid modes are "
            f"{', '.join(VALID_MODES)} (docs/operations.md §Launching)"
        )
    alloc_str = getattr(cfg, "allocation_mode", "") or ""
    if alloc_str:
        # Lazy import: parallel.mesh pulls in jax, which jax-free tool
        # entrypoints must not pay for unless an allocation is configured.
        from areal_tpu.parallel.mesh import AllocationMode

        try:
            alloc = AllocationMode.parse(alloc_str)
        except ValueError as e:
            raise ConfigError(
                f"invalid allocation_mode {alloc_str!r}: {e}"
            ) from None
        n_devices = (
            getattr(cfg, "n_nodes", 1) * getattr(cfg, "n_gpus_per_node", 8)
        )
        for mfc, spec in sorted(alloc.per_mfc.items()):
            if mfc not in KNOWN_MFCS:
                raise ConfigError(
                    f"allocation_mode names unknown MFC '{mfc}': known "
                    f"MFCs are {', '.join(KNOWN_MFCS)} "
                    f"(experiments/ppo_math_exp.py builds the graph)"
                )
            if spec.world_size > n_devices:
                raise ConfigError(
                    f"allocation_mode MFC '{mfc}': spec '{spec}' needs "
                    f"{spec.world_size} devices but the experiment has "
                    f"n_nodes×n_gpus_per_node = {n_devices}"
                )
        for label, spec in (("global", alloc.global_spec),
                            ("generation", alloc.gen_spec)):
            if spec is not None and spec.world_size > n_devices:
                raise ConfigError(
                    f"allocation_mode {label} spec '{spec}' needs "
                    f"{spec.world_size} devices but the experiment has "
                    f"n_nodes×n_gpus_per_node = {n_devices}"
                )
        # Generation-side specs never ring: the decode hot loop passes
        # allow_ring=False (models/transformer.py) so an sp axis there
        # would silently replicate work at server launch. Fail at parse
        # time with the fix instead.
        gen_specs = [("generation", alloc.gen_spec)]
        gen_specs += [(f"MFC '{m}'", s) for m, s in
                      sorted(alloc.per_mfc.items()) if m == "actor_gen"]
        for label, spec in gen_specs:
            if spec is not None and spec.sp > 1:
                raise ConfigError(
                    f"allocation_mode {label} spec '{spec}' sets sp="
                    f"{spec.sp}, but sequence (ring) parallelism only "
                    "applies to training: the decode hot loop never rings "
                    "(token-at-a-time attention has no sequence dim to "
                    "shard). Move the sp factor into dp or tp for the "
                    "generation fleet — e.g. sp2 -> d2 "
                    "(docs/parallelism.md §PP∘SP)."
                )
            if spec is not None and spec.ep > 1:
                raise ConfigError(
                    f"allocation_mode {label} spec '{spec}' sets ep="
                    f"{spec.ep}, but expert parallelism only applies to "
                    "training: the decode hot loop runs the replicated "
                    "one-shard dispatch (models/moe.py never exchanges "
                    "under a KV cache). Move the ep factor into dp or tp "
                    "for the generation fleet — e.g. e2 -> d2 "
                    "(docs/parallelism.md §Expert parallelism)."
                )
        # Expert-parallel train specs need a MoE model whose expert count
        # divides over the axis; anything else silently replicates or
        # crashes inside shard_map at step time, so fail at parse time.
        moe_cfg, moe_src = _actor_moe(cfg)
        train_specs = [("global", alloc.global_spec)]
        train_specs += [(f"MFC '{m}'", s) for m, s in
                        sorted(alloc.per_mfc.items()) if m != "actor_gen"]
        for label, spec in train_specs:
            if spec is None or spec.ep <= 1:
                continue
            if moe_cfg is None:
                raise ConfigError(
                    f"allocation_mode {label} spec '{spec}' sets ep="
                    f"{spec.ep} but the model is dense (actor.tiny.moe is "
                    "unset and no config.json beside actor.path names "
                    "experts): there are no experts to shard. Drop the ep "
                    "factor or use a MoE model "
                    "(docs/parallelism.md §Expert parallelism)."
                )
            if moe_cfg.num_experts % spec.ep != 0:
                raise ConfigError(
                    f"allocation_mode {label} spec '{spec}' sets ep="
                    f"{spec.ep}, which does not divide "
                    f"{moe_src}.num_experts={moe_cfg.num_experts}: every "
                    "ep shard must own the same number of experts "
                    "(docs/parallelism.md §Expert parallelism)."
                )
    moe_dict = getattr(getattr(cfg, "actor", None), "tiny", None)
    moe_dict = moe_dict.get("moe") if isinstance(moe_dict, dict) else None
    if isinstance(moe_dict, dict) and moe_dict.get(
            "capacity_factor", 2.0) is not None:
        cf = float(moe_dict.get("capacity_factor", 2.0))
        if cf <= 0:
            raise ConfigError(
                f"actor.tiny.moe.capacity_factor={cf} must be > 0: the "
                "expert buffer is ceil(top_k * tokens * capacity_factor "
                "/ num_experts) slots, and a non-positive factor drops "
                "every routed token (models/moe.py capacity)."
            )
    nr = getattr(getattr(cfg, "cluster", None), "name_resolve", None)
    if nr is not None and getattr(nr, "type", "nfs") == "etcd3":
        # Same contract as the mode=ray rejection above: the descoped
        # backend must fail while the operator is still at the command
        # line, not as a NotImplementedError after workers spawned.
        raise ConfigError(
            "cluster.name_resolve.type='etcd3' is descoped: no etcd3 "
            "repository is implemented and the etcd3 client package is "
            "not in the TPU image. Use type=nfs (shared filesystem, the "
            "default, works across hosts) or type=memory (single-process "
            "tests). An etcd3 backend would slot in at "
            "base/name_resolve.py:reconfigure."
        )
    asc = getattr(cfg, "autoscale", None)
    if asc is not None and getattr(asc, "enabled", False):
        if asc.min_servers < 1:
            raise ConfigError(
                f"autoscale.min_servers={asc.min_servers} must be >= 1 "
                f"(the fleet can never scale to zero routable servers)"
            )
        if asc.max_servers < asc.min_servers:
            raise ConfigError(
                f"autoscale.max_servers={asc.max_servers} < "
                f"min_servers={asc.min_servers}"
            )
        if asc.interval_secs <= 0:
            raise ConfigError(
                f"autoscale.interval_secs={asc.interval_secs} must be > 0"
            )
        if not 0.0 <= asc.down_utilization < asc.up_utilization:
            raise ConfigError(
                f"autoscale utilization thresholds must satisfy "
                f"0 <= down ({asc.down_utilization}) < up "
                f"({asc.up_utilization}) — equal or inverted thresholds "
                f"make the fleet flap every interval"
            )
        if asc.straggler_defense and asc.straggler_factor <= 1.0:
            raise ConfigError(
                f"autoscale.straggler_factor={asc.straggler_factor} must "
                f"be > 1 (a server is only a straggler when it is slower "
                f"than its peers)"
            )
    serving = getattr(cfg, "serving", None)
    if serving is not None and getattr(serving, "enabled", False):
        # Bad serving bucket lists raise ValueError inside every spawned
        # generation server's __init__; surface them while the operator
        # is still at the command line. policy_from_config is pure
        # bookkeeping (no jax), and experiment_policy_kwargs is the SAME
        # experiment->policy mapping the async experiment wiring feeds
        # into GenerationServerConfig — so this is the exact construction
        # the servers will run, by sharing code rather than replicating
        # the numbers.
        from areal_tpu.system.serving import (
            experiment_policy_kwargs,
            policy_from_config,
        )

        try:
            policy_from_config(serving, **experiment_policy_kwargs(cfg))
        except ValueError as e:
            raise ConfigError(f"invalid serving config: {e}") from None
        share = float(getattr(serving, "min_rollout_share", 0.0))
        if not 0.0 <= share <= 1.0:
            raise ConfigError(
                f"serving.min_rollout_share={share} must be in [0, 1] "
                f"(fraction of each batch reserved for rollout traffic)"
            )
    gp = getattr(cfg, "goodput", None)
    if gp is not None and getattr(gp, "enabled", False):
        tel = getattr(cfg, "telemetry", None)
        if tel is None or not getattr(tel, "enabled", False):
            raise ConfigError(
                "goodput.enabled=true requires telemetry.enabled=true: "
                "the ledger exports through the telemetry registry and "
                "the fleet stitch lives in the master's aggregator — "
                "without telemetry there is nowhere to export "
                "(docs/observability.md §Goodput)"
            )
        if getattr(gp, "export_interval_secs", 1.0) <= 0:
            raise ConfigError(
                f"goodput.export_interval_secs="
                f"{gp.export_interval_secs} must be > 0"
            )
        if getattr(gp, "peak_flops_override", 0.0) < 0:
            raise ConfigError(
                f"goodput.peak_flops_override={gp.peak_flops_override} "
                f"must be >= 0 (0 = auto-detect from the device kind)"
            )
    cw = getattr(cfg, "compile_watch", None)
    if cw is not None and getattr(cw, "enabled", False):
        tel = getattr(cfg, "telemetry", None)
        if tel is None or not getattr(tel, "enabled", False):
            raise ConfigError(
                "compile_watch.enabled=true requires telemetry.enabled=true: "
                "compile events and HBM gauges export through the telemetry "
                "registry and roll up in the master's aggregator — without "
                "telemetry there is nowhere to record them "
                "(docs/observability.md §Compile & memory)"
            )
        if getattr(cw, "storm_warmup_calls", 16) < 1:
            raise ConfigError(
                f"compile_watch.storm_warmup_calls="
                f"{cw.storm_warmup_calls} must be >= 1 (a zero warmup "
                f"would flag every cold-start compile as a storm)"
            )
        if getattr(cw, "mem_sample_interval_secs", 10.0) < 0:
            raise ConfigError(
                f"compile_watch.mem_sample_interval_secs="
                f"{cw.mem_sample_interval_secs} must be >= 0"
            )
        serving = getattr(cfg, "serving", None)
        if serving is not None and getattr(serving, "enabled", False):
            # Unify compiled-shape accounting across serving and training:
            # the serving ShapeBucketPolicy caps its admitted grid set at
            # serving.max_compiled_shapes, but the trainer's microbatch
            # fill sweep contributes its own [R, L] shapes to the SAME
            # compile/distinct_shapes family. Cross-check the worst case
            # at parse time with the sweep's own bound (shared code, not
            # replicated numbers) so an operator who tightened
            # max_compiled_shapes learns which OTHER field defeats it.
            from areal_tpu.backend.microbatch import (
                worst_case_row_candidates,
            )

            max_shapes = int(getattr(serving, "max_compiled_shapes", 0))
            trainer_cands = worst_case_row_candidates()
            if 0 < max_shapes < trainer_cands:
                raise ConfigError(
                    f"serving.max_compiled_shapes={max_shapes} is below "
                    f"the trainer fill sweep's worst-case candidate count "
                    f"({trainer_cands}, from backend/microbatch.py "
                    f"worst_case_row_candidates): the trainer alone could "
                    f"exceed the shape budget the serving policy enforces. "
                    f"Raise serving.max_compiled_shapes to at least "
                    f"{trainer_cands}, or coarsen the trainer's "
                    f"fill_bucket (actor.backend fill_bucket) to shrink "
                    f"the sweep."
                )
    sn = getattr(cfg, "sentinel", None)
    if sn is not None and getattr(sn, "enabled", False):
        tel = getattr(cfg, "telemetry", None)
        if tel is None or not getattr(tel, "enabled", False):
            raise ConfigError(
                "sentinel.enabled=true requires telemetry.enabled=true: "
                "the sentinel lives inside the master's "
                "TelemetryAggregator and evaluates the merged fleet "
                "snapshots — without telemetry there is nothing to watch "
                "(docs/observability.md §Alerting)"
            )
        if getattr(sn, "eval_interval_secs", 1.0) <= 0:
            raise ConfigError(
                f"sentinel.eval_interval_secs="
                f"{sn.eval_interval_secs} must be > 0"
            )
        # Front-run the exact rule-pack construction the master will do:
        # unknown metric names, non-positive for:/cooldown durations, and
        # duplicate rule ids must fail at the command line, naming the
        # offending rule — not inside a spawned master worker.
        from areal_tpu.system.sentinel import rules_from_config

        try:
            rules_from_config(
                sn,
                durability_enabled=getattr(
                    getattr(cfg, "durability", None), "enabled", False
                ),
                compile_watch_enabled=getattr(
                    getattr(cfg, "compile_watch", None), "enabled", False
                ),
            )
        except ValueError as e:
            raise ConfigError(f"invalid sentinel rule pack: {e}") from None
    dur = getattr(cfg, "durability", None)
    if dur is not None and getattr(dur, "enabled", False):
        if dur.spool_segment_bytes <= 0:
            raise ConfigError(
                f"durability.spool_segment_bytes="
                f"{dur.spool_segment_bytes} must be > 0"
            )
        if dur.spool_max_bytes < dur.spool_segment_bytes:
            raise ConfigError(
                f"durability.spool_max_bytes={dur.spool_max_bytes} < "
                f"spool_segment_bytes={dur.spool_segment_bytes}: the "
                f"spool could never roll a full segment"
            )
        if dur.resend_timeout_secs <= 0:
            raise ConfigError(
                f"durability.resend_timeout_secs="
                f"{dur.resend_timeout_secs} must be > 0 (it is the only "
                f"recovery path for a lost ack)"
            )
        if dur.push_block_secs <= 0:
            raise ConfigError(
                f"durability.push_block_secs={dur.push_block_secs} must "
                f"be > 0 (a zero budget fails every send at the HWM)"
            )
    rs = getattr(cfg, "reward_service", None)
    if rs is not None and getattr(rs, "enabled", False):
        if rs.n_workers < 1:
            raise ConfigError(
                f"reward_service.n_workers={rs.n_workers} must be >= 1 "
                f"(an enabled fleet needs at least one sandbox worker)"
            )
        for knob in ("max_inflight", "pool_size", "max_concurrency"):
            if getattr(rs, knob) < 1:
                raise ConfigError(
                    f"reward_service.{knob}={getattr(rs, knob)} must be >= 1"
                )
        for knob in ("grade_timeout_secs", "request_timeout_secs"):
            if getattr(rs, knob) <= 0:
                raise ConfigError(
                    f"reward_service.{knob}={getattr(rs, knob)} must be > 0 "
                    f"(a reward grade must have a finite wall budget)"
                )
        if not rs.languages:
            raise ConfigError(
                "reward_service.languages is empty: an enabled fleet that "
                "grades no language returns 0.0 for every code task — "
                "list at least one of rewards/code_verify.py GRADERS "
                "(e.g. reward_service.languages=python)"
            )
        from areal_tpu.rewards.code_verify import GRADERS

        unknown = [l for l in rs.languages if l not in GRADERS]
        if unknown:
            raise ConfigError(
                f"reward_service.languages={rs.languages}: no grader is "
                f"registered for {unknown} (available: "
                f"{', '.join(sorted(GRADERS))}; new languages register in "
                f"rewards/code_verify.py GRADERS)"
            )


def merge_dict(cfg, d: Dict[str, Any], _path: str = ""):
    """Merge a (nested) plain dict — e.g. parsed YAML — onto a dataclass."""
    fm = _field_map(cfg)
    for k, v in d.items():
        path = f"{_path}.{k}" if _path else k
        if k not in fm:
            raise ConfigError(_unknown_key_msg(cfg, k, path))
        cur = getattr(cfg, k)
        if dataclasses.is_dataclass(cur) and isinstance(v, dict):
            cfg = _safe_set(cfg, k, merge_dict(cur, v, path))
        elif isinstance(v, str) and not isinstance(cur, str) \
                and not dataclasses.is_dataclass(cur):
            cfg = _safe_set(cfg, k, _coerce(v, _field_type(cfg, k)))
        else:
            cfg = _safe_set(cfg, k, v)
    return cfg


def load_yaml(cfg, path: str):
    import yaml

    with open(path) as f:
        d = yaml.safe_load(f) or {}
    return merge_dict(cfg, d)


def to_yaml_dict(cfg) -> Dict[str, Any]:
    """dataclass → plain dict safe for yaml.dump (reference dumps asdict)."""
    out = dataclasses.asdict(cfg)

    def clean(x):
        if isinstance(x, dict):
            return {k: clean(v) for k, v in x.items()}
        if isinstance(x, (list, tuple)):
            return [clean(v) for v in x]
        if isinstance(x, (str, int, float, bool)) or x is None:
            return x
        return repr(x)

    return clean(out)


def save_yaml(cfg, path: str) -> None:
    import os

    import yaml

    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        yaml.dump(to_yaml_dict(cfg), f, default_flow_style=False,
                  sort_keys=False)


def print_config_help(cfg, _indent: int = 0) -> None:
    """Recursive ``--help`` printer (reference cli_args.py:1421)."""
    pad = "  " * _indent
    for f in dataclasses.fields(cfg):
        v = getattr(cfg, f.name)
        if dataclasses.is_dataclass(v):
            print(f"{pad}{f.name}:  ({type(v).__name__})")
            print_config_help(v, _indent + 1)
        else:
            print(f"{pad}{f.name} = {v!r}")


def get_log_path(cfg: BaseExperimentConfig) -> str:
    """<fileroot>/logs/<experiment>/<trial> (reference constants.get_log_path)."""
    import os

    return os.path.join(
        cfg.cluster.fileroot, "logs", cfg.experiment_name, cfg.trial_name
    )
