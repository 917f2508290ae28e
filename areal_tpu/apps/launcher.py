"""Local experiment launcher.

Parity target: ``realhf/apps/main.py:80`` (main_start) +
``realhf/scheduler/local/client.py:71`` (LocalSchedulerClient) +
``training/utils.py:123`` (_run_experiment): spawn one process per worker,
run the master loop in the launcher process, monitor children, tear down.

TPU shape: the *trainer* is ONE process owning the whole trainer mesh
(single-controller SPMD — the reference's per-GPU model workers collapse);
the async generation fleet (servers + manager) is a second process group on
its own slice; rollout workers are CPU asyncio processes. ``mode="local"``
covers single-host; multi-host adds ``jax.distributed`` (launcher-side
support lands with the multi-host runtime).
"""

from __future__ import annotations

import multiprocessing as mp
import os
import threading
import time
from typing import Any, Dict, List, Optional

from areal_tpu.base import logging
from areal_tpu.base.compile_watch import enable_compilation_cache

logger = logging.getLogger("apps.launcher")


def cpu_platform_requested() -> bool:
    """True when the standard ``JAX_PLATFORMS`` puts the CPU first — the
    ONE switch that runs workers on the CPU (tests export it; children
    inherit it). Nothing else, and no experiment option, selects a
    platform."""
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    return first.strip().lower() == "cpu"


def _pin_cpu() -> None:
    """Keep THIS process off the accelerator: a chip belongs to one
    process, and one stray array in a rollout/reward/master process would
    take it from the trainer. The env var covers a jax not imported yet
    (and grandchildren); the config update covers a jax that argument
    unpickling already imported (it read the variable at import time)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


# ---------------------------------------------------------------------------
# child-process entries (must be module-level for mp spawn pickling)
# ---------------------------------------------------------------------------


def derive_chip_assignment(
    alloc_mode: str, n_chips: int
) -> Dict[str, List[int]]:
    """Partition this host's TPU chips between the trainer and the
    generation fleet from the decoupled allocation mode (parity:
    LocalSchedulerClient's CUDA_VISIBLE_DEVICES bookkeeping, reference
    scheduler/local/client.py:87-98).

    Returns {"trainer": [...], "gen": [...]} chip-id lists. Raises with an
    actionable message when the layout cannot fit — two JAX processes must
    never initialize the same chip.
    """
    from areal_tpu.parallel.mesh import AllocationMode

    am = AllocationMode.parse(alloc_mode) if alloc_mode else None
    if am is None or not am.decoupled:
        return {"trainer": list(range(n_chips)), "gen": []}
    need_t = am.global_spec.world_size
    need_g = am.gen_spec.world_size
    if need_t + need_g > n_chips:
        raise RuntimeError(
            f"allocation mode '{alloc_mode}' needs "
            f"{need_t} trainer + {need_g} generation chips but this host has "
            f"{n_chips}; shrink the specs (e.g. gen.d1+d1 needs 2 chips) or "
            "run sync mode (colocated) where trainer and generation share "
            "the same chips"
        )
    return {
        "trainer": list(range(need_t)),
        "gen": list(range(need_t, need_t + need_g)),
    }


def _apply_chip_env(chips: Optional[List[int]]) -> None:
    """Restrict THIS process to the given TPU chips (must run before jax
    initializes). PJRT reads TPU_VISIBLE_CHIPS; the process-bounds vars tell
    libtpu this is a single-process slice of the host."""
    if chips is None:
        return
    os.environ["TPU_VISIBLE_CHIPS"] = ",".join(str(c) for c in chips)
    os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")
    os.environ.setdefault(
        "TPU_CHIPS_PER_PROCESS_BOUNDS", f"{len(chips)},1,1"
    )


def _child_init(exp_cfg, owns_device: bool,
                chips: Optional[List[int]] = None) -> None:
    """Per-process start-up. ``owns_device``: the trainer and the
    generation fleet run on the platform ``JAX_PLATFORMS`` selects (the
    TPU when it is unset) and refuse to start on anything else under
    ``backend=tpu``; every other worker is pinned to the CPU."""
    on_accelerator = owns_device and not cpu_platform_requested()
    if on_accelerator:
        _apply_chip_env(chips)
    else:
        _pin_cpu()
    import jax

    enable_compilation_cache()
    if (on_accelerator and getattr(exp_cfg, "backend", "tpu") == "tpu"
            and jax.default_backend() != "tpu"):
        raise RuntimeError(
            "backend=tpu but this worker got platform "
            f"{jax.default_backend()!r} (devices: {jax.devices()}); no "
            "model runs on a fallback device — export JAX_PLATFORMS=cpu "
            "to run on the CPU on purpose"
        )
    from areal_tpu.experiments import common as C

    C.setup_name_resolve(exp_cfg)
    # Registration side effects for every factory the configs reference.
    import areal_tpu.agents.math_single_step  # noqa: F401
    import areal_tpu.algorithms  # noqa: F401 — registers all interfaces
    import areal_tpu.backend.jax_train  # noqa: F401
    import areal_tpu.datasets.jsonl  # noqa: F401


def _resolve_tokenizer(exp_cfg):
    from areal_tpu.experiments import common as C

    path = getattr(exp_cfg, "actor", None)
    model_path = path.path if path is not None else getattr(
        exp_cfg, "model", None
    ).path
    return C.make_tokenizer(exp_cfg, model_path)


def trainer_entry(exp_cfg, trainer_cfg) -> None:
    # Multi-process CPU testing: the virtual-device flag must land in the
    # environment BEFORE jax initializes in this (spawned, fresh) process.
    if trainer_cfg.dist_world > 1 and trainer_cfg.dist_local_devices:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{trainer_cfg.dist_local_devices}"
            ).strip()
    _child_init(exp_cfg, True, getattr(trainer_cfg, "chips", None))
    from areal_tpu.system.trainer_worker import TrainerWorker

    trainer_cfg.tokenizer = _resolve_tokenizer(exp_cfg)
    TrainerWorker(trainer_cfg).run()


def _build_gen_model(init: Dict):
    """Model config + params for a generation server, from the actor's
    init dict (tiny test config or an HF checkpoint dir)."""
    import jax

    if "tiny" in init:
        from areal_tpu.models import transformer
        from areal_tpu.models.config import tiny_config

        kw = dict(init["tiny"])
        seed = kw.pop("seed", 0)
        cfg = tiny_config(**kw)
        return cfg, transformer.init_params(cfg, jax.random.PRNGKey(seed))
    from areal_tpu.models import hf as hfmod

    cfg, params, _ = hfmod.load_hf_model(init["hf_dir"])
    return cfg, params


def gen_replica_meshes(exp_cfg, n_replicas: int, devices) -> List[Any]:
    """One mesh per generation replica over ITS OWN devices: replica ``i``
    takes devices ``[i*per, (i+1)*per)`` of this process, ``per`` being the
    non-data part (pp·sp·tp) of the decoupled generation spec — one device
    under ``gen.d2``. Without this every server lands on ``devices[0]`` and
    the other generation chips hold nothing. With fewer devices than the
    replicas need (one-device CPU runs) the servers share the default
    device, un-meshed (``None``)."""
    import dataclasses

    from areal_tpu.experiments.common import resolve_allocation
    from areal_tpu.parallel import mesh as pmesh

    spec = resolve_allocation(exp_cfg).gen_spec or pmesh.ParallelSpec()
    one = dataclasses.replace(spec, dp=1, fsdp=1, ep=1)
    per = one.world_size
    if len(devices) < n_replicas * per:
        if devices[0].platform != "cpu":
            raise RuntimeError(
                f"{n_replicas} generation replicas of {per} device(s) need "
                f"{n_replicas * per} devices; this process has {devices}"
            )
        return [None] * n_replicas
    return [
        pmesh.make_mesh(one, devices=devices[i * per:(i + 1) * per])
        for i in range(n_replicas)
    ]


def gen_fleet_entry(exp_cfg, server_cfgs, manager_cfg,
                    chips: Optional[List[int]] = None) -> None:
    """All generation servers + the gserver manager in one asyncio loop."""
    _child_init(exp_cfg, True, chips)
    import asyncio

    import jax

    from areal_tpu.base import monitor
    from areal_tpu.experiments.common import model_init_dict
    from areal_tpu.system.generation_server import GenerationServer
    from areal_tpu.system.gserver_manager import GserverManager

    init = model_init_dict(exp_cfg.actor)

    async def main():
        cfg, params = _build_gen_model(init)
        tok = _resolve_tokenizer(exp_cfg)
        eos = getattr(tok, "eos_token_id", None)
        meshes = gen_replica_meshes(exp_cfg, len(server_cfgs),
                                    jax.local_devices())
        servers = []
        for sc, mesh in zip(server_cfgs, meshes):
            if eos is not None:
                sc.eos_token_id = int(eos)
            srv = GenerationServer(sc, cfg, params, mesh=mesh)
            await srv.start()
            servers.append(srv)
        monitor.log_device_report(logger, "gen_fleet",
                                  n_servers=len(servers))
        mgr = GserverManager(manager_cfg)
        await mgr.start()
        while True:  # runs until the launcher terminates us
            await asyncio.sleep(3600)

    asyncio.run(main())


def gen_server_entry(exp_cfg, server_cfg,
                     chips: Optional[List[int]] = None) -> None:
    """One supervised generation server — the autoscaler's scale-up unit
    (docs/fault_tolerance.md §Autoscaling).

    Spawned by the launcher's AutoscaleExecutor to satisfy the gserver
    manager's published plan. The server joins the fleet through the
    normal path: registers under names.gen_servers, passes the manager's
    health gate, and is reconciled to the current weight version over the
    streamed transport (no checkpoint round-trip). It also serves a
    WorkerControl endpoint (``genserver_<server_id>``) so a drained
    cordon ends in a commanded clean exit the supervisor expects."""
    _child_init(exp_cfg, True, chips)
    import asyncio

    from areal_tpu.base import name_resolve, names
    from areal_tpu.experiments.common import model_init_dict
    from areal_tpu.system.generation_server import GenerationServer
    from areal_tpu.system.worker_base import WorkerControl

    init = model_init_dict(exp_cfg.actor)

    async def main():
        cfg, params = _build_gen_model(init)
        tok = _resolve_tokenizer(exp_cfg)
        eos = getattr(tok, "eos_token_id", None)
        if eos is not None:
            server_cfg.eos_token_id = int(eos)
        srv = GenerationServer(server_cfg, cfg, params)
        await srv.start()
        ctrl = WorkerControl(
            exp_cfg.experiment_name, exp_cfg.trial_name,
            f"genserver_{server_cfg.server_id}",
        )
        try:
            while True:
                await asyncio.to_thread(
                    ctrl.step,
                    lambda: {
                        "server_id": server_cfg.server_id,
                        "version": srv.version,
                        "inflight": srv._inflight,
                    },
                    200,
                )
                if ctrl.should_exit:
                    break
        finally:
            await srv.stop()
            # Withdraw discovery NOW: the manager's next sweep forgets
            # this url instead of probing a corpse until the lease TTL.
            try:
                name_resolve.delete(names.gen_servers(
                    exp_cfg.experiment_name, exp_cfg.trial_name,
                    server_cfg.server_id,
                ))
            except Exception:  # noqa: BLE001 — already gone
                pass
            ctrl.close()

    asyncio.run(main())


def reward_worker_entry(exp_cfg, rw_cfg) -> None:
    """One sandbox reward worker (the sixth worker kind,
    system/reward_worker.py). Deliberately NOT _child_init: a reward
    worker is jax-free and must never initialize an accelerator —
    untrusted code grades on spare CPU, not on the chips that train."""
    os.environ["JAX_PLATFORMS"] = "cpu"  # belt: even if imported
    from areal_tpu.experiments import common as C

    C.setup_name_resolve(exp_cfg)
    from areal_tpu.system.reward_worker import RewardWorker

    RewardWorker(rw_cfg).run()


def rollout_entry(exp_cfg, rollout_cfg) -> None:
    _child_init(exp_cfg, False)
    import asyncio

    from areal_tpu.system.rollout_worker import RolloutWorker

    rollout_cfg.tokenizer = _resolve_tokenizer(exp_cfg)
    eos = getattr(rollout_cfg.tokenizer, "eos_token_id", None)
    if eos is not None:
        rollout_cfg.eos_token_id = int(eos)
    asyncio.run(RolloutWorker(rollout_cfg).run_async())


# ---------------------------------------------------------------------------
# launcher
# ---------------------------------------------------------------------------


class LocalLauncher:
    """Spawn workers, run the master inline, supervise, tear down.

    Child death is classified by failure domain (system/supervisor.py):
    rollout workers and the gen-fleet process are respawned in place with
    backoff behind a crash-loop circuit breaker; trainer death escalates
    to ``run_experiment``'s whole-experiment recovery loop. SIGTERM
    triggers a graceful drain (pause → out-of-band recover checkpoint →
    orderly exits) instead of raw terminate().
    """

    def __init__(self, exp_cfg):
        from areal_tpu.api.train_config import FaultToleranceConfig

        self.exp_cfg = exp_cfg
        # The platform follows the standard JAX_PLATFORMS alone (children
        # inherit it) — never an experiment option such as the tokenizer.
        self.force_cpu = cpu_platform_requested()
        self.ft = (getattr(exp_cfg, "fault_tolerance", None)
                   or FaultToleranceConfig())
        self.supervisor = None  # built in run() once the trial resolves
        self._scaler = None  # AutoscaleExecutor, when autoscale.enabled
        self._drain_evt = threading.Event()
        self._drain_thread: Optional[threading.Thread] = None
        self._drain_deadline: Optional[float] = None
        self._drain_failed = False

    def request_drain(self) -> None:
        """Ask for a graceful drain (same path as SIGTERM): pause the
        rollout fleet, dump a recover checkpoint out-of-band, exit the
        workers in order. Safe from any thread / signal handler."""
        self._drain_evt.set()

    @property
    def procs(self) -> List[mp.process.BaseProcess]:
        return self.supervisor.procs() if self.supervisor else []

    def _spawn(self, target, *args, name: str, kind: str,
               required: bool = True, expendable: bool = False) -> None:
        from areal_tpu.system.supervisor import WorkerSpec

        self.supervisor.spawn(WorkerSpec(
            name=name, kind=kind, target=target, args=args,
            required=required, expendable=expendable,
        ))

    @staticmethod
    def _count_chips() -> int:
        """TPU chips on this host, probed in a subprocess that has exited
        before any worker is spawned: the launcher process itself never
        initializes the TPU runtime (children own the chips). A probe
        that cannot count chips is an error, not a guess."""
        import subprocess
        import sys

        probe = ("import jax; d = jax.devices(); "
                 "print(d[0].platform, len(d))")
        out = subprocess.run(
            [sys.executable, "-c", probe],
            capture_output=True, text=True, timeout=300,
        )
        lines = out.stdout.strip().splitlines()
        fields = lines[-1].split() if out.returncode == 0 and lines else []
        if len(fields) != 2 or fields[0] != "tpu":
            raise RuntimeError(
                f"chip probe failed (exit {out.returncode}); a decoupled "
                "layout needs the TPU chips of this host counted before "
                f"they are partitioned\nstdout: {out.stdout[-500:]}\n"
                f"stderr: {out.stderr[-2000:]}"
            )
        return int(fields[1])

    def _check_children(self) -> None:
        """One supervision sweep. Stateless-domain deaths respawn in
        place; stateful deaths and crash loops raise
        SupervisorEscalation, which run_experiment's recover loop turns
        into a whole-experiment relaunch."""
        self.supervisor.check()

    def _install_sigterm(self):
        """Preemption hook: SIGTERM drives the graceful drain instead of
        killing children outright. Returns a restore callable; no-op off
        the main thread (in-process test launches)."""
        import signal

        try:
            prev = signal.getsignal(signal.SIGTERM)

            def on_term(signum, frame):
                logger.warning("SIGTERM: starting graceful drain")
                self._drain_evt.set()

            signal.signal(signal.SIGTERM, on_term)
            return lambda: signal.signal(signal.SIGTERM, prev)
        except ValueError:  # not the main thread
            return lambda: None

    def run(self) -> Dict[str, Any]:
        from areal_tpu.experiments import common as C
        from areal_tpu.system.master_worker import MasterWorker
        from areal_tpu.system.supervisor import RestartPolicy, Supervisor

        exp = self.exp_cfg
        exp.resolve_trial_name()
        C.setup_name_resolve(exp)
        # The master runs in this process, which spawns the workers that
        # own the chips: whatever jax it touches stays on the CPU.
        import jax

        jax.config.update("jax_platforms", "cpu")
        self.supervisor = Supervisor(
            exp.experiment_name, exp.trial_name,
            policy=RestartPolicy.from_config(self.ft),
            keepalive_ttl=getattr(self.ft, "keepalive_ttl_secs", 0.0),
            heartbeat_interval=getattr(
                self.ft, "heartbeat_interval_secs", 0.0
            ),
            # supervise=False restores the legacy contract: any child
            # death (of any kind) escalates immediately.
            restartable_kinds=(
                ("rollout", "gen_fleet", "reward")
                if getattr(self.ft, "supervise", True) else ()
            ),
        )
        setup = exp.initial_setup()

        # Persist the merged config next to the run (reference main_*.py).
        from areal_tpu.api import cli_args as CA

        CA.save_yaml(exp, os.path.join(
            CA.get_log_path(exp), "config.yaml"
        ))

        # Per-worker chip partitioning (decoupled async mode on real TPU):
        # fail fast on impossible layouts instead of letting two processes
        # claim one chip. CPU-forced runs skip it.
        chips = {"trainer": None, "gen": None}
        if not self.force_cpu and "gen_servers" in setup:
            n_chips = self._count_chips()
            asg = derive_chip_assignment(
                getattr(exp, "allocation_mode", ""), n_chips
            )
            chips = {"trainer": asg["trainer"], "gen": asg["gen"]}
            logger.info(f"chip assignment: {asg}")
        setup["trainer"].chips = chips["trainer"]

        n_dist = getattr(exp, "trainer_dist_procs", 1)
        if n_dist > 1:
            # One SPMD trainer process per (virtual) host; rank 0 owns the
            # control plane, the rest replay its broadcasts.
            import copy as _copy

            # On real TPU, partition the trainer chip list across the dist
            # processes — copying the same list would have every process
            # initialize the same chips (the double-claim the chip
            # assignment exists to prevent).
            chip_slices = [None] * n_dist
            # With trainer_dist_devices_per_proc set, trainer_entry forces
            # virtual CPU devices per process and the chip list is unused.
            if (chips["trainer"] is not None
                    and not getattr(exp, "trainer_dist_devices_per_proc",
                                    None)):
                tchips = list(chips["trainer"])
                if len(tchips) % n_dist != 0:
                    raise RuntimeError(
                        f"trainer_dist_procs={n_dist} does not divide the "
                        f"{len(tchips)} trainer chips {tchips}; pick a "
                        "divisor"
                    )
                per = len(tchips) // n_dist
                chip_slices = [
                    tchips[r * per:(r + 1) * per] for r in range(n_dist)
                ]
            for r in range(n_dist):
                tc = _copy.deepcopy(setup["trainer"])
                tc.dist_rank = r
                tc.dist_world = n_dist
                tc.chips = chip_slices[r]
                tc.dist_local_devices = getattr(
                    exp, "trainer_dist_devices_per_proc", None
                )
                self._spawn(trainer_entry, exp, tc,
                            name=f"trainer{r}", kind="trainer")
        else:
            self._spawn(trainer_entry, exp, setup["trainer"],
                        name="trainer", kind="trainer")
        # Sandbox reward fleet (docs/rewards.md): CPU-only, supervised
        # as a restartable stateless domain — a crashed reward worker
        # respawns in place while clients retry on surviving replicas.
        # Spawned BEFORE the rollout side: reward workers are jax-free
        # and register in well under the fleet's startup time, so the
        # first grade never races their registration into local
        # code execution.
        for i, rw in enumerate(setup.get("reward_workers", [])):
            self._spawn(reward_worker_entry, exp, rw,
                        name=f"reward{i}", kind="reward")
        if "gen_servers" in setup:
            self._spawn(
                gen_fleet_entry, exp, setup["gen_servers"],
                setup["gserver_manager"], chips["gen"],
                name="gen_fleet", kind="gen_fleet",
            )
            for i, rc in enumerate(setup["rollout_workers"]):
                # A bounded worker (max_rollouts set) finishing its quota
                # exits 0 by DESIGN — only unbounded workers' clean exits
                # are the silent data-starvation failure the supervisor
                # must catch.
                self._spawn(rollout_entry, exp, rc,
                            name=f"rollout{i}", kind="rollout",
                            required=getattr(rc, "max_rollouts",
                                             None) is None)
            asc = getattr(exp, "autoscale", None)
            if asc is not None and getattr(asc, "enabled", False):
                self._scaler = self._build_scaler(exp, setup)

        evaluator = None
        if getattr(exp, "auto_eval", False):
            from areal_tpu.apps.evaluator import AutomaticEvaluator

            from areal_tpu.api.cli_args import AutomaticEvaluatorConfig

            eval_data = exp.auto_eval_config.data_names
            default_names = AutomaticEvaluatorConfig().data_names
            if not os.path.isfile(eval_data):
                if eval_data and eval_data != default_names:
                    # An explicitly-set eval set that doesn't exist is a
                    # config error: silently scoring the TRAIN set would
                    # masquerade as held-out accuracy.
                    raise FileNotFoundError(
                        f"auto_eval_config.data_names={eval_data!r} does not "
                        f"exist; point it at a prompt jsonl (the default "
                        f"{default_names!r} falls back to the training set)"
                    )
                logger.warning(
                    "auto_eval_config.data_names=%r is not a local file — "
                    "evaluator will score the TRAINING dataset (%s); "
                    "eval/* metrics are NOT held-out numbers",
                    eval_data, exp.dataset.path,
                )
                eval_data = exp.dataset.path
            # eval/* metrics land in the run's tensorboard alongside the
            # master's training scalars (separate writer, same log dir).
            eval_writer = None
            tb = getattr(setup["master"], "tensorboard_path", None)
            if tb:
                from areal_tpu.base.monitor import MetricWriter

                eval_writer = MetricWriter(
                    tensorboard_path=os.path.join(tb, "eval")
                )
            # With the reward fleet up, eval generations grade there too
            # — untrusted checkpoint output must not execute in the eval
            # subprocess either. The NFS name-resolve root rides along so
            # the subprocess can discover the workers.
            rs = None
            if getattr(getattr(exp, "reward_service", None),
                       "enabled", False):
                import dataclasses as _dc
                import json as _json

                # The same derivation setup_name_resolve applies
                # (experiments/common.py): explicit nfs_record_root or
                # the per-experiment default. Non-NFS repos pass "" —
                # the eval subprocess then uses its environment's
                # default (memory repos cannot cross a process anyway).
                nr_cfg = exp.cluster.name_resolve
                nr_root = ""
                if getattr(nr_cfg, "type", "nfs") == "nfs":
                    nr_root = (nr_cfg.nfs_record_root
                               or C.experiment_paths(exp)["name_resolve"])
                rs = (exp.experiment_name, exp.trial_name, nr_root,
                      _json.dumps(_dc.asdict(exp.reward_service)))
            evaluator = AutomaticEvaluator(
                exp.auto_eval_config,
                save_dir=setup["master"].save_dir,
                dataset_path=eval_data,
                metric_writer=eval_writer,
                mock_tokenizer=bool(getattr(exp, "mock_tokenizer", False)),
                reward_service=rs,
            )
            evaluator.start()
            logger.info(f"automatic evaluator watching "
                        f"{setup['master'].save_dir} (data: {eval_data})")

        master = MasterWorker(setup["master"], setup["dfg"])
        restore_sigterm = self._install_sigterm()
        try:
            result = self._run_master_monitored(master)
        finally:
            restore_sigterm()
            if evaluator is not None:
                evaluator.stop()
            self.shutdown()
        return result

    def _build_scaler(self, exp, setup: Dict[str, Any]):
        """The launcher-side actuator of the manager's autoscale plan:
        spawns supervised single-server workers (gen_server_entry) from a
        clone of the baseline server spec. Dynamic servers are
        ``required=False`` (their WorkerControl-commanded exit after a
        drain is expected) and ``expendable`` (a crash loop removes them
        from the fleet instead of escalating — the plan replaces them)."""
        import copy

        from areal_tpu.system.autoscaler import AutoscaleExecutor

        template = setup["gen_servers"][0]
        if not self.force_cpu:
            # Dynamic servers have no reserved chips on this host: a
            # second JAX process claiming the baseline fleet's chips
            # would abort both. Multi-host/pod launchers place dynamic
            # servers on hosts with free capacity; locally the executor
            # still runs (the plan is visible in fleet-status) but spawn
            # capacity is whatever the platform tolerates.
            logger.warning(
                "autoscale: dynamic generation servers on a single TPU "
                "host share the gen chip set; scale-up beyond the "
                "baseline fleet is intended for CPU runs or multi-host "
                "placement (docs/operations.md §Capacity planning)"
            )

        def _spawn_dyn(server_id: str) -> None:
            sc = copy.deepcopy(template)
            sc.server_id = server_id
            sc.port = None
            # chips=None: dynamic servers are unpinned (see the warning
            # above for the single-host TPU caveat).
            self._spawn(
                gen_server_entry, exp, sc, None,
                name=f"genserver_{server_id}", kind="gen_server",
                required=False, expendable=True,
            )

        return AutoscaleExecutor(
            exp.experiment_name, exp.trial_name, self.supervisor,
            _spawn_dyn,
        )

    def _run_master_monitored(self, master) -> Dict[str, Any]:
        result: Dict[str, Any] = {}
        err: List[BaseException] = []

        def run():
            try:
                result.update(master.run())
            except BaseException as e:  # noqa: BLE001 — surfaced below
                err.append(e)

        t = threading.Thread(target=run, daemon=True)
        t.start()
        while t.is_alive():
            if self._drain_evt.is_set() and self._drain_thread is None:
                self._start_drain()
            if self._drain_deadline is not None and (
                self._drain_failed
                or time.monotonic() > self._drain_deadline
            ):
                # The graceful path died or overran its budget while the
                # master kept running — a silently-dropped SIGTERM would
                # train until the preemptor SIGKILLs with no checkpoint.
                # Raise so the finally-path shutdown() tears the children
                # down now (the caller sees a failed run, as it should).
                raise RuntimeError(
                    "graceful drain failed or timed out; forcing teardown"
                )
            self._check_children()
            if self._scaler is not None:
                try:
                    self._scaler.step()
                except Exception as e:  # noqa: BLE001 — scaling is
                    # best-effort; the run must not die on a bad plan
                    logger.warning(f"autoscale executor step failed: {e}")
            t.join(timeout=1.0)
        if err:
            raise err[0]
        return result

    def _start_drain(self) -> None:
        """Graceful drain in a side thread: the monitor loop keeps
        watching children while the panel sequence (pause → checkpoint →
        exit) runs; the master thread returning normally ends the run.
        The monitor loop enforces the fallback: if this thread fails (or
        the master is still alive well past the drain budget), the run
        is torn down rather than left training through its preemption
        notice."""
        from areal_tpu.system.supervisor import drain_experiment

        exp = self.exp_cfg
        self.supervisor.begin_drain()
        budget = getattr(self.ft, "drain_timeout_secs", 60.0)
        # 2x: the drain sequence itself is bounded by `budget`; the extra
        # slack covers the master finishing its finalization afterwards.
        self._drain_deadline = time.monotonic() + 2 * budget

        def _drain():
            try:
                drain_experiment(
                    exp.experiment_name, exp.trial_name, timeout=budget,
                )
            except Exception as e:  # noqa: BLE001 — monitor loop enforces
                logger.warning(f"graceful drain failed ({e}); the monitor "
                               "loop will force teardown")
                self._drain_failed = True

        self._drain_thread = threading.Thread(target=_drain, daemon=True)
        self._drain_thread.start()

    def shutdown(self) -> None:
        if self._drain_thread is not None:
            self._drain_thread.join(
                timeout=getattr(self.ft, "drain_timeout_secs", 60.0)
            )
        if self.supervisor is not None:
            self.supervisor.shutdown(timeout=10.0)


def run_experiment(exp_cfg) -> Dict[str, Any]:
    """Entry used by training/main_*.py (reference training/utils.py:339).

    ``recover_mode`` ∈ {disabled, resume, auto, fault}: "resume" restores
    from the latest recover checkpoint immediately; "auto"/"fault"
    additionally re-launch the whole experiment (with recovery) when a
    worker dies, up to ``recover_retries`` times — the reference's
    launcher-level restart loop (``realhf/apps/main.py:118-180``).
    """
    # Belt-and-braces re-validation (training/_cli.py already validates at
    # parse time): programmatic callers get the same clear error for the
    # descoped mode=ray instead of a bare NotImplementedError.
    from areal_tpu.api.cli_args import validate_config

    validate_config(exp_cfg)
    mode = getattr(exp_cfg, "mode", "local")
    if mode == "slurm":
        from areal_tpu.apps.slurm import SlurmLauncher

        return SlurmLauncher(exp_cfg).run()
    recover_mode = getattr(exp_cfg, "recover_mode", "disabled")
    retries = (
        getattr(exp_cfg, "recover_retries", 1)
        if recover_mode in ("auto", "fault") else 0
    )
    ft = getattr(exp_cfg, "fault_tolerance", None)
    base = getattr(ft, "relaunch_backoff_secs", 5.0)
    cap = getattr(ft, "relaunch_backoff_max_secs", 60.0)
    attempt = 0
    while True:
        try:
            return LocalLauncher(exp_cfg).run()
        except Exception:
            attempt += 1
            if attempt > retries:
                raise
            backoff = min(base * 2 ** (attempt - 1), cap)
            logger.warning(
                f"experiment failed (attempt {attempt}/{retries}); "
                f"re-launching with recovery in {backoff:.1f}s"
            )
            # The dead incarnation's endpoints (streams, worker control,
            # server urls, model_version) are poison for the relaunch: a
            # new worker resolving them would hang against closed sockets.
            # Clear the whole trial subtree — every live registration
            # belongs to workers the launcher just tore down, and the new
            # incarnation re-registers everything it needs.
            try:
                from areal_tpu.base import name_resolve, names

                name_resolve.clear_subtree(names.trial_root(
                    exp_cfg.experiment_name, exp_cfg.trial_name
                ))
            except Exception as e:  # noqa: BLE001 — best-effort hygiene
                logger.warning(f"stale name_resolve clear failed: {e}")
            time.sleep(backoff)
            exp_cfg.recover_mode = "resume"
