"""FULL async-PPO e2e across processes — the AReaL architecture end to end:

  rollout worker → (staleness gate) gserver manager → generation server
       ↓ ZMQ push                                         ↑ weight fanout
  trainer (stream dataset) ← master DFG (ref/prox inf, actor train)
       └── publishes actor weights (disk path + model_version bump) ──┘

CPU analogue of the reference's async experiment e2e tests.
"""

import multiprocessing as mp

import numpy as np
import pytest

from areal_tpu.api.data import MicroBatchSpec
from areal_tpu.api.dfg import (
    MFCDef,
    MFCInterfaceType,
    ModelInterfaceAbstraction,
    WeightUpdateHook,
    build_graph,
)
from areal_tpu.base import name_resolve
from areal_tpu.base.testing import MockTokenizer, make_mixed_jsonl

EXP, TRIAL = "asyncppo", "t0"
TINY = {"vocab_size": 258, "seed": 0}
# Telemetry rides along on the full-loop e2e (docs/observability.md):
# every worker kind pushes snapshots to the master's aggregator. Fast
# flushes so the few-step run lands several snapshots per worker, and a
# proportionally short stitch grace so traces appear on the LIVE merged
# scrape before the short run ends (tiny models can finish all three
# steps inside the default 5 s grace).
TEL = {"enabled": True, "flush_interval_secs": 0.3,
       "stitch_grace_secs": 0.8}


def _tel():
    from areal_tpu.api.train_config import TelemetryConfig

    return TelemetryConfig(**TEL)


def _sentinel(tmp_path):
    """Sentinel armed on the e2e (docs/observability.md §Alerting): the
    DEFAULT rule pack rides along — a healthy run must fire zero critical
    alerts from it — plus one INJECTED anomaly probe. The injection is a
    hair-trigger threshold on the first train step's gradient signature
    (the FaultInjector pattern applied to the rule pack: arm a condition
    no production config would use, observe the full fire → alert →
    evidence pipeline deterministically inside a 3-step run)."""
    from areal_tpu.api.train_config import SentinelConfig

    return SentinelConfig(
        enabled=True, eval_interval_secs=0.1,
        rules=[{
            "id": "e2e_divergence_probe", "metric": "train/grad_norm",
            "kind": "threshold", "op": "gt", "value": 1e-6,
            "for": 0.2, "cooldown": 600, "severity": "critical",
            "description": "e2e-injected divergence probe",
        }],
        alerts_path=str(tmp_path / "alerts.jsonl"),
        evidence_dir=str(tmp_path / "evidence"),
    )


def _goodput():
    from areal_tpu.api.train_config import GoodputConfig

    # Goodput ledger on (docs/observability.md §Goodput): every worker
    # classifies its wall clock, the trainer emits live MFU, the master
    # stitches fleet goodput. CPU has no entry in the peak table — the
    # override keeps train/mfu computable (the degrade-to-TFLOP/s path
    # is unit-tested in tests/test_goodput.py).
    return GoodputConfig(enabled=True, export_interval_secs=0.2,
                         peak_flops_override=1e12)


def _compile_watch():
    from areal_tpu.api.train_config import CompileWatchConfig

    # Compile & HBM observatory on (docs/observability.md §Compile &
    # memory): every chip-bearing worker traces its jit entry points and
    # samples HBM (degrading once on this CPU backend). The LOW storm
    # warmup lets the injected shape churn in the gen fleet cross the
    # stability threshold within the short run.
    return CompileWatchConfig(enabled=True, storm_warmup_calls=4,
                              mem_sample_interval_secs=0.2)


def _serving():
    from areal_tpu.api.train_config import ServingConfig

    # Serving engine on (docs/serving.md): the fleet carries rollout
    # traffic AND the interactive probe below through one server.
    return ServingConfig(enabled=True)


def _reward_cfg():
    from areal_tpu.api.train_config import RewardServiceConfig

    # Sandbox reward service on (docs/rewards.md): code rewards grade in
    # a SEPARATE reward-worker process, never in the rollout process.
    return RewardServiceConfig(enabled=True, n_workers=1)


def _reward_main(nr_root):
    from areal_tpu.base import name_resolve as nr

    nr.DEFAULT_REPO = nr.NfsNameRecordRepo(nr_root)
    from areal_tpu.system.reward_worker import RewardWorker, RewardWorkerConfig

    RewardWorker(RewardWorkerConfig(
        experiment=EXP, trial=TRIAL, worker_index=0,
        reward=_reward_cfg(), telemetry=_tel(),
    )).run()


def _gen_fleet_main(nr_root, data_path, realloc_dir, flight_dir):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from areal_tpu.base import name_resolve as nr

    nr.DEFAULT_REPO = nr.NfsNameRecordRepo(nr_root)
    import asyncio
    import dataclasses as dc

    from areal_tpu.api.model import GenerationHyperparameters
    from areal_tpu.models import transformer
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system.generation_server import (
        GenerationServer,
        GenerationServerConfig,
    )
    from areal_tpu.system.gserver_manager import (
        GserverManager,
        GserverManagerConfig,
    )
    from areal_tpu.system.rollout_worker import RolloutWorker, RolloutWorkerConfig

    # Flight recorder armed (docs/observability.md): killing this process
    # mid-run must leave flight_<worker>.jsonl evidence behind.
    tel = dc.replace(_tel(), flight_dir=flight_dir)

    async def main():
        kw = dict(TINY)
        seed = kw.pop("seed", 0)
        cfg = tiny_config(**kw)
        params = transformer.init_params(cfg, jax.random.PRNGKey(seed))
        server = GenerationServer(
            GenerationServerConfig(
                experiment=EXP, trial=TRIAL, chunk_tokens=4,
                prompt_bucket=16, batch_window_ms=2, telemetry=tel,
                serving=_serving(), goodput=_goodput(),
                compile_watch=_compile_watch(),
            ),
            cfg, params,
        )
        await server.start()

        # Injected recompile storm (ISSUE 20 acceptance): a tiny watched
        # fn on THIS server's per-instance watch is held shape-stable
        # past storm_warmup_calls, then fed a never-before-seen shape
        # every cycle — compile/storm_events climbs at a rate far above
        # the recompile_storm rule's 0.02/s threshold, and the sentinel
        # on the master must fire within the rule's `for:` window.
        import threading
        import time as _time

        import numpy as _np

        def _storm_forever():
            probe = server.compile_watch.wrap("e2e/storm_probe",
                                              lambda x: x)
            stable = _np.zeros((4,), _np.float32)
            i = 0
            while True:
                for _ in range(4):  # re-stabilize past the warmup window
                    probe(stable)
                i += 1
                probe(_np.zeros((4 + i,), _np.float32))
                _time.sleep(0.05)

        threading.Thread(target=_storm_forever, daemon=True).start()
        mgr = GserverManager(GserverManagerConfig(
            experiment=EXP, trial=TRIAL, n_servers=1, train_batch_size=4,
            max_head_offpolicyness=4, realloc_dir=realloc_dir,
            weight_poll_secs=0.2, telemetry=tel,
        ))
        await mgr.start()
        worker = RolloutWorker(RolloutWorkerConfig(
            experiment=EXP, trial=TRIAL, dataset_path=data_path,
            gconfig=GenerationHyperparameters(max_new_tokens=8),
            group_size=2, chunk_tokens=4, max_concurrent=4,
            tokenizer=MockTokenizer(), max_rollouts=None,
            telemetry=tel, goodput=_goodput(),
            # Reward grading fans out to the reward worker fleet — this
            # process must never execute generated code itself.
            reward_service=_reward_cfg(),
        ))
        await worker.run_async()  # runs until killed

    asyncio.run(main())


def _trainer_main(nr_root, realloc_dir):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from areal_tpu.base import name_resolve as nr

    nr.DEFAULT_REPO = nr.NfsNameRecordRepo(nr_root)
    import areal_tpu.algorithms.ppo  # noqa: F401
    import areal_tpu.backend.jax_train  # noqa: F401
    from areal_tpu.algorithms.ppo import PPOHyperparameters
    from areal_tpu.api.model import FinetuneSpec, GenerationHyperparameters
    from areal_tpu.backend.jax_train import OptimizerConfig
    from areal_tpu.system.trainer_worker import (
        MFCRuntimeConfig,
        ModelRoleConfig,
        TrainerWorker,
        TrainerWorkerConfig,
    )

    hp = PPOHyperparameters(
        gen=GenerationHyperparameters(max_new_tokens=8),
        ppo_n_minibatches=2, group_size=2, kl_ctl=0.05,
        disable_value=True, group_adv_norm=False, adv_norm=True,
        use_decoupled_loss=True, behav_imp_weight_cap=10.0,
    )
    backend_args = {
        "compute_dtype": "float32", "length_bucket": 16, "rows_bucket": 2,
        "seqs_bucket": 4,
        "optimizer": OptimizerConfig(lr=1e-3, lr_scheduler_type="constant",
                                     warmup_steps_proportion=0.0),
    }
    cfg = TrainerWorkerConfig(
        experiment=EXP, trial=TRIAL, handler="trainer",
        models={
            "actor": ModelRoleConfig(init={"tiny": TINY},
                                     backend_args=backend_args),
            "ref": ModelRoleConfig(init={"tiny": TINY},
                                   backend_args=backend_args, train=False),
        },
        mfcs={
            "ref_inf": MFCRuntimeConfig(interface="ref_logprob",
                                        model_name="ref"),
            "actor_inf": MFCRuntimeConfig(
                interface="ppo_actor", interface_args={"hp": hp},
                model_name="actor"),
            "actor_train": MFCRuntimeConfig(
                interface="ppo_actor", interface_args={"hp": hp},
                model_name="actor"),
        },
        batch_size=8,
        ft_spec=FinetuneSpec(1, 32, 8),
        tokenizer=MockTokenizer(),
        stream_dataset=True,
        realloc_dir=realloc_dir,
        telemetry=_tel(),
        goodput=_goodput(),
        compile_watch=_compile_watch(),
    )
    TrainerWorker(cfg).run()


def _build_async_dfg():
    mfcs = [
        MFCDef(
            name="ref_inf", model_name="ref",
            interface_type=MFCInterfaceType.INFERENCE,
            interface_impl=ModelInterfaceAbstraction("ref_logprob"),
            input_keys=("packed_input_ids",),
            output_keys=("packed_ref_logprobs",),
            n_seqs=8, mb_spec=MicroBatchSpec(max_tokens_per_mb=512),
        ),
        MFCDef(
            name="actor_inf", model_name="actor",
            interface_type=MFCInterfaceType.INFERENCE,
            interface_impl=ModelInterfaceAbstraction("ppo_actor"),
            input_keys=("packed_input_ids",),
            output_keys=("prox_logprobs",),
            n_seqs=8, mb_spec=MicroBatchSpec(max_tokens_per_mb=512),
        ),
        MFCDef(
            name="actor_train", model_name="actor",
            interface_type=MFCInterfaceType.TRAIN_STEP,
            interface_impl=ModelInterfaceAbstraction("ppo_actor"),
            input_keys=("packed_input_ids", "prompt_mask", "packed_logprobs",
                        "rewards", "packed_ref_logprobs", "prox_logprobs",
                        "seq_no_eos_mask"),
            n_seqs=8, mb_spec=MicroBatchSpec(max_tokens_per_mb=512),
            post_hooks=[WeightUpdateHook(role="actor")],
        ),
    ]
    return build_graph(mfcs)


@pytest.mark.timeout(600)
def test_async_ppo_full_loop(tmp_path):
    nr_root = str(tmp_path / "nr")
    data_path = str(tmp_path / "math.jsonl")
    realloc_dir = str(tmp_path / "realloc")
    jsonl_path = str(tmp_path / "telemetry.jsonl")
    flight_dir = str(tmp_path / "flight")
    # Mixed math+code training data: code-RL rides the SAME async stack
    # (partial rollout + staleness gate + failover) as math — the
    # Agent/EnvironmentService contract is the extension point, not a
    # math-only special case (docs/rewards.md).
    make_mixed_jsonl(data_path, n_math=6, n_code=2)
    name_resolve.DEFAULT_REPO = name_resolve.NfsNameRecordRepo(nr_root)

    ctx = mp.get_context("spawn")
    trainer = ctx.Process(target=_trainer_main,
                          args=(nr_root, realloc_dir), daemon=True)
    fleet = ctx.Process(target=_gen_fleet_main,
                        args=(nr_root, data_path, realloc_dir, flight_dir),
                        daemon=True)
    # The sixth worker kind: reward grading in its own sandbox process.
    # Started FIRST — it is jax-free and registers in well under the time
    # the fleet takes to come up, so the rollout worker's first grade
    # already finds the fleet.
    reward_proc = ctx.Process(target=_reward_main, args=(nr_root,),
                              daemon=True)
    reward_proc.start()
    trainer.start()
    fleet.start()

    # Mixed-traffic probe (docs/serving.md): while the master drives the
    # rollout workload, a separate thread fires INTERACTIVE requests
    # through the manager's class-aware scheduler at the same fleet —
    # one fleet concurrently serving both classes, end to end.
    import json as _json
    import threading
    import time
    import urllib.request

    interactive_results = []

    def _interactive_probe():
        from areal_tpu.base import names as _names

        def post(url, payload):
            req = urllib.request.Request(
                url, data=_json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                return _json.loads(r.read().decode())

        try:
            murl = name_resolve.wait(
                _names.gen_server_manager(EXP, TRIAL), timeout=120
            )
        except Exception as e:  # noqa: BLE001 — surfaced via the assert
            interactive_results.append({"error": str(e)})
            return
        for i in range(3):
            # Per-attempt isolation: urlopen raises HTTPError on any
            # non-2xx (a transient 429/503 while the fleet churns), and
            # one failed attempt must not kill the remaining ones.
            try:
                route = post(f"{murl}/schedule_request",
                             {"class": "interactive"})
                if not route.get("url"):
                    time.sleep(0.2)
                    continue
                out = post(f"{route['url']}/generate", {
                    "prompt_ids": [7, 8, 9, 10 + i],
                    "class": "interactive",
                    "rid": f"interactive{i}",
                    "gconfig": {"max_new_tokens": 4, "greedy": True},
                    "max_tokens": 4,
                })
                post(f"{murl}/release", {"lease_id": route.get("lease_id"),
                                         "url": route["url"]})
                interactive_results.append(out)
            except Exception as e:  # noqa: BLE001 — surfaced via the assert
                interactive_results.append({"error": str(e)})
                time.sleep(0.2)

    probe = threading.Thread(target=_interactive_probe, daemon=True)
    probe.start()

    # The aggregator's merged fleet endpoint closes with the master, so
    # the "real Prometheus scrape carries the stitched prompt→trained
    # histogram" assertion polls it WHILE the run executes and keeps the
    # first body where the derived trace metrics went nonzero.
    from areal_tpu.base import network

    agg_port = network.find_free_port()
    merged_scrape = []
    sentinel_scrape = []
    goodput_scrape = []
    compile_scrape = []
    storm_scrape = []

    def _compile_ready(body):
        # Compile-observatory acceptance in one snapshot: compile events
        # from >= 2 worker kinds, the fleet compile-seconds rollup, and
        # the HBM surface (real gauges on TPU; on this CPU backend the
        # one-time memory_stats degradation counter).
        kinds = set()
        hbm_ok = False
        for ln in body.splitlines():
            if ln.startswith("areal_compile_events_total{"):
                _, _, rest = ln.partition('worker_kind="')
                kinds.add(rest.partition('"')[0])
            elif ln.startswith((
                "areal_hbm_bytes_in_use{",
                "areal_hbm_memory_stats_unavailable_total{",
            )):
                hbm_ok = True
        return (len(kinds - {"fleet"}) >= 2 and hbm_ok
                and 'worker_kind="fleet"' in body)

    def _goodput_ready(body):
        # Goodput acceptance in one snapshot: ledger counters from >= 3
        # worker kinds, a nonzero stitched fleet-goodput gauge, and a
        # live trainer MFU > 0 (docs/observability.md §Goodput).
        kinds = set()
        fleet_ok = mfu_ok = False
        for ln in body.splitlines():
            if ln.startswith("areal_goodput_secs_total{"):
                _, _, rest = ln.partition('worker_kind="')
                kinds.add(rest.partition('"')[0])
            elif ln.startswith("areal_fleet_goodput{") \
                    and "side=" not in ln:
                fleet_ok = float(ln.rpartition(" ")[2]) > 0
            elif ln.startswith("areal_train_mfu"):
                mfu_ok = float(ln.rpartition(" ")[2]) > 0
        return fleet_ok and mfu_ok and len(kinds) >= 3

    def _merged_scrape_probe():
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline \
                and not (merged_scrape and sentinel_scrape
                         and goodput_scrape and compile_scrape
                         and storm_scrape):
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{agg_port}/metrics", timeout=5
                ) as r:
                    body = r.read().decode()
                # Capture once the body shows BOTH the stitched trace
                # histogram AND the reward fleet's request counter — the
                # "live merged scrape" acceptance for tracing (PR 7) and
                # the reward service (docs/rewards.md) in one snapshot.
                trace_ok = any(
                    ln.startswith("areal_trace_e2e_secs_count")
                    and float(ln.rpartition(" ")[2]) > 0
                    for ln in body.splitlines()
                )
                if not merged_scrape and trace_ok \
                        and "areal_reward_requests_total" in body:
                    merged_scrape.append(body)
                # Separate capture for the sentinel acceptance: the fired
                # alert appears on the LIVE merged scrape as
                # areal_alerts_total{rule,severity} + areal_alert_active.
                # Keyed on the injected divergence rule specifically — the
                # recompile-storm probe fires its own alert much earlier,
                # so "any areal_alerts_total" would capture too soon.
                if not sentinel_scrape \
                        and 'rule="e2e_divergence_probe"' in body:
                    sentinel_scrape.append(body)
                # Third capture for the goodput-ledger acceptance.
                if not goodput_scrape and _goodput_ready(body):
                    goodput_scrape.append(body)
                # Fourth/fifth: the compile & HBM observatory, and the
                # injected recompile storm's alert on the LIVE scrape.
                if not compile_scrape and _compile_ready(body):
                    compile_scrape.append(body)
                if not storm_scrape \
                        and 'rule="recompile_storm"' in body:
                    storm_scrape.append(body)
            except Exception:  # noqa: BLE001 — aggregator not up yet
                pass
            time.sleep(0.3)

    scraper = threading.Thread(target=_merged_scrape_probe, daemon=True)
    scraper.start()
    try:
        from areal_tpu.system.master_worker import (
            ExperimentSaveEvalControl,
            MasterWorker,
            MasterWorkerConfig,
        )

        import dataclasses as dc

        master = MasterWorker(
            MasterWorkerConfig(
                experiment=EXP, trial=TRIAL, train_batch_size=8,
                exp_ctrl=ExperimentSaveEvalControl(
                    total_train_epochs=10**6, benchmark_steps=3,
                ),
                telemetry=dc.replace(_tel(), jsonl_path=jsonl_path,
                                     http_port=agg_port),
                # Training-health sentinel armed: default pack (must stay
                # quiet on this healthy run) + the injected probe.
                sentinel=_sentinel(tmp_path),
                # Fleet-goodput stitching in the same aggregator.
                goodput=_goodput(),
                # Arms the compile-aware sentinel pack (recompile_storm /
                # hbm_pressure / compile_stall) over the fleet's series.
                compile_watch=_compile_watch(),
            ),
            _build_async_dfg(),
        )
        from areal_tpu.base import names

        # Live pause/resume through the WorkerControlPanel (VERDICT #5 /
        # ISSUE 9 acceptance): once the RUNNING experiment has completed
        # a step, pause master+rollout+trainer (master FIRST — it must
        # park between steps before its data producers freeze), observe
        # the paused states and the frozen step counter, then resume and
        # let the run finish. The master is in-process (this thread runs
        # it), so the probe drives the panel from a side thread.
        pause_report = {}

        def _pause_resume_probe():
            from areal_tpu.system.worker_base import WorkerControlPanel

            panel = WorkerControlPanel(EXP, TRIAL, timeout=10.0)
            try:
                # Trigger on REGISTRATION, not on a step count: warm tiny
                # steps take <0.1s, so step-counter polling can miss the
                # whole run; a pause sent once all three control
                # endpoints exist queues on the master's REP socket and
                # lands at its next step boundary deterministically
                # (registration happens during setup, steps away from
                # benchmark completion).
                deadline = time.monotonic() + 240
                while time.monotonic() < deadline:
                    try:
                        if {"master", "rollout0", "trainer"} <= set(
                            panel.list_workers()
                        ):
                            break
                    except Exception:  # noqa: BLE001 — repo not ready
                        pass
                    time.sleep(0.05)
                else:
                    pause_report["error"] = "workers never registered"
                    return
                paused = {}
                for w in ("master", "rollout0", "trainer"):
                    for _ in range(12):  # busy-in-step commands time out
                        try:
                            paused[w] = panel.pause(w)["state"]
                            break
                        except TimeoutError:
                            pass
                pause_report["paused"] = paused
                s0 = master.step
                pause_report["rollout_state"] = \
                    panel.status("rollout0")["state"]
                # status is served from inside the PAUSED loop
                pause_report["master_state"] = \
                    panel.status("master")["state"]
                time.sleep(1.5)
                # Hold the pause until the injected recompile storm has
                # fired: its rule needs 10 s of sustained rate, which a
                # warm ~10 s run only reaches by luck.
                storm_deadline = time.monotonic() + 30
                while time.monotonic() < storm_deadline:
                    try:
                        with open(tmp_path / "alerts.jsonl") as f:
                            if '"recompile_storm"' in f.read():
                                break
                    except OSError:
                        pass
                    time.sleep(0.2)
                pause_report["frozen"] = (master.step == s0)
                pause_report["paused_at"] = s0
                for w in ("master", "rollout0", "trainer"):
                    try:
                        panel.resume(w)
                    except TimeoutError:
                        pass
            finally:
                panel.close()

        pauser = threading.Thread(target=_pause_resume_probe, daemon=True)
        pauser.start()

        result = master.run()
        assert result["steps"] == 3
        # --- pause/resume proven against the RUNNING experiment ---
        pauser.join(timeout=30)
        assert "error" not in pause_report, pause_report
        assert pause_report["paused"] == {
            "master": "paused", "rollout0": "paused", "trainer": "paused",
        }, pause_report
        assert pause_report["master_state"] == "paused"
        assert pause_report["rollout_state"] == "paused"
        assert pause_report["frozen"], pause_report
        # ...and the run ADVANCED past the frozen step after resume_all
        assert result["steps"] > pause_report["paused_at"]
        losses = [s["actor_train/actor_loss"] for s in result["stats"]]
        assert all(np.isfinite(x) for x in losses)
        # the weight-sync circle closed: version reached ≥ 2
        v = int(name_resolve.get(names.model_version(EXP, TRIAL, "actor")))
        assert v >= 2
        # --- unified telemetry landed (docs/observability.md) ---
        # the aggregated jsonl carries spans/metrics from ≥ 3 worker kinds
        import json as _json

        with open(jsonl_path) as f:
            recs = [_json.loads(ln) for ln in f if ln.strip()]
        kinds = {r["worker"].split(":")[0] for r in recs}
        assert len(kinds) >= 3, kinds
        assert any(r["spans"] for r in recs)
        # --- sandbox reward service proven end to end (docs/rewards.md):
        # the SIXTH worker kind pushed telemetry to the aggregator...
        assert "reward" in kinds, kinds
        # ...graded requests (incl. per-kind verdicts for BOTH task
        # kinds of the mixed fixture)...
        reward_counters: dict = {}
        rollout_counters: dict = {}
        for r in recs:
            wk = r["worker"].split(":")[0]
            tgt = reward_counters if wk == "reward" else (
                rollout_counters if wk == "rollout" else None
            )
            if tgt is not None:
                for k, v in (r.get("counters") or {}).items():
                    tgt[k] = tgt.get(k, 0) + v
        assert reward_counters.get("reward/requests", 0) > 0, reward_counters
        assert any(k.startswith("reward/verdicts{task=math")
                   for k in reward_counters), reward_counters
        assert any(k.startswith("reward/verdicts{task=code")
                   for k in reward_counters), reward_counters
        # ...while the ROLLOUT process executed ZERO generated code: every
        # code grade went over HTTP (remote counter), none ran locally.
        assert rollout_counters.get("reward_client/remote", 0) > 0, \
            rollout_counters
        assert not any("local_graded" in k for k in rollout_counters), \
            rollout_counters
        # the reward worker's own Prometheus endpoint serves the verdict
        # surface directly (the fleet-member contract)
        from areal_tpu.base import names as _nm

        (rw_url,) = name_resolve.get_subtree(
            _nm.reward_worker_root(EXP, TRIAL)
        )
        with urllib.request.urlopen(f"{rw_url}/metrics", timeout=10) as r:
            rprom = r.read().decode()
        assert "areal_reward_requests_total" in rprom
        assert 'task="code"' in rprom
        # the interactive probe must have finished BEFORE the scrapes
        # below — its histograms/counters are part of what we assert on.
        probe.join(timeout=60)
        # the generation server (fleet process still alive) serves valid
        # Prometheus text with weight-version + inflight gauges
        (gurl,) = name_resolve.get_subtree(
            names.gen_server_root(EXP, TRIAL)
        )
        with urllib.request.urlopen(f"{gurl}/metrics", timeout=10) as r:
            prom = r.read().decode()
        assert "# TYPE areal_genserver_weight_version gauge" in prom
        assert "areal_genserver_weight_version{" in prom
        assert "areal_genserver_inflight_requests{" in prom
        for ln in prom.splitlines():  # every sample line parses
            if ln and not ln.startswith("#"):
                float(ln.rpartition(" ")[2])
        murl = name_resolve.get(names.gen_server_manager(EXP, TRIAL))
        with urllib.request.urlopen(f"{murl}/metrics", timeout=10) as r:
            mprom = r.read().decode()
        assert "areal_gsmgr_healthy_servers 1" in mprom
        # --- mixed traffic proven end to end (docs/serving.md) ---
        ok_interactive = [
            r for r in interactive_results if r.get("output_ids")
        ]
        assert ok_interactive, interactive_results
        # per-class latency SLO histograms present in telemetry output:
        # the interactive probe AND the bulk rollout class both appear.
        assert "areal_serving_interactive_ttfc_secs_bucket" in prom
        assert "areal_serving_rollout_queue_wait_secs_bucket" in prom
        assert "areal_serving_compiled_shapes" in prom
        assert "areal_genserver_kv_states" in prom
        # the manager routed a class-aware interactive lease
        assert "areal_gsmgr_scheduled_interactive_total" in mprom
        # --- sample-lineage tracing landed (docs/observability.md) ---
        # traces.jsonl (default: next to telemetry.jsonl) holds stitched
        # end-to-end timelines whose spans come from ≥3 worker kinds:
        # the rollout worker that originated the trace, the generation
        # server that decoded it, and the trainer's terminal span.
        import os

        traces_path = str(tmp_path / "traces.jsonl")
        assert os.path.exists(traces_path), os.listdir(tmp_path)
        with open(traces_path) as f:
            traces = [_json.loads(ln) for ln in f if ln.strip()]
        assert traces
        kinds_per_trace = [
            {w.split(":")[0] for w in t["workers"]} for t in traces
        ]
        assert any(
            {"rollout", "generation_server", "trainer"} <= ks
            for ks in kinds_per_trace
        ), kinds_per_trace
        full = next(t for t, ks in zip(traces, kinds_per_trace)
                    if {"rollout", "generation_server", "trainer"} <= ks)
        assert full["e2e_secs"] > 0 and full["weight_version"] >= 0
        names_in_trace = {s["name"] for s in full["spans"]}
        assert "rollout/generate" in names_in_trace
        assert "genserver/queue_wait" in names_in_trace
        assert "trainer/train_sample" in names_in_trace
        assert set(full["stages"]) == {"generate", "queue", "gate",
                                       "train_wait", "train"}
        # the REAL merged Prometheus scrape (captured live) carries the
        # prompt→trained latency histogram with nonzero counts
        scraper.join(timeout=60)
        assert merged_scrape, \
            "merged /metrics never showed trace + reward metrics"
        assert "# TYPE areal_trace_e2e_secs histogram" in merged_scrape[0]
        assert "areal_trace_stage_train_wait_secs_bucket" in merged_scrape[0]
        # the LIVE merged scrape carries the reward fleet's counters
        # (acceptance: reward_requests_total on the merged endpoint)
        assert "areal_reward_requests_total" in merged_scrape[0]
        # --- training-health sentinel (docs/observability.md §Alerting) ---
        from areal_tpu.system.sentinel import DEFAULT_RULES

        alerts_path = tmp_path / "alerts.jsonl"
        assert alerts_path.exists(), os.listdir(tmp_path)
        with open(alerts_path) as f:
            alert_recs = [_json.loads(ln) for ln in f if ln.strip()]
        # (1) the DEFAULT pack stayed quiet: zero critical alerts on a
        # healthy run (conservative thresholds are the contract)
        default_ids = {r["id"] for r in DEFAULT_RULES}
        noisy = [r for r in alert_recs
                 if r.get("event") == "firing"
                 and r.get("severity") == "critical"
                 and r.get("rule") in default_ids]
        assert not noisy, noisy
        # (2) the injected anomaly fired its rule within the configured
        # `for:` window and landed in alerts.jsonl...
        probe = [r for r in alert_recs
                 if r.get("event") == "firing"
                 and r.get("rule") == "e2e_divergence_probe"]
        assert probe, alert_recs
        assert probe[0]["severity"] == "critical"
        assert probe[0]["for_secs"] == 0.2
        assert probe[0]["value"] > 1e-6
        # ...and on the LIVE merged Prometheus scrape
        assert sentinel_scrape, \
            "merged /metrics never showed areal_alerts_total"
        assert ('areal_alerts_total{rule="e2e_divergence_probe",'
                'severity="critical"') in sentinel_scrape[0]
        assert "areal_alert_active" in sentinel_scrape[0]
        # (2b) the INJECTED recompile storm (shape churn in the gen
        # fleet) fired the compile pack's rate rule within its `for:`
        # window, landed in alerts.jsonl with an evidence bundle, and
        # hit the live merged scrape.
        storm_recs = [r for r in alert_recs
                      if r.get("event") == "firing"
                      and r.get("rule") == "recompile_storm"]
        assert storm_recs, alert_recs
        assert storm_recs[0]["severity"] == "warn"
        assert storm_recs[0]["metric"] == "compile/storm_events"
        storm_ev = storm_recs[0].get("evidence_dir")
        assert storm_ev and os.path.isdir(storm_ev), storm_recs[0]
        assert storm_scrape, \
            "merged /metrics never showed the recompile_storm alert"
        assert 'areal_alerts_total{rule="recompile_storm"' \
            in storm_scrape[0]
        # --- goodput ledger (docs/observability.md §Goodput) ---
        # The LIVE merged scrape carried goodput_secs_total{state}
        # counters from >= 3 worker kinds, a nonzero stitched
        # fleet-goodput gauge, and train/mfu > 0 from the live trainer
        # (captured by _goodput_ready while the run executed).
        assert goodput_scrape, \
            "merged /metrics never satisfied the goodput acceptance"
        gbody = goodput_scrape[0]
        gkinds = set()
        gstates = set()
        for ln in gbody.splitlines():
            if ln.startswith("areal_goodput_secs_total{"):
                _, _, rest = ln.partition('worker_kind="')
                gkinds.add(rest.partition('"')[0])
                _, _, rest = ln.partition('state="')
                gstates.add(rest.partition('"')[0])
        assert {"trainer", "generation_server", "rollout"} <= gkinds, gkinds
        # the trainer/genserver wall partition surfaced both busy and
        # waiting states, not just one bucket
        assert "compute" in gstates and "idle" in gstates, gstates
        assert 'areal_fleet_goodput{side="trainer"' in gbody
        mfu_lines = [ln for ln in gbody.splitlines()
                     if ln.startswith("areal_train_mfu")]
        assert mfu_lines and float(mfu_lines[0].rpartition(" ")[2]) > 0
        assert "areal_train_achieved_tflops" in gbody
        # the generation server's analytic decode FLOP/s rode along
        assert "areal_genserver_decode_tflops" in gbody
        # --- compile & HBM observatory (docs/observability.md §Compile
        # & memory) --- the LIVE merged scrape carried compile events
        # from >= 2 chip-bearing worker kinds (trainer jit sites and the
        # generation server's prefill/decode wrappers), per-fn compile
        # seconds with the fleet rollup pseudo-worker, and the HBM
        # degradation counter (this CPU backend has no memory_stats —
        # the observatory must say so rather than export empty-chip
        # zeros).
        assert compile_scrape, \
            "merged /metrics never satisfied the compile acceptance"
        cbody = compile_scrape[0]
        ckinds = set()
        for ln in cbody.splitlines():
            if ln.startswith("areal_compile_events_total{"):
                _, _, rest = ln.partition('worker_kind="')
                ckinds.add(rest.partition('"')[0])
        assert {"trainer", "generation_server"} <= ckinds, ckinds
        assert ('areal_compile_secs_total{worker_index="0",'
                'worker_kind="fleet"}') in cbody
        assert 'fn="train/' in cbody  # trainer jit sites labeled per-fn
        assert "areal_compile_distinct_shapes" in cbody
        assert "areal_hbm_memory_stats_unavailable_total" in cbody
        # (3) evidence was captured while the anomaly was live: the
        # bundle holds the alert + triggering metric window + pinned
        # traces, and the fan-out flight-dump trigger pulls rings from
        # the still-running fleet/reward/trainer processes (each worker
        # acts within one telemetry flush interval).
        evidence_dir = probe[0].get("evidence_dir")
        assert evidence_dir and os.path.isdir(evidence_dir), probe[0]
        with open(os.path.join(evidence_dir, "alert.json")) as f:
            ev = _json.load(f)
        assert ev["rule"] == "e2e_divergence_probe"
        assert ev["metric_window"], ev
        assert any(p["value"] > 1e-6 for p in ev["metric_window"])
        assert any(k.startswith("trainer:0|train/grad_norm")
                   for k in ev["sources"])
        assert os.path.exists(os.path.join(evidence_dir, "traces.json"))

        def _flight_kinds():
            return {
                fn[len("flight_"):].rstrip("0123456789.jsonl") or fn
                for fn in os.listdir(evidence_dir)
                if fn.startswith("flight_")
            }

        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and len(_flight_kinds()) < 2:
            time.sleep(0.3)
        assert len(_flight_kinds()) >= 2, os.listdir(evidence_dir)
        # --- flight recorder: killing a generation server mid-run leaves
        # crash evidence (SIGTERM hook dumps each worker's ring) ---
        assert fleet.is_alive()
        fleet.terminate()
        fleet.join(timeout=15)
        flight_files = sorted(os.listdir(str(tmp_path / "flight")))
        assert any(fn.startswith("flight_generation_server")
                   for fn in flight_files), flight_files
        with open(tmp_path / "flight" / flight_files[0]) as f:
            frecs = [_json.loads(ln) for ln in f if ln.strip()]
        assert frecs and frecs[-1]["kind"] == "dump"
        assert frecs[-1]["reason"] == "sigterm"
    finally:
        for p in (trainer, fleet, reward_proc):
            if p.is_alive():
                p.terminate()
        trainer.join(timeout=10)
        fleet.join(timeout=10)
        reward_proc.join(timeout=10)


# ------------------ device-transport weight bump (in-process e2e) ------


@pytest.mark.reshard
@pytest.mark.timeout(120)
def test_device_transport_weight_bump_e2e(tmp_name_resolve):
    """One weight bump over weight_sync.transport=device, end to end on
    CPU meshes: the trainer reshards its live params into the generation
    fleet's layout ON DEVICE and registers the publication; the manager's
    fanout auto-detects the device descriptor over disk; the server's
    swap stays digest-gated and atomic (a forged digest 500s with the old
    pair still live); and the trainer's goodput ledger attributes the
    publish to goodput/secs{state=comm} on the live scrape. In-process by
    construction — the device transport requires publisher and consumers
    to share one JAX runtime (docs/weight_sync.md §device); the
    cross-process fleets above keep using stream/disk."""
    import asyncio
    import json as _json
    import os

    import jax

    import areal_tpu.backend.jax_train  # noqa: F401 — registers "jax_train"
    from areal_tpu.api.model import FinetuneSpec, make_backend
    from areal_tpu.api.train_config import WeightSyncConfig
    from areal_tpu.base import names, telemetry
    from areal_tpu.base.retry import RetryPolicy
    from areal_tpu.models import transformer
    from areal_tpu.models.config import tiny_config
    from areal_tpu.models.hf import flatten_pytree
    from areal_tpu.parallel import reshard as rsh
    from areal_tpu.system import goodput
    from areal_tpu.system.generation_server import (
        GenerationServer,
        GenerationServerConfig,
    )
    from areal_tpu.system.gserver_manager import (
        GserverManager,
        GserverManagerConfig,
        _ServerHealth,
    )
    from areal_tpu.system.trainer_worker import (
        ModelRoleConfig,
        TrainerWorker,
        TrainerWorkerConfig,
    )

    cfg = TrainerWorkerConfig(
        experiment=EXP, trial=TRIAL,
        models={"actor": ModelRoleConfig(
            init={"tiny": {"vocab_size": 258}},
            backend_args={"compute_dtype": "float32", "length_bucket": 16},
        )},
        ft_spec=FinetuneSpec(1, 32, 8),
        realloc_dir="/nonexistent/never/written",
        weight_sync=WeightSyncConfig(transport="device"),
    )
    w = TrainerWorker(cfg)
    for role, rc in cfg.models.items():
        backend = make_backend(rc.backend, train=rc.train, **rc.backend_args)
        w.models[role] = backend.initialize(
            w._model_factory(role, rc), cfg.ft_spec
        )
    # Arm a real ledger on a private registry: _publish_weights_device
    # runs under state("comm"), and the flush below must surface that on
    # the scrape (the worker's own ledger is wired identically in setup()).
    reg = telemetry.TelemetryRegistry()
    w._ledger = goodput.GoodputLedger(reg, export_interval_secs=0.0)

    # Make the bump observable: perturb the trainer's weights away from
    # the generation server's init, and move the version off 0.
    engine = w.models["actor"].module
    engine.params = jax.tree.map(
        lambda x: x * 1.25 if x.dtype == np.float32 else x, engine.params
    )
    w.models["actor"].version.global_step = 3

    mcfg = tiny_config(vocab_size=258)  # same shapes as the tiny actor
    server = GenerationServer(
        GenerationServerConfig(experiment=EXP, trial=TRIAL, chunk_tokens=4,
                               prompt_bucket=16, batch_window_ms=2),
        mcfg, transformer.init_params(mcfg, jax.random.PRNGKey(1)),
    )

    async def main():
        import aiohttp

        url = await server.start()
        try:
            w.publish_weights("actor")
            # discovery: descriptor + version key, no checkpoint anywhere
            desc = _json.loads(name_resolve.get(
                names.weight_device(EXP, TRIAL, "actor")))
            assert desc["version"] == 3 and desc["digest"]
            assert int(name_resolve.get(
                names.model_version(EXP, TRIAL, "actor"))) == 3
            assert not os.path.exists("/nonexistent/never/written")

            mgr = GserverManager(GserverManagerConfig(
                experiment=EXP, trial=TRIAL, fanout_timeout_secs=5.0,
                fanout_retry=RetryPolicy(max_attempts=2,
                                         base_delay_secs=0.01),
            ))
            mgr.servers = [url]
            mgr._inflight = {url: 0}
            mgr.health = {url: _ServerHealth()}
            async with aiohttp.ClientSession() as sess:
                # transport auto-detection routes at the device
                # publication, not the (nonexistent) disk checkpoint
                payload = mgr._update_payload(3, "/unused/disk/path")
                assert payload.get("device") is True
                assert payload["digest"] == desc["digest"]
                acked = await mgr.fanout_weights(sess, 3,
                                                 "/unused/disk/path")
                assert acked == [url] and mgr.version == 3
                assert server.version == 3

                # gen-side params: bit-identical to the trainer's
                # compute-dtype tree
                want = flatten_pytree(w._compute_dtype_params("actor"),
                                      as_numpy=True)
                got = flatten_pytree(server.params, as_numpy=True)
                assert set(got) == set(want)
                for k in want:
                    np.testing.assert_array_equal(got[k], want[k], err_msg=k)

                # digest gate: a forged fanout 500s and the just-swapped
                # (params, version) pair stays live
                async with sess.post(f"{url}/update_weights", json={
                    "device": True, "role": "actor",
                    "version": 3, "digest": "deadbeef",
                }) as r:
                    assert r.status == 500
                async with sess.get(f"{url}/metrics.json") as r:
                    assert (await r.json())["version"] == 3
                after = flatten_pytree(server.params, as_numpy=True)
                for k in want:
                    np.testing.assert_array_equal(after[k], want[k])
        finally:
            await server.stop()

    try:
        asyncio.run(main())
    finally:
        rsh.clear_publication(EXP, TRIAL, "actor")

    # live scrape: the on-device publish accrued into the comm state
    w._ledger.flush()
    body = telemetry.render_prometheus(reg.snapshot(reset=False),
                                       labels={"kind": "trainer"})
    comm = [ln for ln in body.splitlines()
            if ln.startswith("areal_goodput_secs_total")
            and 'state="comm"' in ln]
    assert comm, body
    assert float(comm[0].rpartition(" ")[2]) > 0.0
