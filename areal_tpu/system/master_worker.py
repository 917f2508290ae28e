"""Master worker — owns the training loop and DFG traversal.

Parity target: ``realhf/system/master_worker.py:49`` +
``function_executor.py:24`` + ``model_function_call.py:54``: per training
step, spawn one asyncio task per MFC plus a data-loading task; each MFC
task blocks on the metadata buffer until its input keys are ready for
n_seqs samples, dispatches the call to the trainer over ZMQ, and amends the
buffer with the outputs. Save/eval frequency control via timeutil; epoch
accounting from the trainer's fetch replies.

TPU-first simplifications: no DP dispatch/redistribution planning (the
trainer is one SPMD process — GSPMD does the sharding the reference's
RedistribPlanner computed), and requests go to a single trainer handler per
model role group.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from types import SimpleNamespace
from typing import Any, Dict, List, Optional

from areal_tpu.api.data import SequenceSample
from areal_tpu.api.dfg import (
    DataFlowGraph,
    MFCDef,
    MFCInterfaceType,
    ParamReallocHook,
    WeightUpdateHook,
)
from areal_tpu.base import logging, telemetry
from areal_tpu.base.stats_tracker import StatsTracker
from areal_tpu.base.timeutil import FrequencyControl
from areal_tpu.system.buffer import AsyncSequenceBuffer
from areal_tpu.system.streams import MasterRequestStream, Payload

logger = logging.getLogger("system.master")


# Canonical home is the dependency-free api.train_config; re-exported here
# because this module historically defined it.
from areal_tpu.api.train_config import (  # noqa: E402,F401
    CompileWatchConfig,
    DurabilityConfig,
    ExperimentSaveEvalControl,
    GoodputConfig,
    SentinelConfig,
    TelemetryConfig,
)


@dataclasses.dataclass
class MasterWorkerConfig:
    experiment: str = "exp"
    trial: str = "trial"
    trainer_handler: str = "trainer"
    train_batch_size: int = 8
    exp_ctrl: ExperimentSaveEvalControl = dataclasses.field(
        default_factory=ExperimentSaveEvalControl
    )
    save_dir: str = "/tmp/areal_tpu/ckpt"
    # async mode: generation happens outside the DFG (rollout workers)
    src_is_stream: bool = False
    # observability (reference master_worker.py:291-350)
    tensorboard_path: Optional[str] = None
    wandb_mode: str = "disabled"
    # Unified telemetry (base/telemetry.py): the master hosts the
    # cross-worker aggregator (telemetry.jsonl + tensorboard mirror +
    # optional Prometheus http port). Off by default.
    telemetry: TelemetryConfig = dataclasses.field(
        default_factory=TelemetryConfig
    )
    # Training-health sentinel (system/sentinel.py): hosted inside the
    # aggregator above; requires telemetry. Off by default — nothing is
    # constructed and the merged scrape is bit-identical.
    sentinel: SentinelConfig = dataclasses.field(
        default_factory=SentinelConfig
    )
    # Goodput ledger (system/goodput.py): when enabled the aggregator
    # hosts the fleet-goodput stitcher (useful chip-seconds / total,
    # split trainer vs generation) on the merged scrape. Off by default.
    goodput: GoodputConfig = dataclasses.field(
        default_factory=GoodputConfig
    )
    # Durable sample delivery (system/sample_spool.py): the master's
    # interest is indirect — the freed-id forwarding below is the ack
    # trigger, and with durability armed the sentinel gains the
    # sample_loss absence rule on spool acks.
    durability: DurabilityConfig = dataclasses.field(
        default_factory=DurabilityConfig
    )
    # Compile & HBM observatory (base/compile_watch.py): the master's
    # interest is rule-pack arming — with the observatory on, the
    # sentinel gains the recompile_storm / hbm_pressure / compile_stall
    # pack over the series the chip-bearing workers export.
    compile_watch: CompileWatchConfig = dataclasses.field(
        default_factory=CompileWatchConfig
    )
    # recover checkpoints (RecoverInfo + trainer train-state) live here
    recover_dir: str = ""
    # resume from the latest recover checkpoint at startup
    recover: bool = False
    keep_recover_ckpts: int = 2


class MasterWorker:
    def __init__(self, cfg: MasterWorkerConfig, dfg: DataFlowGraph):
        self.cfg = cfg
        self.dfg = dfg
        # Every node reads its inputs from the buffer once.
        self.buffer = AsyncSequenceBuffer(n_rpcs_reading=len(dfg.nodes))
        self.stream: Optional[MasterRequestStream] = None
        self.step = 0
        self.epoch = 0
        self._train_nodes = [
            n for n in dfg.nodes.values()
            if n.interface_type == MFCInterfaceType.TRAIN_STEP
        ]
        self._gen_nodes = [
            n for n in dfg.nodes.values()
            if n.interface_type == MFCInterfaceType.GENERATE
        ]
        self.stats = StatsTracker()
        self._save_ctl = FrequencyControl(
            freq_step=cfg.exp_ctrl.save_freq_steps,
        )
        self._ckpt_ctl = FrequencyControl(
            freq_step=cfg.exp_ctrl.ckpt_freq_steps,
            freq_sec=cfg.exp_ctrl.ckpt_freq_secs,
        )
        self._stats_history: List[Dict[str, float]] = []

    # ---------------- setup ----------------

    def setup(self) -> None:
        from areal_tpu.base import monitor
        from areal_tpu.system.worker_base import WorkerControl

        # Lifecycle FSM endpoint (reference worker_base.py:474): the
        # launcher/operator can pause/resume/exit/status this worker
        # between training steps.
        self.ctrl = WorkerControl(
            self.cfg.experiment, self.cfg.trial, "master"
        )
        # Graceful drain (system/supervisor.py drain_experiment): dump a
        # recover checkpoint OUT-OF-BAND of the ckpt cadence. Served
        # between steps (and while paused), so no MFC is in flight when
        # it runs — the trainer RPC below is safe.
        self.ctrl.on_command("checkpoint", self._on_demand_ckpt)
        # The aggregator MUST exist before any worker's pusher looks for
        # it, and before the master's own telemetry configures — so it is
        # the first telemetry object up. Disabled config: nothing starts.
        self._aggregator = None
        self._sentinel = None
        if self.cfg.telemetry.enabled:
            import os

            # Default next to the tensorboard stream (the log dir), per
            # the TelemetryConfig contract; the checkpoint dir is only
            # the last resort for bare configs with no tensorboard path.
            jsonl = self.cfg.telemetry.jsonl_path or os.path.join(
                os.path.dirname(self.cfg.tensorboard_path)
                if self.cfg.tensorboard_path else self.cfg.save_dir,
                "telemetry.jsonl",
            )
            if self.cfg.sentinel.enabled:
                # Training-health sentinel (docs/observability.md
                # §Alerting): hosted in the aggregator below — fed every
                # ingested snapshot, ticked from the ingest loop, no
                # threads of its own. alerts.jsonl and the evidence dir
                # default next to telemetry.jsonl.
                from areal_tpu.system.sentinel import (
                    Sentinel,
                    rules_from_config,
                )

                log_dir = os.path.dirname(jsonl) or "."
                self._sentinel = Sentinel(
                    self.cfg.sentinel, self.cfg.experiment, self.cfg.trial,
                    # The durability pack (sample_loss absence on spool
                    # acks) arms only alongside the durable spool — on a
                    # non-durable run the series never exists and an
                    # absence rule would false-fire.
                    rules=rules_from_config(
                        self.cfg.sentinel,
                        durability_enabled=self.cfg.durability.enabled,
                        # Same gating story for the compile/HBM pack: its
                        # series exist only with the observatory armed.
                        compile_watch_enabled=self.cfg.compile_watch.enabled,
                    ),
                    alerts_path=(self.cfg.sentinel.alerts_path
                                 or os.path.join(log_dir, "alerts.jsonl")),
                    evidence_dir=(self.cfg.sentinel.evidence_dir
                                  or os.path.join(log_dir, "evidence")),
                )
            goodput_stitcher = None
            if self.cfg.goodput.enabled:
                # Fleet goodput (docs/observability.md §Goodput): derived
                # from the worker ledgers' counters as they ingest; the
                # merged scrape gains the "fleet" pseudo-worker row.
                from areal_tpu.system.goodput import FleetGoodput

                goodput_stitcher = FleetGoodput()
            self._aggregator = telemetry.TelemetryAggregator(
                self.cfg.experiment, self.cfg.trial, jsonl_path=jsonl,
                http_port=self.cfg.telemetry.http_port,
                # Stitched sample-lineage traces (one line per trained
                # sample); defaults next to telemetry.jsonl.
                traces_path=self.cfg.telemetry.traces_path,
                stitch_grace_secs=self.cfg.telemetry.stitch_grace_secs,
                sentinel=self._sentinel,
                goodput=goodput_stitcher,
            )
            telemetry.configure(
                self.cfg.experiment, self.cfg.trial, "master", 0,
                self.cfg.telemetry,
            )
        self.stream = MasterRequestStream(
            self.cfg.experiment, self.cfg.trial, [self.cfg.trainer_handler]
        )
        self._model_info = self.stream.call(
            self.cfg.trainer_handler, "model_info", None
        )
        self._peak_flops = monitor.device_peak_flops(
            self._model_info.get("device_kind", "")
        )
        self._flops = monitor.FlopsCounter()
        self._writer = monitor.MetricWriter(
            tensorboard_path=self.cfg.tensorboard_path,
            wandb_mode=self.cfg.wandb_mode,
        )
        if self._aggregator is not None:
            # Mirror per-worker telemetry scalars into the same tensorboard
            # stream as the training stats (telemetry/{worker}/{metric}).
            self._aggregator.set_metric_writer(self._writer)
        if self.cfg.recover and self.cfg.recover_dir:
            self._try_recover()

    def _try_recover(self) -> None:
        """Resume from the latest recover checkpoint (reference
        master_worker.py:585 dump / recover.discover_ckpt)."""
        from areal_tpu.base import recover

        info = recover.load(self.cfg.recover_dir)
        ckpt = recover.discover_ckpt(self.cfg.recover_dir)
        if info is None or ckpt is None:
            logger.info("recover requested but no checkpoint found; "
                        "starting fresh")
            return
        self.step = info.last_step_info.global_step
        self.epoch = info.last_step_info.epoch
        if info.save_ctl_states.get("save"):
            self._save_ctl.load_state_dict(info.save_ctl_states["save"])
        if info.ckpt_ctl_states.get("ckpt"):
            self._ckpt_ctl.load_state_dict(info.ckpt_ctl_states["ckpt"])
        reply = self.stream.call(
            self.cfg.trainer_handler, "restore", {"dir": ckpt}
        )
        logger.info(
            f"recovered at step {self.step} epoch {self.epoch} from {ckpt} "
            f"(model versions: {reply.get('versions')})"
        )

    def _on_demand_ckpt(self, payload=None) -> Dict[str, Any]:
        if not self.cfg.recover_dir:
            return {"saved": False, "reason": "no recover_dir configured"}
        ckpt_dir = self._do_ckpt()
        return {"saved": True, "dir": ckpt_dir, "step": self.step,
                "epoch": self.epoch}

    def _do_ckpt(self) -> Optional[str]:
        from areal_tpu.base import recover

        if not self.cfg.recover_dir:
            return None
        name = recover.ckpt_dirname(self.epoch, self.step, self.step)
        ckpt_dir = f"{self.cfg.recover_dir}/{name}"
        self.stream.call(self.cfg.trainer_handler, "ckpt", {"dir": ckpt_dir})
        # Terminal sentinel AFTER the trainer acked the save: a crash
        # mid-save leaves the dir incomplete and discover_ckpt skips it.
        recover.mark_ckpt_complete(ckpt_dir)
        si = recover.StepInfo(self.epoch, self.step, self.step)
        recover.dump(self.cfg.recover_dir, recover.RecoverInfo(
            recover_start=si, last_step_info=si,
            # Frequency-controller states: without them a recovered run
            # re-anchors its save/ckpt cadence at the restart point
            # (reference RecoverInfo.save_ctl_states, recover.py:26).
            save_ctl_states={"save": self._save_ctl.state_dict()},
            ckpt_ctl_states={"ckpt": self._ckpt_ctl.state_dict()},
        ))
        # GC old recover ckpts (they are large: params + optimizer state).
        import os
        import shutil

        entries = []
        for n in os.listdir(self.cfg.recover_dir):
            st = recover.parse_ckpt_dirname(n)
            if st is not None:
                entries.append((st.global_step, n))
        for _, n in sorted(entries)[: -self.cfg.keep_recover_ckpts]:
            shutil.rmtree(f"{self.cfg.recover_dir}/{n}", ignore_errors=True)
        return ckpt_dir

    def _count_mfc_flops(self, node: MFCDef, metas: List[SequenceSample]) -> None:
        """Analytic FLOPs for one MFC from input metadata (lengths only)."""
        info = self._model_info.get("roles", {}).get(node.model_name)
        if info is None or not metas:
            return
        # The MAIN token key: seqlens also carries scalar keys (rewards:
        # [[1]] per sample), and taking whichever comes first understated
        # the FLOPs ~170x on the first chip run (48 "tokens" for 8.1k).
        lens = [int(m.total_lens()[0]) for m in metas]
        n_tokens = float(sum(lens))
        avg = n_tokens / max(len(lens), 1)

        moe_info = info.get("moe")

        class _C:  # adapter: monitor formulas take config-like fields
            n_layers = info["n_layers"]
            hidden_dim = info["hidden_dim"]
            q_dim = info["q_dim"]
            kv_dim = info["kv_dim"]
            intermediate_dim = info["intermediate_dim"]
            vocab_size = info["vocab_size"]
            is_critic = info["is_critic"]
            # Activated-compute geometry: monitor switches the MLP term
            # to top_k routed + shared expert when this is set.
            moe = (
                None if moe_info is None
                else SimpleNamespace(**moe_info)
            )

        if node.interface_type == MFCInterfaceType.TRAIN_STEP:
            self._flops.add_train(
                _C, n_tokens, avg, remat=info.get("remat", False)
            )
        else:
            self._flops.add_inf(_C, n_tokens, avg)

    # ---------------- per-step DFG traversal ----------------

    async def _load_data(self) -> None:
        """Fill one step's batch from the trainer's dataset/stream.

        Stream mode may return PARTIAL (or empty) fetches — keep fetching
        until train_batch_size samples landed in the buffer; a single
        partial fetch treated as complete deadlocks every MFC gate
        (n_seqs never satisfied) while the trainer sits idle."""
        got = 0
        while got < self.cfg.train_batch_size:
            reply = await asyncio.to_thread(
                self.stream.call, self.cfg.trainer_handler, "fetch",
                self.cfg.train_batch_size - got,
            )
            self.epoch = reply["epoch"]
            self._dataset_size = reply["dataset_size"]
            meta: Optional[SequenceSample] = reply["meta"]
            if meta is None or meta.bs == 0:
                await asyncio.sleep(0.2)
                continue
            singles = [meta.select_idx([i]) for i in range(meta.bs)]
            await self.buffer.put_batch(singles)
            got += meta.bs

    def _hook_dicts(self, node: MFCDef, post: bool) -> List[Dict]:
        out = []
        for h in node.post_hooks if post else node.pre_hooks:
            if isinstance(h, WeightUpdateHook):
                out.append({"kind": "weight_update", "role": h.role})
            elif isinstance(h, ParamReallocHook):
                out.append({
                    "kind": "param_realloc", "source": h.source,
                    "target": h.target, "eta": h.eta,
                })
        return out

    async def _run_mfc(self, node: MFCDef) -> None:
        with telemetry.span("master/mfc_gate", mfc=node.name):
            metas = await self.buffer.get_batch_for_rpc(
                node.name, set(node.input_keys), node.n_seqs
            )
        t_mfc = time.monotonic()
        self._count_mfc_flops(node, metas)
        ids = [m.ids[0] for m in metas]
        payload = Payload(
            handler=self.cfg.trainer_handler,
            handle_name="mfc",
            data={
                "mfc": node.name,
                "ids": ids,
                "method": node.interface_type.value,
                "input_keys": list(node.input_keys),
                "input_remap": node.input_key_remap,
                "output_remap": node.output_key_remap,
            },
            mb_spec=node.mb_spec,
            pre_hooks=self._hook_dicts(node, post=False),
            post_hooks=self._hook_dicts(node, post=True),
        )
        rid = self.stream.post(payload)
        with telemetry.span("master/mfc_exec", mfc=node.name,
                            n_seqs=len(ids)):
            reply = (await asyncio.to_thread(self.stream.gather, [rid]))[0]
        out = reply.output
        if node.interface_type == MFCInterfaceType.TRAIN_STEP:
            if out["stats"]:
                self.stats.scalar(**{
                    f"{node.name}/{k}": v for k, v in out["stats"].items()
                })
        elif node.interface_type == MFCInterfaceType.GENERATE:
            # Trajectories replace the prompt slots (flattened groups).
            new_meta: SequenceSample = out["meta"]
            await self.buffer.drop_ids(ids)
            singles = [new_meta.select_idx([i]) for i in range(new_meta.bs)]
            await self.buffer.put_batch(singles)
            await self.buffer.mark_read(
                [s.ids[0] for s in singles], node.name
            )
        else:
            if out["meta"] is not None:
                await self.buffer.amend_batch(out["meta"])
        self.stats.scalar(**{
            f"timeperf/{node.name}": time.monotonic() - t_mfc
        })

    async def _execute_step(self) -> None:
        tasks = [self._load_data()]
        tasks += [self._run_mfc(n) for n in self.dfg.nodes.values()]
        await asyncio.gather(*tasks)

    # ---------------- main loop ----------------

    def should_stop(self) -> bool:
        ctrl = self.cfg.exp_ctrl
        if ctrl.benchmark_steps is not None and self.step >= ctrl.benchmark_steps:
            return True
        return self.epoch >= ctrl.total_train_epochs

    def run(self) -> Dict[str, Any]:
        # One event loop for the whole experiment: the buffer's asyncio
        # primitives bind to the loop that first touches them.
        return asyncio.run(self._run_async())

    async def _run_async(self) -> Dict[str, Any]:
        self.setup()
        t_start = time.monotonic()
        while not self.should_stop():
            # Serve the control channel between steps; pause blocks here.
            await asyncio.to_thread(
                self.ctrl.step,
                lambda: {"step": self.step, "epoch": self.epoch},
            )
            if self.ctrl.should_exit:
                logger.info("master: exit requested via control channel")
                break
            t0 = time.monotonic()
            with telemetry.span("master/step", step=self.step):
                await self._execute_step()
            self.step += 1
            step_stats = self.stats.export(reset=True)
            dt = time.monotonic() - t0
            step_stats["timeperf/e2e"] = dt
            # Analytic TFLOP/s per chip + MFU (reference master_worker.py:497
            # tabulates the FlopsCounter the same way).
            n_chips = max(self._model_info.get("n_devices", 1), 1)
            flops = self._flops.pop()
            if flops > 0:
                per_chip = flops / dt / n_chips
                step_stats["timeperf/tflops_per_chip"] = per_chip / 1e12
                if self._peak_flops:
                    step_stats["timeperf/mfu"] = per_chip / self._peak_flops
            self._stats_history.append(step_stats)
            self._writer.write(step_stats, self.step)
            # Step wall time on the scrape (throughput-regression rules)
            # and a DIRECT sentinel feed: the master hosts the engine
            # in-process, so its per-step series skip the flush latency
            # every other worker's snapshots pay. Feed only — rule
            # evaluation (and its evidence-capture I/O) belongs to the
            # aggregator's ingest thread, never the step loop.
            telemetry.set_gauge("master/step_secs", dt)
            if self._sentinel is not None:
                # Same "kind:index" identity the flushed copy arrives
                # under, so the direct feed and the aggregator ingest
                # share ONE source slot instead of double-counting.
                self._sentinel.feed("master:0", {
                    "master/step_secs": dt,
                    "master/step": float(self.step),
                })
            logger.info(
                f"step {self.step} epoch {self.epoch} "
                f"({step_stats['timeperf/e2e']:.2f}s): "
                + " ".join(
                    f"{k}={v:.4g}" for k, v in sorted(step_stats.items())
                    if "/" in k
                )
            )
            if self._save_ctl.check(epochs=self.epoch, steps=self.step):
                await asyncio.to_thread(self._request_save)
            if self._ckpt_ctl.check(epochs=self.epoch, steps=self.step):
                await asyncio.to_thread(self._do_ckpt)
            # post-step GC: tell the trainer which samples were fully
            # consumed so its tensor store can drop them.
            freed = await self.buffer.pop_freed()
            await asyncio.to_thread(
                self.stream.call, self.cfg.trainer_handler, "clear", freed
            )
        total = time.monotonic() - t_start
        logger.info(f"experiment complete: {self.step} steps in {total:.1f}s")
        # Published BEFORE the trainer is told to exit: the launcher's
        # supervisor consults this (timestamped) marker when it sees a
        # child die, so the commanded end-of-run trainer exit is never
        # classified as a stateful-worker death and escalated while this
        # thread is still in its teardown tail.
        try:
            import json as _json

            from areal_tpu.base import name_resolve, names
            name_resolve.add(
                names.experiment_status(self.cfg.experiment, self.cfg.trial),
                _json.dumps({"status": "finishing", "ts": time.time()}),
                replace=True, delete_on_exit=False,
            )
        except Exception:  # noqa: BLE001 — marker is advisory
            pass
        await asyncio.to_thread(
            self.stream.call, self.cfg.trainer_handler, "exit"
        )
        telemetry.shutdown()  # final master flush into the aggregator
        if self._aggregator is not None:
            self._aggregator.close()
        self._writer.close()
        self.ctrl.close()
        return {"steps": self.step, "stats": self._stats_history}

    def _request_save(self) -> None:
        rids = [
            self.stream.post(Payload(
                handler=self.cfg.trainer_handler, handle_name="mfc",
                data={"mfc": node.name, "ids": [], "method": "noop"},
                post_hooks=[{
                    "kind": "save", "role": node.model_name,
                    "path": f"{self.cfg.save_dir}/{node.model_name}/step{self.step}",
                }],
            ))
            for node in self._train_nodes
        ]
        self.stream.gather(rids)
