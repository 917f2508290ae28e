"""chip_smoke.py — the quickest proof that areal_tpu still starts on the chip.

    python chip_smoke.py            # one chip: sync PPO + the generation fleet
    python chip_smoke.py --chips 4  # one 2x2 host: async PPO + f2-vs-one-device

Drives the system's main path once through the entry points a user calls, at
the published widths of Qwen2.5-0.5B (24 layers, hidden 896, 14 q / 2 kv
heads of 64, FFN 4864, vocab 151,936, rope base 1e6, tied embeddings, qkv
bias; ``--layers`` cuts DEPTH only). No network and no model files are
needed: the checkpoint is fabricated from ``--seed`` with the repo's own
codec, the prompts with ``base/testing.make_math_jsonl``.

Output contract: the LAST line on stdout is one JSON object with exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

(``"ok": false`` and a non-zero exit on ANY failure: no TPU, a phase's
non-zero exit, a non-finite loss, a timeout). Everything else — one JSON
record per phase — goes on EARLIER stdout lines; the children's own output
goes to log files under ``.chip_smoke/`` and, when a phase fails, to stderr.

This process never imports jax: a chip belongs to one process, so every
phase is a child process tree, one at a time, reaped before the next
starts, and the device is read from what the device-owning children report.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
SELF = os.path.abspath(__file__)  # what the phase children re-execute
WORK = os.path.join(HERE, ".chip_smoke")
# What every device-owning worker must report, and what the train step's
# attention must trace to. Constants, not options: only a CPU rehearsal
# script (which re-executes itself through SELF) ever overrides them.
PLATFORM, KERNEL = "tpu", "pallas"
# The contract allows 1200 s, compilation included; leave room to reap.
DEADLINE_SECS = 1150.0

# Qwen2.5-0.5B, config.json of Qwen/Qwen2.5-0.5B on the Hugging Face hub.
QWEN25_05B = dict(
    n_layers=24, hidden_dim=896, n_q_heads=14, n_kv_heads=2, head_dim=64,
    intermediate_dim=4864, vocab_size=151936, rotary_base=1e6,
    rms_norm_eps=1e-6, tie_word_embeddings=True, use_attention_bias=True,
    max_position_embeddings=32768, hf_family="qwen2", dtype="bfloat16",
)

# PPO shape: 12 prompts x group 4 = 48 trajectories of 13..15 prompt + 155
# generated tokens (min_new_tokens pins the length), which the packer lays
# three to a row into two [8, 512] micro-batches at 0.99 fill — packed
# 128-multiple rows with several documents each, what the attention
# kernel tiles and masks.
N_PROMPTS, GROUP, NEW_TOKENS, TRAIN_STEPS = 12, 4, 155, 3
# generation phase: 2 prompts x group 4, 96 new tokens in 32-token chunks.
GEN_PROMPTS, GEN_NEW_TOKENS, GEN_CHUNK = 2, 96, 32


def final_line(ok: bool, device: Optional[Dict[str, Any]]) -> str:
    """The contract's last stdout line — these keys and no others."""
    d = device or {}
    return json.dumps({
        "ok": bool(ok),
        "device": {
            "platform": d.get("platform"),
            "kind": d.get("kind"),
            "count": d.get("count"),
        },
    })


class PhaseFailed(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# parent side: run children, read their reports
# ---------------------------------------------------------------------------


class Runner:
    """Starts each child in its own session, so the whole tree (launcher
    workers, compilers) can be killed; keeps one global deadline."""

    def __init__(self) -> None:
        self.t0 = time.monotonic()
        self.live: List[subprocess.Popen] = []

    def time_left(self) -> float:
        return DEADLINE_SECS - (time.monotonic() - self.t0)

    def start(self, name: str, cmd: List[str], env: Dict[str, str],
              ) -> subprocess.Popen:
        log = open(os.path.join(WORK, f"{name}.log"), "w")
        try:
            p = subprocess.Popen(
                cmd, cwd=HERE, env=env, stdin=subprocess.DEVNULL,
                stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True,
            )
        finally:
            log.close()  # the child holds its own descriptor
        p.phase_name = name  # type: ignore[attr-defined]
        self.live.append(p)
        return p

    def wait(self, p: subprocess.Popen, cap_secs: float) -> str:
        """Reap ``p`` (and its tree); return its log. Raises PhaseFailed on
        a timeout or a non-zero exit, after echoing the log's tail."""
        name = p.phase_name  # type: ignore[attr-defined]
        try:
            rc = p.wait(timeout=max(min(cap_secs, self.time_left()), 1.0))
            why = f"exit code {rc}" if rc != 0 else None
        except subprocess.TimeoutExpired:
            why = "timeout"
        finally:
            self.kill(p)
        with open(os.path.join(WORK, f"{name}.log"), errors="replace") as f:
            log = f.read()
        if why is not None:
            sys.stderr.write(f"---- {name}: {why}; log tail ----\n"
                             f"{log[-6000:]}\n")
            raise PhaseFailed(f"{name}: {why}")
        return log

    def kill(self, p: subprocess.Popen) -> None:
        """SIGKILL the child's whole session and reap it."""
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        p.wait()
        if p in self.live:
            self.live.remove(p)

    def kill_all(self) -> None:
        for p in list(self.live):
            self.kill(p)


def child_env(cpu: bool = False) -> Dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def self_cmd(phase: str, args: argparse.Namespace) -> List[str]:
    return [sys.executable, SELF, "--phase", phase,
            "--seed", str(args.seed), "--layers", str(args.layers),
            "--chips", str(args.chips)]


def tagged_json(log: str, tag: str) -> List[Dict[str, Any]]:
    """Every ``<tag>{json}`` record a child wrote (log lines carry a
    logger prefix before the tag)."""
    out = []
    for line in log.splitlines():
        i = line.find(tag)
        if i >= 0:
            out.append(json.loads(line[i + len(tag):]))
    return out


def emit(record: Dict[str, Any]) -> None:
    print(json.dumps(record), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def entry_args(trial: str, ckpt: str, data: str, chips: int) -> List[str]:
    """key=value overrides shared by the sync and async entry scripts —
    what a user passes, with the mock tokenizer because the machine has
    no tokenizer files."""
    return [
        "--backend=tpu",
        "experiment_name=chipsmoke", f"trial_name={trial}",
        f"cluster.fileroot={WORK}/exps",
        "mock_tokenizer=true", f"n_gpus_per_node={chips}",
        f"actor.path={ckpt}", f"ref.path={ckpt}", f"dataset.path={data}",
        f"dataset.train_bs_n_seqs={N_PROMPTS}", "dataset.max_prompt_len=64",
        f"group_size={GROUP}",
        f"ppo.gen.max_new_tokens={NEW_TOKENS}",
        f"ppo.gen.min_new_tokens={NEW_TOKENS}",
        "ppo.ppo_n_minibatches=2", "ppo.kl_ctl=0.05",
        "ppo.disable_value=true",
        "actor_train.mb_spec.max_tokens_per_mb=4096",
        "ref_inf.mb_spec.max_tokens_per_mb=4096",
        f"exp_ctrl.benchmark_steps={TRAIN_STEPS}",
        "exp_ctrl.total_train_epochs=1000000",
    ]


def step_stats(log: str) -> List[Dict[str, float]]:
    """The master's per-step log lines → [{stat: value}]."""
    return [
        {k: float(v) for k, v in
         (item.split("=", 1) for item in m.group(1).split())}
        for m in re.finditer(
            r"system\.master INFO: step \d+ epoch \d+ \([\d.]+s\): (.*)",
            log)
    ]


def check_trainer(log: str, name: str, n_devices: int,
                  device: Dict[str, Any]) -> Dict[str, Any]:
    """Pass criteria common to the sync and async trainers; returns the
    phase record."""
    check(f"experiment finished: steps={TRAIN_STEPS}" in log,
          f"{name}: no 'experiment finished: steps={TRAIN_STEPS}'")
    steps = step_stats(log)
    check(len(steps) == TRAIN_STEPS, f"{name}: {len(steps)} step lines")
    for i, st in enumerate(steps):
        for key in ("actor_train/actor_loss", "actor_train/grad_norm"):
            check(key in st and math.isfinite(st[key]),
                  f"{name}: step {i + 1} {key} = {st.get(key)}")
        check(st["actor_train/grad_norm"] > 0,
              f"{name}: step {i + 1} grad_norm is zero")
    reports = [r for r in tagged_json(log, "device_report ")
               if r["worker"].startswith("trainer")]
    check(len(reports) == 2, f"{name}: {len(reports)} trainer reports")
    last = reports[-1]
    check(last["platform"] == PLATFORM
          and last["device_kind"] == device["kind"],
          f"{name}: trainer ran on {last['platform']} "
          f"{last['device_kind']}")
    check(last["device_count"] == n_devices,
          f"{name}: trainer saw {last['device_count']} devices")
    train = last["attention"].get("train", {})
    check(set(train) == {KERNEL},
          f"{name}: train step attention traced to {train} — the Pallas "
          "kernel must be in it and the reference must not")
    return {
        "phase": name, "ok": True,
        "device": {k: last[k] for k in
                   ("platform", "device_kind", "device_count")},
        "steps": [
            {k.split("/", 1)[1]: st[k] for k in (
                "actor_train/actor_loss", "actor_train/grad_norm",
                "actor_train/importance_weight", "actor_train/mean_kl",
                "actor_train/n_action_tokens", "timeperf/e2e",
            ) if k in st} for st in steps
        ],
        "attention": last["attention"],
        "hbm_peak_bytes": [d["peak_bytes_in_use"]
                           for d in last["local_devices"]],
        "compile_cache": last["compile_cache"],
        "native_ops": last["native_ops"],
    }


def run_default(r: Runner, args: argparse.Namespace,
                device: Dict[str, Any], ckpt: str, data: str) -> None:
    t = time.monotonic()
    p = r.start("sync_ppo", [
        sys.executable, os.path.join(HERE, "training", "main_sync_ppo.py"),
        *entry_args("sync", ckpt, data, 1), "allocation_mode=d1",
    ], child_env())
    rec = check_trainer(r.wait(p, 800), "sync_ppo", 1, device)
    first = rec["steps"][0]
    # Same weights generated and scored the step-1 batch: the decode path
    # (KV cache, XLA attention) and the packed train path (Pallas kernel)
    # must agree — importance ratio 1, actor-vs-ref KL 0.
    check(abs(first["importance_weight"] - 1.0) < 0.05,
          f"sync_ppo: step-1 importance weight {first['importance_weight']}")
    check(abs(first["mean_kl"]) < 0.05,
          f"sync_ppo: step-1 actor/ref KL {first['mean_kl']}")
    emit({**rec, "wall_secs": round(time.monotonic() - t, 1)})

    t = time.monotonic()
    p = r.start("gen_server", self_cmd("gen_server", args), child_env())
    log = r.wait(p, 500)
    (rec,) = tagged_json(log, "phase_result ")
    check(rec["device"]["platform"] == PLATFORM
          and rec["device"]["device_kind"] == device["kind"],
          f"gen_server ran on {rec['device']}")
    emit({**rec, "wall_secs": round(time.monotonic() - t, 1)})


def run_four_chips(r: Runner, args: argparse.Namespace,
                   device: Dict[str, Any], ckpt: str, data: str) -> None:
    t = time.monotonic()
    p = r.start("async_ppo", [
        sys.executable, os.path.join(HERE, "training", "main_async_ppo.py"),
        *entry_args("async", ckpt, data, 4), "allocation_mode=gen.d2+f2",
        "max_head_offpolicyness=4", "max_concurrent_rollouts=16",
        "new_tokens_per_chunk=64", "gen_prompt_bucket=64",
    ], child_env())
    log = r.wait(p, 900)
    rec = check_trainer(log, "async_ppo", 2, device)
    (fleet,) = [x for x in tagged_json(log, "device_report ")
                if x["worker"] == "gen_fleet"]
    check(fleet["platform"] == PLATFORM and fleet["device_count"] == 2,
          f"async_ppo: generation fleet got {fleet['platform']} x "
          f"{fleet['device_count']}")
    (trainer_up, _) = [x for x in tagged_json(log, "device_report ")
                       if x["worker"].startswith("trainer")]
    resident = [d["bytes_in_use"] for d in trainer_up["local_devices"]
                + fleet["local_devices"]]
    check(len(resident) == 4 and all(b and b > 1 << 20 for b in resident),
          f"async_ppo: weights not resident on all four chips: {resident}")
    synced = [ln for ln in log.splitlines() if "weight sync v" in ln]
    check(bool(synced), "async_ppo: no weight version reached the servers")
    emit({**rec, "gen_fleet": fleet, "weights_resident_bytes": resident,
          "weight_syncs": len(synced),
          "weight_transport": "stream (the launcher's default)",
          "wall_secs": round(time.monotonic() - t, 1)})

    t = time.monotonic()
    p = r.start("mesh_compare", self_cmd("mesh_compare", args), child_env())
    (rec,) = tagged_json(r.wait(p, 500), "phase_result ")
    emit({**rec, "wall_secs": round(time.monotonic() - t, 1)})


def main_parent(args: argparse.Namespace) -> int:
    device: Optional[Dict[str, Any]] = None
    ok = False
    r = Runner()
    try:
        try:
            from areal_tpu.base.testing import make_math_jsonl
        except ImportError as e:
            raise PhaseFailed(f"the repository is not beside this script "
                              f"({e})") from None
        shutil.rmtree(WORK, ignore_errors=True)
        os.makedirs(WORK)
        # Fabricate on the CPU while the probe holds the chip.
        fab = r.start("fabricate", self_cmd("fabricate", args),
                      child_env(cpu=True))
        probe = r.start("probe", self_cmd("probe", args), child_env())
        (device,) = tagged_json(r.wait(probe, 300), "phase_result ")
        emit({"phase": "probe", "device": device})
        check(device["platform"] == PLATFORM,
              f"JAX found no TPU: {device}")
        check(device["count"] == args.chips,
              f"--chips {args.chips} but JAX reports {device['count']}")
        data = os.path.join(WORK, "prompts.jsonl")
        make_math_jsonl(data, n=64, seed=args.seed)
        (fab_rec,) = tagged_json(r.wait(fab, 400), "phase_result ")
        emit(fab_rec)
        run = run_default if args.chips == 1 else run_four_chips
        run(r, args, device, fab_rec["checkpoint"], data)
        ok = True
    except PhaseFailed as e:
        emit({"phase": "failed", "ok": False, "error": str(e)})
    finally:
        # Nothing may reach stdout after the final line: reap everything
        # first. BaseException (interrupt, a bug here) still ends in a
        # well-formed ok=false line; it then propagates.
        r.kill_all()
        if ok:  # keep the (small) phase logs, drop the gigabytes
            for big in ("ckpt", "exps"):
                shutil.rmtree(os.path.join(WORK, big), ignore_errors=True)
        print(final_line(ok, device), flush=True)
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# child phases (each its own process; these import jax)
# ---------------------------------------------------------------------------


def phase_result(record: Dict[str, Any]) -> None:
    print("phase_result " + json.dumps(record), flush=True)


def model_config(layers: int):
    from areal_tpu.models.config import TransformerConfig

    return TransformerConfig(**{**QWEN25_05B, "n_layers": layers})


def phase_probe(args: argparse.Namespace) -> None:
    import jax

    d = jax.devices()
    phase_result({"platform": d[0].platform, "kind": d[0].device_kind,
                  "count": len(d)})


def phase_fabricate(args: argparse.Namespace) -> None:
    """Random weights from --seed at the published widths, saved with the
    repo's own HF codec so the entry scripts load them like any checkpoint
    (CPU-pinned: the parent exports JAX_PLATFORMS=cpu)."""
    import jax

    from areal_tpu.models import hf, transformer

    t = time.monotonic()
    cfg = model_config(args.layers)
    params = transformer.init_params(cfg, jax.random.PRNGKey(args.seed))
    ckpt = os.path.join(WORK, "ckpt")
    hf.save_hf_checkpoint(jax.device_get(params), cfg, ckpt)
    phase_result({
        "phase": "fabricate", "ok": True, "checkpoint": ckpt,
        "model": "Qwen2.5-0.5B widths", "n_layers": cfg.n_layers,
        "depth_cut": (None if cfg.n_layers == QWEN25_05B["n_layers"] else
                      f"{QWEN25_05B['n_layers']} -> {cfg.n_layers} layers"),
        "n_params": transformer.param_count(cfg), "seed": args.seed,
        "wall_secs": round(time.monotonic() - t, 1),
    })


def phase_gen_server(args: argparse.Namespace) -> None:
    """The generation fleet through its normal worker entry, driven over
    HTTP by this CPU-pinned client: chunked /generate through the manager,
    one streamed weight bump, one more request checked against a float32
    CPU reference of the published weights."""
    import asyncio
    import dataclasses
    import multiprocessing as mp

    import aiohttp
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.api import cli_args as CA
    from areal_tpu.api.model import GenerationHyperparameters
    from areal_tpu.apps import launcher
    from areal_tpu.base import name_resolve, names
    from areal_tpu.base.testing import MockTokenizer
    from areal_tpu.datasets.jsonl import load_jsonl
    from areal_tpu.experiments import common as C
    from areal_tpu.experiments.async_ppo_math_exp import AsyncPPOMATHConfig
    from areal_tpu.models import transformer
    from areal_tpu.models.hf import flatten_pytree
    from areal_tpu.ops.xent import gather_logprobs
    from areal_tpu.system.partial_rollout import PartialRolloutClient
    from areal_tpu.system.weight_stream import WeightStreamPublisher

    # In-process only: the fleet this client spawns inherits the
    # environment untouched and takes the chip.
    jax.config.update("jax_platforms", "cpu")
    ckpt = os.path.join(WORK, "ckpt")
    exp = CA.apply_overrides(AsyncPPOMATHConfig(), [
        "experiment_name=chipsmoke", "trial_name=gen",
        f"cluster.fileroot={WORK}/exps", "mock_tokenizer=true",
        "n_gpus_per_node=1", "allocation_mode=d1",
        f"actor.path={ckpt}", f"dataset.path={WORK}/prompts.jsonl",
        f"new_tokens_per_chunk={GEN_CHUNK}", "gen_prompt_bucket=64",
        # Wide enough that the requests sent together decode together: the
        # server compiles one program per distinct batch row count.
        "gen_batch_window_ms=200",
    ])
    CA.validate_config(exp)
    C.setup_name_resolve(exp)
    setup = exp.initial_setup()
    fleet = mp.get_context("spawn").Process(
        target=launcher.gen_fleet_entry,
        args=(exp, setup["gen_servers"], setup["gserver_manager"]),
    )
    fleet.start()
    publisher = None
    try:
        mgr_key = names.gen_server_manager(exp.experiment_name,
                                           exp.trial_name)
        while True:
            try:
                mgr_url = name_resolve.get(mgr_key)
                break
            except name_resolve.NameEntryNotFoundError:
                check(fleet.is_alive(), "gen_server: the fleet died while "
                                        f"starting (exit {fleet.exitcode})")
                time.sleep(0.5)
        (srv_url,) = name_resolve.get_subtree(
            names.gen_server_root(exp.experiment_name, exp.trial_name)
        )
        tok = MockTokenizer()
        prompts = [tok.encode(rec["prompt"]) for rec in
                   load_jsonl(f"{WORK}/prompts.jsonl")[:GEN_PROMPTS]]
        sampled = GenerationHyperparameters(max_new_tokens=GEN_NEW_TOKENS)
        greedy = dataclasses.replace(sampled, greedy=True)

        def check_results(results, version, n_tokens):
            for res in results:
                check(len(res.output_ids) == n_tokens
                      or tok.eos_token_id in res.output_ids,
                      f"gen_server: {len(res.output_ids)} tokens returned")
                lps = np.asarray(res.output_logprobs)
                check(lps.shape == (len(res.output_ids),)
                      and bool(np.isfinite(lps).all())
                      and bool((lps <= 0).all()),
                      "gen_server: logprobs not finite and <= 0")
                check((res.version_start, res.version_end)
                      == (version, version),
                      f"gen_server: version tags {res.version_start}.."
                      f"{res.version_end}, expected {version}")

        async def drive():
            async with aiohttp.ClientSession() as session:
                client = PartialRolloutClient(mgr_url, session,
                                              chunk_tokens=GEN_CHUNK)
                groups = await asyncio.gather(*[
                    client.generate_group(p, sampled, GROUP) for p in prompts
                ])
                flat = [res for g in groups for res in g]
                check_results(flat, 0, GEN_NEW_TOKENS)
                check(all(res.n_chunks >= GEN_NEW_TOKENS // GEN_CHUNK
                          for res in flat), "gen_server: not chunked")
                before = await client.generate_one(prompts[0], greedy)
                check_results([before], 0, GEN_NEW_TOKENS)

                # Weight bump: other weights (seed + 1), published the
                # way the trainer publishes — bf16 over the streamed
                # transport, then the version key the manager watches,
                # which fans POST /update_weights out to the servers.
                cfg = model_config(args.layers)
                new = transformer.init_params(
                    cfg, jax.random.PRNGKey(args.seed + 1)
                )
                nonlocal publisher
                publisher = WeightStreamPublisher(
                    exp.experiment_name, exp.trial_name, "actor"
                )
                publisher.publish(
                    sorted(flatten_pytree(jax.device_get(new)).items()), 1
                )
                name_resolve.add(
                    names.model_version(exp.experiment_name, exp.trial_name,
                                        "actor"), "1", replace=True,
                )
                deadline = time.monotonic() + 300
                while True:
                    async with session.get(f"{srv_url}/health") as resp:
                        if (await resp.json())["version"] == 1:
                            break
                    check(time.monotonic() < deadline,
                          "gen_server: the weight bump never landed")
                    await asyncio.sleep(0.5)
                after = await client.generate_one(prompts[0], greedy)
                check_results([after], 1, GEN_NEW_TOKENS)
                check(after.output_ids != before.output_ids,
                      "gen_server: same greedy tokens after the bump")

                # Reference: the chip's logprobs of its own tokens against
                # a float32 forward of the same (published) weights here.
                seq = np.asarray([prompts[0] + after.output_ids], np.int32)
                T = seq.shape[1]
                f32 = jax.tree.map(lambda x: x.astype(jnp.float32), new)
                logits, _ = transformer.forward(
                    f32, cfg, jnp.asarray(seq), jnp.arange(T)[None, :],
                    segment_ids=jnp.ones((1, T), jnp.int32),
                    attn_impl="reference",
                )
                ref = np.asarray(gather_logprobs(
                    logits[:, :-1], jnp.asarray(seq[:, 1:])
                ))[0, len(prompts[0]) - 1:]
                err = float(np.abs(
                    ref - np.asarray(after.output_logprobs)).max())
                check(err < 0.2, f"gen_server: logprobs differ from the "
                                 f"float32 reference by {err}")
                async with session.get(f"{srv_url}/metrics.json") as resp:
                    metrics = await resp.json()
                return flat, err, metrics

        flat, err, metrics = asyncio.run(drive())
        dev = metrics.pop("device")
        check(dev["platform"] == PLATFORM, f"gen_server: server on {dev}")
        phase_result({
            "phase": "gen_server", "ok": True,
            "device": {k: dev[k] for k in
                       ("platform", "device_kind", "device_count")},
            "requests": len(flat) + 2,
            "tokens_returned": sum(len(x.output_ids) for x in flat)
            + 2 * GEN_NEW_TOKENS,
            "chunks_per_request": flat[0].n_chunks,
            "versions": [0, 1],
            "max_abs_logprob_err_vs_f32_reference": err,
            "hbm_peak_bytes": dev["hbm_peak_bytes"],
            "attention": dev["attention"],
            "compile_cache": {k: v for k, v in dev["compile_cache"].items()
                              if k != "spans"},
            "server_metrics": {k: metrics[k] for k in (
                "generated_tokens", "prefill_tokens", "compiled_shapes",
                "last_weight_update_latency_s")},
        })
    finally:
        if publisher is not None:
            publisher.close()
        fleet.terminate()
        fleet.join(20)
        if fleet.is_alive():
            fleet.kill()
            fleet.join()


def phase_mesh_compare(args: argparse.Namespace) -> None:
    """What the async trainer's f2 mesh is compared with: the first PPO
    train step on the same batch and weights on ONE device. Loss and
    grad-norm must agree to bf16 tolerance."""
    import jax
    import numpy as np

    from areal_tpu.algorithms.ppo import (
        PPOActorInterface,
        PPOHyperparameters,
    )
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model import FinetuneSpec, Model
    from areal_tpu.backend.jax_train import JaxTrainBackend
    from areal_tpu.base import monitor
    from areal_tpu.base.compile_watch import enable_compilation_cache
    from areal_tpu.models import hf
    from areal_tpu.parallel import mesh as pmesh

    enable_compilation_cache()
    check(jax.default_backend() == PLATFORM and jax.device_count() == 4,
          f"mesh_compare needs the four-chip host, got {jax.devices()}")
    cfg, params = hf.load_hf_checkpoint(os.path.join(WORK, "ckpt"))
    rng = np.random.RandomState(args.seed)
    n_seq = N_PROMPTS * GROUP
    plens = rng.randint(13, 16, N_PROMPTS).repeat(GROUP)
    glens = np.full(n_seq, NEW_TOKENS)
    seqlens = (plens + glens).astype(int)
    batch = SequenceSample.from_default(
        ids=[f"m{i}" for i in range(n_seq)],
        data={
            "packed_input_ids": rng.randint(
                2, cfg.vocab_size, int(seqlens.sum())).astype(np.int32),
            "prompt_mask": np.concatenate([
                np.concatenate([np.ones(p, np.int32), np.zeros(g, np.int32)])
                for p, g in zip(plens, glens)]),
            "packed_logprobs": np.concatenate([
                np.concatenate([np.zeros(p, np.float32),
                                np.full(g, -11.9, np.float32)])
                for p, g in zip(plens, glens)]),
            "rewards": rng.rand(n_seq).astype(np.float32),
            "seq_no_eos_mask": np.ones(n_seq, np.float32),
        },
        seqlens=seqlens.tolist(),
    )
    spec = MicroBatchSpec(max_tokens_per_mb=4096)
    hp = PPOHyperparameters(ppo_n_minibatches=1, kl_ctl=0.0,
                            disable_value=True)

    def first_step(mesh):
        model = JaxTrainBackend(mesh=mesh, remat=True).initialize(
            Model("actor", (cfg, params)), FinetuneSpec(1, 64, 8)
        )
        stats = PPOActorInterface(hp).train_step(model, batch, spec)
        placed = sorted(
            d.id for d in
            jax.tree_util.tree_leaves(model.module.params)[0].devices()
        )
        return stats, placed

    f2 = pmesh.make_mesh(pmesh.ParallelSpec.parse("f2"))
    coords = [list(getattr(d, "coords", ())) for d in f2.devices.flatten()]
    s_mesh, on_mesh = first_step(f2)
    s_one, on_one = first_step(None)
    rel = {k: abs(s_mesh[k] - s_one[k]) / max(abs(s_one[k]), 1e-6)
           for k in ("actor_loss", "grad_norm")}
    check(all(math.isfinite(s_mesh[k]) and math.isfinite(s_one[k])
              for k in rel), f"mesh_compare: non-finite {s_mesh} {s_one}")
    check(all(v < 2e-2 for v in rel.values()),
          f"mesh_compare: f2 {s_mesh} vs one device {s_one}")
    phase_result({
        "phase": "mesh_compare", "ok": True,
        "f2": {k: s_mesh[k] for k in rel}, "f2_devices": on_mesh,
        "f2_coords": coords,
        "one_device": {k: s_one[k] for k in rel}, "one_device_id": on_one,
        "rel_diff": rel, **monitor.device_report(),
    })


PHASES = {"probe": phase_probe, "fabricate": phase_fabricate,
          "gen_server": phase_gen_server, "mesh_compare": phase_mesh_compare}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: sync PPO + generation fleet (default); 4: "
                         "async PPO on gen.d2+f2 and its one-device "
                         "comparison, nothing else")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--layers", type=int, default=QWEN25_05B["n_layers"],
                    help="cut depth (never a width); printed when cut")
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase:
        sys.path.insert(0, HERE)
        try:
            PHASES[args.phase](args)
        except PhaseFailed as e:
            print(f"phase {args.phase} failed: {e}", file=sys.stderr)
            return 1
        return 0
    return main_parent(args)


if __name__ == "__main__":
    sys.exit(main())
