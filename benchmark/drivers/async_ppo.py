"""Driver ``async_ppo``: ``training/main_async_ppo.py --backend=tpu`` as a
user runs it — launcher, master, trainer, generation fleet, rollout
workers — watched from outside by this CPU-pinned process.

The benchmark's own part: a checkpoint fabricated from ``--seed`` (kept
under ``benchmark/.cache/``, which outlasts a run; the entry path has no
weights-from-seed init), prompts from ``base/testing.make_math_jsonl``,
and the clock. Step boundaries are the moments the master's step lines
appear in the log, read every few milliseconds; weight versions are
followed on the version key and the servers' ``/health``. After the
window the master is told to exit over its control channel (the
operator's way), so that every worker writes its last device report.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import statistics
import sys
import time
import urllib.request
from typing import Any, Dict, List

os.environ["JAX_PLATFORMS"] = "cpu"  # this process never owns a chip

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import harness  # noqa: E402

STEP_LINE = re.compile(
    r"system\.master INFO: step (\d+) epoch \d+ \([\d.]+s\): (.*)")
REPORT_TAG = "device_report "


def fabricate(spec: Dict[str, Any]) -> str:
    """A checkpoint of the configuration's widths from ``--seed`` in the
    repo's own HF codec, bf16 as published; reused when it is there."""
    cfg_file = spec["config"]
    tag = (f"{spec['config_name']}-L{cfg_file['num_hidden_layers']}"
           f"-h{cfg_file['hidden_size']}-seed{spec['seed']}")
    root = harness.CACHE_ROOT if spec["platform"] == "tpu" else os.path.join(
        spec["out"], "cache")
    path = os.path.join(root, "ckpt", tag)
    if os.path.isfile(os.path.join(path, "areal_tpu_config.json")):
        return path
    # one checkpoint is ~1 GB: keep only the newest
    shutil.rmtree(os.path.join(root, "ckpt"), ignore_errors=True)
    import jax

    from areal_tpu.models import hf
    from benchmark import weights

    model_cfg = weights.model_config(cfg_file)
    params = weights.make_params(model_cfg, spec["seed"],
                                 dtype=cfg_file.get("torch_dtype", "float32"))
    tmp = path + ".tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    import dataclasses

    hf.save_hf_checkpoint(
        jax.device_get(params),
        dataclasses.replace(model_cfg, dtype=str(
            cfg_file.get("torch_dtype", "float32"))), tmp)
    os.replace(tmp, path)
    return path


def http_json(url: str, timeout: float = 2.0) -> Dict[str, Any]:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def parse_steps(text: str) -> List[Dict[str, float]]:
    out = []
    for m in STEP_LINE.finditer(text):
        st = {k: float(v) for k, v in
              (item.split("=", 1) for item in m.group(2).split())}
        st["step"] = float(m.group(1))
        out.append(st)
    return out


def main() -> int:
    from benchmark import driverlib as dl

    spec = dl.load_spec()
    t, out = spec["traffic"], spec["out"]
    chips = int(spec["cell"]["chips"])
    from areal_tpu.api import cli_args as CA
    from areal_tpu.base import name_resolve, names
    from areal_tpu.base.testing import MockTokenizer, make_math_jsonl
    from areal_tpu.experiments import common as C
    from areal_tpu.experiments import make_experiment_cls
    from areal_tpu.system.worker_base import WorkerControlPanel

    split: Dict[str, float] = {}
    with dl.timed(split, "fabricate_s"):
        if spec["platform"] == "tpu":
            model_args = [f"actor.path={fabricate(spec)}"]
        else:  # the CPU rehearsal: the entry path's own tiny model
            model_args = ["actor.tiny.vocab_size=258",
                          f"actor.tiny.seed={spec['seed']}"]
    data = os.path.join(out, "prompts.jsonl")
    recs = make_math_jsonl(data, n=t["n_prompts"], seed=spec["seed"])
    tok = MockTokenizer()
    mean_prompt = statistics.mean(len(tok.encode(r["prompt"])) for r in recs)
    overrides = [
        "experiment_name=bench", f"trial_name={spec['workload']}",
        f"cluster.fileroot={out}/exps", "mock_tokenizer=true",
        f"n_gpus_per_node={chips}", f"dataset.path={data}",
        f"seed={spec['seed']}", *model_args, *t["overrides"],
        "exp_ctrl.total_train_epochs=1000000",
    ]
    exp = CA.apply_overrides(make_experiment_cls(t["experiment"])(),
                             list(overrides))
    CA.validate_config(exp)
    C.setup_name_resolve(exp)
    tokens_per_step = (exp.dataset.train_bs_n_seqs * exp.group_size
                       * (mean_prompt + exp.ppo.gen.max_new_tokens))
    env = harness.child_env(cpu=(spec["platform"] == "cpu"))
    if spec["platform"] != "cpu":
        env.pop("JAX_PLATFORMS", None)
    entry = harness.Child(
        [sys.executable, os.path.join(harness.ROOT, "training", t["entry"]),
         "--backend=tpu", *overrides],
        env, os.path.join(out, "entry.log"), own_session=False)
    ver_key = names.model_version(exp.experiment_name, exp.trial_name, "actor")
    ver_time_key = names.model_version_time(exp.experiment_name,
                                            exp.trial_name, "actor")
    srv_root = names.gen_server_root(exp.experiment_name, exp.trial_name)
    step_seen: List[float] = []    # host clock when step line k appeared
    syncs: List[Dict[str, float]] = []
    pending: Dict[str, Any] = {}
    w0 = w1 = window_start = None
    pos, text = 0, ""
    servers: List[str] = []
    exit_sent = False
    rc = None
    try:
        deadline = time.monotonic() + 1100
        while time.monotonic() < deadline:
            rc = entry.proc.poll()
            with open(entry.log_path, errors="replace") as f:
                f.seek(pos)
                new = f.read()
                pos = f.tell()
            now = time.monotonic()
            if new:
                text += new
                n = len(STEP_LINE.findall(text))
                step_seen += [now] * (n - len(step_seen))
            if rc is not None:
                break
            if w0 is None and len(step_seen) >= t["window_after_steps"]:
                w0, window_start = step_seen[t["window_after_steps"] - 1], \
                    time.time()
            if w0 is not None and w1 is None and now >= w0 + spec["seconds"]:
                w1 = now
            if w1 is not None and not exit_sent:
                try:
                    servers = servers or list(name_resolve.get_subtree(srv_root))
                    peaks = [max(p for p in http_json(u + "/metrics.json")
                                 ["device"]["hbm_peak_bytes"] if p)
                             for u in servers]
                except Exception as e:  # noqa: BLE001 — report, go on
                    peaks = []
                    harness.log(f"no server memory report: {e!r}")
                split["server_hbm_peak_bytes"] = max(peaks, default=None)
                panel = WorkerControlPanel(exp.experiment_name,
                                           exp.trial_name, timeout=30.0)
                try:
                    panel.exit("master")
                finally:
                    panel.close()
                exit_sent = True
            # weight versions: publish on the key, served on /health
            if w0 is not None and w1 is None:
                try:
                    v = int(name_resolve.get(ver_key))
                    if v > pending.get("v", 0) and v > max(
                            [s["version"] for s in syncs], default=0):
                        pending = {"v": v, "t_seen": now, "t_pub_wall": float(
                            name_resolve.get(ver_time_key))}
                    if pending:
                        servers = servers or list(
                            name_resolve.get_subtree(srv_root))
                        if all(http_json(u + "/health")["version"]
                               >= pending["v"] for u in servers):
                            syncs.append({
                                "version": pending["v"],
                                "secs": time.time() - pending["t_pub_wall"],
                                "t_served": now})
                            pending = {}
                except Exception:  # noqa: BLE001 — key not there yet
                    pass
            time.sleep(0.02)
        if rc is None:
            rc = entry.wait(60)
    finally:
        entry.kill()
    with open(entry.log_path, errors="replace") as f:
        text = f.read()
    if w0 is None or w1 is None:
        harness.log("the run never reached its window; log tail:\n"
                    + text[-6000:])
        return 1

    steps = parse_steps(text)
    in_win = [k for k, ts in enumerate(step_seen)
              if w0 < ts <= w1 and k < len(steps)]
    win_steps = [steps[k] for k in in_win]
    span = (step_seen[in_win[-1]] - w0) if in_win else 0.0
    reports = []
    for line in text.splitlines():
        i = line.find(REPORT_TAG)
        if i >= 0:
            reports.append(json.loads(line[i + len(REPORT_TAG):]))
    owners = [r for r in reports if r["worker"].startswith(("trainer",
                                                            "gen_fleet"))]
    platforms = {r["platform"] for r in owners}
    kinds = {r["device_kind"] for r in owners}
    hbm = [d["peak_bytes_in_use"] for r in owners for d in r["local_devices"]
           if d.get("peak_bytes_in_use")]
    if split.get("server_hbm_peak_bytes"):
        hbm.append(split["server_hbm_peak_bytes"])
    trainer_last = [r for r in owners if r["worker"].startswith("trainer")][-1:]
    train_attn = (trainer_last[0]["attention"].get("train", {})
                  if trainer_last else {})
    want_kernel = {"tpu": "pallas"}.get(spec["platform"], "reference")
    eta = exp.max_head_offpolicyness
    finite = all(
        all(k in st and st[k] == st[k] and abs(st[k]) != float("inf")
            for k in ("actor_train/actor_loss", "actor_train/grad_norm"))
        for st in steps)
    first_imp = steps[0].get("actor_train/importance_weight", float("nan"))
    stale = max((st.get("actor_train/staleness_lag", 0.0) for st in steps),
                default=0.0)
    n_traj = exp.dataset.train_bs_n_seqs * exp.group_size
    correct = (
        len(win_steps) >= t["min_window_steps"] and finite
        and abs(first_imp - 1.0) < 0.05 and stale <= eta
        and platforms == {spec["platform"]} and len(kinds) == 1
        and set(train_attn) == {want_kernel}
        and (spec["platform"] == "cpu"  # virtual devices: all see all
             or sum(r["device_count"] for r in {
                 r["worker"]: r for r in owners}.values()) == chips)
    )
    good_syncs = [s for s in syncs if s["t_served"] <= w1]
    e2e = {"setup_s": window_start - spec["t0"]}
    if in_win and span > 0:
        e2e["async_tok_s_chip"] = len(in_win) * tokens_per_step / span / chips
    if good_syncs:
        e2e["weight_sync_s"] = statistics.median(s["secs"] for s in good_syncs)
    device = {"platform": next(iter(platforms), None),
              "kind": next(iter(kinds), None), "count": chips,
              "memory_peak_bytes": max(hbm, default=None)}
    records = {
        "device": device, "chips": chips, "window_s": w1 - w0,
        "config": spec["config"], "master_steps": win_steps,
        "counters": {"steps_in_window": len(in_win),
                     "tokens_per_step": tokens_per_step,
                     "weight_syncs": len(good_syncs)},
        "memory_peak_bytes": device["memory_peak_bytes"], "trace": {},
        "setup_split": split,
    }
    notes = [f"steps_in_window={len(in_win)} span={span:.2f}s "
             f"tokens_per_step={tokens_per_step:.0f} first_imp={first_imp} "
             f"max_mean_staleness={stale} eta={eta} syncs={syncs} "
             f"train_attention={train_attn} exit_rc={rc} split={split} "
             f"compile_cache={[r.get('compile_cache') for r in owners]}"]
    harness.write_json(os.path.join(out, "result.json"), {
        "correct": bool(correct), "attempted": len(in_win) * n_traj,
        "failed": 0 if finite else n_traj, "end_to_end": e2e,
        "device": device, "breakdown": None, "records": records,
        "notes": notes,
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
