"""Operator CLI for a LIVE run: every command talks to running workers over
HTTP or name-resolve and none of them builds an engine or touches a device
(the file imports no jax). What the trainer's step costs is measured by
``benchmark/run.py`` (PERF.md).

Usage: python tools/perf_probe.py <command> [operands ...]

Live-fleet commands (docs/observability.md; name-resolve root via
AREAL_NAME_RESOLVE_ROOT when not the default):
  scrape <url>                        GET a worker's /metrics (Prometheus
                                      text or JSON) and pretty-print it
  scrape <exp> <trial>                same, against the aggregator's
                                      MERGED fleet endpoint (resolved via
                                      name-resolve; fails with a clear
                                      message when telemetry is disabled
                                      or http_port is 0)
  trace <traces.jsonl> <trace_id>     print a stitched sample-lineage
                                      trace as a critical-path timeline
                                      (docs/observability.md)
  flight-dump <exp> <trial> <dir>     ask EVERY live worker to dump its
                                      flight-recorder ring to
                                      <dir>/flight_<worker>.jsonl
  fleet-status <exp> <trial>          supervision view of a live run:
                                      per-worker heartbeat ages +
                                      incarnations (name-resolve
                                      liveness leases), the drain phase,
                                      the autoscale plan (target/dynamic
                                      fleet size, overload flag), the
                                      per-server fleet map (routable /
                                      cordoned / deprioritized,
                                      draining lease counts), and the
                                      supervisor restart / crash-loop
                                      counters from the merged
                                      Prometheus scrape
                                      (docs/fault_tolerance.md)
  cordon <exp> <trial> <server> [why] preemption-notice hook: cordon one
                                      generation server (server_id like
                                      gen1/dyn2, or its url) — it stops
                                      receiving leases, inflight
                                      rollouts drain or fail over, and
                                      a drained dynamic server exits
                                      via WorkerControl
                                      (docs/fault_tolerance.md
                                      §Autoscaling)
  uncordon <exp> <trial> <server>     lift a cordon; the server
                                      re-admits through the health gate
                                      (probe + weight reconcile)
  drain <exp> <trial>                 graceful preemption drain of a
                                      LIVE run: pause the rollout fleet,
                                      dump an out-of-band recover
                                      checkpoint via the master's
                                      control channel, then exit the
                                      workers in order (the launcher
                                      tears down the rest when the
                                      master returns) —
                                      docs/operations.md runbook
  decode-bench <server_url> [n_requests] [max_tokens]
                                      drive a LIVE generation server with
                                      a mixed-class synthetic workload
                                      (rollout/interactive/eval) and
                                      report tokens/s, per-class latency,
                                      queue depth, and the distinct
                                      compiled-shape count (VERDICT #9,
                                      docs/serving.md)
  reward-bench <exp> <trial> [n]      fan N mixed math/code tasks at a
                                      LIVE reward fleet (discovered via
                                      name-resolve) and report p50/p99
                                      grade latency per task kind plus
                                      the fleet-side verdict distribution
                                      from the merged Prometheus scrape
                                      (docs/rewards.md); also accepts one
                                      worker url: reward-bench <url> [n]
  goodput <exp> <trial> [window_s]    live goodput view of a run: per-
                                      worker compute/comm/data_wait/idle
                                      time-in-state fractions over a
                                      short live window (two scrapes of
                                      areal_goodput_secs_total diffed;
                                      default 5s — a since-start split
                                      would dilute a live stall by the
                                      run's whole history), plus the
                                      stitched fleet-goodput gauges and
                                      live MFU (docs/observability.md
                                      §Goodput); also accepts one
                                      worker url: goodput <url>
  spool-status <exp> <trial>          durable-spool view of a LIVE run
                                      (docs/fault_tolerance.md §Data
                                      durability): per-rollout-worker
                                      depth / bytes / oldest-unacked age
                                      from the merged Prometheus scrape,
                                      plus the fleet delivery totals
                                      (appended / acked / replayed /
                                      resent / stale-dropped) and the
                                      trainer-side dedup counters — the
                                      first stop of the "did we lose
                                      samples?" runbook
                                      (docs/operations.md)
  compile-status <exp> <trial>        compile-observatory view of a LIVE
                                      run (docs/observability.md §Compile
                                      & memory): per-jit-entry-point
                                      compile counts / seconds / distinct
                                      compiled shapes fleet-wide, the
                                      persistent-cache hit ratio,
                                      recompile-storm events, and which
                                      workers are compiling RIGHT NOW —
                                      the first stop of the "my run is
                                      wedged in warmup / my step got
                                      slow" runbook (docs/operations.md)
  mem-status <exp> <trial>            HBM watermark view of a LIVE run:
                                      per-worker per-device bytes-in-use
                                      / peak / limit / utilization plus
                                      the allocation-site high-water
                                      marks (weight publish/consume,
                                      shadow swap, fwd+bwd) —
                                      docs/weight_sync.md §HBM headroom
  alerts <exp> <trial> [severity] [rule]
                                      training-health sentinel view of a
                                      LIVE run: alert totals + active
                                      alerts from the merged Prometheus
                                      scrape, optionally filtered by
                                      severity (info|warn|critical) or
                                      rule id (docs/observability.md
                                      §Alerting)
  alerts <alerts.jsonl> [severity] [rule]
                                      same filters over a run's recorded
                                      alert stream (works after the run
                                      is dead — post-mortem triage)
  silence <exp> <trial> <rule> <dur>  silence one sentinel rule for a
                                      duration ("30s"/"10m"/"1h"): it
                                      keeps evaluating but neither fires
                                      nor captures evidence until the
                                      silence expires
  profile-trigger <exp> <trial> <dir> [secs]
                                      ask the live trainer for an
                                      on-demand jax.profiler capture
  profile-status <exp> <trial>        last capture outcome
"""

import sys
import time

sys.path.insert(0, ".")


def scrape(url: str) -> None:
    """Fetch + pretty-print a worker's /metrics endpoint. Prometheus text
    renders as an aligned table (histograms summarized as count/mean);
    JSON (e.g. /metrics.json) pretty-prints as-is."""
    import json as _json
    import urllib.error
    import urllib.request

    if not url.startswith("http"):
        url = f"http://{url}"
    if "/metrics" not in url:
        url = url.rstrip("/") + "/metrics"
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            ctype = r.headers.get("Content-Type", "")
            body = r.read().decode()
    except (urllib.error.URLError, OSError) as e:
        sys.exit(f"scrape: cannot reach {url}: {e}\n"
                 f"(is the worker up, and telemetry enabled?)")
    if "json" in ctype:
        print(_json.dumps(_json.loads(body), indent=2, sort_keys=True))
        return
    rows = []
    hist = {}
    for line in body.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, val = line.rpartition(" ")
        base, _, labels = name.partition("{")
        labels = ("{" + labels) if labels else ""
        # Key histograms by (family, labels): the master's merged endpoint
        # carries one series per worker — dropping labels would silently
        # overwrite worker 0's sum/count with worker 1's.
        if base.endswith("_sum"):
            hist.setdefault(base[:-4] + labels, {})["sum"] = float(val)
        elif base.endswith("_count"):
            hist.setdefault(base[:-6] + labels, {})["count"] = float(val)
        elif base.endswith("_bucket"):
            continue  # summarized via _sum/_count
        else:
            rows.append((base + labels, float(val)))
    for h, d in sorted(hist.items()):
        n = d.get("count", 0)
        mean = (d.get("sum", 0.0) / n) if n else 0.0
        rows.append((f"{h} (hist)", f"n={n:g} mean={mean:.4g}"))
    w = max((len(r[0]) for r in rows), default=0)
    for k, v in sorted(rows):
        print(f"  {k:<{w}}  {v if isinstance(v, str) else f'{v:g}'}")


def decode_bench(server_url: str, n_requests: int = 24,
                 max_tokens: int = 32) -> None:
    """Decode-throughput probe against a LIVE generation server (the
    probe half of VERDICT #9): fire a mixed-class synthetic workload with
    randomized prompt lengths/budgets, then report client-side tokens/s
    + per-class latency and the server's own queue/shape counters from
    ``/metrics.json``. jax-free: run it from any host that can reach the
    server."""
    import asyncio
    import json as _json
    import random
    import time as _time
    import urllib.request

    import aiohttp

    url = server_url if server_url.startswith("http") \
        else f"http://{server_url}"
    rng = random.Random(0)
    classes = ["rollout", "rollout", "interactive", "eval"]

    async def one(session, i):
        cls = classes[i % len(classes)]
        plen = rng.randint(4, 48)
        budget = rng.randint(4, max_tokens)
        body = {
            "prompt_ids": [rng.randint(2, 90) for _ in range(plen)],
            "class": cls,
            "rid": f"bench{i}",
            "gconfig": {"max_new_tokens": budget, "greedy": False},
            "max_tokens": budget,
        }
        t0 = _time.monotonic()
        async with session.post(f"{url}/generate", json=body) as r:
            if r.status != 200:
                # 429 = admission backpressure, 413 = over capacity, 5xx =
                # server trouble: all reported, none kill the bench.
                return f"{cls}:http{r.status}", None, 0
            out = await r.json()
        return cls, _time.monotonic() - t0, len(out["output_ids"])

    async def run():
        async with aiohttp.ClientSession() as session:
            t0 = _time.monotonic()
            res = await asyncio.gather(
                *[one(session, i) for i in range(n_requests)]
            )
            return res, _time.monotonic() - t0

    results, wall = asyncio.run(run())
    tokens = sum(n for _, _, n in results)
    errs = sorted(c for c, dt, _ in results if dt is None)
    print(f"[decode-bench] {n_requests} requests "
          f"({len(errs)} non-200: {', '.join(errs) or 'none'}), "
          f"{tokens} tokens in {wall:.2f}s -> "
          f"{tokens / max(wall, 1e-9):,.0f} tok/s")
    for cls in ("interactive", "eval", "rollout"):
        lats = [dt for c, dt, _ in results if c == cls and dt is not None]
        if lats:
            lats.sort()
            print(f"[decode-bench] {cls:<12} n={len(lats)} "
                  f"mean={sum(lats) / len(lats) * 1e3:.0f}ms "
                  f"p95={lats[int(0.95 * (len(lats) - 1))] * 1e3:.0f}ms")
    with urllib.request.urlopen(f"{url}/metrics.json", timeout=10) as r:
        m = _json.loads(r.read().decode())
    print(f"[decode-bench] server: tokens_per_sec={m['tokens_per_sec']:.0f} "
          f"compiled_shapes={m.get('compiled_shapes')} "
          f"kv_states={m.get('kv_states')} "
          f"queue_depth={m.get('queue_depth')} "
          f"prefill_tokens={m.get('prefill_tokens')}")


def reward_bench(exp_or_url: str, trial: str = "",
                 n_tasks: int = 32) -> None:
    """Grade-latency probe against a LIVE reward fleet (docs/rewards.md):
    fan a mixed math/code synthetic workload through the real fanout
    client (bounded concurrency + retry across replicas), report client-
    side p50/p99 per task kind, then the fleet's own verdict counters
    from the merged Prometheus scrape (falling back to per-worker
    /metrics when the aggregator endpoint is absent). jax-free."""
    import asyncio
    import json as _json
    import random
    import time as _time
    import urllib.request

    from areal_tpu.api.train_config import RewardServiceConfig
    from areal_tpu.rewards.client import RewardServiceClient

    if exp_or_url.startswith("http"):
        urls = [exp_or_url.rstrip("/")]
    else:
        from areal_tpu.system.reward_worker import resolve_fleet

        urls = resolve_fleet(exp_or_url, trial)
        if not urls:
            sys.exit(
                f"reward-bench: no reward workers registered for "
                f"{exp_or_url}/{trial}.\nEither the fleet is down or the "
                f"service is disabled — relaunch with "
                f"reward_service.enabled=true, or probe one worker "
                f"directly: reward-bench <url>."
            )
    print(f"[reward-bench] fleet: {len(urls)} worker(s)")
    rng = random.Random(0)
    tasks = []
    for i in range(n_tasks):
        if i % 4 == 3:  # 1/4 code, 3/4 math — roughly the mixed-data shape
            k = rng.randint(1, 9)
            ok = rng.random() < 0.5
            code = (f"```python\nx = int(input())\nprint(x + "
                    f"{k if ok else k + 1})\n```")
            tasks.append({"task": "code", "generated": code,
                          "input_output": _json.dumps({
                              "inputs": ["1\n", "2\n"],
                              "outputs": [f"{1 + k}\n", f"{2 + k}\n"],
                          })})
        else:
            v = rng.randint(0, 999)
            guess = v if rng.random() < 0.5 else v + 1
            tasks.append({"task": "math",
                          "generated": f"\\boxed{{{guess}}}",
                          "solutions": [f"\\boxed{{{v}}}"]})

    # local_fallback OFF: a dead fleet must surface as 0.0-scored errors
    # and missing verdict counters, not silently benchmark local grading
    # on the operator's machine.
    client = RewardServiceClient(
        RewardServiceConfig(enabled=True, local_fallback=False), urls=urls
    )
    lats = {"math": [], "code": []}

    async def run():
        import aiohttp

        sem = asyncio.Semaphore(16)

        async def one(session, t):
            t0 = _time.monotonic()
            s = await client.grade_one(session, t, sem)
            lats[t["task"]].append(_time.monotonic() - t0)
            return s

        async with aiohttp.ClientSession() as session:
            t0 = _time.monotonic()
            scores = await asyncio.gather(
                *[one(session, t) for t in tasks]
            )
            return scores, _time.monotonic() - t0

    scores, wall = asyncio.run(run())
    print(f"[reward-bench] {n_tasks} tasks in {wall:.2f}s -> "
          f"{n_tasks / max(wall, 1e-9):.1f} grades/s, "
          f"mean score {sum(scores) / len(scores):.3f}")
    for kind in ("math", "code"):
        ls = sorted(lats[kind])
        if ls:
            print(f"[reward-bench] {kind:<5} n={len(ls)} "
                  f"p50={ls[len(ls) // 2] * 1e3:.1f}ms "
                  f"p99={ls[min(int(0.99 * len(ls)), len(ls) - 1)] * 1e3:.1f}ms")
    # fleet-side verdict distribution: merged scrape when available,
    # per-worker /metrics otherwise
    bodies = []
    if trial:
        from areal_tpu.base import name_resolve, names

        try:
            murl = name_resolve.get(names.telemetry_http(exp_or_url, trial))
            with urllib.request.urlopen(f"{murl}/metrics", timeout=10) as r:
                bodies = [("merged", r.read().decode())]
        except Exception:  # noqa: BLE001 — aggregator absent: per-worker
            pass
    if not bodies:
        for u in urls:
            try:
                with urllib.request.urlopen(f"{u}/metrics", timeout=10) as r:
                    bodies.append((u, r.read().decode()))
            except Exception as e:  # noqa: BLE001 — worker died mid-bench
                print(f"[reward-bench] scrape {u} failed: {e}")
    verdicts = {}
    for src, body in bodies:
        for ln in body.splitlines():
            if ln.startswith("areal_reward_verdicts_total{"):
                labels, _, val = ln.rpartition(" ")
                verdicts[labels] = verdicts.get(labels, 0.0) + float(val)
    if verdicts:
        print(f"[reward-bench] fleet verdicts "
              f"({'merged scrape' if bodies[0][0] == 'merged' else 'per-worker'}):")
        for k, v in sorted(verdicts.items()):
            print(f"  {k} {v:g}")
    else:
        print("[reward-bench] no verdict counters scraped "
              "(telemetry disabled on the fleet?)")


def scrape_fleet(experiment: str, trial: str) -> None:
    """Resolve + scrape the aggregator's MERGED fleet /metrics (the
    telemetry.http_port endpoint). jax-free; fails with an actionable
    message — not a traceback — when telemetry is off."""
    from areal_tpu.base import name_resolve, names

    try:
        url = name_resolve.get(names.telemetry_http(experiment, trial))
    except Exception:  # noqa: BLE001 — key absent: telemetry off/no port
        sys.exit(
            f"scrape: no merged telemetry endpoint registered for "
            f"{experiment}/{trial}.\nEither telemetry is disabled or the "
            f"aggregator has no HTTP port — relaunch with "
            f"telemetry.enabled=true telemetry.http_port=<port>, or "
            f"scrape a worker endpoint directly: scrape <url>."
        )
    print(f"[scrape] merged fleet endpoint {url}")
    scrape(url)


def print_trace(traces_path: str, trace_id: str) -> None:
    """Reconstruct one stitched trace from ``traces.jsonl`` as a
    chronological critical-path timeline: per-span offset from the
    prompt's admission, duration, owning worker — then the derived stage
    decomposition (generate/queue/gate/train-wait/train)."""
    import json as _json

    try:
        with open(traces_path) as f:
            recs = [_json.loads(ln) for ln in f if ln.strip()]
    except OSError as e:
        sys.exit(f"trace: cannot read {traces_path}: {e}")
    hits = [r for r in recs if r.get("trace_id") == trace_id]
    if not hits:
        known = {r.get("trace_id") for r in recs}
        sys.exit(f"trace: {trace_id!r} not in {traces_path} "
                 f"({len(known)} trace ids present)")
    # The LAST record is the most complete view (each trained sample of
    # the group re-stitches the trace with everything seen so far).
    rec = hits[-1]
    spans = sorted(rec.get("spans", []), key=lambda s: s["t_start"])
    t0 = rec.get("t_start", spans[0]["t_start"] if spans else 0.0)
    print(f"trace {trace_id}  sample={rec.get('sample_id')}  "
          f"weight_version={rec.get('weight_version')}  "
          f"e2e={rec.get('e2e_secs', 0):.3f}s  "
          f"workers={','.join(rec.get('workers', []))}")
    w = max((len(s['name']) for s in spans), default=0)
    for s in spans:
        off = s["t_start"] - t0
        attrs = s.get("attrs") or {}
        extra = " ".join(f"{k}={v}" for k, v in sorted(attrs.items())
                         if k not in ("error",))
        print(f"  +{off:8.3f}s  {s['name']:<{w}}  "
              f"{s['dur_secs'] * 1e3:9.1f}ms  [{s.get('worker', '?')}]"
              f"{('  ' + extra) if extra else ''}")
    stages = rec.get("stages") or {}
    if stages:
        print("  stages: " + "  ".join(
            f"{k}={v:.3f}s" for k, v in stages.items()
        ))


def flight_dump(experiment: str, trial: str, out_dir: str) -> None:
    from areal_tpu.base import telemetry

    nonce = telemetry.request_flight_dump(experiment, trial, out_dir)
    print(f"flight-dump trigger {nonce} set for {experiment}/{trial}: "
          f"every worker dumps flight_<worker>.jsonl into {out_dir} "
          f"within one telemetry flush interval (~2s at defaults)")


def spool_status(experiment: str, trial: str) -> None:
    """Durable-spool delivery view of a live run (jax-free), from the
    merged Prometheus scrape: per-rollout-worker spool depth, on-disk
    bytes and oldest-unacked age, plus the fleet-wide delivery ledger.
    ``appended == acked`` (and depth 0 everywhere) means every spooled
    trajectory settled — trained or durably dropped; a growing
    oldest-unacked age means the ack path is wedged
    (docs/operations.md runbook: "Did we lose samples?")."""
    import re
    import urllib.request

    from areal_tpu.base import name_resolve, names

    try:
        url = name_resolve.get(names.telemetry_http(experiment, trial))
        with urllib.request.urlopen(f"{url}/metrics", timeout=10) as r:
            body = r.read().decode()
    except Exception as e:  # noqa: BLE001 — aggregator absent / dead run
        sys.exit(
            f"spool-status: cannot scrape the merged telemetry endpoint "
            f"for {experiment}/{trial}: {e}\nNeeds telemetry.enabled=true "
            f"+ telemetry.http_port on the master. For a dead run, read "
            f"the spool directories under recover_dir/spool_<worker> "
            f"directly (docs/fault_tolerance.md §Data durability)."
        )
    lab_re = re.compile(r'(\w+)="([^"]*)"')
    gauges = {}  # worker_index -> {metric: value}
    totals = {}  # counter family -> summed value
    gauge_families = {
        "areal_spool_depth": "depth",
        "areal_spool_bytes": "bytes",
        "areal_spool_oldest_unacked_age_secs": "oldest_unacked_s",
    }
    counter_families = (
        "areal_spool_appended_total", "areal_spool_acked_total",
        "areal_spool_replayed_total", "areal_spool_resent_total",
        "areal_spool_replay_stale_dropped_total",
        "areal_spool_duplicate_dropped_total",
        "areal_spool_backpressure_waits_total",
        "areal_stream_push_blocked_total",
        "areal_buffer_duplicate_dropped_total",
    )
    for ln in body.splitlines():
        if not ln or ln.startswith("#"):
            continue
        name, _, val = ln.rpartition(" ")
        base, _, rest = name.partition("{")
        if base in gauge_families:
            labels = dict(lab_re.findall(rest))
            w = labels.get("worker_index", "?")
            gauges.setdefault(w, {})[gauge_families[base]] = float(val)
        elif base in counter_families:
            totals[base] = totals.get(base, 0.0) + float(val)
    if not gauges and not totals:
        sys.exit(
            "spool-status: no spool metrics on the merged scrape — the "
            "durable spool is off (durability.enabled=false) or no "
            "rollout worker has flushed telemetry yet."
        )
    if gauges:
        print("per-worker spool state:")
        print(f"  {'worker':>6}  {'depth':>7}  {'bytes':>12}  "
              f"{'oldest unacked':>14}")
        for w in sorted(gauges, key=lambda x: (len(x), x)):
            g = gauges[w]
            print(f"  {w:>6}  {g.get('depth', 0):>7g}  "
                  f"{g.get('bytes', 0):>12g}  "
                  f"{g.get('oldest_unacked_s', 0):>13.1f}s")
    if totals:
        print("fleet delivery totals:")
        width = max(len(k) for k in totals)
        for k in counter_families:
            if k in totals:
                print(f"  {k:<{width}}  {totals[k]:g}")
        appended = totals.get("areal_spool_appended_total", 0.0)
        acked = totals.get("areal_spool_acked_total", 0.0)
        in_flight = sum(g.get("depth", 0) for g in gauges.values())
        if appended:
            print(f"  settled {acked:g}/{appended:g} "
                  f"({in_flight:g} durably queued on disk)")


def _merged_metric_rows(experiment: str, trial: str, command: str):
    """Fetch the aggregator's merged Prometheus scrape and parse it into
    ``(base_name, labels_dict, value)`` rows (jax-free). Shared by the
    compile/HBM observatory commands."""
    import re
    import urllib.request

    from areal_tpu.base import name_resolve, names

    try:
        url = name_resolve.get(names.telemetry_http(experiment, trial))
        with urllib.request.urlopen(f"{url}/metrics", timeout=10) as r:
            body = r.read().decode()
    except Exception as e:  # noqa: BLE001 — aggregator absent / dead run
        sys.exit(
            f"{command}: cannot scrape the merged telemetry endpoint for "
            f"{experiment}/{trial}: {e}\nNeeds telemetry.enabled=true + "
            f"telemetry.http_port on the master."
        )
    lab_re = re.compile(r'(\w+)="([^"]*)"')
    rows = []
    for ln in body.splitlines():
        if not ln or ln.startswith("#"):
            continue
        name, _, val = ln.rpartition(" ")
        base, _, rest = name.partition("{")
        try:
            rows.append((base, dict(lab_re.findall(rest)), float(val)))
        except ValueError:
            continue
    return rows


def program_memory_rows(ledger: dict, top: int = 12) -> list:
    """``[(fn, label, cache, temp GB, peak GB)]`` of a compile ledger's
    (``compile_cache``) executables whose statistics it found, the ones
    that need most of a chip first."""
    found = [(fn, rec) for fn, row in (ledger.get("programs") or {}).items()
             for rec in row.get("executables") or []
             if rec.get("temp_bytes") is not None]
    found.sort(key=lambda x: -x[1]["temp_bytes"])
    return [(fn, " ".join(f"{k}={v}" for k, v in rec["label"].items()),
             rec["cache"], rec["temp_bytes"] / 1e9,
             (rec.get("peak_bytes") or 0) / 1e9) for fn, rec in found[:top]]


def _print_program_memory(experiment: str, trial: str) -> None:
    """What each generation server's programs need of a chip, from the
    compile ledger in its ``/metrics.json`` (always on; the merged scrape
    has no ``compile_cache``). The trainer's table is in its
    ``device_report`` log line."""
    import json as _json
    import urllib.request

    from areal_tpu.base import name_resolve, names

    try:
        mgr = name_resolve.get(names.gen_server_manager(experiment, trial))
        with urllib.request.urlopen(f"{mgr.rstrip('/')}/metrics.json",
                                    timeout=10) as r:
            servers = sorted(_json.loads(r.read().decode()).get("fleet") or {})
    except Exception as e:  # noqa: BLE001 — no fleet / manager down
        print(f"per-executable memory: no generation fleet to ask ({e}); "
              f"the trainer's table is in its device_report log line")
        return
    for url in servers:
        try:
            with urllib.request.urlopen(f"{url.rstrip('/')}/metrics.json",
                                        timeout=10) as r:
                ledger = (_json.loads(r.read().decode()).get("device")
                          or {}).get("compile_cache") or {}
        except Exception as e:  # noqa: BLE001 — server down
            print(f"per-executable memory of {url}: unreachable ({e})")
            continue
        rows = program_memory_rows(ledger)
        print(f"per-executable memory of {url} (the compiler's temporaries "
              f"and peak, GB a chip; {ledger.get('executables_unmatched', 0)}"
              f" unmatched):" + ("" if rows else " none reported"))
        for fn, label, cache, temp, peak in rows:
            print(f"  {fn:<24} {cache:<8} temp {temp:7.3f}  peak {peak:7.3f}"
                  f"  {label}")


def compile_status(experiment: str, trial: str) -> None:
    """Compile observatory view of a live run (jax-free), from the merged
    Prometheus scrape: per-jit-entry-point compile counts / total compile
    seconds / distinct compiled shapes across the fleet, the persistent-
    cache hit ratio, recompile-storm events, and which workers have a
    compile in flight RIGHT NOW — the first stop of the "my run is wedged
    in warmup / my step got slow" runbook (docs/operations.md). Then,
    from each generation server's ``/metrics.json``, the compile ledger's
    per-executable table: what every program needs of a chip."""
    rows = _merged_metric_rows(experiment, trial, "compile-status")
    per_fn = {}  # fn -> {events, secs, shapes}
    inflight = []
    storms = cache_hits = cache_misses = 0.0
    for base, labels, val in rows:
        worker = (f"{labels.get('worker_kind', '?')}:"
                  f"{labels.get('worker_index', '?')}")
        fn = labels.get("fn", "?")
        if base == "areal_compile_events_total":
            per_fn.setdefault(fn, {})["events"] = \
                per_fn.get(fn, {}).get("events", 0.0) + val
        elif base == "areal_compile_secs_total" \
                and labels.get("worker_kind") != "fleet":
            per_fn.setdefault(fn, {})["secs"] = \
                per_fn.get(fn, {}).get("secs", 0.0) + val
        elif base == "areal_compile_distinct_shapes":
            d = per_fn.setdefault(fn, {})
            d["shapes"] = max(d.get("shapes", 0.0), val)
        elif base == "areal_compile_inflight" and val > 0:
            inflight.append(worker)
        elif base == "areal_compile_storm_events_total":
            storms += val
        elif base == "areal_compile_cache_hits_total":
            cache_hits += val
        elif base == "areal_compile_cache_misses_total":
            cache_misses += val
    if not per_fn:
        _print_program_memory(experiment, trial)  # the ledger needs no switch
        sys.exit(
            "compile-status: no compile metrics on the merged scrape — "
            "the observatory is off (compile_watch.enabled=false) or no "
            "watched jit entry point has compiled yet."
        )
    w = max(len(fn) for fn in per_fn)
    print("per-entry-point compile activity (fleet-wide):")
    print(f"  {'fn':<{w}}  {'compiles':>8}  {'secs':>8}  {'shapes':>6}")
    for fn in sorted(per_fn):
        d = per_fn[fn]
        print(f"  {fn:<{w}}  {d.get('events', 0):>8g}  "
              f"{d.get('secs', 0):>8.1f}  {d.get('shapes', 0):>6g}")
    total = cache_hits + cache_misses
    if total:
        print(f"persistent cache: {cache_hits:g} hits / "
              f"{cache_misses:g} misses "
              f"({100.0 * cache_hits / total:.0f}% hit)")
    if storms:
        print(f"RECOMPILE STORMS: {storms:g} storm event(s) — a stable "
              f"entry point saw new shapes after warmup. Check shape "
              f"bucketing (serving.max_compiled_shapes, "
              f"docs/serving.md) and the sentinel's recompile_storm "
              f"alert evidence.")
    if inflight:
        print(f"compiling NOW: {', '.join(sorted(inflight))} — absence "
              f"alerts (trainer_stalled) are suppressed while these "
              f"workers compile.")
    else:
        print("no compiles in flight.")
    _print_program_memory(experiment, trial)


def mem_status(experiment: str, trial: str) -> None:
    """HBM watermark view of a live run (jax-free), from the merged
    Prometheus scrape: per-worker per-device bytes-in-use / peak / limit
    plus the high-water marks recorded around the big allocators (weight
    publish/consume, shadow swap, fwd+bwd) — the capacity-planning view
    of docs/weight_sync.md §HBM headroom."""
    rows = _merged_metric_rows(experiment, trial, "mem-status")
    devs = {}   # (worker, device) -> {in_use, peak, limit, util}
    marks = {}  # (worker, site) -> bytes
    degraded = 0.0
    fields = {
        "areal_hbm_bytes_in_use": "in_use",
        "areal_hbm_peak_bytes": "peak",
        "areal_hbm_limit_bytes": "limit",
        "areal_hbm_utilization": "util",
    }
    for base, labels, val in rows:
        worker = (f"{labels.get('worker_kind', '?')}:"
                  f"{labels.get('worker_index', '?')}")
        if base in fields and labels.get("worker_index") != "fleet":
            key = (worker, labels.get("device", "?"))
            devs.setdefault(key, {})[fields[base]] = val
        elif base == "areal_hbm_watermark_bytes":
            marks[(worker, labels.get("site", "?"))] = val
        elif base == "areal_hbm_memory_stats_unavailable_total":
            degraded += val
    if not devs and not marks and not degraded:
        sys.exit(
            "mem-status: no HBM metrics on the merged scrape — the "
            "observatory is off (compile_watch.enabled=false) or no "
            "worker has sampled device memory yet."
        )
    gib = float(1 << 30)
    if devs:
        print("per-device HBM:")
        print(f"  {'worker':<14}  {'dev':>3}  {'in use':>9}  "
              f"{'peak':>9}  {'limit':>9}  {'util':>5}")
        for (worker, dev) in sorted(devs):
            d = devs[(worker, dev)]
            limit = d.get("limit", 0.0)
            util = d.get("util", (d.get("in_use", 0.0) / limit)
                         if limit else 0.0)
            print(f"  {worker:<14}  {dev:>3}  "
                  f"{d.get('in_use', 0) / gib:>8.2f}G  "
                  f"{d.get('peak', 0) / gib:>8.2f}G  "
                  f"{limit / gib:>8.2f}G  "
                  f"{100.0 * util:>4.0f}%")
    if marks:
        print("allocation-site high-water marks:")
        w = max(len(s) for (_, s) in marks)
        for (worker, site) in sorted(marks, key=lambda k: (k[1], k[0])):
            print(f"  {site:<{w}}  {marks[(worker, site)] / gib:>8.2f}G  "
                  f"[{worker}]")
    if degraded:
        print(f"note: {degraded:g} worker(s) run on devices without "
              f"memory_stats() (CPU backend) — HBM gauges absent there "
              f"by design.")


def fleet_status(experiment: str, trial: str) -> None:
    """Supervision view of a live run (jax-free): heartbeat ages and
    incarnations from the name-resolve liveness keys, the graceful-drain
    phase, and the supervisor restart counters filtered out of the
    merged Prometheus scrape (when telemetry is up)."""
    import json as _json
    import urllib.request

    from areal_tpu.base import name_resolve, names
    from areal_tpu.system.worker_base import WorkerControlPanel

    panel = WorkerControlPanel(experiment, trial, timeout=2.0)
    try:
        hbs = panel.heartbeats()
        if hbs:
            print("heartbeats (liveness leases):")
            w = max(len(k) for k in hbs)
            for worker, d in sorted(hbs.items()):
                age = d.get("age_secs")
                print(f"  {worker:<{w}}  "
                      f"age={'?' if age is None else f'{age:.1f}s'}  "
                      f"incarnation={d.get('incarnation', '?')}  "
                      f"pid={d.get('pid', '?')}")
        else:
            print("no heartbeats registered (run not supervised, or "
                  "fault_tolerance.keepalive_ttl_secs=0)")
        workers = panel.list_workers()
        print(f"control endpoints: {', '.join(workers) or 'none'}")
    finally:
        panel.close()
    try:
        d = _json.loads(name_resolve.get(
            names.drain_status(experiment, trial)
        ))
        print(f"drain phase: {d.get('phase')} "
              f"(at {time.strftime('%H:%M:%S', time.localtime(d.get('ts', 0)))})")
    except Exception:  # noqa: BLE001 — no drain ever requested
        print("drain phase: none")
    try:
        plan = _json.loads(name_resolve.get(
            names.autoscale_plan(experiment, trial)
        ))
        print(f"autoscale plan: target={plan.get('target')} "
              f"dynamic={plan.get('dynamic')} "
              f"overloaded={plan.get('overloaded')}")
    except Exception:  # noqa: BLE001 — autoscale disabled / no plan yet
        print("autoscale plan: none (autoscale disabled?)")
    # Per-server fleet map from the manager (jax-free JSON endpoint):
    # who is routable / cordoned / deprioritized, and what is draining.
    try:
        mgr = name_resolve.get(names.gen_server_manager(experiment, trial))
        with urllib.request.urlopen(f"{mgr.rstrip('/')}/metrics.json",
                                    timeout=10) as r:
            m = _json.loads(r.read().decode())
        asc = m.get("autoscale") or {}
        print(f"fleet: {m.get('healthy_servers')}/{m.get('known_servers')} "
              f"routable, {asc.get('cordoned', 0)} cordoned"
              + (f", target {asc.get('target_size')}"
                 if asc.get("enabled") else ""))
        for u, st in sorted((m.get("fleet") or {}).items()):
            state = ("cordoned" if st.get("cordoned")
                     else "routable" if st.get("routable")
                     else "evicted")
            extra = []
            if st.get("server_id"):
                extra.append(st["server_id"])
            if st.get("deprioritized"):
                extra.append("deprioritized(straggler)")
            if st.get("cordoned"):
                extra.append(f"reason={st.get('cordon_reason', '?')}")
                extra.append(f"draining={st.get('draining', 0)}")
            if st.get("evicted_reason") and state == "evicted":
                extra.append(st["evicted_reason"])
            print(f"  {u}  {state}" + ("  [" + ", ".join(extra) + "]"
                                       if extra else ""))
    except Exception as e:  # noqa: BLE001 — manager down
        print(f"fleet map: manager unreachable ({e})")
    try:
        url = name_resolve.get(names.telemetry_http(experiment, trial))
        with urllib.request.urlopen(f"{url.rstrip('/')}/metrics",
                                    timeout=10) as r:
            body = r.read().decode()
        lines = [ln for ln in body.splitlines()
                 if "areal_supervisor_" in ln and not ln.startswith("#")]
        if lines:
            print("supervisor metrics (merged scrape):")
            for ln in lines:
                print(f"  {ln}")
        else:
            print("supervisor metrics: none yet (no restarts)")
    except Exception:  # noqa: BLE001 — telemetry off / no http port
        print("supervisor metrics: merged scrape unavailable "
              "(telemetry disabled or no http_port)")


def _manager_url(experiment: str, trial: str) -> str:
    from areal_tpu.base import name_resolve, names

    try:
        return name_resolve.get(names.gen_server_manager(experiment, trial))
    except Exception as e:  # noqa: BLE001 — run down / wrong root
        sys.exit(f"cannot resolve the gserver manager for "
                 f"{experiment}/{trial}: {e}\n(is the run up, and "
                 f"AREAL_NAME_RESOLVE_ROOT pointing at its store?)")


def cordon(experiment: str, trial: str, server: str,
           reason: str = "operator request", un: bool = False) -> None:
    """Cordon (or uncordon) one generation server of a live run — the
    operator's preemption-notice hook (docs/fault_tolerance.md
    §Autoscaling). ``server`` is a server_id (e.g. gen1, dyn2) or a full
    http url; the cordoned server stops receiving leases, its inflight
    rollouts drain, and the autoscale loop reaps a drained dynamic
    server via a WorkerControl-commanded exit."""
    import json as _json
    import urllib.error
    import urllib.request

    url = _manager_url(experiment, trial)
    key = "url" if server.startswith("http") else "server_id"
    body = _json.dumps(
        {key: server, "reason": reason}
    ).encode()
    verb = "uncordon" if un else "cordon"
    req = urllib.request.Request(
        f"{url.rstrip('/')}/{verb}", data=body,
        headers={"Content-Type": "application/json"}, method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            d = _json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        sys.exit(f"{verb} {server}: manager said {e.code} "
                 f"({e.read().decode()[:200]})")
    print(_json.dumps(d, indent=2, sort_keys=True))
    if not un and d.get("ok"):
        print(f"{d.get('url')} cordoned; {d.get('draining', 0)} leases "
              f"draining — watch `fleet-status {experiment} {trial}`")


def drain(experiment: str, trial: str) -> None:
    """Trigger the graceful-drain sequence against a live run — the same
    path the launcher's SIGTERM handler drives (docs/operations.md)."""
    import json as _json

    from areal_tpu.system.supervisor import drain_experiment

    report = drain_experiment(experiment, trial)
    print(_json.dumps(report, indent=2, sort_keys=True))
    ck = report.get("checkpoint") or {}
    res = ck.get("result") or {}
    if res.get("saved"):
        print(f"recover checkpoint: {res.get('dir')} "
              f"(step {res.get('step')})")
    else:
        print("WARNING: no recover checkpoint was written "
              f"({ck.get('error') or res.get('reason') or 'master absent'})")


def alerts(exp_or_path: str, trial: str = "", severity: str = "",
           rule: str = "") -> None:
    """Training-health alert view (jax-free): either tail/filter a run's
    ``alerts.jsonl`` (post-mortem), or pull the live alert counters off
    the merged Prometheus scrape (docs/observability.md §Alerting)."""
    import json as _json
    import os as _os
    import urllib.request

    # File mode only for an actual alert-stream file: a directory named
    # after the experiment (launchers create <exp>/ log dirs in cwd)
    # must still route to the live merged scrape.
    if _os.path.isfile(exp_or_path) or exp_or_path.endswith(".jsonl"):
        # file mode: positional args shift left (no trial)
        severity, rule = trial, severity
        try:
            with open(exp_or_path) as f:
                recs = [_json.loads(ln) for ln in f if ln.strip()]
        except OSError as e:
            sys.exit(f"alerts: cannot read {exp_or_path}: {e}")
        shown = 0
        for r in recs:
            if severity and r.get("severity") != severity:
                continue
            if rule and r.get("rule") != rule:
                continue
            shown += 1
            ts = time.strftime("%H:%M:%S", time.localtime(r.get("ts", 0)))
            extra = ""
            if r.get("event") == "firing":
                extra = (f"  {r.get('metric')}={r.get('value')}"
                         + (f"  evidence={r['evidence_dir']}"
                            if r.get("evidence_dir") else ""))
            print(f"{ts}  {r.get('severity', '?'):<8} "
                  f"{r.get('event', '?'):<9} {r.get('rule', '?')}{extra}")
        print(f"({shown}/{len(recs)} records"
              + (f", severity={severity}" if severity else "")
              + (f", rule={rule}" if rule else "") + ")")
        return
    from areal_tpu.base import name_resolve, names

    try:
        url = name_resolve.get(names.telemetry_http(exp_or_path, trial))
    except Exception:  # noqa: BLE001 — telemetry off / no http port
        sys.exit(
            f"alerts: no merged telemetry endpoint for "
            f"{exp_or_path}/{trial}.\nEither the run is down or telemetry "
            f"has no http_port — read the recorded stream instead: "
            f"alerts <log-dir>/alerts.jsonl"
        )
    with urllib.request.urlopen(f"{url.rstrip('/')}/metrics",
                                timeout=10) as r:
        body = r.read().decode()
    lines = []
    for ln in body.splitlines():
        if not (ln.startswith("areal_alerts_total")
                or ln.startswith("areal_alert_active")
                or ln.startswith("areal_sentinel_")):
            continue
        # Only alerts_total carries a severity label — filtering the
        # active/sentinel lines on it would hide every live alert.
        if severity and ln.startswith("areal_alerts_total") \
                and f'severity="{severity}"' not in ln:
            continue
        if rule and f'rule="{rule}"' not in ln:
            continue
        lines.append(ln)
    if not lines:
        print("no sentinel metrics on the scrape "
              "(sentinel disabled, or no rule matched the filters)")
    for ln in lines:
        print(f"  {ln}")
    # active operator silences ride along — an alert that "never fires"
    # is often just silenced
    try:
        now = time.time()
        for key in name_resolve.find_subtree(
                names.sentinel_silence_root(exp_or_path, trial)):
            d = _json.loads(name_resolve.get(key))
            if float(d.get("until", 0)) > now:
                print(f"  silenced: {d.get('rule')} for another "
                      f"{float(d['until']) - now:.0f}s")
    except Exception:  # noqa: BLE001 — no silences registered
        pass


def silence(experiment: str, trial: str, rule: str, duration: str) -> None:
    """Silence one sentinel rule for a duration — it keeps evaluating
    (state machine advances) but fires are suppressed until expiry."""
    import json as _json

    from areal_tpu.base import name_resolve, names
    from areal_tpu.system.sentinel import parse_duration

    try:
        secs = parse_duration(duration)
    except ValueError as e:
        sys.exit(f"silence: {e}")
    until = time.time() + secs
    name_resolve.add(
        names.sentinel_silence(experiment, trial, rule),
        _json.dumps({"rule": rule, "until": until,
                     "ts": time.time(), "duration_secs": secs}),
        replace=True, delete_on_exit=False,
    )
    print(f"silenced sentinel rule {rule!r} for {secs:g}s "
          f"(until {time.strftime('%H:%M:%S', time.localtime(until))}); "
          f"fires are suppressed and counted as "
          f"areal_sentinel_silenced_total")


def goodput_view(exp_or_url: str, trial: str = "",
                 window_secs: float = 5.0) -> None:
    """Live goodput ledger view (jax-free): per-worker time-in-state
    fractions over a SHORT LIVE WINDOW — two scrapes of
    ``areal_goodput_secs_total`` ``window_secs`` apart, diffed — plus
    the fleet-goodput and live MFU gauges, off the merged scrape (or
    one worker's /metrics when given a url). Windowed on purpose: a
    since-start cumulative split dilutes a live stall by the whole
    run's history (the same reason areal_fleet_goodput is windowed —
    docs/observability.md §Goodput); workers whose counters did not
    move inside the window fall back to their cumulative split, marked
    ``(cum)``."""
    import re as _re
    import urllib.error
    import urllib.request

    if exp_or_url.startswith("http"):
        url = exp_or_url.rstrip("/")
    else:
        from areal_tpu.base import name_resolve, names

        try:
            url = name_resolve.get(names.telemetry_http(exp_or_url, trial))
        except Exception:  # noqa: BLE001 — telemetry off / no http port
            sys.exit(
                f"goodput: no merged telemetry endpoint for "
                f"{exp_or_url}/{trial}.\nEither the run is down or "
                f"telemetry has no http_port — relaunch with "
                f"telemetry.enabled=true goodput.enabled=true "
                f"telemetry.http_port=<port>, or probe one worker: "
                f"goodput <url>."
            )
    if "/metrics" not in url:
        url = url.rstrip("/") + "/metrics"
    lab_re = _re.compile(r'(\w+)="([^"]*)"')

    def fetch():
        try:
            with urllib.request.urlopen(url, timeout=10) as r:
                body = r.read().decode()
        except (urllib.error.URLError, OSError) as e:
            sys.exit(f"goodput: cannot reach {url}: {e}")
        per_worker: dict = {}
        overlap: dict = {}
        extras = []
        for ln in body.splitlines():
            counters = ln.startswith("areal_goodput_secs_total{")
            is_overlap = ln.startswith("areal_goodput_overlap_secs_total{")
            if counters or is_overlap:
                name, _, val = ln.rpartition(" ")
                labels = dict(lab_re.findall(name))
                worker = (
                    f"{labels.get('worker_kind', labels.get('server_id', '?'))}"
                    f":{labels.get('worker_index', '')}"
                ).rstrip(":")
                state = labels.get("state", "?")
                tgt = overlap if is_overlap else per_worker
                tgt.setdefault(worker, {})[state] = \
                    tgt.get(worker, {}).get(state, 0.0) + float(val)
            elif (ln.startswith("areal_fleet_goodput")
                  or ln.startswith("areal_train_mfu")
                  or ln.startswith("areal_train_achieved_tflops")
                  or ln.startswith("areal_genserver_decode_mfu")
                  or ln.startswith("areal_genserver_decode_tflops")
                  or ln.startswith("areal_genserver_prefill_tflops")):
                extras.append(ln)
        return per_worker, overlap, extras

    first, _, _ = fetch()
    if not first:
        print("no goodput counters on the scrape "
              "(goodput.enabled=false, or no ledger export yet)")
        return
    time.sleep(max(window_secs, 0.1))
    cum, overlap, extras = fetch()
    if not cum:
        # The aggregator restarted inside the sampling window and the
        # fresh one has no state yet — same friendly exit as fetch one.
        print("no goodput counters on the second scrape "
              "(aggregator restarted mid-window? retry)")
        return
    states = ("compute", "comm", "data_wait", "idle")
    w = max(len(k) for k in cum)
    print(f"  last {window_secs:g}s window "
          f"((cum) = counters idle in the window, since-start split):")
    print(f"  {'worker':<{w}}  {'total_s':>9}  "
          + "  ".join(f"{s:>9}" for s in states))
    for worker, totals in sorted(cum.items()):
        base = first.get(worker, {})
        delta = {s: max(v - base.get(s, 0.0), 0.0)
                 for s, v in totals.items()}
        row, mark = (delta, "") if sum(delta.values()) > 0 \
            else (totals, " (cum)")
        total = sum(row.values())
        fracs = "  ".join(
            f"{row.get(s, 0.0) / total:>8.1%}" if total > 0
            else f"{'-':>9}" for s in states
        )
        print(f"  {worker:<{w}}  {sum(totals.values()):>9.1f}  "
              f"{fracs}{mark}")
    print("  (rollout rows are task-seconds under concurrency, not a "
          "wall partition — docs/observability.md §Goodput)")
    if overlap:
        print("overlap (work racing the owner's partition, e.g. weight "
              "updates during decode — not in the fractions above):")
        for worker, totals in sorted(overlap.items()):
            split = "  ".join(f"{s}={v:.1f}s"
                              for s, v in sorted(totals.items()))
            print(f"  {worker:<{w}}  {split}")
    if extras:
        print("gauges:")
        for ln in sorted(extras):
            print(f"  {ln}")


def profile_trigger(experiment: str, trial: str, out_dir: str,
                    secs: float = 5.0) -> None:
    from areal_tpu.base import telemetry

    telemetry.request_profiler_capture(experiment, trial, out_dir, secs)
    print(f"profiler trigger set for {experiment}/{trial}: "
          f"{secs}s -> {out_dir} (trainer picks it up within ~1s; check "
          f"with `profile-status {experiment} {trial}`)")


def profile_status(experiment: str, trial: str) -> None:
    from areal_tpu.base import telemetry

    st = telemetry.read_profiler_status(experiment, trial)
    print(st if st is not None else "no capture recorded")


COMMANDS = ("scrape", "trace", "flight-dump", "fleet-status", "spool-status",
            "compile-status", "mem-status", "cordon", "uncordon", "drain",
            "alerts", "silence", "goodput", "profile-trigger",
            "profile-status", "decode-bench", "reward-bench")


def main(argv) -> int:
    if not argv or argv[0] not in COMMANDS:
        print(__doc__, file=sys.stderr)
        return 1
    cmd = argv[0]
    try:
        if cmd == "fleet-status":
            fleet_status(argv[1], argv[2])
        elif cmd == "spool-status":
            spool_status(argv[1], argv[2])
        elif cmd == "compile-status":
            compile_status(argv[1], argv[2])
        elif cmd == "mem-status":
            mem_status(argv[1], argv[2])
        elif cmd == "cordon":
            cordon(argv[1], argv[2], argv[3],
                   " ".join(argv[4:]) or "operator request")
        elif cmd == "uncordon":
            cordon(argv[1], argv[2], argv[3], un=True)
        elif cmd == "drain":
            drain(argv[1], argv[2])
        elif cmd == "scrape":
            if len(argv) > 2:
                scrape_fleet(argv[1], argv[2])
            else:
                scrape(argv[1])
        elif cmd == "trace":
            print_trace(argv[1], argv[2])
        elif cmd == "flight-dump":
            flight_dump(argv[1], argv[2], argv[3])
        elif cmd == "decode-bench":
            decode_bench(
                argv[1],
                int(argv[2]) if len(argv) > 2 else 24,
                int(argv[3]) if len(argv) > 3 else 32,
            )
        elif cmd == "reward-bench":
            if argv[1].startswith("http"):
                reward_bench(argv[1],
                             n_tasks=int(argv[2]) if len(argv) > 2 else 32)
            else:
                reward_bench(argv[1], argv[2],
                             int(argv[3]) if len(argv) > 3 else 32)
        elif cmd == "alerts":
            alerts(argv[1],
                   argv[2] if len(argv) > 2 else "",
                   argv[3] if len(argv) > 3 else "",
                   argv[4] if len(argv) > 4 else "")
        elif cmd == "silence":
            silence(argv[1], argv[2], argv[3], argv[4])
        elif cmd == "goodput":
            if argv[1].startswith("http"):
                goodput_view(argv[1], window_secs=(
                    float(argv[2]) if len(argv) > 2 else 5.0))
            else:
                goodput_view(argv[1], argv[2], window_secs=(
                    float(argv[3]) if len(argv) > 3 else 5.0))
        elif cmd == "profile-trigger":
            profile_trigger(argv[1], argv[2], argv[3],
                            float(argv[4]) if len(argv) > 4 else 5.0)
        elif cmd == "profile-status":
            profile_status(argv[1], argv[2])
    except IndexError:
        print(f"missing operand for {cmd!r}\n\n{__doc__}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
