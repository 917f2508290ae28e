"""Perf probe for the bench workload: isolates device kernel time from host
dispatch/packing overhead and sweeps the knobs that plausibly gate MFU.

Usage: python tools/perf_probe.py [probe ...]
Probes: e2e, grad, phases, mbsweep, remat, trace  (default: e2e grad)

Standalone probes (docs/benchmarks.md Tools):
  packfill [cap ...]                  HOST-ONLY (no TPU, no jax): packing
                                      fill of the bench-shaped length
                                      distribution at each token cap
                                      (default 2048 4096 8192), new
                                      128-grain sweep vs the coarse
                                      512-bucket candidates
  blocksweep [T] [S] [out.json]       sweep flash-attention (block_q,
                                      block_kv) at a geometry (default
                                      the bench grid, 1792x1792) and
                                      record the winner to out.json
                                      (default profiles/flash_blocks.json;
                                      load it via AREAL_FLASH_BLOCK_TABLE)
                                      — needs a real TPU: the kernel has
                                      no interpreter on this jax
  reshard-bench [src] [dst] [mb] [layers] [dim]
                                      time the mesh→mesh on-device
                                      reshard (parallel/reshard.py):
                                      build a synthetic stacked-layer
                                      tree, move it src-spec → dst-spec
                                      (default f2t2 → d4) and report the
                                      plan plus per-transfer-group
                                      throughput at the given group
                                      budget (default 64 MB); runs on
                                      CPU test meshes or real chips
                                      (docs/weight_sync.md §device)
  ring-bench [sp,sp,...] [seq,seq,...]
                                      sweep ring attention v2
                                      (parallel/ring.py) over
                                      (sp, seq_len): fwd+bwd step time
                                      zigzag vs the naive v1 oracle plus
                                      the structural causal-skip ratio
                                      ((n+1)/2n at sp=n); runs on CPU
                                      host meshes (JAX_PLATFORMS=cpu +
                                      --xla_force_host_platform_device_
                                      count=N) or real chips
                                      (docs/parallelism.md §PP∘SP)
  moe-bench [E,E,...] [k,k,...] [cf,cf,...]
                                      sweep MoE dispatch (models/moe.py)
                                      over (num_experts, top_k,
                                      capacity_factor): one MoE layer's
                                      fwd+bwd step time, sort-based
                                      grouped path (default) vs the
                                      one-hot einsum oracle, plus the
                                      routed dropped fraction; runs on
                                      CPU or real chips
                                      (docs/parallelism.md §Expert
                                      parallelism)

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

Writes findings to stdout; `trace` saves a jax.profiler trace under
profiles/ for offline inspection.
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


def compile_status(experiment: str, trial: str) -> None:
    """Compile observatory view of a live run (jax-free), from the merged
    Prometheus scrape: per-jit-entry-point compile counts / total compile
    seconds / distinct compiled shapes across the fleet, the persistent-
    cache hit ratio, recompile-storm events, and which workers have a
    compile in flight RIGHT NOW — the first stop of the "my run is wedged
    in warmup / my step got slow" runbook (docs/operations.md)."""
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


def packfill(caps=None) -> None:
    """Host-only packing-fill probe (ISSUE 8 / ROADMAP item 1): what fill
    the micro-batch packer achieves on the bench trajectory distribution
    at each token cap — the padding factor the reported MFU divides by.
    No TPU and no jax needed; safe to run anywhere."""
    from areal_tpu.api.data import MicroBatchSpec
    from areal_tpu.backend import microbatch as mbu
    from areal_tpu.base.testing import bench_trajectory_sample

    caps = [int(c) for c in caps] if caps else [2048, 4096, 8192]
    n_seq = 32
    batch, seqlens = bench_trajectory_sample(0, n_seq)
    print(f"[packfill] {n_seq} bench-shaped seqs, "
          f"{int(seqlens.sum())} tokens, lens "
          f"{int(seqlens.min())}..{int(seqlens.max())}")
    for cap in caps:
        spec = MicroBatchSpec(max_tokens_per_mb=cap)
        for label, fb in (("fine(128)", None), ("coarse(512)", 512)):
            mbs = mbu.split_into_microbatches(
                batch, spec, length_bucket=512, rows_bucket=4,
                seqs_bucket=16, fill_bucket=fb,
            )
            R, L = mbs[0].layout.shape
            print(f"[packfill] cap={cap:<6} {label:<12} "
                  f"n_mbs={len(mbs):<3} R={R:<2} L={L:<5} "
                  f"fill={mbu.pack_fill(mbs):.4f}")


def _blocksweep_candidates(T: int, S: int):
    """All (block_q, block_kv) the kernel accepts at this geometry:
    128-multiples dividing the respective dim, bounded to keep q/kv tiles
    within a sane VMEM envelope. Pure + CPU-testable."""
    from areal_tpu.ops.pallas.flash_attention import LANE

    def divs(n):
        return [b for b in range(LANE, min(n, 2048) + 1, LANE) if n % b == 0]

    return [(bq, bkv) for bq in divs(T) for bkv in divs(S)]


def blocksweep(T: int = 1792, S: int = 1792, out_path: str = None,
               Hq: int = 14, Hkv: int = 2, D: int = 64, B: int = 2) -> None:
    """Sweep flash-attention block sizes at a (T, S) geometry — default
    the bench grid after the r08 fill sweep (L=1792, R=2, Qwen2.5-0.5B
    heads) — timing fwd+bwd per candidate, and record the winner as a
    geometry-keyed JSON table consumable via AREAL_FLASH_BLOCK_TABLE."""
    import json as _json
    import os as _os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.ops.pallas import flash_attention as fa

    if jax.default_backend() != "tpu":
        sys.exit(
            "blocksweep: needs a real TPU — interpreted kernel timings "
            "say nothing about the chip. Results land in the JSON table "
            "for AREAL_FLASH_BLOCK_TABLE."
        )
    # A leftover env pin/table would override every per-candidate
    # set_block_sizes below — the sweep would time one config N times and
    # record a meaningless winner. Clear both for the sweep's lifetime.
    for var in ("AREAL_FLASH_BLOCKS", "AREAL_FLASH_BLOCK_TABLE"):
        if _os.environ.pop(var, None) is not None:
            print(f"[blocksweep] ignoring {var} for the sweep", flush=True)
    fa.clear_block_table()
    cands = _blocksweep_candidates(T, S)
    if not cands:
        sys.exit(f"blocksweep: no 128-multiple blocks divide T={T} S={S}")
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, T, Hq, D).astype(np.float32) * 0.3,
                    jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, S, Hkv, D).astype(np.float32) * 0.3,
                    jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, S, Hkv, D).astype(np.float32) * 0.3,
                    jnp.bfloat16)
    # bench-like packing: two docs per row
    seg = np.ones((B, T), np.int32)
    seg[:, T // 2:] = 2
    pos = np.concatenate([np.arange(T // 2), np.arange(T - T // 2)])
    pos = np.tile(pos, (B, 1)).astype(np.int32)
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)

    def run(bq, bkv):
        fa.set_block_sizes(T, S, bq, bkv)

        def loss(q):
            o = fa.flash_attention(q, k, v, seg, seg, q_positions=pos,
                                   kv_positions=pos)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        g = jax.jit(jax.grad(loss))
        g(q).block_until_ready()  # compile
        t0 = time.perf_counter()
        for _ in range(10):
            out = g(q)
        out.block_until_ready()
        return (time.perf_counter() - t0) / 10

    results = []
    for bq, bkv in cands:
        try:
            dt = run(bq, bkv)
        except Exception as e:  # noqa: BLE001 — kernel may reject a combo
            print(f"[blocksweep] bq={bq:<5} bkv={bkv:<5} FAILED: "
                  f"{type(e).__name__}", flush=True)
            continue
        results.append((dt, bq, bkv))
        print(f"[blocksweep] bq={bq:<5} bkv={bkv:<5} {dt * 1e3:8.2f} ms",
              flush=True)
    fa.clear_block_table()
    if not results:
        sys.exit("blocksweep: every candidate failed")
    results.sort()
    dt, bq, bkv = results[0]
    heur = fa.pick_block_sizes(T, S)
    print(f"[blocksweep] winner: bq={bq} bkv={bkv} ({dt * 1e3:.2f} ms; "
          f"pick_tile default is {heur})")
    out_path = out_path or _os.path.join("profiles", "flash_blocks.json")
    _os.makedirs(_os.path.dirname(out_path) or ".", exist_ok=True)
    table = {}
    if _os.path.exists(out_path):
        try:
            with open(out_path) as f:
                table = _json.load(f)
        except (OSError, ValueError):
            pass
    table[f"{T},{S}"] = [bq, bkv]
    with open(out_path, "w") as f:
        _json.dump(table, f, indent=1, sort_keys=True)
    print(f"[blocksweep] recorded to {out_path} "
          f"(use: AREAL_FLASH_BLOCK_TABLE={out_path})")


def reshard_bench(src_spec: str = "f2t2", dst_spec: str = "d4",
                  group_mb: int = 64, n_layers: int = 8,
                  dim: int = 1024) -> None:
    """Time the mesh→mesh on-device reshard (parallel/reshard.py) between
    two ParallelSpecs on whatever devices this process has (CPU test
    meshes under JAX_PLATFORMS=cpu, real chips otherwise): per
    transfer-group dispatch→barrier latency and MB/s, plus the end-to-end
    publish figure the ``device`` weight-sync transport would pay."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.parallel import mesh as pm
    from areal_tpu.parallel import reshard as rsh
    from areal_tpu.parallel import sharding as psh

    src = pm.ParallelSpec.parse(src_spec)
    dst = pm.ParallelSpec.parse(dst_spec)
    n_dev = len(jax.devices())
    for label, spec in (("src", src), ("dst", dst)):
        if spec.world_size > n_dev:
            sys.exit(f"reshard-bench: {label} spec '{spec}' needs "
                     f"{spec.world_size} devices, have {n_dev} "
                     f"(JAX_PLATFORMS=cpu + "
                     f"XLA_FLAGS=--xla_force_host_platform_device_count=N "
                     f"for a host-mesh dry run)")
    src_mesh, dst_mesh = pm.make_mesh(src), pm.make_mesh(dst)
    # Transformer-shaped synthetic tree: a stacked layer dict sharded the
    # way training shards it, so the plan exercises the real per-leaf
    # PartitionSpecs rather than a flat blob.
    tree = {
        "layers": {
            "wq": jnp.zeros((n_layers, dim, dim), jnp.bfloat16),
            "wo": jnp.zeros((n_layers, dim, dim), jnp.bfloat16),
            "w_up": jnp.zeros((n_layers, dim, 4 * dim), jnp.bfloat16),
            "w_down": jnp.zeros((n_layers, 4 * dim, dim), jnp.bfloat16),
        },
        "embedding": jnp.zeros((4096, dim), jnp.bfloat16),
    }
    specs = jax.tree.map(lambda _: None, tree)
    specs["layers"] = {
        "wq": psh.P(None, "fsdp", "tp"), "wo": psh.P(None, "tp", "fsdp"),
        "w_up": psh.P(None, "fsdp", "tp"),
        "w_down": psh.P(None, "tp", "fsdp"),
    }
    specs["embedding"] = psh.P("fsdp", "tp")
    src_sh = jax.tree.map(
        lambda s: jax.sharding.NamedSharding(src_mesh, s or psh.P()), specs,
        is_leaf=lambda x: x is None or isinstance(x, psh.P),
    )
    dst_sh = jax.tree.map(
        lambda s: jax.sharding.NamedSharding(dst_mesh, s or psh.P()), specs,
        is_leaf=lambda x: x is None or isinstance(x, psh.P),
    )
    tree = jax.tree.map(jax.device_put, tree, src_sh)
    jax.block_until_ready(tree)
    flat_src = rsh._flatten(tree)
    flat_dst = rsh._flatten(dst_sh)
    plan = rsh.plan_reshard(flat_src, flat_dst,
                            group_bytes=int(group_mb) << 20)
    print(f"[reshard-bench] {src} -> {dst} on {n_dev} "
          f"{jax.devices()[0].platform} devices: "
          f"{plan.total_bytes >> 20} MB total, plan {plan.describe()}")
    t_all = time.perf_counter()
    for gi, group in enumerate(plan.groups):
        g_bytes = sum(rsh._leaf_nbytes(flat_src[n]) for n in group)
        t0 = time.perf_counter()
        rsh._move_group(group, flat_src, flat_dst)
        dt = time.perf_counter() - t0
        print(f"[reshard-bench] group {gi}: {len(group)} leaves, "
              f"{g_bytes >> 20:>5} MB, {dt * 1e3:8.2f} ms, "
              f"{g_bytes / dt / 2 ** 20:10.1f} MB/s")
    dt_all = time.perf_counter() - t_all
    t0 = time.perf_counter()
    _, plan2 = rsh.reshard_pytree(tree, dst_sh, group_mb=int(group_mb))
    dt_pub = time.perf_counter() - t0
    mbs = plan.moved_bytes / 2 ** 20
    print(f"[reshard-bench] grouped total: {dt_all * 1e3:.2f} ms "
          f"({mbs / max(dt_all, 1e-9):.1f} MB/s moved); "
          f"end-to-end reshard_pytree: {dt_pub * 1e3:.2f} ms "
          f"(zero-copy leaves: {len(plan2.identical)})")


def ring_bench(sp_list=None, seq_list=None, reps: int = 3) -> None:
    """Sweep ring attention v2 (parallel/ring.py) over (sp, seq_len) on
    whatever devices this process has (host meshes under JAX_PLATFORMS=cpu
    + XLA_FLAGS=--xla_force_host_platform_device_count=N, real chips
    otherwise): fwd+bwd step time for the zig-zag schedule vs the
    contiguous v1 oracle, plus the structural causal-skip ratio from the
    trace-time area counters ((n+1)/2n at sp=n)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.parallel import mesh as pm
    from areal_tpu.parallel import ring as ring_mod

    n_dev = len(jax.devices())
    sp_list = sp_list or [s for s in (1, 2, 4, 8) if s <= n_dev]
    seq_list = seq_list or [1024, 2048, 4096]
    Hq, Hkv, Dh = 4, 2, 64
    print(f"[ring-bench] {n_dev} {jax.devices()[0].platform} devices; "
          f"B=1 Hq={Hq} Hkv={Hkv} Dh={Dh}; fwd+bwd attention step, "
          f"zigzag (active) vs naive (v1 oracle)")
    print(f"[ring-bench] {'sp':>3} {'seq_len':>8} {'zigzag_ms':>10} "
          f"{'naive_ms':>9} {'speedup':>8} {'skip_ratio':>10}")
    rng = np.random.RandomState(0)
    for sp in sp_list:
        mesh = pm.make_mesh(pm.ParallelSpec(sp=sp))
        for T in seq_list:
            if T % max(2 * sp, 1):
                continue
            q = jnp.asarray(rng.randn(1, T, Hq, Dh).astype(np.float32) * .1)
            k = jnp.asarray(rng.randn(1, T, Hkv, Dh).astype(np.float32) * .1)
            v = jnp.asarray(rng.randn(1, T, Hkv, Dh).astype(np.float32) * .1)
            seg = jnp.ones((1, T), jnp.int32)
            res = {}
            for sched in ("zigzag", "naive"):
                def loss(q, k, v, sched=sched):
                    o = ring_mod.ring_attention(q, k, v, seg, mesh,
                                                schedule=sched)
                    return jnp.sum(o * o)

                f = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                ring_mod.reset_ring_counters()
                jax.block_until_ready(f(q, k, v))  # compile; fill counters
                ratio = ring_mod.ring_skip_ratio()
                t0 = time.perf_counter()
                for _ in range(reps):
                    g = f(q, k, v)
                jax.block_until_ready(g)
                res[sched] = ((time.perf_counter() - t0) / reps * 1e3, ratio)
            zz, nv = res["zigzag"], res["naive"]
            print(f"[ring-bench] {sp:>3} {T:>8} {zz[0]:>10.2f} "
                  f"{nv[0]:>9.2f} {nv[0] / max(zz[0], 1e-9):>7.2f}x "
                  f"{zz[1]:>10.3f}")


def moe_bench(e_list=None, k_list=None, cf_list=None, reps: int = 3,
              n_tokens: int = 4096, dim: int = 256) -> None:
    """Sweep the MoE dispatch paths (models/moe.py) over (num_experts,
    top_k, capacity_factor): one MoE layer's fwd+bwd step time for the
    sort-based grouped-GEMM path (the default) vs the one-hot einsum
    oracle (AREAL_MOE_DISPATCH=einsum), plus the fraction of routed
    assignments dropped at the capacity boundary. The einsum oracle pays
    O(tokens x E x capacity) ~ O(k*cf*tokens^2) one-hot dispatch/combine
    contractions plus dense [E, C] buffers; grouped replaces them with a
    sort + ragged GEMMs. Caveat: ragged_dot's CPU lowering scales with E,
    so host-mesh sweeps understate the grouped win at large E — the TPU
    kernel does not."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.models import config as mcfg
    from areal_tpu.models import moe as moe_mod

    e_list = e_list or [4, 8, 16, 32]
    k_list = k_list or [2]
    cf_list = cf_list or [1.0, 2.0]
    print(f"[moe-bench] {len(jax.devices())} "
          f"{jax.devices()[0].platform} devices; tokens={n_tokens} "
          f"dim={dim} ffn={dim * 2}; fwd+bwd one MoE layer, "
          f"grouped (active) vs einsum (oracle)")
    print(f"[moe-bench] {'E':>4} {'top_k':>5} {'cap_f':>5} "
          f"{'grouped_ms':>10} {'einsum_ms':>10} {'speedup':>8} "
          f"{'dropped':>8}")
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(8, n_tokens // 8, dim)
                    .astype(np.float32) * 0.1)
    for E in e_list:
        for k in k_list:
            if k > E:
                continue
            for cf in cf_list:
                moe = mcfg.MoEConfig(num_experts=E, top_k=k,
                                     capacity_factor=cf,
                                     routed_intermediate_dim=dim * 2)
                tcfg = mcfg.tiny_config(hidden_dim=dim, n_q_heads=4,
                                        n_kv_heads=2, moe=_dc.asdict(moe))
                stacked = moe_mod.init_moe_params(
                    _dc.replace(tcfg, n_layers=1), jax.random.PRNGKey(0),
                    jnp.float32)
                lp = {name: w[0] for name, w in stacked.items()}
                res = {}
                for disp in ("grouped", "einsum"):
                    def loss(lp, x, disp=disp):
                        y, aux = moe_mod.moe_mlp(x, lp, moe, dispatch=disp)
                        return jnp.sum(y * y), aux["dropped_frac"]

                    f = jax.jit(jax.grad(loss, has_aux=True))
                    _, dropped = f(lp, x)
                    jax.block_until_ready(dropped)  # compile
                    t0 = time.perf_counter()
                    for _ in range(reps):
                        g, dropped = f(lp, x)
                    jax.block_until_ready(g)
                    res[disp] = ((time.perf_counter() - t0) / reps * 1e3,
                                 float(dropped))
                gr, ei = res["grouped"], res["einsum"]
                print(f"[moe-bench] {E:>4} {k:>5} {cf:>5.2f} "
                      f"{gr[0]:>10.2f} {ei[0]:>10.2f} "
                      f"{ei[0] / max(gr[0], 1e-9):>7.2f}x {gr[1]:>8.3f}")


def _dispatch_fleet_commands(argv) -> bool:
    if not argv or argv[0] not in ("scrape", "decode-bench", "trace",
                                   "flight-dump", "packfill", "blocksweep",
                                   "profile-trigger", "profile-status",
                                   "fleet-status", "drain", "cordon",
                                   "uncordon", "reward-bench", "alerts",
                                   "silence", "goodput", "reshard-bench",
                                   "ring-bench", "moe-bench",
                                   "spool-status", "compile-status",
                                   "mem-status"):
        return False
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
        elif cmd == "packfill":
            packfill(argv[1:])
        elif cmd == "blocksweep":
            blocksweep(
                int(argv[1]) if len(argv) > 1 else 1792,
                int(argv[2]) if len(argv) > 2 else 1792,
                argv[3] if len(argv) > 3 else None,
            )
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
        elif cmd == "reshard-bench":
            reshard_bench(
                argv[1] if len(argv) > 1 else "f2t2",
                argv[2] if len(argv) > 2 else "d4",
                int(argv[3]) if len(argv) > 3 else 64,
                int(argv[4]) if len(argv) > 4 else 8,
                int(argv[5]) if len(argv) > 5 else 1024,
            )
        elif cmd == "ring-bench":
            ring_bench(
                [int(x) for x in argv[1].split(",")] if len(argv) > 1
                else None,
                [int(x) for x in argv[2].split(",")] if len(argv) > 2
                else None,
            )
        elif cmd == "moe-bench":
            moe_bench(
                [int(x) for x in argv[1].split(",")] if len(argv) > 1
                else None,
                [int(x) for x in argv[2].split(",")] if len(argv) > 2
                else None,
                [float(x) for x in argv[3].split(",")] if len(argv) > 3
                else None,
            )
        elif cmd == "profile-trigger":
            profile_trigger(argv[1], argv[2], argv[3],
                            float(argv[4]) if len(argv) > 4 else 5.0)
        elif cmd == "profile-status":
            profile_status(argv[1], argv[2])
    except IndexError:
        print(f"missing operand for {cmd!r}\n\n{__doc__}", file=sys.stderr)
        sys.exit(1)
    return True


if _dispatch_fleet_commands(sys.argv[1:]):
    sys.exit(0)

import jax
import jax.numpy as jnp
import numpy as np


def build(remat=True, length_bucket=512, rows_bucket=4, seqs_bucket=16,
          attn_impl="auto"):
    from areal_tpu.algorithms.ppo import PPOActorInterface, PPOHyperparameters
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model import FinetuneSpec, Model
    from areal_tpu.backend.jax_train import JaxTrainBackend, OptimizerConfig
    from areal_tpu.models import transformer
    from areal_tpu.models.config import TransformerConfig

    cfg = TransformerConfig(
        n_layers=24, hidden_dim=896, n_q_heads=14, n_kv_heads=2, head_dim=64,
        intermediate_dim=4864, vocab_size=151936, rotary_base=1e6,
        tie_word_embeddings=True, use_attention_bias=True, dtype="bfloat16",
    )
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    model = Model("actor", (cfg, params), tokenizer=None)
    backend = JaxTrainBackend(
        optimizer=OptimizerConfig(lr=1e-5, lr_scheduler_type="constant",
                                  warmup_steps_proportion=0.0),
        compute_dtype="bfloat16", length_bucket=length_bucket,
        rows_bucket=rows_bucket, seqs_bucket=seqs_bucket, remat=remat,
        attn_impl=attn_impl,
    )
    model = backend.initialize(model, FinetuneSpec(1, 512, 64))
    hp = PPOHyperparameters(ppo_n_minibatches=1, adv_norm=True,
                            kl_ctl=0.0, disable_value=True)
    iface = PPOActorInterface(hp)

    rng = np.random.RandomState(0)
    n_seq = 32
    plens = rng.randint(200, 257, n_seq)
    glens = rng.randint(512, 769, n_seq)
    seqlens = (plens + glens).astype(int)
    total = int(seqlens.sum())
    toks = rng.randint(2, cfg.vocab_size, total).astype(np.int32)
    pmask, lps = [], []
    for p, g in zip(plens, glens):
        pmask.append(np.concatenate([np.ones(p, np.int32), np.zeros(g, np.int32)]))
        lps.append(np.concatenate([np.zeros(p, np.float32),
                                   -rng.rand(g).astype(np.float32)]))
    batch = SequenceSample.from_default(
        ids=[f"b{i}" for i in range(n_seq)],
        data={
            "packed_input_ids": toks,
            "prompt_mask": np.concatenate(pmask),
            "packed_logprobs": np.concatenate(lps),
            "rewards": rng.rand(n_seq).astype(np.float32),
            "seq_no_eos_mask": np.zeros(n_seq, np.float32),
        },
        seqlens=seqlens.tolist(),
    )
    return cfg, model, iface, batch, total


PEAK = 197e12  # v5e bf16


def report(tag, total, dt, steps, cfg_nparams, remat):
    tps = steps * total / dt
    mfu = 6.0 * cfg_nparams * total * steps / dt / PEAK
    print(f"[{tag}] {tps:,.0f} tok/s  step={dt/steps*1e3:.0f}ms  "
          f"MFU(6N)={mfu:.3f}", flush=True)


def main():
    probes = sys.argv[1:] or ["e2e", "grad"]
    from areal_tpu.api.data import MicroBatchSpec
    from areal_tpu.backend import microbatch as mbu
    from areal_tpu.models import transformer

    spec = MicroBatchSpec(max_tokens_per_mb=4096)

    if "e2e" in probes or "grad" in probes or "trace" in probes:
        cfg, model, iface, batch, total = build()
        nparams = transformer.param_count(cfg)
        eng = model.module
        iface.train_step(model, batch, spec)  # compile
        jax.block_until_ready(eng.params)

        if "e2e" in probes:
            t0 = time.perf_counter()
            for _ in range(3):
                iface.train_step(model, batch, spec)
            jax.block_until_ready(eng.params)
            report("e2e remat=T mb=4096", total, time.perf_counter() - t0, 3,
                   nparams, True)

        if "grad" in probes or "trace" in probes:
            # Device-only: one microbatch's grad step, timed in a tight loop
            # with a single final sync → pure kernel throughput.
            from areal_tpu.algorithms import ppo as ppomod
            extra = ppomod.compute_advantages_and_returns(batch, iface.hp, 0.0)
            extra.pop("_mean_kl")
            b2 = ppomod.attach_keys(batch, extra)
            ppomod.normalize_advantages(b2, iface.hp)
            mbs = mbu.split_into_microbatches(
                b2, spec, length_bucket=512, rows_bucket=4, seqs_bucket=16)
            gfn = eng._get_grad_fn(iface._loss_fn, with_carry=False)
            dbs = [eng._device_batch(mb) for mb in mbs]
            ntok = sum(mb.n_tokens for mb in mbs)
            ncells = sum(int(np.prod(mb.grids["tokens"].shape)) for mb in mbs)
            print(f"[pack] {len(mbs)} mbs, fill={ntok/ncells:.2f} "
                  f"({ntok} tok / {ncells} cells)", flush=True)
            denom = jnp.asarray(1000.0, jnp.float32)
            one = jnp.asarray(1.0, jnp.float32)
            for db in dbs:
                gfn(eng.params, db, denom, one, one)  # compile each shape
            jax.block_until_ready(eng.params)

            if "grad" in probes:
                t0 = time.perf_counter()
                outs = None
                for _ in range(3):
                    for db in dbs:
                        outs = gfn(eng.params, db, denom, one, one)
                jax.block_until_ready(outs)
                report("grad-only (fwd+bwd, no opt)", ntok,
                       time.perf_counter() - t0, 3, nparams, True)

            if "trace" in probes:
                import os
                os.makedirs("profiles", exist_ok=True)
                with jax.profiler.trace("profiles/bench_step"):
                    iface.train_step(model, batch, spec)
                    jax.block_until_ready(eng.params)
                print("[trace] saved to profiles/bench_step", flush=True)

    if "phases" in probes:
        cfg, model, iface, batch, total = build()
        eng = model.module
        iface.train_step(model, batch, spec)
        jax.block_until_ready(eng.params)
        from areal_tpu.algorithms import ppo as ppomod
        t = {}
        for _ in range(3):
            t0 = time.perf_counter()
            extra = ppomod.compute_advantages_and_returns(batch, iface.hp, 0.0)
            extra.pop("_mean_kl")
            b2 = ppomod.attach_keys(batch, extra)
            ppomod.normalize_advantages(b2, iface.hp)
            t["adv+norm"] = t.get("adv+norm", 0) + time.perf_counter() - t0
            t0 = time.perf_counter()
            mbs = mbu.split_into_microbatches(
                b2, spec, length_bucket=512, rows_bucket=4, seqs_bucket=16)
            t["split+pack"] = t.get("split+pack", 0) + time.perf_counter() - t0
            t0 = time.perf_counter()
            dbs = [eng._device_batch(mb) for mb in mbs]
            t["transfer"] = t.get("transfer", 0) + time.perf_counter() - t0
            gfn = eng._get_grad_fn(iface._loss_fn, with_carry=False)
            t0 = time.perf_counter()
            denom = jnp.asarray(1000.0, jnp.float32)
            one = jnp.asarray(1.0, jnp.float32)
            o = None
            ga = None
            for db in dbs:
                loss, stats, grads = gfn(eng.params, db, denom, one, one)
                ga = grads if ga is None else jax.tree.map(jnp.add, ga, grads)
                o = loss
            jax.block_until_ready(o)
            t["grad+acc"] = t.get("grad+acc", 0) + time.perf_counter() - t0
            t0 = time.perf_counter()
            jax.block_until_ready(ga)
            t["acc_drain"] = t.get("acc_drain", 0) + time.perf_counter() - t0
        for k, v in t.items():
            print(f"[phase] {k}: {v/3*1e3:.0f}ms", flush=True)

    if "remat" in probes:
        cfg, model, iface, batch, total = build(remat=False)
        nparams = transformer.param_count(cfg)
        iface.train_step(model, batch, spec)
        jax.block_until_ready(model.module.params)
        t0 = time.perf_counter()
        for _ in range(3):
            iface.train_step(model, batch, spec)
        jax.block_until_ready(model.module.params)
        report("e2e remat=F mb=4096", total, time.perf_counter() - t0, 3,
               nparams, False)

    if "mbsweep" in probes:
        for cap in (8192, 16384, 32768):
            cfg, model, iface, batch, total = build()
            nparams = transformer.param_count(cfg)
            sp = MicroBatchSpec(max_tokens_per_mb=cap)
            iface.train_step(model, batch, sp)
            jax.block_until_ready(model.module.params)
            t0 = time.perf_counter()
            for _ in range(3):
                iface.train_step(model, batch, sp)
            jax.block_until_ready(model.module.params)
            report(f"e2e remat=T mb={cap}", total, time.perf_counter() - t0,
                   3, nparams, True)


if __name__ == "__main__":
    main()
