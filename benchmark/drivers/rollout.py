"""Driver ``rollout``: the generation fleet under a closed loop of grouped
requests, with or without weight bumps.

Two processes. ``--role fleet`` holds the chip: generation servers and the
gserver manager composed as ``launcher.gen_fleet_entry`` composes them
(same experiment config → same server and manager configs → same replica
meshes), with weights made on the device from ``--seed`` instead of read
from a checkpoint through torch, plus a small control endpoint of the
benchmark's own (profiler window, device report, the plain reference on
the weights being served). The main role is the CPU-pinned client: it
draws the traffic, drives ``PartialRolloutClient`` over HTTP, publishes
weight versions the way the trainer does (``WeightStreamPublisher`` + the
version key the manager watches) and takes every end-to-end number on its
own clock.
"""

from __future__ import annotations

import asyncio
import os
import sys
import threading
import time
from typing import Any, Dict, List

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import driverlib as dl  # noqa: E402
from benchmark import harness  # noqa: E402



# ---------------------------------------------------------------------------
# fleet role: holds the chip
# ---------------------------------------------------------------------------


def compose_fleet(exp, model_cfg, params):
    """(servers, manager) — not yet started — built the way
    ``launcher.gen_fleet_entry`` builds them from an experiment config."""
    import jax

    from areal_tpu.apps import launcher
    from areal_tpu.system.generation_server import GenerationServer
    from areal_tpu.system.gserver_manager import GserverManager

    setup = exp.initial_setup()
    server_cfgs = setup["gen_servers"]
    eos = getattr(launcher._resolve_tokenizer(exp), "eos_token_id", None)
    meshes = launcher.gen_replica_meshes(exp, len(server_cfgs),
                                         jax.local_devices())
    servers = []
    for sc, mesh in zip(server_cfgs, meshes):
        if eos is not None:
            sc.eos_token_id = int(eos)
        servers.append(GenerationServer(sc, model_cfg, params, mesh=mesh))
    return servers, GserverManager(setup["gserver_manager"])


def fleet_main(spec: Dict[str, Any]) -> int:
    from aiohttp import web

    from benchmark import weights

    t_mark = time.time()
    import jax

    from areal_tpu.base.compile_watch import enable_compilation_cache
    from areal_tpu.models import generate as genmod
    from areal_tpu.ops import attention

    enable_compilation_cache()
    device = dl.require_device(spec)
    exp = dl.build_experiment(spec, name_resolve=True)
    model_cfg = weights.model_config(spec["config"])
    # float32: what launcher._build_gen_model hands the servers today
    # (models/hf.load_hf_model's default dtype).
    params = weights.make_params(model_cfg, spec["seed"], dtype="float32")
    servers, manager = compose_fleet(exp, model_cfg, params)
    del params
    trace = dl.TraceWindow(spec["out"])
    if spec["trace"]:
        for srv in servers:
            dl.wrap_span(srv, "_decode_batch", "fleet/decode_batch")
            dl.wrap_span(srv, "_prefill_fn", "fleet/prefill_call")
            dl.wrap_span(srv, "_decode_fn", "fleet/decode_call")
            dl.wrap_span(srv, "_stream_and_put_weights", "fleet/weight_swap")
        for fn in ("stack_states", "grow_state", "slice_state",
                   "pad_prompts"):
            dl.wrap_span(genmod, fn, "fleet/" + fn)
        dl.wrap_span(jax, "device_get", "fleet/device_get")
    stop = asyncio.Event()

    def report() -> Dict[str, Any]:
        return {"device": device, "memory_peak_bytes": dl.memory_peak_bytes(),
                "compile_cache": dl.cache_counts(),
                "attention": attention.dispatch_counts()}

    async def h_info(_):
        return web.json_response(report())

    async def h_trace_start(_):  # off the loop: the servers keep serving
        await asyncio.to_thread(trace.start)
        return web.json_response({"ok": True})

    async def h_trace_stop(_):
        await asyncio.to_thread(trace.stop)
        return web.json_response({"ok": True})

    async def h_reference(request):
        d = await request.json()
        live = servers[int(d.get("server", 0))].params
        lp = await asyncio.to_thread(
            dl.reference_logprobs, live, spec["config"],
            np.asarray(d["tokens"], np.int32))
        return web.json_response({"logprobs": lp.tolist(),
                                  "version": servers[0].version})

    async def h_finish(_):
        red = await asyncio.to_thread(trace.reduce)
        return web.json_response({**report(), "trace": red})

    async def h_shutdown(_):
        stop.set()
        return web.json_response({"ok": True})

    async def main():
        from areal_tpu.base import network

        urls = [await srv.start() for srv in servers]
        mgr_url = await manager.start()
        app = web.Application(client_max_size=64 << 20)
        app.router.add_post("/info", h_info)
        app.router.add_post("/trace_start", h_trace_start)
        app.router.add_post("/trace_stop", h_trace_stop)
        app.router.add_post("/reference", h_reference)
        app.router.add_post("/finish", h_finish)
        app.router.add_post("/shutdown", h_shutdown)
        runner = web.AppRunner(app)
        await runner.setup()
        port = network.find_free_port()
        await web.TCPSite(runner, "127.0.0.1", port).start()
        harness.write_json(os.path.join(spec["out"], "fleet.json"), {
            "control": f"http://127.0.0.1:{port}", "manager": mgr_url,
            "servers": urls, "device": device,
            "param_stats": weights.flat_stats(servers[0].params),
            "fleet_up_s": time.time() - spec["t0"],
            "fleet_build_s": time.time() - t_mark,
        })
        await stop.wait()
        await manager.stop()
        for srv in servers:
            await srv.stop()
        await runner.cleanup()

    asyncio.run(main())
    return 0


# ---------------------------------------------------------------------------
# main role: the CPU-pinned client
# ---------------------------------------------------------------------------


class CountingSession:
    """An ``aiohttp.ClientSession`` that also tells ``on_tokens`` when a
    ``/generate`` reply arrived and how many tokens it carried — the
    client-side clock ``gen_tok_s_chip`` is taken on."""

    def __init__(self, session, on_tokens):
        self._s, self._on = session, on_tokens

    def post(self, url, **kw):
        cm = self._s.post(url, **kw)
        return _CountedPost(cm, self._on) if url.endswith("/generate") else cm

    def get(self, url, **kw):
        return self._s.get(url, **kw)


class _CountedPost:
    def __init__(self, cm, on_tokens):
        self._cm, self._on = cm, on_tokens

    async def __aenter__(self):
        return _CountedReply(await self._cm.__aenter__(), self._on)

    async def __aexit__(self, *exc):
        return await self._cm.__aexit__(*exc)


class _CountedReply:
    def __init__(self, reply, on_tokens):
        self._r, self._on = reply, on_tokens
        self.status = reply.status

    async def json(self):
        d = await self._r.json()
        if self.status == 200:
            self._on(time.monotonic(), len(d.get("output_ids") or ()))
        return d


def draw_version(param_stats: Dict[str, Any], seed: int, version: int):
    """Another version's weights, bf16 on the host as the trainer ships
    them: each tensor normal with the mean and std of the served one."""
    import ml_dtypes

    out = []
    for i, (name, (shape, mean, std)) in enumerate(sorted(
            param_stats.items())):
        rng = np.random.default_rng([int(seed), int(version), i])
        a = rng.standard_normal(tuple(shape), dtype=np.float32)
        a *= np.float32(std)
        a += np.float32(mean)
        out.append((name, a.astype(ml_dtypes.bfloat16)))
    return out


def client_main(spec: Dict[str, Any]) -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import json

    from areal_tpu.system.weight_stream import WeightStreamPublisher
    from benchmark import traffic

    t, out = spec["traffic"], spec["out"]
    env = harness.child_env(cpu=(spec["platform"] == "cpu"))
    if spec["platform"] != "cpu":
        env.pop("JAX_PLATFORMS", None)
    fleet = harness.Child(
        [sys.executable, os.path.abspath(__file__), "--spec",
         spec["spec_path"], "--role", "fleet"],
        env, os.path.join(out, "fleet.log"), own_session=False)
    publisher = None
    try:
        exp = dl.build_experiment(spec, name_resolve=True)
        groups = traffic.make_groups(t["shape"], t["n_groups"], spec["seed"],
                                     spec["config"]["vocab_size"])
        fleet_path = os.path.join(out, "fleet.json")
        while not os.path.isfile(fleet_path):
            if not fleet.alive():
                raise RuntimeError("the fleet process died while starting:\n"
                                   + fleet.log_tail())
            time.sleep(0.2)
        with open(fleet_path) as f:
            info = json.load(f)
        bumps_cfg = t.get("bumps")
        n_bumps = 0
        versions: Dict[int, Any] = {}
        if bumps_cfg:
            # only bumps that can land inside the window are published
            n_bumps = 1 + int((spec["seconds"] - bumps_cfg["leave_s"]
                               - bumps_cfg["first_s"]) // bumps_cfg["every_s"])
            publisher = WeightStreamPublisher(
                exp.experiment_name, exp.trial_name, "actor",
                chunk_bytes=exp.weight_sync.chunk_mb << 20)
            # When the first request for a version reaches the publisher,
            # on this process's clock: the servers have begun to pull
            # (per-layer ``bump_poll_wait_s`` only; the publisher has no
            # public hook for it, so without ``_lookup`` there is no value).
            publisher.first_request = {}
            lookup = getattr(publisher, "_lookup", None)
            if lookup is not None:
                def timed_lookup(version):
                    publisher.first_request.setdefault(int(version),
                                                       time.monotonic())
                    return lookup(version)

                publisher._lookup = timed_lookup

            def make_versions():
                for v in range(1, n_bumps + 1):
                    versions[v] = draw_version(info["param_stats"],
                                               spec["seed"], v)

            maker = threading.Thread(target=make_versions, daemon=True)
            maker.start()
        result = asyncio.run(drive(spec, exp, groups, info, publisher,
                                   versions, n_bumps))
        harness.write_json(os.path.join(out, "result.json"), result)
        return 0
    finally:
        if publisher is not None:
            publisher.close()
        fleet.kill()


async def drive(spec, exp, groups, info, publisher, versions, n_bumps):
    import aiohttp

    from areal_tpu.api.model import GenerationHyperparameters
    from areal_tpu.base import name_resolve, names
    from areal_tpu.system.partial_rollout import PartialRolloutClient

    t = spec["traffic"]
    seconds, chips = float(spec["seconds"]), int(spec["cell"]["chips"])
    group_size = exp.group_size
    arrivals: List[tuple] = []   # (t_mono, tokens) per /generate reply
    done: List[Dict[str, Any]] = []  # one per finished or failed request
    started: List[tuple] = []    # (t_mono, prompt tokens x group_size)
    bumps: List[Dict[str, float]] = []
    notes: List[str] = []
    next_group = 0
    closing = False

    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn) as raw:
        session = CountingSession(
            raw, lambda ts, n: arrivals.append((ts, n)))
        client = PartialRolloutClient(
            info["manager"], session, chunk_tokens=exp.new_tokens_per_chunk)

        async def ctl(path: str, body=None):
            async with raw.post(info["control"] + path, json=body or {}) as r:
                return await r.json()

        async def server_metrics() -> Dict[str, float]:
            tot: Dict[str, float] = {}
            for url in info["servers"]:
                async with raw.get(url + "/metrics.json") as r:
                    d = await r.json()
                for k in ("prefill_tokens", "generated_tokens",
                          "compiled_shapes"):
                    tot[k] = tot.get(k, 0) + d[k]
            return tot

        async def one(prompt, gconfig):
            budget = gconfig.max_new_tokens
            try:
                res = await client.generate_one(prompt, gconfig)
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001 — a failed request
                done.append({"t": time.monotonic(), "ok": False,
                             "why": repr(e)[:200]})
                return
            lps = np.asarray(res.output_logprobs, np.float64)
            ok = (len(res.output_ids) == budget and lps.shape == (budget,)
                  and bool(np.isfinite(lps).all()) and bool((lps <= 0).all())
                  and res.version_start <= res.version_end)
            done.append({"t": time.monotonic(), "ok": ok,
                         "tokens": len(res.output_ids),
                         "v0": res.version_start, "v1": res.version_end})

        async def worker(w: int):
            nonlocal next_group
            first = True
            while not closing:
                g = groups[next_group % len(groups)]
                next_group += 1
                budget = g.new_tokens
                if first:  # spread completions from the start
                    fr = t["first_budget_fractions"]
                    m = int(t["shape"]["new_tokens"].get("multiple_of", 1))
                    budget = max(m, int(budget * fr[w % len(fr)]) // m * m)
                    first = False
                gconfig = GenerationHyperparameters(
                    max_new_tokens=budget, min_new_tokens=budget,
                    **t.get("sampling", {}))
                started.append((time.monotonic(),
                                len(g.prompt_ids) * group_size))
                prompt = g.prompt_ids.tolist()
                await asyncio.gather(*[one(prompt, gconfig)
                                       for _ in range(group_size)])

        async def bump(v: int, t_due: float):
            await asyncio.sleep(max(0.0, t_due - time.monotonic()))
            while v not in versions:  # made in set-up; late = noted
                notes.append(f"version {v} not ready when due")
                await asyncio.sleep(0.1)
            t_pub = time.monotonic()
            await asyncio.to_thread(publisher.publish, versions.pop(v), v)
            name_resolve.add(names.model_version_time(
                exp.experiment_name, exp.trial_name, "actor"),
                repr(time.time()), replace=True)
            name_resolve.add(names.model_version(
                exp.experiment_name, exp.trial_name, "actor"), str(v),
                replace=True)
            pending = set(info["servers"])
            while pending:
                for url in list(pending):
                    async with raw.get(url + "/health") as r:
                        if (await r.json())["version"] >= v:
                            pending.discard(url)
                if pending:
                    await asyncio.sleep(0.02)
            bumps.append({"version": v, "t_pub": t_pub,
                          "t_pull": publisher.first_request.get(v),
                          "t_served": time.monotonic()})

        # Set-up: every (rows, capacity) the loop can form is visited once,
        # on purpose — which of them a closed loop forms by itself depends
        # on how its groups happen to interleave, and a shape first met
        # inside the window costs its compilation there. Then the loop
        # runs ``warmup_s`` to fill the pipeline.
        for burst in t.get("warm_bursts", []):
            gconfig = GenerationHyperparameters(
                max_new_tokens=burst["new_tokens"],
                min_new_tokens=burst["new_tokens"], **t.get("sampling", {}))
            await asyncio.gather(*[
                one(groups[-1 - k].prompt_ids.tolist(), gconfig)
                for k in range(burst["groups"]) for _ in range(group_size)])
        warm_failed = sum(not d["ok"] for d in done)
        workers = [asyncio.create_task(worker(w))
                   for w in range(t["groups_in_flight"])]
        await asyncio.sleep(t["warmup_s"])
        # ---- the measured window ----
        fleet_0 = await ctl("/info")  # not /finish: nothing traced yet
        m0 = await server_metrics()
        w0 = time.monotonic()
        window_start = time.time()
        bump_tasks = []
        if t.get("bumps"):
            bump_tasks = [asyncio.create_task(bump(
                k + 1, w0 + t["bumps"]["first_s"] + k * t["bumps"]["every_s"]))
                for k in range(n_bumps)]
        if spec["trace"]:
            await asyncio.sleep(t["trace"]["start_s"])
            await ctl("/trace_start")
            await asyncio.sleep(t["trace"]["seconds"])
            await ctl("/trace_stop")
        await asyncio.sleep(max(0.0, w0 + seconds - time.monotonic()))
        w1 = time.monotonic()
        m1 = await server_metrics()
        closing = True
        for task in workers:
            task.cancel()
        await asyncio.gather(*workers, return_exceptions=True)
        if bump_tasks:  # let a bump that is in flight land, then check
            await asyncio.wait(bump_tasks, timeout=60)
            for task in bump_tasks:
                task.cancel()

        # ---- correctness after the window ----
        last_v = max([b["version"] for b in bumps], default=0)
        checks = []
        ck = t["check"]
        for i in range(ck["requests"]):
            g = groups[-1 - i]
            res = await client.generate_one(
                g.prompt_ids.tolist(), GenerationHyperparameters(
                    max_new_tokens=ck["new_tokens"],
                    min_new_tokens=ck["new_tokens"]))
            toks = g.prompt_ids.tolist() + list(res.output_ids)
            ref = (await ctl("/reference", {"tokens": toks}))["logprobs"]
            cmp = dl.compare_logprobs(
                res.output_logprobs, ref[len(g.prompt_ids) - 1:])
            cmp["version_ok"] = (res.version_start == res.version_end
                                 == last_v)
            cmp["length_ok"] = len(res.output_ids) == ck["new_tokens"]
            checks.append(cmp)
        fleet_1 = await ctl("/finish")
        await ctl("/shutdown")

    # ---- reduction on the client's clock ----
    in_win = lambda ts: w0 <= ts < w1  # noqa: E731
    tokens = sum(n for ts, n in arrivals if in_win(ts))
    # ``gen_tok_s_chip``: the tokens that arrived after the window's first
    # reply, over the time from that reply to the window's last — replies
    # come in batches, so tokens over the fixed window would move in steps
    # of one batch. Nothing is taken out: a compile or a hang inside the
    # window is in the time (and in ``gen_longest_silence_s``).
    win = sorted((ts, n) for ts, n in arrivals if in_win(ts))
    span_s = win[-1][0] - win[0][0] if len(win) > 1 else 0.0
    span_tokens = sum(n for _, n in win[1:])
    silence = float(np.max(np.diff([w0] + [ts for ts, _ in win] + [w1])))
    fin = [d for d in done if in_win(d["t"])]
    failed = sum(not d["ok"] for d in fin)
    good_bumps = [b for b in bumps if w0 <= b["t_pub"] and b["t_served"] < w1]
    # Publish -> every server serves the version, the manager's poll of
    # the version key included (its share: ``bump_poll_wait_s``).
    sync = sorted(b["t_served"] - b["t_pub"] for b in good_bumps)
    poll_wait = [round(b["t_pull"] - b["t_pub"], 3) for b in good_bumps
                 if b["t_pull"] is not None]
    in_bump = lambda ts: any(  # noqa: E731
        b["t_pub"] <= ts < b["t_served"] for b in bumps)
    grid = np.arange(w0, w1, 0.01)  # bumps may overlap: measure the union
    bump_s = 0.01 * sum(in_bump(ts) for ts in grid)
    bump_tokens = sum(n for ts, n in arrivals if in_win(ts) and in_bump(ts))
    versions_ok = all(d["v0"] <= d["v1"] for d in fin if d["ok"])
    want = {"tpu": "tpu"}.get(spec["platform"], "cpu")
    correct = (
        failed == 0 and warm_failed == 0 and len(fin) > 0 and versions_ok
        and fleet_1["device"]["platform"] == want
        and all(c["ok"] and c["version_ok"] and c["length_ok"]
                for c in checks)
        and (not t.get("bumps") or len(good_bumps) > 0) and span_s > 0
    )
    e2e = {
        "gen_tok_s_chip": (span_tokens / span_s if span_s else 0.0) / chips,
        "setup_s": window_start - spec["t0"],
    }
    if sync:
        e2e["weight_sync_s"] = float(np.median(sync))
    red = fleet_1.get("trace") or {}
    counters = {
        "generated_tokens_client": tokens,
        "generated_tokens_server": m1["generated_tokens"]
        - m0["generated_tokens"],
        "prefill_tokens": m1["prefill_tokens"] - m0["prefill_tokens"],
        "prompt_tokens_started": sum(n for ts, n in started if in_win(ts)),
        "window_compiled_shapes": m1["compiled_shapes"]
        - m0["compiled_shapes"],
        "window_compile_cache_misses":
            fleet_1["compile_cache"].get("misses", 0)
            - fleet_0["compile_cache"].get("misses", 0),
        "compiled_shapes_total": m1["compiled_shapes"],
        "requests_finished": len(fin), "bumps_in_window": len(good_bumps),
        "longest_silence_s": silence,
    }
    notes.append(
        f"tokens={tokens} requests={len(fin)} failed={failed} "
        f"span_tokens={span_tokens} span_s={span_s:.3f} "
        f"weight_sync={sync} poll_wait={poll_wait} checks={checks} "
        f"counters={counters} "
        f"fleet_up_s={info['fleet_up_s']:.1f} "
        f"compile_cache={fleet_1['compile_cache']} "
        f"attention={fleet_1['attention']}")
    records = {
        "device": fleet_1["device"], "chips": chips, "window_s": w1 - w0,
        "config": spec["config"], "counters": counters,
        "bumps": {"bump_s": bump_s, "bump_tokens": bump_tokens,
                  "free_s": (w1 - w0) - bump_s,
                  "free_tokens": tokens - bump_tokens,
                  "poll_wait_s": poll_wait},
        "memory_peak_bytes": fleet_1["memory_peak_bytes"], "trace": red,
        "setup_split": {"fleet_up_s": info["fleet_up_s"],
                        "fleet_build_s": info["fleet_build_s"],
                        "warmup_s": t["warmup_s"],
                        "compile_cache_at_window_start":
                            fleet_0["compile_cache"]},
    }
    return {
        "correct": bool(correct), "attempted": len(fin), "failed": int(failed),
        "end_to_end": e2e,
        "device": {**fleet_1["device"],
                   "memory_peak_bytes": fleet_1["memory_peak_bytes"],
                   **({"busy_s": red["busy_s"], "window_s": red["window_s"]}
                      if red else {})},
        "breakdown": dl.breakdown(red), "records": records, "notes": notes,
    }


def main() -> int:

    spec = dl.load_spec()
    return fleet_main(spec) if spec["role"] == "fleet" else client_main(spec)


if __name__ == "__main__":
    sys.exit(main())
