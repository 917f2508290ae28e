"""Sandboxed reward service (docs/rewards.md): the sixth worker kind.

In-process fleets (real aiohttp sockets on loopback, no subprocess
workers) + chaos on injected graders, so the whole suite runs in seconds:

 - service grades math/code over HTTP with per-kind verdict telemetry;
 - client fanout spreads a batch across replicas with bounded concurrency;
 - fleet unreachable  -> local-fallback parity with the legacy path;
 - mid-batch worker death -> retry lands on the surviving replica;
 - grade timeout -> 0.0 verdict + reward_timeouts_total incremented;
 - unsupported language -> 0.0 verdict, no sandbox spawn;
 - disabled config -> batch_reward bit-identical to the legacy local path.
"""

import asyncio
import json

import pytest

from areal_tpu.api.train_config import RewardServiceConfig, TelemetryConfig
from areal_tpu.base import name_resolve

pytestmark = pytest.mark.rewards

EXP, TRIAL = "rewardsvc", "t0"

MATH_OK = {"task": "math", "generated": "\\boxed{4}",
           "solutions": ["\\boxed{4}"]}
MATH_BAD = {"task": "math", "generated": "\\boxed{5}",
            "solutions": ["\\boxed{4}"]}
CODE_IO = json.dumps({"inputs": ["1\n"], "outputs": ["1\n"]})
CODE_OK = {"task": "code", "generated": "```python\nprint(input())\n```",
           "input_output": CODE_IO}
CODE_BAD = {"task": "code", "generated": "```python\nprint('x')\n```",
            "input_output": CODE_IO}


@pytest.fixture(autouse=True)
def _mem_repo():
    old = name_resolve.DEFAULT_REPO
    name_resolve.DEFAULT_REPO = name_resolve.MemoryNameRecordRepo()
    yield
    name_resolve.DEFAULT_REPO = old


@pytest.fixture(autouse=True)
def _clear_service_mode():
    from areal_tpu.rewards import client as rc

    yield
    rc.configure_service(None)


def _worker(index=0, cfg=None, telemetry_enabled=False, grade_fn=None):
    from areal_tpu.system.reward_worker import RewardWorker, RewardWorkerConfig

    return RewardWorker(RewardWorkerConfig(
        experiment=EXP, trial=TRIAL, worker_index=index,
        reward=cfg or RewardServiceConfig(enabled=True),
        telemetry=TelemetryConfig(enabled=telemetry_enabled,
                                  flush_interval_secs=3600),
    ), grade_fn=grade_fn)


async def _http_json(url, payload=None):
    import aiohttp

    async with aiohttp.ClientSession() as s:
        if payload is None:
            async with s.get(url) as r:
                return r.status, await r.json()
        async with s.post(url, json=payload) as r:
            return r.status, await r.json()


def test_service_grades_math_and_code_over_http():
    async def main():
        w = _worker(telemetry_enabled=True)
        url = await w.start()
        try:
            _, out = await _http_json(f"{url}/math_verify", MATH_OK)
            assert out == {"score": 1.0, "verdict": "pass"}
            _, out = await _http_json(f"{url}/math_verify", MATH_BAD)
            assert out == {"score": 0.0, "verdict": "fail"}
            _, out = await _http_json(f"{url}/code_verify", CODE_OK)
            assert out == {"score": 1.0, "verdict": "pass"}
            _, out = await _http_json(f"{url}/batch_reward",
                                      {"tasks": [MATH_OK, CODE_BAD]})
            assert out["scores"] == [1.0, 0.0]
            assert out["verdicts"] == ["pass", "fail"]
            _, health = await _http_json(f"{url}/health")
            assert health["ok"] and health["graded_total"] == 5
            # Prometheus exposition: requests counter + per-kind verdict
            # labels + latency histogram (the PR 4 registry contract).
            import aiohttp

            async with aiohttp.ClientSession() as s:
                async with s.get(f"{url}/metrics") as r:
                    prom = await r.text()
            assert "areal_reward_requests_total" in prom
            assert 'task="math"' in prom and 'verdict="pass"' in prom
            assert "areal_reward_grade_latency_secs_bucket" in prom
            for ln in prom.splitlines():
                if ln and not ln.startswith("#"):
                    float(ln.rpartition(" ")[2])  # every sample parses
        finally:
            await w.stop()

    asyncio.run(main())


def test_client_fanout_spreads_over_fleet():
    async def main():
        from areal_tpu.rewards import client as rc

        cfg = RewardServiceConfig(enabled=True, n_workers=2,
                                  max_concurrency=4)
        w0, w1 = _worker(0, cfg), _worker(1, cfg)
        await w0.start()
        await w1.start()
        try:
            rc.configure_service(cfg, EXP, TRIAL)
            tasks = [MATH_OK, MATH_BAD] * 8
            scores = await rc.abatch_reward(tasks)
            assert scores == [1.0, 0.0] * 8
            # both replicas actually graded (round-robin fanout)
            assert w0.service._graded > 0 and w1.service._graded > 0
            assert w0.service._graded + w1.service._graded == 16
        finally:
            await w0.stop()
            await w1.stop()

    asyncio.run(main())


def test_fleet_unreachable_local_fallback_parity():
    """The fleet never came up: every task degrades to local grading and
    the outputs match the legacy local path exactly."""

    async def main():
        from areal_tpu.rewards import client as rc

        cfg = RewardServiceConfig(enabled=True, max_retries=1,
                                  retry_base_delay_secs=0.01,
                                  retry_max_delay_secs=0.01)
        # no worker registered; also point at a dead URL to exercise the
        # connect-refused path, not just the empty-fleet path
        client = rc.configure_service(
            cfg, EXP, TRIAL, urls=["http://127.0.0.1:9"]
        )
        tasks = [MATH_OK, MATH_BAD, CODE_OK, CODE_BAD]
        scores = await rc.abatch_reward(tasks)
        assert scores == [1.0, 0.0, 1.0, 0.0]
        assert client is rc.service_client()
        return scores

    scores = asyncio.run(main())
    # parity: identical to the legacy local path, bit for bit
    from areal_tpu.rewards import client as rc

    rc.configure_service(None)
    assert rc.batch_reward([MATH_OK, MATH_BAD, CODE_OK, CODE_BAD]) == scores


def test_cold_start_registration_race_retries_before_fallback():
    """Fleet resolves EMPTY on the first attempt (workers still
    registering at launch): the client burns its retry budget with
    backoff instead of immediately executing code locally — the worker
    that registers during the backoff window gets the task."""

    async def main():
        from areal_tpu.rewards import client as rc

        cfg = RewardServiceConfig(enabled=True, max_retries=3,
                                  retry_base_delay_secs=0.05,
                                  retry_max_delay_secs=0.1)
        rc.configure_service(cfg, EXP, TRIAL)
        w = _worker(cfg=cfg)

        async def register_late():
            await asyncio.sleep(0.02)
            await w.start()

        reg = asyncio.create_task(register_late())
        try:
            scores = await rc.abatch_reward([CODE_OK])
            await reg
            assert scores == [1.0]
            # graded by the FLEET (after the race), never locally
            assert w.service._graded == 1
        finally:
            await w.stop()

    asyncio.run(main())


def test_mid_batch_worker_death_retries_on_survivor():
    """One replica dies mid-batch: its in-flight tasks retry on the
    surviving replica; every score still lands."""

    async def main():
        from areal_tpu.rewards import client as rc

        cfg = RewardServiceConfig(enabled=True, n_workers=2, max_retries=2,
                                  retry_base_delay_secs=0.01,
                                  retry_max_delay_secs=0.02,
                                  max_concurrency=2)
        w0, w1 = _worker(0, cfg), _worker(1, cfg)
        u0 = await w0.start()
        await w1.start()
        killed = asyncio.Event()

        async def kill_w0_soon():
            # Let a couple of requests land, then die abruptly (socket
            # closed + deregistered — the respawn-in-place contract's
            # "dead" half).
            while w0.service._graded < 2:
                await asyncio.sleep(0.005)
            await w0.stop()
            killed.set()

        try:
            client = rc.configure_service(cfg, EXP, TRIAL)
            assert u0 in client.refresh()
            killer = asyncio.create_task(kill_w0_soon())
            tasks = [MATH_OK, MATH_BAD] * 12
            scores = await rc.abatch_reward(tasks)
            await killer
            assert killed.is_set()
            assert scores == [1.0, 0.0] * 12
            # the survivor picked up the dead replica's share
            assert w1.service._graded > 0
            # and the fleet view no longer contains the dead URL
            assert u0 not in client.refresh()
        finally:
            await w1.stop()

    asyncio.run(main())


def test_timeout_returns_zero_verdict_and_counter():
    """A grade overrunning grade_timeout_secs: 0.0 score, verdict
    "timeout", reward_timeouts_total incremented — the slot is released,
    later grades proceed."""

    async def main():
        import threading

        release = threading.Event()

        def slow_grade(task):
            if task.get("generated") == "SLOW":
                release.wait(5.0)  # far beyond the budget below
            return {"score": 1.0, "verdict": "pass"}

        cfg = RewardServiceConfig(enabled=True, grade_timeout_secs=1.0)
        w = _worker(cfg=cfg, telemetry_enabled=True, grade_fn=slow_grade)
        url = await w.start()
        try:
            _, out = await _http_json(
                f"{url}/math_verify", {"task": "math", "generated": "SLOW"}
            )
            assert out == {"score": 0.0, "verdict": "timeout"}
            # the slot is free again: a fast grade completes normally
            _, out = await _http_json(
                f"{url}/math_verify", {"task": "math", "generated": "ok"}
            )
            assert out == {"score": 1.0, "verdict": "pass"}
            assert w.service._timeouts == 1
            assert w.telemetry.registry.snapshot(reset=False)[
                "counters"]["reward/timeouts"] == 1
            import aiohttp

            async with aiohttp.ClientSession() as s:
                async with s.get(f"{url}/metrics") as r:
                    prom = await r.text()
            assert "areal_reward_timeouts_total" in prom
        finally:
            release.set()
            await w.stop()

    asyncio.run(main())


def test_task_budget_floors_code_worst_case():
    """grade_timeout_secs bounds a WEDGED grader; a code task's budget
    floors at its legal worst case (per-case timeout x max cases) on
    BOTH sides (server grade + client HTTP timeout share the helper)."""
    from areal_tpu.rewards.service import task_budget_secs

    assert task_budget_secs({"task": "math"}, 30.0) == 30.0
    assert task_budget_secs({"task": "code", "timeout": 8.0}, 30.0) \
        == 8.0 * 16 + 5.0
    # short per-case timeouts keep the configured bound
    assert task_budget_secs({"task": "code", "timeout": 0.1}, 30.0) == 30.0
    # the floor scales with the cases the task ACTUALLY carries (a hung
    # single-case pass-rate task pins its slot ~13s, not ~133s)
    one_case = json.dumps({"inputs": ["1\n"], "outputs": ["1\n"]})
    assert task_budget_secs(
        {"task": "code", "timeout": 8.0, "input_output": one_case}, 5.0
    ) == 8.0 * 1 + 5.0
    many = json.dumps({"inputs": ["1\n"] * 500, "outputs": ["1\n"] * 500})
    assert task_budget_secs(
        {"task": "code", "timeout": 8.0, "input_output": many}, 5.0
    ) == 8.0 * 16 + 5.0


def test_sample_cases_honors_cap_for_every_length():
    from areal_tpu.rewards.code_verify import sample_cases

    for n in (1, 15, 16, 17, 31, 32, 33, 500):
        got = sample_cases([str(i) for i in range(n)],
                           [str(i) for i in range(n)], 16)
        assert len(got) <= 16, (n, len(got))
        assert got[0] == ("0", "0")  # deterministic, starts at case 0
    assert sample_cases([], [], 16) == []


# Grade budget of the two wedge tests: short beside their 10 s wedge, long
# beside a thread's start-up under any load the suite runs beside.
WEDGE_BUDGET_SECS = 2.0


def test_wedged_grader_pool_self_heals():
    """wait_for cannot kill a wedged grader THREAD: once every pool
    thread is a zombie, the pool is replaced wholesale so new grades
    run promptly instead of timing out in executor-queue wait forever."""

    async def main():
        import threading

        release = threading.Event()
        wedged = []

        def grade(task):
            if task.get("generated") == "WEDGE":
                wedged.append(threading.get_ident())
                release.wait(10.0)
            return {"score": 1.0, "verdict": "pass"}

        # The budget only has to be short beside the 10 s wedge. It must
        # NOT be short beside a pool thread's start-up on a busy machine:
        # a grade whose budget runs out before its thread picked it up is
        # cancelled, not wedged, and the pool is (rightly) kept.
        cfg = RewardServiceConfig(enabled=True, pool_size=2, max_inflight=2,
                                  grade_timeout_secs=WEDGE_BUDGET_SECS)
        w = _worker(cfg=cfg, grade_fn=grade)
        url = await w.start()
        pool0 = w.service._pool

        def wedge():
            return asyncio.ensure_future(_http_json(
                f"{url}/math_verify", {"task": "math", "generated": "WEDGE"}))

        try:
            # The two time out apart, as grades do: the first zombie's
            # permit must still be withheld when the second one's budget
            # ends (it rides the THREAD, which runs on — not the awaited
            # future, which the time-out itself cancels).
            first = wedge()
            await asyncio.sleep(0.3)
            outs = await asyncio.gather(first, wedge())
            assert all(o[1]["verdict"] == "timeout" for o in outs)
            assert len(set(wedged)) == 2  # both threads are in the wedge
            # every thread wedged -> the pool was swapped out
            assert w.service._pool is not pool0
            # ...and a fresh grade completes on the new pool, inside its
            # budget: queued behind the zombies (8 s of wedge left) it
            # would come back "timeout"
            _, out = await _http_json(
                f"{url}/math_verify", {"task": "math", "generated": "ok"}
            )
            assert out["verdict"] == "pass"
        finally:
            release.set()
            await w.stop()

    asyncio.run(main())


def test_self_heal_triggers_at_admission_limit():
    """max_inflight < pool_size: the replacement trigger must use the
    CLAMPED admission bound — at max_inflight zombies every admittable
    slot is withheld, and a pool_size-based trigger would never fire
    (permanent deadlock behind sem.acquire)."""

    async def main():
        import threading

        release = threading.Event()

        def grade(task):
            if task.get("generated") == "WEDGE":
                release.wait(10.0)
            return {"score": 1.0, "verdict": "pass"}

        cfg = RewardServiceConfig(enabled=True, pool_size=8, max_inflight=1,
                                  grade_timeout_secs=WEDGE_BUDGET_SECS)
        w = _worker(cfg=cfg, grade_fn=grade)
        url = await w.start()
        try:
            _, out = await _http_json(
                f"{url}/math_verify", {"task": "math", "generated": "WEDGE"}
            )
            assert out["verdict"] == "timeout"
            # admitted and graded inside its budget, not deadlocked
            # behind sem.acquire for the 8 s of wedge that are left
            _, out = await asyncio.wait_for(_http_json(
                f"{url}/math_verify", {"task": "math", "generated": "ok"}
            ), timeout=7.0)
            assert out["verdict"] == "pass"
        finally:
            release.set()
            await w.stop()

    asyncio.run(main())


def test_unsupported_language_verdict():
    from areal_tpu.rewards.service import grade_task

    task = {"task": "code", "generated": "```cpp\nint main(){}\n```",
            "input_output": CODE_IO, "language": "cpp"}
    assert grade_task(task) == {"score": 0.0,
                                "verdict": "unsupported_language"}
    # allowed list narrower than GRADERS also gates
    assert grade_task({**CODE_OK, "language": "python"}, languages=[]) \
        == {"score": 0.0, "verdict": "unsupported_language"}


def test_inflight_cap_bounds_concurrency():
    async def main():
        import threading

        peak = {"v": 0, "cur": 0}
        lock = threading.Lock()

        def counting_grade(task):
            with lock:
                peak["cur"] += 1
                peak["v"] = max(peak["v"], peak["cur"])
            import time as _t

            _t.sleep(0.02)
            with lock:
                peak["cur"] -= 1
            return {"score": 1.0, "verdict": "pass"}

        cfg = RewardServiceConfig(enabled=True, max_inflight=2, pool_size=8)
        w = _worker(cfg=cfg, grade_fn=counting_grade)
        url = await w.start()
        try:
            outs = await asyncio.gather(*[
                _http_json(f"{url}/math_verify",
                           {"task": "math", "generated": "x"})
                for _ in range(10)
            ])
            assert all(o[1]["score"] == 1.0 for o in outs)
            assert peak["v"] <= 2  # admission bound, not pool size
        finally:
            await w.stop()

    asyncio.run(main())


def test_batch_reward_sync_on_running_loop_raises():
    """The old loop-blocking bridge is gone: sync batch_reward on a
    running loop raises, pointing at the real async entrypoint."""
    from areal_tpu.rewards.client import batch_reward

    async def main():
        with pytest.raises(RuntimeError, match="abatch_reward"):
            batch_reward([MATH_OK])

    asyncio.run(main())


def test_agent_env_awaits_async_grading():
    """The math/code env grades through abatch_reward on the caller's
    loop — no dedicated-thread bridge (the satellite contract)."""
    from areal_tpu.agents.math_single_step import MathCodeSingleStepEnv

    env = MathCodeSingleStepEnv({
        "q1": {"task": "math", "solutions": ["\\boxed{4}"]},
    })

    async def main():
        _, scores, done, _ = await env.step(("q1", ["\\boxed{4}", "no"]))
        return scores, done

    scores, done = asyncio.run(main())
    assert scores == [1.0, 0.0] and done


def test_code_agent_format_gate_and_pass_rate():
    from areal_tpu.agents.code_single_step import CodeSingleStepEnv

    io = json.dumps({"inputs": ["1\n", "2\n"], "outputs": ["1\n", "2\n"]})
    id2info = {"c1": {"task": "code", "input_output": io}}

    async def main():
        env = CodeSingleStepEnv(id2info)
        _, scores, _, _ = await env.step(
            ("c1", ["```python\nprint(input())\n```", "just prose"])
        )
        assert scores == [1.0, 0.0]  # prose gated without a sandbox spawn
        env_pr = CodeSingleStepEnv(id2info, pass_rate_reward=True)
        # echoes the input only when it is "1": passes 1 of 2 cases
        half = ("```python\nx=input()\nprint(x if x=='1' else 'no')\n```")
        _, scores, _, _ = await env_pr.step(("c1", [half]))
        assert scores == [pytest.approx(0.5)]

    asyncio.run(main())


def test_worker_control_and_lease_registration():
    """run_async serves WorkerControl (the sixth worker kind speaks the
    same lifecycle language as the other five) and withdraws discovery
    on exit."""

    async def main():
        from areal_tpu.base import names
        from areal_tpu.system.reward_worker import resolve_fleet
        from areal_tpu.system.worker_base import WorkerControlPanel

        cfg = RewardServiceConfig(enabled=True)
        from areal_tpu.system.reward_worker import (
            RewardWorker,
            RewardWorkerConfig,
        )

        w = RewardWorker(RewardWorkerConfig(
            experiment=EXP, trial=TRIAL, worker_index=0, reward=cfg,
            keepalive_ttl_secs=30.0,
        ))
        task = asyncio.create_task(w.run_async())
        deadline = asyncio.get_event_loop().time() + 10
        while not resolve_fleet(EXP, TRIAL):
            assert asyncio.get_event_loop().time() < deadline
            await asyncio.sleep(0.01)
        url = resolve_fleet(EXP, TRIAL)[0]
        _, health = await _http_json(f"{url}/health")
        assert health["ok"]

        def panel_cmds():
            panel = WorkerControlPanel(EXP, TRIAL, timeout=5.0)
            try:
                st = panel.status("reward0")
                assert st["ok"] and st["url"] == url
                # liveness heartbeat under the LAUNCHER's worker name
                # (supervisor respawn purge keys on it)
                assert "reward0" in panel.heartbeats()
                panel.exit("reward0")
            finally:
                panel.close()

        await asyncio.to_thread(panel_cmds)
        await asyncio.wait_for(task, timeout=10)
        assert resolve_fleet(EXP, TRIAL) == []  # discovery withdrawn

    asyncio.run(main())
