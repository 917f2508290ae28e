"""Generation-fleet fault tolerance: chaos + regression tests.

Covers the failure-recovery subsystem (docs/fault_tolerance.md):
 - retry policy / fault injector primitives (base/retry.py)
 - lease release/expiry accounting (no double decrement)
 - weight fanout with an unresponsive server: bounded by the per-server
   timeout budget, dead server evicted, version still advances
 - health-check eviction and re-admission with weight reconcile
 - client chunk failover: replay from accumulated tokens on a new route
 - rollout abandonment: clean /finish_rollout, worker survives
 - full chaos run: one of two real generation servers killed mid-run
 - launcher-level supervision (system/supervisor.py): SIGKILL respawn,
   unexpected-clean-exit detection, backoff + crash-loop circuit
   breaker, ghost-key clearing, graceful drain, liveness leases
   (name_resolve keepalive + heartbeats), crash-safe ConsumedLog

Every test is bounded to seconds: failures come from the FaultInjector,
tiny aiohttp fakes, in-process fake workers, or fake clocks/processes —
never from real TTLs or long sleeps. The two launcher-level e2e chaos
runs (SIGKILL mid-experiment, SIGTERM drain + resume) spawn a complete
async-PPO experiment and are behind the ``slow`` marker like the other
full-experiment launches.
"""

import asyncio
import os
import signal
import threading
import time

import pytest

from areal_tpu.base import name_resolve, names, network
from areal_tpu.base.retry import (
    FaultInjected,
    FaultInjector,
    RetryPolicy,
    aretry,
)
from areal_tpu.system.gserver_manager import (
    GserverManager,
    GserverManagerConfig,
    _ServerHealth,
)
from areal_tpu.system.partial_rollout import (
    GenerationAbandonedError,
    NoHealthyServersError,
    PartialRolloutClient,
)

EXP, TRIAL = "faulttest", "t0"


class _Req:
    """Minimal aiohttp-request stand-in for direct handler calls."""

    def __init__(self, d=None):
        self._d = d or {}

    async def json(self):
        return self._d


def _mgr(**kw) -> GserverManager:
    cfg = GserverManagerConfig(experiment=EXP, trial=TRIAL, **kw)
    return GserverManager(cfg)


async def _start_app(app):
    from aiohttp import web

    runner = web.AppRunner(app)
    await runner.setup()
    port = network.find_free_port()
    site = web.TCPSite(runner, "127.0.0.1", port)
    await site.start()
    return runner, f"http://127.0.0.1:{port}"


# ---------------------------------------------------------------- retry.py


@pytest.mark.chaos
def test_retry_policy_delays_capped():
    p = RetryPolicy(max_attempts=5, base_delay_secs=0.1, max_delay_secs=0.5,
                    multiplier=2.0)
    assert p.delay(1) == pytest.approx(0.1)
    assert p.delay(2) == pytest.approx(0.2)
    assert p.delay(3) == pytest.approx(0.4)
    assert p.delay(4) == pytest.approx(0.5)  # capped
    assert p.delay(10) == pytest.approx(0.5)


@pytest.mark.chaos
def test_aretry_retries_then_succeeds_and_gives_up():
    calls = {"n": 0}

    async def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ValueError("boom")
        return "ok"

    pol = RetryPolicy(max_attempts=3, base_delay_secs=0.001)
    assert asyncio.run(aretry(flaky, pol)) == "ok"
    assert calls["n"] == 3

    async def dead():
        raise ValueError("always")

    with pytest.raises(ValueError):
        asyncio.run(aretry(dead, pol))


@pytest.mark.chaos
def test_fault_injector_arming():
    inj = FaultInjector()
    inj.arm("p", times=2)
    with pytest.raises(FaultInjected):
        inj.maybe_fail("p")
    with pytest.raises(FaultInjected):
        inj.maybe_fail("p")
    inj.maybe_fail("p")  # exhausted: no-op
    assert inj.fired["p"] == 2
    # predicate-gated, unlimited until disarm
    inj.arm("q", times=-1, when=lambda ctx: ctx.get("url") == "dead")
    inj.maybe_fail("q", url="alive")
    with pytest.raises(FaultInjected):
        inj.maybe_fail("q", url="dead")
    inj.disarm("q")
    inj.maybe_fail("q", url="dead")


# ------------------------------------------------------- lease accounting


@pytest.mark.chaos
def test_release_by_url_drops_lease_no_double_decrement():
    """Regression: the legacy by-url /release decremented inflight but left
    the lease alive, so its later TTL expiry decremented the SAME slot a
    second time — corrupting inflight while another request was running."""

    async def main():
        mgr = _mgr(lease_ttl_secs=60.0)
        url = "http://127.0.0.1:7777"
        mgr.servers = [url]
        mgr._inflight = {url: 0}
        mgr.health = {url: _ServerHealth()}

        await mgr.handle_schedule_request(_Req())  # request A
        assert mgr._inflight[url] == 1 and len(mgr._leases) == 1
        lease_a = next(iter(mgr._leases))

        # client releases A by url (legacy path, no lease_id)
        await mgr.handle_release(_Req({"url": url}))
        assert mgr._inflight[url] == 0
        assert lease_a not in mgr._leases  # the fix: lease retired too

        await mgr.handle_schedule_request(_Req())  # request B, in flight
        assert mgr._inflight[url] == 1

        # Force lease-expiry sweep. With the orphaned lease A still alive
        # (old bug) this would decrement B's slot to 0 while B is running.
        mgr._expire_leases()
        assert mgr._inflight[url] == 1

        # B's own expiry still works exactly once.
        lid_b = next(iter(mgr._leases))
        u, _ = mgr._leases[lid_b]
        mgr._leases[lid_b] = (u, time.monotonic() - 1)
        mgr._expire_leases()
        assert mgr._inflight[url] == 0 and not mgr._leases

    asyncio.run(main())


@pytest.mark.chaos
def test_release_by_lease_id_after_eviction_is_harmless():
    async def main():
        mgr = _mgr()
        url = "http://127.0.0.1:7777"
        mgr.servers = [url]
        mgr._inflight = {url: 0}
        mgr.health = {url: _ServerHealth()}
        await mgr.handle_schedule_request(_Req())
        lid = next(iter(mgr._leases))
        mgr._evict(url, "test")
        assert not mgr._leases and url not in mgr._inflight
        # late release from the client of the evicted server: no KeyError,
        # no negative counts
        await mgr.handle_release(_Req({"lease_id": lid, "url": url}))
        await mgr.handle_release(_Req({"url": url}))

    asyncio.run(main())


# ------------------------------------------------------------ weight fanout


@pytest.mark.chaos
def test_fanout_evicts_unresponsive_server_within_budget():
    """One acking server + one that accepts but never replies: the fanout
    must finish within the per-server timeout budget, evict the hung
    server (dropping its leases), bump the version, and route only to the
    survivor."""
    from aiohttp import web

    async def main():
        acks = []
        # the hung handler waits for the test's end, not for the clock: the
        # runner's clean-up waits a handler out
        test_over = asyncio.Event()

        async def ok_update(req):
            acks.append(await req.json())
            return web.json_response({"ok": True})

        async def hang(req):
            await test_over.wait()

        live_app = web.Application()
        live_app.router.add_post("/update_weights", ok_update)
        live_runner, live_url = await _start_app(live_app)
        hung_app = web.Application()
        hung_app.router.add_post("/update_weights", hang)
        hung_runner, hung_url = await _start_app(hung_app)
        try:
            mgr = _mgr(
                fanout_timeout_secs=0.4,
                fanout_retry=RetryPolicy(max_attempts=2,
                                         base_delay_secs=0.05),
            )
            mgr.servers = sorted([live_url, hung_url])
            mgr._inflight = {u: 0 for u in mgr.servers}
            mgr.health = {u: _ServerHealth() for u in mgr.servers}
            # an in-flight lease on the hung server must drain on eviction
            while True:
                await mgr.handle_schedule_request(_Req())
                if any(u == hung_url for u, _ in mgr._leases.values()):
                    break

            import aiohttp

            budget = mgr.cfg.fanout_retry.max_attempts * (
                mgr.cfg.fanout_timeout_secs
                + mgr.cfg.fanout_retry.max_delay_secs
            )
            t0 = time.monotonic()
            async with aiohttp.ClientSession() as sess:
                acked = await mgr.fanout_weights(sess, 1, "/tmp/unused")
            elapsed = time.monotonic() - t0
            assert elapsed < budget + 1.0

            assert acked == [live_url]
            assert mgr.version == 1  # acked servers ⇒ version advanced
            assert [d["version"] for d in acks] == [1]
            assert hung_url not in mgr.servers
            assert not mgr.health[hung_url].routable
            assert all(u != hung_url for u, _ in mgr._leases.values())
            assert hung_url not in mgr._inflight
            for _ in range(4):  # no further routing to the evicted server
                assert mgr._pick_server() == live_url
        finally:
            test_over.set()
            await live_runner.cleanup()
            await hung_runner.cleanup()

    asyncio.run(main())


@pytest.mark.chaos
def test_fanout_total_failure_holds_version_and_fleet():
    """If NO server acks, the failure is systemic (bad/late weight path) —
    the version must NOT advance and the fleet must NOT be mass-evicted
    (that would drop every lease and flap); the watcher retries next poll
    and genuinely dead servers are the health loop's responsibility."""

    async def main():
        import aiohttp

        mgr = _mgr(
            fanout_timeout_secs=0.2,
            fanout_retry=RetryPolicy(max_attempts=1, base_delay_secs=0.01),
        )
        dead = "http://127.0.0.1:1"
        mgr.servers = [dead]
        mgr._inflight = {dead: 0}
        mgr.health = {dead: _ServerHealth()}
        async with aiohttp.ClientSession() as sess:
            acked = await mgr.fanout_weights(sess, 5, "/tmp/unused")
        assert acked == [] and mgr.version == 0
        assert mgr.servers == [dead]  # fleet held, not mass-evicted

    asyncio.run(main())


# --------------------------------------------- health eviction/re-admission


@pytest.mark.chaos
def test_health_eviction_and_readmission_with_reconcile(tmp_name_resolve):
    """/health failures evict after the threshold; a recovered server is
    re-admitted only after its weights are reconciled to the manager's
    current version; a newly registered server joins through the same
    gate."""
    from aiohttp import web

    async def main():
        state = {"alive": True, "version": 0, "updates": []}

        async def health(req):
            if not state["alive"]:
                return web.Response(status=500)
            return web.json_response({"ok": True,
                                      "version": state["version"]})

        async def update(req):
            d = await req.json()
            state["updates"].append(d)
            state["version"] = d["version"]
            return web.json_response({"ok": True})

        app = web.Application()
        app.router.add_get("/health", health)
        app.router.add_post("/update_weights", update)
        runner, url = await _start_app(app)
        name_resolve.add(names.gen_servers(EXP, TRIAL, "flaky"), url,
                         replace=True)
        try:
            import aiohttp

            mgr = _mgr(health_failure_threshold=2,
                       health_check_timeout_secs=0.5)
            mgr.servers = [url]
            mgr._inflight = {url: 0}
            mgr.health = {url: _ServerHealth()}

            async def settle(pred, sweeps=20):
                # re-admission reconciles run detached from the sweep;
                # sweep + poll until the predicate holds
                for _ in range(sweeps):
                    await mgr.check_fleet(sess)
                    for _ in range(20):
                        if pred():
                            return True
                        await asyncio.sleep(0.02)
                return pred()

            timeout = aiohttp.ClientTimeout(total=0.5)
            async with aiohttp.ClientSession(timeout=timeout) as sess:
                await mgr.check_fleet(sess)
                assert url in mgr.servers  # healthy: stays

                state["alive"] = False
                await mgr.check_fleet(sess)
                assert url in mgr.servers  # 1 failure < threshold
                await mgr.check_fleet(sess)
                assert url not in mgr.servers  # threshold hit: evicted
                assert not mgr.health[url].routable

                # manager moved on to v3 while the server was down
                mgr.version = 3
                state["alive"] = True
                assert await settle(lambda: url in mgr.servers)
                # re-admitted AND reconciled to v3 before routing
                assert state["updates"][-1]["version"] == 3
                assert mgr.health[url].acked_version == 3
                assert mgr._inflight[url] == 0

                # a brand-new registration joins through the health gate
                app2 = web.Application()
                app2.router.add_get("/health", health)
                app2.router.add_post("/update_weights", update)
                runner2, url2 = await _start_app(app2)
                try:
                    name_resolve.add(
                        names.gen_servers(EXP, TRIAL, "late"), url2,
                        replace=True,
                    )
                    assert await settle(lambda: url2 in mgr.servers)

                    # deregistration prunes the health map entirely
                    name_resolve.delete(names.gen_servers(EXP, TRIAL,
                                                          "late"))
                    await mgr.check_fleet(sess)
                    assert url2 not in mgr.servers
                    assert url2 not in mgr.health
                finally:
                    await runner2.cleanup()
        finally:
            await runner.cleanup()

    asyncio.run(main())


# ------------------------------------------------------- client failover


def _fake_gen_app(state):
    """Deterministic fake generation server: token i of a request is
    100+tokens_done+i, so replay-from-accumulated is directly observable
    in the output sequence."""
    from aiohttp import web

    async def generate(req):
        d = await req.json()
        td = int(d["tokens_done"])
        mt = int(d["max_tokens"])
        state["calls"].append(td)
        toks = list(range(100 + td, 100 + td + mt))
        return web.json_response({
            "output_ids": toks, "output_logprobs": [0.0] * mt,
            "finished": False, "version": 0,
        })

    async def health(req):
        return web.json_response({"ok": True, "version": 0})

    app = web.Application()
    app.router.add_post("/generate", generate)
    app.router.add_get("/health", health)
    return app


@pytest.mark.chaos
def test_client_failover_replays_from_accumulated_tokens(tmp_name_resolve):
    """A chunk failure mid-generation re-schedules and RESUMES: the final
    token sequence is contiguous (no lost or repeated tokens) and the
    failed chunk was re-requested at the same tokens_done."""
    from areal_tpu.api.model import GenerationHyperparameters

    async def main():
        import aiohttp

        state = {"calls": []}
        runner, url = await _start_app(_fake_gen_app(state))
        name_resolve.add(names.gen_servers(EXP, TRIAL, "gen0"), url,
                         replace=True)
        mgr = _mgr(n_servers=1, max_head_offpolicyness=100,
                   health_check_interval_secs=30.0)
        mgr_url = await mgr.start()
        try:
            inj = FaultInjector()
            # fail exactly one attempt, at the second chunk boundary
            inj.arm("generate", times=1,
                    when=lambda ctx: ctx["tokens_done"] == 4)
            async with aiohttp.ClientSession() as sess:
                client = PartialRolloutClient(
                    mgr_url, sess, chunk_tokens=4,
                    retry=RetryPolicy(max_attempts=4, base_delay_secs=0.01),
                    fault_injector=inj,
                )
                res = await client.generate_one(
                    [1, 2, 3],
                    GenerationHyperparameters(max_new_tokens=8),
                )
            assert res.output_ids == list(range(100, 108))
            assert client.n_failovers == 1 and inj.fired["generate"] == 1
            assert state["calls"] == [0, 4]  # chunk 2 replayed at td=4
            assert res.n_chunks == 2
            # quota accounting survived the failover: no leaked leases
            assert not mgr._leases
            assert all(v == 0 for v in mgr._inflight.values())
        finally:
            await mgr.stop()
            await runner.cleanup()

    asyncio.run(main())


@pytest.mark.chaos
def test_generation_abandoned_after_max_attempts(tmp_name_resolve):
    async def main():
        import aiohttp

        state = {"calls": []}
        runner, url = await _start_app(_fake_gen_app(state))
        name_resolve.add(names.gen_servers(EXP, TRIAL, "gen0"), url,
                         replace=True)
        mgr = _mgr(n_servers=1, health_check_interval_secs=30.0)
        mgr_url = await mgr.start()
        try:
            from areal_tpu.api.model import GenerationHyperparameters

            inj = FaultInjector()
            inj.arm("generate", times=-1)  # fleet permanently dead
            async with aiohttp.ClientSession() as sess:
                client = PartialRolloutClient(
                    mgr_url, sess, chunk_tokens=4,
                    retry=RetryPolicy(max_attempts=3, base_delay_secs=0.01),
                    fault_injector=inj,
                )
                with pytest.raises(GenerationAbandonedError):
                    await client.generate_one(
                        [1, 2], GenerationHyperparameters(max_new_tokens=8)
                    )
            assert inj.fired["generate"] == 3
            assert client.n_abandoned == 1
            # every scheduled route was released on its failure
            assert all(v == 0 for v in mgr._inflight.values())
            assert not mgr._leases
        finally:
            await mgr.stop()
            await runner.cleanup()

    asyncio.run(main())


@pytest.mark.chaos
def test_empty_fleet_waits_on_own_budget_not_failover_attempts():
    """An all-evicted fleet returns 503s in milliseconds; those must burn
    the (longer) no-server wait budget, not the chunk-failover attempts —
    and a fleet gap longer than the budget abandons with a clear error."""
    from areal_tpu.api.model import GenerationHyperparameters

    async def main():
        import aiohttp

        mgr = _mgr()  # zero servers: /schedule_request 503s immediately
        runner, mgr_url = await _start_app(mgr.build_app())
        try:
            async with aiohttp.ClientSession() as sess:
                client = PartialRolloutClient(
                    mgr_url, sess, chunk_tokens=4,
                    retry=RetryPolicy(max_attempts=3, base_delay_secs=0.01,
                                      max_delay_secs=0.05),
                    no_server_wait_secs=0.2,
                )
                with pytest.raises(NoHealthyServersError):
                    await client._schedule()
                t0 = time.monotonic()
                with pytest.raises(GenerationAbandonedError,
                                   match="no routable"):
                    await client.generate_one(
                        [1, 2], GenerationHyperparameters(max_new_tokens=8)
                    )
                # waited out the no-server budget (not the ~30ms the three
                # failover attempts would have taken)
                assert time.monotonic() - t0 >= 0.2
        finally:
            await runner.cleanup()

    asyncio.run(main())


# ------------------------------------------------ rollout worker survival


@pytest.mark.chaos
@pytest.mark.timeout(120)
def test_rollout_worker_abandons_cleanly_never_crashes(tmp_path):
    """With every /generate chunk failing, the worker must abandon each
    rollout after the retry budget — reporting a correct /finish_rollout so
    running_rollouts drains to 0 — and run_async must return, not raise."""
    from areal_tpu.api.model import GenerationHyperparameters
    from areal_tpu.base.testing import MockTokenizer, make_math_jsonl
    from areal_tpu.system.rollout_worker import (
        RolloutWorker,
        RolloutWorkerConfig,
    )
    from areal_tpu.system.streams import ZmqPuller

    name_resolve.DEFAULT_REPO = name_resolve.NfsNameRecordRepo(
        str(tmp_path / "nr")
    )
    data_path = str(tmp_path / "math.jsonl")
    make_math_jsonl(data_path, n=4)

    async def main():
        state = {"calls": []}
        runner, url = await _start_app(_fake_gen_app(state))
        name_resolve.add(names.gen_servers(EXP, TRIAL, "gen0"), url,
                         replace=True)
        mgr = _mgr(n_servers=1, max_head_offpolicyness=100,
                   health_check_interval_secs=30.0)
        await mgr.start()
        puller = ZmqPuller(EXP, TRIAL, "trainer")  # pusher blocks without it
        inj = FaultInjector()
        inj.arm("generate", times=-1)
        worker = RolloutWorker(RolloutWorkerConfig(
            experiment=EXP, trial=TRIAL, dataset_path=data_path,
            gconfig=GenerationHyperparameters(max_new_tokens=8),
            group_size=2, chunk_tokens=4, max_concurrent=2,
            tokenizer=MockTokenizer(), max_rollouts=2,
            retry=RetryPolicy(max_attempts=2, base_delay_secs=0.01),
        ), fault_injector=inj)
        await worker.run_async()  # must NOT raise
        assert worker._abandoned >= 2 and worker._pushed == 0
        # in-flight rollouts beyond max_rollouts drain on the same loop
        for _ in range(200):
            if mgr.running_rollouts == 0 and not mgr._leases:
                break
            await asyncio.sleep(0.05)
        assert mgr.running_rollouts == 0  # no leaked quota
        assert not mgr._leases
        await mgr.stop()
        await runner.cleanup()
        puller.close()

    asyncio.run(main())


@pytest.mark.chaos
@pytest.mark.timeout(300)
def test_chaos_kill_one_of_two_servers_mid_run(tmp_path):
    """THE acceptance chaos run: two real generation servers, one killed
    mid-generation. Interrupted rollouts fail over to the survivor, every
    trajectory is delivered, running_rollouts returns to 0, the worker
    never raises, and the dead server is evicted from routing."""
    import jax

    from areal_tpu.api.model import GenerationHyperparameters
    from areal_tpu.base.testing import MockTokenizer, make_math_jsonl
    from areal_tpu.models import transformer
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system.generation_server import (
        GenerationServer,
        GenerationServerConfig,
    )
    from areal_tpu.system.rollout_worker import (
        RolloutWorker,
        RolloutWorkerConfig,
    )
    from areal_tpu.system.streams import ZmqPuller

    name_resolve.DEFAULT_REPO = name_resolve.NfsNameRecordRepo(
        str(tmp_path / "nr")
    )
    data_path = str(tmp_path / "math.jsonl")
    make_math_jsonl(data_path, n=6)
    mcfg = tiny_config(vocab_size=258, n_layers=2, hidden_dim=32)
    params = transformer.init_params(mcfg, jax.random.PRNGKey(0))

    async def main():
        servers = []
        for sid in ("gen0", "gen1"):
            s = GenerationServer(
                GenerationServerConfig(
                    experiment=EXP, trial=TRIAL, server_id=sid,
                    chunk_tokens=4, prompt_bucket=16, batch_window_ms=2,
                ),
                mcfg, params,
            )
            await s.start()
            servers.append(s)
        victim_url = name_resolve.get(names.gen_servers(EXP, TRIAL, "gen0"))

        mgr = GserverManager(GserverManagerConfig(
            experiment=EXP, trial=TRIAL, n_servers=2,
            train_batch_size=4, max_head_offpolicyness=100,
            realloc_dir=str(tmp_path / "realloc"), weight_poll_secs=5.0,
            health_check_interval_secs=0.1, health_check_timeout_secs=0.5,
            health_failure_threshold=2,
        ))
        await mgr.start()

        puller = ZmqPuller(EXP, TRIAL, "trainer")
        worker = RolloutWorker(RolloutWorkerConfig(
            experiment=EXP, trial=TRIAL, dataset_path=data_path,
            gconfig=GenerationHyperparameters(max_new_tokens=8),
            group_size=2, chunk_tokens=4, max_concurrent=2,
            tokenizer=MockTokenizer(), max_rollouts=6,
            retry=RetryPolicy(max_attempts=10, base_delay_secs=0.02,
                              max_delay_secs=0.5),
            agent_args={"success_rate_lb": 0.0, "success_rate_ub": 1.0},
        ))
        run_task = asyncio.create_task(worker.run_async())

        # let the run make progress, then crash gen0 mid-generation
        while worker._done < 1:
            await asyncio.sleep(0.05)
            assert not run_task.done() or run_task.exception() is None
        await servers[0].stop(abort=True)

        await run_task  # the worker must complete WITHOUT raising

        # all 6 rollouts delivered (failover, not loss): ≥ 6 × group 2
        assert worker._done >= 6 and worker._abandoned == 0
        assert worker._pushed >= 12
        got = 0
        for _ in range(400):
            if puller.pull(timeout_ms=20) is not None:
                got += 1
            elif got >= 12:
                break
        assert got >= 12  # every trajectory arrived over the push stream

        # in-flight rollouts beyond max_rollouts drain on the same loop
        for _ in range(400):
            if mgr.running_rollouts == 0:
                break
            await asyncio.sleep(0.05)
        assert mgr.running_rollouts == 0  # quota fully drained

        # the dead server ends up evicted from routing (health loop)
        for _ in range(100):
            if victim_url not in mgr.servers:
                break
            await asyncio.sleep(0.1)
        assert victim_url not in mgr.servers
        assert not mgr.health[victim_url].routable
        # survivor still routable
        assert len(mgr.servers) == 1

        await mgr.stop()
        await servers[1].stop()
        puller.close()

    asyncio.run(main())


# ----------------------------------------------------------- reward client


@pytest.mark.chaos
def test_batch_reward_event_loop_contract(monkeypatch):
    """The async rollout path awaits abatch_reward (grading never blocks
    the loop); the SYNC form now refuses to run on a running loop — the
    old silent dedicated-thread bridge blocked every in-flight rollout.
    With an unreachable service both forms fall back to local grading
    with identical results."""
    from areal_tpu.rewards import client as rclient

    monkeypatch.setenv(rclient.SERVICE_ENV, "127.0.0.1:9")
    tasks = [{"task": "math", "generated": "\\boxed{4}",
              "solutions": ["4"]}] * 2

    sync_scores = rclient.batch_reward(tasks, max_retries=0)
    assert len(sync_scores) == 2

    async def inside_loop():
        with pytest.raises(RuntimeError, match="abatch_reward"):
            rclient.batch_reward(tasks, max_retries=0)
        return await rclient.abatch_reward(tasks, max_retries=0)

    async_scores = asyncio.run(inside_loop())
    assert async_scores == sync_scores


# ------------------------------------------------- supervision (ISSUE 9)


def _child_sleep_forever():
    while True:
        time.sleep(0.5)


def _child_exit_zero():
    pass  # immediate clean exit


def _child_exit_three():
    import sys

    sys.exit(3)


class _FakeProc:
    """Process stand-in for deterministic supervisor state-machine tests
    (no spawns, no sleeps)."""

    _next_pid = [1000]

    def __init__(self):
        _FakeProc._next_pid[0] += 1
        self.pid = _FakeProc._next_pid[0]
        self._alive = True
        self.exitcode = None

    def is_alive(self):
        return self._alive

    def die(self, code):
        self._alive = False
        self.exitcode = code

    def join(self, timeout=None):
        pass

    def terminate(self):
        self.die(-15)

    def kill(self):
        self.die(-9)


def _fake_supervisor(clock, **policy_kw):
    from areal_tpu.system.supervisor import RestartPolicy, Supervisor

    sup = Supervisor("supfake", "t0",
                     policy=RestartPolicy(**policy_kw), clock=clock)
    sup._make_proc = lambda spec, incarnation: _FakeProc()
    return sup


@pytest.mark.chaos
def test_supervisor_backoff_and_circuit_breaker(tmp_name_resolve):
    """Deaths of a stateless worker schedule respawns with exponential
    backoff; exceeding max_restarts inside the rolling window opens the
    circuit breaker (SupervisorEscalation); restarts outside the window
    are pruned and do not count."""
    from areal_tpu.system.supervisor import SupervisorEscalation, WorkerSpec

    t = [0.0]
    sup = _fake_supervisor(lambda: t[0], max_restarts=2, window_secs=100.0,
                           backoff_base_secs=1.0, backoff_max_secs=8.0,
                           backoff_multiplier=2.0)
    sup.spawn(WorkerSpec(name="rollout0", kind="rollout",
                         target=_child_sleep_forever))
    e = sup._entries["rollout0"]
    p1 = e.proc

    p1.die(-9)  # SIGKILL
    sup.check()
    assert e.respawn_due == pytest.approx(1.0)  # base backoff
    t[0] = 0.5
    sup.check()
    assert e.proc is p1  # not due yet: no respawn
    t[0] = 1.0
    sup.check()
    assert e.proc is not p1 and e.proc.is_alive()
    assert sup.restart_counts == {"rollout": 1}

    e.proc.die(1)
    sup.check()
    assert e.respawn_due == pytest.approx(1.0 + 2.0)  # doubled
    t[0] = 3.0
    sup.check()
    assert sup.restart_counts == {"rollout": 2}

    # third death inside the window: 2 restarts == max_restarts -> open
    e.proc.die(1)
    with pytest.raises(SupervisorEscalation, match="crash-loop"):
        sup.check()

    # outside the window the history is pruned: a fresh death respawns
    sup2 = _fake_supervisor(lambda: t[0], max_restarts=1, window_secs=10.0,
                            backoff_base_secs=0.5, backoff_max_secs=8.0)
    sup2.spawn(WorkerSpec(name="gen_fleet", kind="gen_fleet",
                          target=_child_sleep_forever))
    e2 = sup2._entries["gen_fleet"]
    t[0] = 0.0
    e2.proc.die(-9)
    sup2.check()
    t[0] = 0.5
    sup2.check()
    assert sup2.restart_counts == {"gen_fleet": 1}
    t[0] = 50.0  # window long gone
    e2.proc.die(-9)
    sup2.check()  # would escalate if the old restart still counted
    t[0] = 50.5
    sup2.check()
    assert sup2.restart_counts == {"gen_fleet": 2}


@pytest.mark.chaos
def test_supervisor_failure_domains_and_clean_exit(tmp_name_resolve):
    """Failure-domain classification: trainer (stateful) death escalates
    immediately — including an unexpected CLEAN exit, which previously
    went unnoticed while the master blocked on data-wait forever; a
    required stateless worker's clean exit is respawned; an optional
    worker's clean exit is ignored; drain suppresses everything."""
    from areal_tpu.system.supervisor import SupervisorEscalation, WorkerSpec

    t = [0.0]
    sup = _fake_supervisor(lambda: t[0], backoff_base_secs=0.1)
    sup.spawn(WorkerSpec(name="trainer", kind="trainer",
                         target=_child_sleep_forever))
    sup._entries["trainer"].proc.die(0)  # clean but unrequested
    with pytest.raises(SupervisorEscalation, match="stateful"):
        sup.check()

    sup = _fake_supervisor(lambda: t[0], backoff_base_secs=0.1)
    sup.spawn(WorkerSpec(name="rollout0", kind="rollout",
                         target=_child_sleep_forever))
    sup._entries["rollout0"].proc.die(0)  # early clean exit: a failure
    sup.check()
    assert sup._entries["rollout0"].respawn_due is not None

    sup = _fake_supervisor(lambda: t[0], backoff_base_secs=0.1)
    sup.spawn(WorkerSpec(name="aux", kind="rollout",
                         target=_child_sleep_forever, required=False))
    sup._entries["aux"].proc.die(0)  # optional: done, not a failure
    sup.check()
    assert sup._entries["aux"].respawn_due is None
    assert sup.restart_counts == {}

    sup = _fake_supervisor(lambda: t[0], backoff_base_secs=0.1)
    sup.spawn(WorkerSpec(name="trainer", kind="trainer",
                         target=_child_sleep_forever))
    sup.begin_drain()
    sup._entries["trainer"].proc.die(-15)
    sup.check()  # expected death during drain: no escalation


@pytest.mark.chaos
def test_supervisor_clears_ghost_keys_on_respawn(tmp_name_resolve):
    """A gen-fleet respawn must clear the dead incarnation's discovery
    keys (manager URL, server urls, heartbeats) BEFORE the new process
    binds fresh ones — nothing may resolve a corpse in the gap."""
    from areal_tpu.base import name_resolve, names
    from areal_tpu.system.supervisor import WorkerSpec
    from areal_tpu.system.worker_base import worker_control_key

    t = [0.0]
    sup = _fake_supervisor(lambda: t[0], backoff_base_secs=0.1)
    exp, trial = "supfake", "t0"
    name_resolve.add(names.gen_server_manager(exp, trial),
                     "http://127.0.0.1:1", replace=True)
    name_resolve.add(names.gen_servers(exp, trial, "gen0"),
                     "http://127.0.0.1:2", replace=True)
    name_resolve.add(names.worker_heartbeat(exp, trial, "gserver_manager"),
                     "{}", replace=True)
    name_resolve.add(names.worker_heartbeat(exp, trial, "genserver_gen0"),
                     "{}", replace=True)
    name_resolve.add(names.worker_heartbeat(exp, trial, "rollout0"),
                     "{}", replace=True)  # another worker's: must survive
    name_resolve.add(worker_control_key(exp, trial, "gen_fleet"),
                     "tcp://127.0.0.1:3", replace=True)

    sup.spawn(WorkerSpec(name="gen_fleet", kind="gen_fleet",
                         target=_child_sleep_forever))
    sup._entries["gen_fleet"].proc.die(-9)
    sup.check()
    t[0] = 1.0
    sup.check()  # respawn happens here

    for key in (
        names.gen_server_manager(exp, trial),
        names.gen_servers(exp, trial, "gen0"),
        names.worker_heartbeat(exp, trial, "gserver_manager"),
        names.worker_heartbeat(exp, trial, "genserver_gen0"),
        worker_control_key(exp, trial, "gen_fleet"),
    ):
        with pytest.raises(name_resolve.NameEntryNotFoundError):
            name_resolve.get(key)
    # the rollout worker's heartbeat was not collateral damage
    assert name_resolve.get(
        names.worker_heartbeat(exp, trial, "rollout0")
    ) == "{}"


@pytest.mark.chaos
@pytest.mark.timeout(120)
def test_supervisor_respawns_sigkilled_process(tmp_name_resolve):
    """End to end with REAL processes: SIGKILL a supervised child; the
    supervisor detects the death on its next sweep, backs off, respawns a
    fresh incarnation, and counts the restart."""
    from areal_tpu.system.supervisor import (
        RestartPolicy,
        Supervisor,
        WorkerSpec,
    )

    sup = Supervisor("supreal", "t0", policy=RestartPolicy(
        max_restarts=3, window_secs=60.0, backoff_base_secs=0.05,
        backoff_max_secs=0.2,
    ))
    sup.spawn(WorkerSpec(name="rollout0", kind="rollout",
                         target=_child_sleep_forever))
    e = sup._entries["rollout0"]
    pid1 = e.proc.pid
    deadline = time.monotonic() + 30
    while not e.proc.is_alive() and time.monotonic() < deadline:
        time.sleep(0.02)
    os.kill(pid1, signal.SIGKILL)
    while time.monotonic() < deadline:
        sup.check()
        if e.proc.pid != pid1 and e.proc.is_alive():
            break
        time.sleep(0.02)
    try:
        assert e.proc.pid != pid1 and e.proc.is_alive()
        assert sup.restart_counts == {"rollout": 1}
        assert e.incarnation == 2
    finally:
        sup.shutdown(timeout=5.0, orderly=False)


@pytest.mark.chaos
@pytest.mark.timeout(120)
def test_supervisor_escalates_real_crash_loop(tmp_name_resolve):
    """A child that exits 3 on every start trips the circuit breaker
    after max_restarts respawns."""
    from areal_tpu.system.supervisor import (
        RestartPolicy,
        Supervisor,
        SupervisorEscalation,
        WorkerSpec,
    )

    sup = Supervisor("supreal2", "t0", policy=RestartPolicy(
        max_restarts=1, window_secs=60.0, backoff_base_secs=0.02,
        backoff_max_secs=0.05,
    ))
    sup.spawn(WorkerSpec(name="rollout0", kind="rollout",
                         target=_child_exit_three))
    deadline = time.monotonic() + 60
    try:
        with pytest.raises(SupervisorEscalation, match="crash-loop"):
            while time.monotonic() < deadline:
                sup.check()
                time.sleep(0.02)
            pytest.fail("circuit breaker never opened")
        assert sup.restart_counts == {"rollout": 1}  # 1 respawn, then open
    finally:
        sup.shutdown(timeout=5.0, orderly=False)


# ------------------------------------------------------- graceful drain


def _fake_ctrl_worker(exp, trial, name, events, stop_evt, commands=None):
    """In-process fake worker: serves a WorkerControl loop and records
    lifecycle events. `commands` maps custom cmd -> result."""
    from areal_tpu.system.worker_base import WorkerControl, WorkerState

    ctrl = WorkerControl(exp, trial, name)
    for cmd, result in (commands or {}).items():
        ctrl.on_command(
            cmd,
            lambda payload, c=cmd, r=result: events.append((name, c)) or r,
        )
    last_state = None
    while not stop_evt.is_set():
        ctrl.step()
        if ctrl.state != last_state:
            events.append((name, ctrl.state.value))
            last_state = ctrl.state
        if ctrl.should_exit:
            break
        time.sleep(0.005)
    ctrl.close()


@pytest.mark.chaos
@pytest.mark.timeout(120)
def test_graceful_drain_sequence(tmp_name_resolve):
    """drain_experiment against in-process fakes: master paused FIRST
    (so it never starts another step), rollouts paused, an out-of-band
    checkpoint lands while the master is paused, then everyone exits in
    order. Zero real processes, zero long sleeps."""
    from areal_tpu.system.supervisor import drain_experiment

    exp, trial = "drainfake", "t0"
    events, stop = [], threading.Event()
    threads = [
        threading.Thread(
            target=_fake_ctrl_worker,
            args=(exp, trial, "master", events, stop),
            kwargs={"commands": {"checkpoint": {"saved": True,
                                                "dir": "/tmp/ck"}}},
            daemon=True,
        ),
        threading.Thread(
            target=_fake_ctrl_worker,
            args=(exp, trial, "rollout0", events, stop), daemon=True,
        ),
    ]
    for t in threads:
        t.start()
    try:
        from areal_tpu.system.worker_base import WorkerControlPanel

        wait_panel = WorkerControlPanel(exp, trial, timeout=2.0)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if set(wait_panel.list_workers()) == {"master", "rollout0"}:
                break
            time.sleep(0.02)
        wait_panel.close()
        report = drain_experiment(exp, trial, timeout=20.0)
        assert report["paused"]["master"]["state"] == "paused"
        assert report["paused"]["rollout0"]["state"] == "paused"
        assert report["checkpoint"]["ok"]
        assert report["checkpoint"]["result"] == {"saved": True,
                                                  "dir": "/tmp/ck"}
        assert set(report["exited"]) == {"master", "rollout0"}
        for t in threads:
            t.join(timeout=10)
            assert not t.is_alive()
        # the checkpoint executed while the master was PAUSED (between
        # steps) and before its exit
        midx = [i for i, e in enumerate(events) if e[0] == "master"]
        mevents = [events[i][1] for i in midx]
        assert "checkpoint" in mevents
        assert mevents.index("checkpoint") < mevents.index("exiting")
        from areal_tpu.base import name_resolve as nr
        from areal_tpu.base import names as _names
        import json as _json

        phase = _json.loads(nr.get(_names.drain_status(exp, trial)))
        assert phase["phase"] == "done"
    finally:
        stop.set()


# ------------------------------------------------ liveness leases


@pytest.mark.chaos
def test_name_resolve_keepalive_lease_expiry_and_touch(tmp_name_resolve):
    """Both repo backends: a key registered with keepalive_ttl expires
    once unheartbeaten (get/find purge it); touch() extends the lease;
    re-registration without a lease sheds the old TTL."""
    from areal_tpu.base.name_resolve import (
        MemoryNameRecordRepo,
        NameEntryNotFoundError,
    )

    repos = [MemoryNameRecordRepo(), name_resolve.DEFAULT_REPO]
    for repo in repos:
        repo.add("lease/a", "v1", keepalive_ttl=0.15, replace=True)
        repo.add("lease/b", "v2", replace=True)  # no lease: immortal
        assert repo.get("lease/a") == "v1"
        # touch keeps it alive past the original deadline
        for _ in range(3):
            time.sleep(0.08)
            repo.touch("lease/a")
        assert repo.get("lease/a") == "v1"
        time.sleep(0.25)  # no heartbeat: lease lapses
        with pytest.raises(NameEntryNotFoundError):
            repo.get("lease/a")
        with pytest.raises(NameEntryNotFoundError):
            repo.touch("lease/a")
        assert repo.find_subtree("lease") == ["lease/b"]
        assert repo.get("lease/b") == "v2"
        # an expired slot is re-registerable even without replace=True
        repo.add("lease/a", "v3", keepalive_ttl=0.15)
        # re-registration WITHOUT a ttl must not inherit the old lease
        repo.add("lease/a", "v4", replace=True)
        time.sleep(0.25)
        assert repo.get("lease/a") == "v4"
        repo.delete("lease/a")
        repo.delete("lease/b")


@pytest.mark.chaos
def test_worker_control_heartbeat_and_incarnation(tmp_name_resolve,
                                                  monkeypatch):
    """A supervised worker (env-stamped TTL + incarnation) keeps its
    control advertisement leased via the heartbeat thread, publishes a
    heartbeat key the panel can age, and reports its incarnation in
    status; close() withdraws both keys."""
    from areal_tpu.system import worker_base as wb

    monkeypatch.setenv(wb.ENV_INCARNATION, "3")
    monkeypatch.setenv(wb.ENV_KEEPALIVE_TTL, "0.3")
    exp, trial = "hbexp", "t0"
    stop = threading.Event()

    def worker():
        ctrl = wb.WorkerControl(exp, trial, "w0")
        while not stop.is_set():
            ctrl.step()
            if ctrl.should_exit:
                break
            time.sleep(0.01)
        ctrl.close()

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    panel = wb.WorkerControlPanel(exp, trial, timeout=5.0)
    try:
        st = panel.status("w0")
        assert st["incarnation"] == 3
        hbs = panel.heartbeats()
        assert hbs["w0"]["incarnation"] == 3
        assert hbs["w0"]["age_secs"] < 5.0
        # the lease outlives its TTL because the heartbeat touches it
        time.sleep(0.6)
        assert panel.list_workers() == ["w0"]
        panel.exit("w0")
        t.join(timeout=5)
        assert not t.is_alive()
        # close() withdrew advertisement + heartbeat
        assert panel.list_workers() == []
        assert panel.heartbeats() == {}
    finally:
        stop.set()
        panel.close()


# ------------------------------------------------ crash-safe ConsumedLog


@pytest.mark.chaos
def test_consumed_log_fsync_and_torn_tail(tmp_path):
    """Every append reaches disk before add() returns (no buffered FH
    loss), and a torn tail (crash mid-append: final line without its
    newline) is dropped by the reader instead of being treated as a
    consumed uid — the prompt re-trains once, which is the safe
    direction."""
    from areal_tpu.system.rollout_worker import ConsumedLog

    log = ConsumedLog(str(tmp_path), worker_index=0)
    log.add("q1")
    log.add("q2")
    # durable WITHOUT close(): a SIGKILL after add() must lose nothing
    with open(log.path) as f:
        assert f.read() == "q1\nq2\n"
    # simulate a crash mid-append: torn record without its newline
    with open(log.path, "a") as f:
        f.write("q3@r")
    log2 = ConsumedLog(str(tmp_path), worker_index=0)
    assert "q1" in log2 and "q2" in log2
    assert "q3@r" not in log2 and "q3@r1" not in log2
    # the reader REPAIRED the file (fragment truncated), so appends after
    # a torn tail start on a fresh line instead of merging into it
    log2.add("q4")
    log3 = ConsumedLog(str(tmp_path), worker_index=0)
    assert log3.seen == {"q1", "q2", "q4"}
    log.close()
    log2.close()


# ------------------------------------- run_experiment relaunch hygiene


@pytest.mark.chaos
def test_run_experiment_relaunch_backoff_and_subtree_clear(
    tmp_name_resolve, monkeypatch
):
    """The auto-recover relaunch loop backs off between attempts and
    clears the dead incarnation's name_resolve subtree so the relaunch
    cannot discover stale endpoints."""
    import types

    from areal_tpu.apps import launcher as L

    cfg = types.SimpleNamespace(
        experiment_name="rx", trial_name="t0", mode="local",
        recover_mode="auto", recover_retries=2, serving=None,
        fault_tolerance=types.SimpleNamespace(
            relaunch_backoff_secs=0.2, relaunch_backoff_max_secs=1.0,
        ),
    )
    name_resolve.add("areal_tpu/rx/t0/stream/trainer", "tcp://dead:1",
                     replace=True)
    calls = {"n": 0}
    sleeps = []

    class _FakeLauncher:
        def __init__(self, exp_cfg, force_cpu=None):
            pass

        def run(self):
            calls["n"] += 1
            if calls["n"] == 1:
                # the stale key must still be visible to attempt 1
                assert name_resolve.get(
                    "areal_tpu/rx/t0/stream/trainer"
                ) == "tcp://dead:1"
                raise RuntimeError("worker died")
            # attempt 2: the subtree was cleared before the relaunch
            with pytest.raises(name_resolve.NameEntryNotFoundError):
                name_resolve.get("areal_tpu/rx/t0/stream/trainer")
            return {"steps": 7}

    monkeypatch.setattr(L, "LocalLauncher", _FakeLauncher)
    monkeypatch.setattr(L.time, "sleep", lambda s: sleeps.append(s))
    result = L.run_experiment(cfg)
    assert result == {"steps": 7}
    assert calls["n"] == 2
    assert sleeps == [pytest.approx(0.2)]
    assert cfg.recover_mode == "resume"


# ------------------------------------ launcher-level e2e (slow suite)


def _build_supervised_async_cfg(tmp_path, exp_name, benchmark_steps,
                                http_port=0):
    """A complete tiny async-PPO experiment config routed through the
    REAL launcher (supervisor, liveness leases, graceful drain) — the
    in-process analogue of test_entry_scripts' CLI launches."""
    from areal_tpu.base.testing import make_math_jsonl
    from areal_tpu.experiments.async_ppo_math_exp import AsyncPPOMATHConfig

    data_path = str(tmp_path / "math.jsonl")
    if not os.path.exists(data_path):
        make_math_jsonl(data_path, n=8)
    cfg = AsyncPPOMATHConfig(
        experiment_name=exp_name, trial_name="t0", mock_tokenizer=True,
    )
    cfg.cluster.fileroot = str(tmp_path / "exps")
    cfg.actor.tiny = {"vocab_size": 258, "seed": 0}
    cfg.ref.tiny = {"vocab_size": 258, "seed": 0}
    cfg.dataset.path = data_path
    cfg.dataset.train_bs_n_seqs = 4
    cfg.group_size = 2
    import dataclasses as _dc

    cfg.ppo.gen = _dc.replace(cfg.ppo.gen, max_new_tokens=8)
    cfg.ppo.ppo_n_minibatches = 2
    cfg.ppo.kl_ctl = 0.05
    cfg.ppo.disable_value = True
    cfg.ppo.use_decoupled_loss = True
    cfg.exp_ctrl.benchmark_steps = benchmark_steps
    cfg.exp_ctrl.total_train_epochs = 10**6
    cfg.max_head_offpolicyness = 4
    cfg.max_concurrent_rollouts = 4
    cfg.new_tokens_per_chunk = 4
    cfg.gen_batch_window_ms = 2
    cfg.gen_prompt_bucket = 16
    cfg.telemetry.enabled = True
    cfg.telemetry.flush_interval_secs = 0.3
    cfg.telemetry.http_port = http_port
    cfg.fault_tolerance.backoff_base_secs = 0.2
    cfg.fault_tolerance.backoff_max_secs = 1.0
    cfg.fault_tolerance.keepalive_ttl_secs = 10.0
    return cfg


def _wait_master_step(exp, trial, min_step, deadline_secs=420):
    """Poll the master's control status until its step counter reaches
    min_step (commands time out while it is busy inside a step)."""
    from areal_tpu.system.worker_base import WorkerControlPanel

    panel = WorkerControlPanel(exp, trial, timeout=3.0)
    try:
        deadline = time.monotonic() + deadline_secs
        while time.monotonic() < deadline:
            try:
                st = panel.status("master")
                if st.get("step", 0) >= min_step:
                    return st["step"]
            except TimeoutError:
                pass
            time.sleep(0.25)
    finally:
        panel.close()
    raise AssertionError(f"master never reached step {min_step}")


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.timeout(900)
def test_chaos_e2e_sigkill_rollout_and_fleet_no_relaunch(tmp_path):
    """THE ISSUE 9 acceptance chaos run: SIGKILL one rollout worker AND
    the gen-fleet process during a live launcher-supervised async-PPO
    experiment. The supervisor respawns both in place (rejoining through
    name_resolve + the manager's re-admission/weight-reconcile), the
    experiment completes with ZERO whole-experiment relaunches, and the
    per-kind supervisor restart counters are visible on the merged
    Prometheus scrape."""
    import urllib.request

    from areal_tpu.apps.launcher import LocalLauncher
    from areal_tpu.base import network as _network
    from areal_tpu.experiments import common as C

    port = _network.find_free_port()
    # Enough steps that the run genuinely DEPENDS on the killed workers:
    # warm tiny-model steps take <1s, so a short run would complete
    # before the chaos window opens; with 40 steps the master stalls on
    # data-wait while the fleet is down and only finishes because the
    # respawns restore the flow.
    cfg = _build_supervised_async_cfg(tmp_path, "supchaos",
                                      benchmark_steps=40, http_port=port)
    C.setup_name_resolve(cfg)
    launcher = LocalLauncher(cfg)
    result, errs = {}, []

    def _run():
        try:
            result.update(launcher.run())
        except BaseException as e:  # noqa: BLE001 — asserted below
            errs.append(e)

    t = threading.Thread(target=_run, daemon=True)
    t.start()
    try:
        _wait_master_step("supchaos", "t0", 1)
        sup = launcher.supervisor

        # SIGKILL the rollout worker; wait for its respawn
        e_roll = sup._entries["rollout0"]
        pid = e_roll.proc.pid
        os.kill(pid, signal.SIGKILL)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if e_roll.proc.pid != pid and e_roll.proc.is_alive():
                break
            time.sleep(0.1)
        assert e_roll.proc.pid != pid and e_roll.proc.is_alive()

        # SIGKILL the whole gen-fleet process (servers + manager)
        e_fleet = sup._entries["gen_fleet"]
        pid = e_fleet.proc.pid
        os.kill(pid, signal.SIGKILL)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if e_fleet.proc.pid != pid and e_fleet.proc.is_alive():
                break
            time.sleep(0.1)
        assert e_fleet.proc.pid != pid and e_fleet.proc.is_alive()

        # the restart counters reach the merged Prometheus scrape while
        # the run is still alive (the aggregator dies with the master)
        scrape = None
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and t.is_alive():
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=5
                ) as r:
                    body = r.read().decode()
                if (
                    'areal_supervisor_restarts_total{'
                    in body
                    and 'worker_kind="rollout"' in body
                    and 'worker_kind="gen_fleet"' in body
                ):
                    scrape = body
                    break
            except Exception:  # noqa: BLE001 — aggregator busy
                pass
            time.sleep(0.3)
        assert scrape is not None, "supervisor metrics never scraped"

        t.join(timeout=700)
        assert not t.is_alive(), "experiment never completed"
        assert not errs, errs  # zero escalations / whole-run relaunches
        assert result["steps"] == 40
        assert launcher.supervisor.restart_counts == {
            "rollout": 1, "gen_fleet": 1,
        }
    finally:
        launcher.request_drain()
        t.join(timeout=30)
        if launcher.supervisor is not None:
            launcher.supervisor.shutdown(timeout=10.0, orderly=False)


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.timeout(900)
def test_drain_e2e_sigterm_then_resume(tmp_path):
    """THE ISSUE 9 acceptance drain run: a graceful drain mid-step (the
    SIGTERM path — request_drain() is the handler's body) produces a
    COMPLETE (.complete-marked) recover checkpoint and clean worker
    exits; relaunching with recover_mode=resume continues from the
    drained step to completion without re-training consumed prompts."""
    from areal_tpu.apps.launcher import LocalLauncher, run_experiment
    from areal_tpu.base import recover
    from areal_tpu.experiments import common as C

    cfg = _build_supervised_async_cfg(tmp_path, "supdrain",
                                      benchmark_steps=40)
    C.setup_name_resolve(cfg)
    paths = C.experiment_paths(cfg)
    launcher = LocalLauncher(cfg)
    result, errs = {}, []

    def _run():
        try:
            result.update(launcher.run())
        except BaseException as e:  # noqa: BLE001 — asserted below
            errs.append(e)

    t = threading.Thread(target=_run, daemon=True)
    t.start()
    _wait_master_step("supdrain", "t0", 1)
    launcher.request_drain()  # == the SIGTERM handler's body
    t.join(timeout=420)
    assert not t.is_alive(), "drain never completed"
    assert not errs, errs
    drained_steps = result["steps"]
    assert 1 <= drained_steps < 40  # exited early, cleanly

    # a COMPLETE out-of-band recover checkpoint exists at the drained step
    info = recover.load(paths["recover"])
    assert info is not None
    assert info.last_step_info.global_step == drained_steps
    ckpt = recover.discover_ckpt(paths["recover"])
    assert ckpt is not None
    assert os.path.exists(os.path.join(ckpt, recover.CKPT_COMPLETE_MARKER))

    # consumed-uid log survived the drain (fsynced appends)
    consumed_path = os.path.join(paths["recover"], "rollout_consumed_0.log")
    assert os.path.exists(consumed_path)
    with open(consumed_path) as f:
        consumed_before = {ln.strip() for ln in f if ln.strip()}
    assert consumed_before

    # resume: the relaunch restores the drained step and finishes the
    # remaining steps; consumed prompts are not re-trained (the log only
    # GROWS — a re-train would require re-consuming one of them, which
    # the skiplist forbids by construction)
    cfg.recover_mode = "resume"
    result2 = run_experiment(cfg)
    assert result2["steps"] == 40
    with open(consumed_path) as f:
        consumed_after = {ln.strip() for ln in f if ln.strip()}
    assert consumed_before <= consumed_after


@pytest.mark.chaos
def test_supervisor_honors_shutdown_markers(tmp_name_resolve):
    """A commanded teardown (master's end-of-run marker, or an EXTERNAL
    drain's phase record) makes subsequent deaths expected — the
    trainer's commanded exit during the master's teardown tail must not
    escalate a successful run. Markers older than the supervisor (a
    previous incarnation's) do NOT suppress detection."""
    import json as _json

    from areal_tpu.system.supervisor import SupervisorEscalation, WorkerSpec

    t = [0.0]
    sup = _fake_supervisor(lambda: t[0], backoff_base_secs=0.1)
    sup.spawn(WorkerSpec(name="trainer", kind="trainer",
                         target=_child_sleep_forever))
    name_resolve.add(
        names.experiment_status("supfake", "t0"),
        _json.dumps({"status": "finishing", "ts": time.time() + 1}),
        replace=True,
    )
    sup._entries["trainer"].proc.die(0)
    sup.check()  # expected: no escalation
    assert sup._entries["trainer"].done
    name_resolve.delete(names.experiment_status("supfake", "t0"))

    # stale marker from a PREVIOUS trial incarnation: detection stays on
    sup2 = _fake_supervisor(lambda: t[0], backoff_base_secs=0.1)
    name_resolve.add(
        names.experiment_status("supfake", "t0"),
        _json.dumps({"status": "finishing", "ts": time.time() - 3600}),
        replace=True,
    )
    sup2.spawn(WorkerSpec(name="trainer", kind="trainer",
                          target=_child_sleep_forever))
    sup2._entries["trainer"].proc.die(0)
    with pytest.raises(SupervisorEscalation):
        sup2.check()
    name_resolve.delete(names.experiment_status("supfake", "t0"))


@pytest.mark.chaos
def test_heartbeat_reregisters_lapsed_lease(tmp_name_resolve):
    """A lease that lapsed (stall/purge longer than the TTL) is
    RE-REGISTERED by the next beat when the value was recorded — a live
    worker must never stay deregistered because one heartbeat was
    late."""
    from areal_tpu.system.worker_base import HeartbeatThread

    hb = HeartbeatThread("hbre", "t0", "w0", interval=0.05)
    try:
        name_resolve.add("hbre/k", "addr", keepalive_ttl=5.0, replace=True)
        hb.lease("hbre/k", "addr", 5.0)
        name_resolve.delete("hbre/k")  # simulate an expiry purge
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            try:
                if name_resolve.get("hbre/k") == "addr":
                    break
            except name_resolve.NameEntryNotFoundError:
                pass
            time.sleep(0.02)
        assert name_resolve.get("hbre/k") == "addr"
    finally:
        hb.close()
