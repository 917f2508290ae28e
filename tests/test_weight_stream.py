"""Streamed weight sync: manifest/chunk protocol, integrity gates, atomic
swap, fault-tolerance integration, and disk-fallback parity.

Covers the subsystem in ``system/weight_stream.py`` plus its wiring through
``trainer_worker`` / ``generation_server`` / ``gserver_manager``
(docs/weight_sync.md):

 - manifest round-trip: a pytree published over the stream arrives
   bit-identical, shapes/dtypes preserved (bf16 stays 2 bytes)
 - torn/corrupted/reordered streams are rejected by checksum + digest
   verification and the server's live params are never touched
 - atomic (params, version) swap under a concurrent /generate load: the
   version visible via /metrics only changes after a complete verified
   manifest applied
 - a server failing mid-stream surfaces a non-200 ack, so the manager's
   existing eviction/retry machinery owns it (PR 2 guarantees)
 - disk-fallback parity: both transports deliver the same pytree bytes
"""

import asyncio
import json
import threading
import time

import jax
import numpy as np
import pytest

from areal_tpu.base import name_resolve, names, network
from areal_tpu.models.hf import flatten_pytree, unflatten_pytree
from areal_tpu.system.weight_stream import (
    WeightStreamConsumer,
    WeightStreamError,
    WeightStreamPublisher,
)

EXP, TRIAL = "wstest", "t0"


def _tree(seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    return {
        "embedding": rng.randn(64, 16).astype(dtype),
        "layers": {
            "wq": rng.randn(2, 16, 16).astype(dtype),
            "ln1": rng.randn(2, 16).astype(dtype),
        },
        "final_ln": rng.randn(16).astype(dtype),
    }


def _publish(tree, version=1, **kw) -> WeightStreamPublisher:
    pub = WeightStreamPublisher(EXP, TRIAL, "actor", **kw)
    pub.publish(sorted(flatten_pytree(tree).items()), version)
    return pub


# ------------------------------------------------------------ round trip


def test_manifest_roundtrip_bitexact(tmp_name_resolve):
    tree = _tree()
    pub = _publish(tree, version=3, chunk_bytes=1024)  # force multi-chunk
    consumer = WeightStreamConsumer(pub.endpoint)
    try:
        manifest, flat = consumer.fetch(3)
        assert manifest["version"] == 3
        assert manifest["total_bytes"] == sum(
            v.nbytes for v in flatten_pytree(tree, as_numpy=True).values()
        )
        # multi-chunk actually exercised (embedding is 4096 bytes)
        assert max(t["n_chunks"] for t in manifest["tensors"]) > 1
        got = unflatten_pytree(dict(flat))
        for k, want in flatten_pytree(tree, as_numpy=True).items():
            have = np.asarray(flat[k])
            assert have.dtype == want.dtype and have.shape == want.shape
            np.testing.assert_array_equal(have, want)
        assert set(flatten_pytree(got)) == set(flatten_pytree(tree))
        # endpoint is discoverable through the names schema
        assert name_resolve.get(
            names.weight_stream(EXP, TRIAL, "actor")
        ) == pub.endpoint
    finally:
        consumer.close()
        pub.close()


def test_bf16_wire_format_preserved(tmp_name_resolve):
    import ml_dtypes

    tree = {"w": np.arange(32, dtype=np.float32).astype(ml_dtypes.bfloat16)}
    pub = _publish(tree)
    consumer = WeightStreamConsumer(pub.endpoint)
    try:
        _, flat = consumer.fetch(1)
        assert flat["w"].dtype == ml_dtypes.bfloat16  # 2 bytes on the wire
        np.testing.assert_array_equal(flat["w"], tree["w"])
    finally:
        consumer.close()
        pub.close()


def test_jax_leaves_gathered_lazily(tmp_name_resolve):
    """Publishing device arrays works: the gather thread performs the d2h
    and the consumer sees the same values."""
    tree = jax.tree.map(jax.numpy.asarray, _tree(seed=7))
    pub = _publish(tree)
    consumer = WeightStreamConsumer(pub.endpoint)
    try:
        _, flat = consumer.fetch(1)
        for k, v in flatten_pytree(tree, as_numpy=True).items():
            np.testing.assert_array_equal(np.asarray(flat[k]), v)
    finally:
        consumer.close()
        pub.close()


def test_unknown_version_and_replay(tmp_name_resolve):
    pub = _publish(_tree(), version=5)
    c1 = WeightStreamConsumer(pub.endpoint, timeout_secs=5)
    c2 = WeightStreamConsumer(pub.endpoint, timeout_secs=5)
    try:
        with pytest.raises(WeightStreamError, match="not cached"):
            c1.fetch_manifest(4)
        # per-server replay: two consumers fetch the same publish
        _, f1 = c1.fetch(5)
        _, f2 = c2.fetch(5)
        for k in f1:
            np.testing.assert_array_equal(f1[k], f2[k])
    finally:
        c1.close()
        c2.close()
        pub.close()


# ------------------------------------------------------- integrity gates


def test_corrupted_chunk_rejected(tmp_name_resolve):
    """Bytes corrupted in the publisher cache AFTER checksumming must fail
    the consumer's wire CRC check — the swap never happens."""
    pub = _publish(_tree(), chunk_bytes=1024)
    assert pub.wait_complete(1, timeout=10)
    entry = pub._cache[1]
    entry.arrays[0] = entry.arrays[0].copy()
    entry.arrays[0].reshape(-1).view(np.uint8)[3] ^= 0xFF
    consumer = WeightStreamConsumer(pub.endpoint, timeout_secs=5)
    try:
        with pytest.raises(WeightStreamError, match="checksum mismatch"):
            consumer.fetch(1)
    finally:
        consumer.close()
        pub.close()


def test_reordered_stream_rejected(tmp_name_resolve):
    """Replies arriving out of request order (swapped chunk coordinates)
    must abort: the echoed (tensor, chunk) is verified per reply."""
    pub = _publish(_tree(), chunk_bytes=512)
    assert pub.wait_complete(1, timeout=10)
    orig = pub._handle

    def swapped(frames):
        reply = orig(frames)
        if frames[0] == b"chunk":
            meta = json.loads(reply[1])
            meta["chunk"] += 1  # lie about which chunk this is
            reply[1] = json.dumps(meta).encode()
        return reply

    pub._handle = swapped
    consumer = WeightStreamConsumer(pub.endpoint, timeout_secs=5)
    try:
        with pytest.raises(WeightStreamError, match="out-of-order"):
            consumer.fetch(1)
    finally:
        consumer.close()
        pub.close()


def test_replies_keep_a_consumers_order_while_the_gather_lags(
        tmp_name_resolve):
    """A consumer that outruns the gather thread has requests deferred on
    the publisher; a later request of the SAME consumer whose data is
    ready must not overtake them (iter_tensors reads replies in request
    order: "out-of-order chunk" on a busy machine). Another consumer's
    servable request is not held up by them."""
    import threading

    gate = threading.Event()

    class Gated:  # a leaf whose d2h gather blocks until the gate opens
        shape, dtype = (4,), np.dtype(np.float32)

        def __array__(self, dtype=None, copy=None):
            assert gate.wait(30)
            return np.arange(4, dtype=np.float32)

    pub = WeightStreamPublisher(EXP, TRIAL, "actor")
    pub.publish([("a_ready", np.ones(4, np.float32)), ("b_late", Gated())],
                1)
    assert pub._cache[1].ready[0].wait(10)
    ours, other = WeightStreamConsumer(pub.endpoint, timeout_secs=1), \
        WeightStreamConsumer(pub.endpoint, timeout_secs=10)

    def ask(consumer, tensor):
        consumer._request(b"chunk", {"version": 1, "tensor": tensor,
                                     "chunk": 0})

    def answered(consumer):
        return json.loads(consumer._recv()[0])["tensor"]

    try:
        ask(ours, 1)   # not gathered yet: deferred
        ask(ours, 0)   # gathered, but behind the deferred one
        ask(other, 0)
        assert answered(other) == 0  # no head-of-line block across consumers
        with pytest.raises(WeightStreamError, match="no reply"):
            ours._recv()
        gate.set()
        ours.timeout_secs = 10
        assert [answered(ours), answered(ours)] == [1, 0]
    finally:
        gate.set()
        ours.close()
        other.close()
        pub.close()


def test_digest_catches_divergent_crcs(tmp_name_resolve):
    """Even if per-chunk checks were fooled, the final digest compare
    against the publisher's complete CRC list gates the swap."""
    pub = _publish(_tree(), chunk_bytes=1024)
    assert pub.wait_complete(1, timeout=10)
    consumer = WeightStreamConsumer(pub.endpoint, timeout_secs=5)
    try:
        manifest = consumer.fetch_manifest(1)
        list(consumer.iter_tensors(1, manifest))
        consumer._local_crcs[0][0] ^= 1  # simulate a silently-wrong chunk
        with pytest.raises(WeightStreamError, match="digest mismatch"):
            consumer.verify_digest(1)
    finally:
        consumer.close()
        pub.close()


def test_consumer_death_midstream_leaves_publisher_serving(tmp_name_resolve):
    """A server dying mid-stream must not wedge the publisher: a fresh
    consumer completes a full verified fetch afterwards."""
    pub = _publish(_tree(), chunk_bytes=256)
    dead = WeightStreamConsumer(pub.endpoint, timeout_secs=5)
    manifest = dead.fetch_manifest(1)
    it = dead.iter_tensors(1, manifest)
    next(it)  # pull one tensor, leave requests in flight...
    dead.close()  # ...and die
    survivor = WeightStreamConsumer(pub.endpoint, timeout_secs=10)
    try:
        _, flat = survivor.fetch(1)
        assert set(flat) == set(flatten_pytree(_tree()))
    finally:
        survivor.close()
        pub.close()


# ------------------------------------------- server swap atomicity (e2e)


def _tiny_server(**kw):
    from areal_tpu.models import transformer
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system.generation_server import (
        GenerationServer,
        GenerationServerConfig,
    )

    mcfg = tiny_config(vocab_size=258, n_layers=2, hidden_dim=32)
    params = transformer.init_params(mcfg, jax.random.PRNGKey(0))
    cfg = GenerationServerConfig(
        experiment=EXP, trial=TRIAL, chunk_tokens=4, prompt_bucket=16,
        batch_window_ms=2, **kw,
    )
    return GenerationServer(cfg, mcfg, params), mcfg


@pytest.mark.timeout(120)
def test_atomic_swap_under_concurrent_generate(tmp_name_resolve):
    """POST /update_weights with a stream payload while /generate traffic
    is in flight: every response is tagged with a version the server
    actually held (old or new, never torn), and /metrics flips to the new
    version exactly when the verified swap lands."""
    import aiohttp

    async def main():
        server, mcfg = _tiny_server()
        url = await server.start()
        new_params = jax.tree.map(
            lambda x: x + 0.01 if x.dtype == np.float32 else x, server.params
        )
        pub = WeightStreamPublisher(EXP, TRIAL, "actor")
        pub.publish(sorted(flatten_pytree(new_params).items()), 1)
        try:
            async with aiohttp.ClientSession() as sess:
                versions = []

                async def update():
                    await asyncio.sleep(0.05)
                    async with sess.post(f"{url}/update_weights", json={
                        "endpoint": pub.endpoint, "version": 1,
                    }) as r:
                        assert r.status == 200
                        assert (await r.json())["version"] == 1

                async with sess.get(f"{url}/metrics.json") as r:
                    assert (await r.json())["version"] == 0
                upd = asyncio.create_task(update())
                # keep /generate traffic flowing until the swap landed AND
                # at least one post-swap response was observed
                for _ in range(400):
                    async with sess.post(f"{url}/generate", json={
                        "prompt_ids": [3, 4, 5], "max_tokens": 4,
                    }) as r:
                        assert r.status == 200
                        versions.append((await r.json())["version"])
                    if upd.done() and versions[-1] == 1:
                        break
                await upd
                assert set(versions) <= {0, 1}  # never a torn in-between
                assert versions[-1] == 1  # post-swap traffic sees v1
                async with sess.get(f"{url}/metrics.json") as r:
                    assert (await r.json())["version"] == 1
            # swapped weights match the published tree bit-exactly
            for k, v in flatten_pytree(new_params, as_numpy=True).items():
                np.testing.assert_array_equal(
                    np.asarray(flatten_pytree(server.params)[k]), v
                )
        finally:
            pub.close()
            await server.stop()

    asyncio.run(main())


@pytest.mark.timeout(120)
def test_failed_stream_keeps_old_weights_and_500s(tmp_name_resolve):
    """A dead endpoint (server died mid-stream analogue) must yield a
    non-200 ack with the OLD version still live — the manager's existing
    retry/evict machinery takes it from there."""
    import aiohttp

    async def main():
        server, _ = _tiny_server()
        url = await server.start()
        before = flatten_pytree(server.params, as_numpy=True)
        try:
            async with aiohttp.ClientSession() as sess:
                async with sess.post(f"{url}/update_weights", json={
                    "endpoint": "tcp://127.0.0.1:1",
                    "version": 1, "timeout": 1,
                }) as r:
                    assert r.status == 500
                    body = await r.json()
                    assert body["ok"] is False and body["version"] == 0
                async with sess.get(f"{url}/metrics.json") as r:
                    assert (await r.json())["version"] == 0
            after = flatten_pytree(server.params, as_numpy=True)
            for k in before:
                np.testing.assert_array_equal(before[k], after[k])
        finally:
            await server.stop()

    asyncio.run(main())


@pytest.mark.chaos
@pytest.mark.timeout(120)
def test_fanout_stream_payload_and_eviction(tmp_name_resolve):
    """Manager fanout in stream mode: with the publisher endpoint
    registered, acked servers get the endpoint payload; a server that
    fails its stream is evicted while the version still bumps over the
    acker (the PR 2 guarantee, unchanged by the new transport)."""
    from aiohttp import web

    from areal_tpu.system.gserver_manager import (
        GserverManager,
        GserverManagerConfig,
        _ServerHealth,
    )
    from areal_tpu.base.retry import RetryPolicy

    async def _start_app(app):
        runner = web.AppRunner(app)
        await runner.setup()
        port = network.find_free_port()
        site = web.TCPSite(runner, "127.0.0.1", port)
        await site.start()
        return runner, f"http://127.0.0.1:{port}"

    async def main():
        import aiohttp

        pub = _publish(_tree(), version=7)
        payloads = []

        async def ok_update(req):
            payloads.append(await req.json())
            return web.json_response({"ok": True})

        async def bad_update(req):
            # stream consumption failed server-side (mid-stream death)
            return web.json_response({"ok": False}, status=500)

        ok_app = web.Application()
        ok_app.router.add_post("/update_weights", ok_update)
        ok_runner, ok_url = await _start_app(ok_app)
        bad_app = web.Application()
        bad_app.router.add_post("/update_weights", bad_update)
        bad_runner, bad_url = await _start_app(bad_app)
        try:
            mgr = GserverManager(GserverManagerConfig(
                experiment=EXP, trial=TRIAL,
                fanout_timeout_secs=2.0,
                fanout_retry=RetryPolicy(max_attempts=2,
                                         base_delay_secs=0.01),
            ))
            mgr.servers = sorted([ok_url, bad_url])
            mgr._inflight = {u: 0 for u in mgr.servers}
            mgr.health = {u: _ServerHealth() for u in mgr.servers}
            async with aiohttp.ClientSession() as sess:
                acked = await mgr.fanout_weights(sess, 7, "/unused/disk/path")
            assert acked == [ok_url]
            assert mgr.version == 7
            # stream payload (endpoint), not the disk path
            assert payloads and payloads[0]["endpoint"] == pub.endpoint
            assert "path" not in payloads[0]
            assert bad_url not in mgr.servers  # evicted, not silently stale
            assert not mgr.health[bad_url].routable
        finally:
            pub.close()
            await ok_runner.cleanup()
            await bad_runner.cleanup()

    asyncio.run(main())


# ------------------------------------------------------ transport parity


@pytest.mark.timeout(120)
def test_disk_and_stream_transports_deliver_identical_pytrees(
    tmp_name_resolve, tmp_path
):
    """The same publish through both transports ends in byte-identical
    server params (the fallback is a true fallback)."""
    import aiohttp

    from areal_tpu.models import hf as hfmod

    async def main():
        server_a, mcfg = _tiny_server(server_id="gen0")
        server_b, _ = _tiny_server(server_id="gen1")
        url_a = await server_a.start()
        url_b = await server_b.start()
        new_params = jax.tree.map(
            lambda x: x * 1.25 if x.dtype == np.float32 else x,
            server_a.params,
        )
        # disk publish (trainer _save_role fmt="native" analogue)
        disk_dir = str(tmp_path / "v1")
        hfmod.save_native_checkpoint(
            jax.tree.map(np.asarray, new_params), mcfg, disk_dir
        )
        pub = WeightStreamPublisher(EXP, TRIAL, "actor")
        pub.publish(sorted(flatten_pytree(new_params).items()), 1)
        try:
            async with aiohttp.ClientSession() as sess:
                async with sess.post(f"{url_a}/update_weights", json={
                    "endpoint": pub.endpoint, "version": 1,
                }) as r:
                    assert r.status == 200
                async with sess.post(f"{url_b}/update_weights", json={
                    "path": disk_dir, "version": 1,
                }) as r:
                    assert r.status == 200
            fa = flatten_pytree(server_a.params, as_numpy=True)
            fb = flatten_pytree(server_b.params, as_numpy=True)
            assert set(fa) == set(fb)
            for k in fa:
                assert fa[k].dtype == fb[k].dtype
                np.testing.assert_array_equal(fa[k], fb[k])
            assert server_a.version == server_b.version == 1
        finally:
            pub.close()
            await server_a.stop()
            await server_b.stop()

    asyncio.run(main())


# ------------------------------------------------- trainer-side publish


@pytest.mark.timeout(120)
def test_trainer_stream_publish_end_to_end(tmp_name_resolve):
    """TrainerWorker with weight_sync.transport=stream publishes an
    endpoint + version (no realloc dir write), and a consumer pulls the
    actor weights in the engine's compute dtype."""
    import os

    import areal_tpu.backend.jax_train  # noqa: F401 — registers "jax_train"
    from areal_tpu.api.model import FinetuneSpec
    from areal_tpu.api.train_config import WeightSyncConfig
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
        weight_sync=WeightSyncConfig(transport="stream"),
    )
    w = TrainerWorker(cfg)
    for role, rc in cfg.models.items():
        model = w._model_factory(role, rc)
        from areal_tpu.api.model import make_backend

        backend = make_backend(rc.backend, train=rc.train, **rc.backend_args)
        w.models[role] = backend.initialize(model, cfg.ft_spec)
    w.publish_weights("actor")
    try:
        assert not os.path.exists("/nonexistent/never/written")
        v = int(name_resolve.get(names.model_version(EXP, TRIAL, "actor")))
        endpoint = name_resolve.get(names.weight_stream(EXP, TRIAL, "actor"))
        consumer = WeightStreamConsumer(endpoint, timeout_secs=30)
        try:
            _, flat = consumer.fetch(v)
        finally:
            consumer.close()
        want = flatten_pytree(w.models["actor"].module.params, as_numpy=True)
        assert set(flat) == set(want)
        for k in want:
            np.testing.assert_array_equal(np.asarray(flat[k]), want[k])
    finally:
        for pub in w._weight_publishers.values():
            pub.close()
