"""Streamed weight sync: trainer → generation fleet over ZMQ, no disk.

The disk publish path (``trainer_worker.publish_weights`` →
``generation_server._load_and_put_weights``) round-trips every weight
through the filesystem: serialize + write on the trainer, read + parse on
every server. §3.5 of the source paper makes low-latency weight sync the
lynchpin of staleness control, and AReaL's NCCL update path / SGLang's
``update_weights_from_distributed`` both stream tensors directly instead.
This module is the TPU-native analogue over the repo's existing ZMQ fabric
(``streams.py`` socket idioms, ``names.py`` discovery).

Roles:

 - :class:`WeightStreamPublisher` (trainer, rank 0): holds a host-side
   cache of the published tensors and serves them to any number of
   consumers over a ROUTER socket — per-server replay from one d2h gather,
   the multi-subscriber fanout. ``publish()`` returns immediately; a
   background *gather* thread pulls tensors off the device one at a time
   (d2h of tensor *i+1* overlaps the wire transfer of tensor *i*, which
   the consumer overlaps with its ``device_put`` of tensor *i−1* — the
   three-leg pipeline).
 - :class:`WeightStreamConsumer` (generation server): fetches the manifest,
   streams chunks with a bounded window of in-flight requests, reassembles
   tensors, and verifies the whole transfer against the publisher's digest
   before the caller swaps anything live.

Wire protocol (REQ-less DEALER↔ROUTER, multipart frames):

 - ``[b"manifest", {"version": v}]`` → ``[b"ok", manifest-json]``
   Manifest: tensor names, shapes, dtypes, per-tensor byte counts and
   chunk counts, the wire chunk size, and the weight version.
 - ``[b"chunk", {"version", "tensor", "chunk"}]`` →
   ``[b"ok", {"tensor", "chunk", "crc32"}, payload]``
   Blocks (bounded) until the gather thread has produced that tensor.
 - ``[b"digest", {"version": v}]`` → ``[b"ok", {"crcs": [[...], ...]}]``
   Per-chunk CRC32s of the COMPLETE publish — available only once the
   gather finished, so a consumer that verifies its locally computed CRCs
   against the digest has proof the stream was neither torn nor reordered
   nor corrupted before it swaps.

Every reply echoes the (version, tensor, chunk) coordinates; a consumer
receiving an echo that does not match its request order aborts. Trust
model: intra-cluster, same as the pickled control plane in ``streams.py``
— checksums defend against torn/reordered/corrupted transfers, not
adversaries.
"""

from __future__ import annotations

import json
import threading
import time
import zlib
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np
import zmq

from areal_tpu.base import logging, name_resolve, names, network, telemetry

logger = logging.getLogger("system.weight_stream")

DEFAULT_CHUNK_BYTES = 32 << 20  # 32 MB wire chunks
DEFAULT_PIPELINE_DEPTH = 4  # in-flight chunk requests per consumer


class WeightStreamError(RuntimeError):
    """Torn / reordered / corrupted / timed-out weight stream."""


class _NotReady(Exception):
    """Internal: the request needs data the gather thread has not produced
    yet — the serve loop defers it instead of blocking (other consumers'
    requests keep flowing)."""


def _np_dtype(name: str) -> np.dtype:
    """np.dtype by name, including the ml_dtypes extended types (bfloat16)
    that plain numpy does not resolve from strings."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _as_wire_array(leaf: Any) -> np.ndarray:
    """Host, contiguous view of a (possibly device-resident) tensor. For
    jax arrays this is the d2h transfer — called from the gather thread so
    it overlaps the wire leg of previously gathered tensors."""
    return np.ascontiguousarray(np.asarray(leaf))


class _PublishedVersion:
    """Host cache of one published weight version."""

    def __init__(self, version: int, tensors: Sequence[Tuple[str, Any]],
                 chunk_bytes: int):
        self.version = version
        self.chunk_bytes = chunk_bytes
        self.names = [n for n, _ in tensors]
        self.leaves: List[Any] = [v for _, v in tensors]  # device refs
        self.arrays: List[Optional[np.ndarray]] = [None] * len(tensors)
        self.crcs: List[List[int]] = [[] for _ in tensors]
        # Shapes/dtypes are known without any d2h: manifests are servable
        # the moment publish() is called.
        self.shapes = [tuple(int(d) for d in np.shape(v)) for _, v in tensors]
        self.dtypes = [str(getattr(v, "dtype", None) or np.asarray(v).dtype)
                       for _, v in tensors]
        self.nbytes = [
            int(np.prod(s, dtype=np.int64)) * _np_dtype(d).itemsize
            for s, d in zip(self.shapes, self.dtypes)
        ]
        self.n_chunks = [
            max(1, -(-nb // chunk_bytes)) for nb in self.nbytes
        ]
        self.ready = [threading.Event() for _ in tensors]
        self.complete = threading.Event()
        self.gather_secs = 0.0

    def manifest(self) -> Dict[str, Any]:
        return {
            "version": self.version,
            "chunk_bytes": self.chunk_bytes,
            "total_bytes": int(sum(self.nbytes)),
            "tensors": [
                {"name": n, "shape": list(s), "dtype": d, "nbytes": nb,
                 "n_chunks": nc}
                for n, s, d, nb, nc in zip(
                    self.names, self.shapes, self.dtypes, self.nbytes,
                    self.n_chunks,
                )
            ],
        }

    def chunk_view(self, t: int, c: int) -> memoryview:
        a = self.arrays[t]
        raw = a.reshape(-1).view(np.uint8) if a.nbytes else \
            np.empty(0, np.uint8)
        return memoryview(raw)[c * self.chunk_bytes:(c + 1) * self.chunk_bytes]


class WeightStreamPublisher:
    """Rank-0 host cache + replay server for streamed weight publishes.

    One instance lives for the whole training run; each ``publish()``
    registers a new version. The last ``keep_versions`` publishes stay
    replayable so a server re-admitted by the manager's health loop can
    reconcile to the fleet version without a disk checkpoint existing.
    """

    def __init__(self, experiment: str, trial: str, role: str = "actor",
                 chunk_bytes: int = DEFAULT_CHUNK_BYTES,
                 keep_versions: int = 2,
                 chunk_wait_secs: float = 300.0):
        self.chunk_bytes = int(chunk_bytes)
        self.keep_versions = keep_versions
        self.chunk_wait_secs = chunk_wait_secs
        self._cache: Dict[int, _PublishedVersion] = {}
        self._lock = threading.Lock()
        self._closing = False
        self._ctx = zmq.Context.instance()
        self._sock = self._ctx.socket(zmq.ROUTER)
        port = self._sock.bind_to_random_port(f"tcp://{network.bind_addr()}")
        self.endpoint = network.advertised_tcp(port)
        self._key = names.weight_stream(experiment, trial, role)
        name_resolve.add(self._key, self.endpoint, replace=True)
        self._serve_thread = threading.Thread(
            target=self._serve_loop, daemon=True, name="weight-stream-serve"
        )
        self._serve_thread.start()
        logger.info(f"weight stream publisher for {role} at {self.endpoint}")

    # ---------------- publishing ----------------

    def publish(self, tensors: Sequence[Tuple[str, Any]], version: int,
                ) -> Dict[str, Any]:
        """Register ``version`` and start gathering its tensors to host in
        the background. ``tensors`` is an ordered [(name, array)] list —
        jax arrays are gathered lazily (pipelined d2h); numpy arrays are
        served as-is. Returns the manifest immediately."""
        pub = _PublishedVersion(version, tensors, self.chunk_bytes)
        with self._lock:
            self._cache[version] = pub
            for v in sorted(self._cache):
                if len(self._cache) <= self.keep_versions:
                    break
                if v != version:
                    del self._cache[v]
        t = threading.Thread(
            target=self._gather_loop, args=(pub,), daemon=True,
            name=f"weight-stream-gather-v{version}",
        )
        t.start()
        return pub.manifest()

    def _gather_loop(self, pub: _PublishedVersion) -> None:
        from areal_tpu.system import memwatch

        t0 = time.monotonic()
        try:
            # The d2h gather holds the compute-dtype publish copy on
            # device until each leaf's ref drops below — the trainer-side
            # HBM high-water mark of a streamed publish.
            with memwatch.watermark("weight_stream/gather"):
                self._gather_leaves(pub)
            pub.gather_secs = time.monotonic() - t0
            pub.complete.set()
            # d2h leg throughput for the unified telemetry stream (the
            # trainer process owns this publisher).
            total = float(sum(pub.nbytes))
            telemetry.set_gauge("weight_stream/gather_secs",
                                pub.gather_secs)
            telemetry.set_gauge(
                "weight_stream/gather_mb_per_sec",
                (total / max(pub.gather_secs, 1e-9)) / (1 << 20),
            )
            telemetry.inc("weight_stream/published_bytes", total)
        except Exception as e:  # noqa: BLE001 — surfaced via chunk errors
            logger.error(f"weight gather v{pub.version} failed: {e}")
            with self._lock:
                self._cache.pop(pub.version, None)
            # Wake blocked chunk waits so they error out instead of hanging.
            for ev in pub.ready:
                ev.set()
            pub.complete.set()

    def _gather_leaves(self, pub: _PublishedVersion) -> None:
        for i, leaf in enumerate(pub.leaves):
            a = _as_wire_array(leaf)
            if a.nbytes != pub.nbytes[i]:
                raise WeightStreamError(
                    f"tensor {pub.names[i]} gathered {a.nbytes} bytes, "
                    f"manifest promised {pub.nbytes[i]}"
                )
            pub.arrays[i] = a
            pub.leaves[i] = None  # drop the device ref
            raw = a.reshape(-1).view(np.uint8) if a.nbytes else \
                np.empty(0, np.uint8)
            cb = pub.chunk_bytes
            pub.crcs[i] = [
                zlib.crc32(memoryview(raw)[c * cb:(c + 1) * cb])
                for c in range(pub.n_chunks[i])
            ]
            pub.ready[i].set()

    def wait_complete(self, version: int, timeout: float = 300.0) -> bool:
        with self._lock:
            pub = self._cache.get(version)
        return pub is not None and pub.complete.wait(timeout)

    # ---------------- serving ----------------

    def _lookup(self, version: int) -> _PublishedVersion:
        with self._lock:
            pub = self._cache.get(version)
        if pub is None:
            raise WeightStreamError(
                f"version {version} not cached "
                f"(have {sorted(self._cache)})"
            )
        return pub

    def _handle(self, frames: List[bytes]) -> List[bytes]:
        """One request → reply frames. Raises :class:`_NotReady` when the
        gather thread has not produced the needed data yet — the serve
        loop defers the request rather than blocking, so one consumer
        racing ahead of the gather never head-of-line-blocks another
        consumer's (already-servable) manifest or chunk requests."""
        cmd = frames[0]
        meta = json.loads(frames[1]) if len(frames) > 1 else {}
        version = int(meta.get("version", -1))
        pub = self._lookup(version)
        if cmd == b"manifest":
            return [b"ok", json.dumps(pub.manifest()).encode()]
        if cmd == b"digest":
            if not pub.complete.is_set():
                raise _NotReady
            self._lookup(version)  # gather failure evicts the cache entry
            return [b"ok", json.dumps(
                {"version": version, "crcs": pub.crcs}
            ).encode()]
        if cmd == b"chunk":
            t, c = int(meta["tensor"]), int(meta["chunk"])
            if not (0 <= t < len(pub.names)) or not (0 <= c < pub.n_chunks[t]):
                raise WeightStreamError(f"chunk ({t},{c}) out of range")
            if not pub.ready[t].is_set():
                raise _NotReady
            if pub.arrays[t] is None:  # gather failed
                raise WeightStreamError("publisher gather failed")
            telemetry.inc("weight_stream/chunks_served")
            return [
                b"ok",
                json.dumps({"version": version, "tensor": t, "chunk": c,
                            "crc32": pub.crcs[t][c]}).encode(),
                pub.chunk_view(t, c),
            ]
        raise WeightStreamError(f"unknown command {cmd!r}")

    def _reply(self, ident: bytes, reply: List[bytes]) -> None:
        try:
            self._sock.send_multipart([ident, *reply], copy=False)
        except zmq.ZMQError:
            # Consumer died mid-stream: ROUTER drops the reply; the
            # manager's eviction/retry machinery owns that server now.
            pass

    def _try_serve(self, ident: bytes, frames: List[bytes]) -> bool:
        """Handle one request; returns False iff it must be deferred."""
        try:
            reply = self._handle(frames)
        except _NotReady:
            return False
        except WeightStreamError as e:
            reply = [b"err", str(e).encode()]
        except Exception as e:  # noqa: BLE001 — keep serving
            logger.error(f"weight stream request failed: {e}")
            reply = [b"err", str(e).encode()]
        self._reply(ident, reply)
        return True

    def _serve_loop(self) -> None:
        # Requests not answered yet, in arrival order: [(ident, frames,
        # deadline)]. A consumer reads its replies in the order it asked
        # (iter_tensors), so once one of its requests waits here for the
        # gather thread, its later ones wait behind it even when their own
        # data is ready; other consumers' requests keep flowing.
        pending: List[tuple] = []
        while not self._closing:
            if self._sock.poll(20 if pending else 100):
                while True:
                    try:
                        ident, *frames = self._sock.recv_multipart(
                            zmq.NOBLOCK
                        )
                    except zmq.Again:
                        break
                    pending.append((
                        ident, frames,
                        time.monotonic() + self.chunk_wait_secs,
                    ))
            still = []
            waiting = set()  # consumers with an earlier request deferred
            for ident, frames, deadline in pending:
                if ident not in waiting and self._try_serve(ident, frames):
                    continue
                if time.monotonic() > deadline:
                    self._reply(ident, [
                        b"err",
                        b"timed out waiting for the gather thread",
                    ])
                    continue
                waiting.add(ident)
                still.append((ident, frames, deadline))
            pending = still

    def close(self) -> None:
        self._closing = True
        try:
            name_resolve.delete(self._key)
        except Exception:  # noqa: BLE001 — already gone / repo reset
            pass
        self._serve_thread.join(timeout=2)
        self._sock.close(linger=0)


class WeightStreamConsumer:
    """One server's view of a publisher: fetch manifest, stream tensors
    with a bounded request window, verify the digest."""

    def __init__(self, endpoint: str,
                 pipeline_depth: int = DEFAULT_PIPELINE_DEPTH,
                 timeout_secs: float = 600.0):
        # timeout_secs must cover the publisher-side d2h gather of the
        # LARGEST tensor (a chunk request blocks server-side until its
        # tensor is gathered), not just wire latency; it is a liveness
        # backstop, not a performance bound.
        self.endpoint = endpoint
        self.pipeline_depth = max(1, int(pipeline_depth))
        self.timeout_secs = timeout_secs
        self._ctx = zmq.Context.instance()
        self._sock = self._ctx.socket(zmq.DEALER)
        self._sock.connect(endpoint)
        # Stats for the bench / metrics: where the wall-clock went.
        self.bytes_received = 0
        self.checksum_secs = 0.0  # host-side CPU work (the "io" analogue)
        self.wire_wait_secs = 0.0

    def _request(self, cmd: bytes, meta: Dict[str, Any]) -> None:
        self._sock.send_multipart([cmd, json.dumps(meta).encode()])

    def _recv(self) -> List[bytes]:
        t0 = time.monotonic()
        if not self._sock.poll(int(self.timeout_secs * 1000)):
            raise WeightStreamError(
                f"no reply from {self.endpoint} within {self.timeout_secs}s"
            )
        frames = self._sock.recv_multipart()
        self.wire_wait_secs += time.monotonic() - t0
        if frames[0] == b"err":
            raise WeightStreamError(
                f"publisher error: {frames[1].decode(errors='replace')}"
            )
        if frames[0] != b"ok":
            raise WeightStreamError(f"bad reply frame {frames[0]!r}")
        return frames[1:]

    def fetch_manifest(self, version: int) -> Dict[str, Any]:
        self._request(b"manifest", {"version": version})
        manifest = json.loads(self._recv()[0])
        if int(manifest["version"]) != version:
            raise WeightStreamError(
                f"manifest version {manifest['version']} != requested "
                f"{version}"
            )
        return manifest

    def iter_tensors(
        self, version: int, manifest: Dict[str, Any]
    ) -> Iterator[Tuple[str, np.ndarray]]:
        """Yield (name, array) in manifest order, keeping up to
        ``pipeline_depth`` chunk requests in flight so the wire leg overlaps
        whatever the caller does with each yielded tensor (device_put).
        Records per-chunk CRC32s for :meth:`verify_digest`."""
        coords = [
            (t, c)
            for t, spec in enumerate(manifest["tensors"])
            for c in range(spec["n_chunks"])
        ]
        self._local_crcs: List[List[int]] = [
            [0] * spec["n_chunks"] for spec in manifest["tensors"]
        ]
        pending = 0
        sent = 0
        parts: List[bytes] = []
        cur_tensor = 0
        for t, c in coords[: self.pipeline_depth]:
            self._request(b"chunk", {"version": version, "tensor": t,
                                     "chunk": c})
            sent += 1
            pending += 1
        for t, c in coords:
            meta_raw, payload = self._recv()
            pending -= 1
            if sent < len(coords):
                nt, nc = coords[sent]
                self._request(b"chunk", {"version": version, "tensor": nt,
                                         "chunk": nc})
                sent += 1
                pending += 1
            meta = json.loads(meta_raw)
            if (int(meta["version"]), int(meta["tensor"]),
                    int(meta["chunk"])) != (version, t, c):
                raise WeightStreamError(
                    f"out-of-order chunk: expected v{version} ({t},{c}), "
                    f"got v{meta['version']} "
                    f"({meta['tensor']},{meta['chunk']})"
                )
            t0 = time.monotonic()
            crc = zlib.crc32(payload)
            if crc != int(meta["crc32"]):
                raise WeightStreamError(
                    f"chunk ({t},{c}) checksum mismatch: wire {crc} != "
                    f"published {meta['crc32']}"
                )
            self._local_crcs[t][c] = crc
            self.bytes_received += len(payload)
            parts.append(payload)
            self.checksum_secs += time.monotonic() - t0
            spec = manifest["tensors"][t]
            if c == spec["n_chunks"] - 1:
                t0 = time.monotonic()
                buf = parts[0] if len(parts) == 1 else b"".join(parts)
                if len(buf) != spec["nbytes"]:
                    raise WeightStreamError(
                        f"tensor {spec['name']}: received {len(buf)} bytes, "
                        f"manifest promised {spec['nbytes']}"
                    )
                arr = np.frombuffer(buf, dtype=_np_dtype(spec["dtype"]))
                arr = arr.reshape(spec["shape"])
                parts = []
                cur_tensor += 1
                self.checksum_secs += time.monotonic() - t0
                yield spec["name"], arr
        assert pending == 0 and cur_tensor == len(manifest["tensors"])

    def verify_digest(self, version: int) -> None:
        """Compare locally computed per-chunk CRCs against the publisher's
        complete digest. Raises if ANY chunk differs — the caller must not
        swap weights before this passes."""
        self._request(b"digest", {"version": version})
        digest = json.loads(self._recv()[0])
        t0 = time.monotonic()
        if digest["crcs"] != self._local_crcs:
            raise WeightStreamError(
                f"digest mismatch for v{version}: stream was torn or "
                "reordered; aborting swap"
            )
        self.checksum_secs += time.monotonic() - t0

    def fetch(self, version: int) -> Tuple[Dict[str, Any],
                                           Dict[str, np.ndarray]]:
        """Convenience: full verified transfer → (manifest, {name: array})."""
        manifest = self.fetch_manifest(version)
        out = dict(self.iter_tensors(version, manifest))
        self.verify_digest(version)
        return manifest, out

    def close(self) -> None:
        self._sock.close(linger=0)
