"""TPU generation server — the SGLang/JetStream role, in-house.

Parity target: ``realhf/system/generation_server.py`` + the sglang patch
(``patch/sglang/v0.4.6.post4.patch``: interruptible generation, weight
update from disk). TPU-first design differences:

 - **Chunked decoding replaces interruption.** The reference patches SGLang
   to abort in-flight requests when weights update. Here every ``/generate``
   call decodes AT MOST ``chunk_tokens`` new tokens as one static-shape
   ``lax.scan`` and returns a partial result tagged with the weight version
   that produced it; the client (PartialRolloutManager) re-submits with the
   accumulated prefix. Weight updates therefore wait at most one chunk —
   the same bound the reference achieves by aborting, with zero lost work
   and no recompilation (chunk length is static).
 - **Micro-batched continuous batching**: concurrent requests are drained
   from a queue every ``batch_window_ms`` and decoded together, padded to
   bucketed prompt lengths (prefix re-prefill per chunk; a paged KV cache
   across chunks is a later optimization).
 - **Scheduling is delegated to the serving engine**
   (system/serving.py, docs/serving.md): request-class admission control
   with bounded queues and 429 backpressure, priority batch formation,
   cross-request prefix-reuse KV behind a token trie, bounded
   compile-shape bucketing, and per-class latency SLO histograms. With
   ``serving.enabled=false`` (default) the engine reproduces the legacy
   rollout-only behavior exactly.
 - ``/update_weights`` hot-swaps params in place (device_put over the old
   sharding) from the trainer's publish — either streamed per-tensor over
   ZMQ (§3.5 low-latency path, system/weight_stream.py) or read from the
   published checkpoint (disk fallback).

Endpoints: POST /generate, POST /update_weights, GET /health,
GET /metrics (Prometheus text), GET /metrics.json (structured).
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Any, Dict, List, Optional

import numpy as np

from areal_tpu.api.model import GenerationHyperparameters
from areal_tpu.api.train_config import (
    CompileWatchConfig,
    GoodputConfig,
    ServingConfig,
    TelemetryConfig,
)
from areal_tpu.base import compile_watch as compile_watch_mod
from areal_tpu.base import logging, name_resolve, names, network, telemetry
from areal_tpu.models import generate as genmod
from areal_tpu.models import transformer  # noqa: F401 (engine deps)
from areal_tpu.ops.attention import dispatch_label
from areal_tpu.system import goodput as goodput_mod
from areal_tpu.system import memwatch as memwatch_mod
from areal_tpu.system import serving as serving_mod

logger = logging.getLogger("system.genserver")


@dataclasses.dataclass
class GenerationServerConfig:
    experiment: str = "exp"
    trial: str = "trial"
    server_id: str = "gen0"
    # Shape-policy inputs default to the serving module's GEN_*_DEFAULT
    # constants: cli_args.validate_config front-runs the ShapeBucketPolicy
    # construction at config-parse time (jax-free) with the same numbers.
    chunk_tokens: int = (  # static decode length per /generate call
        serving_mod.GEN_CHUNK_TOKENS_DEFAULT
    )
    batch_window_ms: int = 5
    max_batch_size: int = serving_mod.GEN_MAX_BATCH_SIZE_DEFAULT
    prompt_bucket: int = serving_mod.GEN_PROMPT_BUCKET_DEFAULT
    eos_token_id: int = 1
    pad_token_id: int = 0
    port: Optional[int] = None
    # Persistent-KV continuous batching: keep per-request decode state so a
    # chunk continuation decodes from its cache instead of re-prefilling the
    # whole prefix (the reference's SGLang radix-cache role). 0 disables.
    kv_slots: int = 256
    # KV capacity granularity (slots)
    kv_bucket: int = serving_mod.GEN_KV_BUCKET_DEFAULT
    # Hard budget on retained KV BYTES (not just state count): per-request
    # KV grows with sequence length, so count alone can exhaust HBM long
    # before kv_slots states (advisor r2, medium). LRU-evicted states simply
    # re-prefill on their next chunk.
    kv_bytes_budget: int = 4 << 30
    # In-flight chunk requests when consuming a streamed weight update
    # (weight_sync.pipeline_depth threaded through the experiment config).
    weight_stream_pipeline_depth: int = 4
    # Serving engine (system/serving.py): request-class admission control,
    # cross-request prefix-reuse KV, bounded compile shapes, per-class
    # SLOs. Disabled = exact legacy behavior.
    serving: ServingConfig = dataclasses.field(default_factory=ServingConfig)
    # Unified telemetry (base/telemetry.py). The gen-fleet process hosts
    # servers AND the manager, so each owns its own instance (distinct
    # worker kinds at the aggregator) instead of the process global.
    telemetry: TelemetryConfig = dataclasses.field(
        default_factory=TelemetryConfig
    )
    # Goodput ledger (system/goodput.py): prefill/decode compute vs
    # queue-empty idle vs weight-update comm counters + analytic decode
    # FLOP/s and MFU gauges per batch. Off by default — null ledger.
    goodput: GoodputConfig = dataclasses.field(default_factory=GoodputConfig)
    # Liveness lease on the server's gen_servers/ registration
    # (docs/fault_tolerance.md): a SIGKILLed server's ghost URL expires
    # from discovery instead of being probed forever. 0 falls back to
    # the supervisor-set AREAL_WORKER_KEEPALIVE_TTL env.
    keepalive_ttl_secs: float = 0.0
    # Compile & HBM observatory (base/compile_watch.py +
    # system/memwatch.py): per-INSTANCE watches bound to this server's
    # telemetry (same reason telemetry itself is per-instance here — the
    # gen-fleet process hosts many servers plus the manager). Off by
    # default: raw genmod entry points, no device polls.
    compile_watch: CompileWatchConfig = dataclasses.field(
        default_factory=CompileWatchConfig
    )


class _Pending:
    __slots__ = ("rid", "prompt", "gconfig", "future", "max_tokens",
                 "tokens_done", "cls", "t_enqueue", "t_enqueue_wall",
                 "trace")

    def __init__(self, prompt, gconfig, max_tokens, future, rid=None,
                 tokens_done=0, cls="rollout", trace=None):
        self.rid = rid
        self.prompt = prompt
        self.gconfig = gconfig
        self.max_tokens = max_tokens
        self.tokens_done = tokens_done
        self.future = future
        self.cls = cls  # request class (serving.REQUEST_CLASSES)
        self.t_enqueue = time.monotonic()
        self.t_enqueue_wall = time.time()
        # Adopted cross-worker trace context (telemetry.TraceContext) —
        # the server's queue-wait/prefill/decode spans link back to the
        # client's generate span through it. None for untraced requests.
        self.trace = trace


# Retained decode states moved into the serving engine (KVStateStore);
# kept importable under the old name for callers/tests.
_ReqState = serving_mod.ReqState


class GenerationServer:
    """Owns (cfg, params) of the serving model; hot-swappable."""

    def __init__(self, cfg: GenerationServerConfig, model_cfg, params,
                 mesh=None, fault_injector=None):
        self.cfg = cfg
        self.model_cfg = model_cfg
        # Chaos seam (base/retry.py): an armed "decode" delay point
        # simulates a straggling server — the injected latency lands
        # inside the measured decode window, so the /health-reported
        # EWMAs (and the manager's straggler defense) see it exactly
        # like real slowness.
        self.faults = fault_injector
        import jax

        if mesh is not None:
            from areal_tpu.parallel import sharding as psh

            params = psh.shard_params(params, mesh, model_cfg)
        else:
            params = jax.tree.map(jax.numpy.asarray, params)
        self.params = params
        self.mesh = mesh
        self.version = 0
        # Atomic (params, version) publication for the decode thread: a
        # single attribute holding the pair — two separate attribute
        # loads could interleave with the update handler's swap and tag
        # old-weight tokens (and retained KV) with the new version.
        self._published = (params, 0)
        self._key = jax.random.PRNGKey(0)
        self._tokens_out = 0
        self._prefill_tokens = 0
        self._t_start = time.monotonic()
        self._runner_task = None
        self._last_update_latency = 0.0
        self._inflight = 0  # /generate requests accepted but not replied
        # Recent-latency EWMAs reported in /health for the manager's
        # autoscale signals + straggler defense (per decoded token, and
        # enqueue -> first tokens of a new generation).
        self._decode_ewma_secs: Optional[float] = None
        self._ttfc_ewma_secs: Optional[float] = None
        self._last_stream_stats: Dict[str, float] = {}
        # server_id "gen3" → worker_index 3 at the aggregator. Dynamic
        # (autoscaler-spawned) "dynN" ids live in a disjoint index range:
        # the aggregator merges snapshots by (worker_kind, worker_index),
        # so dyn1 sharing index 1 with baseline gen1 would silently
        # overwrite its counters/traces/flight dumps.
        idx = int("".join(c for c in cfg.server_id if c.isdigit()) or 0)
        if cfg.server_id.startswith("dyn"):
            idx += 1000
        self.telemetry = (
            telemetry.Telemetry(
                cfg.experiment, cfg.trial, "generation_server",
                idx, cfg=cfg.telemetry,
            ) if cfg.telemetry.enabled else telemetry.NULL
        )
        # Goodput ledger + live decode MFU (system/goodput.py): idle is
        # the base state (queue-empty waits), decode/prefill windows
        # enter compute, weight updates enter comm. Null when disabled.
        self.ledger = goodput_mod.make_ledger(cfg.goodput, self.telemetry)
        self._mfu = None
        self._n_chips = 1
        if self.ledger.enabled:
            self._n_chips = max(jax.device_count(), 1)
            self._mfu = goodput_mod.MfuEmitter(
                self.telemetry,
                goodput_mod.resolve_peak_flops(
                    cfg.goodput, jax.devices()[0].device_kind
                ),
                tflops_name="genserver/decode_tflops",
                mfu_name="genserver/decode_mfu",
                context=f"genserver {cfg.server_id}",
            )
        # Compile & HBM observatory: per-instance watches bound to THIS
        # server's telemetry (several servers share the gen-fleet
        # process). The jit entry points below route through the
        # wrappers; NULL when disabled, so the hot path pays one extra
        # plain call at most.
        arm_watch = cfg.compile_watch.enabled and cfg.telemetry.enabled
        self.compile_watch = (
            compile_watch_mod.CompileWatch(
                self.telemetry,
                storm_warmup_calls=cfg.compile_watch.storm_warmup_calls,
            ) if arm_watch else compile_watch_mod.NULL
        )
        self.memwatch = (
            memwatch_mod.MemWatch(
                self.telemetry,
                sample_interval_secs=(
                    cfg.compile_watch.mem_sample_interval_secs
                ),
            ) if arm_watch else memwatch_mod.NULL
        )
        self._prefill_fn = self.compile_watch.wrap(
            "genserver/prefill", genmod.prefill_state
        )
        self._decode_fn = self.compile_watch.wrap(
            "genserver/decode", genmod.decode_chunk_rows
        )
        self._extend_fn = self.compile_watch.wrap(
            "genserver/extend", genmod.extend_state
        )
        # The serving engine owns queueing, batch formation, retained-KV
        # lifecycle, and the compile-shape set; this server's handlers and
        # decode loop delegate those decisions (docs/serving.md).
        self.serving = serving_mod.ServingEngine(
            cfg.serving,
            kv_slots=cfg.kv_slots,
            kv_bytes_budget=cfg.kv_bytes_budget,
            kv_bucket=cfg.kv_bucket,
            chunk_tokens=cfg.chunk_tokens,
            max_batch_size=cfg.max_batch_size,
            prompt_bucket=cfg.prompt_bucket,
            telemetry=self.telemetry,
        )
        self._queue = self.serving.queue

    # ---------------- decode core ----------------

    def _decode_batch(self, batch: List[_Pending]) -> List[Dict[str, Any]]:
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        kv = self.serving.kv
        shapes = self.serving.shapes
        # Capture (params, version) atomically — a single load of the
        # published pair. handle_update_weights swaps both on the event
        # loop while we run in a thread; reading two separate attributes
        # could tag old-weight tokens (and retained KV, which the serving
        # engine hands out as prefix-reuse donors) with the new version.
        params, version = self._published
        # Sampling params are per-ROW dynamic arrays (ops.sampling), so a
        # batch may freely mix gconfigs; only the chunk length (static) is
        # shared. The shape policy rounds it to a configured bucket (rows
        # with a smaller budget stop early via row_budget), then clamps it
        # so the longest prefix in the batch still fits the largest KV
        # capacity bucket — admission guarantees at least one slot of room.
        chunk = shapes.round_chunk(
            min(cfg.chunk_tokens, max(p.max_tokens for p in batch))
        )
        if shapes.capacity_buckets is not None:
            # Remaining room under the largest capacity bucket, measured
            # against the BUCKETED prompt width (what prefill actually
            # pads to — prompt_bucket multiple, then the policy's width
            # bucket, exactly what admission checked). Admission
            # guarantees ≥ 1 slot; snapping the clamped chunk DOWN to a
            # bucket keeps near-ceiling batches from minting one compiled
            # shape per distinct room value — and with widths bucketed
            # too, room itself takes at most len(width_buckets) values.
            widest = max(
                shapes.round_width(
                    serving_mod.round_up(len(p.prompt), cfg.prompt_bucket)
                )
                for p in batch
            )
            room = shapes.capacity_buckets[-1] - widest
            chunk = max(1, shapes.round_chunk_down(min(chunk, room)))

        # Split: requests whose decode state survived (same version, prefix
        # length matches) continue from their KV; the rest prefill — via a
        # shared-prefix donor when the serving engine finds one. The state
        # OBJECT is captured here: /update_weights may clear the store on
        # the event loop while this thread runs, and a later re-lookup
        # would find nothing.
        cont: List[tuple] = []  # (pending, captured ReqState)
        fresh: List[_Pending] = []
        for p in batch:
            st = None
            if p.rid is not None and cfg.kv_slots > 0:
                st = kv.get(p.rid)
            if (
                st is not None and st.version == version
                and st.cur_len == len(p.prompt)
            ):
                st.last_used = time.monotonic()
                cont.append((p, st))
            else:
                fresh.append(p)

        row_states = {}
        fresh = [p for p in fresh
                 if not self._try_seed_from_prefix(
                     p, row_states, params, version, chunk)]
        if fresh:
            padded, plens = genmod.pad_prompts(
                [p.prompt for p in fresh], cfg.pad_token_id,
                bucket=cfg.prompt_bucket,
            )
            # Snap the padded prompt width to a policy width bucket
            # (pass-through when serving is off): per-prompt_bucket widths
            # are an unbounded compiled-shape family; geometric widths
            # keep the prefill shape set inside max_compiled_shapes.
            W = shapes.round_width(padded.shape[1])
            if W > padded.shape[1]:
                padded = np.concatenate([
                    padded,
                    np.full((padded.shape[0], W - padded.shape[1]),
                            cfg.pad_token_id, dtype=padded.dtype),
                ], axis=1)
            # Pad prefill rows up to a row bucket (dummy single-pad-token
            # prompts, sliced away below) so prefill compiles per bucketed
            # (rows, prompt, capacity), not per exact batch size.
            B_pad = shapes.round_rows(len(fresh))
            if B_pad > len(fresh):
                padded = np.concatenate([
                    padded,
                    np.full((B_pad - len(fresh), padded.shape[1]),
                            cfg.pad_token_id, dtype=padded.dtype),
                ])
                plens = np.concatenate([
                    plens, np.ones(B_pad - len(fresh), plens.dtype)
                ])
            S = shapes.round_capacity(padded.shape[1] + chunk)
            shapes.observe("prefill", B_pad, padded.shape[1], S)
            t_prefill_wall = time.time()
            t_prefill = time.monotonic()
            with dispatch_label("prefill"):
                st = self._prefill_fn(
                    params, self.model_cfg, jnp.asarray(padded),
                    jnp.asarray(plens), S,
                )
            prefill_secs = time.monotonic() - t_prefill
            n_prefill = int(plens[:len(fresh)].sum())
            self._prefill_tokens += n_prefill
            if self._mfu is not None and prefill_secs > 0 and n_prefill:
                # Analytic prefill FLOP/s (forward-only, shared formula
                # family with the trainer's gauges — base/monitor.py).
                from areal_tpu.base import monitor

                pf = monitor.model_flops_per_token(
                    self.model_cfg, n_prefill / max(len(fresh), 1),
                    backward=False,
                ) * n_prefill
                self.telemetry.set_gauge(
                    "genserver/prefill_tflops",
                    pf / prefill_secs / self._n_chips / 1e12,
                )
            for i, p in enumerate(fresh):
                row_states[id(p)] = genmod.slice_state(st, i)
                if p.trace is not None:
                    # Shared batched-prefill window, tagged per request.
                    self.telemetry.add_span(
                        "genserver/prefill", t_prefill_wall, prefill_secs,
                        trace=p.trace, prompt_len=len(p.prompt),
                        batch_size=len(fresh),
                    )
        for p, rs in cont:
            row_states[id(p)] = genmod.grow_state(
                rs.state, shapes.round_capacity(rs.cur_len + chunk)
            )

        # Group rows by KV capacity (static shape per decode_chunk call).
        groups: Dict[int, List[_Pending]] = {}
        for p in batch:
            S = row_states[id(p)]["kv_k"].shape[2]
            groups.setdefault(S, []).append(p)

        res_by_id: Dict[int, Dict[str, Any]] = {}
        for S, group in groups.items():
            # Pad the group to a row bucket with copies of row 0 given a
            # zero budget — they finish at step 0 and their outputs are
            # discarded, so decode compiles per bucketed (rows, S, chunk).
            rows = shapes.round_rows(len(group))
            n_dummy = rows - len(group)
            states = [row_states[id(p)] for p in group]
            stacked = genmod.stack_states(states + states[:1] * n_dummy)
            done = jnp.asarray(
                [p.tokens_done for p in group] + [0] * n_dummy, jnp.int32
            )
            self._key, sub = jax.random.split(self._key)
            from areal_tpu.ops.sampling import sampling_from_gconfigs

            shapes.observe("decode", rows, S, chunk)
            new_state, out = self._decode_fn(
                params, self.model_cfg, stacked, done, sub,
                sampling_from_gconfigs(
                    [p.gconfig for p in group]
                    + [group[0].gconfig] * n_dummy
                ),
                n_tokens=chunk,
                eos_token_id=cfg.eos_token_id, pad_token_id=cfg.pad_token_id,
                # Rows with a smaller remaining budget than the batch chunk
                # stop sampling at their own allowance (dummies at 0).
                row_budget=jnp.asarray(
                    [min(p.max_tokens, chunk) for p in group]
                    + [0] * n_dummy, jnp.int32
                ),
            )
            out = jax.device_get(out)
            for i, p in enumerate(group):
                # Never hand back more than the request's remaining budget —
                # the client appends every token we return.
                n = min(int(out["output_lens"][i]), p.max_tokens)
                toks = np.asarray(out["output_ids"][i][:n])
                lps = np.asarray(out["output_logprobs"][i][:n])
                # "finished" = the MODEL ended the sequence (EOS). Budget
                # exhaustion is the client's call — it knows the total
                # budget across chunks, we only see this chunk's slice.
                emitted_eos = bool((toks == cfg.eos_token_id).any())
                res_by_id[id(p)] = {
                    "output_ids": toks.tolist(),
                    "output_logprobs": lps.tolist(),
                    "finished": emitted_eos,
                    "version": version,
                }
                self._tokens_out += n
                if p.rid is not None and cfg.kv_slots > 0:
                    allowance = min(p.max_tokens, chunk)
                    keep = (
                        # Serving: the client's next prefix is exactly
                        # prompt+n whenever the row ran its full allowance
                        # without EOS; even if the client never returns,
                        # the retained state doubles as a prefix-reuse
                        # donor and LRU + the bytes budget reclaim it.
                        (cfg.serving.enabled and n == allowance)
                        # Legacy: keep only full-chunk continuations with
                        # budget left (a consumed allowance might mean the
                        # client never comes back; budget truncation would
                        # desync cur_len) — the pre-serving behavior.
                        or (not cfg.serving.enabled
                            and n == chunk and n < p.max_tokens)
                    )
                    if emitted_eos or not keep:
                        kv.pop(p.rid)
                    else:
                        kv.put(p.rid, _ReqState(
                            genmod.slice_state(new_state, i),
                            cur_len=len(p.prompt) + n,
                            version=version,
                            # The full token sequence only feeds the
                            # prefix trie — skip the per-chunk O(seq)
                            # concatenate when reuse can't consume it.
                            tokens=np.concatenate([
                                np.asarray(p.prompt, np.int64),
                                toks.astype(np.int64),
                            ]) if kv.prefix_reuse else None,
                        ))
        kv.evict()
        return [res_by_id[id(p)] for p in batch]

    def _try_seed_from_prefix(self, p: _Pending, row_states: Dict,
                              params, version: int, chunk: int) -> bool:
        """Cross-request prefix seeding (docs/serving.md): if a retained
        state's token sequence shares a prefix with this prompt, clone
        the donor's KV at the shared length and prefill only the suffix.
        Returns True when ``row_states[id(p)]`` was seeded."""
        import jax.numpy as jnp

        cfg = self.cfg
        shapes = self.serving.shapes
        got = self.serving.kv.acquire_prefix(
            p.prompt, version, min_len=cfg.serving.min_prefix_tokens
        )
        if got is None:
            return False
        rid, shared = got
        try:
            T = None
            if shared < len(p.prompt):
                # Prefill and extend pad to the same width buckets, so a
                # clone+extend only saves compute when the bucketed suffix
                # is strictly narrower than the full-prompt prefill width.
                # Otherwise it's a net loss: same padded matmul, plus
                # clone/grow/trie overhead, plus it pulls the row out of
                # the batched prefill into a serial B=1 extend dispatch.
                try:
                    W_full = shapes.round_width(
                        serving_mod.round_up(
                            len(p.prompt), cfg.prompt_bucket
                        )
                    )
                    T = shapes.round_width(
                        serving_mod.round_up(
                            len(p.prompt) - shared, cfg.prompt_bucket
                        )
                    )
                except serving_mod.PromptTooLong:
                    return False  # near the capacity ceiling: plain prefill
                if T >= W_full:
                    self.telemetry.inc("serving/prefix_skipped_no_savings")
                    return False
            donor = self.serving.kv.get(rid)
            if donor is None:
                # /update_weights cleared the store on the event loop
                # between acquire and here — fall back to a plain prefill.
                return False
            st = genmod.clone_prefix(donor.state, shared)
            suffix = np.asarray(p.prompt[shared:], np.int32)
            if len(suffix) == 0:
                # Exact full-sequence match: the donor's last_logits are
                # the ones this prompt needs — a pure clone, zero prefill.
                need = shapes.round_capacity(len(p.prompt) + chunk)
                if need > st["kv_k"].shape[2]:
                    st = genmod.grow_state(st, need)
                # decode_chunk_rows donates its input state, and a
                # single-row group's stack_states returns these very
                # arrays (a one-array concatenate is the identity) —
                # donation would delete the donor's retained buffers in
                # place, poisoning the store. Copy every leaf still
                # shared with the donor (grow_state already freed the KV
                # leaves when it grew; last_logits is always shared).
                st = {
                    k: (jnp.copy(v) if v is donor.state.get(k) else v)
                    for k, v in st.items()
                }
                row_states[id(p)] = st
                self.telemetry.inc("serving/prefix_hits")
                self.telemetry.inc("serving/prefix_tokens_saved", shared)
                return True
            # T (the suffix width, through the same buckets as prefill)
            # was computed by the savings gate above; the extend kernel
            # is one more compiled-shape family the policy keeps finite.
            try:
                need = shapes.round_capacity(
                    max(len(p.prompt) + chunk, shared + T)
                )
            except serving_mod.PromptTooLong:
                return False  # near the capacity ceiling: plain prefill
            if need > st["kv_k"].shape[2]:
                st = genmod.grow_state(st, need)
            padded = np.full((1, T), cfg.pad_token_id, np.int32)
            padded[0, :len(suffix)] = suffix
            shapes.observe("extend", 1, T, st["kv_k"].shape[2])
            row_states[id(p)] = self._extend_fn(
                params, self.model_cfg, st, jnp.asarray(padded),
                jnp.asarray([len(suffix)], jnp.int32),
            )
            self._prefill_tokens += len(suffix)
            self.telemetry.inc("serving/prefix_hits")
            self.telemetry.inc("serving/prefix_tokens_saved", shared)
            return True
        finally:
            self.serving.kv.release(rid)

    async def _runner(self):
        cfg = self.cfg
        while True:
            # Re-anchor the ledger at idle every iteration: this loop is
            # the partition's single owner (weight updates accrue comm
            # via add(), never transitions — a concurrent restore racing
            # the decode's would wedge the partition in a stale state).
            self.ledger.enter("idle")
            first: _Pending = await self._queue.get()
            batch = [first]
            await asyncio.sleep(cfg.batch_window_ms / 1000)
            # Drain up to max_batch_size. The serving queue pops in class
            # priority order (interactive > eval > rollout; plain FIFO
            # when serving is disabled). Sampling params are per-row
            # vectors inside the decode kernel, so mixed gconfigs batch
            # together — no deferral, no starvation within a class.
            batch += self._queue.drain(cfg.max_batch_size - 1)
            t_formed = time.monotonic()
            for p in batch:
                # The serving engine owns the SLO observation AND the
                # per-request trace span for the queue stage.
                self.serving.record_queue_wait(
                    p.cls, t_formed - p.t_enqueue,
                    trace=p.trace, t_start_wall=p.t_enqueue_wall,
                )
            try:
                if self.faults is not None:
                    # Injected straggler latency: inside the measured
                    # decode window so the reported EWMAs include it.
                    await self.faults.maybe_delay(
                        "decode", server_id=self.cfg.server_id,
                    )
                with self.telemetry.span("genserver/decode_chunk",
                                         batch_size=len(batch)) as attrs, \
                        self.ledger.state("compute"):
                    results = await asyncio.to_thread(
                        self._decode_batch, batch
                    )
                    attrs["tokens"] = sum(
                        len(r["output_ids"]) for r in results
                    )
                self.telemetry.inc("genserver/decode_chunks")
                self.telemetry.inc("genserver/generated_tokens",
                                   attrs["tokens"])
                dt = time.monotonic() - t_formed
                t_decode_wall = time.time() - dt
                if self._mfu is not None and attrs["tokens"] and dt > 0:
                    # Analytic decode FLOP/s + MFU per batch: each new
                    # token runs one forward at roughly the row's current
                    # context length (base/monitor.py formula family).
                    from areal_tpu.base import monitor

                    avg_ctx = sum(
                        len(p.prompt) + p.tokens_done for p in batch
                    ) / len(batch)
                    self._mfu.emit(
                        monitor.model_flops_per_token(
                            self.model_cfg, avg_ctx, backward=False
                        ) * attrs["tokens"] / dt / self._n_chips
                    )
                chunk_tokens = max(
                    (len(r["output_ids"]) for r in results), default=0
                )
                if chunk_tokens > 0:
                    # Per-token decode latency EWMA for /health — the
                    # manager's straggler EWMAs feed off this.
                    sample = dt / chunk_tokens
                    self._decode_ewma_secs = (
                        sample if self._decode_ewma_secs is None
                        else 0.7 * self._decode_ewma_secs + 0.3 * sample
                    )
                for p, r in zip(batch, results):
                    n_tok = len(r["output_ids"])
                    if p.trace is not None:
                        # This request's share of the batched decode
                        # window (wall window is shared — per-request
                        # token counts distinguish the rows).
                        self.telemetry.add_span(
                            "genserver/decode", t_decode_wall, dt,
                            trace=p.trace, tokens=n_tok,
                            batch_size=len(batch),
                            version=r.get("version"),
                        )
                    if p.tokens_done == 0:
                        # Time-to-first-chunk: enqueue → first tokens of a
                        # NEW generation (continuations measure per-token).
                        ttfc = time.monotonic() - p.t_enqueue
                        self.serving.record_first_chunk(p.cls, ttfc)
                        self._ttfc_ewma_secs = (
                            ttfc if self._ttfc_ewma_secs is None
                            else 0.7 * self._ttfc_ewma_secs + 0.3 * ttfc
                        )
                    if n_tok:
                        self.serving.record_token_latency(p.cls, dt / n_tok)
                    # A disconnected client's handler task was cancelled,
                    # cancelling its future — set_result would raise
                    # InvalidStateError and the generic handler below
                    # would then 500 every other request in the batch.
                    if not p.future.done():
                        p.future.set_result(r)
                self.serving.export_gauges()
            except asyncio.CancelledError:
                # Server stopping mid-decode: fail the batch so its HTTP
                # handlers return immediately instead of hanging through
                # the runner's graceful-shutdown window.
                for p in batch:
                    if not p.future.done():
                        p.future.set_exception(
                            RuntimeError("generation server stopping")
                        )
                raise
            except Exception as e:  # noqa: BLE001 — propagate per-request
                for p in batch:
                    if not p.future.done():
                        p.future.set_exception(e)

    # ---------------- http ----------------

    async def handle_generate(self, request):
        from aiohttp import web

        d = await request.json()
        gconfig = GenerationHyperparameters(**d.get("gconfig", {}))
        cls = serving_mod.normalize_class(d.get("class"))
        prompt = np.asarray(d["prompt_ids"], np.int32)
        fut = asyncio.get_running_loop().create_future()
        p = _Pending(
            prompt=prompt,
            gconfig=gconfig,
            max_tokens=int(d.get("max_tokens", gconfig.max_new_tokens)),
            future=fut,
            rid=d.get("rid"),
            tokens_done=int(d.get("tokens_done", 0)),
            cls=cls,
            # Adopt the caller's trace (header absent / telemetry off
            # → None, zero extra work).
            trace=(telemetry.extract_headers(request.headers)
                   if self.telemetry.enabled else None),
        )
        try:
            # Admission + enqueue are one atomic decision on the event
            # loop: either the request is queued or the client gets
            # backpressure NOW (429 + Retry-After) instead of a spot in an
            # unbounded pending list its SLO could never survive.
            # "budget_total" is the chunked client's FULL remaining token
            # budget (partial_rollout sends it); absent — a single-shot
            # or third-party client — only this request's prompt is
            # feasibility-checked, the pre-existing behavior.
            budget = d.get("budget_total")
            self.serving.admit(
                p, cls, prompt_len=len(prompt),
                planned_len=(
                    len(prompt) + int(budget) if budget else None
                ),
            )
        except serving_mod.AdmissionReject as e:
            import math

            # Header is RFC 9110 delay-seconds (integer); the JSON body
            # keeps the precise float for clients that read it.
            return web.json_response(
                {"ok": False, "reason": "admission", "class": cls,
                 "queue_depth": e.depth, "retry_after": e.retry_after},
                status=429,
                headers={"Retry-After": str(math.ceil(e.retry_after))},
            )
        except serving_mod.PromptTooLong as e:
            return web.json_response(
                {"ok": False, "reason": "prompt_too_long",
                 "needed_slots": e.needed, "max_slots": e.cap},
                status=413,
            )
        self._inflight += 1
        try:
            return web.json_response(await fut)
        finally:
            self._inflight -= 1

    def _load_and_put_weights(self, path: str):
        """Host-side checkpoint read + device upload. Runs in a worker
        thread — the event loop (and /generate batching) never blocks on
        disk or transfer; only the final reference swap happens on-loop."""
        import jax

        from areal_tpu.models import hf as hfmod

        _, params = hfmod.load_checkpoint_auto(path)
        # Preserve the existing per-leaf device placement/sharding.
        return jax.tree.map(
            lambda old, npv: jax.device_put(
                np.asarray(npv, dtype=old.dtype), old.sharding
            ),
            self.params,
            params,
        )

    def _stream_and_put_weights(self, endpoint: str, version: int,
                                timeout_secs: Optional[float] = None):
        """Streamed transport (docs/weight_sync.md): pull the manifest +
        per-tensor chunks from the trainer's WeightStreamPublisher into a
        SHADOW pytree, device_put'ing each tensor as it lands so the h2d
        upload of tensor i−1 overlaps the wire transfer of tensor i (whose
        d2h gather the publisher is doing concurrently). The shadow tree
        only replaces ``self.params`` after the publisher's digest verifies
        the complete stream — a torn, reordered, or corrupted transfer
        raises before anything live is touched."""
        from areal_tpu.models.hf import flatten_pytree
        from areal_tpu.system.weight_stream import WeightStreamConsumer

        old_flat = flatten_pytree(self.params)
        consumer = WeightStreamConsumer(
            endpoint,
            pipeline_depth=self.cfg.weight_stream_pipeline_depth,
            **({} if timeout_secs is None
               else {"timeout_secs": timeout_secs}),
        )
        # The shadow-pytree swap is the server's HBM high-water mark: old
        # + new params coexist until the verified swap. The watermark
        # gauge is the measured number docs/weight_sync.md budgets 2x
        # params for.
        with self.memwatch.watermark("genserver/shadow_swap"):
            return self._stream_shadow(consumer, version, old_flat)

    def _stream_shadow(self, consumer, version: int, old_flat):
        import jax

        from areal_tpu.models.hf import unflatten_pytree
        from areal_tpu.system.weight_stream import WeightStreamError

        try:
            manifest = consumer.fetch_manifest(version)
            shadow = {}
            for name, arr in consumer.iter_tensors(version, manifest):
                old = old_flat.get(name)
                if old is None:
                    raise WeightStreamError(
                        f"streamed tensor {name!r} not in the live pytree"
                    )
                if tuple(arr.shape) != tuple(old.shape):
                    raise WeightStreamError(
                        f"tensor {name!r}: streamed shape {arr.shape} != "
                        f"live {old.shape}"
                    )
                # Async dispatch: device_put returns immediately, so the
                # upload runs while the next chunks arrive.
                shadow[name] = jax.device_put(
                    np.asarray(arr, dtype=old.dtype), old.sharding
                )
            if set(shadow) != set(old_flat):
                missing = sorted(set(old_flat) - set(shadow))
                raise WeightStreamError(
                    f"incomplete stream: {len(missing)} tensors missing "
                    f"(e.g. {missing[:3]})"
                )
            # The gate: no swap without a checksum-verified manifest.
            consumer.verify_digest(version)
            new = unflatten_pytree(shadow)
            jax.block_until_ready(new)
            # Per-leg stream stats for /metrics + telemetry: wire wait,
            # digest/checksum CPU, and total bytes of this consume.
            # Recorded ONLY on a verified success — a failed update must
            # leave /metrics unchanged (the except handler's contract).
            self._last_stream_stats = {
                "stream_bytes": float(consumer.bytes_received),
                "digest_verify_secs": consumer.checksum_secs,
                "wire_wait_secs": consumer.wire_wait_secs,
            }
            return new
        finally:
            consumer.close()

    def _reshard_published_weights(self, role: str, version: int,
                                   digest: str):
        """Device transport (docs/weight_sync.md §device): the trainer
        resharded its live params into this fleet's layout ON DEVICE and
        registered them (parallel/reshard.py); the fanout payload carries
        the publication digest out of band. consume_device verifies
        version + digest + tree compatibility against the live pytree
        before returning the weights resharded into this server's own
        shardings — any gate failure raises with the old weights still
        live, the same contract as a torn stream."""
        import jax

        from areal_tpu.parallel import reshard as rsh

        with self.memwatch.watermark("genserver/device_consume"):
            new = rsh.consume_device(
                self.cfg.experiment, self.cfg.trial, role,
                version, digest, self.params,
            )
            jax.block_until_ready(new)
        return new

    async def handle_update_weights(self, request):
        from aiohttp import web

        d = await request.json()
        t0 = time.monotonic()
        transport = ("device" if d.get("device")
                     else "stream" if d.get("endpoint") else "disk")
        try:
            with self.telemetry.span("genserver/weight_update",
                                     transport=transport,
                                     version=int(d.get("version", -1))):
                if d.get("device"):
                    new = await asyncio.to_thread(
                        self._reshard_published_weights,
                        d.get("role", "actor"), int(d["version"]),
                        d.get("digest", ""),
                    )
                elif d.get("endpoint"):
                    new = await asyncio.to_thread(
                        self._stream_and_put_weights, d["endpoint"],
                        int(d["version"]),
                        d.get("timeout"),
                    )
                else:
                    new = await asyncio.to_thread(
                        self._load_and_put_weights, d["path"]
                    )
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — keep old weights, report
            # Old (params, version) stay live and /metrics unchanged; the
            # manager's fanout retry/eviction machinery owns what happens
            # to this server next (docs/fault_tolerance.md).
            self.telemetry.inc("genserver/weight_update_failures")
            logger.error(f"weight update failed; keeping v{self.version}: {e}")
            return web.json_response(
                {"ok": False, "version": self.version, "error": str(e)},
                status=500,
            )
        finally:
            # Weight-update comm is ACCRUED in the overlap family, not a
            # partition transition: the update overlaps in-flight decodes
            # on this event loop — a concurrent enter/restore pair would
            # wedge the partition (the runner owns idle<->compute
            # exclusively), and folding it into the partition counters
            # would make states sum past wall clock, deflating every
            # derived utilization fraction.
            self.ledger.add_overlap("comm", time.monotonic() - t0)
        # Atomic (params, version) swap: in-flight _decode_batch threads
        # captured the old pair and tag their tokens with the old version.
        self.params = new
        self.version = int(d.get("version", self.version + 1))
        self._published = (new, self.version)
        # KV computed under the old weights is stale — continuations after
        # a version change re-prefill once (reference: SGLang flushes its
        # cache on update_weights_from_disk). The prefix trie empties with
        # it: old-version states must never seed new requests.
        self.serving.kv.clear()
        dt = time.monotonic() - t0
        self._last_update_latency = dt
        self.telemetry.set_gauge("genserver/weight_version", self.version)
        self.telemetry.set_gauge("genserver/weight_update_secs", dt)
        if transport == "stream":
            # Disk updates must not republish the previous stream's stats
            # as if they described this sync.
            for k, v in self._last_stream_stats.items():
                self.telemetry.set_gauge(f"genserver/{k}", v)
        logger.info(f"weights updated to v{self.version} in {dt:.2f}s")
        return web.json_response({"ok": True, "version": self.version,
                                  "latency_s": dt})

    async def handle_health(self, request):
        # Polled by the gserver manager's fleet-health loop: ``version`` is
        # what the manager reconciles against when re-admitting this server
        # after an eviction (docs/fault_tolerance.md).
        from aiohttp import web

        # The manager's periodic probe doubles as the ledger's heartbeat:
        # a long queue-empty idle accrues onto the scrape without waiting
        # for the next decode transition.
        self.ledger.poll()
        return web.json_response({
            "ok": True,
            "version": self.version,
            "server_id": self.cfg.server_id,
            "uptime_secs": time.monotonic() - self._t_start,
            # Load/latency stats riding the probe: the manager's
            # autoscale signals (queue depth, TTFC SLO) and straggler
            # EWMAs come for free with the health sweep it already runs.
            "queue_depth": self._queue.qsize(),
            "inflight": self._inflight,
            "decode_ewma_secs": self._decode_ewma_secs,
            "ttfc_ewma_secs": self._ttfc_ewma_secs,
        })

    def _metrics_dict(self) -> Dict[str, Any]:
        self.ledger.poll()  # scrape-time freshness for the idle state
        # HBM gauges piggyback on the scrape cadence (rate-limited inside
        # the watch; NULL when the observatory is off).
        self.memwatch.sample()
        dt = max(time.monotonic() - self._t_start, 1e-6)
        d = {
            "generated_tokens": self._tokens_out,
            "prefill_tokens": self._prefill_tokens,
            "tokens_per_sec": self._tokens_out / dt,
            "kv_states": self.serving.kv.count,
            "kv_bytes": self.serving.kv.nbytes,
            # Distinct compiled (kind, dims) decode-engine shapes so far —
            # the compile-churn bound VERDICT #9 asks to watch.
            "compiled_shapes": self.serving.shapes.distinct_shapes,
            "version": self.version,
            "inflight_requests": self._inflight,
            "queue_depth": self._queue.qsize(),
            "decode_ewma_secs": self._decode_ewma_secs or 0.0,
            "ttfc_ewma_secs": self._ttfc_ewma_secs or 0.0,
            "last_weight_update_latency_s": self._last_update_latency,
            # Stats of the last SUCCESSFUL streamed consume (absent until
            # one lands; a later disk update does not describe these).
            **{f"last_stream_{k}": v
               for k, v in self._last_stream_stats.items()},
        }
        if self.cfg.serving.enabled:
            for c in serving_mod.REQUEST_CLASSES:
                d[f"serving_queue_{c}"] = self._queue.depth(c)
        return d

    async def handle_metrics(self, request):
        """Prometheus exposition text (docs/observability.md): live server
        state as ``areal_genserver_*`` gauges — including weight_version
        and inflight_requests — plus this server's telemetry registry
        (decode spans → histograms) when telemetry is enabled. The old
        JSON body moved to ``/metrics.json``."""
        from aiohttp import web

        d = self._metrics_dict()
        extra = {f"genserver_{k}": v for k, v in d.items()}
        # Canonical gauge name, present from boot (the registry's copy
        # only exists once the first /update_weights lands).
        extra["genserver_weight_version"] = d["version"]
        body = telemetry.render_prometheus(
            self.telemetry.snapshot(reset=False),
            extra_gauges=extra,
            labels={"server_id": self.cfg.server_id},
        )
        return web.Response(
            text=body, content_type="text/plain",
            charset="utf-8", headers={"X-Prometheus-Version": "0.0.4"},
        )

    def _device_info(self) -> Dict[str, Any]:
        """Where THIS server's weights live, as jax reports it, plus the
        process-wide trace/compile counters a chip run is judged by."""
        import jax

        from areal_tpu.ops import attention

        devs = sorted(jax.tree_util.tree_leaves(self.params)[0].devices(),
                      key=lambda d: d.id)
        stats = [d.memory_stats() or {} for d in devs]
        return {
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": jax.device_count(),
            "device_ids": [d.id for d in devs],
            "hbm_bytes_in_use": [s.get("bytes_in_use") for s in stats],
            "hbm_peak_bytes": [s.get("peak_bytes_in_use") for s in stats],
            "attention": attention.dispatch_counts(),
            "compile_cache": compile_watch_mod.cache_stats(),
        }

    async def handle_metrics_json(self, request):
        from aiohttp import web

        return web.json_response(
            {**self._metrics_dict(), "device": self._device_info()}
        )

    def build_app(self):
        from aiohttp import web

        app = web.Application(client_max_size=256 * 1024 * 1024)
        app.router.add_post("/generate", self.handle_generate)
        app.router.add_post("/update_weights", self.handle_update_weights)
        app.router.add_get("/health", self.handle_health)
        app.router.add_get("/metrics", self.handle_metrics)
        app.router.add_get("/metrics.json", self.handle_metrics_json)
        return app

    async def start(self) -> str:
        """Start serving; registers the URL under names.gen_servers."""
        from aiohttp import web

        self._runner_task = asyncio.create_task(self._runner())
        app = self.build_app()
        runner = web.AppRunner(app)
        await runner.setup()
        port = self.cfg.port or network.find_free_port()
        site = web.TCPSite(runner, network.bind_addr(), port)
        await site.start()
        url = f"http://{network.gethostip()}:{port}"
        from areal_tpu.system.worker_base import (
            HeartbeatThread,
            env_keepalive_ttl,
        )

        ttl = self.cfg.keepalive_ttl_secs or env_keepalive_ttl() or 0.0
        key = names.gen_servers(self.cfg.experiment, self.cfg.trial,
                                self.cfg.server_id)
        name_resolve.add(key, url, replace=True, keepalive_ttl=ttl or None)
        # Heartbeat from a dedicated THREAD, not this event loop: a long
        # decode compile blocks the loop for minutes, and the lease must
        # not lapse (the manager would forget a merely-busy server). The
        # lease exists for SIGKILLed processes — those lose their
        # threads too, so the ghost key still expires.
        self._hb = None
        if ttl:
            from areal_tpu.system.worker_base import (
                default_heartbeat_interval,
            )

            self._hb = HeartbeatThread(
                self.cfg.experiment, self.cfg.trial,
                f"genserver_{self.cfg.server_id}",
                interval=default_heartbeat_interval(ttl),
                # Compile-aware liveness: publish names.compile_inflight
                # while prefill/decode/extend compile a fresh shape.
                inflight_fn=self.compile_watch.inflight,
            )
            self._hb.lease(key, url, ttl)
        logger.info(f"generation server {self.cfg.server_id} at {url}"
                    + (f" (keepalive {ttl:.0f}s)" if ttl else ""))
        self._runner_obj = runner
        return url

    async def stop(self, abort: bool = False):
        """Stop serving. ``abort=True`` is the crash-like path (chaos
        tests): queued requests are failed immediately instead of drained,
        so connected clients see errors now rather than a hung socket."""
        if self._runner_task:
            self._runner_task.cancel()
        if abort:
            while not self._queue.empty():
                p = self._queue.get_nowait()
                if not p.future.done():
                    p.future.set_exception(RuntimeError("server aborted"))
        if getattr(self, "_hb", None) is not None:
            self._hb.close()
        self.ledger.flush()
        self.memwatch.close()
        self.compile_watch.close()
        self.telemetry.close()
        await self._runner_obj.cleanup()
