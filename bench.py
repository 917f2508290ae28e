"""Benchmark: PPO trained-tokens/sec on the available chip(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

AREAL_TELEMETRY=1 additionally enables the in-process telemetry registry
(base/telemetry.py, no pusher/sockets) and emits the trainer step-phase
breakdown — split_pack / fwd_bwd / optimizer seconds per timed step — as
a "train_phases" field, so the BENCH trajectory records where each step's
wall clock went instead of one opaque scalar. Telemetry stays OFF by
default: the headline number always measures the uninstrumented path.
The phases are HOST seconds (fwd_bwd is dispatch time; the device's work
shows as the wait in fetch_stats): no span syncs the device.

Protocol (mirrors the reference's "effective trained tokens/sec",
benchmark/verl_v0_3_0_post1_76084d3/README.md:27-34): time full PPO actor
train steps — micro-batched forward+backward+optimizer over packed
variable-length trajectories — and divide the trajectory token count by
wall clock. Model: Qwen2.5-0.5B geometry (the largest BASELINE-family model
whose params+Adam+logits fit one 16G chip) in bf16. vs_baseline is
measured/analytic-roofline (MFU proxy) since the reference publishes no
absolute tokens/sec (BASELINE.md).
"""

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, ".")

import jax  # noqa: E402
import numpy as np  # noqa: E402


def main():
    from areal_tpu.base import telemetry
    from areal_tpu.base.compile_watch import enable_compilation_cache

    # JAX_COMPILATION_CACHE_DIR when set, else <repo>/.jax_cache — the
    # same directory every other compiling process of the repo uses.
    enable_compilation_cache()

    use_telemetry = os.environ.get("AREAL_TELEMETRY", "") not in ("", "0")
    if use_telemetry:
        # Local registry only — no aggregator exists here, so no pusher.
        telemetry.configure("bench", "b0", "trainer", 0, push=False)
    from areal_tpu.algorithms.ppo import (
        PPOActorInterface,
        PPOHyperparameters,
    )
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model import FinetuneSpec, Model
    from areal_tpu.backend.jax_train import JaxTrainBackend, OptimizerConfig
    from areal_tpu.models import transformer
    from areal_tpu.models.config import TransformerConfig

    # Qwen2.5-0.5B geometry (24 layers, d=896, 14q/2kv heads, ffn 4864) —
    # the largest BASELINE-family model whose params+Adam+logits fit one
    # 16G-HBM chip; multi-chip configs scale via the same engine's mesh.
    cfg = TransformerConfig(
        n_layers=24, hidden_dim=896, n_q_heads=14, n_kv_heads=2, head_dim=64,
        intermediate_dim=4864, vocab_size=151936, rotary_base=1e6,
        tie_word_embeddings=True, use_attention_bias=True, dtype="bfloat16",
    )
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    model = Model("actor", (cfg, params), tokenizer=None)
    del params  # the engine upcasts to f32 masters; don't pin the bf16 tree
    backend = JaxTrainBackend(
        # bf16 Adam moments: on this 16G chip the f32-master + f32-moment
        # layout doesn't leave room for the no-remat activation budget;
        # bf16 moments (math still f32 per step) restore it.
        optimizer=OptimizerConfig(lr=1e-5, lr_scheduler_type="constant",
                                  warmup_steps_proportion=0.0,
                                  mu_dtype="bfloat16", nu_dtype="bfloat16"),
        compute_dtype="bfloat16", length_bucket=512, rows_bucket=4,
        seqs_bucket=16,
        # r08 config: the cap-4096 + "dots"-remat + chunked-logprob combo
        # (ROADMAP item 1 retry). The r05 sweep measured cap-4096 dots ≈
        # cap-2048 no-remat within noise — but at the packer's old 0.84
        # fill; the 128-grain fill sweep (backend/microbatch.py) packs the
        # same trajectories at ≥0.96, so the 4096 cap now buys ~14% more
        # real tokens per padded FLOP. Since PR 28 the engine chooses per
        # grid what the backward re-runs ("matmuls" where it fits is what
        # "dots" was, plus the flash kernel's residuals); the chunked head
        # drops the [R, L, V] logits grid that no longer fits at L≈1792.
        remat=True, logprob_chunk=512,
    )
    model = backend.initialize(model, FinetuneSpec(1, 512, 64))
    # HONESTY NOTE vs BENCH_r04: r4's engine silently trained fully in
    # bf16 — params, Adam moments, updates (optax weak-type chain) — which
    # is lighter AND faster but rounds away updates smaller than ~4e-3
    # relative (bf16 mantissa), a silent quality bug for PPO-scale lrs.
    # The engine now keeps explicit f32 masters (backend/jax_train.py);
    # the bench measures the CORRECT training path. r05-r07 ran it at the
    # cap-2048 no-remat config (the best fit then); r08 moves to
    # cap-4096 + "dots"-remat + chunked-logprob, which the "dots" remat
    # fits in the same budget (see the backend block above).

    hp = PPOHyperparameters(ppo_n_minibatches=1, adv_norm=True,
                            kl_ctl=0.0, disable_value=True)
    iface = PPOActorInterface(hp)

    # Synthetic rollout batch: 32 trajectories, 256-token prompt + ~768 gen
    # (canonical recipe: base/testing.bench_trajectory_dist — shared with
    # perf_probe packfill and the packing-fill test gate).
    from areal_tpu.base.testing import bench_trajectory_dist

    n_seq = 32
    rng, plens, glens = bench_trajectory_dist(0, n_seq)
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
    spec = MicroBatchSpec(max_tokens_per_mb=4096)

    # Achieved packing fill (host-only, same packer the train step runs,
    # parameterized from the SAME backend fields so it cannot desync from
    # the engine's layout): the padding factor the reported MFU divides
    # by — tracked in the output so BENCH_r* records the fill lever
    # alongside tokens/s.
    from areal_tpu.backend import microbatch as mbu

    pack_mbs = mbu.split_into_microbatches(
        batch, spec, length_bucket=backend.length_bucket,
        rows_bucket=backend.rows_bucket, seqs_bucket=backend.seqs_bucket,
        fill_bucket=backend.fill_bucket,
    )
    pack_fill = mbu.pack_fill(pack_mbs)
    del pack_mbs

    # Warmup/compile wall clock as a first-class bench field: the trace
    # cost every fresh launch pays before step 1. Cache-sensitive — a warm
    # persistent cache (apps/launcher.py) collapses it — so the
    # bench_compare gate carries a wide tolerance (docs/benchmarks.md).
    t0 = time.perf_counter()
    iface.train_step(model, batch, spec)  # warmup/compile
    jax.block_until_ready(model.module.params)
    warmup_compile_s = time.perf_counter() - t0
    telemetry.get().snapshot(reset=True)  # drop warmup-step spans
    t0 = time.perf_counter()
    steps = 3
    for _ in range(steps):
        iface.train_step(model, batch, spec)
    jax.block_until_ready(model.module.params)
    dt = time.perf_counter() - t0

    # Trainer step-phase breakdown from the timed steps' telemetry spans
    # (backend/jax_train.py train_batch instrumentation).
    train_phases = None
    if use_telemetry:
        spans = telemetry.get().snapshot(reset=True)["spans"]
        agg = {}
        for s in spans:
            if s["name"].startswith("train/"):
                agg[s["name"]] = agg.get(s["name"], 0.0) + s["dur_secs"]
        train_phases = {
            k.split("/", 1)[1] + "_s": round(v / steps, 4)
            for k, v in sorted(agg.items())
        }

    n_chips = jax.device_count()
    tokens_per_sec_chip = steps * total / dt / n_chips

    # Device-memory high-water mark over the timed PPO steps (the whole
    # process so far, which the train loop dominates) — the same
    # allocator counter system/memwatch.py exports live as hbm/peak_bytes.
    # CPU backends have no memory_stats(); the field is then omitted and
    # bench_compare reports it n/a (docs/benchmarks.md).
    hbm_peak_gb = None
    try:
        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices()
        ]
        if any(peaks):
            hbm_peak_gb = max(peaks) / float(1 << 30)
    except Exception:  # noqa: BLE001 — backend-dependent, best-effort
        pass

    # North-star metric #2 (BASELINE.json): trainer→rollout weight-sync
    # latency, measured through the STREAMED transport (the production
    # path since this round, docs/weight_sync.md): the trainer-side
    # WeightStreamPublisher gathers bf16 tensors d2h in a background
    # thread while a consumer (standing in for one generation server)
    # pulls the chunks over ZMQ and device_puts each tensor as it lands —
    # the checkpoint round-trip through the filesystem is gone, and BOTH
    # host↔device legs are measured directly (r05's disk path measured d2h
    # and extrapolated h2d as symmetric; see docs/benchmarks.md for the
    # method discontinuity).
    import jax.numpy as jnp

    from areal_tpu.models.hf import flatten_pytree
    from areal_tpu.system.weight_stream import (
        WeightStreamConsumer,
        WeightStreamPublisher,
    )

    eng = model.module
    publisher = None
    consumer = None
    try:
        t0 = time.perf_counter()
        # Publish in the compute dtype (bf16), cast on device — mirrors
        # trainer_worker._publish_weights_stream: half the d2h/wire/h2d
        # bytes vs shipping the f32 masters.
        pub = jax.tree.map(
            lambda x: x.astype(eng.compute_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            eng.params,
        )
        old_flat = flatten_pytree(pub)  # device refs, no transfer
        publisher = WeightStreamPublisher("bench", "b0", "actor")
        publisher.publish(sorted(old_flat.items()), version=1)
        consumer = WeightStreamConsumer(publisher.endpoint)
        manifest = consumer.fetch_manifest(1)
        shadow = {}
        for name, arr in consumer.iter_tensors(1, manifest):
            old = old_flat[name]
            # Async dispatch: h2d of tensor i−1 overlaps the wire transfer
            # of tensor i and the publisher's d2h gather of tensor i+1.
            shadow[name] = jax.device_put(
                np.asarray(arr, dtype=old.dtype), old.sharding
            )
        consumer.verify_digest(1)
        assert set(shadow) == set(old_flat)
        jax.block_until_ready(list(shadow.values()))
        weight_sync_s = time.perf_counter() - t0
        # "io" = the host-side CPU work the framework controls (checksums,
        # framing, reassembly) — the analogue of r05's serialize+disk leg;
        # everything else is d2h/wire/h2d transport, pipelined.
        weight_sync_io_s = consumer.checksum_secs
        weight_sync_transport_s = weight_sync_s - weight_sync_io_s
    finally:
        if consumer is not None:
            consumer.close()
        if publisher is not None:
            publisher.close()

    # The device transport measured next to it (same params, same chip):
    # reshard-in-place publish + digest-gated consume through the
    # in-process registry (parallel/reshard.py) — no d2h, no wire, no h2d.
    # On a colocated single mesh the publish is a zero-copy plan walk, so
    # this number is the transport's floor; heterogeneous layouts add the
    # grouped on-device moves (tools/perf_probe.py reshard-bench sweeps
    # those).
    from areal_tpu.parallel import reshard as rsh

    t0 = time.perf_counter()
    dev_pub = rsh.publish_device(
        "bench", "b0", "actor", pub,
        target_shardings=rsh.shardings_of(pub), version=1,
    )
    got = rsh.consume_device(
        "bench", "b0", "actor", 1, dev_pub.digest, pub
    )
    jax.block_until_ready(got)
    weight_sync_device_s = time.perf_counter() - t0
    rsh.clear_publication("bench", "b0", "actor")

    # Durable-spool overhead (host-only, no sockets): the per-trajectory
    # cost the rollout worker pays when durability is on — msgpack-frame
    # each bench trajectory the way ZmqPusher wires it, append (CRC +
    # fsync) to a SampleSpool, then ack the batch (watermark write + GC).
    # Reported per record so the number is workload-size independent;
    # gated by tools/bench_compare.py (docs/fault_tolerance.md §Data
    # durability).
    import shutil
    import tempfile

    from areal_tpu.system import streams
    from areal_tpu.system.sample_spool import SampleSpool

    frames = []
    off = 0
    for i, (p, g) in enumerate(zip(plens, glens)):
        ln = int(p + g)
        single = SequenceSample.from_default(
            ids=[f"b{i}"],
            data={
                "packed_input_ids": toks[off:off + ln],
                "prompt_mask": np.concatenate(
                    [np.ones(p, np.int32), np.zeros(g, np.int32)]),
                "packed_logprobs": lps[i],
                "rewards": rng.rand(1).astype(np.float32),
                "seq_no_eos_mask": np.zeros(1, np.float32),
            },
            seqlens=[ln],
        )
        frames.append(streams._pack(single.as_json_compatible()))
        off += ln
    spool_dir = tempfile.mkdtemp(prefix="bench_spool_")
    try:
        spool = SampleSpool(spool_dir)
        t0 = time.perf_counter()
        seqnos = [spool.append(raw) for raw in frames]
        spool_append_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        spool.ack(seqnos)
        spool_ack_s = time.perf_counter() - t0
        spool.close()
    finally:
        shutil.rmtree(spool_dir, ignore_errors=True)
    spool_append_ms = spool_append_s / len(frames) * 1e3
    spool_ack_ms = spool_ack_s / len(frames) * 1e3

    # Long-context ring-attention row (ISSUE 18): one attention layer's
    # fwd+bwd step time at long context under the active ring schedule
    # (zig-zag + causal-skip + double-buffered ppermute) vs the contiguous
    # v1 oracle (AREAL_RING_SCHEDULE=naive), on an sp=<all local chips>
    # ring. The skip ratio comes from the trace-time area counters
    # (parallel/ring.py), so it is structural — (n+1)/2n at sp=n — not a
    # timing artifact. On one chip the ring is degenerate (sp=1, both
    # schedules identical); the fields still emit so the BENCH trajectory
    # has the row, and `perf_probe ring-bench` sweeps the multi-shard
    # shapes on host devices. See docs/benchmarks.md for the method note.
    from areal_tpu.parallel import mesh as pmesh_mod
    from areal_tpu.parallel import ring as ring_mod

    ring_sp = n_chips
    ring_seq = 4096
    ring_mesh = pmesh_mod.make_mesh(pmesh_mod.ParallelSpec(sp=ring_sp))
    rngr = np.random.RandomState(0)
    rq = jnp.asarray(rngr.randn(1, ring_seq, cfg.n_q_heads, cfg.head_dim)
                     .astype(np.float32) * 0.1)
    rk = jnp.asarray(rngr.randn(1, ring_seq, cfg.n_kv_heads, cfg.head_dim)
                     .astype(np.float32) * 0.1)
    rv = jnp.asarray(rngr.randn(1, ring_seq, cfg.n_kv_heads, cfg.head_dim)
                     .astype(np.float32) * 0.1)
    rseg = jnp.ones((1, ring_seq), jnp.int32)

    def ring_step_time(schedule):
        def loss(q, k, v):
            o = ring_mod.ring_attention(q, k, v, rseg, ring_mesh,
                                        schedule=schedule)
            return jnp.sum(o * o)

        f = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        ring_mod.reset_ring_counters()
        jax.block_until_ready(f(rq, rk, rv))  # compile; fills counters
        ratio = ring_mod.ring_skip_ratio()
        reps = 5
        t0 = time.perf_counter()
        for _ in range(reps):
            g = f(rq, rk, rv)
        jax.block_until_ready(g)
        return (time.perf_counter() - t0) / reps * 1e3, ratio

    ring_sched = ring_mod.resolve_schedule(None, ring_seq, ring_sp)
    ring_step_ms, ring_skip = ring_step_time(ring_sched)
    ring_naive_step_ms, _ = ring_step_time("naive")

    # MoE dispatch row (ISSUE 19): one MoE layer's fwd+bwd step time under
    # the sort-based grouped compute path (default) vs the one-hot einsum
    # oracle (AREAL_MOE_DISPATCH=einsum), at E=8 experts on this host. The
    # headline PPO loop above stays DENSE — this row isolates the dispatch
    # method exactly like the ring row isolates the attention schedule;
    # `perf_probe moe-bench` sweeps (E, top_k, capacity_factor) shapes.
    # See docs/benchmarks.md for the method note.
    from areal_tpu.models import config as mcfg_mod
    from areal_tpu.models import moe as moe_mod

    moe_cfg = mcfg_mod.MoEConfig(
        num_experts=8, top_k=2, capacity_factor=2.0,
        routed_intermediate_dim=cfg.intermediate_dim,
    )
    moe_tcfg = dataclasses.replace(cfg, n_layers=1, moe=moe_cfg)
    moe_dim = cfg.hidden_dim
    moe_tokens = 4096
    stacked = moe_mod.init_moe_params(
        moe_tcfg, jax.random.PRNGKey(0), jnp.float32)
    moe_params = {k: v[0] for k, v in stacked.items()}  # layer 0 of 1
    mx = jnp.asarray(rngr.randn(8, moe_tokens // 8, moe_dim)
                     .astype(np.float32) * 0.1)

    def moe_step_time(dispatch):
        def loss(lp, x):
            y, _ = moe_mod.moe_mlp(x, lp, moe_cfg, dispatch=dispatch)
            return jnp.sum(y * y)

        f = jax.jit(jax.grad(loss))
        jax.block_until_ready(f(moe_params, mx))  # compile
        reps = 5
        t0 = time.perf_counter()
        for _ in range(reps):
            g = f(moe_params, mx)
        jax.block_until_ready(g)
        return (time.perf_counter() - t0) / reps * 1e3

    moe_step_ms = moe_step_time("grouped")
    moe_einsum_step_ms = moe_step_time("einsum")

    # Roofline context over the bf16 peak of one chip. The 6·N·T train
    # FLOPs estimate and the per-generation peak table live in
    # base/monitor.py — ONE accounting shared with the live trainer's
    # train/achieved_tflops + train/mfu gauges (system/goodput.py), so
    # the bench number and the live gauges can never drift apart.
    from areal_tpu.base import monitor

    # Activated params, not total: for MoE geometries only top_k of the
    # expert FFNs run per token, and 6·N·T over total params would claim
    # FLOPs that never execute (dense configs: identical to param_count).
    n_params = transformer.activated_param_count(cfg)
    flops = monitor.train_flops_6nt(n_params, steps * total)
    # None off the TPU (no peak, no MFU — never a zero); a TPU kind the
    # table lacks raises.
    peak = monitor.device_peak_flops(jax.devices()[0].device_kind)
    mfu = (flops / dt / n_chips / peak) if peak else None

    out = {
        "metric": "ppo_trained_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": None if mfu is None else round(mfu, 4),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": n_chips,
        "pack_fill": round(pack_fill, 4),
        "warmup_compile_s": round(warmup_compile_s, 3),
        "weight_sync_latency_s": round(weight_sync_s, 3),
        "weight_sync_io_s": round(weight_sync_io_s, 3),
        "weight_sync_transport_s": round(weight_sync_transport_s, 3),
        "weight_sync_device_s": round(weight_sync_device_s, 3),
        "spool_append_ms": round(spool_append_ms, 3),
        "spool_ack_ms": round(spool_ack_ms, 3),
        "ring_seq_len": ring_seq,
        "ring_sp": ring_sp,
        "ring_step_ms": round(ring_step_ms, 3),
        "ring_naive_step_ms": round(ring_naive_step_ms, 3),
        "ring_skip_ratio": round(ring_skip, 4),
        # Discontinuity key for the ring_* fields (bench_compare skips
        # them when the schedule method changes, like weight_sync_*).
        "ring_schedule_method": f"{ring_sched}-sp{ring_sp}",
        "moe_num_experts": moe_cfg.num_experts,
        "moe_top_k": moe_cfg.top_k,
        "moe_capacity_factor": moe_cfg.capacity_factor,
        "moe_step_ms": round(moe_step_ms, 3),
        "moe_einsum_step_ms": round(moe_einsum_step_ms, 3),
        # Discontinuity key for the moe_* fields (bench_compare skips
        # them when the dispatch method changes).
        "moe_dispatch_method": "grouped-vs-einsum",
        # METHOD CHANGE vs r6: the device transport (on-device reshard
        # publish + digest-gated consume) is measured ALONGSIDE the
        # streamed path — weight_sync_latency_s still names the streamed
        # number (r6 continuity), weight_sync_device_s is the new
        # transport. See docs/benchmarks.md for the discontinuity note.
        "weight_sync_transport_method": "streamed+device-measured",
    }
    if hbm_peak_gb is not None:
        out["hbm_peak_gb"] = round(hbm_peak_gb, 3)
    if train_phases is not None:
        # Phase fields are a measurement-method ADDITION (AREAL_TELEMETRY=1
        # runs only): phases sum to ~the per-step wall clock; the headline
        # tokens/s stays defined by the uninstrumented default run.
        out["train_phases"] = train_phases
    print(json.dumps(out))


if __name__ == "__main__":
    main()
