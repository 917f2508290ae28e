"""The main path's TPU kernel, compiled for the real chip without the chip.

The TPU compiler is installed in the sandbox and compiles for a DESCRIBED,
unattached v5e (on-chip-measurement guide §2.3): tiling alignment, the VMEM
limit and whether a kernel can be partitioned are what interpret mode never
checks. The kernel is asked for explicitly (``impl="pallas"``) — under
a described topology ``jax.default_backend()`` is still ``cpu``, so
``"auto"`` would take the reference branch and prove nothing. A compile that
passes is not a chip run.

libtpu admits ONE process at a time (``/tmp/libtpu_lockfile``), so the
compiles run in child processes — this file, executed as a script: with no
argument every case of ``_compile_all``, with ``lfm2`` / ``glm`` / ``kimi``
/ ``keye`` that cell's cut alone — and the pytest processes themselves never load libtpu.
Nothing here starts a child. ``tests/conftest.py`` owns the one mechanism
(``tests/compile_chain.py``):

- ``DESCRIBED_CHIP_CHILDREN`` there names the children: fixture name ->
  (command, time limit). A test takes the fixture (``compiled``,
  ``compiled_lfm2``, ``compiled_glm``, ``compiled_kimi``,
  ``compiled_keye``) and gets the JSON
  object its child printed last. A child for a new configuration is ONE entry in that table
  (and its ``_compile_<name>`` here): no new fixture, no test placed in
  another file to schedule it.
- The children a collection holds readers of are started at the run's
  START, by whichever xdist worker is first, as one detached chain that
  holds conftest's ``libtpu_lock`` for its length; the readers (and every
  other holder of that lock) are collected LAST, so by the time a worker
  reaches one, some ten minutes of chain have had the whole run to finish
  in and the case reads a file. ``pytest tests/test_tpu_compile.py -k
  lfm2`` starts that child alone; a run of an unrelated file starts none.
- Results live in the run's shared temp directory
  (``<basetemp>/../<name>.json``, written by rename; the child's output
  beside it as ``<name>.stdout`` / ``.stderr``, the chain's own lines in
  ``compile_chain.log``): ``returncode``, ``seconds`` and ``stderr`` of
  the child, and under ``results`` what it printed — with ``seconds`` a
  case, so which compile costs what needs no profiler. A child that fails
  or runs out of its time makes every reader of it FAIL with its stderr.

The whole 24-layer train step is compiled the same way by a scratch
script, not here (its set-up materialises 0.5B parameters).
"""

import functools
import json
import os
import re
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The windowed kernel at Mellum 2's geometry (32 query / 4 key-value heads
# of 128, window 1024): the benchmark's rows of 6016 (padded to 6144) and
# 6656 tokens, and the longest row a micro-batch can be.
WINDOW_T = {6016: 1, 6656: 1, 8192: 1}
# rows of the Trinity-Mini cell (padded 9216, 11776) and the longest a
# 16,384-token micro-batch can hold, at ITS window
WINDOW_2K_T = {8832: 1, 11776: 1, 16384: 1}
# The grouped-head kernel under a causal mask (a full layer) at
# Qwen2.5-0.5B's 14 query / 2 key-value heads of 64: train-long's longest
# row and train-packed's 8 x 512 grid; and at OLMoE's 16 / 16 heads of 128
# on the four-chip mesh, a row a chip.
CAUSAL_T = {7296: 1, 512: 8}
# Rows whose schedule is narrowed by their segment ids (every row of more
# than one block: the block mask is then a traced operand of the kernels,
# not a constant): train-long's and train-packed's other grids at Qwen's
# heads, and the phi4flash cell's F / X call — 40 query / 20 key-value
# heads, q and k of 64 under a value of 128 — through the dispatch.
NARROWED_T = {6016: 1, 3072: 1, 2688: 1}
WIDE_VALUE = ((1, 7424), (40, 20, 64, 128))
CAUSAL_MESH = ("e4", (4, 3968), (16, 16, 128))
# f2 is the async trainer's mesh in chip_smoke.py --chips 4; p2t2 nests the
# kernel's shard_map inside the pipeline stages' manual-pp region.
MESH_SPECS = ("f2", "p2t2")
# Mellum's two grids and OLMoE's largest, forward + backward without the
# head under the entry their remat plans give them: (configuration, mesh,
# grid), and what the commit before the row bound (9987316) compiled to by
# the same program — GB of temporaries, ``ragged-dot`` calls in the text.
# Mellum's is PERF.md §5's 1.80 GB; OLMoE's 4.625 GB there is the engine's
# own program, with the head and the gradient carry. ``attn_impl="pallas"``
# asks for every kernel a TPU process takes, the experts' grouped GEMM
# among them (models/moe.py: the bounded branch of a share's pass).
EXPERT_GRIDS = {
    "mellum": ("mellum2-12b-a2.5b", None, (1, 6656)),
    "mellum-6016": ("mellum2-12b-a2.5b", None, (1, 6016)),
    "olmoe": ("olmoe-1b-7b", "e4", (4, 3968)),
}
# Mellum's 1x6016, held since PR 35, stands against PR 35's parent
# (c1f5a93: 1.900 GB with the bound, 1.688 before it; 1.781 once the
# bounded pass adds its rows into their tokens), and its text's
# ``ragged-dot`` also counts mentions that are no calls (248 before the
# bound, 448 with it): the calls are held at 1x6656.
EXPERT_PARENT = {"mellum": (1.8047, 236), "mellum-6016": (1.9004, None),
                 "olmoe": (2.4754, 56)}
# The hybrid cell's packed rows (its three grids are 1 x these): ONE latent
# expert layer, forward + backward. The pass gathers its rows from the
# latent tokens [T, 1024], a source small enough for the compiler to hold
# in VMEM, and at [3456, 1024] its in-VMEM gather ran out of scoped vmem
# (the layer compiled at every other multiple of 128 up to 4096); the
# source is therefore padded to whole row tiles (moe._whole_row_tiles),
# with which every multiple of 128 up to 4096 compiles (PERF.md §6, PR 34).
LATENT_T = (3072, 3456, 3712)
# One expert layer, forward + backward, whose bounded pass adds only its
# live rows (moe._add_live: a loop with a traced trip count) at rows no
# whole program above holds: the Trinity cell's two grids, and the
# [12288, 2304] source at which XLA's row gather of a bound ran out of
# scoped vmem before (ROADMAP S10 (g)).
WALK_LAYERS = {"trinity-8832": ("trinity-mini", 8832),
               "trinity-11776": ("trinity-mini", 11776),
               "mellum-12288": ("mellum2-12b-a2.5b", 12288)}
# The phi4flash (SambaY) cell: its selective scan (rows, length, d_inner,
# states) and its packed grid, at the published widths.
SAMBAY_SCAN = (1, 8192, 5120, 16)
SAMBAY_GRID = (1, 8192)
# The Mamba-2 scans of the Granite and the Nemotron cell: (rows, length —
# no multiple of Granite's chunk —, heads, head_dim, groups, states, chunk).
# The Qwen3-Next cell: its attention block's call at 16 query / 2
# key-value heads of 256 (rows of the cell's two grids) and its grad
# program's packed grid, at the published widths.
WIDE_HEAD_T = (14336, 8704)
# ... and the GLM-4.7-Flash cell's latent attention at the kernel: 20 / 20
# heads of 256 at the rows of its two grids. {case: (row, query heads,
# key/value heads, the program's temporaries at most)} — q, k, v and
# their gradients padded to the blocks and the outputs' layouts: 0.24-0.34
# GB, 0.90 GB where 13,440 pads to 14,336 at 20 heads
WIDE_HEADS = {**{f"causal-256-{T}": (T, 16, 2, 0.6e9) for T in WIDE_HEAD_T},
              "causal-256-glm-14336": (14336, 20, 20, 0.6e9),
              "causal-256-glm-13440": (13440, 20, 20, 1.0e9)}
QNEXT_GRID = (1, 16384)
# ... and its gated delta rule alone, forward + backward: (rows, length,
# key heads, value heads, head size, chunk), bfloat16 as the cell times it
# and float32 as its ``rule_error`` calls it.
GDN_RULE = (1, 16384, 16, 32, 128, 64)
# ... at the grad program's row and at the cell's own two; at those two the
# backward kernel ALONE too, with the mixer's norms inside as the cell runs
# it: its program's temporaries and its body's size.
GDN_RULE_T = (16384, 14336, 8704)
# The LFM2-MoE cell's cut (configs/lfm2-24b-a2b.json): its grad program's
# largest micro-batch — the largest grid the packer makes of the cell's
# traffic (traffic/train-toolcall-16k.json, ``compile_grid``) — at the
# published widths. A child of its own (``compiled_lfm2`` in conftest's
# ``DESCRIBED_CHIP_CHILDREN``): a failure here costs no other case.
LFM2_GRID = (2, 7168)
# The GLM-4.7-Flash cell's cut (configs/glm-4.7-flash.json) likewise: its
# largest grid is one row (traffic/train-swe-agent-16k.json); child
# ``compiled_glm``.
GLM_GRID = (1, 14336)
# The Kimi-Linear cell's cut (configs/kimi-linear-48b-a3b.json) likewise,
# on the grid of its traffic whose grad program needs most (two rows of
# 7,552, 15,104 tokens of at most 16,384): child ``kimi``, fixture
# ``compiled_kimi``.
KIMI_GRID = (2, 7552)
# The Keye-VL-2.0 cell's cut (configs/keye-vl-2.0-30b-a3b.json) likewise,
# on the grid of its traffic that holds most tokens (two rows of 7,808):
# child ``keye``, fixture ``compiled_keye``.
KEYE_GRID = (2, 7808)
SSD_SCANS = {"ssd-scan-granite": (1, 7040, 32, 64, 1, 128, 256),
             "ssd-scan-nemotron": (1, 4096, 16, 64, 1, 128, 128)}


def _compile_all():
    """Child process: every case against a described v5e:2x2."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from areal_tpu.models import transformer
    from areal_tpu.models.config import tiny_config
    from areal_tpu.ops.pallas import window_attention as wa
    from areal_tpu.parallel import mesh as pmesh
    from areal_tpu.parallel import sharding as psh

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this image
        return {"skip": f"cannot describe a v5e:2x2 topology here: {e}"}
    # Such a compile is written to the persistent cache but cannot be read
    # back without the chip: keep the cache out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    out = {}
    # how often a call's schedule was narrowed while a case was traced
    narrowed = []
    narrow = wa._narrowed
    wa._narrowed = lambda *a: narrowed.append(1) or narrow(*a)

    last = time.monotonic()

    def lap():
        """Seconds since the last call: what the case that ends here took,
        its tracing and lowering included."""
        nonlocal last
        was, last = last, time.monotonic()
        return round(last - was, 2)

    def record(name, compiled):
        text = compiled.as_text()
        out[name] = {
            "seconds": lap(),
            "narrowed": len(narrowed),
            "custom_calls": text.count("tpu_custom_call"),
            "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
            # kernel INSTANCES by the name the device's op line shows (what
            # benchmark/moe_trace.py counts as the experts' GEMMs): the
            # Pallas grouped GEMM, and XLA's own ragged dot
            "gmm_calls": len(re.findall(
                r"%t?gmm[.\d]* = [^\n]*custom-call\(", text)),
            "ragged_dot_calls": len(re.findall(
                r"%ragged-dot-none[.\d]* = [^\n]*custom-call\(", text)),
        }
        narrowed.clear()

    chip = SingleDeviceSharding(topo.devices[0])
    # The windowed kernel, forward AND backward, K/V at their 4 heads.
    for T, rows, window in [(T, r, 1024) for T, r in WINDOW_T.items()] + [
            (T, r, 2048) for T, r in WINDOW_2K_T.items()]:
        def spec(*shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

        def loss(q, k, v, seg, window=window):
            o = wa.window_attention(q, k, v, seg, seg, window=window)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        compiled = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2))
        ).lower(
            spec(rows, T, 32, 128), spec(rows, T, 4, 128),
            spec(rows, T, 4, 128), spec(rows, T, dtype=jnp.int32),
        ).compile()
        name = f"window-{T}" if window == 1024 else f"window{window}-{T}"
        record(name, compiled)
        out[name]["splash_kernels"] = sorted(
            {k for k in ("splash_mqa_fwd", "splash_mqa_dkv", "splash_mqa_dq")
             if k in compiled.as_text()})
        out[name]["tile"] = wa.pick_tile(T, window)

    # The same kernel under a causal mask, K/V at their 2 heads.
    splash_names = ("splash_mqa_fwd", "splash_mqa_dkv", "splash_mqa_dq")
    for T, rows in {**CAUSAL_T, **NARROWED_T}.items():
        def spec(*shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

        def loss(q, k, v, seg):
            o = wa.window_attention(q, k, v, seg, seg)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        compiled = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2))
        ).lower(
            spec(rows, T, 14, 64), spec(rows, T, 2, 64),
            spec(rows, T, 2, 64), spec(rows, T, dtype=jnp.int32),
        ).compile()
        record(f"causal-{T}", compiled)
        out[f"causal-{T}"].update(
            splash_kernels=sorted(
                k for k in splash_names if k in compiled.as_text()),
            flash_kernels="flash_attention" in compiled.as_text(),
            tile=wa.pick_tile(T), padded=wa.padded_len(T))

    # ... and through the dispatch on the four-chip mesh: the kernel in a
    # shard_map, a row a chip.
    from areal_tpu.ops import attention as attn_ops

    mesh_spec, (rows, T), (hq, hkv, dh) = CAUSAL_MESH
    mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse(mesh_spec),
                           devices=list(topo.devices))
    by_row = NamedSharding(mesh, P(pmesh.DATA_AXES))

    def mesh_loss(q, k, v, seg):
        o = attn_ops.packed_attention(q, k, v, seg, seg, impl="pallas")
        return jnp.sum(o.astype(jnp.float32) ** 2)

    with psh.activation_sharding(mesh), attn_ops.dispatch_label(
            "causal-mesh"):
        compiled = jax.jit(
            jax.value_and_grad(mesh_loss, argnums=(0, 1, 2))).lower(*(
                jax.ShapeDtypeStruct((rows, T, h, dh), jnp.bfloat16,
                                     sharding=by_row)
                for h in (hq, hkv, hkv)),
                jax.ShapeDtypeStruct((rows, T), jnp.int32, sharding=by_row),
            ).compile()
    record("causal-mesh", compiled)
    out["causal-mesh"].update(
        splash_kernels=sorted(
            k for k in splash_names if k in compiled.as_text()),
        dispatch=attn_ops.dispatch_counts()["causal-mesh"])

    # A value wider than q and k on a row with blocks to skip.
    (rows, T), (hq, hkv, dh, dv) = WIDE_VALUE

    def wide_loss(q, k, v, seg):
        o = attn_ops.packed_attention(q, k, v, seg, seg, impl="pallas")
        return jnp.sum(o.astype(jnp.float32) ** 2)

    compiled = jax.jit(
        jax.value_and_grad(wide_loss, argnums=(0, 1, 2))).lower(*(
            jax.ShapeDtypeStruct((rows, T, h, d), jnp.bfloat16, sharding=chip)
            for h, d in ((hq, dh), (hkv, dh), (hkv, dv))),
            jax.ShapeDtypeStruct((rows, T), jnp.int32, sharding=chip),
        ).compile()
    record("causal-wide-value", compiled)
    out["causal-wide-value"].update(splash_kernels=sorted(
        k for k in splash_names if k in compiled.as_text()))

    # A small model through transformer.forward on multi-chip meshes.
    cfg = tiny_config(vocab_size=1024, n_layers=4, hidden_dim=256,
                      n_q_heads=4, n_kv_heads=2)
    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    for mesh_spec in MESH_SPECS:
        mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse(mesh_spec),
                               devices=list(topo.devices))
        params = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16,
                                              sharding=s),
            shapes, psh.named_shardings(mesh, psh.param_partition_specs(cfg)),
        )
        tok = jax.ShapeDtypeStruct((8, 512), jnp.int32,
                                   sharding=NamedSharding(mesh, P()))

        def loss_and_grad(p, tokens, pos, seg):
            def loss(p):
                y, _ = transformer.forward(
                    p, cfg, tokens, pos, segment_ids=seg,
                    attn_impl="pallas", return_kv=False,
                )
                return jnp.sum(y.astype(jnp.float32) ** 2)

            return jax.value_and_grad(loss)(p)

        with psh.activation_sharding(mesh):
            record(f"mesh-{mesh_spec}",
                   jax.jit(loss_and_grad).lower(params, tok, tok, tok)
                   .compile())

    # The same under a layer pattern on the data-parallel mesh: the windowed
    # call sits in the same shard_map as the causal one.
    import contextlib
    import dataclasses

    pattern = dataclasses.replace(
        cfg, sliding_window=256,
        layer_types=("sliding", "sliding", "sliding", "full"))
    mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse("f2"),
                           devices=list(topo.devices))
    params = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16, sharding=s),
        shapes, psh.named_shardings(mesh, psh.param_partition_specs(pattern)))
    tok = jax.ShapeDtypeStruct((8, 512), jnp.int32,
                               sharding=NamedSharding(mesh, P()))

    def pattern_loss_and_grad(p, tokens, pos, seg):
        def loss(p):
            y, _ = transformer.forward(
                p, pattern, tokens, pos, segment_ids=seg,
                attn_impl="pallas", return_kv=False)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        return jax.value_and_grad(loss)(p)

    with psh.activation_sharding(mesh):
        record("mesh-f2-pattern", jax.jit(pattern_loss_and_grad).lower(
            params, tok, tok, tok).compile())

    # What the backward pass re-runs (transformer.REMAT_ENTRIES): the
    # layer scan's body appears once in the program text, so the kernels
    # it holds are the kernels of one layer.
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16, sharding=chip),
        shapes)
    tok = jax.ShapeDtypeStruct((2, 512), jnp.int32, sharding=chip)
    for entry in transformer.REMAT_ENTRIES:
        def loss_and_grad(p, tokens, pos, seg, entry=entry):
            def loss(p):
                y, _ = transformer.forward(
                    p, cfg, tokens, pos, segment_ids=seg,
                    attn_impl="pallas", remat=entry, return_kv=False,
                )
                return jnp.sum(y.astype(jnp.float32) ** 2)

            return jax.value_and_grad(loss)(p)

        record(f"remat-{entry}",
               jax.jit(loss_and_grad).lower(params, tok, tok, tok).compile())

    # The expert layer at the benchmark's real sizes: on Mellum's share the
    # sorted pass is bounded (models/moe.sorted_rows), so the program holds
    # it at two row counts; under expert parallelism it is not.
    from benchmark import weights

    for name, (config, mesh_spec, grid) in EXPERT_GRIDS.items():
        with open(os.path.join(REPO, "benchmark", "configs",
                               config + ".json")) as f:
            moe_cfg = weights.model_config(json.load(f))
        shapes = jax.eval_shape(
            lambda: transformer.init_params(moe_cfg, jax.random.PRNGKey(0)))
        if mesh_spec is None:
            on_mesh = contextlib.nullcontext()
            p_shard, t_shard = jax.tree.map(lambda _: chip, shapes), chip
        else:
            mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse(mesh_spec),
                                   devices=list(topo.devices))
            on_mesh = psh.activation_sharding(mesh)
            p_shard = psh.named_shardings(
                mesh, psh.param_partition_specs(moe_cfg))
            t_shard = NamedSharding(mesh, P(pmesh.DATA_AXES))
        params = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16,
                                              sharding=s), shapes, p_shard)
        tok = jax.ShapeDtypeStruct(grid, jnp.int32, sharding=t_shard)

        def expert_loss_and_grad(p, tokens, pos, seg, moe_cfg=moe_cfg):
            def loss(p):
                y, _, aux = transformer.forward(
                    p, moe_cfg, tokens, pos, segment_ids=seg,
                    attn_impl="pallas", remat="matmuls", return_kv=False,
                    return_hidden=True, return_aux=True)
                return (jnp.sum(y.astype(jnp.float32) ** 2)
                        + aux["aux_total"]), aux

            return jax.value_and_grad(loss, has_aux=True)(p)

        with on_mesh:
            compiled = jax.jit(expert_loss_and_grad).lower(
                params, tok, tok, tok).compile()
        record(f"experts-{name}", compiled)
        out[f"experts-{name}"]["ragged_dot"] = compiled.as_text().count(
            "ragged-dot")
        out[f"experts-{name}"]["conditionals"] = compiled.as_text().count(
            " conditional(")
    from areal_tpu.models import moe as moemod

    # {"rows x K x N/groups": "gmm" | "ragged_dot"} as those programs traced
    out["gemms"] = {"%dx%dx%d/%d" % key: how
                    for key, how in moemod.gemm_counts().items()}

    # One latent expert layer of the hybrid configuration at the cell's
    # rows, asked for the kernels as a TPU process asks.
    with open(os.path.join(REPO, "benchmark", "configs",
                           "nemotron-3-super-120b-a12b.json")) as f:
        hybrid = weights.model_config(json.load(f))
    lp = {k: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)
          for k, shape in moemod.moe_param_shapes(hybrid).items()}

    def layer_grad(lp, x, valid, cfg=hybrid):
        def loss(lp, x):
            y, _ = moemod.moe_mlp(x, lp, cfg.moe, mask=valid > 0,
                                  impl="pallas")
            return jnp.sum(y.astype(jnp.float32) ** 2)

        return jax.grad(jax.checkpoint(loss), argnums=(0, 1))(lp, x)

    for T in LATENT_T:
        x = jax.ShapeDtypeStruct((1, T, hybrid.hidden_dim), jnp.bfloat16,
                                 sharding=chip)
        valid = jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=chip)
        record(f"latent-{T}",
               jax.jit(layer_grad).lower(lp, x, valid).compile())
        M = T * hybrid.moe.top_k
        forward = jax.jit(lambda lp, x, valid: moemod.moe_mlp(
            x, lp, hybrid.moe, mask=valid > 0, impl="pallas")[0]).lower(
                lp, x, valid).as_text().splitlines()
        out[f"latent-{T}"].update(
            rows=moemod.sorted_rows(M, hybrid.moe.num_experts,
                                    hybrid.moe.n_routed),
            # in ONE forward pass of the layer: the sorts of all M entries
            # (jnp.argsort lowers to a call of a private function) and the
            # gathers of M rows at the latent width
            entry_sorts=sum("call @argsort" in line
                            and f"(tensor<{M}xi32>)" in line
                            for line in forward),
            entry_gathers=sum(
                "stablehlo.gather" in line and "-> tensor<%dx%dx" % (
                    M, hybrid.moe.latent_dim) in line for line in forward))
    out["latent-gemms"] = {"%dx%dx%d/%d" % key: how
                           for key, how in moemod.gemm_counts().items()
                           if key[-1] == hybrid.moe.num_experts}

    for name, (config, T) in WALK_LAYERS.items():
        with open(os.path.join(REPO, "benchmark", "configs",
                               config + ".json")) as f:
            cfg = weights.model_config(json.load(f))
        lp = {k: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)
              for k, shape in moemod.moe_param_shapes(cfg).items()}
        x = jax.ShapeDtypeStruct((1, T, cfg.hidden_dim), jnp.bfloat16,
                                 sharding=chip)
        valid = jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=chip)
        compiled = jax.jit(functools.partial(layer_grad, cfg=cfg)).lower(
            lp, x, valid).compile()
        record(f"walk-{name}", compiled)
        out[f"walk-{name}"].update(
            rows=moemod.sorted_rows(T * cfg.moe.top_k, cfg.moe.num_experts,
                                    cfg.moe.n_routed),
            whiles=len(re.findall(r" while\(", compiled.as_text())))

    # The phi4flash (SambaY) cell at the published widths: the selective
    # scan's kernels alone, forward + backward, and the whole six-layer
    # cut forward + backward (no head) at the cell's grid.
    from areal_tpu.models import ssm as ssmmod

    def f32(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def scan_loss(x, dt, A, Bm, Cm, Dk, seg):
        return jnp.sum(ssmmod.selective_scan(x, dt, A, Bm, Cm, Dk, seg,
                                             "pallas") ** 2)

    # The Mamba-2 (SSD) scan's kernels alone, forward + backward, at the
    # two cells' rows: (rows, length, heads, head_dim, groups, states,
    # chunk), bfloat16.
    def ssd_loss(x, dt, A, Bm, Cm, seg, chunk):
        return jnp.sum(ssmmod.ssd_scan(x, dt, A, Bm, Cm, seg, chunk,
                                       "pallas") ** 2)

    for name, (R, T, H, P, G, N, Q) in SSD_SCANS.items():
        bf = jnp.bfloat16
        record(name, jax.jit(
            jax.value_and_grad(ssd_loss, argnums=(0, 1, 2, 3, 4)),
            static_argnums=6).lower(
                f32(R, T, H, P, dtype=bf), f32(R, T, H), f32(H),
                f32(R, T, G, N, dtype=bf), f32(R, T, G, N, dtype=bf),
                f32(R, T, dtype=jnp.int32), Q).compile())

    R, T, Dn, N = SAMBAY_SCAN
    record("s6-scan", jax.jit(
        jax.value_and_grad(scan_loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
            f32(R, T, Dn, dtype=jnp.bfloat16), f32(R, T, Dn), f32(Dn, N),
            f32(R, T, N, dtype=jnp.bfloat16), f32(R, T, N, dtype=jnp.bfloat16),
            f32(Dn), f32(R, T, dtype=jnp.int32)).compile())
    with open(os.path.join(REPO, "benchmark", "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        sambay = weights.model_config(json.load(f))
    shapes = jax.eval_shape(
        lambda: transformer.init_params(sambay, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, jnp.bfloat16, sharding=chip), shapes)
    tok = jax.ShapeDtypeStruct(SAMBAY_GRID, jnp.int32, sharding=chip)

    def sambay_grad(p, tokens, pos, seg):
        def loss(p):
            y, _ = transformer.forward(
                p, sambay, tokens, pos, segment_ids=seg, attn_impl="pallas",
                remat="full", return_kv=False, return_hidden=True)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        return jax.value_and_grad(loss)(p)

    scans = sum(ssmmod.s6_geometry_counts().values())
    record("sambay-cell", jax.jit(sambay_grad).lower(
        params, tok, tok, tok).compile())
    out["sambay-cell"]["scans_traced"] = sum(
        ssmmod.s6_geometry_counts().values()) - scans

    # The causal kernel at heads of 256, forward and backward, at the
    # blocks the wide-head table picks for the shape.
    for name, (T, hq, hkv, _) in WIDE_HEADS.items():
        def wide_loss(q, k, v, seg):
            o = wa.window_attention(q, k, v, seg, seg)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        def wide(*shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

        with attn_ops.dispatch_label(name):
            compiled = jax.jit(
                jax.value_and_grad(wide_loss, argnums=(0, 1, 2))).lower(
                    wide(1, T, hq, 256), wide(1, T, hkv, 256),
                    wide(1, T, hkv, 256),
                    wide(1, T, dtype=jnp.int32)).compile()
        record(name, compiled)
        # the blocks the traced call ran, by the kernel's own counter
        (_, _, blocks), = wa.causal_geometry_counts()[name]
        out[name].update(
            splash_kernels=sorted(
                k for k in splash_names if k in compiled.as_text()),
            blocks=blocks.label())

    # The Qwen3-Next cell's cut (configs/qwen3-next-80b-a3b.json): the
    # whole model's forward + backward on a 1 x 16,384 row under full remat.
    from areal_tpu.models import gdn as gdnmod

    with open(os.path.join(REPO, "benchmark", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        qnext = weights.model_config(json.load(f))
    shapes = jax.eval_shape(
        lambda: transformer.init_params(qnext, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, jnp.bfloat16, sharding=chip), shapes)
    tok = jax.ShapeDtypeStruct(QNEXT_GRID, jnp.int32, sharding=chip)

    def qnext_grad(p, tokens, pos, seg):
        def loss(p):
            y, _ = transformer.forward(
                p, qnext, tokens, pos, segment_ids=seg, attn_impl="pallas",
                remat="full", return_kv=False, return_hidden=True)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        return jax.value_and_grad(loss)(p)

    def rule_loss(q, k, v, g, beta, seg, chunk):
        return jnp.sum(gdnmod.gated_delta_rule(q, k, v, g, beta, seg, chunk,
                                               "pallas") ** 2)

    from areal_tpu.ops.pallas import gated_delta_rule as rule_kernel

    def rule_bwd_alone(q, k, v, g, beta, seg, states, dy, z, w):
        return rule_kernel.rule_bwd(q, k, v, g, beta, seg, states, dy, Q,
                                    norms=(z, w, 1e-6, 1e-6))

    def body_eqns(jaxpr, inside=False):
        """Equations of the Pallas kernels' bodies in ``jaxpr``, nested
        ones counted: what every program that holds the kernel traces and
        lowers again."""
        n = 0
        for eqn in jaxpr.eqns:
            kernel = inside or eqn.primitive.name == "pallas_call"
            n += inside
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += body_eqns(sub, kernel)
        return n

    R, _, G, H, D, Q = GDN_RULE
    for dt in (jnp.bfloat16, jnp.float32):
        for T in GDN_RULE_T:
            name = f"gdn-rule-{jnp.dtype(dt).name}-{T}"
            rule = (f32(R, T, G, D, dtype=dt), f32(R, T, G, D, dtype=dt),
                    f32(R, T, H, D, dtype=dt), f32(R, T, H), f32(R, T, H),
                    f32(R, T, dtype=jnp.int32))
            record(name, jax.jit(
                jax.value_and_grad(rule_loss, argnums=(0, 1, 2, 3, 4)),
                static_argnums=6).lower(*rule, Q).compile())
            if T == GDN_RULE[1]:
                continue
            alone = rule + (
                f32(R, T // Q, G, D, H // G * D, dtype=dt),
                f32(R, T, H * D, dtype=dt), f32(R, T, H * D, dtype=dt),
                f32(D, dtype=dt))
            bwd = jax.jit(rule_bwd_alone).lower(*alone).compile()
            out[name].update(
                bwd_seconds=lap(),
                bwd_custom_calls=bwd.as_text().count("tpu_custom_call"),
                bwd_temp_bytes=bwd.memory_analysis().temp_size_in_bytes,
                bwd_body_eqns=body_eqns(
                    jax.make_jaxpr(rule_bwd_alone)(*alone).jaxpr))

    rules = sum(gdnmod.geometry_counts().values())
    impls = dict(gdnmod.rule_impl_counts())
    norms = gdnmod.mixer_norm_counts()
    cell = jax.jit(qnext_grad).lower(params, tok, tok, tok).compile()
    record("qnext-cell", cell)
    out["qnext-cell"].update(
        rules_traced=sum(gdnmod.geometry_counts().values()) - rules,
        rule_impl={k: n - impls.get(k, 0)
                   for k, n in gdnmod.rule_impl_counts().items()
                   if n - impls.get(k, 0)},
        rule_kernels=[n for n in ("gdn_rule_fwd", "gdn_rule_bwd")
                      if n in cell.as_text()],
        mixer_norms={k: n - norms.get(k, 0)
                     for k, n in gdnmod.mixer_norm_counts().items()
                     if n - norms.get(k, 0)},
        param_bytes=18 * transformer.param_count(qnext))
    return out


def _compile_lfm2():
    """Child process: the LFM2-MoE cell's cut, the whole model's forward +
    backward on its largest grid under full remat, against a described
    v5e:2x2."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from areal_tpu.models import shortconv, transformer
    from benchmark import weights

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this image
        return {"skip": f"cannot describe a v5e:2x2 topology here: {e}"}
    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(REPO, "benchmark", "configs",
                           "lfm2-24b-a2b.json")) as f:
        lfm2 = weights.model_config(json.load(f))
    shapes = jax.eval_shape(
        lambda: transformer.init_params(lfm2, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, jnp.bfloat16, sharding=chip), shapes)
    tok = jax.ShapeDtypeStruct(LFM2_GRID, jnp.int32, sharding=chip)

    def lfm2_grad(p, tokens, pos, seg):
        def loss(p):
            y, _ = transformer.forward(
                p, lfm2, tokens, pos, segment_ids=seg, attn_impl="pallas",
                remat="full", return_kv=False, return_hidden=True)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        return jax.value_and_grad(loss)(p)

    began = time.monotonic()
    got = jax.jit(lfm2_grad).lower(params, tok, tok, tok).compile()
    return {
        "seconds": round(time.monotonic() - began, 2),
        "custom_calls": got.as_text().count("tpu_custom_call"),
        "temp_bytes": got.memory_analysis().temp_size_in_bytes,
        "convs_traced": sum(shortconv.geometry_counts().values()),
        "param_bytes": 18 * transformer.param_count(lfm2)}


def _compile_glm():
    """Child process: the GLM-4.7-Flash cell's cut, the whole model's
    forward + backward on its largest grid under full remat, against a
    described v5e:2x2."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from areal_tpu.models import mla, transformer
    from benchmark import weights

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this image
        return {"skip": f"cannot describe a v5e:2x2 topology here: {e}"}
    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(REPO, "benchmark", "configs",
                           "glm-4.7-flash.json")) as f:
        glm = weights.model_config(json.load(f))
    shapes = jax.eval_shape(
        lambda: transformer.init_params(glm, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, jnp.bfloat16, sharding=chip), shapes)
    tok = jax.ShapeDtypeStruct(GLM_GRID, jnp.int32, sharding=chip)

    def glm_grad(p, tokens, pos, seg):
        def loss(p):
            y, _ = transformer.forward(
                p, glm, tokens, pos, segment_ids=seg, attn_impl="pallas",
                remat="full", return_kv=False, return_hidden=True)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        return jax.value_and_grad(loss)(p)

    began = time.monotonic()
    got = jax.jit(glm_grad).lower(params, tok, tok, tok).compile()
    return {
        "seconds": round(time.monotonic() - began, 2),
        "custom_calls": got.as_text().count("tpu_custom_call"),
        "temp_bytes": got.memory_analysis().temp_size_in_bytes,
        "assemblies_traced": {"%dx%d/h%d/q%dkv%d/%d+%d/v%d" % g: n
                              for g, n in mla.geometry_counts().items()},
        "param_bytes": 18 * transformer.param_count(glm)}


def _compile_kimi():
    """Child process: the Kimi-Linear cell's cut, the whole model's forward
    + backward on its largest grid under full remat, against a described
    v5e:2x2."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from areal_tpu.models import kda, mla, transformer
    from areal_tpu.ops.pallas import kda_rule
    from areal_tpu.ops.pallas import window_attention as wa
    from benchmark import weights

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this image
        return {"skip": f"cannot describe a v5e:2x2 topology here: {e}"}
    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(REPO, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        kimi = weights.model_config(json.load(f))
    shapes = jax.eval_shape(
        lambda: transformer.init_params(kimi, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, jnp.bfloat16, sharding=chip), shapes)
    tok = jax.ShapeDtypeStruct(KIMI_GRID, jnp.int32, sharding=chip)

    def kimi_grad(p, tokens, pos, seg):
        def loss(p):
            y, _ = transformer.forward(
                p, kimi, tokens, pos, segment_ids=seg, attn_impl="pallas",
                remat="full", return_kv=False, return_hidden=True)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        return jax.value_and_grad(loss)(p)

    began = time.monotonic()
    got = jax.jit(kimi_grad).lower(params, tok, tok, tok).compile()
    text = got.as_text()
    (widths,) = {w for by in wa.head_width_counts().values()
                 for w in by.values()}
    return {
        "seconds": round(time.monotonic() - began, 2),
        "custom_calls": text.count("tpu_custom_call"),
        "temp_bytes": got.memory_analysis().temp_size_in_bytes,
        "rule_kernels": [n for n in ("kda_rule_fwd", "kda_rule_bwd")
                         if n in text],
        "rule_impl": kda.rule_impl_counts(),
        "mixer_norms": kda.mixer_norm_counts(),
        "rule_steps": ["%dx%d/h%d/fwd%d/bwd%d" % g
                       for g in kda_rule.step_counts()],
        "rules_traced": {"%dx%d/%d/h%d/%d/r%d" % g: n
                         for g, n in kda.geometry_counts().items()},
        "assemblies_traced": {"%dx%d/h%d/q%dkv%d/%d+%d/v%d" % g: n
                              for g, n in mla.geometry_counts().items()},
        "head_widths": list(widths),
        "param_bytes": 18 * transformer.param_count(kimi)}


def _compile_keye():
    """Child process: the Keye-VL-2.0 cell's cut, the whole model's forward
    + backward on its fullest grid under full remat, against a described
    v5e:2x2."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from areal_tpu.models import dsa, transformer
    from areal_tpu.ops import attention
    from benchmark import weights

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this image
        return {"skip": f"cannot describe a v5e:2x2 topology here: {e}"}
    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(REPO, "benchmark", "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        keye = weights.model_config(json.load(f))
    shapes = jax.eval_shape(
        lambda: transformer.init_params(keye, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, jnp.bfloat16, sharding=chip), shapes)
    tok = jax.ShapeDtypeStruct(KEYE_GRID, jnp.int32, sharding=chip)

    def keye_grad(p, tokens, pos, seg):
        def loss(p):
            y, _ = transformer.forward(
                p, keye, tokens, pos, segment_ids=seg, attn_impl="pallas",
                remat="full", return_kv=False, return_hidden=True)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        return jax.value_and_grad(loss)(p)

    began = time.monotonic()
    with attention.dispatch_label("keye"):
        got = jax.jit(keye_grad).lower(params, tok, tok, tok).compile()
    text = got.as_text()
    return {
        "seconds": round(time.monotonic() - began, 2),
        "custom_calls": text.count("tpu_custom_call"),
        "temp_bytes": got.memory_analysis().temp_size_in_bytes,
        "kernels": [n for n in ("dsa_select", "dsa_attend_fwd",
                                "dsa_attend_dq", "dsa_attend_dkv")
                    if n in text],
        "dispatch": attention.dispatch_counts().get("keye"),
        "impl": dsa.impl_counts(),
        "geometry": ["%d/%d/q%dkv%d/k%d" % g for g in dsa.geometry_counts()],
        "param_bytes": 18 * transformer.param_count(keye)}


@pytest.mark.parametrize("T", WINDOW_T)
def test_window_attention_compiles_for_v5e(compiled, T):
    """The windowed kernel's forward, dKV and dQ at the published heads
    and window, at the measured tile, inside the VMEM/HBM limits."""
    got = compiled[f"window-{T}"]
    assert got["tile"] == 512
    assert got["splash_kernels"] == ["splash_mqa_dkv", "splash_mqa_dq",
                                     "splash_mqa_fwd"]
    assert got["temp_bytes"] < 2 << 30


@pytest.mark.parametrize("T", CAUSAL_T)
def test_causal_attention_compiles_for_v5e(compiled, T):
    """A full layer's call at Qwen2.5-0.5B's heads: the grouped-head
    kernel's forward and its fused backward — and no flash kernel — at
    the tile the causal rule picks, inside the VMEM/HBM limits."""
    got = compiled[f"causal-{T}"]
    assert got["splash_kernels"] == ["splash_mqa_dkv", "splash_mqa_fwd"]
    assert (got["tile"], got["padded"]) == {7296: (1024, 8192),
                                            512: (512, 512)}[T]
    assert not got["flash_kernels"]
    assert got["padded"] % got["tile"] == 0 and got["padded"] >= T
    assert got["temp_bytes"] < 2 << 30


@pytest.mark.parametrize("T", NARROWED_T)
def test_a_narrowed_causal_schedule_compiles_for_v5e(compiled, T):
    """Rows of more than one block take their block mask from the row's
    segment ids — a traced operand of the forward and the fused backward
    — at the cells' other Qwen grids."""
    got = compiled[f"causal-{T}"]
    assert got["narrowed"] > 0
    assert got["splash_kernels"] == ["splash_mqa_dkv", "splash_mqa_fwd"]
    assert (got["tile"], got["padded"]) == {
        6016: (1024, 6144), 3072: (1024, 3072), 2688: (1024, 3072)}[T]
    assert got["temp_bytes"] < 2 << 30


@pytest.mark.parametrize("name,narrowed", [
    ("causal-7296", True), ("causal-512", False), ("window-6656", True),
    ("window2048-11776", True), ("causal-mesh", True),
    ("causal-wide-value", True)])
def test_which_compiled_schedules_are_narrowed(compiled, name, narrowed):
    """The narrowing is built where a row has more than one block — the
    windowed call's three kernels, the mesh's row a chip and the wide
    value's call among them — and not for the 8 x 512 grid."""
    got = compiled[name]
    assert (got["narrowed"] > 0) == narrowed
    assert "splash_mqa_fwd" in got["splash_kernels"]
    assert got["temp_bytes"] < 2 << 30


def test_causal_attention_compiles_on_the_v5e_mesh_at_olmoes_heads(compiled):
    """16 query / 16 key-value heads of 128 (groups of one), a row a chip
    of the 2 x 2 mesh, through ``packed_attention``'s dispatch."""
    got = compiled["causal-mesh"]
    assert got["dispatch"] == {"pallas": 1}
    assert got["splash_kernels"] == ["splash_mqa_dkv", "splash_mqa_fwd"]
    assert got["temp_bytes"] < 2 << 30


@pytest.mark.parametrize("T", WINDOW_2K_T)
def test_window_attention_at_window_2048_compiles_for_v5e(compiled, T):
    """The same kernel at window 2048 in rows up to 16,384 tokens: the
    tile the rule picks (512: the sweep at that window, PERF.md §5, PR 39)
    fits the chip's fast memory, forward and backward."""
    got = compiled[f"window2048-{T}"]
    assert got["tile"] == 512
    assert got["splash_kernels"] == ["splash_mqa_dkv", "splash_mqa_dq",
                                     "splash_mqa_fwd"]
    assert got["temp_bytes"] < 2 << 30


def test_a_layer_pattern_compiles_on_a_v5e_mesh(compiled):
    """Three windowed layers (forward, dKV, dQ) and a full one (forward,
    fused backward) a period, inside the kernels' shard_map."""
    assert compiled["mesh-f2-pattern"]["custom_calls"] >= 3 * 3 + 2


@pytest.mark.parametrize("spec", MESH_SPECS)
def test_model_with_the_kernel_compiles_on_a_v5e_mesh(compiled, spec):
    """Mosaic kernels cannot be partitioned by GSPMD: on more than one
    chip the lowering raises unless the call sits in a shard_map manual
    over every mesh axis (ops/pallas/window_attention.kernel_on_mesh) —
    the model's causal layers run the grouped-head kernel there, forward
    and fused backward."""
    assert compiled[f"mesh-{spec}"]["custom_calls"] >= 2


@pytest.mark.parametrize("entry,calls", [("full", 3), ("attention", 2),
                                         ("matmuls", 2)])
def test_kept_residuals_spare_the_forward_kernel(compiled, entry, calls):
    """A layer of the compiled grad program holds the forward kernel
    twice under "full" (forward and recomputation, beside the fused
    backward) and once where the kernel's residuals are kept."""
    assert compiled[f"remat-{entry}"]["custom_calls"] == calls


@pytest.mark.parametrize("name", EXPERT_GRIDS)
def test_the_bounded_expert_pass_keeps_the_programs_temporaries(compiled,
                                                                name):
    """The grad program of a Mellum grid holds the expert pass at
    both row counts — a forward and a backward ``cond`` a layer in the
    text, the bounded branch's grouped GEMMs as the Pallas kernel and the
    whole-buffer branch's as ``ragged_dot``, one for one: as many kernel
    instances as ragged dots (3 a forward pass, 9 a backward pass, 4
    layers) — and needs no more memory for it than the program before the
    bound did (within 5 %): the two branches are never alive together, and
    nothing but the pass's arguments is kept between its forward and its
    backward. OLMoE's, expert-parallel, holds no ``cond``, no kernel and
    the grouped GEMMs it had."""
    got = compiled[f"experts-{name}"]
    parent_gb, parent_calls = EXPERT_PARENT[name]
    bounded = name.startswith("mellum")
    if bounded:
        assert got["gmm_calls"] == got["ragged_dot_calls"] == 4 * (3 + 9)
    else:
        assert got["gmm_calls"] == 0
        assert got["ragged_dot"] == parent_calls
    assert (got["conditionals"] >= 2) == bounded
    assert bounded or got["conditionals"] == 0
    assert got["temp_bytes"] <= 1.05 * parent_gb * 1e9


def test_the_kernel_is_in_the_bounded_branch_and_nowhere_else(compiled):
    """What those programs traced (``moe.gemm_counts()``): ``gmm`` at the
    bounded row counts of Mellum's two grids, both orientations,
    ``ragged_dot`` at their whole buffers and at every pass of an OLMoE
    ``e4`` shard; Nemotron's bounded pass (320 rows a group) is refused by
    the tile rule and stays ``ragged_dot`` though it was asked."""
    want = {f"{rows}x{k}x{n}/16": "gmm" for rows in (24064, 26624)
            for k, n in ((2304, 896), (896, 2304))}
    got = compiled["gemms"]
    assert {key: how for key, how in got.items() if how == "gmm"} == want
    assert {key.split("x")[0] for key, how in got.items()
            if how == "ragged_dot"} == {"48128", "53248", "31744"}
    latent = compiled["latent-gemms"]
    assert set(latent) == {"2560x1024x2688/8", "2560x2688x1024/8",
                           *(f"{22 * T}x{k}x{n}/8" for T in LATENT_T
                             for k, n in ((1024, 2688), (2688, 1024)))}
    assert set(latent.values()) == {"ragged_dot"}


@pytest.mark.parametrize("name", [
    "experts-olmoe", "mesh-f2", "mesh-p2t2", "mesh-f2-pattern", "remat-full",
    "latent-3072", "latent-3456", "latent-3712"])
def test_programs_that_hold_no_grouped_gemm_kernel(compiled, name):
    """An OLMoE ``e4`` grad program, the dense (Qwen-family) programs and
    Nemotron's latent expert layer compile without a Pallas grouped GEMM:
    their device programs are the parent's."""
    assert compiled[name]["gmm_calls"] == 0


if __name__ == "__main__":
    print(json.dumps(_compile_lfm2() if sys.argv[1:] == ["lfm2"]
                     else _compile_glm() if sys.argv[1:] == ["glm"]
                     else _compile_kimi() if sys.argv[1:] == ["kimi"]
                     else _compile_keye() if sys.argv[1:] == ["keye"]
                     else _compile_all()))


@pytest.mark.parametrize("T", LATENT_T)
def test_a_latent_expert_layer_compiles_at_the_hybrid_cells_rows(compiled,
                                                                T):
    """Forward + backward of one latent expert layer on the share (top-22
    of 512, 8 held, latent 1024), the pass bounded to 2560 of its 22 x T
    sorted rows: the gather from the latent tokens compiles — at 3456
    tokens it did not before the source was padded to whole row tiles —
    within the temporaries the remat budget reckons (11 copies of
    ``top_k x latent`` x tokens)."""
    got = compiled[f"latent-{T}"]
    assert got["rows"] == 2560
    assert got["temp_bytes"] <= 11 * 22 * 1024 * T * 2
    # the bounded pass adds its rows into their tokens: ONE sort of the
    # 22 x T entries a pass, and of gathers of 22 x T rows of the latent
    # width only the whole-buffer branch's row gather — the parent sorted
    # twice (the inverse permutation) and un-permuted [22 x T, 1024] too
    assert got["entry_sorts"] == 1 and got["entry_gathers"] == 1


@pytest.mark.parametrize("name", sorted(WALK_LAYERS))
def test_an_expert_layers_live_walk_compiles_for_v5e(compiled, name):
    """Forward + backward of one expert layer of the Trinity cell at its
    two grids' rows, and of Mellum's at the 12,288 tokens whose row gather
    XLA refused once ("ran out of scoped vmem"): each branch's combine,
    forward and re-run, and the transpose of its row gather are loops over
    the live rows (three a branch: 6 loops at least), the bounded branch's
    GEMMs the Pallas kernel."""
    got = compiled[f"walk-{name}"]
    config, T = WALK_LAYERS[name]
    # twice the held share of the ``8 x T`` entries, in row tiles of 512
    share = {"trinity-mini": 1, "mellum2-12b-a2.5b": 4}[config]
    assert got["rows"] == -(-share * T // 512) * 512 < 8 * T
    assert got["whiles"] >= 6
    assert got["gmm_calls"] > 0 and got["ragged_dot_calls"] > 0


def test_the_selective_scan_kernels_compile_for_v5e(compiled):
    """Forward and backward at the phi4flash cell's row (1 x 8192, 5120
    channels of 16 states): two Mosaic kernels, and no [T, d_inner, N]
    array beside them — the temporaries are the float32 copies of x, Δ,
    y and their gradients and the column layouts of B and C."""
    got = compiled["s6-scan"]
    assert got["custom_calls"] == 2
    R, T, Dn, N = SAMBAY_SCAN
    assert got["temp_bytes"] < R * T * Dn * N * 4 / 2


@pytest.mark.parametrize("name", sorted(SSD_SCANS))
def test_the_ssd_scan_kernels_compile_for_v5e(compiled, name):
    """Forward and backward of the Mamba-2 scan at a cell's row: two
    Mosaic kernels inside the VMEM they ask for, and no [heads, chunk,
    chunk] block beside them — the temporaries are y in float32, the
    states entering each chunk, the gradients and the small [T, heads]
    arrays of the decays, far under the 32 KB a token of the XLA form's
    float32 decay block alone."""
    got = compiled[name]
    assert got["custom_calls"] == 2
    R, T, H, P, G, N, Q = SSD_SCANS[name]
    assert got["temp_bytes"] < R * T * H * Q * 4


@pytest.mark.parametrize("name", sorted(WIDE_HEADS))
def test_the_causal_kernel_compiles_at_heads_of_256(compiled, name):
    """Qwen3-Next's attention block (16 query / 2 key-value heads of 256)
    and GLM-4.7-Flash's latent attention as the kernel sees it (20 / 20),
    at the rows of their cells: what the chip's compiler took — by the
    kernel's own count of the call it traced — is the wide-head table's
    entry for the shape, forward, dKV and dQ kernels (a geometry that asks
    for more than the chip's 16 MB of scoped VMEM fails the child); the
    fused kernel's dQ partial sums (one a key block) would be gigabytes of
    temporaries."""
    from areal_tpu.ops.pallas import window_attention as wa

    _, hq, hkv, temp_bound = WIDE_HEADS[name]
    got = compiled[name]
    assert got["blocks"] == wa.WIDE_BLOCKS[256, hq // hkv].label()
    assert got["splash_kernels"] == ["splash_mqa_dkv", "splash_mqa_dq",
                                     "splash_mqa_fwd"]
    assert got["temp_bytes"] < temp_bound


@pytest.mark.parametrize("T", GDN_RULE_T)
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_gated_delta_rule_kernels_compile_for_v5e(compiled, dtype, T):
    """Forward and backward of the rule at the Qwen3-Next cell's geometry
    (1 x 16,384, 14,336 and 8,704, 16 / 32 heads of 128, chunk 64): two
    Mosaic kernels inside the VMEM they ask for and nothing [chunk, chunk]
    beside them — the temporaries are o in float32, the state entering each
    chunk in the compute dtype, the gradients and the gates' tiles: under
    what a dozen float32 [64, 64] blocks a chunk and value head take, which
    the XLA form writes. At the cell's own rows the backward alone, with
    the norms inside: ONE kernel, beside it less than the gradients it
    returns would take in float32 (the gates' tiles, and the copies that
    give dq, dk and dv their heads: 206 MB at 14,336 in bfloat16, 485 MB in
    float32), and a body of under 1,000 equations (842 in bfloat16 when
    its chunks' work became [chunks, ., .] arrays, 833 when a loop walked
    them: a body that grows by unrolling costs every program that holds it
    its set-up)."""
    got = compiled[f"gdn-rule-{dtype}-{T}"]
    assert got["custom_calls"] == 2
    R, _, G, H, D, Q = GDN_RULE
    assert got["temp_bytes"] < R * T * H * Q * 4 * 12
    if T != GDN_RULE[1]:
        assert got["bwd_custom_calls"] == 1
        assert got["bwd_temp_bytes"] < R * T * (G + H) * D * 2 * 4
        assert 0 < got["bwd_body_eqns"] < 1000


def test_the_qwen3_next_cut_compiles_inside_the_memory_it_leaves(compiled):
    """The grad program of the cut (16 of 512 experts held: 424.7 M
    parameters) on a 1 x 16,384 row, at the published widths, beside the
    state: 18 B a parameter, the bf16 gradient the program returns, its
    temporaries — the number the cut was chosen by (with 32 held the same
    program needs 4.9 GB beside 12.5 GB: PERF.md section 4)."""
    got = compiled["qnext-cell"]
    assert got["rules_traced"] == 1  # L L L is one run, scanned
    # ... and it runs the kernel pair (the backward re-runs the forward)
    assert got["rule_impl"] == {"pallas": 1}
    assert got["rule_kernels"] == ["gdn_rule_fwd", "gdn_rule_bwd"]
    # ... with the mixer's two norms inside them
    assert got["mixer_norms"] == {"kernel": 1}
    # attention: forward twice, dKV, dQ; the experts' grouped GEMMs
    assert got["custom_calls"] >= 4
    # 4.70 GB in the XLA form (a quarter of the heads at a time under a
    # checkpoint); 6.47 GB when the kernels ran all heads at once and the
    # mixer kept what its convolution, gates and norms left for the
    # backward (a checkpoint around them held 4.99 GB and cost the cell
    # 3.8 % of its rate: PERF.md section 6, PR 53); 5.39 GB since the
    # norms run inside the kernels (PR 55): no float32 q, k or o by head
    assert got["temp_bytes"] < 5.5e9
    gradient = got["param_bytes"] // 9  # 2 B a parameter
    assert got["param_bytes"] + gradient + got["temp_bytes"] < 15.0e9


def test_the_sambay_cell_compiles_at_the_published_widths(compiled):
    """The six-layer cut (M S M F G X) forward + backward under "full" at
    1 x 8192: every attention layer a kernel, both scans the Pallas
    kernels, in the temporaries the cell has room for beside 12.6 GB of
    state and a 1.4 GB gradient."""
    got = compiled["sambay-cell"]
    # 2 M layers x (forward, the forward the checkpoint re-runs, backward)
    assert got["scans_traced"] == 2
    # ... and S (forward twice, dKV, dQ), F and X (forward twice, the
    # fused backward)
    assert got["custom_calls"] >= 6 + 4 + 2 * 3
    assert got["temp_bytes"] < 2.4e9


def test_the_lfm2_cut_compiles_inside_the_memory_it_leaves(compiled_lfm2):
    """The grad program of the cut (a dense block and the period A c c c,
    8 of 64 experts held: 469.3 M parameters) on 2 x 7168, the largest
    grid the packer makes of the cell's traffic, at the published widths,
    beside the state: 18 B a parameter, the bf16 gradient the program
    returns, its temporaries — the number the micro-batch was chosen by
    (configs/lfm2-24b-a2b.json, ``deployment``)."""
    got = compiled_lfm2
    # one convolution a run of short-convolution blocks: the dense block's
    # and the three expert blocks' (scanned)
    assert got["convs_traced"] == 2
    # attention: forward twice, dKV, dQ; the experts' grouped GEMMs
    assert got["custom_calls"] >= 4
    # 2.91 GB: the dense FFN's [14,336, 11,776] gate / up / product
    assert got["temp_bytes"] < 3.1e9
    gradient = got["param_bytes"] // 9  # 2 B a parameter
    assert got["param_bytes"] + gradient + got["temp_bytes"] < 12.5e9


def test_the_kimi_cut_compiles_inside_the_memory_it_leaves(compiled_kimi):
    """The grad program of the cut (block 1 and the period 5-8: four KDA
    mixers, one un-rotated latent attention, 8 of 256 experts held: 602.4 M
    parameters) on 2 x 7,552, the grid of the cell's traffic that needs
    most, at the published widths: every rule the kernel pair, the
    attention kernel handed a key of 192 in 256 lanes and a value of 128
    in its own, the rule's kernels at 8 chunks a grid step though 118 chunks
    are no whole number of steps, every mixer's ends inside them and all 32
    heads in one call (PR 65: a forward call a run of KDA blocks fewer than
    the head groups' checkpoint cost, 72 -> 70 custom calls, and 1.2 GB
    fewer temporaries). Whether the micro-batch FITS is not read off this
    program (the whole tree's gradient at once, returned beside its
    temporaries: 4.96 GB + 1.20): the engine's own ``train_grad_sliced`` of
    this grid runs on the chip beside the 10.84 GB (PERF.md section 6, PR
    63 / 65) — what is held here is that the temporaries do not grow."""
    got = compiled_kimi
    assert got["rule_kernels"] == ["kda_rule_fwd", "kda_rule_bwd"]
    assert set(got["rule_impl"]) == {"pallas"}
    # one mixer a run of KDA blocks, its ends in the kernels
    assert got["mixer_norms"] == {"kernel": 2}
    # a row of 118 chunks (2 x 59), all 32 heads: 8 chunks a grid step of
    # both kernels, the last step short; never the 2 that divide
    assert got["rule_steps"] == ["2x7552/h32/fwd8/bwd8"]
    # a run's rule: forward, the forward its block's backward re-runs,
    # backward — and no forward of a head group's own
    assert got["custom_calls"] == 70
    # one rule a run of KDA blocks (block 1's, the expert blocks')
    assert got["rules_traced"] == {"2x7552/64/h32/128/r128": 2}
    assert got["assemblies_traced"] == {"2x7552/h32/q0kv512/128+64/v128": 1}
    assert got["head_widths"] == [192, 256, 128, 128]
    # 4.96 GB (6.18 with a group of 8 heads at a time and XLA's ends)
    assert got["temp_bytes"] < 5.2e9
    assert got["param_bytes"] == 10_843_819_776


def test_the_keye_cut_compiles_inside_the_memory_it_leaves(compiled_keye):
    """The grad program of the cut (6 blocks under a learned selection, 8
    of 128 experts held: 432.7 M parameters) on 2 x 7,808, the grid of the
    cell's traffic that holds most tokens, at the published widths: every
    block's attention traced as ``sparse`` and run by the four kernels of
    ops/pallas/sparse_attention.py (the selection's scratch of a query
    tile's scores against a row of 8,192 keys fits the chip's fast memory:
    the compiler refuses a kernel that does not), rows padded to whole
    tiles of 512. Whether the micro-batch FITS is the engine's own program
    on the chip (PERF.md section 5, PR 66); held here is that the
    temporaries do not grow."""
    got = compiled_keye
    assert got["kernels"] == ["dsa_select", "dsa_attend_fwd",
                              "dsa_attend_dq", "dsa_attend_dkv"]
    assert got["dispatch"] == {"sparse": 1} and got["impl"] == {"kernel": 1}
    assert got["geometry"] == ["7808/8192/q256kv512/k2048"]
    # 4.94 GB
    assert got["temp_bytes"] < 5.2e9
    assert got["param_bytes"] == 7_788_556_800
    gradient = got["param_bytes"] // 9  # 2 B a parameter
    assert got["param_bytes"] + gradient + got["temp_bytes"] < 14.5e9


def test_the_glm_cut_compiles_inside_the_memory_it_leaves(compiled_glm):
    """The grad program of the cut (a dense block and four expert blocks,
    latent attention in each, 8 of 64 experts held: 591.7 M parameters) on
    1 x 14,336, the largest grid the packer makes of the cell's traffic,
    at the published widths, beside the state: 18 B a parameter, the bf16
    gradient the program returns, its temporaries — the number the
    micro-batch was chosen by (configs/glm-4.7-flash.json,
    ``deployment``)."""
    got = compiled_glm
    # one assembly a run of blocks (the dense block's, the expert blocks'
    # scanned); the checkpoint's re-run forward is the same trace
    assert got["assemblies_traced"] == {
        "1x14336/h20/q768kv512/192+64/v256": 2}
    # attention a run: forward twice, dKV, dQ; the experts' grouped GEMMs
    assert got["custom_calls"] >= 8
    # 3.33 GB
    assert got["temp_bytes"] < 3.5e9
    gradient = got["param_bytes"] // 9  # 2 B a parameter
    assert got["param_bytes"] == 10_650_387_456
    assert got["param_bytes"] + gradient + got["temp_bytes"] < 15.5e9
