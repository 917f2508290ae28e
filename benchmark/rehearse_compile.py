"""Described-topology compiles (``on-chip-measurement`` guide, 2.3): the
decode step and the model's forward + backward of each configuration at
the cells' real shapes, for a ``v5e:2x2`` chip that is described and not
attached, with ``memory_analysis()`` printed. Nothing runs: it shows what
the chip's compiler refuses and how many bytes one program needs — not
what else the process keeps on the device, and no time.
"""

from __future__ import annotations

import json
import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"

from benchmark import harness  # noqa: E402

CHUNK = 128
# What to compile is data of the traffic file: ``compile_grid`` is the
# largest packed [rows, length] micro-batch its batches make, and
# ``compile_decode`` the (rows, KV capacity) of its decode calls.


def mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes")}


def main(pairs) -> int:
    """``pairs``: (configuration name, traffic name) of every cell to
    compile for."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from areal_tpu.api.model import GenerationHyperparameters
    from areal_tpu.models import generate as genmod
    from areal_tpu.models import transformer
    from areal_tpu.ops.sampling import sampling_from_gconfigs
    from benchmark import weights

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree, dtype=None):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype if dtype is not None and jnp.issubdtype(
                x.dtype, jnp.floating) else x.dtype, sharding=chip), tree)

    for config_name, traffic_name in pairs:
        with open(os.path.join(harness.BENCH_DIR, "configs",
                               config_name + ".json")) as f:
            cfg = weights.model_config(json.load(f))
        traffic = harness.load_traffic(traffic_name)
        c = {"name": config_name}
        shapes = jax.eval_shape(
            lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
        # decode: f32 weights as the server holds them, and the state that
        # prefill hands over
        for ROWS, CAPACITY in traffic.get("compile_decode", ()):
            t = time.time()
            params = on_chip(shapes, jnp.float32)
            state = jax.eval_shape(
                lambda p: genmod.prefill_state(
                    p, cfg, jnp.zeros((ROWS, 512), jnp.int32),
                    jnp.full((ROWS,), 512, jnp.int32), CAPACITY,
                    attn_impl="reference"), shapes)
            g = GenerationHyperparameters(max_new_tokens=CHUNK,
                                          min_new_tokens=CHUNK)
            samp = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip)
                    for k, v in sampling_from_gconfigs([g] * ROWS).items()}
            i32 = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=chip)
            key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
            dec = genmod.decode_chunk_rows.lower(
                params, cfg, on_chip(state), i32, key, samp, n_tokens=CHUNK,
                eos_token_id=1, pad_token_id=0, row_budget=i32).compile()
            print(json.dumps({
                "config": c["name"], "program": "decode_chunk_rows",
                "rows": ROWS, "capacity": CAPACITY, "chunk": CHUNK,
                "kv_dtype": str(state["kv_k"].dtype), "memory": mem(dec),
                "compile_s_here": round(time.time() - t, 1)}))
        # train: bf16 compute copy, flash kernel, full remat, one grid
        if "compile_grid" not in traffic:
            continue
        GRID = tuple(traffic["compile_grid"])
        t = time.time()
        tok = jax.ShapeDtypeStruct(GRID, jnp.int32, sharding=chip)

        def loss_and_grad(p, tokens, pos, seg):
            def loss(p):
                y, _ = transformer.forward(
                    p, cfg, tokens, pos, segment_ids=seg, attn_impl="pallas",
                    remat=True, return_kv=False, return_hidden=True)
                return jnp.sum(y.astype(jnp.float32) ** 2)

            return jax.value_and_grad(loss)(p)

        tr = jax.jit(loss_and_grad).lower(
            on_chip(shapes, jnp.bfloat16), tok, tok, tok).compile()
        print(json.dumps({"config": c["name"], "traffic": traffic_name,
                          "program": "forward+backward (no head)",
                          "grid": GRID, "memory": mem(tr),
                          "flash_calls": tr.as_text().count("tpu_custom_call"),
                          "compile_s_here": round(time.time() - t, 1)}))
    return 0
