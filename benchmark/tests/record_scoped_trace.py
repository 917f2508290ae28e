"""Records the small trace kept beside ``test_program_trace.py`` — run once
on the chip (``python3 benchmark/tests/record_scoped_trace.py <out_dir>``):
two named jitted programs whose ops sit under ``jax.named_scope`` names of
the program's list (one of them a gradient through a checkpointed scan, so
that ``transpose(jvp(..))``, ``checkpoint`` and ``while/body`` wrap the
names), called under ``areal/`` host spans that carry counts, with sleeps
between them. Also writes the numbers ``program_trace`` gives for it,
which the test then pins. Plain jax: nothing of the program is imported."""

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from benchmark import driverlib as dl
    from benchmark import program_trace as pt
    from benchmark import trace_reduce as tr

    def layer(h, w):
        with jax.named_scope("mlp"):
            return jnp.tanh(h @ w), None

    def model(x, ws, head):
        with jax.named_scope("layer_scan"):
            h, _ = jax.lax.scan(jax.checkpoint(layer), x, ws)
        with jax.named_scope("head"):
            return h @ head

    def infer_forward(x, ws, head):
        return model(x, ws, head)

    def train_grad(x, ws, head):
        def loss(ws):
            with jax.named_scope("ppo_loss"):
                return jnp.mean(model(x, ws, head).astype(jnp.float32) ** 2)

        g = jax.grad(loss)(ws)
        with jax.named_scope("grad_accum"):
            return g * 0.5

    infer, grad = jax.jit(infer_forward), jax.jit(train_grad)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1024, 512), jnp.bfloat16)
    ws = jax.random.normal(key, (4, 512, 512), jnp.bfloat16) * 0.05
    head = jax.random.normal(key, (512, 2048), jnp.bfloat16) * 0.05
    infer(x, ws, head).block_until_ready()
    grad(x, ws, head).block_until_ready()
    tw = dl.TraceWindow(out_dir)
    tw.start()
    for i in range(3):
        with TraceAnnotation("areal/ppo/inference"):
            with TraceAnnotation("areal/infer/upload", real_tokens=900 + i,
                                 padded_tokens=1024, n_mbs=1, grid="2x512"):
                time.sleep(0.002)
            with TraceAnnotation("areal/infer/dispatch"):
                out = infer(x, ws, head)
            with TraceAnnotation("areal/infer/fetch"):
                out.block_until_ready()
        time.sleep(0.003)  # under no span
        with TraceAnnotation("areal/ppo/train_step"):
            with TraceAnnotation("areal/train/upload", real_tokens=3600,
                                 padded_tokens=4096, n_mbs=4, grid="2x512"):
                time.sleep(0.002)
            with TraceAnnotation("areal/train/fwd_bwd"):
                g = grad(x, ws, head)
            with TraceAnnotation("areal/train/fetch_stats"):
                g.block_until_ready()
    tw.stop()
    path = tr.find_xplane(tw.dir)
    data = os.path.join(out_dir, "data")
    os.makedirs(data, exist_ok=True)
    kept = os.path.join(data, "scoped.xplane.pb")
    shutil.copy(path, kept)
    red = pt.reduce_file(kept)
    names = pt.read_framework_names(kept)
    for f in os.listdir(data):  # what xprof leaves beside the file it reads
        if f.endswith(".op_stats.pb"):
            os.remove(os.path.join(data, f))
    red.pop("path", None)
    with open(os.path.join(data, "scoped.expected.json"), "w") as f:
        json.dump(red, f, indent=1)
    print(os.path.getsize(kept), json.dumps(red)[:3000])
    print(json.dumps(sorted(set(names.values())))[:6000])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
