"""Records the small trace kept beside ``test_trace_reduce.py`` — run once
on the chip (``python3 benchmark/tests/record_trace.py <out_dir>``): a few
small jitted calls with host spans and sleeps between them, so that the
trace holds device ops, idle gaps and ``bench/`` spans. Also writes the
numbers the reduction gives for it, which the test then pins."""

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

    from benchmark import driverlib as dl
    from benchmark import trace_reduce as tr

    @jax.jit
    def work(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    work(x).block_until_ready()
    tw = dl.TraceWindow(out_dir)
    tw.start()
    for i in range(3):
        with dl.span("test/call"):
            work(x).block_until_ready()
        with dl.span("test/sleep"):
            time.sleep(0.01)
    tw.stop()
    path = tr.find_xplane(tw.dir)
    red = tr.reduce_planes(tr.read_xplane(path))
    data = os.path.join(out_dir, "data")
    os.makedirs(data, exist_ok=True)
    shutil.copy(path, os.path.join(data, "small.xplane.pb"))
    with open(os.path.join(data, "small.expected.json"), "w") as f:
        json.dump({"window_s": red["window_s"], "busy_s": red["busy_s"],
                   "chips": len(red["busy_s_per_chip"]), "ops": red["ops"],
                   "idle_gaps": red["idle_gaps"]}, f, indent=1)
    print(os.path.getsize(path), json.dumps(red)[:2000])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
