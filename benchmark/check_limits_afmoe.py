"""What the reference tolerance of ``drivers/train_afmoe.py`` is FOR, on
the chip, by hand (not a cell, not run by the driver):

    python3 benchmark/check_limits_afmoe.py --seed <n>

Builds the cell ``trinity-mini.train-reason-long``'s model as its driver
does, takes the engine's logprobs of the first 8192 tokens of the first
batch's longest trajectory, and compares them with ``reference_afmoe`` as
it is and with WRONG references (``wrong_models``), each of which has to
come out over at least one of the driver's limits:

 - ``no_gate``: the attention output not multiplied by sigmoid(x W_g);
 - ``rope_on_full``: RoPE on the full-attention block too;
 - ``window_halved`` (1024) and ``no_window`` on the sliding blocks;
 - ``no_post_norms``: the two norms on the branches' outputs left out;
 - ``gates_not_scaled``: ``route_scale`` 1 in place of 2.826;
 - ``softmax_for_sigmoid``: the router's scores a softmax;
 - ``no_shared_expert``;
 - ``dense_as_experts``: the leading dense block run as an expert block
   (the first expert block's router, experts and shared expert);
 - ``float8``: the projections' and the experts' inputs and weights
   rounded to float8_e4m3, the nearest precision below the
   configuration's bfloat16.

One seed a process (the engine holds 9.1 GB of the chip); prints one JSON
line and appends it to ``chiprun_out/check_limits_afmoe.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, FrozenSet, Tuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, traffic  # noqa: E402

CELL = "trinity-mini.train-reason-long"


def wrong_models(cfg: Dict[str, Any],
                 ) -> Dict[str, Tuple[Dict[str, Any], FrozenSet[str]]]:
    """{name: (configuration, ``reference_afmoe``'s ``wrong`` names)}."""
    def named(*names):
        return cfg, frozenset(names)

    return {
        "as_published": named(),
        "no_gate": named("no_gate"),
        "rope_on_full": named("rope_on_full"),
        "window_halved": ({**cfg, "sliding_window":
                           cfg["sliding_window"] // 2}, frozenset()),
        "no_window": ({**cfg, "sliding_window": 10 ** 9}, frozenset()),
        "no_post_norms": named("no_post_norms"),
        "gates_not_scaled": ({**cfg, "route_scale": 1.0}, frozenset()),
        "softmax_for_sigmoid": ({**cfg, "score_func": "softmax"},
                                frozenset()),
        "no_shared_expert": ({**cfg, "num_shared_experts": 0}, frozenset()),
        "dense_as_experts": named("dense_as_experts"),
        "float8": named("float8"),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    seed = ap.parse_args().seed
    import jax

    from areal_tpu.base.compile_watch import enable_compilation_cache
    from benchmark import reference_afmoe as ref
    from benchmark.drivers import train_afmoe as drv
    from benchmark.drivers.train import to_sample
    from benchmark.drivers.train_ep import build_experiment

    enable_compilation_cache()
    out = os.path.join(harness.OUT_ROOT, f"check-limits-{seed}")
    os.makedirs(out, exist_ok=True)
    spec = {**harness.resolve_cell(CELL), "workload": CELL, "seed": seed,
            "out": out, "t0": time.time(), "platform": "tpu", "trace": 0}
    exp = build_experiment(spec)
    model, ifaces, _ = drv.build_model(spec, exp)
    t = spec["traffic"]
    raw = traffic.make_train_batches(
        t["shape"], 1, exp.dataset.train_bs_n_seqs, exp.group_size, seed,
        spec["config"]["vocab_size"])[0]
    raw["packed_logprobs"] = np.zeros(len(raw["packed_input_ids"]),
                                      np.float32)
    got, toks = drv.reference_prefix(ifaces, model, exp.actor_inf.mb_spec,
                                     to_sample(raw, "b0"))
    params = model.module.params
    line = {"seed": seed, "tokens": int(len(toks)),
            "limits": {"max": drv.LOGPROB_MAX_ERR,
                       "mean": drv.LOGPROB_MEAN_ERR}}
    for name, (cfg, wrong) in wrong_models(spec["config"]).items():
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.token_logprobs(params, cfg, toks, wrong))
        line[name] = drv.compare_logprobs(got, want)
        if name == "as_published":  # where the worst tokens are
            err = np.abs(np.asarray(got, np.float64) - want)
            worst = np.argsort(-err)[:8]
            line["worst_tokens"] = [[int(i), round(float(err[i]), 4)]
                                    for i in worst]
            line["err_quantiles"] = [round(float(q), 5) for q in np.quantile(
                err, [0.5, 0.9, 0.99, 0.999])]
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/check_limits_afmoe.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
