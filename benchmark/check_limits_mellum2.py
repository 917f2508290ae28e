"""What the reference tolerance of ``drivers/train_share.py`` is FOR, on
the chip, by hand (not a cell, not run by the driver):

    python3 benchmark/check_limits_mellum2.py --seed <n>

Builds the cell ``mellum2-12b-a2.5b.train-code-8k``'s model as its driver
does, takes the engine's logprobs of the first trajectory's first 4096
tokens, and compares them with ``reference_mellum2`` as it is and with
four WRONG references, each of which has to come out over the driver's
limits: the window left off the sliding layers, plain RoPE in place of
YaRN on the full layer, the gates not renormalised, and the experts'
inputs and weights rounded to float8_e4m3 (the nearest precision below
the configuration's bfloat16). One seed a process (the engine holds 10.7
GB of the chip); prints one JSON line and appends it to
``chiprun_out/check_limits_mellum2.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, traffic  # noqa: E402

CELL = "mellum2-12b-a2.5b.train-code-8k"


def wrong_configs(cfg):
    plain = cfg["rope_parameters"]["sliding_attention"]
    return {
        "as_published": cfg,
        "no_window": {**cfg, "sliding_window": 10 ** 9},
        "no_yarn": {**cfg, "rope_parameters": {
            **cfg["rope_parameters"], "full_attention": plain}},
        "gates_not_renormalised": {**cfg, "norm_topk_prob": False},
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    seed = ap.parse_args().seed
    import jax.numpy as jnp

    from areal_tpu.base.compile_watch import enable_compilation_cache
    from benchmark import reference_mellum2 as ref
    from benchmark.drivers import train_share as drv
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
    for name, cfg in wrong_configs(spec["config"]).items():
        line[name] = drv.compare_logprobs(
            got, drv.reference_logprobs(params, cfg, toks))
    # the nearest precision below bfloat16, on the experts
    real = ref.experts

    def fp8(a):
        return jnp.asarray(a, jnp.float32).astype(
            jnp.float8_e4m3fn).astype(jnp.float32)

    ref.experts = lambda x, g, w_gate, w_up, w_down: real(
        fp8(x), g, fp8(w_gate), fp8(w_up), fp8(w_down))
    line["experts_in_float8"] = drv.compare_logprobs(
        got, drv.reference_logprobs(params, spec["config"], toks))
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/check_limits_mellum2.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
