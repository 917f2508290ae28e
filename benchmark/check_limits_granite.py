"""What the reference tolerance of ``drivers/train_granite.py`` is FOR, on
the chip, by hand (not a cell, not run by the driver):

    python3 benchmark/check_limits_granite.py --seed <n>

Builds the cell ``granite-4.0-h-micro.train-rag-packed``'s model as its
driver does, takes the engine's logprobs of the longest trajectory the
packer placed behind another in its row, and compares them with
``reference_granite_hybrid`` as it is and with WRONG references, each of
which should come out over at least one of the driver's limits
(``logits / 8`` on random weights flattens the logprobs and shrinks every
error eightfold, so the limits are tight and each control is shown):

 - ``softmax_scale_of_the_head``: ``attention_multiplier`` 1/8 =
   1/sqrt(head_dim) in place of the published 1/64;
 - ``residual_multiplier_1``, ``logits_scaling_1``,
   ``embedding_multiplier_1``: that multiplier left at the identity;
 - ``reset_left_off``: the Mamba blocks run over the trajectory's packed
   row (the documents of its own micro-batch and row ahead of it, then
   itself) as if it were one document (state and convolution carried
   across the boundaries; attention still by document);
 - ``norm_before_gate``: RMSNorm first, gate second;
 - ``matmuls_in_float8``: the reference computed in float8_e4m3, the
   nearest precision below the configuration's bfloat16 — both operands
   of every matrix product against a weight (projections, MLP, head)
   rounded to it.

One seed a process (the engine holds 11.75 GB of the chip); prints one
JSON line and appends it to ``chiprun_out/check_limits_granite.jsonl``.
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

CELL = "granite-4.0-h-micro.train-rag-packed"
WRONG_KEYS = {
    "softmax_scale_of_the_head": {"attention_multiplier": 0.125},
    "residual_multiplier_1": {"residual_multiplier": 1.0},
    "logits_scaling_1": {"logits_scaling": 1.0},
    "embedding_multiplier_1": {"embedding_multiplier": 1.0},
}


def logprobs_without_reset(ref, params, cfg, docs, n_ref: int):
    """Logprobs of the LAST of ``docs`` (token arrays, in row order), its
    first ``n_ref`` tokens, under a model whose Mamba blocks never reset:
    they see the documents as one."""
    import jax
    import jax.numpy as jnp

    docs = list(docs[:-1]) + [docs[-1][:n_ref]]
    ends = np.cumsum([len(d) for d in docs])
    bounds = list(zip([0] + list(ends[:-1]), ends))
    toks = jnp.asarray(np.concatenate(docs), jnp.int32)
    eps, m = ref.eps_of(cfg), cfg["residual_multiplier"]
    h = cfg["embedding_multiplier"] * ref.f32(params["embedding"][toks])
    for kind, lp in ref.layers_of(params, cfg):
        u = ref._rms(h, ref.f32(lp["ln1"]), eps)
        if kind == "full":
            mix = jnp.concatenate(
                [ref.attention(u[a:b], cfg, lp) for a, b in bounds], 0)
        else:
            mix = ref.mamba(u, cfg, lp)
        h = h + m * mix
        h = h + m * ref.mlp(ref._rms(h, ref.f32(lp["ln2"]), eps), lp)
    a, b = bounds[-1]
    lp = jax.nn.log_softmax(ref.head(params, cfg, h[a:b])[:-1], -1)
    return np.asarray(jnp.take_along_axis(lp, toks[a + 1:b, None], -1)[:, 0])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--platform", default="tpu")  # cpu: a rehearsal
    args = ap.parse_args()
    seed = args.seed
    import jax
    import jax.numpy as jnp

    from areal_tpu.base.compile_watch import enable_compilation_cache
    from benchmark import reference_granite_hybrid as ref
    from benchmark.drivers import train_granite as drv
    from benchmark.drivers.train import to_sample
    from benchmark.drivers.train_ep import build_experiment

    enable_compilation_cache()
    out = os.path.join(harness.OUT_ROOT, f"check-limits-{seed}")
    os.makedirs(out, exist_ok=True)
    if args.platform == "tpu":
        spec = {**harness.resolve_cell(CELL), "workload": CELL, "seed": seed,
                "out": out, "t0": time.time(), "platform": "tpu", "trace": 0}
    else:  # the driver's toy size
        from benchmark import rehearse

        spec = {**rehearse.tiny_spec(CELL, 0, 8.0), "seed": seed, "out": out}
    exp = build_experiment(spec)
    model, ifaces, _ = drv.build_model(spec, exp)
    placements = drv.Placements(model.module)
    t, cfg = spec["traffic"], spec["config"]
    raw = traffic.make_train_batches(
        t["shape"], 1, exp.dataset.train_bs_n_seqs, exp.group_size, seed,
        cfg["vocab_size"])[0]
    raw["packed_logprobs"] = np.zeros(len(raw["packed_input_ids"]),
                                      np.float32)
    sample = to_sample(raw, "b0")
    got, toks, where = drv.placed_later(
        ifaces, model, exp.actor_inf.mb_spec, sample, placements)
    params = model.module.params
    line = {"seed": seed, "where": where,
            "limits": {"max": drv.LOGPROB_MAX_ERR,
                       "mean": drv.LOGPROB_MEAN_ERR,
                       "head_mean": drv.LOGPROB_HEAD_ERR}}

    def against(cfg_file):
        return drv.compare_logprobs(
            got, drv.reference_logprobs(params, cfg_file, toks))

    line["as_published"] = against(cfg)
    for key, wrong in WRONG_KEYS.items():
        line[key] = against({**cfg, **wrong})

    # the documents ahead of it in its row, then itself: no reset
    lens = [int(n) for n in sample.total_lens("packed_input_ids")]
    ids = np.asarray(sample.data["packed_input_ids"])
    docs = [ids[sum(lens[:j]):sum(lens[:j + 1])]
            for j in where["ahead_in_row"] + [where["trajectory"]]]
    with jax.default_matmul_precision("highest"):
        no_reset = logprobs_without_reset(ref, params, cfg, docs,
                                          where["tokens"])
    line["reset_left_off"] = drv.compare_logprobs(got, no_reset)

    def patched(key, name, wrong):
        """``line[key]``: the comparison with ``ref.<name>`` made wrong."""
        real = getattr(ref, name)
        setattr(ref, name, wrong(real))
        try:
            line[key] = against(cfg)
        finally:
            setattr(ref, name, real)

    def norm_first(_):
        def gated_norm(y, z, w, groups, eps, sum_sq=None, width=None):
            T, di = y.shape
            y = y.reshape(T, groups, di // groups)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
            return y.reshape(T, di) * w * jax.nn.silu(z)
        return gated_norm

    patched("norm_before_gate", "gated_norm", norm_first)

    def fp8(a):
        return jnp.asarray(a, jnp.float32).astype(
            jnp.float8_e4m3fn).astype(jnp.float32)

    patched("matmuls_in_float8", "mm",
            lambda real: lambda a, b: real(fp8(a), fp8(b)))
    line["passes_every_limit"] = sorted(
        k for k, v in line.items() if isinstance(v, dict) and v.get("ok")
        and k != "as_published")
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/check_limits_granite.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
