"""What the reference tolerance of ``drivers/train_hybrid.py`` is FOR, on
the chip, by hand (not a cell, not run by the driver):

    python3 benchmark/check_limits_nemotron_h.py --seed <n>

Builds the cell ``nemotron-3-super-120b-a12b.train-agent-4k``'s model as
its driver does, takes the engine's logprobs of the first 2048 tokens of
a trajectory the packer placed behind another in its row, and compares
them with ``reference_nemotron_h`` as it is and with WRONG references,
each of which should come out over the driver's limits:

 - ``reset_left_off``: the Mamba layers run over the trajectory's packed
   row (the documents of its own micro-batch and row ahead of it, then
   itself) as if it were one document (state and convolution carried
   across the boundaries; attention still by document). Nearly all of its
   error is in the ten tokens behind the boundary, so it is the limit on
   the first ``HEAD_TOKENS`` logprobs' mean error that refuses it;
 - ``norm_before_gate``: RMSNorm first, gate second. (The issue's "norm
   over all of d_inner" cannot differ ON THIS SHARE: it holds one of the
   8 groups, so its 1024 channels ARE one group; tests/
   test_nemotron_h_parity.py shows that wrong model on two groups.)
 - ``gates_not_scaled`` (``routed_scaling_factor`` 1), ``no_choice_bias``
   (top-22 of the scores alone), ``silu_for_relu2``;
 - ``state_in_bfloat16``: the recurrent state rounded to bfloat16 after
   every token (the nearest precision below the float32 it is kept in).
   It is reported and does NOT fail: at the published decays a state
   forgets within tens of tokens and the rounding does not build up;
 - ``matmuls_in_float8``: the reference computed in float8_e4m3, the
   nearest precision below the configuration's bfloat16 — both operands
   of every matrix product against a weight (projections, router, latent
   projections, shared and routed experts, head) rounded to it;
 - ``experts_in_float8``: the same on the held routed experts alone. The
   logprobs do not see it (8 of 512 experts are held, 1.6 % of the (token,
   expert) pairs land here); the driver's ``held_experts_error`` — the
   routed part of the first expert layer alone — does, and is printed
   under ``held_experts`` as published and under both float8 references.

One seed a process (the engine holds 12.6 GB of the chip); prints one
JSON line, appends it to ``chiprun_out/check_limits_nemotron_h.jsonl`` and
writes every token's error, as published and with the reset left off, to
``chiprun_out/check_limits_nemotron_h_errors_<seed>.json``.
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

CELL = "nemotron-3-super-120b-a12b.train-agent-4k"


def logprobs_without_reset(ref, params, cfg, docs, n_ref: int):
    """Logprobs of the LAST of ``docs`` (token arrays, in row order), its
    first ``n_ref`` tokens, under a model whose Mamba layers never reset:
    they see the documents as one."""
    import jax
    import jax.numpy as jnp

    docs = list(docs[:-1]) + [docs[-1][:n_ref]]
    ends = np.cumsum([len(d) for d in docs])
    bounds = list(zip([0] + list(ends[:-1]), ends))
    toks = jnp.asarray(np.concatenate(docs), jnp.int32)
    eps = ref.eps_of(cfg)
    h = ref.f32(params["embedding"][toks])
    for kind, lp in ref.layers_of(params, cfg):
        u = ref._rms(h, ref.f32(lp["ln"]), eps)
        if kind == "attention_only":
            out = jnp.concatenate(
                [ref.attention(u[a:b], cfg, lp) for a, b in bounds], 0)
        else:
            out = ref.MIXERS[kind](u, cfg, lp)
        h = h + out
    a, b = bounds[-1]
    logits = ref.mm(ref._rms(h[a:b], ref.f32(params["final_ln"]), eps),
                    ref.f32(params["lm_head"]))
    lp = jax.nn.log_softmax(logits[:-1], -1)
    return np.asarray(jnp.take_along_axis(lp, toks[a + 1:b, None], -1)[:, 0])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    seed = ap.parse_args().seed
    import jax
    import jax.numpy as jnp

    from areal_tpu.base.compile_watch import enable_compilation_cache
    from benchmark import reference_nemotron_h as ref
    from benchmark.drivers import train_hybrid as drv
    from benchmark.drivers.train import to_sample
    from benchmark.drivers.train_ep import build_experiment

    enable_compilation_cache()
    out = os.path.join(harness.OUT_ROOT, f"check-limits-{seed}")
    os.makedirs(out, exist_ok=True)
    spec = {**harness.resolve_cell(CELL), "workload": CELL, "seed": seed,
            "out": out, "t0": time.time(), "platform": "tpu", "trace": 0}
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

    def against(cfg_file, p=params):
        return drv.compare_logprobs(
            got, drv.reference_logprobs(p, cfg_file, toks))

    sound = drv.reference_logprobs(params, cfg, toks)
    line["as_published"] = drv.compare_logprobs(got, sound)
    # the held experts' part alone (drivers/train_hybrid.held_experts_error)
    held = {"as_published": drv.held_experts_error(model.module, cfg, toks)}
    errors = {"as_published": np.abs(got - sound)}
    line["gates_not_scaled"] = against({**cfg, "routed_scaling_factor": 1})
    line["silu_for_relu2"] = against({**cfg, "mlp_hidden_act": "silu"})
    no_bias = {**params, "layers": {**params["layers"], "moe_only": {
        **params["layers"]["moe_only"], "router_bias": jnp.zeros_like(
            params["layers"]["moe_only"]["router_bias"])}}}
    line["no_choice_bias"] = against(cfg, no_bias)

    # the documents ahead of it in its row, then itself: no reset
    lens = [int(n) for n in sample.total_lens("packed_input_ids")]
    ids = np.asarray(sample.data["packed_input_ids"])
    docs = [ids[sum(lens[:j]):sum(lens[:j + 1])]
            for j in where["ahead_in_row"] + [where["trajectory"]]]
    with jax.default_matmul_precision("highest"):
        no_reset = logprobs_without_reset(ref, params, cfg, docs,
                                          where["tokens"])
    line["reset_left_off"] = drv.compare_logprobs(got, no_reset)
    errors["reset_left_off"] = np.abs(got - no_reset)

    def patched(key, name, wrong, experts_too=False):
        """``line[key]``: the comparison with ``ref.<name>`` made wrong."""
        real = getattr(ref, name)
        setattr(ref, name, wrong(real))
        try:
            line[key] = against(cfg)
            if experts_too:
                held[key] = drv.held_experts_error(model.module, cfg, toks)
        finally:
            setattr(ref, name, real)

    def norm_first(_):
        def gated_norm(y, z, w, groups, eps):
            T, di = y.shape
            y = y.reshape(T, groups, di // groups)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
            return y.reshape(T, di) * w * jax.nn.silu(z)
        return gated_norm

    patched("norm_before_gate", "gated_norm", norm_first)
    patched("state_in_bfloat16", "scan",
            lambda real: lambda *a: real(*a, state_dtype=jnp.bfloat16))

    def fp8(a):
        return jnp.asarray(a, jnp.float32).astype(
            jnp.float8_e4m3fn).astype(jnp.float32)

    patched("experts_in_float8", "experts",
            lambda real: lambda v, g, w_up, w_down, act: real(
                fp8(v), g, fp8(w_up), fp8(w_down), act), experts_too=True)
    patched("matmuls_in_float8", "mm",
            lambda real: lambda a, b: real(fp8(a), fp8(b)), experts_too=True)
    line["held_experts"] = held
    line["limits"]["held_experts_median_rel"] = drv.EXPERTS_MEDIAN_REL_ERR
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    # every token's error, sound and with the reset left off: where it lies
    with open(f"chiprun_out/check_limits_nemotron_h_errors_{seed}.json",
              "w") as f:
        json.dump({k: [round(float(x), 5) for x in v]
                   for k, v in errors.items()}, f)
    with open("chiprun_out/check_limits_nemotron_h.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
