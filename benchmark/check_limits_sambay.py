"""What the reference tolerance of ``drivers/train_sambay.py`` is FOR, on
the chip, by hand (not a cell, not run by the driver):

    python3 benchmark/check_limits_sambay.py --seed <n>

Builds the cell ``phi-4-mini-flash-reasoning.train-cot-packed``'s model as
its driver does, takes the engine's logprobs of the first 4096 tokens of
the longest trajectory the packer placed behind another in its row, and
compares them with ``reference_sambay`` as it is and with WRONG
references, each of which should come out over one of the driver's
limits:

 - ``lambda_term_dropped``: plain attention, ``o = softmax(q1 k1) v``
   (still under the sub-norm);
 - ``sub_norm_dropped``: no RMSNorm over the 128 behind the combine;
 - ``cross_reads_window_kv``: the X layer attends over the WINDOW layer's
   K/V (published layer 15's) in place of the full layer's;
 - ``window_off`` and ``window_1024``: the S layer's window;
 - ``memory_behind_gate``: ``m = y * silu(z)`` handed to the memory unit;
 - ``reset_left_off``: the Mamba layers (scan and convolution) run over
   the trajectory's packed row — the documents ahead of it, then itself —
   as one document; attention still by document;
 - ``delta_without_bias``: ``Δ = softplus(δ W_dt)``;
 - ``lambda_init_of_layer_0``: lambda_init at this cut's indices 0-5, not
   the published 14-19;
 - ``matmuls_in_float8``: both operands of every matrix product rounded
   to float8_e4m3, the nearest precision below the configuration's
   bfloat16.

One seed a process (the engine holds 12.6 GB of the chip); prints one
JSON line and appends it to ``chiprun_out/check_limits_sambay.jsonl``.
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

CELL = "phi-4-mini-flash-reasoning.train-cot-packed"


def logprobs_without_reset(ref, params, cfg, docs, n_ref: int):
    """Logprobs of the LAST of ``docs`` (token arrays, in row order), its
    first ``n_ref`` tokens, under a model whose Mamba layers never reset:
    they see the documents as one. Attention, the memory units and the
    MLPs run a document at a time."""
    import jax
    import jax.numpy as jnp

    docs = list(docs[:-1]) + [docs[-1][:n_ref]]
    ends = np.cumsum([len(d) for d in docs])
    bounds = list(zip([0] + list(ends[:-1]), ends))
    toks = jnp.asarray(np.concatenate(docs), jnp.int32)
    pattern, eps = ref.pattern_of(cfg), ref.eps_of(cfg)
    h = ref.f32(params["embedding"])[toks]
    memories, kvs = {}, {}
    for i, (letter, lp) in enumerate(ref.layers_of(params, cfg)):
        u = ref.layer_norm(h, ref.f32(lp["ln1"]), ref.f32(lp["ln1_b"]), eps)
        if letter == "M":
            mix, memories[i] = ref.mamba(u, cfg, lp)
        elif letter == "G":
            mix = ref.gmu(u, memories[ref.memory_source(pattern)], lp)
        else:
            window = cfg["sliding_window"] if letter == "S" else None
            outs = []
            for a, b in bounds:
                kv = None
                if letter == "X":
                    k, v = kvs[ref.kv_source(pattern)]
                    kv = (k[a:b], v[a:b])
                outs.append(ref.attention(u[a:b], cfg, lp, i, window, kv))
            mix = jnp.concatenate([o for o, _ in outs], 0)
            kvs[i] = tuple(jnp.concatenate([kv[j] for _, kv in outs], 0)
                           for j in range(2))
        h = h + mix
        h = h + ref.mlp(ref.layer_norm(h, ref.f32(lp["ln2"]),
                                       ref.f32(lp["ln2_b"]), eps), lp)
    a, b = bounds[-1]
    hn = ref.layer_norm(h[a:b], ref.f32(params["final_ln"]),
                        ref.f32(params["final_ln_b"]), eps)
    lp = jax.nn.log_softmax(
        ref.mm(hn, ref.f32(params["embedding"]).T)[:-1], -1)
    return np.asarray(jnp.take_along_axis(lp, toks[a + 1:b, None], -1)[:, 0])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    seed = ap.parse_args().seed
    import jax
    import jax.numpy as jnp

    from areal_tpu.base.compile_watch import enable_compilation_cache
    from benchmark import reference_sambay as ref
    from benchmark.drivers import train_sambay as drv
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
    # the cell's own first batch (lengths are drawn for all its batches at
    # once: one batch alone would be another mix)
    raw = traffic.make_train_batches(
        t["shape"], t["n_batches"], exp.dataset.train_bs_n_seqs,
        exp.group_size, seed, cfg["vocab_size"])[0]
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

    line["as_published"] = against(cfg)
    line["window_off"] = against({**cfg, "sliding_window": None})
    line["window_1024"] = against({**cfg, "sliding_window": 1024})
    s6 = dict(params["layers"]["s6"])
    s6["dt_bias"] = jnp.zeros_like(s6["dt_bias"])
    line["delta_without_bias"] = against(
        cfg, {**params, "layers": {**params["layers"], "s6": s6}})

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

    def fp8(a):
        return jnp.asarray(a, jnp.float32).astype(
            jnp.float8_e4m3fn).astype(jnp.float32)

    patched("lambda_term_dropped", "combine",
            lambda real: lambda o1, o2, lam: o1)
    patched("sub_norm_dropped", "sub_norm", lambda real: lambda o, w, eps: o)
    patched("cross_reads_window_kv", "kv_source",
            lambda real: lambda pattern: pattern.rindex("S"))
    patched("memory_behind_gate", "memory_of",
            lambda real: lambda y, z: y * jax.nn.silu(z))
    patched("lambda_init_of_layer_0", "lambda_init_of",
            lambda real: lambda c, layer: real(
                {**c, "first_layer_index": 0}, layer))
    patched("matmuls_in_float8", "mm",
            lambda real: lambda a, b: real(fp8(a), fp8(b)))
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/check_limits_sambay.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
