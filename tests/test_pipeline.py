"""Pipeline parallelism (parallel/pipeline.py): GPipe micro-batch streaming
over the "pp" mesh axis must be numerically identical to the plain
scan-over-layers forward, including gradients and MoE aux losses.

Parity target: the reference's pipeline_parallel instruction VM + schedules
(realhf/impl/model/parallelism/pipeline_parallel/, pipe_runner.py:148) —
there, correctness is established by comparing pipelined train/forward
against the non-pipelined engine; same strategy here on the 8-CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import transformer
from areal_tpu.models.config import tiny_config
from areal_tpu.parallel import mesh as pmesh
from areal_tpu.parallel import pipeline as ppl
from areal_tpu.parallel import ring as ring_mod
from areal_tpu.parallel import sharding as psh


def _batch(cfg, B=8, T=16, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, (B, T)).astype(np.int32)
    positions = np.tile(np.arange(T, dtype=np.int32), (B, 1))
    seg = np.ones((B, T), np.int32)
    # two documents packed per row, one padded tail row
    seg[:, T // 2:] = 2
    seg[-1, T - 3:] = 0
    return tokens, positions, seg


def test_pick_pp_microbatches_gates():
    cfg = tiny_config(n_layers=4)
    # Mixed (pp + auto axes) mesh: partial-manual shard_map.
    m = pmesh.make_mesh(pmesh.ParallelSpec.parse("d2p2t2"))
    assert ppl.pick_pp_microbatches(None, cfg, 8) is None
    assert ppl.pick_pp_microbatches(m, cfg, 8) == 4  # auto: 2*pp
    assert ppl.pick_pp_microbatches(m, cfg, 6) == 3
    assert ppl.pick_pp_microbatches(m, cfg, 8, requested=2) == 2
    assert ppl.pick_pp_microbatches(m, cfg, 8, requested=3) is None  # 3∤8
    assert ppl.pick_pp_microbatches(m, cfg, 1) is None  # can't fill stages
    # layers must divide across stages
    cfg3 = tiny_config(n_layers=3)
    assert ppl.pick_pp_microbatches(m, cfg3, 8) is None
    # sp meshes pipeline too (PP∘SP) — when the sequence shards over the
    # ring; without a seq_len (or with an indivisible one) they fall back
    msp = pmesh.make_mesh(pmesh.ParallelSpec.parse("p2s2"))
    assert ppl.pick_pp_microbatches(msp, cfg, 8) is None
    assert ppl.pick_pp_microbatches(msp, cfg, 8, seq_len=31) is None
    assert ppl.pick_pp_microbatches(msp, cfg, 8, seq_len=32) == 4
    # ... also with auto axes mixed in
    mspt = pmesh.make_mesh(pmesh.ParallelSpec.parse("p2s2t2"))
    assert ppl.pick_pp_microbatches(mspt, cfg, 8, seq_len=32) == 4
    # no pp axis
    mnp = pmesh.make_mesh(pmesh.ParallelSpec.parse("d2f2t2"))
    assert ppl.pick_pp_microbatches(mnp, cfg, 8) is None


@pytest.mark.parametrize("spec_str", ["p2", "p4", "d2p2t2"])
def test_pipeline_forward_parity(spec_str):
    """Pipelined logits == single-device logits (return_kv=False routes
    through the pipeline when the mesh has pp>1)."""
    cfg = tiny_config(n_layers=4, hidden_dim=32, n_q_heads=4, n_kv_heads=2)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens, positions, seg = _batch(cfg)
    ref, _ = transformer.forward(
        params, cfg, tokens, positions, segment_ids=seg, return_kv=False
    )

    m = pmesh.make_mesh(pmesh.ParallelSpec.parse(spec_str))
    sp = psh.shard_params(params, m, cfg)

    def fwd(p, t, pos, s):
        with psh.activation_sharding(m):
            out, _ = transformer.forward(
                p, cfg, t, pos, segment_ids=s, return_kv=False
            )
        return out

    out = jax.jit(fwd)(sp, tokens, positions, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_pipeline_grad_parity():
    """jax.grad through the pipeline (reverse ppermute schedule) must match
    the non-pipelined gradient."""
    cfg = tiny_config(n_layers=4, hidden_dim=32, n_q_heads=4, n_kv_heads=2)
    params = transformer.init_params(cfg, jax.random.PRNGKey(1))
    tokens, positions, seg = _batch(cfg, seed=1)

    def loss(p, mesh):
        import contextlib

        ctx = (psh.activation_sharding(mesh) if mesh is not None
               else contextlib.nullcontext())
        with ctx:
            logits, _ = transformer.forward(
                p, cfg, tokens, positions, segment_ids=seg, return_kv=False
            )
        mask = (seg > 0).astype(jnp.float32)
        return jnp.sum(jnp.tanh(logits.astype(jnp.float32)) ** 2
                       * mask[..., None])

    g_ref = jax.jit(lambda p: jax.grad(loss)(p, None))(params)

    m = pmesh.make_mesh(pmesh.ParallelSpec.parse("p4"))
    sp = psh.shard_params(params, m, cfg)
    g_pp = jax.jit(lambda p: jax.grad(loss)(p, m))(sp)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pp)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-3, rtol=1e-3
        )


def test_pipeline_remat_parity():
    cfg = tiny_config(n_layers=2, hidden_dim=32, n_q_heads=4, n_kv_heads=2)
    params = transformer.init_params(cfg, jax.random.PRNGKey(2))
    tokens, positions, seg = _batch(cfg, seed=2)
    ref, _ = transformer.forward(
        params, cfg, tokens, positions, segment_ids=seg, return_kv=False
    )
    m = pmesh.make_mesh(pmesh.ParallelSpec.parse("p2"))
    sp = psh.shard_params(params, m, cfg)

    def fwd(p):
        with psh.activation_sharding(m):
            out, _ = transformer.forward(
                p, cfg, tokens, positions, segment_ids=seg,
                return_kv=False, remat=True,
            )
        return out

    np.testing.assert_allclose(
        np.asarray(jax.jit(fwd)(sp)), np.asarray(ref), atol=2e-4
    )


def _pipeline_call(cfg, params, batch, mesh, n_micro, schedule,
                   remat=False):
    """Call pipeline_apply_layers directly (both schedules) on the raw
    layer stack — the 1F1B-vs-GPipe harness, bypassing forward()'s head so
    mismatches point at the schedule, not the embedding/norm."""
    tokens, positions, seg = batch
    h = params["embedding"][jnp.asarray(tokens)]
    cos, sin = transformer.rope_tables(
        jnp.asarray(positions), cfg.head_dim, cfg.rotary_base
    )
    return ppl.pipeline_apply_layers(
        cfg, params["layers"], h, cos, sin, jnp.asarray(seg),
        jnp.asarray(positions), mesh, n_micro, remat=remat,
        schedule=schedule,
    )


@pytest.mark.parametrize("remat", [False, True])
def test_1f1b_matches_gpipe_oracle(remat):
    """The hand-written 1F1B custom-vjp backward must reproduce the GPipe
    scan oracle — outputs AND gradients — including with remat and with a
    bubble-heavy schedule (n_micro == pp, steps = 2*pp - 1)."""
    cfg = tiny_config(n_layers=4, hidden_dim=32, n_q_heads=4, n_kv_heads=2)
    params = transformer.init_params(cfg, jax.random.PRNGKey(4))
    tokens, positions, seg = _batch(cfg, seed=4)
    m = pmesh.make_mesh(pmesh.ParallelSpec.parse("p2"))
    sp = psh.shard_params(params, m, cfg)
    n_micro = 2  # == pp: maximal bubble fraction, worst case for masking

    outs, grads = {}, {}
    for sched in ("gpipe", "1f1b"):
        def loss(p):
            with psh.activation_sharding(m):
                out, _ = _pipeline_call(
                    cfg, p, (tokens, positions, seg), m, n_micro, sched,
                    remat=remat,
                )
            mask = (jnp.asarray(seg) > 0).astype(jnp.float32)
            return jnp.sum(
                jnp.tanh(out.astype(jnp.float32)) ** 2 * mask[..., None]
            )

        def fwd(p):
            with psh.activation_sharding(m):
                return _pipeline_call(
                    cfg, p, (tokens, positions, seg), m, n_micro, sched,
                    remat=remat,
                )[0]

        outs[sched] = np.asarray(jax.jit(fwd)(sp))
        grads[sched] = jax.jit(jax.grad(loss))(sp)

    np.testing.assert_allclose(outs["1f1b"], outs["gpipe"], atol=1e-5)
    for a, b in zip(jax.tree.leaves(grads["1f1b"]),
                    jax.tree.leaves(grads["gpipe"])):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4
        )


def test_1f1b_matches_gpipe_moe_aux():
    """MoE aux totals AND their gradient contributions must agree between
    the schedules (the aux cotangent rides the hand-written backward)."""
    from areal_tpu.models.config import MoEConfig

    cfg = tiny_config(
        n_layers=4, hidden_dim=32, n_q_heads=4, n_kv_heads=2,
        moe=MoEConfig(num_experts=4, top_k=2, capacity_factor=2.0),
    )
    params = transformer.init_params(cfg, jax.random.PRNGKey(5))
    tokens, positions, seg = _batch(cfg, seed=5)
    m = pmesh.make_mesh(pmesh.ParallelSpec.parse("p2"))
    sp = psh.shard_params(params, m, cfg)

    n_micro = 4

    def loss(p, sched):
        out, aux = _pipeline_call(
            cfg, p, (tokens, positions, seg), m, n_micro, sched
        )
        mask = (jnp.asarray(seg) > 0).astype(jnp.float32)
        main = jnp.sum(
            jnp.tanh(out.astype(jnp.float32)) ** 2 * mask[..., None]
        )
        # aux_total enters the loss -> its cotangent must flow through
        # the backward schedule into the router weights.
        return main + 0.1 * jnp.sum(aux["aux_total"]), aux

    # Values + aux: 1F1B vs the GPipe oracle (forward-only on the oracle —
    # jax 0.4.x's experimental shard_map cannot transpose the oracle's
    # psum'd P() aux outputs, one more reason the 1F1B backward is
    # hand-written).
    def fwd(p, sched):
        with psh.activation_sharding(m):
            return loss(p, sched)

    (v1, aux1) = jax.jit(lambda p: fwd(p, "1f1b"))(sp)
    (v2, aux2) = jax.jit(lambda p: fwd(p, "gpipe"))(sp)
    np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5)
    aux1, aux2 = jax.device_get((aux1, aux2))
    assert set(aux1) == set(aux2)
    for k in aux1:
        np.testing.assert_allclose(aux1[k], aux2[k], atol=1e-6, rtol=1e-5)

    # Gradients: 1F1B vs the micro-batched NON-pipelined reference (the
    # same contract the forward-parity oracle test uses for values).
    g1 = jax.jit(jax.grad(
        lambda p: fwd(p, "1f1b")[0], has_aux=False
    ))(sp)

    mb = tokens.shape[0] // n_micro

    def ref_loss(p):
        total = jnp.zeros((), jnp.float32)
        aux_tot = jnp.zeros((), jnp.float32)
        for i in range(n_micro):
            sl = slice(i * mb, (i + 1) * mb)
            h = p["embedding"][jnp.asarray(tokens[sl])]
            cos, sin = transformer.rope_tables(
                jnp.asarray(positions[sl]), cfg.head_dim, cfg.rotary_base
            )
            out, aux = transformer.apply_layer_stack(
                cfg, h, p["layers"], cos, sin, jnp.asarray(seg[sl]),
                jnp.asarray(positions[sl]),
            )
            mask = (jnp.asarray(seg[sl]) > 0).astype(jnp.float32)
            total += jnp.sum(
                jnp.tanh(out.astype(jnp.float32)) ** 2 * mask[..., None]
            )
            aux_tot += jnp.sum(aux["aux_total"].astype(jnp.float32))
        return total + 0.1 * aux_tot / n_micro

    g_ref = jax.jit(jax.grad(ref_loss))(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-3
        )


def test_1f1b_backward_residuals_scale_with_n_micro():
    """The peak-memory regression test (ISSUE 8): the 1F1B backward's live
    activation set — measured from the ABSTRACT shapes of the real forward
    via jax.eval_shape, no TPU needed — must be exactly n_micro stage
    inputs per stage, independent of ``steps = n_micro + pp - 1``. The
    GPipe scan, by construction, keeps >= steps/n_micro times that (its
    scan saves per-step residuals and stacks [steps, ...] outputs), which
    is what OOM'd cap-4096 PP configs."""
    cfg = tiny_config(n_layers=4, hidden_dim=32, n_q_heads=4, n_kv_heads=2)
    params = transformer.init_params(cfg, jax.random.PRNGKey(6))

    def measure(spec, B, T, n_micro):
        m = pmesh.make_mesh(pmesh.ParallelSpec.parse(spec))
        sp = psh.shard_params(params, m, cfg)
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, cfg.vocab_size, (B, T)).astype(np.int32)
        positions = np.tile(np.arange(T, dtype=np.int32), (B, 1))
        seg = np.ones((B, T), np.int32)
        h = params["embedding"][jnp.asarray(tokens)]
        cos, sin = transformer.rope_tables(
            jnp.asarray(positions), cfg.head_dim, cfg.rotary_base
        )
        return ppl.backward_residual_bytes(
            cfg, sp["layers"], h, cos, sin, jnp.asarray(seg),
            jnp.asarray(positions), m, n_micro,
        )

    B, T, D = 8, 16, cfg.hidden_dim
    itemsize = 4  # f32 test params/activations
    expected = B * T * D * itemsize  # n_micro * mb * T * D per stage
    got_p2 = measure("p2", B, T, n_micro=4)
    got_p4 = measure("p4", B, T, n_micro=4)
    # Exactly the n_micro stage inputs, nothing stacked by `steps`:
    assert got_p2 == expected
    # ... and INVARIANT to pipeline depth (steps grows 5 -> 7 here):
    assert got_p4 == got_p2
    # The GPipe-scan formulation's boundary working set per stage grows
    # with steps (saved per-step inputs + the [steps, ...] ys stack it
    # slices the output from). At the cap-4096 bench geometry the factor
    # is what pushed PP past the 16G budget:
    for pp, n_micro in ((4, 4), (4, 8)):
        steps = n_micro + pp - 1
        one_f1b = n_micro  # micro-batch-input equivalents per stage
        gpipe = 2 * steps  # per-step saved inputs + stacked ys
        assert gpipe / one_f1b >= 1 + (pp - 1) / n_micro
    # Doubling n_micro at fixed B keeps the residual set pinned at B rows:
    assert measure("p2", B, T, n_micro=8) == expected


@pytest.mark.ring
@pytest.mark.parametrize("ring_schedule", ["zigzag", "naive"])
@pytest.mark.parametrize("sched", ["1f1b", "gpipe"])
def test_ppsp_matches_gspmd_oracle(sched, ring_schedule, monkeypatch):
    """PP∘SP e2e parity: on a pp×sp CPU mesh both pipeline schedules, with
    ring attention running inside each stage (both ring schedules), must
    reproduce the dense scan oracle's loss AND gradients at the existing
    pipeline parity tolerances."""
    monkeypatch.setattr(ring_mod, "DEFAULT_SCHEDULE", ring_schedule)
    cfg = tiny_config(n_layers=4, hidden_dim=32, n_q_heads=4, n_kv_heads=2)
    params = transformer.init_params(cfg, jax.random.PRNGKey(7))
    tokens, positions, seg = _batch(cfg, seed=7)
    m = pmesh.make_mesh(pmesh.ParallelSpec.parse("p2s2"))
    assert ppl.pick_pp_microbatches(m, cfg, tokens.shape[0],
                                    seq_len=tokens.shape[1]) is not None
    sp = psh.shard_params(params, m, cfg)
    mask = (jnp.asarray(seg) > 0).astype(jnp.float32)

    def dense_loss(p):
        h = p["embedding"][jnp.asarray(tokens)]
        cos, sin = transformer.rope_tables(
            jnp.asarray(positions), cfg.head_dim, cfg.rotary_base
        )
        out, _ = transformer.apply_layer_stack(
            cfg, h, p["layers"], cos, sin, jnp.asarray(seg),
            jnp.asarray(positions),
        )
        return jnp.sum(
            jnp.tanh(out.astype(jnp.float32)) ** 2 * mask[..., None]
        )

    def pp_loss(p):
        with psh.activation_sharding(m):
            out, _ = _pipeline_call(
                cfg, p, (tokens, positions, seg), m, 2, sched
            )
        return jnp.sum(
            jnp.tanh(out.astype(jnp.float32)) ** 2 * mask[..., None]
        )

    v_ref, g_ref = jax.jit(jax.value_and_grad(dense_loss))(params)
    v_pp, g_pp = jax.jit(jax.value_and_grad(pp_loss))(sp)
    np.testing.assert_allclose(float(v_pp), float(v_ref), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pp)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-3, rtol=1e-3
        )


@pytest.mark.ring
def test_backward_residuals_invariant_to_sp():
    """PP∘SP must not change the 1F1B residual accounting: the per-stage
    saved set is the same n_micro GLOBAL micro-batch inputs whether or not
    the sequence dim shards over a ring (each sp shard holds 1/sp of it,
    but the metric counts the reassembled global buffer)."""
    cfg = tiny_config(n_layers=4, hidden_dim=32, n_q_heads=4, n_kv_heads=2)
    params = transformer.init_params(cfg, jax.random.PRNGKey(6))
    B, T = 8, 16

    def measure(spec):
        m = pmesh.make_mesh(pmesh.ParallelSpec.parse(spec))
        sp = psh.shard_params(params, m, cfg)
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, cfg.vocab_size, (B, T)).astype(np.int32)
        positions = np.tile(np.arange(T, dtype=np.int32), (B, 1))
        seg = np.ones((B, T), np.int32)
        h = params["embedding"][jnp.asarray(tokens)]
        cos, sin = transformer.rope_tables(
            jnp.asarray(positions), cfg.head_dim, cfg.rotary_base
        )
        return ppl.backward_residual_bytes(
            cfg, sp["layers"], h, cos, sin, jnp.asarray(seg),
            jnp.asarray(positions), m, n_micro=4,
        )

    base = measure("p2")
    assert base == B * T * cfg.hidden_dim * 4  # f32 stage inputs
    assert measure("p2s2") == base
    assert measure("p2s4") == base


def test_pipeline_moe_aux_parity():
    """MoE models pipeline too; aux totals must match the scan path
    (bubble steps run garbage and must not pollute the balancing loss)."""
    from areal_tpu.models.config import MoEConfig

    cfg = tiny_config(
        n_layers=4, hidden_dim=32, n_q_heads=4, n_kv_heads=2,
        moe=MoEConfig(num_experts=4, top_k=2, capacity_factor=2.0),
    )
    params = transformer.init_params(cfg, jax.random.PRNGKey(3))
    tokens, positions, seg = _batch(cfg, seed=3)
    ref, _, _ = transformer.forward(
        params, cfg, tokens, positions, segment_ids=seg,
        return_kv=False, return_aux=True,
    )
    # Aux (balancing) losses are nonlinear in the batch, so the pipeline's
    # per-micro-batch aux matches the MICRO-BATCHED reference (what any
    # grad-accumulation engine, the reference's included, optimizes) — not
    # the whole-batch value.
    m = pmesh.make_mesh(pmesh.ParallelSpec.parse("p2"))
    n_micro = ppl.pick_pp_microbatches(m, cfg, tokens.shape[0])
    mb = tokens.shape[0] // n_micro
    aux_ref = None
    for i in range(n_micro):
        sl = slice(i * mb, (i + 1) * mb)
        _, _, a = transformer.forward(
            params, cfg, tokens[sl], positions[sl], segment_ids=seg[sl],
            return_kv=False, return_aux=True,
        )
        aux_ref = a if aux_ref is None else {
            k: aux_ref[k] + a[k] for k in a
        }
    aux_ref = {k: v / n_micro for k, v in aux_ref.items()}
    sp = psh.shard_params(params, m, cfg)

    def fwd(p):
        with psh.activation_sharding(m):
            return transformer.forward(
                p, cfg, tokens, positions, segment_ids=seg,
                return_kv=False, return_aux=True,
            )

    out, _, aux = jax.jit(fwd)(sp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3,
                               rtol=2e-3)
    # The pipeline carries exactly the SCALAR aux keys (_aux_keys): the
    # per-expert expert_load histogram is vector-valued and doesn't ride
    # the scan carries / 1F1B cotangents. The scan path reports it on top.
    assert set(aux) == set(ppl._aux_keys(cfg))
    assert set(aux) < set(aux_ref)
    for k in aux:
        np.testing.assert_allclose(
            float(aux[k]), float(aux_ref[k]), atol=1e-4, rtol=2e-3
        )
