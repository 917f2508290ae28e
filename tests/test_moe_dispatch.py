"""Grouped-dispatch and expert-parallel parity tests (models/moe.py).

The sort-based grouped-GEMM path is the production default; the one-hot
einsum path is the retained GShard oracle. Both implement the identical
capacity/drop policy, so forward outputs AND gradients must agree exactly
(up to float reassociation) — including dropped tokens and padding masks.
The expert-parallel all-to-all path must match the replicated layer
numerically on CPU host meshes with a real "ep" axis.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import moe as moemod
from areal_tpu.models import transformer
from areal_tpu.models.config import MoEConfig, tiny_config
from areal_tpu.parallel import mesh as pmesh

pytestmark = pytest.mark.moe


def _layer_params(rng, D, F, E, shared=None):
    lp = {
        "router": jnp.asarray(rng.randn(D, E).astype(np.float32) * 0.5),
        "e_gate": jnp.asarray(rng.randn(E, D, F).astype(np.float32) * 0.1),
        "e_up": jnp.asarray(rng.randn(E, D, F).astype(np.float32) * 0.1),
        "e_down": jnp.asarray(rng.randn(E, F, D).astype(np.float32) * 0.1),
    }
    if shared:
        lp["s_gate"] = jnp.asarray(
            rng.randn(D, shared).astype(np.float32) * 0.1)
        lp["s_up"] = jnp.asarray(
            rng.randn(D, shared).astype(np.float32) * 0.1)
        lp["s_down"] = jnp.asarray(
            rng.randn(shared, D).astype(np.float32) * 0.1)
    return lp


def _loss_fn(moe, x, mask, dispatch):
    def loss(lp):
        y, aux = moemod.moe_mlp(x, lp, moe, mask=mask, dispatch=dispatch)
        return jnp.sum(y * y) + aux["aux_total"], aux

    return loss


@pytest.mark.parametrize(
    "E,k,cf",
    [(4, 2, 1.0), (8, 2, 2.0), (8, 1, 0.5), (16, 4, 1.5)],
)
def test_grouped_matches_einsum_fwd_and_grad(E, k, cf):
    """Loss, grads, and dropped_frac identical between the grouped path
    and the einsum oracle — across shapes that exercise no-drop, heavy
    drop (cf=0.5), k=1, and k=4, with a packed padding mask and a shared
    expert in the mix."""
    rng = np.random.RandomState(E * 10 + k)
    D, F, B, T = 16, 32, 4, 16
    lp = _layer_params(rng, D, F, E, shared=24)
    x = jnp.asarray(rng.randn(B, T, D).astype(np.float32))
    mask = jnp.asarray(  # last 20% of each row is grid padding
        (np.arange(T)[None, :] < int(T * 0.8)).repeat(B, 0))
    moe = MoEConfig(num_experts=E, top_k=k, capacity_factor=cf,
                    aux_loss_coeff=1e-2, z_loss_coeff=1e-3,
                    shared_intermediate_dim=24)

    (lg, ag), gg = jax.value_and_grad(
        _loss_fn(moe, x, mask, "grouped"), has_aux=True)(lp)
    (le, ae), ge = jax.value_and_grad(
        _loss_fn(moe, x, mask, "einsum"), has_aux=True)(lp)

    assert float(lg) == pytest.approx(float(le), rel=1e-5, abs=1e-6)
    assert float(ag["dropped_frac"]) == pytest.approx(
        float(ae["dropped_frac"]), abs=1e-6)
    if cf <= 0.5:  # the tight-capacity cases must actually drop
        assert float(ag["dropped_frac"]) > 0.0
    for name in gg:
        np.testing.assert_allclose(
            np.asarray(gg[name]), np.asarray(ge[name]),
            rtol=2e-4, atol=1e-6, err_msg=f"grad mismatch on {name}")


def test_grouped_is_default_and_env_oracle():
    assert moemod.resolve_dispatch(None) == "grouped"
    assert moemod.resolve_dispatch("einsum") == "einsum"
    with pytest.raises(ValueError, match="unknown MoE dispatch"):
        moemod.resolve_dispatch("scatter")
    old = dict(__import__("os").environ)
    import os

    try:
        os.environ["AREAL_MOE_DISPATCH"] = "einsum"
        assert moemod.resolve_dispatch(None) == "einsum"
        # explicit arg wins over the env var
        assert moemod.resolve_dispatch("grouped") == "grouped"
    finally:
        os.environ.clear()
        os.environ.update(old)


def test_routing_health_aux():
    """expert_load sums to 1 over experts (pre-drop share of routed
    assignments) and expert_load_ratio sits in [1, E]."""
    rng = np.random.RandomState(3)
    D, F, E = 8, 16, 4
    lp = _layer_params(rng, D, F, E)
    x = jnp.asarray(rng.randn(2, 32, D).astype(np.float32))
    moe = MoEConfig(num_experts=E, top_k=2, capacity_factor=2.0)
    _, aux = moemod.moe_mlp(x, lp, moe)
    load = np.asarray(aux["expert_load"])
    assert load.shape == (E,)
    assert float(load.sum()) == pytest.approx(1.0, abs=1e-5)
    ratio = float(aux["expert_load_ratio"])
    assert 1.0 - 1e-5 <= ratio <= E + 1e-5
    assert ratio == pytest.approx(float(load.max() / load.mean()), rel=1e-5)


@pytest.mark.parametrize("spec", ["e2", "d2e2", "e4t2", "d1f1e2"])
def test_ep_matches_replicated(spec):
    """The all-to-all expert-parallel path on a real ep mesh axis matches
    the replicated grouped layer — loss, grads, dropped_frac — in the
    no-drop regime (per-shard capacity changes drop priority, so drops
    are compared structurally elsewhere)."""
    ps = pmesh.ParallelSpec.parse(spec)
    if ps.world_size > len(jax.devices()):
        pytest.skip(f"needs {ps.world_size} devices")
    mesh = pmesh.make_mesh(ps)
    rng = np.random.RandomState(7)
    D, F, E, B, T = 16, 32, 4, 8, 8
    lp = _layer_params(rng, D, F, E)
    x = jnp.asarray(rng.randn(B, T, D).astype(np.float32))
    moe = MoEConfig(num_experts=E, top_k=2, capacity_factor=8.0)
    assert moemod.ep_eligible(mesh, moe, B, T)

    def loss_ep(lp):
        y, aux = moemod.moe_mlp(x, lp, moe, mesh=mesh)
        return jnp.sum(y * y) + aux["aux_total"], aux

    (l_ep, a_ep), g_ep = jax.value_and_grad(loss_ep, has_aux=True)(lp)
    (l_ref, a_ref), g_ref = jax.value_and_grad(
        _loss_fn(moe, x, None, "grouped"), has_aux=True)(lp)

    assert float(l_ep) == pytest.approx(float(l_ref), rel=1e-5)
    assert float(a_ep["dropped_frac"]) == pytest.approx(
        float(a_ref["dropped_frac"]), abs=1e-6)
    for name in g_ref:
        np.testing.assert_allclose(
            np.asarray(g_ep[name]), np.asarray(g_ref[name]),
            rtol=2e-4, atol=1e-6, err_msg=f"grad mismatch on {name}")


def test_ep_eligible_gates():
    mesh = pmesh.make_mesh(pmesh.ParallelSpec(ep=2))
    moe = MoEConfig(num_experts=4, top_k=2)
    assert moemod.ep_eligible(mesh, moe, 4, 8)
    # experts must divide over ep
    assert not moemod.ep_eligible(
        mesh, MoEConfig(num_experts=3, top_k=1), 4, 8)
    # batch must divide the data axes (dp*fsdp*ep = 2)
    assert not moemod.ep_eligible(mesh, moe, 3, 8)
    # no mesh / dense model / ep=1 → never
    assert not moemod.ep_eligible(None, moe, 4, 8)
    assert not moemod.ep_eligible(mesh, None, 4, 8)
    dense_mesh = pmesh.make_mesh(pmesh.ParallelSpec(dp=2))
    assert not moemod.ep_eligible(dense_mesh, moe, 4, 8)


def test_init_moe_params_distinct_keys():
    """Every initialized weight draws from its own split — the router must
    not silently share a key with an expert matrix, with or without the
    shared expert in the set (regression: the old code split a fixed
    count and zipped, so adding a weight shifted neighbours' keys)."""
    cfg = tiny_config(moe=dict(num_experts=4, top_k=2))
    p = moemod.init_moe_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    assert set(p) == {"router", "e_gate", "e_up", "e_down"}
    flat = [np.asarray(v).ravel()[:8] for v in p.values()]
    for i in range(len(flat)):
        for j in range(i + 1, len(flat)):
            assert not np.allclose(flat[i], flat[j])
    cfg_s = tiny_config(
        moe=dict(num_experts=4, top_k=2, shared_intermediate_dim=16))
    p_s = moemod.init_moe_params(cfg_s, jax.random.PRNGKey(0), jnp.float32)
    assert {"s_gate", "s_up", "s_down"} <= set(p_s)
    flat_s = [np.asarray(v).ravel()[:8] for v in p_s.values()]
    for i in range(len(flat_s)):
        for j in range(i + 1, len(flat_s)):
            assert not np.allclose(flat_s[i], flat_s[j])


def test_activated_param_count():
    """MoE activated params = total minus the (E - top_k) idle routed
    FFNs per layer; dense configs are unchanged."""
    dense = tiny_config()
    assert transformer.activated_param_count(dense) == \
        transformer.param_count(dense)
    cfg = tiny_config(moe=dict(num_experts=8, top_k=2))
    total = transformer.param_count(cfg)
    act = transformer.activated_param_count(cfg)
    fr = cfg.moe.routed_intermediate_dim or cfg.intermediate_dim
    idle = cfg.n_layers * (cfg.moe.num_experts - cfg.moe.top_k) \
        * 3 * cfg.hidden_dim * fr
    assert act == total - idle
    assert act < total


def test_moe_flops_accounting_activated():
    """monitor.model_flops_per_token counts top_k routed experts + router
    + shared expert, not all num_experts."""
    from areal_tpu.base import monitor

    cfg = tiny_config(moe=dict(num_experts=8, top_k=2,
                               shared_intermediate_dim=16))
    dense = dataclasses.replace(cfg, moe=None)
    f_moe = monitor.model_flops_per_token(cfg, 128.0, backward=False)
    f_dense = monitor.model_flops_per_token(dense, 128.0, backward=False)
    d = cfg.hidden_dim
    fr = cfg.intermediate_dim
    expect_delta = cfg.n_layers * (
        (cfg.moe.top_k * 3 * 2 * d * fr + 2 * d * 8 + 3 * 2 * d * 16)
        - 3 * 2 * d * fr
    )
    assert f_moe - f_dense == pytest.approx(expect_delta)


def test_validate_config_rejects_bad_ep():
    from areal_tpu.api.cli_args import ConfigError, validate_config

    class _NS:
        def __init__(self, **kw):
            self.__dict__.update(kw)

    def cfg(alloc, moe=None):
        tiny = {"moe": moe} if moe is not None else {}
        return _NS(mode="local", allocation_mode=alloc, n_nodes=1,
                   n_gpus_per_node=8, actor=_NS(tiny=tiny))

    # ep on the generation side never applies
    with pytest.raises(ConfigError, match="ep"):
        validate_config(cfg("gen.e2+train.d2",
                            moe={"num_experts": 4, "top_k": 2}))
    # train-side ep on a dense model
    with pytest.raises(ConfigError, match="dense"):
        validate_config(cfg("e2"))
    # experts must divide over ep
    with pytest.raises(ConfigError, match="num_experts"):
        validate_config(cfg("e2", moe={"num_experts": 3, "top_k": 1}))
    # capacity_factor must be positive
    with pytest.raises(ConfigError, match="capacity_factor"):
        validate_config(cfg("d2", moe={"num_experts": 4, "top_k": 2,
                                       "capacity_factor": 0.0}))
    # the happy path passes
    validate_config(cfg("e2", moe={"num_experts": 4, "top_k": 2}))
    validate_config(cfg("d2f2t2"))


def test_validate_config_reads_experts_from_a_checkpoints_config(tmp_path):
    """A model that comes from a checkpoint can be expert-parallel: the
    expert count is read from the ``config.json`` beside ``actor.path``
    through the family mapping; a dense checkpoint is still refused."""
    import json

    from areal_tpu.api.cli_args import ConfigError, validate_config

    class _NS:
        def __init__(self, **kw):
            self.__dict__.update(kw)

    def cfg(alloc, hf_keys):
        d = tmp_path / f"ckpt{len(list(tmp_path.iterdir()))}"
        d.mkdir()
        (d / "config.json").write_text(json.dumps(hf_keys))
        return _NS(mode="local", allocation_mode=alloc, n_nodes=1,
                   n_gpus_per_node=8, actor=_NS(tiny={}, path=str(d)))

    base = dict(num_hidden_layers=2, hidden_size=32, num_attention_heads=4,
                num_key_value_heads=4, intermediate_size=16, vocab_size=97)
    olmoe = dict(base, model_type="olmoe", num_experts=64,
                 num_experts_per_tok=8)
    validate_config(cfg("e4", olmoe))
    validate_config(cfg("d2e4", olmoe))
    with pytest.raises(ConfigError, match="num_experts=64"):
        validate_config(cfg("e3", olmoe))
    with pytest.raises(ConfigError, match="dense"):
        validate_config(cfg("e4", dict(base, model_type="qwen2")))
    with pytest.raises(ConfigError, match="ep"):  # never on the fleet's side
        validate_config(cfg("gen.e2+train.d2", olmoe))
    # a dropless tiny model: no capacity to check
    validate_config(_NS(mode="local", allocation_mode="e2", n_nodes=1,
                        n_gpus_per_node=8, actor=_NS(tiny={"moe": {
                            "num_experts": 4, "top_k": 2,
                            "capacity_factor": None}})))


@pytest.mark.parametrize("spec", ["e2", "e4"])
def test_ep_dropless_matches_replicated_under_any_skew(spec):
    """No capacity: the expert-parallel path computes every chosen pair —
    output, loss and gradients equal to the one-shard layer's, with a
    router that spreads the tokens and with one that sends them all to the
    experts of a single shard; nothing is dropped."""
    ps = pmesh.ParallelSpec.parse(spec)
    if ps.world_size > len(jax.devices()):
        pytest.skip(f"needs {ps.world_size} devices")
    mesh = pmesh.make_mesh(ps)
    rng = np.random.RandomState(11)
    D, F, E, B, T = 16, 32, 8, 8, 8
    moe = MoEConfig(num_experts=E, top_k=2, capacity_factor=None)
    x = jnp.asarray(rng.randn(B, T, D).astype(np.float32))
    for skew in (False, True):
        lp = _layer_params(rng, D, F, E)
        if skew:  # every token picks experts 0 and 1
            lp["router"] = jnp.zeros_like(lp["router"])

        def loss_ep(lp):
            y, aux = moemod.moe_mlp(x, lp, moe, mesh=mesh)
            return jnp.sum(y * y) + aux["aux_total"], (y, aux)

        def loss_one(lp):
            y, aux = moemod.moe_mlp(x, lp, moe)
            return jnp.sum(y * y) + aux["aux_total"], (y, aux)

        (l_ep, (y_ep, a_ep)), g_ep = jax.value_and_grad(
            loss_ep, has_aux=True)(lp)
        (l_1, (y_1, a_1)), g_1 = jax.value_and_grad(
            loss_one, has_aux=True)(lp)
        assert float(a_ep["dropped_frac"]) == float(a_1["dropped_frac"]) == 0
        assert float(a_ep["routed_rows"]) == B * T * 2
        np.testing.assert_allclose(y_ep, y_1, rtol=1e-5, atol=1e-6)
        assert float(l_ep) == pytest.approx(float(l_1), rel=1e-5)
        for name in g_1:
            np.testing.assert_allclose(g_ep[name], g_1[name], rtol=2e-4,
                                       atol=1e-6, err_msg=name)
        # the einsum oracle, dropless, agrees too
        y_o, a_o = moemod.moe_mlp(x, lp, moe, dispatch="einsum")
        np.testing.assert_allclose(y_o, y_1, rtol=1e-5, atol=1e-6)
        assert float(a_o["dropped_frac"]) == 0.0
