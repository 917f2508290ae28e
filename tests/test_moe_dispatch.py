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

    (lg, ag), gg = jax.jit(jax.value_and_grad(
        _loss_fn(moe, x, mask, "grouped"), has_aux=True))(lp)
    (le, ae), ge = jax.jit(jax.value_and_grad(
        _loss_fn(moe, x, mask, "einsum"), has_aux=True))(lp)

    assert float(lg) == pytest.approx(float(le), rel=1e-5, abs=1e-6)
    assert float(ag["dropped_frac"]) == pytest.approx(
        float(ae["dropped_frac"]), abs=1e-6)
    if cf <= 0.5:  # the tight-capacity cases must actually drop
        assert float(ag["dropped_frac"]) > 0.0
    for name in gg:
        np.testing.assert_allclose(
            np.asarray(gg[name]), np.asarray(ge[name]),
            rtol=2e-4, atol=1e-6, err_msg=f"grad mismatch on {name}")


def test_grouped_is_default_and_the_oracle_is_asked_for_by_name():
    assert moemod.resolve_dispatch(None) == "grouped"
    assert moemod.resolve_dispatch("grouped") == "grouped"
    assert moemod.resolve_dispatch("einsum") == "einsum"
    with pytest.raises(ValueError, match="unknown MoE dispatch"):
        moemod.resolve_dispatch("scatter")


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

    (l_ep, a_ep), g_ep = jax.jit(
        jax.value_and_grad(loss_ep, has_aux=True))(lp)
    (l_ref, a_ref), g_ref = jax.jit(jax.value_and_grad(
        _loss_fn(moe, x, None, "grouped"), has_aux=True))(lp)

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

        (l_ep, (y_ep, a_ep)), g_ep = jax.jit(jax.value_and_grad(
            loss_ep, has_aux=True))(lp)
        (l_1, (y_1, a_1)), g_1 = jax.jit(jax.value_and_grad(
            loss_one, has_aux=True))(lp)
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


# ---- the sorted pass's row bound (moe.sorted_rows, moe._bounded_pass) ----

def _bound_case(where, rng):
    """(moe config, layer params, x, mesh) of a layer whose sorted pass is
    bounded: one chip's share of a group (16 of 64 experts held), or an
    expert-parallel mesh."""
    D, F = 16, 32
    if where == "share":
        E, held, k, B, T = 64, 16, 4, 2, 256  # 2048 entries, bound 1024
        lp = _layer_params(rng, D, F, held)
        lp["router"] = jnp.asarray(rng.randn(D, E).astype(np.float32) * 0.5)
        kw = dict(num_experts=held, router_experts=E, first_expert=0)
        mesh = None
    else:
        ps = pmesh.ParallelSpec.parse(where)
        if ps.world_size > len(jax.devices()):
            pytest.skip(f"needs {ps.world_size} devices")
        mesh = pmesh.make_mesh(ps)
        E, k, B, T = 8, 2, 8, 256  # a source's 1024 (e4) / 2048 (e2) entries
        lp = _layer_params(rng, D, F, E)
        kw = dict(num_experts=E)
    x = jnp.asarray(rng.randn(B, T, D).astype(np.float32))
    return dict(kw, top_k=k), lp, x, mesh


def _run_layer(moe, lp, x, mesh):
    def loss(lp, x):
        y, aux = moemod.moe_mlp(x, lp, moe, mesh=mesh)
        return jnp.sum(y * y) + aux["aux_total"], (y, aux)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(
        lp, x)


@pytest.mark.parametrize("drop", [False, True], ids=["dropless", "capacity"])
@pytest.mark.parametrize("where", ["share", "e2", "e4"])
def test_bounded_pass_equals_the_whole_pass(where, drop, monkeypatch):
    """The pass on the rows a shard can expect gives what the pass on the
    whole sorted buffer gives — output, loss, every gradient, the dropped
    share — to float32 rounding, dropless and with a capacity (whose slots
    are counted over all the entries). Under expert parallelism the bound
    is held back (moe._dispatch_ep): whatever the head-room, its passes
    run whole, with no ``cond`` and no count of passes."""
    rng = np.random.RandomState(23)
    kw, lp, x, mesh = _bound_case(where, rng)
    # a capacity that drops: a share's slots are reckoned over the held
    # experts, which see a quarter of the entries
    cf = None if not drop else 0.2 if where == "share" else 0.75
    moe = MoEConfig(capacity_factor=cf, aux_loss_coeff=1e-2, **kw)
    (l_b, (y_b, a_b)), g_b = _run_layer(moe, lp, x, mesh)
    if mesh is None:
        assert float(a_b["passes"]) == 1
        assert float(a_b["full_passes"]) == 0  # the live rows fit
    else:
        assert "passes" not in a_b and "full_passes" not in a_b
        assert "cond" not in str(jax.make_jaxpr(
            lambda lp, x: moemod.moe_mlp(x, lp, moe, mesh=mesh)[0])(lp, x))
    monkeypatch.setattr(moemod, "_ROW_HEADROOM", 1e9)  # no bound
    (l_w, (y_w, a_w)), g_w = _run_layer(moe, lp, x, mesh)
    assert "passes" not in a_w and "full_passes" not in a_w
    np.testing.assert_allclose(y_b, y_w, rtol=1e-6, atol=1e-7)
    assert float(l_b) == pytest.approx(float(l_w), rel=1e-6)
    assert float(a_b["dropped_frac"]) == float(a_w["dropped_frac"])
    if drop:
        assert float(a_b["dropped_frac"]) > 0
    for (path, b), w in zip(jax.tree_util.tree_leaves_with_path(g_b),
                            jax.tree.leaves(g_w)):
        np.testing.assert_allclose(b, w, rtol=1e-5, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("where", ["share", "e4"])
def test_a_skewed_router_takes_the_whole_pass_and_loses_no_row(where):
    """Every token chooses experts held on ONE shard: on a share its live
    rows do not fit the bound, so its pass runs on the whole buffer —
    counted in ``full_passes`` — and the layer still equals the replicated
    one (every expert on one shard, no bound), output and gradients;
    nothing drops. Nor under expert parallelism, whose passes are whole."""
    rng = np.random.RandomState(29)
    kw, lp, x, mesh = _bound_case(where, rng)
    moe = MoEConfig(capacity_factor=None, **kw)
    lp["router"] = jnp.zeros_like(lp["router"])  # top-k of ties: 0..k-1
    (l_b, (y_b, a_b)), (g_b, gx_b) = _run_layer(moe, lp, x, mesh)
    if mesh is None:
        assert float(a_b["passes"]) == float(a_b["full_passes"]) == 1
    else:
        assert "full_passes" not in a_b
    assert float(a_b["dropped_frac"]) == 0
    # the replicated layer: all the router's experts held, so no bound
    E = lp["router"].shape[1]
    full = dict(lp)
    if where == "share":  # the experts held elsewhere: chosen by no token
        pad = E - lp["e_gate"].shape[0]
        for name in ("e_gate", "e_up", "e_down"):
            full[name] = jnp.concatenate(
                [lp[name], jnp.ones((pad,) + lp[name].shape[1:])])
    rep = MoEConfig(capacity_factor=None, num_experts=E, top_k=kw["top_k"])
    (l_r, (y_r, a_r)), (g_r, gx_r) = _run_layer(rep, full, x, None)
    assert "full_passes" not in a_r
    assert float(a_b["routed_rows"]) == float(a_r["routed_rows"])
    if where == "share":
        assert float(a_b["local_rows"]) == float(a_r["routed_rows"])
    np.testing.assert_allclose(y_b, y_r, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gx_b, gx_r, rtol=2e-4, atol=1e-6)
    for name in g_b:
        np.testing.assert_allclose(
            g_b[name], g_r[name][:g_b[name].shape[0]]
            if name != "router" else g_r[name],
            rtol=2e-4, atol=1e-6, err_msg=name)


def test_sorted_rows_rule():
    """Twice the held share, in whole row tiles; the buffer itself where
    that is no shorter: every expert held, or a buffer of under two tiles
    (a decode step's)."""
    assert moemod._ROW_HEADROOM == 2.0 and moemod._ROW_TILE == 512
    assert moemod.sorted_rows(53248, 16, 64) == 26624  # Mellum 1x6656
    assert moemod.sorted_rows(48128, 16, 64) == 24064  # Mellum 1x6016
    assert moemod.sorted_rows(31744, 16, 64) == 15872  # OLMoE e4, 3968
    assert moemod.sorted_rows(3000, 16, 64) == 1536  # up to the tile
    assert moemod.sorted_rows(4096, 64, 64) == 4096  # every expert held
    assert moemod.sorted_rows(4096, 32, 64) == 4096  # e2: twice a half
    assert moemod.sorted_rows(512, 16, 64) == 512  # 64 rows x 8: decode
    assert moemod.sorted_rows(1000, 1, 64) == 512


# ---- the bounded pass's combine: rows added into their tokens ----

_COMBINE_N, _COMBINE_K, _COMBINE_ROWS = 64, 4, 96  # M = 256 entries
# {case: (live entries, capacity)}: the live rows against the bound R = 96
# (one past it is the whole-buffer branch), every entry live on a skewed
# router (that branch adding all M rows), and a capacity that drops.
COMBINE_CASES = {
    "live0": (0, None), "liveR-1": (95, None), "liveR": (96, None),
    "liveR+1": (97, None), "skewed": (256, None), "capacity": (90, 10),
}


def _combine_inputs(kind, case, rng):
    """(xf, eid, gates, cap, (gate_w, up_w, down_w), act, k) of one pass
    over 4 held experts: gated ``silu`` experts reading the tokens
    themselves, or ungated ``relu2`` experts reading a latent source that
    holds rows past the tokens (as moe._whole_row_tiles leaves it)."""
    N, k, G, D, F = _COMBINE_N, _COMBINE_K, 4, 16, 32
    M = N * k
    live, cap = COMBINE_CASES[case]
    T = N if kind == "gated" else N + 24
    xf = jnp.asarray(rng.randn(T, D).astype(np.float32))
    groups = rng.randint(0, G, size=M)
    if case == "skewed":
        groups = np.where(rng.rand(M) < 0.9, 0, groups)
    eid = np.full(M, G)
    here = rng.permutation(M)[:live]
    eid[here] = groups[here]
    gates = jnp.asarray(rng.rand(M).astype(np.float32))
    w = [jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.2)
         for shape in ((G, D, F), (G, D, F), (G, F, D))]
    if kind == "latent":
        return (xf, jnp.asarray(eid), gates, cap, [None] + w[1:],
                moemod.relu2, k)
    return xf, jnp.asarray(eid), gates, cap, w, jax.nn.silu, k


@pytest.mark.parametrize("case", list(COMBINE_CASES))
@pytest.mark.parametrize("kind", ["gated", "latent"])
def test_rows_added_into_tokens_equal_the_unpermuted_entries(kind, case):
    """The bounded pass's combine — the R rows it ran on, added into their
    tokens — against the whole pass's (``take(ys, argsort(order))
    .reshape(N, k, D).sum(1)``, what ``rows == M`` runs): the per-token
    sums, the entries kept and the gradient of every differentiable
    argument, to float32 rounding; a latent source's rows past the tokens
    are never read and take a zero gradient; the counts say which branch
    ran; and moe.combine_counts() says which combine was traced."""
    rng = np.random.RandomState(len(kind) * 100 + len(case))
    xf, eid, gates, cap, w, act, k = _combine_inputs(kind, case, rng)
    M, N, R = eid.shape[0], _COMBINE_N, _COMBINE_ROWS
    diff = [xf, gates] + [a for a in w if a is not None]

    def run(rows):
        def loss(xf, gates, *w):
            w = ([None] if kind == "latent" else []) + list(w)
            y, kept, counts = moemod._sorted_expert_ffn(
                xf, eid, gates, cap, *w, rows, act, k)
            return jnp.sum(jnp.sin(y)), (y, kept, counts)

        return jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(len(diff))), has_aux=True))(*diff)

    (_, (y_r, kept_r, counts)), g_r = run(R)
    (_, (y_e, kept_e, none)), g_e = run(M)
    live = COMBINE_CASES[case][0]
    assert none == {} and float(counts["passes"]) == 1
    assert float(counts["full_passes"]) == (live > R)
    assert y_r.shape == y_e.shape == (N, xf.shape[1])
    assert float(kept_r) == float(kept_e) <= live
    assert (float(kept_r) < live) == (cap is not None)
    np.testing.assert_allclose(y_r, y_e, rtol=1e-5, atol=1e-5)
    assert bool(jnp.any(y_e != 0)) == (live > 0)
    for name, r, e in zip(("xf", "gates", "w0", "w1", "w2"), g_r, g_e):
        np.testing.assert_allclose(r, e, rtol=1e-5, atol=1e-5, err_msg=name)
    assert not np.any(np.asarray(g_r[0][N:]))  # rows past the tokens
    counts = moemod.combine_counts()
    assert counts[(M, R, xf.shape[0], xf.shape[1])] == "rows"
    assert counts[(M, M, xf.shape[0], xf.shape[1])] == "entries"


def test_rows_are_added_in_float32_and_rounded_once():
    """22 bfloat16 rows a token: the bounded pass's sums are the rows
    summed in float32 and rounded once, to the bit — as the sum over ``k``
    of the whole pass is — where a running bfloat16 sum of the same rows
    is ulps off. Every row is exact in bfloat16 (eighths times a power of
    two through diagonal experts, unit gates), so that only the combine
    rounds."""
    rng = np.random.RandomState(22)
    N, k, G, D = 16, 22, 4, 16
    M = N * k
    bf = jnp.bfloat16
    x = rng.randint(-32, 33, size=(N, D)) / 8.0
    scale = np.array([0.5, 1.0, 2.0, 4.0])
    eid = np.full((N, k), G)
    eid[:8] = rng.randint(0, G, size=(8, k))  # 8 tokens, every choice here
    up_w = jnp.asarray(np.tile(np.eye(D), (G, 1, 1)), bf)
    down_w = jnp.asarray(scale[:, None, None] * np.eye(D), bf)

    def sums(rows):
        y, _, counts = moemod._sorted_expert_ffn(
            jnp.asarray(x, bf), jnp.asarray(eid.reshape(M)),
            jnp.ones((M,), jnp.float32), None, None, up_w, down_w, rows,
            act=lambda h: h, k=k)
        assert y.dtype == bf
        assert (rows == M) == (counts == {})
        return np.asarray(y.astype(jnp.float32))

    terms = x[:, None, :] * np.append(scale, 0.0)[eid][:, :, None]
    once = np.asarray(jnp.asarray(
        terms.astype(np.float32).sum(axis=1), bf).astype(jnp.float32))
    np.testing.assert_array_equal(sums(M // 2), once)
    np.testing.assert_array_equal(sums(M), once)
    running = jnp.zeros((N, D), bf)
    for j in range(k):
        running = running + jnp.asarray(terms[:, j], bf)
    assert np.any(np.asarray(running.astype(jnp.float32)) != once)


# ---- the live walk: moe._gather_live / moe._add_live ----

_WALK_STEP, _WALK_R, _WALK_T = 32, 112, 40  # 3.5 steps: the last starts early
WALK_LIVE = {"0": 0, "1": 1, "step-1": 31, "step": 32, "step+1": 33,
             "R-1": 111, "R": 112}


@pytest.mark.parametrize("layout", ["uniform", "skewed"])
@pytest.mark.parametrize("live", list(WALK_LIVE))
def test_the_live_walk_moves_the_live_rows_and_no_other(live, layout,
                                                        monkeypatch):
    """The two row movements of a bounded pass against ``jnp.take`` and
    ``.at[].add`` on the live rows alone: the gather reads every row, the
    add leaves out the rows past the live ones (NaN there reaches
    nothing) and sums in float32, rounded once; each one's VJP is the
    other's forward, so the gather's drops the dead rows' cotangent."""
    monkeypatch.setattr(moemod, "_WALK_ROWS", _WALK_STEP)
    rng = np.random.RandomState(51)
    n, (R, T, D) = WALK_LIVE[live], (_WALK_R, _WALK_T, 16)
    tok = rng.randint(0, T, size=R)
    if layout == "skewed":  # nine rows in ten are two tokens'
        tok = np.where(rng.rand(R) < 0.9, rng.randint(0, 2, size=R), tok)
    tok, n_live = jnp.asarray(tok), jnp.asarray(n, jnp.int32)
    xf = jnp.asarray(rng.randn(T, D), jnp.bfloat16)
    ys = jnp.asarray(rng.randn(R, D), jnp.bfloat16)
    head = (np.arange(R) < n)[:, None]

    gather = jax.jit(lambda xf: moemod._gather_live(T, xf, tok, n_live))
    add = jax.jit(lambda ys: moemod._add_live(T, ys, tok, n_live))
    xs, gather_vjp = jax.vjp(gather, xf)
    np.testing.assert_array_equal(xs, jnp.take(xf, tok, axis=0))
    want = jnp.zeros((T, D), jnp.float32).at[tok].add(
        jnp.where(head, ys, 0).astype(jnp.float32)).astype(jnp.bfloat16)
    y, add_vjp = jax.vjp(add, jnp.where(head, ys, jnp.nan))
    np.testing.assert_array_equal(y, want)
    np.testing.assert_array_equal(gather_vjp(ys)[0], want)
    np.testing.assert_array_equal(add_vjp(xf)[0], xs)
    assert int(moemod.walked_rows(R, n_live)) == min(
        -(-n // _WALK_STEP) * _WALK_STEP, R)


def test_walked_rows_lie_between_the_live_rows_and_the_bound():
    """``walked_rows`` of a bounded layer: whole steps of the walk, so at
    least the rows that landed here (``local_rows``) and at most the
    bound's (``sorted_rows``) where the pass fits it — the whole buffer's
    where it does not; an unbounded layer carries none."""
    rng = np.random.RandomState(37)
    kw, lp, x, _ = _bound_case("share", rng)
    moe = MoEConfig(capacity_factor=None, **kw)
    M = x.shape[0] * x.shape[1] * kw["top_k"]
    R = moemod.sorted_rows(M, kw["num_experts"], kw["router_experts"])
    _, aux = jax.jit(lambda lp, x: moemod.moe_mlp(x, lp, moe))(lp, x)
    assert float(aux["full_passes"]) == 0 and float(aux["bound_rows"]) == R
    assert float(aux["local_rows"]) <= float(aux["walked_rows"]) <= R < M
    assert float(aux["walked_rows"]) % min(moemod._WALK_ROWS, R) == 0
    lp["router"] = jnp.zeros_like(lp["router"])  # every entry lands here
    _, aux = jax.jit(lambda lp, x: moemod.moe_mlp(x, lp, moe))(lp, x)
    assert float(aux["full_passes"]) == 1 and float(aux["bound_rows"]) == M
    assert float(aux["local_rows"]) == float(aux["walked_rows"]) == M
    fn, lp, x = _unbounded_layer("all_held")
    assert "walked_rows" not in jax.eval_shape(fn, lp, x)[1]


# sha256 of the lowered text of the layer (output and aux) where the pass
# is NOT bounded, as the commit before the bound (9987316) lowers it.
UNBOUNDED_TEXT = {
    "all_held":
        "d395bda29aa182e9562395c8f29931a7f5cbef693e54ff11e9c2047872dcc9d0",
    "decode_sized":
        "52bc3dd40eacdadff10b4af21abcbfe9811c9bf64399ec051027996daf37b27c",
}


def _unbounded_layer(which):
    rng = np.random.RandomState(31)
    D, F = 16, 32
    if which == "all_held":  # one shard, every expert: 4096 entries
        moe = MoEConfig(num_experts=8, top_k=2, capacity_factor=None)
        lp = _layer_params(rng, D, F, 8)
        x = jnp.asarray(rng.randn(2, 1024, D).astype(np.float32))
    else:  # a share's decode step: 64 rows, a token each, 8 choices
        moe = MoEConfig(num_experts=16, router_experts=64, first_expert=16,
                        top_k=8, capacity_factor=None)
        lp = _layer_params(rng, D, F, 16)
        lp["router"] = jnp.asarray(rng.randn(D, 64).astype(np.float32))
        x = jnp.asarray(rng.randn(64, 1, D).astype(np.float32))
    return (lambda lp, x: moemod.moe_mlp(x, lp, moe)), lp, x


@pytest.mark.parametrize("which", list(UNBOUNDED_TEXT))
def test_an_unbounded_pass_is_the_parents_program(which):
    """Where the bound would reach the buffer there is no ``cond`` and no
    count of passes: the layer traces, forward and backward, without one,
    and lowers to the text it had before the bound existed."""
    import hashlib

    fn, lp, x = _unbounded_layer(which)
    _, aux = jax.eval_shape(fn, lp, x)
    assert "passes" not in aux and "full_passes" not in aux
    grad = jax.grad(lambda lp, x: jnp.sum(fn(lp, x)[0] ** 2))
    assert "cond" not in str(jax.make_jaxpr(grad)(lp, x))
    text = jax.jit(fn).lower(lp, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == UNBOUNDED_TEXT[which]


@pytest.mark.parametrize("entry", transformer.REMAT_ENTRIES)
def test_a_bounded_layer_holds_both_branches_under_every_entry(
        entry, monkeypatch):
    """The gradient of a model whose expert pass is bounded holds the
    grouped GEMMs of both row counts — twice the unbounded model's, under
    every remat entry alike (``ragged_dot`` is never kept), so the
    benchmark's count of expert passes stays true."""
    cfg = tiny_config(vocab_size=64, n_layers=2, moe=MoEConfig(
        num_experts=4, router_experts=16, top_k=2, capacity_factor=None))
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.ones((2, 1024), jnp.int32)
    pos = jnp.tile(jnp.arange(1024), (2, 1))

    def count():
        def loss(p):
            y, _ = transformer.forward(p, cfg, tokens, pos,
                                       segment_ids=jnp.ones_like(tokens),
                                       remat=entry, return_kv=False)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        return str(jax.make_jaxpr(jax.grad(loss))(params)).count(
            "ragged_dot")

    bounded = count()
    monkeypatch.setattr(moemod, "_ROW_HEADROOM", 1e9)
    assert bounded == 2 * count() > 0


def test_full_passes_reach_the_statistics_the_gauge_and_the_log(monkeypatch):
    """The engine's step tail: ``moe_passes`` / ``moe_full_passes`` /
    ``moe_walked_rows`` are sums
    over the step's micro-batches (never divided by their count), ride on
    ``train/finish_stats``, set ``train/moe_full_passes`` and, where not
    0, leave a warning in the trainer's log."""
    import types

    from areal_tpu.backend import jax_train
    from areal_tpu.base import telemetry

    def fetched(full):
        return {"loss": np.float32(1.0), "moe_routed_rows": np.float32(4096),
                "moe_passes": np.float32(8), "moe_full_passes": np.float32(full),
                "moe_local_rows": np.float32(1000),
                "moe_bound_rows": np.float32(2048),
                "moe_walked_rows": np.float32(1536),
                "moe_dropped_frac": np.float32(0.0),
                "moe_expert_load_ratio": np.float32(3.0)}

    attrs = jax_train._moe_step_stats(fetched(2), n_mbs=2)
    assert attrs["moe_passes"] == 8 and attrs["moe_full_passes"] == 2
    assert attrs["moe_routed_rows"] == 4096
    assert attrs["moe_local_rows"] == 1000 <= attrs["moe_walked_rows"] == 1536
    assert attrs["moe_expert_load_ratio"] == 1.5  # a mean over the two
    gauges, warned = {}, []
    monkeypatch.setattr(telemetry, "set_gauge", gauges.__setitem__)
    monkeypatch.setattr(jax_train.logger, "warning", warned.append)
    engine = types.SimpleNamespace(_EXPERT_LOAD_BUCKETS=(0.5, 1.0),
                                   moe_rows={})
    out = jax_train.JaxTrainEngine._finish_stats(engine, fetched(0), 2)
    assert gauges["train/moe_full_passes"] == 0 and not warned
    assert gauges["train/moe_walked_rows"] == out["moe_walked_rows"] == 1536
    out = jax_train.JaxTrainEngine._finish_stats(engine, fetched(2), 2)
    assert out["moe_full_passes"] == 2 and out["moe_passes"] == 8
    assert gauges["train/moe_full_passes"] == 2
    assert len(warned) == 1 and "2 of 8 expert passes" in warned[0]
    assert engine.moe_rows == {"local": 2000, "bound": 4096, "walked": 3072}
