"""Drift guard: the yardstick must not quietly measure a path users do
not run. The fleet the rollout driver builds is compared with what
``launcher.gen_fleet_entry`` builds from the same experiment on a tiny
saved checkpoint; the train driver's backend with what a trainer worker
builds from the same experiment."""

import dataclasses

import jax
import numpy as np
import pytest

from benchmark import driverlib as dl
from benchmark import weights


def tiny_spec(tmp_path, driver):
    """The toy-size spec of the first traffic mix that ``driver`` drives,
    whether or not a shipped cell uses it."""
    import os

    from benchmark import harness, rehearse

    bench = harness.load_benchmark()
    mixes = sorted(f[:-5] for f in os.listdir(
        os.path.join(harness.BENCH_DIR, "traffic")) if f.endswith(".json"))
    mix = next(m for m in mixes
               if harness.load_traffic(m)["driver"] == driver)
    shipped = [w for w in bench["workloads"] if w["traffic"] == mix]
    cell = shipped[0] if shipped else {
        "name": "unshipped." + mix, "config": bench["configs"][0]["name"],
        "traffic": mix, "chips": 1, "why": "test only"}
    spec = rehearse.tiny_spec(cell["name"], trace=0, seconds=1.0,
                              unshipped=None if shipped else cell)
    spec["out"] = str(tmp_path)
    spec["config"]["vocab_size"] = 300
    return spec


class _Stop(Exception):
    pass


def test_rollout_fleet_is_composed_as_gen_fleet_entry_composes_it(
        tmp_path, monkeypatch):
    from areal_tpu.apps import launcher
    from areal_tpu.models import hf
    from areal_tpu.system import generation_server, gserver_manager
    from benchmark.drivers import rollout

    spec = tiny_spec(tmp_path, "rollout")
    model_cfg = weights.model_config(spec["config"])
    params = weights.make_params(model_cfg, 1, dtype="float32")
    ckpt = str(tmp_path / "ckpt")
    hf.save_hf_checkpoint(jax.device_get(params), model_cfg, ckpt)

    seen = {"servers": [], "manager": None}

    class Server:
        def __init__(self, cfg, mcfg, p, mesh=None):
            seen["servers"].append((cfg, mcfg, p, mesh))

        async def start(self):
            return "http://x"

    class Manager:
        def __init__(self, cfg):
            seen["manager"] = cfg

        async def start(self):
            raise _Stop

    monkeypatch.setattr(generation_server, "GenerationServer", Server)
    monkeypatch.setattr(gserver_manager, "GserverManager", Manager)
    exp = dl.build_experiment(spec, name_resolve=True)
    rollout.compose_fleet(exp, model_cfg, params)
    ours = dict(seen)
    seen.update(servers=[], manager=None)
    exp.actor.path = ckpt
    setup = exp.initial_setup()
    with pytest.raises(_Stop):
        launcher.gen_fleet_entry(exp, setup["gen_servers"],
                                 setup["gserver_manager"])
    assert len(ours["servers"]) == len(seen["servers"]) == 1
    (cfg_a, m_a, p_a, mesh_a), (cfg_b, m_b, p_b, mesh_b) = (
        ours["servers"][0], seen["servers"][0])
    assert dataclasses.asdict(cfg_a) == dataclasses.asdict(cfg_b)
    assert dataclasses.asdict(ours["manager"]) == dataclasses.asdict(
        seen["manager"])
    assert m_a == m_b  # the model config a checkpoint's config.json gives
    assert (mesh_a is None) == (mesh_b is None)
    la, lb = hf.flatten_pytree(p_a), hf.flatten_pytree(p_b)
    assert set(la) == set(lb)
    for k in la:
        assert la[k].shape == lb[k].shape, k
        assert np.dtype(la[k].dtype) == np.dtype(lb[k].dtype), k
        np.testing.assert_allclose(np.asarray(la[k]), np.asarray(lb[k]),
                                   atol=1e-6)


def test_train_backend_is_the_trainer_workers(tmp_path):
    import areal_tpu.backend.jax_train  # noqa: F401
    from areal_tpu.api.model import make_backend
    from benchmark.drivers import train

    spec = tiny_spec(tmp_path, "train")
    exp = dl.build_experiment(spec)
    model, ifaces, tcfg = train.build_model(spec, exp)
    rc = exp.build_trainer_config(async_mode=True).models["actor"]
    theirs = make_backend(rc.backend, **{"train": rc.train,
                                         **rc.backend_args})
    engine = model.module
    # what a default experiment gives a trainer: bf16 compute, full remat,
    # f32 masters and f32 Adam moments
    assert str(engine.compute_dtype) == theirs.compute_dtype == "bfloat16"
    assert engine.remat == theirs.remat is True
    assert engine.length_bucket == theirs.length_bucket
    assert engine.attn_impl == theirs.attn_impl == "auto"
    leaves = jax.tree_util.tree_leaves(engine.params)
    assert {str(x.dtype) for x in leaves} == {"float32"}
    moments = [x for x in jax.tree_util.tree_leaves(engine.opt_state)
               if getattr(x, "ndim", 0) >= 1]
    assert moments and {str(x.dtype) for x in moments} == {"float32"}
    assert set(ifaces) == {"actor_inf", "actor_train"}
    hp = ifaces["actor_train"].hp
    assert hp.use_decoupled_loss and hp.disable_value and hp.kl_ctl == 0
    assert exp.actor_train.mb_spec.max_tokens_per_mb is not None
    assert "ref" not in tcfg.models  # GRPO with kl_ctl=0: no reference
