"""chip_smoke.py's contract, checked without a chip and without launching an
experiment: the final line's exact shape, the failure path end to end on the
CPU, how the trainer's log is judged, the one compile-cache helper, and the
launcher pieces the smoke relies on (platform from JAX_PLATFORMS alone, no
model on a fallback device, one device per generation replica).
"""

import json
import os
import subprocess
import sys
import types

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.mark.parametrize("ok,device,want", [
    (True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
            "coords": [0, 0, 0]},
     {"ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1}}),
    (False, {"platform": "cpu", "kind": "cpu", "count": 1},
     {"ok": False, "device": {"platform": "cpu", "kind": "cpu",
                              "count": 1}}),
    # failed before any child reported a device
    (False, None,
     {"ok": False, "device": {"platform": None, "kind": None,
                              "count": None}}),
])
def test_final_line_has_exactly_the_contract_keys(ok, device, want):
    line = chip_smoke.final_line(ok, device)
    assert "\n" not in line
    got = json.loads(line)
    assert got == want
    assert list(got) == ["ok", "device"]
    assert list(got["device"]) == ["platform", "kind", "count"]


def test_chip_smoke_fails_on_cpu_with_ok_false_last():
    """No accelerator: non-zero exit, the last stdout line is the contract
    object with ok=false, nothing after it, no child left running."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--layers", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert r.stdout.endswith("\n")
    lines = r.stdout.splitlines()
    last = json.loads(lines[-1])
    assert list(last) == ["ok", "device"]
    assert list(last["device"]) == ["platform", "kind", "count"]
    assert last["ok"] is False
    assert (last["device"]["platform"], last["device"]["kind"]) == \
        ("cpu", "cpu")
    # earlier lines are JSON records too; the failure names its cause
    earlier = [json.loads(ln) for ln in lines[:-1]]
    assert "no TPU" in earlier[-1]["error"]
    assert subprocess.run(["pgrep", "-f", "chip_smoke.py --phase"],
                          capture_output=True).returncode == 1


def _trainer_log(train_dispatch):
    report = {
        "worker": "trainer0", "platform": "tpu",
        "device_kind": "TPU v5 lite", "device_count": 1,
        "local_devices": [{"id": 0, "coords": [0, 0, 0],
                           "bytes_in_use": 1, "peak_bytes_in_use": 2}],
        "attention": {"generate": {"fallback": 1},
                      "train": train_dispatch},
        "compile_cache": {"dir": "/c", "hits": 0, "misses": 9,
                          "compile_secs": 1.0},
        "native_ops": "g++",
    }
    lines = ["20260926-14:00:00.000 areal.system.trainer INFO: "
             "device_report " + json.dumps({**report, "stage": "setup"})]
    for i in (1, 2, 3):
        lines.append(
            f"20260926-14:00:0{i}.000 areal.system.master INFO: step {i} "
            f"epoch 0 (1.50s): actor_train/actor_loss=-0.0{i} "
            "actor_train/grad_norm=1.5 actor_train/importance_weight=1 "
            "actor_train/mean_kl=1e-05 actor_train/n_action_tokens=7440 "
            "timeperf/e2e=1.5"
        )
    lines += ["x areal.system.trainer INFO: device_report "
              + json.dumps({**report, "stage": "exit"}),
              "x areal.quickstart INFO: experiment finished: steps=3"]
    return "\n".join(lines)


def test_trainer_log_is_judged_by_device_and_kernel():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    rec = chip_smoke.check_trainer(_trainer_log({"pallas": 2}), "sync_ppo",
                                   1, device)
    assert [s["actor_loss"] for s in rec["steps"]] == [-0.01, -0.02, -0.03]
    assert rec["hbm_peak_bytes"] == [2]
    # a reference fallback inside the train step fails the phase ...
    with pytest.raises(chip_smoke.PhaseFailed, match="attention"):
        chip_smoke.check_trainer(
            _trainer_log({"pallas": 2, "fallback": 1}), "sync_ppo", 1, device)
    # ... so does a non-finite loss, and a trainer on another device
    with pytest.raises(chip_smoke.PhaseFailed, match="actor_loss"):
        chip_smoke.check_trainer(
            _trainer_log({"pallas": 2}).replace("actor_loss=-0.02",
                                                "actor_loss=nan"),
            "sync_ppo", 1, device)
    with pytest.raises(chip_smoke.PhaseFailed, match="ran on"):
        chip_smoke.check_trainer(_trainer_log({"pallas": 2}), "sync_ppo", 1,
                                 dict(device, kind="TPU v4"))


def test_compile_cache_helper_env_or_fixed_checkout_path(monkeypatch,
                                                         tmp_path):
    from areal_tpu.base import compile_watch as cw

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    assert cw.compilation_cache_dir() == str(tmp_path / "cc")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert cw.compilation_cache_dir() == os.path.join(REPO, ".jax_cache")
    assert "AREAL_COMPILATION_CACHE" not in open(cw.__file__).read()


def _exp_cfg(allocation_mode=""):
    return types.SimpleNamespace(
        mock_tokenizer=True, backend="tpu", fault_tolerance=None,
        allocation_mode=allocation_mode, n_nodes=1, n_gpus_per_node=8,
    )


def test_launcher_platform_follows_jax_platforms_not_tokenizer(monkeypatch):
    from areal_tpu.apps.launcher import LocalLauncher

    monkeypatch.delenv("JAX_PLATFORMS")
    assert LocalLauncher(_exp_cfg()).force_cpu is False
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert LocalLauncher(_exp_cfg()).force_cpu is True


def test_device_worker_refuses_a_fallback_device(libtpu_lock):
    """backend=tpu, JAX_PLATFORMS unset, no TPU: jax falls back to the CPU
    by itself — the worker must raise, with the device list, instead of
    running a model there."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.update(PYTHONPATH=REPO, TPU_LOG_DIR="disabled")
    code = ("import types; from areal_tpu.apps import launcher; "
            "launcher._child_init(types.SimpleNamespace(backend='tpu'), True)")
    with libtpu_lock():  # the child loads libtpu to look for a chip
        r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                           capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "backend=tpu but this worker got platform 'cpu'" in r.stderr
    assert "CpuDevice" in r.stderr


@pytest.mark.parametrize("mode,per", [("gen.d2+f2", 1), ("gen.d2t2+d2", 2)])
def test_gen_fleet_places_each_replica_on_its_own_devices(mode, per):
    from areal_tpu.apps.launcher import gen_replica_meshes

    devices = jax.devices()
    meshes = gen_replica_meshes(_exp_cfg(mode), 2, devices)
    for i, mesh in enumerate(meshes):
        assert list(mesh.devices.flatten()) == \
            devices[i * per:(i + 1) * per]
        assert mesh.shape["tp"] == per
    # fewer devices than replicas (a one-device CPU run): shared, un-meshed
    assert gen_replica_meshes(_exp_cfg(mode), 2, devices[:1]) == [None, None]
