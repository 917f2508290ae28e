"""System-fabric e2e: master + trainer in separate processes over ZMQ,
running the full sync-PPO DFG (gen → rew/ref/prox inf → actor train) with
weight publishing. The CPU analogue of the reference's
tests/experiments/test_math_ppo.py (run_test_exp)."""

import multiprocessing as mp
import os

import numpy as np
import pytest

from areal_tpu.api.data import MicroBatchSpec
from areal_tpu.api.dfg import (
    MFCDef,
    MFCInterfaceType,
    ModelInterfaceAbstraction,
    WeightUpdateHook,
    build_graph,
)
from areal_tpu.api.model import FinetuneSpec
from areal_tpu.base import name_resolve, names
from areal_tpu.base.testing import MockTokenizer, make_math_jsonl

EXP, TRIAL = "systest", "t0"


def _trainer_main(nr_root, data_path, realloc_dir):
    # runs in a spawned process that inherits JAX_PLATFORMS=cpu (conftest)
    from areal_tpu.base import name_resolve as nr

    nr.DEFAULT_REPO = nr.NfsNameRecordRepo(nr_root)
    import areal_tpu.algorithms.ppo  # noqa: F401 — register interfaces
    import areal_tpu.algorithms.reward  # noqa: F401
    import areal_tpu.backend.jax_train  # noqa: F401 — register backends
    import areal_tpu.datasets.jsonl  # noqa: F401 — register datasets
    from areal_tpu.system.trainer_worker import (
        MFCRuntimeConfig,
        ModelRoleConfig,
        TrainerWorker,
        TrainerWorkerConfig,
    )

    hp_args = {
        "ppo_n_minibatches": 2, "group_size": 2, "kl_ctl": 0.05,
        "disable_value": True, "group_adv_norm": True, "adv_norm": False,
        "use_decoupled_loss": True,
        "gen": {"max_new_tokens": 8},
    }
    # PPOActorInterface accepts hp or flat kwargs; gen passed as dict needs
    # conversion — interface_args carry a ready PPOHyperparameters.
    from areal_tpu.algorithms.ppo import PPOHyperparameters
    from areal_tpu.api.model import GenerationHyperparameters

    hp = PPOHyperparameters(
        gen=GenerationHyperparameters(max_new_tokens=8),
        ppo_n_minibatches=2, group_size=2, kl_ctl=0.05,
        disable_value=True, group_adv_norm=True, adv_norm=False,
        use_decoupled_loss=True,
    )
    backend_args = {
        "compute_dtype": "float32", "length_bucket": 16, "rows_bucket": 2,
        "seqs_bucket": 4,
        "optimizer": {"lr": 1e-3, "lr_scheduler_type": "constant",
                      "warmup_steps_proportion": 0.0},
    }
    from areal_tpu.backend.jax_train import OptimizerConfig

    backend_args["optimizer"] = OptimizerConfig(**backend_args["optimizer"])
    cfg = TrainerWorkerConfig(
        experiment=EXP, trial=TRIAL, handler="trainer",
        models={
            "actor": ModelRoleConfig(
                init={"tiny": {"vocab_size": 258, "seed": 0}},
                backend_args=backend_args),
            "ref": ModelRoleConfig(
                init={"tiny": {"vocab_size": 258, "seed": 0}},
                backend_args=backend_args, train=False),
            "rw": ModelRoleConfig(init={"null": True}, backend="null"),
        },
        mfcs={
            "actor_gen": MFCRuntimeConfig(
                interface="ppo_actor", interface_args={"hp": hp},
                model_name="actor"),
            "rew_inf": MFCRuntimeConfig(
                interface="rw_math_code",
                interface_args={"dataset_path": data_path, "group_size": 2},
                model_name="rw"),
            "ref_inf": MFCRuntimeConfig(
                interface="ref_logprob", model_name="ref"),
            "actor_inf": MFCRuntimeConfig(
                interface="ppo_actor", interface_args={"hp": hp},
                model_name="actor"),
            "actor_train": MFCRuntimeConfig(
                interface="ppo_actor", interface_args={"hp": hp},
                model_name="actor"),
        },
        dataset="math_code_prompt",
        dataset_args={"dataset_path": data_path},
        batch_size=4,
        ft_spec=FinetuneSpec(1, 8, 4),
        tokenizer=MockTokenizer(),
        realloc_dir=realloc_dir,
    )
    TrainerWorker(cfg).run()


def _build_dfg():
    traj_keys = ("packed_input_ids", "prompt_mask", "packed_logprobs",
                 "seq_no_eos_mask", "task_ids", "version_start",
                 "version_end")
    mfcs = [
        MFCDef(
            name="actor_gen", model_name="actor",
            interface_type=MFCInterfaceType.GENERATE,
            interface_impl=ModelInterfaceAbstraction("ppo_actor"),
            input_keys=("packed_prompts", "task_ids"),
            output_keys=traj_keys,
            n_seqs=4, mb_spec=MicroBatchSpec(max_tokens_per_mb=512),
        ),
        MFCDef(
            name="rew_inf", model_name="rw",
            interface_type=MFCInterfaceType.INFERENCE,
            interface_impl=ModelInterfaceAbstraction("rw_math_code"),
            input_keys=("packed_input_ids", "prompt_mask"),
            output_keys=("rewards",),
            n_seqs=8, mb_spec=MicroBatchSpec(),
        ),
        MFCDef(
            name="ref_inf", model_name="ref",
            interface_type=MFCInterfaceType.INFERENCE,
            interface_impl=ModelInterfaceAbstraction("ref_logprob"),
            input_keys=("packed_input_ids",),
            output_keys=("packed_ref_logprobs",),
            n_seqs=8, mb_spec=MicroBatchSpec(max_tokens_per_mb=512),
        ),
        MFCDef(
            name="actor_inf", model_name="actor",
            interface_type=MFCInterfaceType.INFERENCE,
            interface_impl=ModelInterfaceAbstraction("ppo_actor"),
            input_keys=("packed_input_ids",),
            output_keys=("prox_logprobs",),
            n_seqs=8, mb_spec=MicroBatchSpec(max_tokens_per_mb=512),
        ),
        MFCDef(
            name="actor_train", model_name="actor",
            interface_type=MFCInterfaceType.TRAIN_STEP,
            interface_impl=ModelInterfaceAbstraction("ppo_actor"),
            input_keys=("packed_input_ids", "prompt_mask", "packed_logprobs",
                        "rewards", "packed_ref_logprobs", "prox_logprobs",
                        "seq_no_eos_mask"),
            n_seqs=8, mb_spec=MicroBatchSpec(max_tokens_per_mb=512),
            post_hooks=[WeightUpdateHook(role="actor")],
        ),
    ]
    return build_graph(mfcs)


@pytest.mark.timeout(600)
def test_sync_ppo_through_fabric(tmp_path):
    nr_root = str(tmp_path / "nr")
    data_path = str(tmp_path / "math.jsonl")
    realloc_dir = str(tmp_path / "realloc")
    make_math_jsonl(data_path, n=8)

    name_resolve.DEFAULT_REPO = name_resolve.NfsNameRecordRepo(nr_root)

    ctx = mp.get_context("spawn")
    proc = ctx.Process(
        target=_trainer_main, args=(nr_root, data_path, realloc_dir),
        daemon=True,
    )
    proc.start()
    try:
        from areal_tpu.system.master_worker import (
            ExperimentSaveEvalControl,
            MasterWorker,
            MasterWorkerConfig,
        )

        master = MasterWorker(
            MasterWorkerConfig(
                experiment=EXP, trial=TRIAL, trainer_handler="trainer",
                train_batch_size=4,
                exp_ctrl=ExperimentSaveEvalControl(
                    total_train_epochs=10, benchmark_steps=2,
                ),
            ),
            _build_dfg(),
        )
        result = master.run()
        assert result["steps"] == 2
        for st in result["stats"]:
            assert np.isfinite(st["actor_train/actor_loss"])
            assert st["actor_train/n_action_tokens"] > 0
        # weight publishing happened: version key exists + ckpt on disk
        v = name_resolve.get(names.model_version(EXP, TRIAL, "actor"))
        assert int(v) >= 1
        # publish_weights writes the NATIVE pytree format; the json
        # sentinel is written last (models/hf.py save_native_checkpoint).
        assert os.path.exists(os.path.join(realloc_dir, "actor", v,
                                           "areal_tpu_native.json"))
        assert os.path.exists(os.path.join(realloc_dir, "actor", v,
                                           "model.safetensors"))
        proc.join(timeout=30)
        assert proc.exitcode == 0
    finally:
        if proc.is_alive():
            proc.terminate()
