"""Every caller of the engine trains through ONE grad program: what
``train_batch`` runs for SFT, reward modeling, the critic and the actor
under ``group_adv_norm`` is the program ``train_uniform`` runs for the
actor's device-prep path (backend/jax_train.py)."""

import collections

import pytest

from areal_tpu.algorithms.ppo import (
    PPOActorInterface,
    PPOCriticInterface,
    PPOHyperparameters,
)
from areal_tpu.algorithms.rw import RewardModelingInterface
from areal_tpu.algorithms.sft import SFTInterface
from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.api.train_config import CompileWatchConfig
from areal_tpu.base import compile_watch, telemetry
from areal_tpu.base.testing import MockTokenizer, make_sft_jsonl
from areal_tpu.datasets.jsonl import (
    PromptAnswerDataset,
    RewardModelingPairedDataset,
)

from test_interfaces_e2e import _make_model
from test_rw_interface import _paired_jsonl
from test_uniform_prep import _make_batch

# several micro-batches a step, so both the first (no carry) and the
# accumulating (carry donated) program are built
SPEC = MicroBatchSpec(max_tokens_per_mb=64)
HP = dict(ppo_n_minibatches=1, adv_norm=True, kl_ctl=0.0)


def _sft(tmp_path):
    path = tmp_path / "sft.jsonl"
    make_sft_jsonl(str(path), n=8)
    ds = PromptAnswerDataset(dataset_path=str(path),
                             tokenizer=MockTokenizer())
    data = SequenceSample.gather([ds[i] for i in range(8)])
    return _make_model("sft"), SFTInterface(), data


def _rm(tmp_path):
    path = tmp_path / "rw.jsonl"
    _paired_jsonl(str(path), n=4)
    ds = RewardModelingPairedDataset(dataset_path=str(path),
                                     tokenizer=MockTokenizer())
    data = SequenceSample.gather([ds[i] for i in range(4)])
    return (_make_model("rm", is_critic=True), RewardModelingInterface(),
            data)


def _critic(tmp_path):
    hp = PPOHyperparameters(**HP)
    return (_make_model("critic", vocab=128, is_critic=True),
            PPOCriticInterface(hp),
            _make_batch(with_values=True))


def _actor_group_adv_norm(tmp_path):
    hp = PPOHyperparameters(**HP, disable_value=True, group_adv_norm=True)
    return (_make_model("actor", vocab=128), PPOActorInterface(hp),
            _make_batch())


CALLERS = {"sft": _sft, "rm": _rm, "critic": _critic,
           "actor_group_adv_norm": _actor_group_adv_norm}


@pytest.mark.parametrize("caller", sorted(CALLERS))
def test_every_caller_runs_one_grad_program(caller, tmp_path):
    watch = compile_watch.configure(
        CompileWatchConfig(enabled=True), telemetry.TelemetryRegistry())
    try:
        model, iface, data = CALLERS[caller](tmp_path)
        eng = model.module
        calls = []
        real = eng.train_batch

        def recording(sample, spec, loss_fn, weight_fn, **kw):
            calls.append((sample, spec, loss_fn, weight_fn, kw))
            return real(sample, spec, loss_fn, weight_fn, **kw)

        eng.train_batch = recording
        iface.train_step(model, data, SPEC)
        (call,) = calls  # the caller did go through train_batch
        sample, spec, loss_fn, weight_fn, kw = call
        ub = eng.upload_uniform(sample, spec)
        assert ub.n_mbs > 1
        eng.train_uniform(
            ub, loss_fn, weight_fn,
            token_normalize_scope=kw.get("token_normalize_scope", "global"))
        # one grad program per (loss, grid, carry) ...
        per_loss_and_carry = collections.Counter(
            (key[0], key[1]) for key in eng._grad_fns if key[0] is loss_fn)
        assert per_loss_and_carry == {(loss_fn, False): 1, (loss_fn, True): 1}
        # ... compiled once each, under one label
        stats = watch.stats()
        assert [n for n in stats if n.startswith("train/grad")] == [
            "train/grad_sliced"]
        assert stats["train/grad_sliced"]["distinct_shapes"] == 2.0
    finally:
        compile_watch.shutdown()
