"""PPO actor / critic interfaces — the algorithm layer.

Parity target: ``realhf/impl/model/interface/ppo_interface.py`` —
``PPOActorInterface`` (:210; generate :301, inference :474 recomputing
proximal logprobs, train_step :527 with GAE + reward shaping + advantage
normalization + minibatch loop) and ``PPOCriticInterface`` (:984), plus the
value-normalization running moments (``realhf/impl/model/modules/rms.py``).

Data contract (all per-token keys full-length aligned to
``packed_input_ids``; see backend/microbatch.py):
 - ``packed_input_ids`` int32, ``prompt_mask`` (1 on prompt tokens)
 - ``packed_logprobs`` f32 — behaviour-policy logprob of token t at slot t
   (0 on prompt slots and each doc's first token)
 - ``prox_logprobs`` f32 — recomputed under the trainer's current policy
   (decoupled PPO; produced by actor ``inference``)
 - ``packed_ref_logprobs`` f32 — reference-policy logprobs (KL penalty)
 - ``values`` f32 — critic values (denormalized; produced by critic
   ``inference``), absent/zero when ``disable_value`` (GRPO)
 - ``rewards`` f32 [1/sample] — task score; ``seq_no_eos_mask`` f32
   [1/sample] — 1.0 when generation was truncated (no EOS)
 - ``task_ids`` int32 [1/sample]

Deviation from the reference, by design: generated groups are FLATTENED into
independent samples (ids "qid@k", metadata ``group``) rather than grouped
seqlens inside one sample — packing/attention masks stay per-document and
GRPO group statistics use the metadata instead.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.algorithms import ppo_functional as F
from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.models.moe import SUMMED_AUX
from areal_tpu.api.model import (
    GenerationHyperparameters,
    Model,
    ModelInterface,
    register_interface,
)
from areal_tpu.backend import microbatch as mbu
from areal_tpu.base import logging, telemetry
from areal_tpu.models import packing

logger = logging.getLogger("algorithms.ppo")


@dataclasses.dataclass
class PPOHyperparameters:
    """Reference cli_args.py:597 (PPOHyperparameters)."""

    gen: GenerationHyperparameters = dataclasses.field(
        default_factory=GenerationHyperparameters
    )
    ppo_n_minibatches: int = 4
    eps_clip: float = 0.2
    c_clip: Optional[float] = None
    value_eps_clip: float = 0.2
    early_stop_imp_ratio: float = 5.0
    reward_output_scaling: float = 1.0
    reward_output_bias: float = 0.0
    max_reward_clip: float = 20.0
    mask_no_eos_with_zero: bool = False
    discount: float = 1.0
    gae_lambda: float = 1.0
    adv_norm: bool = True
    kl_ctl: float = 0.1
    use_adaptive_kl_ctl: bool = False
    adaptive_kl_target: float = 6.0
    adaptive_kl_horizon: float = 10000.0
    disable_value: bool = False  # GRPO: no critic
    value_norm: bool = True
    value_norm_beta: float = 0.99995
    value_norm_eps: float = 1e-5
    group_size: int = 1
    group_adv_norm: bool = False
    use_decoupled_loss: bool = False
    behav_imp_weight_cap: Optional[float] = None
    recompute_logprob: bool = False


class RunningMoments:
    """EMA mean/std for value normalization (reference rms.py)."""

    def __init__(self, beta: float = 0.99995, eps: float = 1e-5):
        self.beta = beta
        self.eps = eps
        self.mean = 0.0
        self.mean_sq = 1.0
        self._initialized = False

    def update(self, x: np.ndarray, mask: np.ndarray) -> None:
        m = mask.astype(bool)
        if m.sum() == 0:
            return
        bm, bsq = float(x[m].mean()), float((x[m] ** 2).mean())
        if not self._initialized:
            self.mean, self.mean_sq = bm, bsq
            self._initialized = True
        else:
            # EMA of mean and mean-square (reference rms.py): the variance
            # E[x^2]-E[x]^2 then includes batch-mean drift.
            self.mean = self.beta * self.mean + (1 - self.beta) * bm
            self.mean_sq = self.beta * self.mean_sq + (1 - self.beta) * bsq

    @property
    def var(self) -> float:
        return max(self.mean_sq - self.mean**2, self.eps)

    def normalize(self, x):
        return (x - self.mean) / np.sqrt(self.var + self.eps)

    def denormalize(self, x):
        return x * np.sqrt(self.var + self.eps) + self.mean

    def state_dict(self):
        return {
            "mean": self.mean, "mean_sq": self.mean_sq,
            "initialized": self._initialized,
        }

    def load_state_dict(self, d):
        self.mean, self.mean_sq = d["mean"], d["mean_sq"]
        self._initialized = d["initialized"]


# ---------------- shared prep ----------------

def _action_mask(grids: Dict[str, np.ndarray]) -> np.ndarray:
    """Host-side view of the shared loss mask (ppo_functional)."""
    return np.asarray(
        F.action_token_mask(grids["segment_ids"], grids["prompt_mask"])
    )


def compute_advantages_and_returns(
    sample: SequenceSample, hp: PPOHyperparameters, kl_coef: float
) -> Dict[str, np.ndarray]:
    """Full-batch grid pass: KL-shaped token rewards → GAE. Returns packed
    1-D arrays keyed advantages/returns/kl_rewards plus scalar stats.

    Mirrors reference train_step pre-processing (ppo_interface.py:560-690):
    sparse task reward on the last token, −kl_coef·KL(π_behav‖π_ref)
    everywhere, GAE over values (zeros under GRPO)."""
    mb = mbu.make_microbatch(sample, length_bucket=64, rows_bucket=1, seqs_bucket=1)
    g = mb.grids
    amask = _action_mask(g)
    behav = g["packed_logprobs"]
    ref = g.get("packed_ref_logprobs", np.zeros_like(behav))
    kl = (behav - ref) * amask  # k1 estimator, same as reference
    values = g.get("values", np.zeros_like(behav)) * (g["segment_ids"] > 0)

    score = np.asarray(sample.data["rewards"], np.float32).reshape(-1)
    no_eos = (
        np.asarray(sample.data["seq_no_eos_mask"]).reshape(-1) > 0
        if "seq_no_eos_mask" in sample.keys
        else np.zeros(sample.bs, bool)
    )
    if hp.mask_no_eos_with_zero:
        score = np.where(no_eos, 0.0, score)
    n = mb.n_seqs
    # KL-only penalty (this IS the logged kl_rewards key, as in the
    # reference where it is cloned BEFORE the task score lands).
    kl_rw = (-kl_coef * kl * amask).astype(np.float32)
    tok_score = np.clip(
        (score - hp.reward_output_bias) * hp.reward_output_scaling,
        -hp.max_reward_clip, hp.max_reward_clip,
    )
    rewards = kl_rw.copy()
    rewards[mb.seq_rows[:n], mb.seq_last_cols[:n]] += tok_score
    # Reference value alignment (pygae1d_nolp_misalign; ppo_interface.py:
    # 575-579): the baseline for the action at slot t is V at slot t−1 (the
    # pre-action state), so δ_t = r_t + γ·V_t − V_{t−1}. In the grid layout
    # that is gae_grid over right-shifted values, whose internal v_next[t]
    # = v_shifted[t+1] = V_t.
    v_prev = np.asarray(F.shift_right_in_doc(values, g["segment_ids"]))
    # The last action's next-value is V at the final token, kept only when
    # generation was truncated (no EOS): the reference both zeroes the EOS
    # value and multiplies by the bootstrap mask — one product covers both.
    boot = np.zeros_like(values)
    boot[mb.seq_rows[:n], mb.seq_last_cols[:n]] = (
        values[mb.seq_rows[:n], mb.seq_last_cols[:n]] * no_eos
    )
    # GAE over action tokens only: restrict the segment grid to them so
    # prompt positions neither receive advantage nor relay the recursion
    # (action slots are a contiguous suffix of each doc, so restricting
    # changes nothing the actor loss reads). v_prev at the first action slot
    # still holds the last-prompt-slot value — shift BEFORE restricting.
    act_seg = np.where(amask, g["segment_ids"], 0)
    # One jitted dispatch instead of gae_grid's ~20 eager device ops.
    adv, ret = _gae_grid_jit(
        jnp.asarray(rewards), jnp.asarray(v_prev), jnp.asarray(act_seg),
        jnp.asarray(boot), hp.discount, hp.gae_lambda,
    )
    adv, ret = np.asarray(adv), np.asarray(ret)
    out = {}
    for key, grid in (("advantages", adv), ("returns", ret), ("kl_rewards", kl_rw)):
        out[key] = np.concatenate(
            mbu.scatter_back([mb], [grid], sample.bs)
        ).astype(np.float32)
    out["_mean_kl"] = float(kl.sum() / max(amask.sum(), 1))
    return out


@functools.partial(jax.jit, static_argnums=(4, 5))
def _gae_grid_jit(rewards, v_prev, act_seg, boot, gamma, lam):
    return F.gae_grid(
        rewards, v_prev, act_seg, bootstrap=boot, gamma=gamma, lam=lam
    )


def make_advantage_prep(hp: PPOHyperparameters):
    """Device-side advantage pipeline over an uploaded UniformBatch: the
    jnp mirror of compute_advantages_and_returns + normalize_advantages,
    fused into ONE dispatch with no host round trip (grids stay on device
    for the grad steps). Global advantage whitening only — group_adv_norm
    keeps the host path."""

    @jax.named_scope("gae")
    def prep(grids, seq, R, scalars):
        seg = grids["segment_ids"]
        amask = F.action_token_mask(seg, grids["prompt_mask"])
        amf = amask.astype(jnp.float32)
        behav = grids["packed_logprobs"]
        ref = grids.get("packed_ref_logprobs", jnp.zeros_like(behav))
        kl = (behav - ref) * amf
        values = grids.get("values", jnp.zeros_like(behav)) * (seg > 0)

        score = seq["rewards"].astype(jnp.float32)  # [n_mbs, S]
        no_eos = (
            seq["seq_no_eos_mask"] > 0
            if "seq_no_eos_mask" in seq
            else jnp.zeros_like(score, bool)
        )
        if hp.mask_no_eos_with_zero:
            score = jnp.where(no_eos, 0.0, score)
        tok_score = jnp.clip(
            (score - hp.reward_output_bias) * hp.reward_output_scaling,
            -hp.max_reward_clip, hp.max_reward_clip,
        )
        # Flatten [n_mbs, S] sequence coordinates into the [n_mbs*R, L] grid.
        n_mbs = seq["seq_rows"].shape[0]
        mb_off = (jnp.arange(n_mbs)[:, None] * R)
        rows_f = (seq["seq_rows"] + mb_off).reshape(-1)
        lasts_f = seq["seq_last_cols"].reshape(-1)
        valid_f = seq["seq_mask"].reshape(-1).astype(jnp.float32)

        kl_rw = -scalars["kl_coef"] * kl * amf
        rewards_grid = kl_rw.at[rows_f, lasts_f].add(
            tok_score.reshape(-1) * valid_f
        )
        v_prev = F.shift_right_in_doc(values, seg)
        boot = jnp.zeros_like(values).at[rows_f, lasts_f].add(
            values[rows_f, lasts_f]
            * no_eos.reshape(-1).astype(jnp.float32) * valid_f
        )
        act_seg = jnp.where(amask, seg, 0)
        adv, ret = F.gae_grid(
            rewards_grid, v_prev, act_seg, bootstrap=boot,
            gamma=hp.discount, lam=hp.gae_lambda,
        )
        out_scalars = {
            "_mean_kl": kl.sum() / jnp.maximum(amf.sum(), 1.0),
            # Advantage scale BEFORE whitening (post-norm it is ~1 by
            # construction): a collapsing or exploding raw advantage is a
            # reward/value-pipeline divergence signature the sentinel
            # watches as train/adv_scale.
            "_adv_scale": jnp.sum(jnp.abs(adv) * amf)
                          / jnp.maximum(amf.sum(), 1.0),
        }
        if hp.adv_norm:
            adv = F.masked_normalization(adv, amask)
        return (
            {"advantages": adv, "returns": ret, "kl_rewards": kl_rw},
            out_scalars,
        )

    return prep


def _group_keys(sample: SequenceSample) -> List[str]:
    if "group" in sample.metadata:
        return [str(x) for x in sample.metadata["group"]]
    return [str(i).rsplit("@", 1)[0] for i in sample.ids]


def normalize_advantages(
    sample: SequenceSample, hp: PPOHyperparameters
) -> None:
    """In-place advantage whitening: global, or per prompt-group (GRPO)."""
    adv = sample.data["advantages"]
    amask_packed = (
        (1 - np.asarray(sample.data["prompt_mask"])) > 0
    )  # includes doc-first token; its adv is 0 anyway
    if hp.group_adv_norm:
        groups = _group_keys(sample)
        offs = sample.offsets("advantages")
        lens = [int(x) for x in sample.total_lens("advantages")]
        for gkey in set(groups):
            idx = [i for i, g in enumerate(groups) if g == gkey]
            sel = np.concatenate(
                [np.arange(offs[i], offs[i] + lens[i]) for i in idx]
            )
            m = amask_packed[sel]
            vals = adv[sel]
            mu = vals[m].mean() if m.any() else 0.0
            sd = vals[m].std() + 1e-5
            adv[sel] = np.where(m, (vals - mu) / sd, 0.0)
    else:
        m = amask_packed
        mu = adv[m].mean() if m.any() else 0.0
        sd = adv[m].std() + 1e-5
        sample.data["advantages"] = np.where(m, (adv - mu) / sd, 0.0).astype(
            np.float32
        )


# ---------------- actor ----------------

class PPOActorInterface(ModelInterface):
    def __init__(self, hp: Optional[PPOHyperparameters] = None, **kw):
        self.hp = hp or PPOHyperparameters(**kw)
        if self.hp.use_adaptive_kl_ctl:
            self.kl_ctl = F.AdaptiveKLController(
                self.hp.kl_ctl, self.hp.adaptive_kl_target, self.hp.adaptive_kl_horizon
            )
        else:
            self.kl_ctl = F.FixedKLController(self.hp.kl_ctl)
        self._gen_calls = 0
        hp_ = self.hp

        @jax.named_scope("ppo_loss")
        def actor_loss_fn(logits, batch):
            # With the engine's chunked-logprob head (wants_token_logprobs)
            # this receives the [B, L] logprobs directly; otherwise raw
            # [B, L, V] logits.
            lp = logits if logits.ndim == 2 else F.token_logprobs_from_logits(
                logits, batch["tokens"], batch["segment_ids"]
            )
            amask = F.action_token_mask(
                batch["segment_ids"], batch["prompt_mask"]
            )
            prox = batch.get("prox_logprobs") if hp_.use_decoupled_loss else None
            loss, st = F.actor_loss(
                lp,
                batch["packed_logprobs"],
                batch["advantages"],
                amask,
                eps_clip=hp_.eps_clip,
                c_clip=hp_.c_clip,
                proximal_logprobs=prox,
                behav_imp_weight_cap=hp_.behav_imp_weight_cap,
                loss_scale=jnp.asarray(1.0),  # sum; engine divides by weight
            )
            stats = {f"{k}_sum": v * 1.0 for k, v in st.items()}
            stats["n_action_tokens"] = jnp.sum(amask)
            return loss, stats

        actor_loss_fn.wants_token_logprobs = True
        self._loss_fn = actor_loss_fn
        self._prep_fn = make_advantage_prep(self.hp)

    # ---- MFC methods ----

    def generate(
        self, model: Model, data: SequenceSample, mb_spec: MicroBatchSpec
    ) -> SequenceSample:
        """Prompt batch → flattened trajectory batch (group_size per prompt)."""
        hp = self.hp
        engine = model.module
        eos = getattr(model.tokenizer, "eos_token_id", 1) or 1
        pad = getattr(model.tokenizer, "pad_token_id", 0) or 0
        gconfig = dataclasses.replace(hp.gen, n=hp.group_size)
        # Distinct key per call even within one model version.
        key = jax.random.fold_in(
            jax.random.PRNGKey(model.version.global_step), self._gen_calls
        )
        self._gen_calls += 1
        out = engine.generate(
            data, mb_spec, gconfig, key=key,
            eos_token_id=eos, pad_token_id=pad,
        )
        return trajectories_from_gen_output(
            data, out, group_size=hp.group_size,
            version=model.version.global_step, eos_token_id=eos,
        )

    def inference(
        self, model: Model, data: SequenceSample, mb_spec: MicroBatchSpec
    ) -> SequenceSample:
        """Recompute logprobs under the current policy → prox_logprobs."""
        engine = model.module
        with telemetry.span("ppo/inference", **_sample_attrs(data)):
            per_sample = engine.forward(data, mb_spec,
                                        post_hook=_logprob_hook)
        return SequenceSample(
            ids=list(data.ids),
            keys={"prox_logprobs"},
            seqlens={"prox_logprobs": [list(s) for s in
                                       data.seqlens["packed_input_ids"]]},
            data={"prox_logprobs": np.concatenate(per_sample).astype(np.float32)},
        )

    def train_step(
        self, model: Model, data: SequenceSample, mb_spec: MicroBatchSpec
    ) -> Dict[str, float]:
        with telemetry.span("ppo/train_step", **_sample_attrs(data)):
            return self._train_step(model, data, mb_spec)

    def _train_step(
        self, model: Model, data: SequenceSample, mb_spec: MicroBatchSpec
    ) -> Dict[str, float]:
        hp = self.hp
        engine = model.module
        skip_rule = (
            "importance_weight_sum", "n_action_tokens",
            hp.early_stop_imp_ratio or 0.0,
        )
        agg: Dict[str, float] = {}
        n_steps = 0
        mean_kl = 0.0
        adv_scale = 0.0

        if not hp.group_adv_norm:
            # Fast path: ONE h2d upload of the whole batch, GAE + advantage
            # whitening fused on device (make_advantage_prep), micro-batches
            # sliced on device by index — per step this is n_mb dispatches,
            # one apply and ONE host sync per PPO minibatch.
            # Request at least ppo_n_minibatches micro-batches from the
            # packer: with the default MicroBatchSpec the whole batch packs
            # into ONE uniform micro-batch, which would silently collapse
            # the PPO minibatch loop (reference ppo_interface.py:698) to a
            # single optimizer step.
            ub = engine.upload_uniform(data, dataclasses.replace(
                mb_spec, n_mbs=max(mb_spec.n_mbs or 1, hp.ppo_n_minibatches)
            ))
            scalars = engine.run_prep(
                ub, self._prep_fn, self._prep_fn,
                scalars={"kl_coef": self.kl_ctl.value},
            )
            k = min(hp.ppo_n_minibatches, ub.n_mbs)
            # Contiguous micro-batch groups, one optimizer step each
            # (reference ppo_interface.py:698-760 minibatch loop).
            bounds = np.linspace(0, ub.n_mbs, k + 1).astype(int)
            groups = [
                list(range(bounds[i], bounds[i + 1]))
                for i in range(k) if bounds[i + 1] > bounds[i]
            ]
            for gi, g in enumerate(groups):
                stats = engine.train_uniform(
                    ub, self._loss_fn, _action_token_weight, mb_indices=g,
                    skip_update_rule=skip_rule,
                    extra_fetch={"_mean_kl": scalars["_mean_kl"],
                                 "_adv_scale": scalars["_adv_scale"]},
                )
                mean_kl = stats.pop("_mean_kl")
                adv_scale = stats.pop("_adv_scale")
                n_steps += 1
                for key, v in stats.items():
                    agg[key] = agg.get(key, 0.0) + float(v)
                if stats.get("update_applied", 1.0) == 0.0:
                    n = max(stats.get("n_action_tokens", 1.0), 1.0)
                    imp = stats.get("importance_weight_sum", 0.0) / n
                    logger.warning(
                        f"early-stopping PPO minibatches: importance ratio "
                        f"{imp:.2f} > {hp.early_stop_imp_ratio} "
                        "(update skipped)"
                    )
                    break
        else:
            extra = compute_advantages_and_returns(data, hp, self.kl_ctl.value)
            mean_kl = extra.pop("_mean_kl")
            # Raw advantage scale (pre-whitening), mirroring the device
            # prep's _adv_scale: the prompt-mask approximation of the
            # action mask is exact here — doc-first-token advantages are
            # 0 by construction.
            am = (1 - np.asarray(data.data["prompt_mask"])) > 0
            if am.any():
                adv_scale = float(np.abs(extra["advantages"][am]).mean())
            data = attach_keys(data, extra)
            if hp.adv_norm or hp.group_adv_norm:
                normalize_advantages(data, hp)

            # PPO minibatch loop (reference ppo_interface.py:698-760): split
            # the batch into ppo_n_minibatches, one optimizer step each.
            minibatches, _ = data.split(k=min(hp.ppo_n_minibatches, data.bs))
            for mb_sample in minibatches:
                if mb_sample.bs == 0:
                    continue
                # Early-stop semantics (reference ppo_interface.py:735-760):
                # the importance ratio is checked BEFORE the optimizer step —
                # the engine skips the update on device when the ratio
                # exceeds the cap, and we stop the remaining minibatches.
                stats = engine.train_batch(
                    mb_sample, mb_spec, self._loss_fn,
                    _action_token_weight,
                    version_steps=model.version.global_step,
                    skip_update_rule=skip_rule,
                )
                n_steps += 1
                for k, v in stats.items():
                    agg[k] = agg.get(k, 0.0) + float(v)
                if stats.get("update_applied", 1.0) == 0.0:
                    n = max(stats.get("n_action_tokens", 1.0), 1.0)
                    imp = stats.get("importance_weight_sum", 0.0) / n
                    logger.warning(
                        f"early-stopping PPO minibatches: importance ratio "
                        f"{imp:.2f} > {hp.early_stop_imp_ratio} "
                        "(update skipped)"
                    )
                    break
        self.kl_ctl.update(mean_kl, n_steps=1)
        # Version-staleness of the TRAINED batch (how many publishes
        # behind the samples' generation weights are) — measured before
        # this step's version bump, in the same sample units the
        # staleness gate budgets (max_head_offpolicyness).
        staleness = 0.0
        if "version_start" in data.keys:
            staleness = float(
                model.version.global_step
                - np.mean(np.asarray(data.data["version_start"],
                                     np.float64))
            )
        model.inc_version()
        n = max(agg.get("n_action_tokens", 1.0), 1.0)
        moe_stats = {  # means over the optimizer steps, but for the sums
            k: v if k[4:] in SUMMED_AUX else v / max(n_steps, 1)
            for k, v in agg.items() if k.startswith("moe_")
        }
        # a learned selection's exact counts (models/dsa.py): sums
        moe_stats.update({k: v for k, v in agg.items()
                          if k.startswith("dsa_")})
        rewards_np = np.asarray(data.data["rewards"], np.float32).reshape(-1)
        return {
            **moe_stats,
            "actor_loss": agg.get("loss", 0.0),
            "importance_weight": agg.get("importance_weight_sum", 0.0) / n,
            "clip_ratio": agg.get("clip_ratio_sum", 0.0) / n,
            "dual_clip_ratio": agg.get("dual_clip_ratio_sum", 0.0) / n,
            "mean_kl": mean_kl,
            "kl_coef": self.kl_ctl.value,
            "grad_norm": agg.get("grad_norm", 0.0) / max(n_steps, 1),
            "lr": agg.get("lr", 0.0) / max(n_steps, 1),
            "n_action_tokens": agg.get("n_action_tokens", 0.0),
            "n_ppo_steps": float(n_steps),
            "task_reward": float(rewards_np.mean()),
            # Training-dynamics divergence signatures (first-class
            # telemetry via trainer_worker._export_train_stats; the
            # sentinel's default rule pack keys off these —
            # docs/observability.md §Alerting).
            "approx_kl": agg.get("approx_kl_sum", 0.0) / n,
            "entropy": agg.get("entropy_sum", 0.0) / n,
            "behav_imp_tail": agg.get("behav_tail_sum", 0.0) / n,
            "reward_std": float(rewards_np.std()),
            "adv_scale": float(adv_scale),
            "staleness_lag": staleness,
        }

    def save(self, model: Model, save_dir: str) -> None:
        from areal_tpu.models import hf as hfmod

        engine = model.module
        hfmod.save_hf_checkpoint(
            jax.device_get(engine.params), engine.cfg, save_dir,
            meta={"version": model.version.global_step},
        )

    def state_dict(self):
        return {"kl_ctl": getattr(self.kl_ctl, "_value", self.kl_ctl.value)}

    def load_state_dict(self, d):
        if hasattr(self.kl_ctl, "_value"):
            self.kl_ctl._value = d["kl_ctl"]


def _sample_attrs(data: SequenceSample) -> Dict[str, int]:
    """Attributes of the ``ppo/`` root spans: what the step was given."""
    return {
        "sequences": data.bs,
        "real_tokens": int(sum(data.total_lens("packed_input_ids"))),
    }


def _logprob_hook(logits, batch):
    if logits.ndim == 2:  # engine's chunked-logprob head already did it
        return logits
    return F.token_logprobs_from_logits(
        logits, batch["tokens"], batch["segment_ids"]
    )


_logprob_hook.wants_token_logprobs = True


def _values_hook(values, batch):
    # critic forward output is [B, L] already
    return values * (batch["segment_ids"] > 0)


def _action_token_weight(mb: mbu.MicroBatch) -> float:
    return float(_action_mask(mb.grids).sum())


def attach_keys(data: SequenceSample, extra: Dict[str, np.ndarray]) -> SequenceSample:
    """New sample with full-length per-token keys added (non-mutating)."""
    sls = data.seqlens["packed_input_ids"]
    return SequenceSample(
        ids=list(data.ids),
        keys=set(data.keys) | set(extra.keys()),
        seqlens={**data.seqlens, **{k: [list(s) for s in sls] for k in extra}},
        data={**data.data, **extra},
        metadata=data.metadata,
    )


# ---------------- critic ----------------

class PPOCriticInterface(ModelInterface):
    def __init__(self, hp: Optional[PPOHyperparameters] = None, **kw):
        self.hp = hp or PPOHyperparameters(**kw)
        self.rms = RunningMoments(self.hp.value_norm_beta, self.hp.value_norm_eps)
        hp_ = self.hp

        @jax.named_scope("ppo_loss")
        def critic_loss_fn(values, batch):
            amask = F.action_token_mask(
                batch["segment_ids"], batch["prompt_mask"]
            )
            # Returns at action slot t target the PRE-action value V_{t−1}
            # (reference leave_one_indices pairing, ppo_interface.py:936-948):
            # shift both the fresh forward values and the stored clip
            # baseline right by one inside each doc before the loss.
            seg = batch["segment_ids"]
            loss, st = F.critic_loss(
                F.shift_right_in_doc(values, seg),
                F.shift_right_in_doc(batch["values"], seg),
                batch["_norm_returns"],
                amask,
                value_eps_clip=hp_.value_eps_clip,
                loss_scale=jnp.asarray(1.0),
            )
            return loss, {
                "value_clip_ratio_sum": st["value_clip_ratio"],
                "n_action_tokens": jnp.sum(amask),
            }

        self._loss_fn = critic_loss_fn

    def inference(
        self, model: Model, data: SequenceSample, mb_spec: MicroBatchSpec
    ) -> SequenceSample:
        """Critic forward → denormalized per-token values."""
        engine = model.module
        per_sample = engine.forward(data, mb_spec, post_hook=_values_hook)
        vals = np.concatenate(per_sample).astype(np.float32)
        if self.hp.value_norm:
            vals = self.rms.denormalize(vals).astype(np.float32)
        return SequenceSample(
            ids=list(data.ids),
            keys={"values"},
            seqlens={"values": [list(s) for s in data.seqlens["packed_input_ids"]]},
            data={"values": vals},
        )

    def train_step(
        self, model: Model, data: SequenceSample, mb_spec: MicroBatchSpec
    ) -> Dict[str, float]:
        hp = self.hp
        engine = model.module
        extra = compute_advantages_and_returns(data, hp, 0.0)
        extra.pop("_mean_kl")
        returns = extra["returns"]
        pm = np.asarray(data.data["prompt_mask"])
        amask = (1 - pm) > 0
        if hp.value_norm:
            self.rms.update(returns, amask)
            extra["_norm_returns"] = self.rms.normalize(returns).astype(np.float32)
        else:
            extra["_norm_returns"] = returns
        # The critic trains in normalized space; its stored "values" input
        # key must be normalized the same way for the clip baseline.
        if hp.value_norm and "values" in data.keys:
            data = attach_keys(
                data,
                {"values": self.rms.normalize(
                    np.asarray(data.data["values"])).astype(np.float32)},
            )
        data = attach_keys(data, extra)
        minibatches, _ = data.split(k=min(hp.ppo_n_minibatches, data.bs))
        agg: Dict[str, float] = {}
        n_steps = 0
        for mb_sample in minibatches:
            if mb_sample.bs == 0:
                continue
            stats = engine.train_batch(
                mb_sample, mb_spec, self._loss_fn, _action_token_weight,
                version_steps=model.version.global_step,
            )
            n_steps += 1
            for k, v in stats.items():
                agg[k] = agg.get(k, 0.0) + float(v)
        model.inc_version()
        n = max(agg.get("n_action_tokens", 1.0), 1.0)
        return {
            "critic_loss": agg.get("loss", 0.0),
            "value_clip_ratio": agg.get("value_clip_ratio_sum", 0.0) / n,
            "grad_norm": agg.get("grad_norm", 0.0) / max(n_steps, 1),
            "value_mean": float(self.rms.mean),
            "value_var": float(self.rms.var),
        }

    def state_dict(self):
        return {"rms": self.rms.state_dict()}

    def load_state_dict(self, d):
        self.rms.load_state_dict(d["rms"])


register_interface("ppo_critic", PPOCriticInterface)


def trajectories_from_gen_output(
    prompts: SequenceSample,
    gen_out: Dict[str, np.ndarray],
    group_size: int,
    version: int,
    eos_token_id: int = 1,
) -> SequenceSample:
    """Assemble flattened trajectory samples from engine.generate output."""
    offs = prompts.offsets("packed_prompts")
    plens = prompts.total_lens("packed_prompts")
    ids, seqlens = [], []
    toks, pmask, lps = [], [], []
    n_eos = []
    for i in range(prompts.bs):
        prompt = prompts.data["packed_prompts"][offs[i] : offs[i] + plens[i]]
        for j in range(group_size):
            r = i * group_size + j
            gl = int(gen_out["output_lens"][r])
            gl = max(gl, 1)
            g_toks = gen_out["output_ids"][r][:gl]
            g_lps = gen_out["output_logprobs"][r][:gl]
            ids.append(f"{prompts.ids[i]}@{j}")
            seqlens.append(len(prompt) + gl)
            toks.append(np.concatenate([prompt, g_toks]))
            pmask.append(
                np.concatenate([np.ones(len(prompt), np.int32),
                                np.zeros(gl, np.int32)])
            )
            lps.append(
                np.concatenate([np.zeros(len(prompt), np.float32), g_lps])
            )
            # Truncated iff EOS never appeared among the emitted tokens
            # (gen_mask.all() alone misses EOS landing on the final slot).
            n_eos.append(float(eos_token_id not in g_toks))
    md_task = prompts.metadata.get("task", ["math"] * prompts.bs)
    return SequenceSample.from_default(
        ids=ids,
        data={
            "packed_input_ids": np.concatenate(toks).astype(np.int32),
            "prompt_mask": np.concatenate(pmask),
            "packed_logprobs": np.concatenate(lps).astype(np.float32),
            "seq_no_eos_mask": np.asarray(n_eos, np.float32),
            "task_ids": np.repeat(
                np.asarray(
                    prompts.data.get(
                        "task_ids", np.zeros(prompts.bs, np.int32)
                    )
                ).reshape(-1),
                group_size,
            ),
            "version_start": np.full(len(ids), version, np.int32),
            "version_end": np.full(len(ids), version, np.int32),
        },
        seqlens=seqlens,
        metadata={
            "group": [str(prompts.ids[i]) for i in range(prompts.bs)
                      for _ in range(group_size)],
            "task": [md_task[i] for i in range(prompts.bs)
                     for _ in range(group_size)],
        },
    )


class LogprobInterface(ModelInterface):
    """Frozen-model logprob recompute (the reference's ref_inf MFC: actor
    ``inference`` run on the reference policy with an output-key remap)."""

    def __init__(self, output_key: str = "packed_ref_logprobs"):
        self.output_key = output_key

    def inference(
        self, model: Model, data: SequenceSample, mb_spec: MicroBatchSpec
    ) -> SequenceSample:
        per_sample = model.module.forward(data, mb_spec, post_hook=_logprob_hook)
        return SequenceSample(
            ids=list(data.ids),
            keys={self.output_key},
            seqlens={self.output_key: [list(s) for s in
                                       data.seqlens["packed_input_ids"]]},
            data={self.output_key: np.concatenate(per_sample).astype(np.float32)},
        )


register_interface("ppo_actor", PPOActorInterface)
register_interface("ref_logprob", LogprobInterface)
