"""In-process generation engine: prefill + KV-cache decode under one jit.

Parity target: the reference's in-house generation
(``realhf/impl/model/nn/real_llm_generate.py:30,256`` — genstep + generate
with KV cache). TPU-first differences:
 - the whole decode loop is a single ``lax.scan`` with static shapes (no
   CUDA-graph capture needed — XLA compiles the step once);
 - prompts are right-padded to a bucket length, responses capped at
   ``max_new_tokens``; finished rows keep emitting ``pad_token`` with zero
   logprob so shapes stay static.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.api.model import GenerationHyperparameters
from areal_tpu.models.config import TransformerConfig
from areal_tpu.models import transformer
from areal_tpu.models.transformer import (
    forward,
    init_kv_cache,
    kv_valid_by_kind,
)
from areal_tpu.ops.sampling import (
    sample_token,
    sample_token_rows,
    sampling_from_gconfigs,
)


def decode_refusal(cfg: TransformerConfig) -> Optional[str]:
    """Why this model cannot be decoded here, by name
    (``transformer.DECODE_REFUSAL``), or None: a layer that is one mixer
    alone — a state-space layer among them — a selective-scan block, or
    a layer that reads another layer's memory or K/V has no cache to
    decode from; a Gated DeltaNet block its own name (its state is a
    recurrent matrix and its convolution's last taps: ``gdn.DECODE_REFUSAL``),
    a Kimi Delta Attention block likewise (``kda.DECODE_REFUSAL``,
    ``channel_decay_rule_decode_state``),
    a short-convolution block likewise (the last ``conv_L_cache - 1`` tokens
    of ``B ⊙ x`` beside the attention blocks' K/V:
    ``shortconv.DECODE_REFUSAL``), latent attention likewise (a cache of
    the kv latent and the shared rotary key with the up-projections
    absorbed, and separate prefill and decode paths:
    ``mla.DECODE_REFUSAL``, ``latent_attention_decode_cache``).
    Every entry point below prefills through ``forward``, which raises it."""
    return transformer.decode_refusal(cfg)


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "gconfig", "max_new_tokens", "eos_token_id", "pad_token_id", "attn_impl",
    ),
)
def generate_batch(
    params,
    cfg: TransformerConfig,
    prompts: jnp.ndarray,  # [B, P] right-padded with pad_token
    prompt_lens: jnp.ndarray,  # [B]
    key: jax.Array,
    gconfig: GenerationHyperparameters,
    max_new_tokens: int,
    eos_token_id: int,
    pad_token_id: int,
    attn_impl: str = "auto",
) -> Dict[str, jnp.ndarray]:
    """Returns {"output_ids": [B, N], "output_logprobs": [B, N],
    "output_lens": [B], "prompt_logprobs": [B, P]}.

    output_lens counts generated tokens incl. the EOS; slots beyond it hold
    pad_token / 0.0 logprob.
    """
    B, P = prompts.shape
    N = max_new_tokens
    S = P + N

    positions = jnp.broadcast_to(jnp.arange(P)[None, :], (B, P))
    seg = (positions < prompt_lens[:, None]).astype(jnp.int32)
    logits, kv = forward(
        params, cfg, prompts, positions, segment_ids=seg, attn_impl=attn_impl
    )
    # Log-probs of prompt tokens (teacher-forced), for optional prompt
    # scoring — gather + fused logsumexp, no [B, P, V] f32 copy (ops/xent).
    from areal_tpu.ops.xent import gather_logprobs

    nxt = jnp.concatenate([prompts[:, 1:], prompts[:, :1]], axis=1)
    prompt_logprobs = gather_logprobs(logits, nxt)

    # Pad per-layer KV to the full decode length.
    kv_cache = init_kv_cache(cfg, B, S, dtype=kv["k"].dtype)
    kv_cache = {
        "k": jax.lax.dynamic_update_slice_in_dim(kv_cache["k"], kv["k"], 0, axis=2),
        "v": jax.lax.dynamic_update_slice_in_dim(kv_cache["v"], kv["v"], 0, axis=2),
    }

    last_idx = jnp.maximum(prompt_lens - 1, 0)
    last_logits = jnp.take_along_axis(logits, last_idx[:, None, None], axis=1)[:, 0]

    slot_ids = jnp.arange(S)

    def step(carry, n):
        kv_cache, last_logits, finished, key = carry
        key, sub = jax.random.split(key)
        if gconfig.min_new_tokens > 0:
            # Forbid EOS until min_new_tokens have been emitted (reference
            # suppresses EOS in its logits warper the same way).
            eos_block = (n < gconfig.min_new_tokens) & (
                jnp.arange(last_logits.shape[-1]) == eos_token_id
            )
            last_logits = jnp.where(eos_block[None, :], -1e30, last_logits)
        token, logprob = sample_token(last_logits, sub, gconfig)
        token = jnp.where(finished, pad_token_id, token)
        logprob = jnp.where(finished, 0.0, logprob)
        emit_token, emit_logprob = token, logprob

        pos = prompt_lens + n  # [B]
        valid = (slot_ids[None, :] < prompt_lens[:, None]) | (
            (slot_ids[None, :] >= P) & (slot_ids[None, :] <= P + n)
        )
        # Cache slot j holds position j (prompt) or plen + (j - P) (decode);
        # a sliding-window layer reads only the slots inside its window.
        slot_pos = jnp.where(
            slot_ids[None, :] < P,
            slot_ids[None, :],
            prompt_lens[:, None] + (slot_ids[None, :] - P),
        )
        valid = kv_valid_by_kind(cfg, valid, pos[:, None] - slot_pos)
        logits_step, kv_cache = forward(
            params,
            cfg,
            token[:, None],
            pos[:, None],
            kv_cache=kv_cache,
            cache_write_index=P + n,
            kv_valid=valid,
        )
        now_finished = finished | (token == eos_token_id)
        return (kv_cache, logits_step[:, 0], now_finished, key), (
            emit_token,
            emit_logprob,
            finished,
        )

    finished0 = jnp.zeros((B,), bool)
    (_, _, _, _), (toks, lps, was_finished) = jax.lax.scan(
        step, (kv_cache, last_logits, finished0, key), jnp.arange(N)
    )
    output_ids = toks.T  # [B, N]
    output_logprobs = lps.T
    gen_mask = ~was_finished.T  # True where the token was actually generated
    output_lens = gen_mask.sum(axis=1).astype(jnp.int32)
    return {
        "output_ids": output_ids,
        "output_logprobs": output_logprobs.astype(jnp.float32),
        "output_lens": output_lens,
        "gen_mask": gen_mask,
        "prompt_logprobs": prompt_logprobs.astype(jnp.float32),
    }


# ---------------------------------------------------------------------------
# Persistent decode state (chunked generation without re-prefill)
# ---------------------------------------------------------------------------
#
# The chunked-generation client re-submits prompt+accumulated tokens each
# chunk; re-prefilling that prefix every time is O(L²) over a generation
# (VERDICT r1 weakness #3 / the reference's SGLang radix-cache role,
# patch/sglang/v0.4.6.post4.patch). Instead the server keeps per-request
# decode state: a KV cache laid out COMPACTLY (slot j of row b is valid iff
# j < cur_len[b]; decode token n of a row writes slot cur_len, so the pad
# slots left by the bucketed prompt prefill are progressively overwritten)
# plus the last-step logits. A chunk continuation is then pure decode steps.
# Weight updates invalidate the state (KV computed under old weights is
# stale), which re-prefills once per version change — the same bound the
# reference gets by aborting requests on update_weights_from_disk.


@partial(jax.jit, static_argnames=("cfg", "S", "attn_impl"))
def prefill_state(
    params,
    cfg: TransformerConfig,
    prompts: jnp.ndarray,  # [B, P] right-padded
    prompt_lens: jnp.ndarray,  # [B]
    S: int,  # KV capacity (≥ P + first chunk length)
    attn_impl: str = "auto",
) -> Dict[str, jnp.ndarray]:
    """Prefill → decode state {kv_k, kv_v [L,B,S,Hkv,Dh], last_logits [B,V],
    cur_len [B]}."""
    B, P = prompts.shape
    positions = jnp.broadcast_to(jnp.arange(P)[None, :], (B, P))
    seg = (positions < prompt_lens[:, None]).astype(jnp.int32)
    logits, kv = forward(
        params, cfg, prompts, positions, segment_ids=seg, attn_impl=attn_impl
    )
    kv_cache = init_kv_cache(cfg, B, S, dtype=kv["k"].dtype)
    kv_cache = {
        "k": jax.lax.dynamic_update_slice_in_dim(kv_cache["k"], kv["k"], 0, axis=2),
        "v": jax.lax.dynamic_update_slice_in_dim(kv_cache["v"], kv["v"], 0, axis=2),
    }
    last_idx = jnp.maximum(prompt_lens - 1, 0)
    last_logits = jnp.take_along_axis(
        logits, last_idx[:, None, None], axis=1
    )[:, 0]
    return {
        "kv_k": kv_cache["k"],
        "kv_v": kv_cache["v"],
        "last_logits": last_logits.astype(jnp.float32),
        "cur_len": prompt_lens.astype(jnp.int32),
    }


@partial(
    jax.jit,
    static_argnames=("cfg", "n_tokens", "eos_token_id", "pad_token_id"),
    donate_argnames=("state",),
)
def decode_chunk_rows(
    params,
    cfg: TransformerConfig,
    state: Dict[str, jnp.ndarray],
    tokens_done: jnp.ndarray,  # [B] tokens generated in previous chunks
    key: jax.Array,
    sampling: Dict[str, jnp.ndarray],  # per-row arrays (ops.sampling)
    n_tokens: int,
    eos_token_id: int,
    pad_token_id: int,
    row_budget: Optional[jnp.ndarray] = None,  # [B] max tokens THIS chunk
) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
    """Continue decoding ``n_tokens`` from a decode state.

    Per-row sampling params (temperature/top_k/top_p/greedy/min_new_tokens)
    are DYNAMIC [B] arrays: one compiled kernel serves arbitrary gconfig
    mixes, so the server batches purely by computation shape. ``row_budget``
    finishes a row after its own token allowance even when the (static)
    chunk length is longer — mixed-budget batches stop sampling for
    exhausted rows instead of generating tokens the caller would discard.

    Returns (new_state, out) with out like generate_batch's (output_ids /
    output_logprobs / output_lens / gen_mask). Equivalent to the tail of
    ``generate_batch``'s scan — chunking N into pieces with this function
    yields identical greedy tokens (tested in test_kv_reuse.py).
    """
    S = state["kv_k"].shape[2]
    V = state["last_logits"].shape[-1]
    slot_ids = jnp.arange(S)

    def step(carry, n):
        kv_k, kv_v, last_logits, cur_len, done, finished, key = carry
        if row_budget is not None:
            finished = finished | (n >= row_budget)
        key, sub = jax.random.split(key)
        logits = last_logits
        # Forbid EOS while a row is under its min_new_tokens budget.
        eos_block = (done < sampling["min_new_tokens"])[:, None] & (
            jnp.arange(V) == eos_token_id
        )[None, :]
        logits = jnp.where(eos_block, -1e30, logits)
        token, logprob = sample_token_rows(logits, sub, sampling)
        token = jnp.where(finished, pad_token_id, token)
        logprob = jnp.where(finished, 0.0, logprob)

        pos = cur_len  # [B] slot & RoPE position of the new token
        valid = slot_ids[None, :] <= pos[:, None]
        valid = kv_valid_by_kind(cfg, valid, pos[:, None] - slot_ids[None, :])
        logits_step, kv = forward(
            params, cfg, token[:, None], pos[:, None],
            kv_cache={"k": kv_k, "v": kv_v},
            cache_write_index=pos, kv_valid=valid,
        )
        now_finished = finished | (token == eos_token_id)
        cur_len = jnp.where(finished, cur_len, cur_len + 1)
        done = done + (~finished).astype(jnp.int32)
        # Freeze last_logits once a row is finished: later steps feed pad
        # tokens, and a retained state (serving-mode row_budget truncation)
        # must carry the logits after its last REAL token — a continuation
        # or a full-match prefix clone samples its next token from them.
        last_logits = jnp.where(
            finished[:, None], last_logits,
            logits_step[:, 0].astype(jnp.float32),
        )
        return (
            kv["k"], kv["v"], last_logits,
            cur_len, done, now_finished, key,
        ), (token, logprob, finished)

    finished0 = jnp.zeros(state["cur_len"].shape, bool)
    carry0 = (
        state["kv_k"], state["kv_v"], state["last_logits"],
        state["cur_len"], tokens_done.astype(jnp.int32), finished0, key,
    )
    (kv_k, kv_v, last_logits, cur_len, _, _, _), (toks, lps, was_fin) = (
        jax.lax.scan(step, carry0, jnp.arange(n_tokens))
    )
    gen_mask = ~was_fin.T
    new_state = {
        "kv_k": kv_k, "kv_v": kv_v,
        "last_logits": last_logits, "cur_len": cur_len,
    }
    out = {
        "output_ids": toks.T,
        "output_logprobs": lps.T.astype(jnp.float32),
        "output_lens": gen_mask.sum(axis=1).astype(jnp.int32),
        "gen_mask": gen_mask,
    }
    return new_state, out


def decode_chunk(
    params,
    cfg: TransformerConfig,
    state: Dict[str, jnp.ndarray],
    tokens_done: jnp.ndarray,
    key: jax.Array,
    gconfig: GenerationHyperparameters,
    n_tokens: int,
    eos_token_id: int,
    pad_token_id: int,
) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
    """Uniform-gconfig convenience wrapper over decode_chunk_rows."""
    B = int(state["cur_len"].shape[0])
    sampling = sampling_from_gconfigs([gconfig] * B)
    return decode_chunk_rows(
        params, cfg, state, tokens_done, key, sampling,
        n_tokens=n_tokens, eos_token_id=eos_token_id,
        pad_token_id=pad_token_id,
    )


def clone_prefix(state: Dict[str, jnp.ndarray], L) -> Dict[str, jnp.ndarray]:
    """A decode state truncated to its first ``L`` tokens.

    The compact KV layout (slot j holds token j) makes this free: the KV
    arrays are shared as-is (jax arrays are immutable; slots ≥ L are
    masked out by every downstream ``kv_valid``), only ``cur_len`` drops
    to L. The cross-request prefix-seeding primitive: clone a donor's
    retained state at the shared-prefix length, then
    :func:`extend_state` the unshared suffix. ``last_logits`` is the
    donor's (stale for L < donor length) — callers must extend with ≥ 1
    token unless L equals the donor's full length.
    """
    return {
        "kv_k": state["kv_k"],
        "kv_v": state["kv_v"],
        "last_logits": state["last_logits"],
        "cur_len": jnp.full_like(state["cur_len"], L),
    }


@partial(jax.jit, static_argnames=("cfg", "attn_impl"))
def extend_state(
    params,
    cfg: TransformerConfig,
    state: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,  # [B, T] suffix, right-padded with pad tokens
    token_lens: jnp.ndarray,  # [B] real suffix lengths (≥ 1)
    attn_impl: str = "auto",
) -> Dict[str, jnp.ndarray]:
    """Teacher-force ``tokens`` through the model on top of an existing
    decode state — the suffix prefill of cross-request prefix seeding: a
    request whose prompt extends a retained state's tokens only pays
    forward passes for the unshared suffix, not the whole prompt.

    KV capacity must satisfy ``S ≥ max(cur_len + T)``. Slots written by
    the padding tail hold garbage K/V but sit at positions ≥ the new
    ``cur_len``: every later attention masks them (``slot ≤ pos``) until
    decode overwrites them one step at a time.
    """
    B, T = tokens.shape
    S = state["kv_k"].shape[2]
    cur = state["cur_len"].astype(jnp.int32)
    positions = cur[:, None] + jnp.arange(T)[None, :]  # [B, T]
    slot_ids = jnp.arange(S)
    # Causal over the compact layout: suffix token t of row b attends
    # slots j ≤ cur[b] + t (its own slot included — written above before
    # attention — but never its padded/future siblings).
    kv_valid = slot_ids[None, None, :] <= positions[:, :, None]  # [B, T, S]
    kv_valid = kv_valid_by_kind(
        cfg, kv_valid, positions[:, :, None] - slot_ids[None, None, :])
    logits, kv = forward(
        params, cfg, tokens, positions,
        kv_cache={"k": state["kv_k"], "v": state["kv_v"]},
        cache_write_index=cur, kv_valid=kv_valid, attn_impl=attn_impl,
    )
    last_idx = jnp.maximum(token_lens - 1, 0)
    last_logits = jnp.take_along_axis(
        logits, last_idx[:, None, None], axis=1
    )[:, 0]
    return {
        "kv_k": kv["k"],
        "kv_v": kv["v"],
        "last_logits": last_logits.astype(jnp.float32),
        "cur_len": cur + token_lens.astype(jnp.int32),
    }


def grow_state(state: Dict[str, jnp.ndarray], new_S: int) -> Dict[str, jnp.ndarray]:
    """Pad the KV capacity of a decode state up to new_S slots."""
    S = state["kv_k"].shape[2]
    if new_S <= S:
        return state
    pad = [(0, 0)] * state["kv_k"].ndim
    pad[2] = (0, new_S - S)
    return {
        **state,
        "kv_k": jnp.pad(state["kv_k"], pad),
        "kv_v": jnp.pad(state["kv_v"], pad),
    }


def slice_state(state: Dict[str, jnp.ndarray], i: int) -> Dict[str, jnp.ndarray]:
    """Row i of a batched decode state (keeps a batch axis of 1)."""
    return {
        "kv_k": state["kv_k"][:, i:i + 1],
        "kv_v": state["kv_v"][:, i:i + 1],
        "last_logits": state["last_logits"][i:i + 1],
        "cur_len": state["cur_len"][i:i + 1],
    }


def stack_states(states) -> Dict[str, jnp.ndarray]:
    """Concatenate single-row decode states along the batch axis."""
    return {
        "kv_k": jnp.concatenate([s["kv_k"] for s in states], axis=1),
        "kv_v": jnp.concatenate([s["kv_v"] for s in states], axis=1),
        "last_logits": jnp.concatenate([s["last_logits"] for s in states]),
        "cur_len": jnp.concatenate([s["cur_len"] for s in states]),
    }


def pad_prompts(
    prompt_list, pad_token_id: int, bucket: int = 64
) -> Tuple[np.ndarray, np.ndarray]:
    """Right-pad a list of int lists/arrays to a bucketed max length (static
    shapes → no recompilation churn; SURVEY §7 hard-part 6)."""
    lens = np.array([len(p) for p in prompt_list], dtype=np.int32)
    P = max(int(np.max(lens)), 1)
    P = ((P + bucket - 1) // bucket) * bucket
    out = np.full((len(prompt_list), P), pad_token_id, dtype=np.int32)
    for i, p in enumerate(prompt_list):
        out[i, : len(p)] = np.asarray(p, dtype=np.int32)
    return out, lens
