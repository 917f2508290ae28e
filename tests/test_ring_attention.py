"""Ring attention (context parallelism) parity + integration tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import packing, transformer
from areal_tpu.models.config import tiny_config
from areal_tpu.ops import attention as attn
from areal_tpu.parallel import mesh as pmesh
from areal_tpu.parallel import ring as ring_mod
from areal_tpu.parallel import sharding as psh
from areal_tpu.parallel.ring import ring_attention

pytestmark = pytest.mark.ring


def _case(seqlens, Hq, Hkv, D, row_len, seed=0):
    rng = np.random.RandomState(seed)
    # min 2 rows so the batch dim divides the dp×fsdp mesh axes
    layout = packing.plan_packing(seqlens, row_len=row_len, min_rows=2)
    grid = packing.make_grid(layout)
    B, L = layout.shape
    q = jnp.asarray(rng.randn(B, L, Hq, D).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(B, L, Hkv, D).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(B, L, Hkv, D).astype(np.float32) * 0.3)
    return grid, q, k, v


@pytest.mark.parametrize("schedule", ["zigzag", "naive"])
@pytest.mark.parametrize("spec", ["s4", "d2s2t2", "s8"])
@pytest.mark.parametrize("seqlens,row_len", [([32], 32), ([20, 9, 3], 32)])
def test_ring_matches_reference(spec, seqlens, row_len, schedule):
    mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse(spec))
    grid, q, k, v = _case(seqlens, Hq=4, Hkv=2, D=16, row_len=row_len)
    seg = jnp.asarray(grid["segment_ids"])
    pos = jnp.asarray(grid["positions"])
    ref = attn.packed_attention(q, k, v, seg, seg, q_positions=pos,
                                kv_positions=pos, impl="reference")
    out = jax.jit(
        lambda q, k, v, s: ring_attention(q, k, v, s, mesh,
                                          schedule=schedule)
    )(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("schedule", ["zigzag", "naive"])
def test_ring_gradients_flow(schedule):
    mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse("s4"))
    grid, q, k, v = _case([16, 12], Hq=2, Hkv=2, D=8, row_len=32)
    seg = jnp.asarray(grid["segment_ids"])
    pos = jnp.asarray(grid["positions"])

    def loss_ring(q, k, v):
        return jnp.sum(
            ring_attention(q, k, v, seg, mesh, schedule=schedule) ** 2
        )

    def loss_ref(q, k, v):
        o = attn.packed_attention(q, k, v, seg, seg, q_positions=pos,
                                  kv_positions=pos, impl="reference")
        return jnp.sum(o**2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   err_msg=f"grad {name}")


def test_zigzag_permutation_roundtrip():
    for T, n in [(16, 2), (32, 4), (64, 8)]:
        fwd = np.asarray(ring_mod.zigzag_permutation(T, n))
        inv = np.asarray(ring_mod.inverse_permutation(fwd))
        assert sorted(fwd.tolist()) == list(range(T))
        np.testing.assert_array_equal(fwd[inv], np.arange(T))
        np.testing.assert_array_equal(inv[fwd], np.arange(T))
        # Rank r holds chunks (r, 2n-1-r) of the 2n global chunks — one
        # early, one late, so causal work balances across the ring.
        c = T // (2 * n)
        chunk_of = fwd.reshape(n, 2, c) // c
        for r in range(n):
            assert chunk_of[r, 0, 0] == r
            assert chunk_of[r, 1, 0] == 2 * n - 1 - r


def test_zigzag_matches_naive_oracle():
    """The balanced schedule and the contiguous v1 oracle agree to float
    round-off on packed multi-document rows."""
    mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse("s4"))
    grid, q, k, v = _case([20, 9, 3], Hq=4, Hkv=2, D=16, row_len=32)
    seg = jnp.asarray(grid["segment_ids"])
    out_zz = jax.jit(
        lambda q, k, v, s: ring_attention(q, k, v, s, mesh,
                                          schedule="zigzag")
    )(q, k, v, seg)
    out_nv = jax.jit(
        lambda q, k, v, s: ring_attention(q, k, v, s, mesh,
                                          schedule="naive")
    )(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(out_zz), np.asarray(out_nv),
                               atol=1e-6)


def test_resolve_schedule_default_and_downgrades():
    assert ring_mod.resolve_schedule("naive", 32, 4) == "naive"
    assert ring_mod.resolve_schedule(None, 32, 4) == "zigzag"
    with pytest.raises(ValueError):
        ring_mod.resolve_schedule("bogus", 32, 4)
    # Downgrades to the oracle when zig-zag's preconditions fail.
    assert ring_mod.resolve_schedule("zigzag", 30, 4) == "naive"
    assert ring_mod.resolve_schedule("zigzag", 32, 4,
                                     causal=False) == "naive"
    assert ring_mod.resolve_schedule("zigzag", 32, 1) == "naive"


@pytest.mark.parametrize("spec,n", [("s4", 4), ("s8", 8)])
def test_zigzag_skip_ratio_structural(spec, n):
    """Causal skip proven structurally: the trace-time area counters show
    exactly (n+1)/2n of the naive per-step attention work executes."""
    mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse(spec))
    grid, q, k, v = _case([32], Hq=2, Hkv=2, D=8, row_len=32)
    seg = jnp.asarray(grid["segment_ids"])
    ring_mod.reset_ring_counters()
    jax.jit(
        lambda q, k, v, s: ring_attention(q, k, v, s, mesh,
                                          schedule="zigzag")
    )(q, k, v, seg)
    assert ring_mod.ring_counters()["naive_area"] > 0
    assert ring_mod.ring_skip_ratio() == pytest.approx((n + 1) / (2 * n))


def test_transformer_forward_with_sp_mesh():
    """Full model forward under an sp>1 mesh dispatches to ring attention
    and matches the unsharded result."""
    cfg = tiny_config(n_layers=2, hidden_dim=32, n_q_heads=4, n_kv_heads=2)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    B, T = 2, 32
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab_size, (B, T)).astype(np.int32)
    positions = np.tile(np.arange(T, dtype=np.int32), (B, 1))
    seg = np.ones((B, T), np.int32)
    ref, _ = transformer.forward(params, cfg, tokens, positions,
                                 segment_ids=seg)

    mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse("d2s2t2"))
    sp = psh.shard_params(params, mesh, cfg)

    def fwd(p, t, pos, s):
        with psh.activation_sharding(mesh):
            out, _ = transformer.forward(p, cfg, t, pos, segment_ids=s)
        return out

    out = jax.jit(fwd)(sp, tokens, positions, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)
