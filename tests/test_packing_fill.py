"""Host-side packing-fill regression (ISSUE 8): the micro-batch packer's
fill on a PPO-shaped length distribution must be >= 0.92 — it was 0.84
with the coarse 512-bucket candidates — and the finer bucketing must keep the python and
native-C FFD paths bit-identical. CPU-only; no model, no device work
except one tiny engine step that checks the telemetry export."""

import numpy as np
import pytest

from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.backend import microbatch as mbu
from areal_tpu.base import datapack


def _bench_batch(seed=0, n_seq=32):
    """~250-token prompts + ~640-token generations, from the shared
    recipe (base/testing.bench_trajectory_dist)."""
    from areal_tpu.base.testing import bench_trajectory_sample

    return bench_trajectory_sample(seed, n_seq)


@pytest.mark.parametrize("cap", [2048, 4096])
def test_bench_distribution_fill(cap):
    batch, seqlens = _bench_batch()
    mbs = mbu.split_into_microbatches(
        batch, MicroBatchSpec(max_tokens_per_mb=cap),
        length_bucket=512, rows_bucket=4, seqs_bucket=16,
    )
    fill = mbu.pack_fill(mbs)
    assert fill >= 0.92, f"fill {fill:.4f} < 0.92 at cap {cap}"
    # every micro-batch respects the token cap and the lane alignment the
    # flash kernel needs
    for mb in mbs:
        R, L = mb.layout.shape
        assert R * L <= cap
        assert L % 128 == 0


def test_fill_across_distributions():
    """The sweep must not be tuned to one seed: >= 0.92 across seeds and
    batch sizes of the bench-shaped distribution."""
    for seed in range(5):
        for n_seq in (16, 32, 64):
            batch, _ = _bench_batch(seed=seed, n_seq=n_seq)
            mbs = mbu.split_into_microbatches(
                batch, MicroBatchSpec(max_tokens_per_mb=4096),
                length_bucket=512, rows_bucket=4, seqs_bucket=16,
            )
            fill = mbu.pack_fill(mbs)
            assert fill >= 0.92, (seed, n_seq, fill)


def test_scatter_roundtrip_at_fine_buckets():
    """Data integrity is layout-independent: the finer candidate grid must
    still scatter back to the exact input tokens."""
    batch, _ = _bench_batch(seed=3)
    mbs = mbu.split_into_microbatches(
        batch, MicroBatchSpec(max_tokens_per_mb=4096),
        length_bucket=512, rows_bucket=4, seqs_bucket=16,
    )
    outs = [mb.grids["tokens"] for mb in mbs]
    per_sample = mbu.scatter_back(mbs, outs, batch.bs)
    np.testing.assert_array_equal(
        np.concatenate(per_sample), batch.data["packed_input_ids"]
    )


def test_fill_bucket_override_respected():
    batch, _ = _bench_batch()
    mbs = mbu.split_into_microbatches(
        batch, MicroBatchSpec(max_tokens_per_mb=4096),
        length_bucket=512, rows_bucket=4, seqs_bucket=16, fill_bucket=512,
    )
    assert mbs[0].layout.row_len % 512 == 0


def test_ffd_python_native_parity_on_new_bucketing():
    """The 128-grain candidate capacities are new territory for the native
    FFD (csrc/interval_ops.cpp): its bins must stay bit-identical to the
    Python loop at every candidate the sweep can now emit."""
    if datapack._ffd_native([4, 3], 8, force=True) is None:
        pytest.skip("native interval ops unavailable in this build")
    _, seqlens = _bench_batch(seed=1, n_seq=96)
    sizes = seqlens.tolist()
    lo = 128 * ((max(sizes) + 127) // 128)
    for capacity in range(lo, 4096 + 1, 128):
        py = datapack.ffd_allocate(sizes, capacity, use_native=False)
        nat = datapack.ffd_allocate(sizes, capacity, use_native=True)
        assert py == nat, f"FFD parity broke at capacity {capacity}"


def test_pack_fill_telemetry_export():
    """train/pack_fill must land in the telemetry registry when a train
    step runs with telemetry configured (the bench/observability wiring)."""
    import jax

    from areal_tpu.api.model import FinetuneSpec
    from areal_tpu.backend.jax_train import JaxTrainEngine, OptimizerConfig
    from areal_tpu.base import telemetry
    from areal_tpu.models import transformer
    from areal_tpu.models.config import tiny_config

    cfg = tiny_config(vocab_size=64)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    eng = JaxTrainEngine(
        cfg, params, opt_cfg=OptimizerConfig(lr=1e-4),
        ft_spec=FinetuneSpec(1, 8, 4), compute_dtype="float32",
        length_bucket=16, rows_bucket=2,
    )
    rng = np.random.RandomState(0)
    lens = rng.randint(4, 20, 8)
    sample = SequenceSample.from_default(
        ids=[f"s{i}" for i in range(8)],
        data={
            "packed_input_ids": rng.randint(
                2, 64, int(lens.sum())
            ).astype(np.int32),
            "loss_mask": np.ones(int(lens.sum()), np.float32),
        },
        seqlens=lens.tolist(),
    )

    import jax.numpy as jnp

    def loss(logits, batch):
        return (jnp.sum(logits.astype(jnp.float32) ** 2) * 1e-6,
                {"n": jnp.sum(batch["segment_ids"] > 0)})

    telemetry.configure("t", "t0", "trainer", 0, push=False)
    try:
        eng.train_batch(
            sample, MicroBatchSpec(max_tokens_per_mb=64), loss,
            lambda mb: float(mb.n_tokens),
        )
        snap = telemetry.get().snapshot(reset=True)
        assert "train/pack_fill" in snap["gauges"]
        assert 0.5 < snap["gauges"]["train/pack_fill"] <= 1.0
    finally:
        telemetry.shutdown()


@pytest.mark.parametrize("m", [1, 2, 4])
def test_rows_are_a_multiple_of_the_meshs_data_degree(m):
    """``rows_multiple`` (the engine passes its mesh's data degree): R is
    a multiple of it and a micro-batch stays inside the token cap, so its
    rows split evenly over the chips; 1 changes nothing."""
    for seed in range(3):
        batch, _ = _bench_batch(seed=seed, n_seq=48)
        spec = MicroBatchSpec(max_tokens_per_mb=8192, n_mbs=4)
        mbs = mbu.split_into_microbatches(batch, spec, rows_multiple=m)
        R, L = mbs[0].layout.shape
        assert R % m == 0 and R * L <= 8192
        assert all(mb.layout.shape == (R, L) for mb in mbs)
        assert sum(mb.n_tokens for mb in mbs) == sum(
            batch.total_lens("packed_input_ids"))
        if m == 1:
            plain = mbu.split_into_microbatches(batch, spec)
            assert plain[0].layout.shape == (R, L) and len(plain) == len(mbs)


def test_rows_multiple_when_the_cap_allows_fewer_rows():
    """A sequence longer than cap / rows_multiple: R is rows_multiple
    itself and L the sequence's bucket (over the cap, as one long sequence
    always was)."""
    batch, seqlens = _bench_batch(seed=1, n_seq=8)
    longest = max(seqlens)
    spec = MicroBatchSpec(max_tokens_per_mb=2 * longest)
    mbs = mbu.split_into_microbatches(batch, spec, rows_multiple=4)
    R, L = mbs[0].layout.shape
    assert R == 4 and L == mbu.packing.round_up(longest, 128)
