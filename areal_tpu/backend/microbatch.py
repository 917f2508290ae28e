"""SequenceSample → fixed-shape device micro-batches.

The jit boundary of every engine call. Replaces the reference's dynamic
varlen micro-batching (``SequenceSample.split`` + flash-attn cu_seqlens) with
bucketed [B, L] grids (models/packing.py) so XLA sees a small, stable set of
shapes (SURVEY §7 hard-part 6: recompilation churn).

Key-layout contract (deviation from the reference, by design): every
per-token key of a sample has the SAME per-sample seqlens as the main token
key (``packed_input_ids``) — logprobs/masks/etc are full-length with unused
slots zeroed — so one PackLayout serves all keys. Scalar keys (one value per
sample, e.g. rewards) ride along as [n_seqs] vectors plus (row, last_col)
index arrays into the grid.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.base import datapack
from areal_tpu.models import packing


@dataclasses.dataclass
class MicroBatch:
    layout: packing.PackLayout
    # [B, L] grids: always "tokens", "segment_ids", "positions"; plus one per
    # extra token-aligned key.
    grids: Dict[str, np.ndarray]
    # [S] per-sequence vectors (scalar keys), padded to the seqs bucket.
    scalars: Dict[str, np.ndarray]
    # [S] grid coordinates per sequence (padded entries point at (0, 0)).
    seq_rows: np.ndarray
    seq_first_cols: np.ndarray
    seq_last_cols: np.ndarray
    # [S] 1.0 for real sequences, 0.0 for bucket padding.
    seq_mask: np.ndarray
    # indices into the parent sample for scatter-back (real sequences only)
    sample_indices: List[int]

    @property
    def n_seqs(self) -> int:
        return len(self.sample_indices)

    @property
    def n_tokens(self) -> int:
        return int(sum(self.layout.seqlens))


# The fill sweep below bounds its candidate row lengths to
# ``min(cap, max(2*base, 64*fill_bucket))`` stepped by ``fill_bucket`` —
# at most this many distinct L values regardless of the token budget.
FILL_SWEEP_MAX_CANDIDATES = 64


def worst_case_row_candidates(
    length_bucket: int = 128,
    fill_bucket: Optional[int] = None,
    max_tokens_per_mb: Optional[int] = None,
) -> int:
    """Upper bound on distinct candidate row lengths the fill sweep in
    :func:`split_into_microbatches` can ever emit — i.e. the worst-case
    contribution of trainer ``[R, L]`` packed grids to the
    ``compile/distinct_shapes`` family. Pure arithmetic (no jax): shared
    by ``cli_args.validate_config``'s cross-check against
    ``serving.max_compiled_shapes`` so the parse-time check and the
    runtime sweep agree by construction."""
    if fill_bucket is None:
        fill_bucket = min(length_bucket, 128)
    fill_bucket = max(int(fill_bucket), 1)
    n = FILL_SWEEP_MAX_CANDIDATES
    if max_tokens_per_mb:
        # cap also bounds hi: at most ceil(cap / fill_bucket) multiples fit.
        n = min(n, -(-int(max_tokens_per_mb) // fill_bucket))
    return max(n, 1)


def split_into_microbatches(
    sample: SequenceSample,
    mb_spec: MicroBatchSpec,
    token_key: str = "packed_input_ids",
    length_bucket: int = 128,
    rows_bucket: int = 8,
    seqs_bucket: int = 8,
    row_len: Optional[int] = None,
    fill_bucket: Optional[int] = None,
    rows_multiple: int = 1,
) -> List[MicroBatch]:
    """Pack ``sample`` into micro-batches of IDENTICAL ``[R, L]`` grid shape.

    Pack-then-split (not split-then-pack): sequences are FFD-packed into
    rows of a single row length L, and rows are grouped R-per-micro-batch
    so every micro-batch compiles to the same shape. L is chosen from the
    multiples of ``fill_bucket`` that fit the longest sequence by
    minimizing total padded cells (measured r3: the old per-mb
    round_up(max_len) layout reached only 0.67 fill on ~1k-token rollouts
    — a third of the MXU work was padding).

    ``fill_bucket`` (default ``min(length_bucket, 128)``) is the candidate
    row-length granularity — decoupled from ``length_bucket`` in round 8
    because stepping candidates by a coarse 512 bucket was itself a fill
    ceiling: at ~700-1000-token trajectories (the distribution of
    tests/test_packing_fill.py) the only coarse candidates were
    1536/2048-token rows at ≤0.85 fill, while the 128-grain sweep finds
    rows ≥0.92 full under a cap-4096 budget. 128 is the floor the Pallas
    attention kernel's lane width imposes on row lengths. The rows-per-micro-batch choice is swept as well (the old
    fixed ``cap // L`` wasted up to R-1 padding rows in the last
    micro-batch). Finer candidates mean the compiled [R, L] shape tracks
    the length distribution more closely — more distinct shapes across
    drifting distributions; raise ``fill_bucket`` back toward
    ``length_bucket`` to trade fill for shape stability.

    ``rows_bucket`` is kept for API compatibility; uniform grouping already
    pins the compiled shape set. ``rows_multiple`` (the engine's mesh: the
    degree of its data axes) restricts R to its multiples, so that a
    micro-batch's rows split evenly over the chips; where the token cap
    allows fewer rows than that, R is ``rows_multiple`` itself.
    """
    if sample.bs == 0:
        return []
    if fill_bucket is None:
        fill_bucket = min(length_bucket, 128)
    seqlens = [int(x) for x in sample.total_lens(token_key)]
    total = sum(seqlens)
    cap = int(mb_spec.max_tokens_per_mb or total)
    base = packing.round_up(max(seqlens), fill_bucket)
    cap = max(cap, base * rows_multiple)
    if row_len is not None:
        L0 = packing.round_up(row_len, length_bucket)
        if max(seqlens) > L0:
            raise ValueError(
                f"sequence of length {max(seqlens)} exceeds row_len {L0}"
            )
        cands = [L0]
    else:
        # Bound the sweep: rows much longer than a few multiples of the
        # longest sequence stop improving fill, and an uncapped token
        # budget must not turn into an O(total/fill_bucket) FFD sweep.
        hi = min(cap // rows_multiple, max(2 * base, 64 * fill_bucket))
        cands = list(range(base, hi + 1, fill_bucket))
    min_mbs = mb_spec.n_mbs or 1
    best = None
    for L in cands:
        rows = datapack.ffd_allocate(seqlens, L)
        # Rows per micro-batch: bounded by the token cap AND small enough
        # that >= mb_spec.n_mbs groups come out (the documented minimum);
        # swept downward because ceil(len(rows)/R) rounding can pad the
        # last micro-batch with up to R-1 dead rows.
        max_R = max(min(cap // L, len(rows) // min_mbs), 1)
        max_R = max(max_R // rows_multiple, 1) * rows_multiple
        for R in range(max_R, 0, -rows_multiple):
            n_mbs = -(-len(rows) // R)
            cells = n_mbs * R * L
            # Strict < keeps the FIRST optimum: the smaller row length
            # (less per-row causal attention waste) and, within one L, the
            # larger R (fewer dispatches) for the same padded-cell count.
            if best is None or cells < best[0]:
                best = (cells, L, R, rows)
    _, L, R, rows = best
    out = []
    for m in range(0, len(rows), R):
        grp = rows[m : m + R]
        idxs = [i for r in grp for i in r]
        if not idxs:
            continue
        placements: List[Tuple[int, int]] = [None] * len(idxs)  # type: ignore
        sub_pos = {g: p for p, g in enumerate(idxs)}
        for row, r in enumerate(grp):
            col = 0
            for i in r:
                placements[sub_pos[i]] = (row, col)
                col += seqlens[i]
        layout = packing.PackLayout(
            n_rows=R, row_len=L, placements=placements,
            seqlens=[seqlens[i] for i in idxs],
        )
        out.append(
            make_microbatch(
                sample.select_idx(idxs), token_key=token_key,
                length_bucket=length_bucket, rows_bucket=rows_bucket,
                seqs_bucket=seqs_bucket, layout=layout, sample_indices=idxs,
            )
        )
    return out


def pack_fill(mbs: List[MicroBatch]) -> float:
    """Achieved packing fill of a micro-batch split: real tokens over
    allocated [R, L] cells — the padding factor the reported MFU divides
    by. Exported as the ``train/pack_fill`` telemetry gauge."""
    ntok = sum(mb.n_tokens for mb in mbs)
    ncells = sum(int(np.prod(mb.layout.shape)) for mb in mbs)
    return (ntok / ncells) if ncells else 0.0


def docs_per_row(mbs: List[MicroBatch]) -> float:
    """Documents over the rows that hold any, of a micro-batch split: how
    many times a row's scans, convolutions and attention masks start over.
    Exported as the ``train/docs_per_row`` telemetry gauge."""
    docs = sum(len(mb.layout.placements) for mb in mbs)
    rows = sum(len({row for row, _ in mb.layout.placements}) for mb in mbs)
    return (docs / rows) if rows else 0.0


def resets_in_chunk_per_row(mbs: List[MicroBatch], chunk: int) -> float:
    """Document starts that fall INSIDE a chunk of ``chunk`` tokens (off
    the chunk grid, the row's first document not counted) over the rows
    that hold any document, of a micro-batch split: how often a chunk of
    the gated delta rule masks its triangular matrices and the state it
    reads. Exported as the ``train/gdn_resets_in_chunk_per_row`` (and
    ``train/kda_resets_in_chunk_per_row``) gauge."""
    from areal_tpu.models.gdn import resets_in_chunk

    inside = sum(resets_in_chunk((col for _, col in mb.layout.placements),
                                 mb.layout.shape[1], chunk) for mb in mbs)
    rows = sum(len({row for row, _ in mb.layout.placements}) for mb in mbs)
    return (inside / rows) if rows else 0.0


def shortconv_resets_per_row(mbs: List[MicroBatch]) -> float:
    """Document starts at which a short convolution's taps are cut (a
    start behind another document of its row) over the rows that hold any
    document, of a micro-batch split. Exported as the
    ``train/shortconv_resets_per_row`` gauge."""
    per_row = docs_per_row(mbs)  # a row's first document cuts nothing
    return per_row - 1.0 if per_row else 0.0


def make_microbatch(
    sample: SequenceSample,
    token_key: str = "packed_input_ids",
    length_bucket: int = 128,
    rows_bucket: int = 8,
    seqs_bucket: int = 8,
    row_len: Optional[int] = None,
    sample_indices: Optional[Sequence[int]] = None,
    layout: Optional[packing.PackLayout] = None,
) -> MicroBatch:
    assert sample.data is not None, "micro-batching needs materialized data"
    seqlens = [int(x) for x in sample.total_lens(token_key)]
    if layout is None:
        layout = packing.plan_packing(
            seqlens, length_bucket=length_bucket, rows_multiple=rows_bucket,
            row_len=row_len,
        )
    grid = packing.make_grid(layout)
    grids: Dict[str, np.ndarray] = {
        "tokens": packing.batch_from_packed(
            sample.data[token_key].astype(np.int32), layout
        ),
        "segment_ids": grid["segment_ids"],
        "positions": grid["positions"],
    }
    scalars: Dict[str, np.ndarray] = {}
    total = sum(seqlens)
    for k in sample.keys:
        if k == token_key or sample.data.get(k) is None:
            continue
        v = sample.data[k]
        if v.shape[0] == total and [sum(s) for s in sample.seqlens[k]] == seqlens:
            grids[k] = packing.batch_from_packed(v, layout)
        elif v.shape[0] == sample.bs:
            scalars[k] = v
        else:
            raise ValueError(
                f"key {k}: leading dim {v.shape[0]} is neither token-aligned "
                f"({total}) nor per-sample ({sample.bs}); pad per-token keys "
                "to full length (see module docstring)"
            )
    # Bucket the sequence count too: without this, every distinct n_seqs
    # would recompile the jitted step (the [S]-shaped arrays below are jit
    # inputs), re-introducing the churn the [B, L] bucketing removes.
    n = len(seqlens)
    S = packing.round_up(max(n, 1), seqs_bucket)
    rows = np.zeros(S, np.int32)
    firsts = np.zeros(S, np.int32)
    lasts = np.zeros(S, np.int32)
    seq_mask = np.zeros(S, np.float32)
    rows[:n] = [p[0] for p in layout.placements]
    firsts[:n] = [p[1] for p in layout.placements]
    lasts[:n] = [p[1] + sl - 1 for p, sl in zip(layout.placements, layout.seqlens)]
    seq_mask[:n] = 1.0
    for k, v in scalars.items():
        pad = np.zeros((S,) + v.shape[1:], v.dtype)
        pad[:n] = v
        scalars[k] = pad
    return MicroBatch(
        layout=layout,
        grids=grids,
        scalars=scalars,
        seq_rows=rows,
        seq_first_cols=firsts,
        seq_last_cols=lasts,
        seq_mask=seq_mask,
        sample_indices=list(sample_indices) if sample_indices is not None else
        list(range(sample.bs)),
    )


def scatter_back(
    mbs: List[MicroBatch],
    per_mb_grids: List[np.ndarray],  # [B, L, ...] device outputs per micro-batch
    n_samples: int,
) -> List[np.ndarray]:
    """Undo the micro-batch split: per-sample packed arrays in the ORIGINAL
    sample order (inverse of split_into_microbatches)."""
    out: List[Optional[np.ndarray]] = [None] * n_samples
    for mb, g in zip(mbs, per_mb_grids):
        g = np.asarray(g)
        for i, (placement, n) in enumerate(zip(mb.layout.placements, mb.layout.seqlens)):
            row, col = placement
            out[mb.sample_indices[i]] = g[row, col : col + n]
    missing = [i for i, v in enumerate(out) if v is None]
    if missing:
        raise ValueError(f"samples {missing} appear in no micro-batch")
    return out  # type: ignore
