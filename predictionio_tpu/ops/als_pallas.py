"""Pallas TPU segment accumulator for the ALS normal equations.

Replaces the scatter-add hot loop (`ops.als._segment_stats`) on single-device
TPU runs with a one-hot MXU formulation that contains NO scatter at all:

  1. HOST (once per training run, reused across all iterations): sort the
     COO stream by segment and block-pad it so every ``T``-row tile of the
     stream lands in exactly ONE ``S``-row block of the accumulator.
  2. DEVICE (per half-step): gather the opposite factors, build the flat
     update rows [P, 128] = [vec(w * v v^T) | rhs*v | valid | 0-pad], and
     run the pallas kernel: for each tile, a [T, S] one-hot of the local
     segment ids is contracted with the update tile on the MXU,
     accumulating into the tile's (VMEM-resident, revisited) output block.
     The fused kernel (``segment_stats_fused``, the first rung) builds the
     update rows in VMEM from the gathered rows and never writes them; the
     chunked one (``segment_stats_pallas``) reads them from HBM a chunk at
     a time.

Cost is nnz * S * 128 * 2 FLOPs — ~0.65 TFLOP per ML-20M half-step —
independent of index distribution, versus a TPU scatter that processes one
row at a time and degrades further under skew.  One-hot entries are exact in
bfloat16; the precision choices (``_make_kernel``) concern the update rows
only.  What a half-step costs on the chip, by operation, is in PERF.md
(sections 5 and 6) and the ledger's ``als_accumulate_device_s``: numbers live
there, not here.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

S = 128   # accumulator rows per output block (lane-aligned)
T = 1024  # COO rows per tile
W = 128   # default flat row width (k*k + k + 1 <= 128 for rank <= 10);
          # higher ranks widen to the next 128 multiple (see row_width)


def row_width(rank: int) -> int:
    """Flat update row width for ``rank``: vec(A) | b | count, padded to
    full 128-lane tiles so the kernel's [T, W] blocks stay lane-aligned."""
    need = rank * rank + rank + 1
    return (need + 127) // 128 * 128


@dataclass(frozen=True)
class SegmentPlan:
    """Host-side layout for one scatter direction (by-user or by-item).

    Static across training iterations — the expensive argsort happens once.
    """

    seg3: np.ndarray          # [nt, T//128, 128] int32 local ids, -1 = pad
    dest_perm: np.ndarray     # [P] original-row index feeding each slot
    pad_mask: np.ndarray      # [P] bool, True where slot is padding
    block_map: np.ndarray     # [nt] int32 output block per tile
    first: np.ndarray         # [nt] int32 1 on a block's first tile
    n_blocks: int
    n_tiles: int
    padded_len: int


def radix_passes(num_seg_pad: int) -> int:
    """16-bit digits that cover every int32 id below ``num_seg_pad``: the
    stable argsorts ``build_plan`` takes its order from."""
    return 1 if num_seg_pad <= 1 << 16 else 2


def _stable_order(keys: np.ndarray, num_seg_pad: int) -> np.ndarray:
    """``argsort(keys, kind="stable")`` of int32 ``keys`` in
    [0, num_seg_pad), in linear time: numpy's stable sort is a radix sort
    for 8- and 16-bit integers only (for int32 it is a merge sort), so the
    order is composed least-significant digit first from one uint16
    argsort a digit."""
    digit = np.empty(len(keys), np.uint16)
    order = None
    for p in range(radix_passes(num_seg_pad)):
        # the unsafe cast keeps the low 16 bits of the shifted key: digit p,
        # written into the one buffer with no int32 temporaries (a fresh
        # 80 MB array costs the chip's host ~0.1 s in page faults)
        np.right_shift(keys, 16 * p, out=digit, casting="unsafe")
        if order is None:
            order = np.argsort(digit, kind="stable")
        else:
            order = order[np.argsort(digit[order], kind="stable")]
    return order


#: rows a ``bincount`` call of ``_segment_counts`` takes
_COUNT_ROWS = 1 << 20


def _segment_counts(keys: np.ndarray, num_seg_pad: int) -> np.ndarray:
    """``bincount(keys, minlength=num_seg_pad)``, ``_COUNT_ROWS`` rows a
    call: bincount widens its int32 input to an int64 copy first, 160 MB
    of fresh pages at 20 M rows and four fifths of the call's time on the
    chip's host; a slice's copy is 8 MB that the allocator hands back."""
    counts = np.bincount(keys[:_COUNT_ROWS], minlength=num_seg_pad)
    for start in range(_COUNT_ROWS, len(keys), _COUNT_ROWS):
        counts += np.bincount(
            keys[start:start + _COUNT_ROWS], minlength=num_seg_pad
        )
    return counts


def build_plan(seg: np.ndarray, num_seg_pad: int) -> SegmentPlan:
    """Sort by segment + block-pad; ~3% extra rows at ML-20M shapes.

    Only the order is sorted for.  The sorted stream is
    ``repeat(arange(num_seg_pad), bincount(seg))``, so the blocks' counts
    and offsets, the local ids and the padding all come from the counts,
    and the sorted rows fill the slots that are not padding in order."""
    if num_seg_pad % S != 0:
        raise ValueError(f"num_seg_pad must be a multiple of {S}")
    if len(seg) and (int(seg.min()) < 0 or int(seg.max()) >= num_seg_pad):
        # the scatter path this replaces dropped out-of-range ids via
        # .at[].add(mode="drop"); here they would index past the output
        # buffer through block_map — fail loudly instead of corrupting
        raise ValueError(
            f"segment ids must be in [0, {num_seg_pad}); got "
            f"[{int(seg.min())}, {int(seg.max())}]"
        )
    keys = np.ascontiguousarray(seg, dtype=np.int32)
    order = _stable_order(keys, num_seg_pad)
    n_blocks = num_seg_pad // S
    seg_counts = _segment_counts(keys, num_seg_pad).reshape(n_blocks, S)
    counts = seg_counts.sum(axis=1)
    padded_counts = np.maximum((counts + T - 1) // T * T, T)
    starts = np.concatenate([[0], np.cumsum(padded_counts)[:-1]])
    P = int(padded_counts.sum())
    # a block's slots: its segments' rows under their local ids, in segment
    # order, then -1 up to the tile boundary
    block_ids = np.append(np.arange(S, dtype=np.int32), np.int32(-1))
    seg_local = np.repeat(
        np.tile(block_ids, n_blocks),
        np.column_stack([seg_counts, padded_counts - counts]).ravel(),
    )
    pad_mask = seg_local < 0
    # the slots that are not padding take the sorted rows in their order
    dest_perm = np.zeros(P, np.int64)
    dest_perm[~pad_mask] = order
    nt = P // T
    block_map = np.repeat(
        np.arange(n_blocks, dtype=np.int32), padded_counts // T
    )
    first = np.zeros(nt, np.int32)
    first[starts // T] = 1
    return SegmentPlan(
        seg3=seg_local.reshape(nt, T // 128, 128),
        dest_perm=dest_perm,
        pad_mask=pad_mask,
        block_map=block_map,
        first=first,
        n_blocks=n_blocks,
        n_tiles=nt,
        padded_len=P,
    )


def _make_kernel(precision: str):
    """Kernel body with the MXU pass count as a compile-time choice.

    The one-hot operand is EXACT in bf16 (entries 0/1), so all the
    precision choices concern the update-row operand:

    - "highest": lax.Precision.HIGHEST — XLA's 6-pass f32 decomposition.
      Exact but 6x the MXU cycles; at ML-20M the matmul passes alone cost
      ~150 ms/half-step.
    - "hilo": 2-pass Dekker-style split — upd = hi + lo with hi = bf16(upd)
      and lo = bf16(upd - hi); accumulate onehot@hi + onehot@lo in f32.
      Relative error ~2^-16 (vs 2^-24 exact), 3x fewer MXU passes than
      HIGHEST.  This is the default.
    - "bf16": single pass, update rows rounded to bf16 (~2^-8) — fastest,
      for quality-insensitive sweeps.
    """

    def kernel(block_map_ref, first_ref, seg_ref, upd_ref, out_ref):
        i = pl.program_id(0)
        seg = seg_ref[0]  # [T//128, 128] int32
        onehot = (
            seg[:, :, None]
            == jax.lax.broadcasted_iota(jnp.int32, (T // 128, 128, S), 2)
        ).astype(jnp.float32).reshape(T, S)
        dn = (((0,), (0,)), ((), ()))
        upd = upd_ref[:]
        if precision == "highest":
            contrib = jax.lax.dot_general(
                onehot, upd, dimension_numbers=dn,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
        else:
            oh16 = onehot.astype(jnp.bfloat16)
            hi = upd.astype(jnp.bfloat16)
            contrib = jax.lax.dot_general(
                oh16, hi, dimension_numbers=dn,
                preferred_element_type=jnp.float32,
            )
            if precision == "hilo":
                lo = (upd - hi.astype(jnp.float32)).astype(jnp.bfloat16)
                contrib = contrib + jax.lax.dot_general(
                    oh16, lo, dimension_numbers=dn,
                    preferred_element_type=jnp.float32,
                )

        @pl.when(first_ref[i] == 1)
        def _():
            out_ref[:] = contrib

        @pl.when(first_ref[i] == 0)
        def _():
            out_ref[:] = out_ref[:] + contrib

    return kernel


def make_segment_accum(
    n_tiles: int,
    n_blocks: int,
    width: int = W,
    precision: str = "hilo",
    interpret: bool = False,
):
    """pallas_call: (block_map[nt], first[nt], seg3, updates[P, width]) ->
    accumulator [n_blocks * S, width]."""
    if precision not in ("highest", "hilo", "bf16"):
        raise ValueError(f"unknown precision {precision!r}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, T // 128, 128), lambda i, bm, fr: (i, 0, 0)),
            pl.BlockSpec((T, width), lambda i, bm, fr: (i, 0)),
        ],
        out_specs=pl.BlockSpec((S, width), lambda i, bm, fr: (bm[i], 0)),
    )
    return pl.pallas_call(
        _make_kernel(precision),
        out_shape=jax.ShapeDtypeStruct((n_blocks * S, width), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
        name="als_segment_accum",
    )


#: width-slab size of the fused kernel: each grid step builds a
#: [SLAB_W, T] slice of the transposed update rows, so VMEM per block is
#: ~SLAB_W*T*4*5 bytes regardless of rank (wide ranks add grid steps,
#: not VMEM or compile size)
SLAB_W = 128

#: lanes of a gathered row of the fused path: the three bfloat16 parts of
#: ``k`` float32 factors side by side, zeros up to one full lane tile
#: (3 * 32 = 96 <= 128 wherever ``ops.als._use_pallas`` lets the kernel run)
PARTS_W = 128


def split3(x):
    """The three bfloat16 parts of float32 ``x``, as float32 arrays:
    ``hi = bf16(x)``, ``mid = bf16(x - hi)``, ``lo = x - hi - mid``.

    A float32's 24 mantissa bits fit three bfloat16's 3 x 8, both
    subtractions are exact, and the last remainder has at most 7 significant
    bits, so ``hi + mid + lo == x`` bit for bit, summed in float32 from
    either end, wherever no part is a subnormal number and ``hi`` is finite
    (``2**-103 <= |x| <= 3.3895e38``; ``tests/test_als_pallas.py`` says what
    happens outside).  Rounded with ``reduce_precision``: the chip's
    compiler removes a cast to bfloat16 and back."""
    def bf16(v):
        return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)

    hi = bf16(x)
    rest = x - hi
    mid = bf16(rest)
    return hi, mid, rest - mid


def split_table(factors):
    """``[N, k]`` float32 factors -> ``[N, PARTS_W]`` bfloat16 rows
    ``hi | mid | lo | 0``: what the fused path gathers, 256 bytes a row.
    The split is made here, on the table's N rows, and not on the P rows
    the gather writes."""
    n, k = factors.shape
    if 3 * k > PARTS_W:
        raise ValueError(f"rank {k}: three parts do not fit {PARTS_W} lanes")
    parts = jnp.concatenate(
        [*split3(factors), jnp.zeros((n, PARTS_W - 3 * k), factors.dtype)],
        axis=1,
    )
    return parts.astype(jnp.bfloat16)  # exact: every part is a bfloat16


def selectors(k: int) -> np.ndarray:
    """The fused kernel's 0/1 selection matrices, one ``[2 * SLAB_W,
    PARTS_W]`` block a width slab: rows ``[:SLAB_W]`` pick component
    ``a = r // k`` of update row ``r`` (``r - k*k`` in the rhs block),
    rows ``[SLAB_W:]`` pick ``b = r % k``, each from all three parts of a
    ``split_table`` row at once."""
    kk = k * k
    r = np.arange(row_width(k))[:, None]
    lane = np.arange(PARTS_W)[None, :]
    c = np.where(lane < 3 * k, lane % k, -1)
    pa = (np.where(r < kk, r // k, r - kk) == c) & (r < kk + k)
    pb = ((r % k) == c) & (r < kk)
    sel = np.stack(
        [pa.reshape(-1, SLAB_W, PARTS_W), pb.reshape(-1, SLAB_W, PARTS_W)],
        axis=1,
    )
    return sel.reshape(-1, PARTS_W).astype(np.float32)


def _make_fused_kernel(k: int, precision: str):
    """Whole-stream fused kernel in TRANSPOSED orientation.

    The opposite side's rows arrive as the gather wrote them,
    ``rows [T, PARTS_W]`` bfloat16 (``split_table``: the three bfloat16
    parts of each factor), and the static weights as ``wrv [nt, 3, T]``.
    The gather's result is the one tall array of the path (P rows of 256
    bytes, 5.3 GB at ML-20M); nothing copies it into another layout.

    The flat update rows are built IN VMEM as their transpose
    ``updT [SLAB_W, T]`` (one 128-row slab of the full row_width per grid
    step) without any sublane concatenation.  With ``v`` the rows' float32
    factors, ``A[r, t] = v[t, r // k]`` and ``B[r, t] = v[t, r % k]`` are
    selections, and both come from ONE bfloat16 MXU pass that contracts the
    rows' minor dimension (``sel . rows^T``, the ``q @ k^T`` form): the
    selector (``selectors``) is 0/1, the parts are exact in bfloat16, the
    pass accumulates in float32, and ``hi + mid + lo`` is the factor bit for
    bit (``split3``).  Then

        updT = A * (B * w + sel_rhs * rhs) + sel_val * val

    — rows r < k*k get v_a*v_b*w, rows k*k..k*k+k get v_c*rhs (B is zero
    there), row k*k+k gets val, the rest 0.  (Selecting from float32 rows
    with products at Precision.HIGHEST is as exact and costs six MXU passes
    a product, each over a contraction of k that fills the 128-deep array
    as 128 would: two thirds of such a kernel's time at ML-20M, PERF.md
    section 6, PR 39.)

    The grid is (n_slabs, n_tiles) with the SLAB AXIS OUTER: within one
    slab the stream sweeps tiles in block-sorted order, so each output
    block stays VMEM-resident across all its tiles and is written to HBM
    exactly once — the chunk scan's per-chunk accumulator
    read-modify-write (71 MB per chunk per half-step at ML-20M)
    disappears entirely.  Wide ranks (rank 32 -> 9 slabs) re-read the
    input streams once per slab instead of blowing up the kernel's VMEM
    footprint or its Mosaic compile time (the monolithic width-1152
    chunked kernel took ~25 min to compile; each slab kernel is the same
    small program at every rank).
    """
    kk = k * k

    def kernel(block_map_ref, first_ref, seg_ref, rows_ref, wrv_ref, sel_ref,
               out_ref):
        s = pl.program_id(0)
        i = pl.program_id(1)
        seg = seg_ref[0]  # [T//128, 128] int32
        onehot = (
            seg[:, :, None]
            == jax.lax.broadcasted_iota(jnp.int32, (T // 128, 128, S), 2)
        ).astype(jnp.float32).reshape(T, S)
        wrv = wrv_ref[0]  # [3, T]
        w, rhs, val = wrv[0:1, :], wrv[1:2, :], wrv[2:3, :]
        # [2*SLAB_W, PARTS_W] . [T, PARTS_W]^T: A over B, one bf16 pass
        ab = jax.lax.dot_general(
            sel_ref[:], rows_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        A, B = ab[:SLAB_W], ab[SLAB_W:]
        r1 = (
            jax.lax.broadcasted_iota(jnp.int32, (SLAB_W, 1), 0) + s * SLAB_W
        )
        sel_rhs = ((r1 >= kk) & (r1 < kk + k)).astype(jnp.float32)
        sel_val = (r1 == kk + k).astype(jnp.float32)
        updT = A * (B * w + sel_rhs * rhs) + sel_val * val

        dn = (((1,), (0,)), ((), ()))  # contract T: [width,T] @ [T,S]
        if precision == "highest":
            contrib = jax.lax.dot_general(
                updT, onehot, dimension_numbers=dn,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
        else:
            oh16 = onehot.astype(jnp.bfloat16)
            hi = updT.astype(jnp.bfloat16)
            contrib = jax.lax.dot_general(
                hi, oh16, dimension_numbers=dn,
                preferred_element_type=jnp.float32,
            )
            if precision == "hilo":
                lo = (updT - hi.astype(jnp.float32)).astype(jnp.bfloat16)
                contrib = contrib + jax.lax.dot_general(
                    lo, oh16, dimension_numbers=dn,
                    preferred_element_type=jnp.float32,
                )

        @pl.when(first_ref[i] == 1)
        def _():
            out_ref[:] = contrib

        @pl.when(first_ref[i] == 0)
        def _():
            out_ref[:] = out_ref[:] + contrib

    return kernel


def make_fused_accum(
    n_tiles: int,
    n_blocks: int,
    rank: int,
    precision: str = "hilo",
    interpret: bool = False,
):
    """pallas_call over the WHOLE stream: (block_map[nt], first[nt],
    seg3[nt, T//128, 128], rows[nt, T, PARTS_W] bf16, wrv[nt, 3, T],
    sel[n_slabs * 2 * SLAB_W, PARTS_W] bf16) -> TRANSPOSED accumulator
    [n_blocks * width, S] (SLAB_W-row blocks, width-slab grid axis outer so
    blocks revisit consecutively within a slab).

    ``rows`` is the gather's own result, a tile of it one contiguous
    256 KB block; ``wrv`` is [nt, small, T] (Mosaic wants the last two
    block dims divisible by (8, 128) or equal to the array dims, so the
    tile axis leads and the small axis spans its whole dimension); a
    slab's selectors are fetched when the slab changes."""
    if precision not in ("highest", "hilo", "bf16"):
        raise ValueError(f"unknown precision {precision!r}")
    width = row_width(rank)
    n_slabs = width // SLAB_W
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_slabs, n_tiles),
        in_specs=[
            pl.BlockSpec((1, T // 128, 128), lambda s, i, bm, fr: (i, 0, 0)),
            pl.BlockSpec((1, T, PARTS_W), lambda s, i, bm, fr: (i, 0, 0)),
            pl.BlockSpec((1, 3, T), lambda s, i, bm, fr: (i, 0, 0)),
            pl.BlockSpec((2 * SLAB_W, PARTS_W), lambda s, i, bm, fr: (s, 0)),
        ],
        out_specs=pl.BlockSpec(
            (SLAB_W, S), lambda s, i, bm, fr: (bm[i] * n_slabs + s, 0)
        ),
    )
    return pl.pallas_call(
        _make_fused_kernel(rank, precision),
        out_shape=jax.ShapeDtypeStruct((n_blocks * width, S), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
        name="als_fused_accum",
    )


def make_wrv(rating2d, valid2d, implicit_prefs: bool, alpha: float):
    """Static per-row weights for the fused kernel, layout-clean
    [nt, 3, T]: A-weight | rhs | valid.  Depends on data + train
    hyperparams only — computed once per train dispatch, NOT per
    iteration."""
    from predictionio_tpu.ops.als import confidence_weights

    w, rhs = confidence_weights(
        rating2d, valid2d, implicit_prefs, alpha, jnp.float32
    )
    return jnp.stack([w, rhs, valid2d.astype(jnp.float32)], axis=1)


def segment_stats_fused(
    plan_args: tuple,
    other_idx2d,    # [nt, T] int32 padded/permuted opposite-entity index
    wrv,            # [nt, 3, T] f32 from make_wrv
    other_factors,  # [num_other_pad, k] replicated
    n_tiles: int,
    n_blocks: int,
    precision: str = "hilo",
    interpret: bool = False,
):
    """Single-grid fused accumulation over the whole stream.  Same output
    contract as segment_stats_pallas ([n_blocks*S, row_width] with columns
    [vec(A) | b | count]); internally everything runs transposed (see
    _make_fused_kernel) and the per-half-step device work is the table's
    split, ONE gather of its rows ([nt, T, PARTS_W], read by the kernel
    where the gather wrote it) and the kernel."""
    block_map, first, seg3 = plan_args
    k = other_factors.shape[1]
    width = row_width(k)
    rows = jnp.take(
        split_table(other_factors), other_idx2d, axis=0, mode="clip"
    )
    accum = make_fused_accum(
        n_tiles, n_blocks, k, precision=precision, interpret=interpret
    )
    sel = jnp.asarray(selectors(k), jnp.bfloat16)
    acc_t = accum(block_map, first, seg3, rows, wrv, sel)
    return (
        acc_t.reshape(n_blocks, width, S)
        .transpose(0, 2, 1)
        .reshape(n_blocks * S, width)
    )


@dataclass(frozen=True)
class ChunkedPlan:
    """Per-chunk tile layout: the stream is processed ``tiles_per_chunk``
    tiles at a time inside a lax.scan, bounding the [rows, W] flat-update
    intermediate to one chunk instead of the whole stream (the full-stream
    version OOMs HBM at ML-20M scale)."""

    seg3: np.ndarray       # [C, tpc, T//128, 128]
    block_map: np.ndarray  # [C, tpc]
    first: np.ndarray      # [C, tpc] 1 on a block's first tile IN THE CHUNK
    visited: np.ndarray    # [C, n_blocks] f32 1.0 where the chunk touched
    dest_perm: np.ndarray  # [C*tpc*T] original row per slot (0 for filler)
    pad_mask: np.ndarray   # [C*tpc*T] True at padding/filler slots
    n_blocks: int
    n_chunks: int
    tiles_per_chunk: int


def chunk_plan(plan: SegmentPlan, tiles_per_chunk: int = 1024) -> ChunkedPlan:
    tpc = min(tiles_per_chunk, max(plan.n_tiles, 1))
    C = (plan.n_tiles + tpc - 1) // tpc
    nt2 = C * tpc
    fill = nt2 - plan.n_tiles
    seg3 = np.concatenate(
        [plan.seg3, np.full((fill, T // 128, 128), -1, np.int32)]
    )
    # filler tiles target block 0 with first=1: they zero block 0 of their
    # chunk's temp accumulator and contribute nothing; block 0's real rows
    # live in chunk 0 (sorted stream), so later chunks add masked zeros
    block_map = np.concatenate([plan.block_map, np.zeros(fill, np.int32)])
    first = np.concatenate([plan.first, np.ones(fill, np.int32)]).astype(
        np.int32
    )
    # a block continuing across a chunk boundary must re-zero in the new
    # chunk's temp accumulator
    first = first.copy()
    first[np.arange(0, nt2, tpc)] = 1
    visited = np.zeros((C, plan.n_blocks), np.float32)
    for c in range(C):
        visited[c, np.unique(block_map[c * tpc : (c + 1) * tpc])] = 1.0
    dest_perm = np.concatenate(
        [plan.dest_perm, np.zeros(fill * T, np.int64)]
    )
    pad_mask = np.concatenate(
        [plan.pad_mask, np.ones(fill * T, bool)]
    )
    return ChunkedPlan(
        seg3=seg3.reshape(C, tpc, T // 128, 128),
        block_map=block_map.reshape(C, tpc),
        first=first.reshape(C, tpc),
        visited=visited,
        dest_perm=dest_perm,
        pad_mask=pad_mask,
        n_blocks=plan.n_blocks,
        n_chunks=C,
        tiles_per_chunk=tpc,
    )


def segment_stats_pallas(
    plan_args: tuple,
    other_idx_p,  # [C, tpc*T] padded/permuted opposite-entity index
    rating_p,     # [C, tpc*T] padded rating (0 at padding)
    valid_p,      # [C, tpc*T] padded validity (0 at padding)
    other_factors,  # [num_other_pad, k] replicated
    implicit_prefs: bool,
    alpha: float,
    tiles_per_chunk: int,
    n_blocks: int,
    precision: str = "hilo",
    interpret: bool = False,
):
    """Flat per-segment stats [n_blocks*S, width] via the one-hot MXU
    kernel, scanning chunk by chunk.  Column layout matches
    ops.als._segment_stats: [vec(A) | b | count]; width = row_width(rank)."""
    block_map, first, seg3, visited = plan_args
    k = other_factors.shape[1]
    width = row_width(k)
    accum = make_segment_accum(
        tiles_per_chunk, n_blocks, width=width, precision=precision,
        interpret=interpret,
    )
    rows = tiles_per_chunk * T

    from predictionio_tpu.ops.als import confidence_weights

    def body(acc, xs):
        bm, fr, s3, vis, oth, rat, val = xs
        cv = other_factors[oth]
        a_weight, rhs = confidence_weights(
            rat, val, implicit_prefs, alpha, cv.dtype
        )
        flat = jnp.concatenate(
            [
                (cv[:, :, None] * cv[:, None, :]).reshape(rows, k * k)
                * a_weight[:, None],
                cv * rhs[:, None],
                val[:, None],
                jnp.zeros((rows, width - (k * k + k + 1)), cv.dtype),
            ],
            axis=1,
        )
        out = accum(bm, fr, s3, flat)
        # blocks this chunk never visited hold garbage (possibly NaN) —
        # where(), not multiply: NaN * 0 is still NaN
        mask = jnp.repeat(vis, S)[:, None] > 0
        return acc + jnp.where(mask, out, 0.0), None

    acc0 = jnp.zeros((n_blocks * S, width), jnp.float32)
    acc, _ = jax.lax.scan(
        body, acc0,
        (block_map, first, seg3, visited, other_idx_p, rating_p, valid_p),
    )
    return acc
