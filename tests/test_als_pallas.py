"""Pallas segment accumulator: logic (interpret mode) + plan construction.

The TPU kernel itself runs only on real hardware; these tests validate the
host-side plan and the kernel semantics through the pallas interpreter so
the scatter-free path is covered on every platform.
"""

import numpy as np
import jax
import jax.numpy as jnp
import ml_dtypes
import pytest

from predictionio_tpu.ops import als_pallas as ap


def test_plan_covers_every_row_once():
    rng = np.random.default_rng(0)
    seg = rng.integers(0, 300, 4000)
    plan = ap.build_plan(seg.astype(np.int64), 384)
    assert plan.padded_len % ap.T == 0
    assert plan.n_tiles == plan.padded_len // ap.T
    # every real row appears exactly once; padding slots are marked
    real = ~plan.pad_mask
    assert real.sum() == len(seg)
    assert sorted(plan.dest_perm[real]) == list(range(len(seg)))
    # a tile's rows all belong to the tile's block
    seg_flat = plan.seg3.reshape(plan.n_tiles, ap.T)
    for t in range(plan.n_tiles):
        rows = seg_flat[t]
        assert ((rows >= -1) & (rows < ap.S)).all()
    # first flags mark exactly one tile per non-empty block
    assert plan.first.sum() == plan.n_blocks


def test_out_of_range_segment_rejected():
    # the scatter path dropped bad ids; the pallas path must fail loudly
    # rather than index past the output buffer (silent corruption)
    with pytest.raises(ValueError, match="segment ids"):
        ap.build_plan(np.array([0, 5, 384]), 384)
    with pytest.raises(ValueError, match="segment ids"):
        ap.build_plan(np.array([-1, 5]), 384)


def _reference_plan(seg: np.ndarray, num_seg_pad: int) -> ap.SegmentPlan:
    """``build_plan`` as it stood until PR 29, after its input checks (a
    32-bit merge sort, then gathers and scatters through the order): the
    plan the linear-time one has to return, element for element and dtype
    for dtype."""
    S, T = ap.S, ap.T
    order = np.argsort(seg.astype(np.int32), kind="stable")
    seg_sorted = seg[order]
    n_blocks = num_seg_pad // S
    blk = seg_sorted // S
    counts = np.bincount(blk, minlength=n_blocks)
    padded_counts = np.maximum((counts + T - 1) // T * T, T)
    starts = np.concatenate([[0], np.cumsum(padded_counts)[:-1]])
    P = int(padded_counts.sum())
    within = np.arange(len(seg)) - np.concatenate(
        [[0], np.cumsum(counts)[:-1]]
    )[blk]
    dest = starts[blk] + within
    seg_local = np.full(P, -1, np.int32)
    seg_local[dest] = (seg_sorted - blk * S).astype(np.int32)
    nt = P // T
    block_map = np.repeat(
        np.arange(n_blocks, dtype=np.int32), padded_counts // T
    )
    first = np.zeros(nt, np.int32)
    first[starts // T] = 1
    dest_perm = np.zeros(P, np.int64)
    dest_perm[dest] = order
    return ap.SegmentPlan(
        seg3=seg_local.reshape(nt, T // 128, 128),
        dest_perm=dest_perm,
        pad_mask=seg_local < 0,
        block_map=block_map,
        first=first,
        n_blocks=n_blocks,
        n_tiles=nt,
        padded_len=P,
    )


def _uniform(rng, n, num_seg_pad):
    return rng.integers(0, num_seg_pad, n)


def _zipf(rng, n, num_seg_pad):
    # a few heavy segments scattered over the id range, a long thin tail
    ids = rng.permutation(num_seg_pad)
    return ids[np.minimum(rng.zipf(1.3, n) - 1, num_seg_pad - 1)]


def _one_segment(rng, n, num_seg_pad):
    return np.full(n, num_seg_pad - 3)


def _exact_tiles(rng, n, num_seg_pad):
    # the last block holds exactly 2 T rows (no padding slot in it), the
    # first T + 1 (one row into its second tile); every block between them
    # is empty
    seg = np.concatenate([
        rng.integers(num_seg_pad - ap.S, num_seg_pad, 2 * ap.T),
        rng.integers(0, ap.S, ap.T + 1),
    ])
    return seg[rng.permutation(len(seg))]


def _no_rows(rng, n, num_seg_pad):
    return np.zeros(0, np.int64)


def _as_int32(seg):
    return seg.astype(np.int32)


def _as_int64(seg):
    return seg.astype(np.int64)


def _as_strided_view(seg):
    # every other element of a buffer twice as long: not contiguous
    buf = np.zeros(2 * len(seg), np.int32)
    buf[::2] = seg
    view = buf[::2]
    assert len(view) < 2 or not view.flags.c_contiguous
    return view


@pytest.mark.parametrize("given", [_as_int32, _as_int64, _as_strided_view])
@pytest.mark.parametrize(
    "ids", [_uniform, _zipf, _one_segment, _exact_tiles, _no_rows]
)
@pytest.mark.parametrize("num_seg_pad", [384, 65_536, 65_664, 1_000_064])
def test_plan_equals_the_sorted_and_gathered_plan(
    monkeypatch, num_seg_pad, ids, given
):
    """The plan laid out from counts and a 16-bit radix order IS the plan
    the 32-bit sort and the gathers through its order gave: every field,
    values and dtypes, with ids on both sides of the 16-bit digit (65,664),
    blocks with no rows (4,000 rows over up to 7,813 blocks), a block whose
    count is a multiple of ``T``, and no rows at all."""
    # the counts come a slice of rows at a time: three slices here, the
    # last one short
    monkeypatch.setattr(ap, "_COUNT_ROWS", 1500)
    rng = np.random.default_rng(num_seg_pad)
    seg = given(ids(rng, 4000, num_seg_pad))
    plan = ap.build_plan(seg, num_seg_pad)
    want = _reference_plan(seg, num_seg_pad)
    for field in ("seg3", "dest_perm", "pad_mask", "block_map", "first"):
        got, ref = getattr(plan, field), getattr(want, field)
        assert got.dtype == ref.dtype, field
        assert np.array_equal(got, ref), field  # shapes too
    for field in ("n_blocks", "n_tiles", "padded_len"):
        got, ref = getattr(plan, field), getattr(want, field)
        assert type(got) is type(ref) and got == ref, field


@pytest.mark.parametrize(
    "num_seg_pad, passes",
    [(128, 1), (65_536, 1), (65_664, 2), (1_000_064, 2), (1 << 31, 2)],
)
def test_radix_passes_follow_the_id_width(num_seg_pad, passes):
    assert ap.radix_passes(num_seg_pad) == passes


@pytest.mark.parametrize("num_users, passes", [(65_536, 1), (65_664, 2)])
def test_plan_span_says_rows_and_sort_passes(pallas_on_cpu, num_users, passes):
    """``als.stage.plan`` carries what the plan did on each side: the rows
    it laid out, the slots they take, and the 16-bit sorts the order cost
    (one up to 65,536 segments, two above)."""
    from predictionio_tpu.obs.tracing import trace
    from predictionio_tpu.ops import als

    rng = np.random.default_rng(4)
    n, num_items = 600, 20
    u = rng.integers(0, num_users, n).astype(np.int32)
    u[0] = num_users - 1  # a row on the far side of the 16-bit digit
    i = rng.integers(0, num_items, n).astype(np.int32)
    r = rng.random(n).astype(np.float32)
    with trace("train") as root:
        als.train_als(
            u, i, r, num_users, num_items,
            als.ALSParams(rank=2, num_iterations=1),
        )
    (stage,) = [c for c in root.children if c.name == "als.stage"]
    tags = {
        c.tags["side"]: c.tags
        for c in stage.children if c.name == "als.stage.plan"
    }
    assert tags == {
        "user": {
            "side": "user", "rows": n, "sort_passes": passes,
            "padded_rows": ap.build_plan(u, num_users).padded_len,
        },
        "item": {
            "side": "item", "rows": n, "sort_passes": 1,
            "padded_rows": ap.build_plan(i, 128).padded_len,
        },
    }
    assert als.LAST_PLAN_INFO["rows_user"] == tags["user"]["padded_rows"]
    assert als.LAST_PLAN_INFO["rows_item"] == tags["item"]["padded_rows"]


def _accum_vs_numpy(precision):
    rng = np.random.default_rng(1)
    n, nseg = 5000, 256
    seg = rng.integers(0, 200, n)
    plan = ap.build_plan(seg.astype(np.int64), nseg)
    upd = rng.standard_normal((n, ap.W)).astype(np.float32)
    updp = upd[plan.dest_perm]
    updp[plan.pad_mask] = 0
    acc = ap.make_segment_accum(
        plan.n_tiles, plan.n_blocks, precision=precision, interpret=True
    )(
        jnp.asarray(plan.block_map),
        jnp.asarray(plan.first),
        jnp.asarray(plan.seg3),
        jnp.asarray(updp),
    )
    ref = np.zeros((nseg, ap.W), np.float32)
    np.add.at(ref, seg, upd)
    return np.asarray(acc)[:nseg], ref


def test_interpret_matches_numpy_add_at():
    acc, ref = _accum_vs_numpy("highest")
    np.testing.assert_allclose(acc, ref, rtol=2e-5, atol=2e-5)


def test_hilo_precision_near_f32():
    # 2-pass Dekker split: ~2^-16 relative — the training default
    acc, ref = _accum_vs_numpy("hilo")
    np.testing.assert_allclose(acc, ref, rtol=2e-4, atol=2e-3)


def test_bf16_precision_coarse():
    # single pass: ~2^-8 relative
    acc, ref = _accum_vs_numpy("bf16")
    err = np.abs(acc - ref) / (np.abs(ref) + 1.0)
    assert err.max() < 3e-2


def test_row_width():
    assert ap.row_width(10) == 128
    assert ap.row_width(11) == 256
    assert ap.row_width(32) == 1152


def test_segment_stats_matches_scatter_semantics():
    """segment_stats_pallas (interpret) == the scatter kernel's A/b/counts."""
    rng = np.random.default_rng(2)
    n, nseg, noth, k = 3000, 256, 64, 6
    seg = rng.integers(0, 250, n)
    oth = rng.integers(0, noth, n).astype(np.int32)
    rat = rng.uniform(-2, 2, n).astype(np.float32)
    factors = rng.standard_normal((noth, k)).astype(np.float32)
    plan = ap.chunk_plan(
        ap.build_plan(seg.astype(np.int64), nseg), tiles_per_chunk=2
    )
    rows = plan.n_chunks * plan.tiles_per_chunk * ap.T
    oth_p = oth[plan.dest_perm].copy()
    rat_p = rat[plan.dest_perm].copy()
    val_p = np.ones(rows, np.float32)
    oth_p[plan.pad_mask] = 0
    rat_p[plan.pad_mask] = 0
    val_p[plan.pad_mask] = 0
    shape2 = (plan.n_chunks, plan.tiles_per_chunk * ap.T)

    for implicit in (False, True):
        acc = ap.segment_stats_pallas(
            (jnp.asarray(plan.block_map), jnp.asarray(plan.first),
             jnp.asarray(plan.seg3), jnp.asarray(plan.visited)),
            jnp.asarray(oth_p.reshape(shape2)),
            jnp.asarray(rat_p.reshape(shape2)),
            jnp.asarray(val_p.reshape(shape2)),
            jnp.asarray(factors), implicit, 1.5,
            plan.tiles_per_chunk, plan.n_blocks, interpret=True,
        )
        acc = np.asarray(acc)[:nseg]
        v = factors[oth]
        if implicit:
            w = 1.5 * np.abs(rat)
            rhs = (1.0 + w) * (rat > 0)
        else:
            w = np.ones(n, np.float32)
            rhs = rat
        A_ref = np.zeros((nseg, k, k), np.float32)
        b_ref = np.zeros((nseg, k), np.float32)
        c_ref = np.zeros(nseg, np.float32)
        np.add.at(A_ref, seg, v[:, :, None] * v[:, None, :] * w[:, None, None])
        np.add.at(b_ref, seg, v * rhs[:, None])
        np.add.at(c_ref, seg, 1.0)
        np.testing.assert_allclose(
            acc[:, : k * k].reshape(nseg, k, k), A_ref, rtol=1e-4, atol=1e-4
        )
        np.testing.assert_allclose(
            acc[:, k * k : k * k + k], b_ref, rtol=1e-4, atol=1e-4
        )
        np.testing.assert_allclose(acc[:, k * k + k], c_ref, rtol=1e-5)


def _staged(plan, oth, rat):
    """The streams as ``ops/als`` stages them: permuted into the plan's
    slots, zero in the padding."""
    oth_p = oth[plan.dest_perm].copy()
    rat_p = rat[plan.dest_perm].copy()
    val_p = np.ones(plan.padded_len, np.float32)
    oth_p[plan.pad_mask] = 0
    rat_p[plan.pad_mask] = 0
    val_p[plan.pad_mask] = 0
    return oth_p, rat_p, val_p


def test_segment_stats_fused_matches_scatter_semantics():
    """The single-grid fused kernel (packed rows built in VMEM) must give
    the same A/b/counts as the chunked path and the scatter reference."""
    rng = np.random.default_rng(5)
    n, nseg, noth, k = 3000, 256, 64, 6
    seg = rng.integers(0, 250, n)
    oth = rng.integers(0, noth, n).astype(np.int32)
    rat = rng.uniform(-2, 2, n).astype(np.float32)
    factors = rng.standard_normal((noth, k)).astype(np.float32)
    plan = ap.build_plan(seg.astype(np.int64), nseg)
    oth_p, rat_p, val_p = _staged(plan, oth, rat)
    nt = plan.n_tiles
    for implicit in (False, True):
        wrv = ap.make_wrv(
            jnp.asarray(rat_p.reshape(nt, ap.T)),
            jnp.asarray(val_p.reshape(nt, ap.T)),
            implicit, 1.5,
        )
        acc = ap.segment_stats_fused(
            (jnp.asarray(plan.block_map), jnp.asarray(plan.first),
             jnp.asarray(plan.seg3)),
            jnp.asarray(oth_p.reshape(nt, ap.T)), wrv,
            jnp.asarray(factors),
            plan.n_tiles, plan.n_blocks, interpret=True,
        )
        acc = np.asarray(acc)[:nseg]
        v = factors[oth]
        if implicit:
            w = 1.5 * np.abs(rat)
            rhs = (1.0 + w) * (rat > 0)
        else:
            w = np.ones(n, np.float32)
            rhs = rat
        A_ref = np.zeros((nseg, k, k), np.float32)
        b_ref = np.zeros((nseg, k), np.float32)
        c_ref = np.zeros(nseg, np.float32)
        np.add.at(A_ref, seg, v[:, :, None] * v[:, None, :] * w[:, None, None])
        np.add.at(b_ref, seg, v * rhs[:, None])
        np.add.at(c_ref, seg, 1.0)
        np.testing.assert_allclose(
            acc[:, : k * k].reshape(nseg, k, k), A_ref, rtol=1e-4, atol=2e-3
        )
        np.testing.assert_allclose(
            acc[:, k * k : k * k + k], b_ref, rtol=1e-4, atol=2e-3
        )
        np.testing.assert_allclose(acc[:, k * k + k], c_ref, rtol=1e-5)


def test_fused_wide_rank_slabs():
    """Wide ranks run fused via the width-slab grid axis: rank 32 builds
    1152/128 = 9 slabs per tile and must match the scatter reference."""
    assert ap.row_width(10) == 128
    assert ap.row_width(32) == 1152
    rng = np.random.default_rng(7)
    n, nseg, noth, k = 2000, 256, 40, 17  # width 384 -> 3 slabs
    seg = rng.integers(0, 250, n)
    oth = rng.integers(0, noth, n).astype(np.int32)
    rat = rng.uniform(-2, 2, n).astype(np.float32)
    factors = rng.standard_normal((noth, k)).astype(np.float32)
    plan = ap.build_plan(seg.astype(np.int64), nseg)
    nt = plan.n_tiles
    oth_p, rat_p, val_p = _staged(plan, oth, rat)
    wrv = ap.make_wrv(
        jnp.asarray(rat_p.reshape(nt, ap.T)),
        jnp.asarray(val_p.reshape(nt, ap.T)), False, 1.0,
    )
    acc = ap.segment_stats_fused(
        (jnp.asarray(plan.block_map), jnp.asarray(plan.first),
         jnp.asarray(plan.seg3)),
        jnp.asarray(oth_p.reshape(nt, ap.T)), wrv, jnp.asarray(factors),
        nt, plan.n_blocks, interpret=True,
    )
    acc = np.asarray(acc)[:nseg]
    v = factors[oth]
    A_ref = np.zeros((nseg, k, k), np.float32)
    b_ref = np.zeros((nseg, k), np.float32)
    c_ref = np.zeros(nseg, np.float32)
    np.add.at(A_ref, seg, v[:, :, None] * v[:, None, :])
    np.add.at(b_ref, seg, v * rat[:, None])
    np.add.at(c_ref, seg, 1.0)
    np.testing.assert_allclose(
        acc[:, : k * k].reshape(nseg, k, k), A_ref, rtol=1e-4, atol=2e-3
    )
    np.testing.assert_allclose(
        acc[:, k * k : k * k + k], b_ref, rtol=1e-4, atol=2e-3
    )
    np.testing.assert_allclose(acc[:, k * k + k], c_ref, rtol=1e-5)


def _mixed_magnitude_factors(rng, n_other, k):
    """Factors of order one with, in a few rows, one column of magnitude
    1e-30 or 1e30, negatives everywhere and exact zeros: what a selection
    that was a product in disguise would round, flush or turn to NaN."""
    factors = rng.standard_normal((n_other, k)).astype(np.float32)
    factors[rng.random((n_other, k)) < 0.1] = 0.0
    for row, scale in ((1, 1e-30), (2, -1e-30), (3, 1e30), (4, -1e30)):
        factors[row, rng.integers(k)] = np.float32(scale) * np.float32(
            1 + rng.random()
        )
    return factors


def _accumulator_from_written_out_updates(plan, oth_p, wrv, factors, precision):
    """The fused accumulator with ``updT`` written out in numpy: ``A`` and
    ``B`` are the gathered factors' columns BY INDEXING (no product picks
    them), the update rows are the kernel's float32 expression, and a tile's
    rows meet its block through the kernel's one-hot contraction at
    ``precision``, one ``[SLAB_W, T] @ [T, S]`` a slab and tile, summed in
    the kernel's order (a block's tiles in turn)."""
    k = factors.shape[1]
    kk, width = k * k, ap.row_width(k)
    v = factors[oth_p]                                   # [P, k]
    r = np.arange(width)
    A = np.where(r < kk + k, v[:, np.where(r < kk, r // k, (r - kk) % k)], 0)
    B = np.where(r < kk, v[:, r % k], 0)
    w, rhs, val = (wrv[:, j, :].reshape(-1, 1) for j in range(3))
    sel_rhs = ((r >= kk) & (r < kk + k)).astype(np.float32)
    sel_val = (r == kk + k).astype(np.float32)
    with np.errstate(over="ignore", invalid="ignore"):
        upd = (A * (B * w + sel_rhs * rhs) + sel_val * val).astype(np.float32)

    @jax.jit
    def contract(updT, onehot):
        dn = (((1,), (0,)), ((), ()))
        if precision == "highest":
            return jax.lax.dot_general(
                updT, onehot, dimension_numbers=dn,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
        oh16 = onehot.astype(jnp.bfloat16)
        hi = updT.astype(jnp.bfloat16)
        out = jax.lax.dot_general(
            hi, oh16, dimension_numbers=dn, preferred_element_type=jnp.float32
        )
        if precision == "hilo":
            lo = (updT - hi.astype(jnp.float32)).astype(jnp.bfloat16)
            out = out + jax.lax.dot_general(
                lo, oh16, dimension_numbers=dn,
                preferred_element_type=jnp.float32,
            )
        return out

    acc = np.zeros((plan.n_blocks * ap.S, width), np.float32)
    seg = plan.seg3.reshape(plan.n_tiles, ap.T)
    for i in range(plan.n_tiles):
        onehot = (seg[i][:, None] == np.arange(ap.S)).astype(np.float32)
        updT = upd[i * ap.T:(i + 1) * ap.T].T
        block = acc[plan.block_map[i] * ap.S:(plan.block_map[i] + 1) * ap.S]
        for s in range(width // ap.SLAB_W):
            cols = slice(s * ap.SLAB_W, (s + 1) * ap.SLAB_W)
            contrib = np.asarray(contract(updT[cols], onehot)).T
            if plan.first[i]:
                block[:, cols] = contrib
            else:
                block[:, cols] = block[:, cols] + contrib
    return acc


@pytest.mark.parametrize("implicit", [False, True], ids=["explicit", "implicit"])
@pytest.mark.parametrize("precision", ["highest", "hilo", "bf16"])
@pytest.mark.parametrize("rank", [4, 10, 17, 32])
def test_fused_selection_is_exact(rank, precision, implicit):
    """``segment_stats_fused`` IS the accumulator of update rows whose
    factors were picked by indexing, bit for bit: the one bfloat16 MXU pass
    over the table's three parts selects and never rounds, at every rank the
    kernel runs (one slab, three, nine), whatever the main contraction's
    precision, with factors of 1e-30 and 1e30 beside ones, negatives and
    zeros.  (1e30 squared is inf and an inf times the one-hot's zeros is
    NaN: the accumulator's NaNs have to be the reference's NaNs, and few.)"""
    rng = np.random.default_rng(100 * rank + len(precision))
    n, nseg, n_other = 2500, 256, 48
    seg = rng.integers(0, 250, n)
    oth = rng.integers(0, n_other, n).astype(np.int32)
    # segments 250 and 251 see the 1e-30 rows alone: beside factors of order
    # one a sum would swallow them
    seg[:6], oth[:6] = [250, 250, 250, 251, 251, 251], [1, 1, 1, 2, 2, 2]
    rat = rng.uniform(-2, 2, n).astype(np.float32)
    factors = _mixed_magnitude_factors(rng, n_other, rank)
    plan = ap.build_plan(seg.astype(np.int64), nseg)
    nt = plan.n_tiles
    oth_p, rat_p, val_p = _staged(plan, oth, rat)
    wrv = ap.make_wrv(
        jnp.asarray(rat_p.reshape(nt, ap.T)),
        jnp.asarray(val_p.reshape(nt, ap.T)), implicit, 1.5,
    )
    acc = np.asarray(ap.segment_stats_fused(
        (jnp.asarray(plan.block_map), jnp.asarray(plan.first),
         jnp.asarray(plan.seg3)),
        jnp.asarray(oth_p.reshape(nt, ap.T)), wrv, jnp.asarray(factors),
        nt, plan.n_blocks, precision=precision, interpret=True,
    ))
    want = _accumulator_from_written_out_updates(
        plan, oth_p, np.asarray(wrv), factors, precision
    )
    assert np.array_equal(acc, want, equal_nan=True)
    assert np.isnan(want).mean() < 0.02
    # the extremes arrived: a sum of order 1e30 and one of order 1e-30
    finite = np.abs(want[np.isfinite(want)])
    assert finite.max() > 1e29
    assert ((finite > 0) & (finite < 1e-28)).any()


def test_three_bfloat16_parts_are_the_float32():
    """``split3``: ``hi + mid + lo == x`` bit for bit, summed in float32
    from either end, each part a bfloat16, over a million random float32 bit
    patterns that are finite and normal and over the range a factor table
    has (1e-3 to 10, both signs).

    Two ends of the float32 range are outside the identity, here as on the
    chip.  Above bfloat16's largest finite value (3.3895e38) ``hi`` rounds
    to inf.  Below ``2**-103`` (9.9e-32) ``lo``, 23 binary places under
    ``x``, can be a SUBNORMAL number, and XLA flushes those to zero on the
    CPU and on the TPU alike, as it does a subnormal ``x`` itself (whose
    parts are all zero): the sum then misses ``x`` by less than ``2**-126``,
    the smallest normal number, and no more.  ALS factors are eight and more
    decimal orders above that."""
    rng = np.random.default_rng(39)
    bits = rng.integers(0, 1 << 32, 1_100_000, dtype=np.uint64).astype(np.uint32)
    exponent = (bits >> 23) & 0xFF
    x = bits[(exponent >= 1) & (exponent <= 254)].view(np.float32)
    x = x[np.abs(x) <= np.float32(3.3895e38)]
    assert len(x) >= 1_000_000
    table = (
        10.0 ** rng.uniform(-3, 1, 200_000) * rng.choice([-1.0, 1.0], 200_000)
    ).astype(np.float32)
    x = np.concatenate([x, table, np.float32([1.0, -1.0])])

    hi, mid, lo = (np.asarray(part) for part in jax.jit(ap.split3)(x))
    for part in (hi, mid, lo):
        assert part.dtype == np.float32
        as_bf16 = part.astype(ml_dtypes.bfloat16).astype(np.float32)
        assert np.array_equal(as_bf16.view(np.uint32), part.view(np.uint32))

    exact = np.abs(x) >= np.float32(2.0 ** -103)
    for total in ((hi + mid) + lo, hi + (mid + lo)):
        assert np.array_equal(
            total[exact].view(np.uint32), x[exact].view(np.uint32)
        )
        assert np.abs(total[~exact] - x[~exact]).max() < 2.0 ** -126
    assert (~exact).sum() > 50_000  # the flushed end was looked at

    # a zero's parts are zeros (the sum of a -0.0's is +0.0)
    assert not np.asarray(jax.jit(ap.split3)(np.float32([0.0, -0.0]))).any()

    # the rows the gather reads: the parts side by side as bfloat16, zeros
    # up to the lane tile
    k = 10
    rows = np.asarray(ap.split_table(jnp.asarray(table[:5000].reshape(-1, k))))
    assert rows.dtype == ml_dtypes.bfloat16 and rows.shape == (500, ap.PARTS_W)
    parts = rows.astype(np.float32)
    assert not parts[:, 3 * k:].any()
    total = (parts[:, :k] + parts[:, k:2 * k]) + parts[:, 2 * k:3 * k]
    assert np.array_equal(total, table[:5000].reshape(-1, k))


@pytest.mark.parametrize("rank", [4, 10, 17, 32])
def test_selectors_pick_each_component_from_all_three_parts(rank):
    """A slab's selector block is 0/1 with, in update row ``r``, a one over
    component ``r // k`` (``r - k*k`` in the rhs block) of EACH of the three
    parts in its upper half and over ``r % k`` in its lower half, and
    nothing over the zero lanes or past the count row."""
    sel = ap.selectors(rank)
    kk, width = rank * rank, ap.row_width(rank)
    assert sel.shape == (2 * width, ap.PARTS_W)
    assert set(np.unique(sel)) == {0.0, 1.0}
    sel = sel.reshape(width // ap.SLAB_W, 2, ap.SLAB_W, ap.PARTS_W)
    pa = sel[:, 0].reshape(width, ap.PARTS_W)
    pb = sel[:, 1].reshape(width, ap.PARTS_W)
    v = np.arange(1, rank + 1, dtype=np.float32)
    row = np.concatenate(
        [v, 100 * v, 10_000 * v, np.full(ap.PARTS_W - 3 * rank, 7.0)]
    )
    r = np.arange(width)
    want_a = np.where(
        r < kk, v[np.minimum(r // rank, rank - 1)],
        np.where(r < kk + rank, v[(r - kk) % rank], 0),
    )
    want_b = np.where(r < kk, v[r % rank], 0)
    assert np.array_equal(pa @ row, 10_101 * want_a)
    assert np.array_equal(pb @ row, 10_101 * want_b)
