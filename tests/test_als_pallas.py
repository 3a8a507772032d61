"""Pallas segment accumulator: logic (interpret mode) + plan construction.

The TPU kernel itself runs only on real hardware; these tests validate the
host-side plan and the kernel semantics through the pallas interpreter so
the scatter-free path is covered on every platform.
"""

import numpy as np
import jax.numpy as jnp
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
    rows = plan.padded_len
    oth_p = oth[plan.dest_perm].copy()
    rat_p = rat[plan.dest_perm].copy()
    val_p = np.ones(rows, np.float32)
    oth_p[plan.pad_mask] = 0
    rat_p[plan.pad_mask] = 0
    val_p[plan.pad_mask] = 0

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
    oth_p = oth[plan.dest_perm].copy()
    rat_p = rat[plan.dest_perm].copy()
    val_p = np.ones(plan.padded_len, np.float32)
    oth_p[plan.pad_mask] = 0
    rat_p[plan.pad_mask] = 0
    val_p[plan.pad_mask] = 0
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
