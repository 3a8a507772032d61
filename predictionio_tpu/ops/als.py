"""Alternating Least Squares as an XLA program over a device mesh.

The TPU-native replacement for Spark MLlib's ALS used by the reference's
recommendation templates (examples/scala-parallel-recommendation/.../
ALSAlgorithm.scala:52 explicit; examples/scala-parallel-similarproduct/...
ALS.trainImplicit implicit).  Where MLlib block-partitions factor matrices
across executors and shuffles ratings, this implementation:

  - keeps ratings as padded COO arrays sharded along the mesh ``data`` axis;
  - computes per-entity normal equations with a chunked scatter-add
    (``lax.scan`` over fixed-size chunks -> static shapes, no giant
    [nnz, k, k] intermediate);
  - ``psum``s the partial statistics over the mesh (XLA collective over ICI,
    the shuffle replacement);
  - solves the batched k x k systems with each device owning a slice of the
    entities, then ``all_gather``s the updated factors.

Explicit feedback solves  (Vu^T Vu + reg * I) x = Vu^T r_u  with MLlib's
ALS-WR option of scaling reg by the per-entity rating count.  Implicit
feedback (Hu-Koren) solves  (V^T V + Vu^T diag(alpha r) Vu + reg I) x =
Vu^T (1 + alpha r) 1.
"""

from __future__ import annotations

import dataclasses
import logging
import math
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PSpec

from predictionio_tpu.obs.tracing import trace
from predictionio_tpu.parallel.mesh import pad_to_multiple

log = logging.getLogger("predictionio_tpu.ops.als")


@dataclass(frozen=True)
class ALSParams:
    """Hyperparameters; defaults mirror the reference template's engine.json
    (rank=10, numIterations=20, lambda=0.01, seed=3)."""

    rank: int = 10
    num_iterations: int = 20
    reg: float = 0.01
    implicit_prefs: bool = False
    alpha: float = 1.0  # implicit confidence scale
    scale_reg_with_count: bool = True  # MLlib ALS-WR lambda * n_u scaling
    seed: int = 3
    #: COO entries per scan step; measured on v5e: 1<<19 runs the ML-20M
    #: half-step in 227 ms vs 1953 ms at 1<<16 (fewer scan trips over the
    #: accumulator); clamped down automatically for small datasets
    chunk_size: int = 1 << 19
    #: pallas accumulator MXU precision (see als_pallas._make_kernel):
    #: "hilo" (2-pass, ~2^-16 rel err — default), "highest" (6-pass exact),
    #: "bf16" (1-pass, ~2^-8)
    pallas_precision: str = "hilo"


@dataclass
class ALSState:
    """Trained factors (host numpy after persistence; device arrays live)."""

    user_factors: Any  # [num_users, rank]
    item_factors: Any  # [num_items, rank]


def _pvary(x, axis):
    """Mark a freshly-created array as varying over a shard_map axis.

    Inside shard_map, zeros created in the body are 'unvarying' while scan
    outputs fed by sharded operands are 'varying'; the carry types must match
    (vma checking)."""
    if axis is None:
        return x
    return jax.lax.pcast(x, (axis,), to="varying")


def _segment_stats(
    seg_idx, other_idx, other_factors, weights, rhs, valid,
    num_segments, chunk_size, axis=None,
):
    """Accumulate flat rows [vec(w * v v^T) | rhs * v | valid] per segment.

    Chunked scatter-add: reshapes the (padded) COO stream into
    [n_chunks, chunk_size] and scans, gathering v = other_factors[other_idx]
    per chunk so no [nnz, k] intermediate is materialized.

    One flat [chunk, k*k+k+1] scatter instead of separate [chunk, k, k] /
    [chunk, k] / [chunk] ones: a ~128-lane minor dimension keeps the TPU
    scatter on full vector tiles.  Measured on v5e at ML-20M scale (Zipf
    item skew), the item half-step drops 2669 ms -> 578 ms vs the
    [chunk, k, k] layout.  Skew now *helps* rather than hurts: the lowering
    combines duplicate indices within a chunk, so hot segments cost one
    HBM read-modify-write; the worst case is unique-index uniform data.
    """
    n = seg_idx.shape[0]
    k = other_factors.shape[1]
    n_chunks = n // chunk_size
    acc0 = _pvary(
        jnp.zeros((num_segments, k * k + k + 1), other_factors.dtype), axis
    )

    def body(acc, chunk):
        ci, coi, cw, cr, cval = chunk
        cv = other_factors[coi]
        flat = jnp.concatenate(
            [
                (cv[:, :, None] * cv[:, None, :]).reshape(chunk_size, k * k)
                * cw[:, None],
                cv * cr[:, None],
                cval[:, None],
            ],
            axis=1,
        )
        return acc.at[ci].add(flat, mode="drop"), None

    chunks = (
        seg_idx.reshape(n_chunks, chunk_size),
        other_idx.reshape(n_chunks, chunk_size),
        weights.reshape(n_chunks, chunk_size),
        rhs.reshape(n_chunks, chunk_size),
        valid.reshape(n_chunks, chunk_size),
    )
    acc, _ = jax.lax.scan(body, acc0, chunks)
    return acc


def confidence_weights(rating, valid, implicit_prefs: bool, alpha: float, dtype):
    """(A-weight, rhs) per COO row — the ONE home of the MLlib semantics.

    Explicit: plain least squares (weight = valid, rhs = r).  Implicit
    (Hu-Koren / trainImplicit): confidence from |r|, preference = 1 iff
    r > 0 — negative ratings are high-confidence negatives (the
    similarproduct LikeAlgorithm dislike path).  Shared by the scatter
    (_half_step) and pallas (als_pallas.segment_stats_pallas) paths so the
    two backends cannot drift."""
    if implicit_prefs:
        conf_minus_1 = alpha * jnp.abs(rating) * valid
        pref = (rating > 0).astype(dtype)
        return conf_minus_1, (1.0 + conf_minus_1) * pref * valid  # c * p
    return valid, rating * valid


#: rank cutoff for the unrolled structure-of-arrays solve: the unroll
#: emits ~k^3/6 scalar HLO ops, and past ~16 that graph (x2 half-steps,
#: inside the training loop body) pushes XLA compile time from seconds
#: into tens of minutes.  Wider ranks use the batched lax.linalg kernels:
#: slower per step (the docstring below) but a constant-size program.
_SOA_MAX_RANK = 16


def _solve_factors(A, b, counts, reg, scale_reg, gram=None):
    """Solve (A + reg' I [+ gram]) x = b batched over the leading axis.

    Structure-of-arrays Cholesky: the systems are transposed to [k, k, n]
    so every scalar step of the factorization/solve is an elementwise op
    over ALL n entities in the vector lanes.  Batched k x k lax.linalg
    kernels pad each tiny matrix to full vector tiles and serialize the
    triangular solves — measured 230-260 ms for n=138k, k=10 on v5e, vs
    ~74 MFLOPs of real work; the SoA form runs in a few ms.  The unrolled
    loops are over the STATIC rank (gated at ``_SOA_MAX_RANK`` — the
    unroll is quadratic-to-cubic in PROGRAM SIZE, which is compile time),
    so the program stays a flat fused elementwise graph.  No pivoting:
    the operands are SPD + ridge.
    """
    k = b.shape[-1]
    reg_eff = reg * jnp.maximum(counts, 1.0) if scale_reg else jnp.full_like(counts, reg)
    lhs = A + reg_eff[:, None, None] * jnp.eye(k, dtype=A.dtype)
    if gram is not None:
        lhs = lhs + gram
    if k > _SOA_MAX_RANK:
        L = jnp.linalg.cholesky(lhs)
        y = jax.lax.linalg.triangular_solve(
            L, b[..., None], left_side=True, lower=True
        )
        x = jax.lax.linalg.triangular_solve(
            L, y, left_side=True, lower=True, transpose_a=True
        )
        return x[..., 0]
    At = jnp.transpose(lhs, (1, 2, 0))  # [k, k, n]
    bT = jnp.transpose(b, (1, 0))       # [k, n]
    L = [[None] * k for _ in range(k)]
    for j in range(k):
        s = At[j, j]
        for p in range(j):
            s = s - L[j][p] * L[j][p]
        L[j][j] = jnp.sqrt(s)
        for i2 in range(j + 1, k):
            s = At[i2, j]
            for p in range(j):
                s = s - L[i2][p] * L[j][p]
            L[i2][j] = s / L[j][j]
    y: list = [None] * k
    for i2 in range(k):
        s = bT[i2]
        for p in range(i2):
            s = s - L[i2][p] * y[p]
        y[i2] = s / L[i2][i2]
    x: list = [None] * k
    for i2 in reversed(range(k)):
        s = y[i2]
        for p in range(i2 + 1, k):
            s = s - L[p][i2] * x[p]
        x[i2] = s / L[i2][i2]
    return jnp.stack(x, axis=-1)  # [n, k]


def _half_step(
    seg_idx,  # [nnz_local] entity being solved (sharded over 'data')
    other_idx,  # [nnz_local] opposite entity
    rating,  # [nnz_local]
    valid,  # [nnz_local] 1.0 real / 0.0 padding
    other_factors,  # [num_other_pad, k] replicated
    num_seg_pad: int,
    p: ALSParams,
    axis: str | None,
    gather_output: bool = True,
):
    """One alternating update: recompute factors for ``seg`` entities.

    ``gather_output=False`` returns each device's OWN solved slice instead
    of all-gathering to a replicated table — the sharded-state training
    layout, where factors persist 1/n_dev per device between iterations and
    only the transient all-gather inside the NEXT half-step materializes a
    full table."""
    with jax.named_scope("als.weights"):
        a_weight, rhs = confidence_weights(
            rating, valid, p.implicit_prefs, p.alpha, other_factors.dtype
        )
    k = other_factors.shape[1]
    with jax.named_scope("als.accumulate"):
        acc = _segment_stats(
            seg_idx, other_idx, other_factors, a_weight, rhs, valid,
            num_seg_pad, p.chunk_size, axis,
        )
        if axis:
            # one psum over the flat stats (A | b | counts packed together)
            acc = jax.lax.psum(acc, axis)
            n_dev = jax.lax.axis_size(axis)
            slice_size = num_seg_pad // n_dev
            start = jax.lax.axis_index(axis) * slice_size
            acc = jax.lax.dynamic_slice_in_dim(acc, start, slice_size)
    with jax.named_scope("als.solve"):
        # other_factors is replicated, so the Gram needs no collective.
        gram = other_factors.T @ other_factors if p.implicit_prefs else None
        A = acc[:, : k * k].reshape(-1, k, k)
        b = acc[:, k * k : k * k + k]
        counts = acc[:, -1]
        x = _solve_factors(A, b, counts, p.reg, p.scale_reg_with_count, gram)
    if axis and gather_output:
        return jax.lax.all_gather(x, axis, axis=0, tiled=True)
    return x


#: compiled-step cache: repeated train_als calls with the same mesh/shapes/
#: program params (a warm-up then a timed run; retrain-on-deploy) must not
#: pay a second trace+compile — num_iterations and seed don't enter the
#: compiled program, so they are excluded from the key.  Bounded (FIFO) so a
#: long-lived retraining server on growing data can't pin dead executables.
_STEP_CACHE: dict = {}
_STEP_CACHE_MAX = 8


def _use_pallas(p: "ALSParams") -> bool:
    """Single-device TPU runs route the normal-equation accumulation through
    the scatter-free pallas MXU kernel (ops/als_pallas.py) when the flat row
    fits its 128-lane width."""
    if p.rank > 32:  # row_width(32) = 1152 lanes; wider is untested
        return False
    # a backend that fails to initialise raises here: an error, not a
    # reason to pick the scatter path
    return jax.default_backend() == "tpu"


def _make_pallas_step(
    key_shapes, p: ALSParams, num_users_pad, num_items_pad, fused: bool,
    single_step: bool = False,
):
    """Jitted one-iteration fn over pre-planned (sorted+padded) streams.

    ``single_step`` compiles a straight-line one-iteration program (no
    fori_loop): last rung of the OOM ladder, because the while-loop's
    loop-carried remat copies are what the padded-layout blowup bites."""
    key = ("pallas", key_shapes, num_users_pad, num_items_pad, p.rank, p.reg,
           p.implicit_prefs, p.alpha, p.scale_reg_with_count,
           p.pallas_precision, fused, single_step)
    cached = _STEP_CACHE.get(key)
    if cached is not None:
        return cached
    while len(_STEP_CACHE) >= _STEP_CACHE_MAX:
        del _STEP_CACHE[next(iter(_STEP_CACHE))]
    from predictionio_tpu.ops import als_pallas

    (tpcu, nbu, tpci, nbi) = key_shapes
    k = p.rank

    def solve(acc, other_factors):
        A = acc[:, : k * k].reshape(-1, k, k)
        b = acc[:, k * k : k * k + k]
        counts = acc[:, k * k + k]
        gram = (
            other_factors.T @ other_factors if p.implicit_prefs else None
        )
        return _solve_factors(A, b, counts, p.reg, p.scale_reg_with_count, gram)

    # named scopes: every device operation of the step carries the name of
    # its part in its metadata (``als.user_half/als.accumulate/...``), so a
    # reduction of the device trace finds the parts after any refactor
    def half(side, plan_args, oth, wrv_or_rat, val, other_factors, tpc,
             n_blocks, num_seg_pad):
        with jax.named_scope(f"als.{side}_half"):
            with jax.named_scope("als.accumulate"):
                if fused:
                    # wrv_or_rat is the precomputed [nt, 3, T] weight
                    # stack; val is unused (folded into wrv once per
                    # dispatch)
                    acc = als_pallas.segment_stats_fused(
                        plan_args, oth, wrv_or_rat, other_factors, tpc,
                        n_blocks, precision=p.pallas_precision,
                    )[:num_seg_pad]
                else:
                    acc = als_pallas.segment_stats_pallas(
                        plan_args, oth, wrv_or_rat, val, other_factors,
                        p.implicit_prefs, p.alpha, tpc, n_blocks,
                        precision=p.pallas_precision,
                    )[:num_seg_pad]
            with jax.named_scope("als.solve"):
                return solve(acc, other_factors)

    def prep(rat, val):
        """Per-dispatch (NOT per-iteration) weight precompute for the
        fused path; the chunked kernel recomputes weights per chunk
        in-body instead."""
        if not fused:
            return rat
        with jax.named_scope("als.weights"):
            return als_pallas.make_wrv(rat, val, p.implicit_prefs, p.alpha)

    if single_step:

        @jax.jit
        def steps(u_plan, u_oth, u_rat, u_val,
                  i_plan, i_oth, i_rat, i_val, U, V, n_iters):
            del n_iters  # one iteration per dispatch, caller loops
            u_w, i_w = prep(u_rat, u_val), prep(i_rat, i_val)
            U = half("user", u_plan, u_oth, u_w, u_val, V, tpcu, nbu,
                     num_users_pad)
            V = half("item", i_plan, i_oth, i_w, i_val, U, tpci, nbi,
                     num_items_pad)
            return U, V

    else:

        @jax.jit
        def steps(u_plan, u_oth, u_rat, u_val,
                  i_plan, i_oth, i_rat, i_val, U, V, n_iters):
            """ALL iterations inside one compiled program (lax.fori_loop
            with a dynamic trip count, so num_iterations stays out of the
            compile key).  One host dispatch per train instead of one per
            iteration."""
            u_w, i_w = prep(u_rat, u_val), prep(i_rat, i_val)

            def body(_, uv):
                U, V = uv
                U = half("user", u_plan, u_oth, u_w, u_val, V, tpcu, nbu,
                         num_users_pad)
                V = half("item", i_plan, i_oth, i_w, i_val, U, tpci, nbi,
                         num_items_pad)
                return U, V

            return jax.lax.fori_loop(0, n_iters, body, (U, V))

    _STEP_CACHE[key] = steps
    return steps


#: diagnostics from the most recent _train_pallas staging (the benchmark's
#: plan_info reader): padded row counts and block counts per scatter direction
LAST_PLAN_INFO: dict = {}

#: single-entry staging cache: the host sort/permute + device upload of the
#: COO streams depends only on the DATA, not on hyperparameters or the
#: iteration count — retraining on the same ratings (the deploy-retrain
#: path, hyperparameter sweeps) reuses the staged device arrays, the way
#: Spark caches a partitioned RDD across ALS iterations.
#: Keyed by a full content hash (sha1 of the raw arrays, ~1 s at 20M rows vs
#: ~13 s restaging); bounded to ONE dataset so stale streams don't pin HBM.
_STAGE_CACHE: dict = {}


def _data_fingerprint(*arrays) -> str:
    import hashlib

    h = hashlib.sha1()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _is_oom_error(e: Exception) -> bool:
    """Resource exhaustion as surfaced by jax across paths: direct
    RESOURCE_EXHAUSTED XlaRuntimeErrors and stringified 'Ran out of memory
    in memory space hbm'."""
    s = str(e)
    return (
        "RESOURCE_EXHAUSTED" in s
        or "Ran out of memory" in s
        or "out of memory" in s.lower()
    )


def _first_rung(nnz: int) -> str:
    """The ladder's first rung, from an estimate of the fused path's HBM.

    The fused single-grid kernel reads the gather's own rows
    ([nt, T, PARTS_W] bfloat16: 256 bytes a row at every rank it runs) per
    half-step; any rank runs fused (wide ranks add width slabs, not VMEM),
    so the only reason to start on the chunk-scan is the gather transient
    crowding HBM."""
    from predictionio_tpu.ops import als_pallas

    est_rows = int(nnz * 1.06) + als_pallas.T  # ~pad factor
    # Fused-path HBM budget: the gathered rows are the big per-half-step
    # transient (one side's at a time: XLA's plan for the ML-20M program,
    # tests/test_tpu_aot.py), and both sides' wrv [nt, 3->4, T] stacks
    # live for the whole train.
    fused_bytes = est_rows * (2 * als_pallas.PARTS_W + 2 * 4 * 4)
    # budget ~half of a v5e's 16G HBM for these (leaves room for the
    # staged streams and the accumulator); the OOM ladder catches an
    # underestimate by falling back to chunked
    return "fused" if fused_bytes <= 8 << 30 else "chunked"


def _train_pallas(user_idx, item_idx, rating, num_users, num_items,
                  p: ALSParams, dtype) -> "ALSState":
    """Single-device TPU train via the scatter-free pallas accumulator.

    Degrades instead of dying on HBM exhaustion: the dispatch ladder is
    ``fused -> chunked -> chunked per-iteration`` (each step cuts peak HBM
    — the chunk scan drops the whole-stream packed transients; per-
    iteration dispatch drops the fori_loop's loop-carried remat copies):
    one OOM must cost a retry, not the train."""
    mode = _first_rung(len(user_idx))
    ladder = [(mode, False)]
    if mode == "fused":
        ladder.append(("chunked", False))
    ladder.append(("chunked", True))
    for i, (m, per_iter) in enumerate(ladder):
        try:
            return _train_pallas_mode(
                user_idx, item_idx, rating, num_users, num_items, p, dtype,
                m, per_iter
            )
        except Exception as e:  # noqa: BLE001 — filtered to OOM below
            if not _is_oom_error(e) or i == len(ladder) - 1:
                raise
            import warnings

            nxt = ladder[i + 1]
            warnings.warn(
                f"ALS pallas {m}{' per-iter' if per_iter else ''} path ran "
                f"out of HBM ({type(e).__name__}); retrying as "
                f"{nxt[0]}{' per-iter' if nxt[1] else ''}",
                RuntimeWarning,
                stacklevel=2,
            )
            _STAGE_CACHE.clear()  # drop this mode's device streams first
    raise AssertionError("unreachable")


def _train_pallas_mode(user_idx, item_idx, rating, num_users, num_items,
                       p: ALSParams, dtype, mode: str,
                       per_iter: bool) -> "ALSState":
    from predictionio_tpu.ops import als_pallas

    num_users_pad = max((num_users + 127) // 128 * 128, 128)
    num_items_pad = max((num_items + 127) // 128 * 128, 128)

    def stage(side, seg, oth, num_seg_pad, num_oth_pad, parent):
        """One scatter direction, on a pool thread: plan (radix order +
        block layout), permute, upload — each a child span of ``parent``
        (``als.stage``)."""
        with trace("als.stage.plan", parent=parent) as span:
            span.tags = {
                "side": side,
                "rows": len(seg),
                "sort_passes": als_pallas.radix_passes(num_seg_pad),
            }
            plan = als_pallas.build_plan(np.asarray(seg), num_seg_pad)
            span.tags["padded_rows"] = plan.padded_len
            if mode == "fused":
                # [nt, T], minor dim 1024: layout-clean on device (no
                # T(8,128) minor-dim padding possible)
                shape2 = (plan.n_tiles, als_pallas.T)
            else:
                plan = als_pallas.chunk_plan(plan)
                shape2 = (plan.n_chunks, plan.tiles_per_chunk * als_pallas.T)
        with trace("als.stage.permute", parent=parent) as span:
            span.tags = {"side": side}
            perm, pad_mask = plan.dest_perm, plan.pad_mask
            oth_p = np.asarray(oth, np.int32)[perm]
            rat_p = np.asarray(rating, np.float32)[perm]
            oth_p[pad_mask] = 0
            rat_p[pad_mask] = 0.0
        with trace("als.stage.upload", parent=parent) as span:
            span.tags = {"side": side, "upload_bytes": 0}

            def upload(host: np.ndarray):
                span.tags["upload_bytes"] += host.nbytes
                return jnp.asarray(host)

            # Transfer-lean uploads: ship the narrowest encoding and widen
            # on device.  seg3 ids are < S=128 -> int8 (4x); the
            # opposite-entity index fits uint16 below 64Ki rows (2x);
            # validity is DERIVED from seg3 (padding rows carry -1), so it
            # costs zero transfer.
            seg3_dev = upload(plan.seg3.astype(np.int8)).astype(jnp.int32)
            if num_oth_pad <= 0xFFFF:
                oth_dev = upload(
                    oth_p.astype(np.uint16).reshape(shape2)
                ).astype(jnp.int32)
            else:
                oth_dev = upload(oth_p.reshape(shape2))
            val_dev = (
                (seg3_dev.reshape(shape2) >= 0).astype(jnp.float32)
            )
            dev_plan_args = (
                upload(plan.block_map), upload(plan.first), seg3_dev,
            )
            if mode != "fused":
                dev_plan_args += (upload(plan.visited),)
            rat_dev = upload(rat_p.reshape(shape2))
        return plan, dev_plan_args, oth_dev, rat_dev, val_dev

    with trace("als.fingerprint") as span:
        span.tags = {
            "bytes": sum(
                np.asarray(a).nbytes for a in (user_idx, item_idx, rating)
            )
        }
        fingerprint = _data_fingerprint(user_idx, item_idx, rating)
    cache_key = (fingerprint, num_users_pad, num_items_pad, mode)
    staged = _STAGE_CACHE.get(cache_key)
    if staged is None:
        # evict BEFORE staging: holding the old dataset's device streams
        # while uploading the new ones would transiently double HBM use
        _STAGE_CACHE.clear()
        # the two scatter directions stage concurrently: the work is
        # numpy sorts (a radix sort on 16-bit digits: als_pallas._stable_order)
        # + copies + permutes (GIL-released), so two threads nearly halve
        # the cold-train host staging wall time
        from concurrent.futures import ThreadPoolExecutor

        with trace("als.stage") as stage_span:
            with ThreadPoolExecutor(2) as pool:
                fu = pool.submit(stage, "user", user_idx, item_idx,
                                 num_users_pad, num_items_pad, stage_span)
                fi = pool.submit(stage, "item", item_idx, user_idx,
                                 num_items_pad, num_users_pad, stage_span)
                staged = (fu.result(), fi.result())
            stage_span.tags = {
                "upload_bytes": sum(
                    c.tags["upload_bytes"]
                    for c in stage_span.children
                    if c.name == "als.stage.upload"
                )
            }
        LAST_PLAN_INFO["stage_s"] = round(stage_span.duration_s, 2)
        LAST_PLAN_INFO["upload_bytes"] = stage_span.tags["upload_bytes"]
        _STAGE_CACHE[cache_key] = staged
    else:
        LAST_PLAN_INFO["upload_bytes"] = 0  # staged streams reused
    (up, u_plan, u_oth, u_rat, u_val), (ip, i_plan, i_oth, i_rat, i_val) = (
        staged
    )
    fused = mode == "fused"
    if fused:
        tiles_u, tiles_i = up.n_tiles, ip.n_tiles
        rows_u, rows_i = up.padded_len, ip.padded_len
        chunks_u = chunks_i = 1
    else:
        tiles_u, tiles_i = up.tiles_per_chunk, ip.tiles_per_chunk
        rows_u = up.n_chunks * up.tiles_per_chunk * als_pallas.T
        rows_i = ip.n_chunks * ip.tiles_per_chunk * als_pallas.T
        chunks_u, chunks_i = up.n_chunks, ip.n_chunks
    LAST_PLAN_INFO.update(
        rank=p.rank,
        width=als_pallas.row_width(p.rank),
        rows_user=rows_u,
        rows_item=rows_i,
        blocks_user=up.n_blocks,
        blocks_item=ip.n_blocks,
        chunks_user=chunks_u,
        chunks_item=chunks_i,
        precision=p.pallas_precision,
        mode=mode,
        per_iter=per_iter,
    )

    with trace("als.init"):
        U, V = _init_factors(p, num_users_pad, num_items_pad, num_users,
                             num_items, dtype)
    steps = _make_pallas_step(
        (tiles_u, up.n_blocks, tiles_i, ip.n_blocks),
        p, num_users_pad, num_items_pad, fused, single_step=per_iter,
    )
    with trace("als.device_loop") as loop_span:
        # the trip count each dispatch carries, times the dispatches
        loop_span.tags = {
            "iterations": p.num_iterations, "mode": mode,
            "per_iter": per_iter,
        }
        if per_iter:
            for _ in range(p.num_iterations):
                U, V = steps(u_plan, u_oth, u_rat, u_val,
                             i_plan, i_oth, i_rat, i_val, U, V, jnp.int32(1))
        else:
            U, V = steps(u_plan, u_oth, u_rat, u_val,
                         i_plan, i_oth, i_rat, i_val, U, V,
                         jnp.int32(p.num_iterations))
        jax.block_until_ready((U, V))
    wall_s = loop_span.duration_s
    LAST_PLAN_INFO.update(
        iterations=p.num_iterations, loop_s=round(wall_s, 4)
    )
    _log_train_path("als.pallas_step", wall_s, mode=mode, per_iter=per_iter)
    return ALSState(user_factors=U[:num_users], item_factors=V[:num_items])


def _log_train_path(path: str, wall_s: float, **detail) -> None:
    """Name the train path that ran, under the efficiency tracker's entry
    name (``als.pallas_step`` / ``als.train_step``)."""
    fields = {"als_path": path, "wall_s": round(wall_s, 3), **detail}
    log.info("ALS train ran %s %s", path, detail, extra=fields)


def _make_train_step(
    mesh: Mesh | None, num_users_pad, num_items_pad, p: ALSParams,
    shard_state: bool = False,
):
    """Build (or fetch) the jitted one-iteration function.

    ``shard_state=True`` (the single-controller mesh path) keeps the factor
    tables row-sharded over the ``data`` axis BETWEEN iterations — per-device
    persistent factor HBM drops 1/n_dev as devices grow, and only a
    transient all-gather inside each half-step materializes the full
    opposite table for the COO gathers.  The solved slices, psums, and
    per-device solves are identical either way, so the numerics match the
    replicated layout bit-for-bit."""
    key = (
        mesh,  # jax.sharding.Mesh is hashable (None for single device)
        num_users_pad, num_items_pad,
        p.rank, p.reg, p.implicit_prefs, p.alpha,
        p.scale_reg_with_count, p.chunk_size, shard_state,
    )
    cached = _STEP_CACHE.get(key)
    if cached is not None:
        return cached
    while len(_STEP_CACHE) >= _STEP_CACHE_MAX:
        del _STEP_CACHE[next(iter(_STEP_CACHE))]

    def step(u_idx, i_idx, rating, valid, U, V):
        axis = "data" if mesh is not None else None
        if shard_state and axis:
            # factors arrive as this device's row slice: gather the full
            # opposite table transiently, return only the solved slice
            with jax.named_scope("als.user_half"):
                Vf = jax.lax.all_gather(V, axis, axis=0, tiled=True)
                U = _half_step(u_idx, i_idx, rating, valid, Vf,
                               num_users_pad, p, axis, gather_output=False)
            with jax.named_scope("als.item_half"):
                Uf = jax.lax.all_gather(U, axis, axis=0, tiled=True)
                V = _half_step(i_idx, u_idx, rating, valid, Uf,
                               num_items_pad, p, axis, gather_output=False)
            return U, V
        with jax.named_scope("als.user_half"):
            U = _half_step(
                u_idx, i_idx, rating, valid, V, num_users_pad, p, axis
            )
        with jax.named_scope("als.item_half"):
            V = _half_step(
                i_idx, u_idx, rating, valid, U, num_items_pad, p, axis
            )
        return U, V

    if mesh is None:
        fn = jax.jit(step)
    else:
        coo_spec = PSpec("data")
        repl = PSpec(None, None)
        factor_spec = PSpec("data", None) if shard_state else repl
        # check_vma=False: replicated outputs are all_gather'ed values the
        # static vma analysis cannot prove (sharded outputs are fine
        # either way).
        fn = jax.jit(
            jax.shard_map(
                step,
                mesh=mesh,
                in_specs=(coo_spec, coo_spec, coo_spec, coo_spec,
                          factor_spec, factor_spec),
                out_specs=(factor_spec, factor_spec),
                check_vma=False,
            )
        )
    _STEP_CACHE[key] = fn
    return fn


def _init_factors(p: ALSParams, num_users_pad, num_items_pad, num_users, num_items, dtype):
    """MLlib-style nonnegative init (abs of gaussians, scaled): keeps initial
    scores O(1) and positive, which conditions ALS well on rating data.
    Padded rows are zeroed so the implicit-feedback Gram (Y^T Y) sees only
    real entities.  Seed-deterministic AND mesh-independent: the gaussians
    are drawn for the REAL entity counts and zero-padded to the mesh lane,
    so a single-device run and an 8-device mesh start from identical
    factors (mesh-vs-single parity) and every process of a multi-host run
    computes identical replicas."""
    key = jax.random.PRNGKey(p.seed)
    ku, kv = jax.random.split(key)
    U0 = jnp.abs(jax.random.normal(ku, (num_users, p.rank), dtype)) / math.sqrt(p.rank)
    V0 = jnp.abs(jax.random.normal(kv, (num_items, p.rank), dtype)) / math.sqrt(p.rank)
    U0 = jnp.pad(U0, ((0, num_users_pad - num_users), (0, 0)))
    V0 = jnp.pad(V0, ((0, num_items_pad - num_items), (0, 0)))
    return U0, V0


def train_als_global(
    user_idx,
    item_idx,
    rating,
    valid,
    num_users: int,
    num_items: int,
    mesh: Mesh,
    params: ALSParams | None = None,
    dtype=jnp.float32,
) -> ALSState:
    """Multi-process SPMD entry point (the multi-host data plane).

    The COO inputs are *global* jax.Arrays sharded along the mesh ``data``
    axis — each process contributed only the rows it read from its own event
    shards (``parallel.mesh.balance_local_chunks`` + ``global_data_array``)
    plus a ``valid`` mask zeroing its padding.  Every process calls this
    with identical arguments (single-controller-per-process SPMD, the
    WorkflowContext.scala:28 role); factors are returned as host numpy from
    the local replica.
    """
    p = params or ALSParams()
    n_dev = mesh.devices.size
    if user_idx.shape[0] % (n_dev * p.chunk_size) != 0:
        raise ValueError(
            f"global COO length {user_idx.shape[0]} must be a multiple of "
            f"n_devices * chunk_size = {n_dev} * {p.chunk_size}"
        )
    lane = 8 * n_dev
    num_users_pad = max(math.ceil(num_users / lane) * lane, lane)
    num_items_pad = max(math.ceil(num_items / lane) * lane, lane)
    from predictionio_tpu.parallel.mesh import global_replicated_array

    U0, V0 = _init_factors(p, num_users_pad, num_items_pad, num_users, num_items, dtype)
    U = global_replicated_array(mesh, np.asarray(U0))
    V = global_replicated_array(mesh, np.asarray(V0))
    step = _make_train_step(mesh, num_users_pad, num_items_pad, p)
    for _ in range(p.num_iterations):
        U, V = step(user_idx, item_idx, rating, valid, U, V)
    jax.block_until_ready((U, V))
    Uh = np.asarray(U.addressable_data(0))[:num_users]
    Vh = np.asarray(V.addressable_data(0))[:num_items]
    return ALSState(user_factors=Uh, item_factors=Vh)


def train_als(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    rating: np.ndarray,
    num_users: int,
    num_items: int,
    params: ALSParams | None = None,
    mesh: Mesh | None = None,
    dtype=jnp.float32,
    init_factors: tuple[np.ndarray, np.ndarray] | None = None,
) -> ALSState:
    """Train ALS factors from COO ratings.

    Entity counts are padded so each mesh device owns an equal factor slice;
    the COO stream is padded to a chunk multiple with valid=0 entries.
    Returns device arrays (callers device_get for persistence).

    ``init_factors`` warm-starts the solve: ``(U0, V0)`` host arrays of
    shape ``[num_users, rank]`` / ``[num_items, rank]`` (callers align rows
    to THEIR vocab order — lifecycle retrains map the previous generation's
    factors through the old→new vocab) replace the random init, so an
    incremental retrain converges in a fraction of the cold iteration
    count.
    """
    p = params or ALSParams()
    # the pallas accumulator is f32-only; other dtypes keep the scatter path
    if (
        mesh is None and dtype == jnp.float32 and _use_pallas(p)
        and init_factors is None
    ):
        return _train_pallas(
            user_idx, item_idx, rating, num_users, num_items, p, dtype
        )
    n_dev = mesh.devices.size if mesh is not None else 1
    lane = 8 * n_dev  # keep slices sublane-aligned and evenly divisible
    num_users_pad = max(math.ceil(num_users / lane) * lane, lane)
    num_items_pad = max(math.ceil(num_items / lane) * lane, lane)

    # host staging of the COO streams, under the span names of the pallas
    # path (one side here: both half-steps read the same padded stream)
    with trace("als.stage") as stage_span:
        with trace("als.stage.plan"):
            # clamp the chunk so small datasets aren't padded to a huge
            # multiple (one scan step is enough when nnz/device fits a
            # single chunk)
            per_dev = max((len(user_idx) + n_dev - 1) // n_dev, 1)
            if per_dev < p.chunk_size:
                p = dataclasses.replace(
                    p,
                    chunk_size=max(
                        1 << max(per_dev - 1, 1).bit_length(), 256
                    ),
                )
            chunk_total = p.chunk_size * n_dev
        with trace("als.stage.permute"):
            u, n_real = pad_to_multiple(
                np.asarray(user_idx, np.int32), chunk_total
            )
            i, _ = pad_to_multiple(np.asarray(item_idx, np.int32), chunk_total)
            r, _ = pad_to_multiple(np.asarray(rating, np.float32), chunk_total)
            valid = np.zeros(len(u), np.float32)
            valid[:n_real] = 1.0
            # padding rows scatter into a real segment with weight 0 —
            # harmless
            u[n_real:] = 0
            i[n_real:] = 0
        with trace("als.stage.upload"):
            upload_bytes = u.nbytes + i.nbytes + r.nbytes + valid.nbytes
            if mesh is not None:
                coo_sh = NamedSharding(mesh, PSpec("data"))
                u = jax.device_put(u, coo_sh)
                i = jax.device_put(i, coo_sh)
                r = jax.device_put(r, coo_sh)
                valid = jax.device_put(valid, coo_sh)
            else:
                # once, not once per iteration as arguments of the step
                u, i, r, valid = (jnp.asarray(a) for a in (u, i, r, valid))
        stage_span.tags = {"upload_bytes": upload_bytes}
    LAST_PLAN_INFO.update(
        stage_s=round(stage_span.duration_s, 2), upload_bytes=upload_bytes
    )

    with trace("als.init"):
        U0, V0 = _init_factors(
            p, num_users_pad, num_items_pad, num_users, num_items, dtype
        )
        if init_factors is not None:
            Uw, Vw = init_factors
            if Uw.shape != (num_users, p.rank) or Vw.shape != (
                num_items, p.rank
            ):
                raise ValueError(
                    f"init_factors shapes {Uw.shape}/{Vw.shape} do not "
                    f"match ({num_users}, {p.rank})/({num_items}, {p.rank})"
                )
            U0 = U0.at[:num_users].set(jnp.asarray(Uw, dtype))
            V0 = V0.at[:num_items].set(jnp.asarray(Vw, dtype))
        if mesh is not None:
            # sharded factor state (ROADMAP item 1): the tables and
            # everything derived from them persist row-sharded over the
            # mesh, so the per-device factor footprint drops as devices
            # grow — each step all-gathers the opposite table transiently
            # for its COO gathers
            factor_sh = NamedSharding(mesh, PSpec("data", None))
            U0 = jax.device_put(U0, factor_sh)
            V0 = jax.device_put(V0, factor_sh)

    step = _make_train_step(
        mesh, num_users_pad, num_items_pad, p, shard_state=mesh is not None
    )
    import time as _time

    from predictionio_tpu.obs import device as device_obs
    from predictionio_tpu.parallel.mesh import meter_shards

    # the solve step on the roofline: XLA's own per-iteration cost joined
    # with the measured wall clock.  The capture is deferred BEFORE the
    # loop so its out-of-band analysis compile runs concurrently with the
    # training dispatches instead of adding a second synchronous compile
    # to the cold-train wall time; the factor shapes are part of the key
    # (same COO, different rank or entity count is a different program
    # with a different cost)
    eff = device_obs.default_efficiency()
    sig = device_obs.signature_of(u, i, r, valid, U0, V0)
    eff.capture_cost(
        "als.train_step", step, u, i, r, valid, U0, V0,
        signature=sig, defer=True,
    )
    # per-iteration timeline track: with PIO_TRAIN_STEP_TIMELINE=1 and a
    # bound trace id (an operator chasing step jitter), each solve
    # iteration becomes one device-track fragment in the distributed
    # timeline.  Costs one host-device block per iteration, so it needs
    # the EXPLICIT opt-in —
    # a trace id alone is not enough, because run_train binds the engine
    # instance id as every training run's correlation (and thus trace) id,
    # and production retrains must keep the fully async dispatch loop.
    import os

    from predictionio_tpu.obs.disttrace import record_fragment
    from predictionio_tpu.obs.logging import get_trace_id

    emit_steps = (
        bool(os.environ.get("PIO_TRAIN_STEP_TIMELINE"))
        and get_trace_id() is not None
    )
    with trace("als.device_loop") as loop_span:
        # one iteration a dispatch, ``num_iterations`` dispatches
        loop_span.tags = {
            "iterations": p.num_iterations, "mode": "scatter",
            "per_iter": True,
        }
        U, V = U0, V0
        for it in range(p.num_iterations):
            t_step = _time.time()
            U, V = step(u, i, r, valid, U, V)
            if emit_steps:
                jax.block_until_ready(V)
                record_fragment(
                    f"als.train_step[{it}]",
                    t_step,
                    _time.time() - t_step,
                    track=f"train:{n_dev}dev",
                    tags={"iteration": it, "devices": n_dev},
                )
        U = jax.block_until_ready(U)
    wall_s = loop_span.duration_s
    LAST_PLAN_INFO.update(
        iterations=p.num_iterations, loop_s=round(wall_s, 4)
    )
    if eff.cached_cost("als.train_step", sig) is None:
        # settle the residue of the concurrent capture (usually zero: the
        # analysis compile raced the real compile + N iterations)
        eff.flush(timeout=30.0)
    eff.observe(
        "als.train_step",
        wall_s / max(p.num_iterations, 1),
        signature=sig,
    )
    # per-device factor attribution: the hook sharded serving/training
    # extends (ROADMAP item 1) — which device holds how many factor bytes,
    # and what the solve spent per device of wall clock
    meter_shards("als.factors", (U, V), seconds=wall_s)
    _log_train_path("als.train_step", wall_s, devices=n_dev)
    # NOTE: the un-padding slice below re-lays-out the result (uneven row
    # counts cannot stay P("data")-sharded); the sharded-state win is the
    # LOOP, where factors + normal-equation state persist 1/n_dev per
    # device across all num_iterations steps (metered just above).  Serving
    # re-shards from the host checkpoint via its own ShardPlan.
    return ALSState(user_factors=U[:num_users], item_factors=V[:num_items])
