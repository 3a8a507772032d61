"""Top-k for serving paths: host replicas AND the fused device kernel.

Host half (the original module): the reference's P2L algorithms serve single
queries from a *local* model on the driver (controller/P2LAlgorithm.scala:
46-76) — the TPU-native analog keeps a host numpy replica of small
factor/score tables and answers solo queries without touching the device at
all.  A [n_items] argpartition is ~0.1 ms at ML-20M scale and, unlike a
device dispatch, immune to device queue congestion.

Device half (:func:`fused_topk_batch`): the batched serving waves used to
run score-then-``lax.top_k`` as two steps over a fully materialized
``[B, n_items]`` score row — n_items * 4 bytes of HBM written and re-read
per query for an answer that keeps only ``k`` of them.  The fused pallas
kernel contracts the query factors against one ``TILE_ROWS``-row slab of
the item table at a time and maintains a running k-best (value, id) list in
the revisited output block, so the full score row **never exists** in any
memory: per grid step the only live score slab is ``[B, TILE_ROWS]``.

Selection is by ``(value desc, global id asc)`` — exactly ``lax.top_k``'s
tie order — implemented as ``k`` unrolled max/min-reduction steps (Mosaic
has no top-k primitive): pick the max value, among its holders pick the
lowest id, retire that entry to ``(-inf, RETIRED_ID)``.  The streaming
merge is therefore bit-identical to a single-device ``lax.top_k`` on the
full row, including ties that straddle tile boundaries (tier-1 parity
suite).  ``LAST_KERNEL_SHAPES`` records each launch's per-tile shape — the
proof hook that ``rows_tile < n_items`` (no full row), mirrored per-shard
when the kernel runs inside the PR 8 ``build_sharded_topk`` shard_map.

Shapes off the fused menu (``k`` past :data:`MAX_FUSED_K`) fall back to the
materialized-row kernels and are COUNTED: ``pio_topk_full_row_fallback_
total`` plus a logged ``(batch, k)`` shape, so a run claiming zero
fallbacks is a checkable fact.
"""

from __future__ import annotations

import logging
from functools import lru_cache

import numpy as np

log = logging.getLogger("predictionio_tpu.ops.topk")

#: item rows scored per grid step — the largest score slab that ever
#: exists; the no-full-row claim is ``TILE_ROWS < n_items`` at catalog
#: scale (recorded per launch in LAST_KERNEL_SHAPES)
TILE_ROWS = 1024

#: batch rows per block (larger waves sweep the batch grid axis)
BATCH_BLOCK = 128

#: largest k on the fused menu: selection is k unrolled reduction steps, so
#: very deep k's belong on the materialized-row path (counted as fallbacks)
MAX_FUSED_K = 128

#: precision of EVERY serving score contraction on the device, in this
#: kernel and in the XLA programs beside it (ALS, NCF, similarity): full
#: f32.  The TPU default contracts f32 operands in ONE bf16 MXU pass — on a
#: v5e scores came out ~1e-3 relative off the f32 value, enough to reorder
#: near-equal items, so the same query ranked differently on the device
#: than on the host replica.  The contraction is over the rank (tens of
#: terms) and the work is reading the item table: the extra passes are
#: noise.  A string so this module stays importable without jax.
SCORE_PRECISION = "highest"

#: retired-entry / padding sentinel id — a power of two, exactly
#: representable in f32, and above the 2^24 packed-id ceiling every catalog
#: already honors (models/ncf/engine._packable_n_items)
RETIRED_ID = float(1 << 25)

#: trace-time record of the most recent fused launch per kernel name — the
#: no-full-row proof hook (``rows_tile`` is the score-slab width; compare
#: with ``n_items``).  The sharded kernels' per-shard shapes live in
#: ``parallel.placement.LAST_KERNEL_SHAPES``; this one covers the fused
#: single-device and per-shard launches.
LAST_KERNEL_SHAPES: dict[str, dict[str, int]] = {}


class FusedTopKUnsupported(ValueError):
    """The requested (batch, k, n_items) shape is off the fused menu."""


def host_topk(scores: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Top-k (values, indices) of a 1-D score vector, sorted descending."""
    n = scores.shape[0]
    k = min(k, n)
    if k <= 0:
        return scores[:0], np.zeros((0,), np.int64)
    if k < n:
        idx = np.argpartition(scores, n - k)[n - k:]
    else:
        idx = np.arange(n)
    order = np.argsort(scores[idx])[::-1]
    idx = idx[order]
    return scores[idx], idx


def host_topk_batch(scores: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Row-wise top-k of a [B, n] score matrix, each row sorted descending."""
    b, n = scores.shape
    k = min(k, n)
    if k <= 0:
        return scores[:, :0], np.zeros((b, 0), np.int64)
    if k < n:
        idx = np.argpartition(scores, n - k, axis=1)[:, n - k:]
    else:
        idx = np.broadcast_to(np.arange(n), (b, n)).copy()
    vals = np.take_along_axis(scores, idx, axis=1)
    order = np.argsort(vals, axis=1)[:, ::-1]
    idx = np.take_along_axis(idx, order, axis=1)
    return np.take_along_axis(scores, idx, axis=1), idx


# ---------------------------------------------------------------------------
# fused score + top-k pallas kernel


def fused_supported(batch: int, k: int, n_items: int) -> bool:
    """True when (batch, k, n_items) is on the fused menu: every wave shape
    the pow2 padding menu produces qualifies; only k past MAX_FUSED_K (or a
    degenerate catalog) falls back to a materialized score row."""
    return 0 < k <= MAX_FUSED_K and k <= n_items and batch > 0


#: shapes already warned about — the counter ticks per dispatch, but a
#: steady off-menu workload must not log one identical WARNING per wave
#: at serving QPS
_WARNED_FALLBACK_SHAPES: set[tuple] = set()


def note_full_row_fallback(
    batch: int, k: int, n_items: int, where: str
) -> None:
    """Count (and name) one full-score-row fallback: a top-k that had to
    materialize the whole ``[batch, n_items]`` row because its shape is off
    the fused menu.  Any non-zero count names the offending (wave, k) shape
    in the log (once per distinct shape — the counter carries the
    per-dispatch cardinality)."""
    from predictionio_tpu.obs.metrics import REGISTRY

    REGISTRY.counter(
        "pio_topk_full_row_fallback_total",
        "Top-k dispatches that materialized a full score row",
        labelnames=("where",),
    ).labels(where).inc()
    shape = (where, batch, k, n_items)
    if shape not in _WARNED_FALLBACK_SHAPES:
        _WARNED_FALLBACK_SHAPES.add(shape)
        log.warning(
            "full-score-row top-k fallback at %s: batch=%d k=%d n_items=%d "
            "(off the fused menu: k<=%d; counted per dispatch in "
            "pio_topk_full_row_fallback_total, logged once per shape)",
            where, batch, k, n_items, MAX_FUSED_K,
        )


def _make_fused_topk_kernel(k: int, bc: int, tile: int):
    """Kernel body: one [bc, tile] score slab, merged into the running
    k-best carried in the revisited output block.

    Selection order is (value desc, id asc) — lax.top_k's exact tie rule —
    via k unrolled steps: max value, then min id among its holders, then
    retire the winner to (-inf, RETIRED_ID) so it never re-selects.  The
    running list initializes to (-inf, RETIRED_ID) on the first tile;
    because callers guarantee k <= n_items, at least k real entries exist
    and sentinel entries always lose the id tiebreak, so they can never
    surface in the output."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(limit_ref, q_ref, v_ref, out_ref):
        i = pl.program_id(1)  # tile index — INNER axis: blocks revisit
        q = q_ref[:]          # [bc, r]
        vt = v_ref[:]         # [tile, r]
        # the only score slab that ever exists: [bc, tile], never [bc, N]
        scores = jax.lax.dot_general(
            q, vt, dimension_numbers=(((1,), (1,)), ((), ())),
            precision=SCORE_PRECISION,
            preferred_element_type=jnp.float32,
        )
        neg = jnp.float32(-jnp.inf)
        col = jax.lax.broadcasted_iota(jnp.int32, (bc, tile), 1)
        gidx = col + i * tile
        # rows past the valid-row limit (sharding/pad fill, catalog end)
        # must never win; their -inf entries keep REAL global ids so the
        # id tiebreak stays exactly lax.top_k's even among excluded rows
        scores = jnp.where(gidx < limit_ref[0], scores, neg)
        run_v = jnp.where(
            i == 0, jnp.full((bc, k), neg, jnp.float32), out_ref[0]
        )
        run_i = jnp.where(
            i == 0,
            jnp.full((bc, k), RETIRED_ID, jnp.float32),
            out_ref[1],
        )
        cand_v = jnp.concatenate([run_v, scores], axis=1)  # [bc, k+tile]
        cand_i = jnp.concatenate(
            [run_i, gidx.astype(jnp.float32)], axis=1
        )
        vals = []
        ids = []
        for _ in range(k):
            m = jnp.max(cand_v, axis=1)
            sel = jnp.min(
                jnp.where(cand_v == m[:, None], cand_i, RETIRED_ID),
                axis=1,
            )
            hit = (cand_v == m[:, None]) & (cand_i == sel[:, None])
            vals.append(m)
            ids.append(sel)
            cand_v = jnp.where(hit, neg, cand_v)
            cand_i = jnp.where(hit, RETIRED_ID, cand_i)
        out_ref[0] = jnp.stack(vals, axis=1)
        out_ref[1] = jnp.stack(ids, axis=1)

    return kernel


@lru_cache(maxsize=64)
def _fused_topk_call(
    nb: int, nt: int, bc: int, rank: int, k: int, tile: int, n_rows: int,
    interpret: bool,
):
    """Build (and cache) one pallas_call: ``(limit[1], q[B, r], table
    [n_rows, r]) -> packed [2, B, k]``.  The valid-row limit rides as a
    scalar-prefetch operand, so one compiled kernel serves every n_items
    (and a traced per-shard limit inside shard_map)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, nt),
        in_specs=[
            pl.BlockSpec((bc, rank), lambda b, i, lim: (b, 0)),
            pl.BlockSpec((tile, rank), lambda b, i, lim: (i, 0)),
        ],
        # every tile of one batch block revisits the SAME [2, bc, k]
        # output block — the running k-best stays VMEM-resident across
        # the whole table sweep and is written to HBM once per block
        out_specs=pl.BlockSpec((2, bc, k), lambda b, i, lim: (0, b, 0)),
    )
    return pl.pallas_call(
        _make_fused_topk_kernel(k, bc, tile),
        out_shape=jax.ShapeDtypeStruct((2, nb * bc, k), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
        name="fused_topk",
    )


def fused_topk_batch(
    queries,
    table,
    k: int,
    limit=None,
    *,
    name: str = "fused_topk",
    interpret: bool | None = None,
):
    """Fused score+top-k: ``queries [B, r] x table [N, r] -> packed
    [2, B, k]`` f32 (row 0 scores, row 1 global row ids, exact < 2^24) —
    without ever materializing a ``[B, N]`` score row.

    ``limit`` is the number of valid table rows (default N): rows at or
    past it can never surface.  It may be a TRACED scalar — how the
    per-shard launch inside ``build_sharded_topk`` masks the catalog tail
    on the last shard only.  One wave is ONE kernel launch at any wave
    size: the batch sweeps a second grid axis in :data:`BATCH_BLOCK`
    chunks, so the pow2 wave menu (8..64) is a single block and bulk eval
    batches just add grid steps.

    Raises :class:`FusedTopKUnsupported` off the menu — callers fall back
    to a materialized row and must count it (:func:`note_full_row_
    fallback`)."""
    import jax
    import jax.numpy as jnp

    q = jnp.asarray(queries, jnp.float32)
    t = jnp.asarray(table)
    b, rank = q.shape
    n_rows = t.shape[0]
    if not fused_supported(b, k, n_rows):
        raise FusedTopKUnsupported(
            f"fused top-k menu: batch={b} k={k} n_items={n_rows} "
            f"(k must be in 1..{MAX_FUSED_K} and <= n_items)"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bc = min(BATCH_BLOCK, max(b, 1))
    pad_b = (-b) % bc
    if pad_b:
        q = jnp.concatenate([q, jnp.zeros((pad_b, rank), q.dtype)])
    nb = (b + pad_b) // bc
    nt = -(-n_rows // TILE_ROWS)
    if limit is None:
        limit = n_rows
    limit_arr = jnp.asarray(
        jnp.reshape(jnp.asarray(limit, jnp.int32), (1,))
    )
    # the proof hook: the per-step score slab is rows_tile wide, never
    # n_items — asserted by the no-full-row tests (single-device AND
    # per-shard, where this records each shard's local launch)
    LAST_KERNEL_SHAPES[name] = {
        # 1 = the pallas interpreter ran the body (the CPU-test rule
        # above), 0 = Mosaic compiled it for the chip
        "interpret": int(interpret),
        "rows_tile": int(min(TILE_ROWS, n_rows)),
        "batch": int(b),
        "batch_block": int(bc),
        "k": int(k),
        "n_rows": int(n_rows),
        "n_tiles": int(nt),
    }
    call = _fused_topk_call(
        nb, nt, bc, rank, k, TILE_ROWS, n_rows, interpret
    )
    # the caller's name for this launch, on every device operation of it
    with jax.named_scope(name):
        packed = call(limit_arr, q, t)
    if pad_b:
        packed = packed[:, :b]
    return packed


def fused_topk_roofline(
    batch: int, rank: int, n_items: int, k: int
) -> dict[str, float]:
    """Analytic per-launch HBM bytes and MXU flops of the fused kernel
    (pallas bodies are opaque to XLA's cost_analysis, same as the ALS
    train kernel): the table is read once per batch block, queries once
    per tile, and only the [2, B, k] winners are written."""
    nb = -(-batch // BATCH_BLOCK)
    nt = -(-n_items // TILE_ROWS)
    bytes_moved = (
        n_items * rank * 4.0 * nb         # table slabs, once per batch block
        + batch * rank * 4.0 * nt         # query block re-read per tile
        + 2.0 * batch * k * 4.0           # packed winners out
    )
    flops = 2.0 * batch * n_items * rank  # the score contraction
    return {"bytes": bytes_moved, "flops": flops}
