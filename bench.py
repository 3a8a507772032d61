"""Headline benchmark: ALS full train at MovieLens-20M scale + quality +
serving latency.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "s", "vs_baseline": N,
   "map_at_10": ..., "precision_at_10": ...,
   "serving_p50_ms": ..., "serving_p50_concurrent32_ms": ...}

The reference publishes no benchmark numbers (SURVEY.md §6); the baseline is
the driver-set north-star from BASELINE.json: full ALS train on
MovieLens-20M in < 60 s (reference hyperparams rank=10, 20 iterations,
lambda=0.01 — examples/scala-parallel-recommendation/customize-serving/
engine.json:14-21) and /queries.json p50 < 10 ms.  ``vs_baseline`` is the
speedup vs the 60 s budget (>1.0 = beating the target).

Zero-egress environment -> the dataset is a DETERMINISTIC MovieLens-like
generator at the ML-20M shape (20M ratings, 138k users, 27k items): Zipf
item popularity, heavy-tailed user activity, planted low-rank preference
structure + noise, ratings clipped to the 0.5-5 star scale.  A held-out
split (random ~3% of high ratings from active users) feeds MAP@10 /
Precision@10 computed through the framework's Metric classes
(models/recommendation/evaluation.py), vs the reference's Evaluation.scala
PrecisionAtK protocol.

Serving latency is measured twice:
  - single-query p50 through ALSAlgorithm.predict (the engine hot path:
    vocab lookup + host-replica top-k, the P2L local-model pattern);
  - p50 under 32 concurrent clients against a real AsyncAppServer running
    the micro-batched /queries.json route (HTTP + JSON + coalescing
    included).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

RANK_PLANTED = 8
K = 10


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_sync(x) -> None:
    """Every timed section ends here: dispatch is asynchronous, so a timing
    that does not wait for the result measures the enqueue."""
    import jax

    jax.block_until_ready(x)


def make_movielens_like(
    nnz: int,
    num_users: int,
    num_items: int,
    seed: int = 3,
    browse_k: int = 8,
    browse_frac: float = 0.7,
):
    """Deterministic ML-shaped ratings (COO): Zipf item popularity, lognormal
    user activity, item quality correlated with popularity, planted rank-8
    personal preference structure + noise.

    Exposure is preference-correlated the way real watch data is: for
    ``browse_frac`` of interactions the user "browses" ``browse_k``
    popularity-drawn candidates and watches the one they prefer most
    (best-of-K choice); the rest are pure popularity impressions.  Marginal
    item popularity stays Zipf-anchored (candidates are always drawn from
    the Zipf), so popularity is still a strong baseline — but which popular
    item a user watches, and rates highly, carries their planted taste.
    """
    rng = np.random.default_rng(seed)
    item_p = (np.arange(num_items) + 10.0) ** -0.8
    item_p /= item_p.sum()
    item_cdf = np.cumsum(item_p)
    user_w = rng.lognormal(0.0, 1.0, num_users)
    user_p = user_w / user_w.sum()
    user_cdf = np.cumsum(user_p)
    # inverse-CDF sampling: ~10x faster than rng.choice(p=...) at this scale
    user_idx = np.searchsorted(user_cdf, rng.random(nnz)).astype(np.int64)
    user_idx = np.minimum(user_idx, num_users - 1)
    uf = rng.standard_normal((num_users, RANK_PLANTED)).astype(np.float32)
    vf = rng.standard_normal((num_items, RANK_PLANTED)).astype(np.float32)

    item_idx = np.empty(nnz, np.int64)
    browse = rng.random(nnz) < browse_frac
    n_plain = int((~browse).sum())
    plain = np.searchsorted(item_cdf, rng.random(n_plain)).astype(np.int64)
    item_idx[~browse] = np.minimum(plain, num_items - 1)
    b_users = user_idx[browse]
    browse_pos = np.flatnonzero(browse)
    # chunked best-of-K: candidates by popularity, winner by planted taste
    for c0 in range(0, len(b_users), 2_000_000):
        bu = b_users[c0 : c0 + 2_000_000]
        cand = np.searchsorted(
            item_cdf, rng.random((len(bu), browse_k))
        ).astype(np.int64)
        cand = np.minimum(cand, num_items - 1)
        pref = np.einsum("nk,njk->nj", uf[bu], vf[cand])
        pick = cand[np.arange(len(bu)), pref.argmax(1)]
        item_idx[browse_pos[c0 : c0 + 2_000_000]] = pick

    zpop = -np.log(np.arange(num_items) + 10.0)
    zpop = (zpop - zpop.mean()) / zpop.std()
    item_bias = (
        0.3 * zpop + 0.2 * rng.standard_normal(num_items)
    ).astype(np.float32)
    # base 1.55: best-of-K selection raises the mean planted preference of
    # *watched* items by ~+1.3 stars, so the observed rating distribution
    # recenters near the ML-20M shape (mean ~3.4, ~40% of ratings >= 4)
    raw = (
        1.55
        + item_bias[item_idx]
        + 1.8
        * np.einsum("nk,nk->n", uf[user_idx], vf[item_idx])
        / np.sqrt(RANK_PLANTED)
        + 0.4 * rng.standard_normal(nnz).astype(np.float32)
    )
    rating = np.clip(np.round(raw * 2.0) / 2.0, 0.5, 5.0).astype(np.float32)
    return user_idx, item_idx, rating


def holdout_split(user_idx, item_idx, rating, rng, min_count=15, frac=0.03):
    """Move a random slice of high ratings from active users to a test set."""
    counts = np.bincount(user_idx, minlength=user_idx.max() + 1)
    test_mask = (
        (counts[user_idx] >= min_count)
        & (rating >= 4.0)
        & (rng.uniform(size=len(rating)) < frac)
    )
    train = ~test_mask
    return (
        (user_idx[train], item_idx[train], rating[train]),
        (user_idx[test_mask], item_idx[test_mask]),
    )


def compute_ranking_metrics(
    U, V, train_u, train_i, test_u, test_i, max_eval_users=10_000, seed=0
):
    """MAP@10 / Precision@10 via the framework metrics, excluding each
    user's train items from the ranking (reference blacklist protocol)."""
    from predictionio_tpu.models.recommendation.engine import (
        ItemScore,
        PredictedResult,
        Query,
    )
    from predictionio_tpu.models.recommendation.evaluation import (
        MAPAtK,
        PrecisionAtK,
    )
    from predictionio_tpu.ops.topk import host_topk_batch

    rng = np.random.default_rng(seed)
    eval_users = np.unique(test_u)
    if len(eval_users) > max_eval_users:
        eval_users = rng.choice(eval_users, max_eval_users, replace=False)
        eval_users.sort()

    # per-user index slices into the (sorted-by-user) train/test streams
    train_order = np.argsort(train_u, kind="stable")
    train_u_sorted = train_u[train_order]
    train_i_sorted = train_i[train_order]
    test_order = np.argsort(test_u, kind="stable")
    test_u_sorted = test_u[test_order]
    test_i_sorted = test_i[test_order]

    Uh = np.asarray(U, np.float32)
    Vh = np.asarray(V, np.float32)
    triples = []
    chunk = 2048
    for c0 in range(0, len(eval_users), chunk):
        users = eval_users[c0 : c0 + chunk]
        scores = Uh[users] @ Vh.T  # [B, n_items]
        t_lo = np.searchsorted(train_u_sorted, users, "left")
        t_hi = np.searchsorted(train_u_sorted, users, "right")
        for row, (u, lo, hi) in enumerate(zip(users, t_lo, t_hi)):
            scores[row, train_i_sorted[lo:hi]] = -np.inf
        top_s, top_i = host_topk_batch(scores, K)
        e_lo = np.searchsorted(test_u_sorted, users, "left")
        e_hi = np.searchsorted(test_u_sorted, users, "right")
        for row, (u, lo, hi) in enumerate(zip(users, e_lo, e_hi)):
            actual = frozenset(str(i) for i in test_i_sorted[lo:hi])
            pred = PredictedResult(
                item_scores=tuple(
                    ItemScore(item=str(ii), score=float(ss))
                    for ii, ss in zip(top_i[row], top_s[row])
                )
            )
            triples.append((Query(user=str(u), num=K), pred, actual))
    fold_data = [({}, triples)]
    return (
        MAPAtK(K).calculate(fold_data),
        PrecisionAtK(K).calculate(fold_data),
        len(triples),
    )


def build_als_model(state, num_users, num_items):
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.recommendation.engine import ALSModel

    user_vocab = BiMap.from_keys(np.asarray([str(u) for u in range(num_users)]))
    item_vocab = BiMap.from_keys(np.asarray([str(i) for i in range(num_items)]))
    return ALSModel(
        user_factors=np.asarray(state.user_factors),
        item_factors=np.asarray(state.item_factors),
        user_vocab=user_vocab,
        item_vocab=item_vocab,
    )


def build_ncf_model(ncf_state, num_users, num_items):
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.ncf.engine import NCFModel

    return NCFModel(
        state=ncf_state,
        user_vocab=BiMap.from_keys(
            np.asarray([str(u) for u in range(num_users)])
        ),
        item_vocab=BiMap.from_keys(
            np.asarray([str(i) for i in range(num_items)])
        ),
    )


def ncf_ranking_metrics(
    ncf_params,
    train_u,
    train_i,
    test_u,
    test_i,
    n_items,
    max_eval_users=10_000,
    cand=2048,
    seed=0,
):
    """MAP@10 / Precision@10 for the NCF model through the SAME framework
    Metric classes and blacklist protocol as the ALS number.

    NCF scores live on device (the MLP tower over the full catalog is a
    device matmul, not a host dot product), so the ranking is computed as
    device top-``cand`` per user; the per-user train blacklist is applied
    on host over those candidates.  Users whose train-item count could
    exhaust the candidate list fall back to a full-row transfer, so the
    protocol is exact for every user.
    """
    from functools import partial

    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models.recommendation.engine import (
        ItemScore,
        PredictedResult,
        Query,
    )
    from predictionio_tpu.models.recommendation.evaluation import (
        MAPAtK,
        PrecisionAtK,
    )
    from predictionio_tpu.ops.ncf import score_all_items

    @partial(jax.jit, static_argnames=("n_items", "cand"))
    def topc(params, users, n_items: int, cand: int):
        scores = jax.vmap(lambda u: score_all_items(params, u))(users)
        masked = jnp.where(
            jnp.arange(scores.shape[1])[None, :] < n_items, scores, -jnp.inf
        )
        s, i = jax.lax.top_k(masked, cand)
        return jnp.stack([s, i.astype(jnp.float32)])

    cand = min(cand, n_items)
    rng = np.random.default_rng(seed)
    eval_users = np.unique(test_u)
    if len(eval_users) > max_eval_users:
        eval_users = rng.choice(eval_users, max_eval_users, replace=False)
        eval_users.sort()
    tro = np.argsort(train_u, kind="stable")
    tru, tri = train_u[tro], train_i[tro]
    teo = np.argsort(test_u, kind="stable")
    teu, tei = test_u[teo], test_i[teo]
    # size the candidate list to the HEAVIEST eval user's blacklist UP
    # FRONT (next pow2 of max_seen + K): the per-user fallback below then
    # never fires — BENCH_r05's "ncf eval full-row fallbacks: 2" was two
    # users whose train history exhausted the fixed 2048 menu
    if len(eval_users):
        max_seen = int(
            (
                np.searchsorted(tru, eval_users, "right")
                - np.searchsorted(tru, eval_users, "left")
            ).max()
        )
        need = max_seen + K
        if need > cand:
            cand = min(1 << (need - 1).bit_length(), n_items)

    triples = []
    B = 512
    pad = (-len(eval_users)) % B
    users_p = np.concatenate([eval_users, np.zeros(pad, np.int64)])
    fallbacks = 0
    for c0 in range(0, len(users_p), B):
        users = users_p[c0 : c0 + B]
        packed = np.asarray(
            topc(ncf_params, jnp.asarray(users, jnp.int32), n_items, cand)
        )
        top_s, top_i = packed[0], packed[1].astype(np.int64)
        lo = np.searchsorted(tru, users, "left")
        hi = np.searchsorted(tru, users, "right")
        elo = np.searchsorted(teu, users, "left")
        ehi = np.searchsorted(teu, users, "right")
        for row in range(min(B, len(eval_users) - c0)):
            u = users[row]
            seen = frozenset(tri[lo[row] : hi[row]].tolist())
            if len(seen) > cand - K and cand < n_items:
                # candidate list could be exhausted by the blacklist:
                # exact fallback on the full score row — COUNTED
                # (pio_topk_full_row_fallback_total) and shape-logged; the
                # up-front cand sizing above should make this unreachable
                from predictionio_tpu.ops.topk import note_full_row_fallback

                note_full_row_fallback(1, cand, n_items, "ncf.eval")
                full = np.asarray(
                    topc(ncf_params, jnp.asarray([u] * 1, jnp.int32),
                         n_items, n_items)
                )
                row_s, row_i = full[0][0], full[1][0].astype(np.int64)
                fallbacks += 1
            else:
                row_s, row_i = top_s[row], top_i[row]
            pred = []
            for ss, ii in zip(row_s, row_i):
                if int(ii) not in seen and np.isfinite(ss):
                    pred.append(ItemScore(item=str(int(ii)), score=float(ss)))
                    if len(pred) == K:
                        break
            actual = frozenset(
                str(int(x)) for x in tei[elo[row] : ehi[row]]
            )
            triples.append(
                (Query(user=str(int(u)), num=K),
                 PredictedResult(item_scores=tuple(pred)), actual)
            )
    if fallbacks:
        log(f"# ncf eval full-row fallbacks: {fallbacks}")
    fold_data = [({}, triples)]
    return (
        MAPAtK(K).calculate(fold_data),
        PrecisionAtK(K).calculate(fold_data),
        len(triples),
    )


def ncf_serving_p50(model, num_users, n=200):
    """NCF-template solo serving: vocab lookup + on-device score_all_items
    top-k through NCFAlgorithm.predict, as ONE packed device->host
    transfer.  The wall clock includes the dispatch round trip (see
    dispatch_rtt_ms); ncf_solo_device_ms is the device's share."""
    from predictionio_tpu.models.ncf.engine import NCFAlgorithm, Query

    algo = NCFAlgorithm()
    algo.predict(model, Query(user="0", num=K))  # compile
    lat = []
    for q in range(n):
        t0 = time.perf_counter()
        r = algo.predict(model, Query(user=str(q % num_users), num=K))
        lat.append(time.perf_counter() - t0)
        assert r.item_scores
    lat.sort()
    return lat[len(lat) // 2] * 1000


def ncf_solo_e2e_p50(model, num_users, n=60, depth=4):
    """Solo end-to-end WALL including dispatch, through the async pipelined
    path (the PR 12 target): per-query completion interval at steady state
    with ``depth`` unfenced queries in flight.  A synchronous solo query
    pays the full dispatch->fence round trip; with dispatch_batch the next
    query's dispatch overlaps this one's fence, so the steady-state
    per-query wall collapses toward the device cost."""
    from collections import deque

    from predictionio_tpu.models.ncf.engine import NCFAlgorithm, Query

    algo = NCFAlgorithm()

    def dispatch(q):
        fin = algo.dispatch_batch(
            model, [(0, Query(user=str(q % num_users), num=K))]
        )
        assert fin is not None
        return fin

    dispatch(0)()  # compile + warm
    pend: deque = deque()
    done_t = []
    for q in range(n):
        pend.append(dispatch(q))
        if len(pend) > depth:
            pend.popleft()()
            done_t.append(time.perf_counter())
    while pend:
        pend.popleft()()
        done_t.append(time.perf_counter())
    intervals = np.diff(np.asarray(done_t)) * 1000
    intervals.sort()
    return float(intervals[len(intervals) // 2])


def dispatch_rtt_ms(n=30):
    """p50 of a trivial jit dispatch + tiny device->host transfer: the floor
    under any synchronous device query, reported so the serving numbers can
    separate framework cost from dispatch cost."""
    import jax
    import jax.numpy as jnp

    x = jnp.zeros((8,), jnp.float32)
    f = jax.jit(lambda v: v + 1.0)
    np.asarray(f(x))  # compile
    lat = []
    for _ in range(n):
        t0 = time.perf_counter()
        np.asarray(f(x))
        lat.append(time.perf_counter() - t0)
    lat.sort()
    return lat[len(lat) // 2] * 1000


def ncf_solo_device_ms(ncf_params, n_items, num_users, n=100):
    """Device-compute cost of ONE solo NCF query: n distinct solo
    dispatches pipelined back-to-back with a single dependent sync, so the
    dispatch round trip amortizes out (the in-order device queue proves all
    n executed before the last value arrived)."""
    import jax.numpy as jnp

    from predictionio_tpu.models.ncf.engine import _score_topk

    outs = [
        _score_topk(ncf_params, jnp.int32(q % num_users), n_items, K)
        for q in range(5)
    ]
    device_sync(outs[-1])
    t0 = time.perf_counter()
    outs = [
        _score_topk(ncf_params, jnp.int32(q % num_users), n_items, K)
        for q in range(n)
    ]
    device_sync(outs[-1])
    return (time.perf_counter() - t0) / n * 1000


def serving_p50_single(model, num_users, n=500):
    """Engine-path solo-query p50: ALSAlgorithm.predict end to end."""
    from predictionio_tpu.models.recommendation.engine import ALSAlgorithm, Query

    algo = ALSAlgorithm()
    algo.predict(model, Query(user="0", num=K))  # warm host replica
    lat = []
    for q in range(n):
        t0 = time.perf_counter()
        r = algo.predict(model, Query(user=str(q % num_users), num=K))
        lat.append(time.perf_counter() - t0)
        assert r.item_scores
    lat.sort()
    return lat[len(lat) // 2] * 1000


def _interned_const(n: int, value: str) -> np.ndarray:
    """Constant object column sharing ONE Python object (``np.full`` boxes
    n distinct copies, defeating the store's pointer fast paths)."""
    a = np.empty(n, object)
    a[:] = value
    return a


def _events_checksum(gu, gi, gr) -> int:
    """Order-insensitive content checksum over the scanned columns — the
    pre/post-compaction parity proof (compaction reorders rows; it must
    never change their multiset)."""
    h = (
        gu.astype(np.uint64) * np.uint64(1315423911)
        ^ gi.astype(np.uint64) * np.uint64(2654435761)
        ^ (gr.astype(np.float64) * 2).astype(np.uint64) * np.uint64(97)
    )
    return int(np.bitwise_xor.reduce(h) ^ np.uint64(len(gu)))


def bench_event_store(
    tr_u, tr_i, tr_r, num_users, num_items, events_scale_m: float | None = None
):
    """Prove the sharded parquet data plane at benchmark scale: parallel
    sharded bulk write, shard scan with dictionary-decode + projection,
    watermarked compaction (content-checksum parity pre/post), and the
    per-user history point read (the serving-path access pattern).

    With ``events_scale_m`` unset, every train interaction becomes a rate
    event (the BENCH_r05-comparable ``events20m_*`` lines).  With it set
    (``--events-scale 100``), that many MILLION synthetic events stream in
    in chunks — multiple write-hot segments per shard, which is what the
    compactor exists to fold.

    This is the HBase-class role (HBEventsUtil.scala:83 rowkey layout ->
    entity-hash shard files; HBPEvents bulk scan -> iter_shards) exercised
    at the scale the reference runs against a server fleet.
    """
    import shutil
    import tempfile

    from predictionio_tpu.data.storage.base import EventFrame
    from predictionio_tpu.data.storage.parquet_backend import (
        ParquetClient,
        ParquetLEvents,
        ParquetPEvents,
    )
    from predictionio_tpu.obs.metrics import REGISTRY
    from predictionio_tpu.ops.als import ALSParams, train_als

    synthetic = events_scale_m is not None
    n = int(events_scale_m * 1e6) if synthetic else len(tr_r)
    label = f"{events_scale_m:g}m" if synthetic else "20m"
    root = tempfile.mkdtemp(prefix="pio_bench_events_")
    try:
        client = ParquetClient(root, n_shards=16)
        pe = ParquetPEvents(client)
        le = ParquetLEvents(client)
        t0 = time.perf_counter()
        # vectorized column build: u<id>/i<id> string vocabularies once,
        # indexed per event — no per-event Python objects anywhere.
        # Properties ride the EventFrame LAZY-row contract (pre-serialized
        # JSON strings): ratings take ~20 distinct values, so the N
        # documents are ~20 interned strings indexed per event.
        user_names = np.array([f"u{x}" for x in range(num_users)], object)
        item_names = np.array([f"i{x}" for x in range(num_items)], object)
        if synthetic:
            rng = np.random.default_rng(11)
            rat_vals = np.arange(1, 11) / 2.0
        else:
            rat_vals, rat_code = np.unique(tr_r, return_inverse=True)
        rat_docs = np.array(
            [json.dumps({"rating": float(v)}) for v in rat_vals], object
        )

        def build_chunk(lo: int, hi: int) -> EventFrame:
            m = hi - lo
            if synthetic:
                cu = rng.integers(0, num_users, m)
                ci = rng.integers(0, num_items, m)
                cc = rng.integers(0, len(rat_vals), m)
            else:
                cu, ci, cc = tr_u[lo:hi], tr_i[lo:hi], rat_code[lo:hi]
            return EventFrame(
                event=_interned_const(m, "rate"),
                entity_type=_interned_const(m, "user"),
                entity_id=user_names[cu],
                target_entity_type=_interned_const(m, "item"),
                target_entity_id=item_names[ci],
                event_time_ms=np.full(m, 1_700_000_000_000, np.int64)
                + np.arange(lo, hi, dtype=np.int64) % 86_400_000,
                properties=rat_docs[cc],
            )

        # chunked ingest: bounded host RAM at 100M rows, and >1 write-hot
        # segment per shard so compaction folds real backlog
        chunk = min(n, 12_500_000)
        build_s = 0.0
        write_s = 0.0
        for lo in range(0, n, chunk):
            t0 = time.perf_counter()
            frame = build_chunk(lo, min(lo + chunk, n))
            build_s += time.perf_counter() - t0
            t0 = time.perf_counter()
            pe.write(frame, app_id=1)
            write_s += time.perf_counter() - t0
            del frame

        from predictionio_tpu.data.storage.base import ptr_factorize

        def names_to_int(col: np.ndarray, prefix: str) -> np.ndarray:
            # "u123" -> 123.  Scans hand back dictionary-decoded columns
            # whose rows POINT at the vocabulary, so the string parse runs
            # once per unique name, not once per row
            f = ptr_factorize(col)
            if f is not None:
                codes, uniq = f
                vals = np.fromiter(
                    (int(s[len(prefix):]) for s in uniq),
                    np.int32,
                    len(uniq),
                )
                return vals[codes]
            return np.char.lstrip(col.astype(str), prefix).astype(np.int32)

        def scan():
            got_u, got_i, got_r, rows = [], [], [], 0
            for _, f in pe.iter_shards(
                1, columns=["entity_id", "target_entity_id", "properties"]
            ):
                rows += len(f)
                got_u.append(names_to_int(f.entity_id, "u"))
                got_i.append(names_to_int(f.target_entity_id, "i"))
                got_r.append(f.property_column("rating"))
            return (
                rows,
                np.concatenate(got_u),
                np.concatenate(got_i),
                np.concatenate(got_r).astype(np.float32),
            )

        t0 = time.perf_counter()
        rows, gu, gi, gr = scan()
        scan_s = time.perf_counter() - t0
        assert rows == n, f"store round trip lost rows: {rows} != {n}"
        checksum_pre = _events_checksum(gu, gi, gr)

        gb = sum(
            f.stat().st_size
            for f in __import__("pathlib").Path(root).rglob("*.parquet")
        ) / 1e9
        # watermarked background compaction: fold the write-hot head, then
        # prove the scan is bit-identical (row count + content checksum)
        t0 = time.perf_counter()
        live = pe.compact(1)
        compact_s = time.perf_counter() - t0
        assert live == n, f"compaction changed row count: {live} != {n}"
        status = pe.status(1)
        t0 = time.perf_counter()
        rows2, gu2, gi2, gr2 = scan()
        scan_post_s = time.perf_counter() - t0
        checksum_post = _events_checksum(gu2, gi2, gr2)
        assert rows2 == n and checksum_post == checksum_pre, (
            "post-compaction scan is not bit-identical: "
            f"rows {rows2}!={n} or checksum {checksum_post}!={checksum_pre}"
        )
        del gu2, gi2, gr2

        # per-user history point read on the compacted store — the
        # sequence engine's serving-path access pattern.  Bytes-read vs
        # bytes-skipped counters prove the segment/row-group skipping.
        def _counter(family):
            return REGISTRY.counter(
                family, labelnames=("kind",)
            ).labels("entity").value

        br0, bs0 = (
            _counter("pio_eventstore_bytes_read_total"),
            _counter("pio_eventstore_bytes_skipped_total"),
        )
        probes = 200
        rng2 = np.random.default_rng(5)
        lats = []
        for q in rng2.integers(0, num_users, probes):
            t0 = time.perf_counter()
            evs = list(
                le.find_by_entity(
                    1, "user", f"u{q}", limit=50, reversed=True
                )
            )
            lats.append(time.perf_counter() - t0)
        lats.sort()
        hist_p50_ms = lats[probes // 2] * 1000
        hist_p99_ms = lats[int(probes * 0.99)] * 1000
        br, bs = (
            _counter("pio_eventstore_bytes_read_total") - br0,
            _counter("pio_eventstore_bytes_skipped_total") - bs0,
        )
        bytes_frac = br / (br + bs) if (br + bs) else 0.0

        train1_s = None
        if not synthetic:
            # one ALS iteration trained from the scanned columns (the
            # PEventStore seam end to end; nnz parity asserted above)
            t0 = time.perf_counter()
            st = train_als(
                gu, gi, gr, num_users, num_items,
                params=ALSParams(rank=10, reg=0.01, seed=3, num_iterations=1),
            )
            device_sync(st.user_factors)
            train1_s = time.perf_counter() - t0
            assert np.isfinite(np.asarray(st.user_factors)).all()
        del gu, gi, gr

        log(
            f"# event store @{label}: build={build_s:.0f}s "
            f"write={write_s:.1f}s ({gb:.2f} GB parquet) "
            f"shard_scan={scan_s:.1f}s compact={compact_s:.1f}s "
            f"scan_postcompact={scan_post_s:.1f}s "
            f"user_history p50={hist_p50_ms:.2f}ms p99={hist_p99_ms:.2f}ms "
            f"(bytes touched {bytes_frac:.1%}) backlog="
            f"{status['backlog_segments']} rows={rows}"
            + (f" train1_from_store={train1_s:.0f}s" if train1_s else "")
        )
        out = {
            f"events{label}_write_s": round(write_s, 1),
            f"events{label}_scan_s": round(scan_s, 1),
            f"events{label}_parquet_gb": round(gb, 2),
            f"events{label}_compact_s": round(compact_s, 1),
            f"events{label}_scan_postcompact_s": round(scan_post_s, 1),
            "events_scale_m": round(n / 1e6, 3),
            "events_write_mb_s": round(gb * 1000 / write_s, 1),
            "events_scan_mb_s": round(gb * 1000 / scan_s, 1),
            "events_user_history_p50_ms": round(hist_p50_ms, 2),
            "events_user_history_p99_ms": round(hist_p99_ms, 2),
            "events_history_bytes_frac": round(bytes_frac, 4),
            "events_compaction_backlog": status["backlog_segments"],
        }
        return out
    finally:
        shutil.rmtree(root, ignore_errors=True)


# The asyncio load client lives in predictionio_tpu.replay.workload (one
# traffic generator for BENCH and the production-day harness); it's spawned
# as `python -m predictionio_tpu.replay.workload PORT CONNS PER_CONN
# NUM_USERS ROUNDS` and prints one JSON result line per round.


_SERVER_SCRIPT = r"""
# Serving process for the concurrent bench: a FRESH interpreter pinned to
# cpu (the parent holds the chip, and one chip belongs to one process; ALS
# serves these waves from its host replica anyway).  Everything it reports
# is a HOST metric.
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import threading, types
import numpy as np
from bench import build_als_model
from predictionio_tpu.core.base import FirstServing
from predictionio_tpu.models.recommendation.engine import ALSAlgorithm
from predictionio_tpu.server.aio import AsyncAppServer
from predictionio_tpu.server.prediction_server import (
    DeployedEngine, create_prediction_server_app,
)

blob = np.load(sys.argv[1])

class _State:
    user_factors = blob["U"]
    item_factors = blob["V"]

model = build_als_model(_State(), len(blob["U"]), len(blob["V"]))
deployed = DeployedEngine.__new__(DeployedEngine)
deployed._lock = threading.RLock()
deployed.instance = types.SimpleNamespace(id="bench")
deployed.storage = None
deployed.algorithms = [ALSAlgorithm()]
deployed.models = [model]
deployed.serving = FirstServing()
app = create_prediction_server_app(deployed, use_microbatch=True)
server = AsyncAppServer(app, "127.0.0.1", 0).start_background()
print(server.port, flush=True)
sys.stdin.readline()  # parent closes stdin to stop us
sizes = sorted(app.microbatcher.wave_sizes.items())
print(f"waves {sizes}", file=sys.stderr, flush=True)
# one-line decomposed-latency snapshot (p50/p95/p99 from the log buckets):
# request latency split into queue wait vs device time per wave
from predictionio_tpu.obs.metrics import REGISTRY, render_json_line
print("metrics " + render_json_line(REGISTRY, [
    "pio_request_latency_seconds",
    "pio_microbatch_queue_wait_seconds",
    "pio_microbatch_device_seconds",
    "pio_microbatch_batch_size",
]), file=sys.stderr, flush=True)
# solo-path host-stage attribution (obs/hotpath.py): where the request's
# wall time went, by named stage — the BENCH-side view of /hotpath.json
import json as _json
print("hotpath " + _json.dumps(app.hotpath.snapshot()),
      file=sys.stderr, flush=True)
# the watch loop's verdict on the run: tick the default alert pack once
# over everything the load just metered — a healthy bench must show ZERO
# firing alerts (a firing one here means the default thresholds would
# have paged on this very run)
if getattr(app, "alerts", None) is not None:
    app.alerts.tick()
    snap = app.alerts.snapshot()
    print("alerts " + _json.dumps({
        "firing": snap["firing"], "pending": snap["pending"],
        "rules": len(snap["rules"]),
        "firing_rules": sorted({a["rule"] for a in snap["alerts"]
                                if a["state"] == "firing"}),
    }), file=sys.stderr, flush=True)
server.shutdown()
"""


def bench_fleet_section(model, num_users, n_replicas: int, requests: int = 300):
    """`python bench.py --fleet N`: router-overhead section.

    N replica serving subprocesses (the same fresh-interpreter _SERVER_SCRIPT
    the concurrent section uses, pinned to cpu) behind an in-process fleet
    router; measures sequential p50/p99 direct-to-one-replica vs through the
    router (same keep-alive client loop), plus the retry-elsewhere rate —
    the router's whole value is affinity + failover at near-zero latency
    cost, and ``fleet_router_overhead_ms`` is the regression gate on that
    claim (BENCH_GATE_METRICS)."""
    import subprocess
    import tempfile

    from predictionio_tpu.fleet.membership import FleetState
    from predictionio_tpu.fleet.router import create_router_app
    from predictionio_tpu.obs.metrics import MetricsRegistry
    from predictionio_tpu.replay.workload import measure_closed_loop
    from predictionio_tpu.server.httpd import AppServer

    with tempfile.NamedTemporaryFile(suffix=".npz", delete=False) as f:
        np.savez(
            f,
            U=np.asarray(model.user_factors, np.float32),
            V=np.asarray(model.item_factors, np.float32),
        )
        blob_path = f.name
    procs = []
    ports = []
    router = None
    fleet = None
    try:
        for _ in range(n_replicas):
            srv = subprocess.Popen(
                [sys.executable, "-c", _SERVER_SCRIPT, blob_path],
                stdin=subprocess.PIPE,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            procs.append(srv)
        for srv in procs:
            line = srv.stdout.readline()
            if not line.strip():
                srv.kill()
                _, err = srv.communicate(timeout=10)
                raise RuntimeError(f"fleet replica failed to start: {err[-800:]}")
            ports.append(int(line))
        reg = MetricsRegistry()
        fleet = FleetState(
            [f"http://127.0.0.1:{p}" for p in ports], registry=reg
        )
        fleet.probe_once()
        router = AppServer(
            create_router_app(fleet, registry=reg), "127.0.0.1", 0
        ).start_background()

        def measure(port: int, n: int) -> list[float]:
            # shared closed-loop client (predictionio_tpu.replay.workload) —
            # same keep-alive loop BENCH always used, now also the unit the
            # `pio day` harness builds on
            return measure_closed_loop("127.0.0.1", port, n, num_users)

        measure(ports[0], 20)  # warm the direct path (jit + keep-alive)
        measure(router.port, 20)  # warm the router path + all replicas
        direct = measure(ports[0], requests)
        routed = measure(router.port, requests)
        retries = 0.0
        forwards = 0.0
        fam = reg.get("pio_router_retry_elsewhere_total")
        if fam is not None:
            retries = sum(c.value for _, c in fam.series())
        fam = reg.get("pio_router_forwards_total")
        if fam is not None:
            forwards = sum(c.value for _, c in fam.series())
        out = {
            "fleet_replicas": n_replicas,
            "fleet_direct_p50_ms": round(direct[len(direct) // 2], 3),
            "fleet_direct_p99_ms": round(direct[int(len(direct) * 0.99)], 3),
            "fleet_router_p50_ms": round(routed[len(routed) // 2], 3),
            "fleet_router_p99_ms": round(routed[int(len(routed) * 0.99)], 3),
            "fleet_router_overhead_ms": round(
                routed[len(routed) // 2] - direct[len(direct) // 2], 3
            ),
            "fleet_retry_elsewhere_rate": round(
                retries / forwards if forwards else 0.0, 6
            ),
        }
        log(
            f"# fleet replicas={n_replicas} "
            f"direct p50={out['fleet_direct_p50_ms']:.2f}ms "
            f"router p50={out['fleet_router_p50_ms']:.2f}ms "
            f"p99={out['fleet_router_p99_ms']:.2f}ms "
            f"overhead={out['fleet_router_overhead_ms']:.2f}ms "
            f"retry_elsewhere={out['fleet_retry_elsewhere_rate']:.4f}"
        )
        return out
    finally:
        if router is not None:
            router.shutdown()
        if fleet is not None:
            fleet.stop()
        for srv in procs:
            try:
                if srv.poll() is None:
                    srv.communicate(input="\n", timeout=10)
            except Exception:
                srv.kill()
        try:
            os.unlink(blob_path)
        except OSError:
            pass


#: the scripted day `bench.py --fleet N --day` replays: fixed script +
#: fixed seed so fleet_day_* numbers are comparable release over release
#: (the gate refuses to compare runs whose scenario echo differs)
_DAY_SCENARIO = {
    "name": "bench-mini-day",
    "seed": 7,
    "num_entities": 12,
    "num_items": 10,
    "max_inflight": 32,
    "phases": [
        {"name": "warm", "duration_s": 6, "qps": 8, "read_frac": 1.0,
         "p99_ms": 5000},
        {"name": "peak", "duration_s": 12, "qps": 20, "read_frac": 0.85,
         "p99_ms": 5000},
        {"name": "cool", "duration_s": 6, "qps": 8, "read_frac": 1.0,
         "p99_ms": 5000},
    ],
    "actions": [
        {"at_s": 9, "kind": "kill_replica"},
        {"at_s": 14, "kind": "canary_flip"},
    ],
    "slo": {"autoscaler_tolerance": 2},
}


def bench_fleet_day_section(n_replicas: int):
    """`python bench.py --fleet N --day`: the production-day section.

    Replays the fixed ``_DAY_SCENARIO`` through the real multi-replica
    topology (``pio day``) in a throwaway PIO_HOME — subprocess-isolated
    like the sharded section, cpu-pinned so the replicas never fight this
    process for the device — and distills the report into the schema-v8
    ``fleet_day_*`` gate metrics plus the verdict booleans as
    diagnostics."""
    import hashlib
    import shutil
    import subprocess
    import tempfile

    day_home = tempfile.mkdtemp(prefix="pio-bench-day-")
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, PIO_HOME=day_home, JAX_PLATFORMS="cpu")
    scenario_path = os.path.join(day_home, "scenario.json")
    report_path = os.path.join(day_home, "report.json")
    with open(scenario_path, "w") as f:
        json.dump(_DAY_SCENARIO, f)
    try:
        seeded = subprocess.run(
            [
                sys.executable,
                "-c",
                "import sys; from predictionio_tpu.replay.day import "
                "seed_demo_home; seed_demo_home(sys.argv[1])",
                day_home,
            ],
            env=env, cwd=repo, capture_output=True, text=True, timeout=600,
        )
        if seeded.returncode != 0:
            raise RuntimeError(
                f"day seeding failed: {seeded.stderr[-800:]}"
            )
        proc = subprocess.run(
            [
                sys.executable, "-m", "predictionio_tpu.tools.cli", "day",
                "--scenario", f"@{scenario_path}",
                "--replicas", str(n_replicas),
                "--seed", str(_DAY_SCENARIO["seed"]),
                "--report", report_path,
            ],
            env=env, cwd=repo, capture_output=True, text=True, timeout=900,
        )
        if not os.path.exists(report_path):
            raise RuntimeError(
                f"pio day produced no report (exit {proc.returncode}): "
                f"{proc.stderr[-800:] or proc.stdout[-800:]}"
            )
        with open(report_path) as f:
            report = json.load(f)
        verdict = report["verdict"]
        rows = verdict.get("phases", [])
        p99s = [
            r.get("telemetry_p99_ms") or r.get("p99_ms")
            for r in rows
            if (r.get("telemetry_p99_ms") or r.get("p99_ms")) is not None
        ]
        scheduled = sum(int(r.get("scheduled", 0)) for r in rows)
        answered = sum(int(r.get("answered", 0)) for r in rows)
        shed = sum(float(r.get("shed", 0.0) or 0.0) for r in rows)
        retry = sum(
            float(r.get("retry_elsewhere_rate", 0.0) or 0.0)
            * int(r.get("answered", 0))
            for r in rows
        )
        device_s = sum(
            float(r.get("device_s", 0.0) or 0.0)
            for r in rows
            if r.get("device_s") is not None
        )
        # config echo: name + content hash; two runs only compare when the
        # scripted day was byte-identical
        digest = hashlib.sha256(
            json.dumps(_DAY_SCENARIO, sort_keys=True).encode()
        ).hexdigest()[:12]
        out = {
            "fleet_day_scenario": f"{_DAY_SCENARIO['name']}@{digest}",
            "fleet_day_p99_ms": round(max(p99s), 3) if p99s else None,
            "fleet_day_shed_rate": round(shed / scheduled, 6)
            if scheduled else 0.0,
            "fleet_day_retry_rate": round(retry / answered, 6)
            if answered else 0.0,
            "fleet_day_device_s": round(device_s, 6),
            "fleet_day_verdict_pass": bool(verdict.get("pass")),
            "fleet_day": {
                "exit_code": proc.returncode,
                "clauses": {
                    c["clause"]: bool(c["passed"])
                    for c in verdict.get("clauses", [])
                },
                "requests": verdict.get("requests"),
            },
        }
        out.update(bench_tenant_day_metrics(env, repo))
        log(
            f"# fleet_day scenario={out['fleet_day_scenario']} "
            f"verdict={'PASS' if out['fleet_day_verdict_pass'] else 'FAIL'} "
            f"p99={out['fleet_day_p99_ms']}ms "
            f"shed_rate={out['fleet_day_shed_rate']:.4f} "
            f"retry_rate={out['fleet_day_retry_rate']:.4f} "
            f"device_s={out['fleet_day_device_s']:.3f}"
        )
        return out
    finally:
        shutil.rmtree(day_home, ignore_errors=True)


def bench_tenant_day_metrics(env, repo):
    """The two-tenant isolation half of the fleet_day section (schema v9):
    replay the in-process quota-flood day (``replay.tenant_day``) in a
    subprocess — the victim tenant's availability and tail latency under a
    neighbor's 10× flood are the gate metrics; the isolation verdict rides
    along as a diagnostic."""
    import subprocess
    import tempfile

    report_path = os.path.join(
        tempfile.mkdtemp(prefix="pio-bench-tenant-day-"), "report.json"
    )
    code = (
        "import sys; from predictionio_tpu.replay.tenant_day import "
        "run_tenant_day; rc, _ = run_tenant_day(report_path=sys.argv[1], "
        "out=lambda s: None); sys.exit(rc)"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code, report_path],
        env=env, cwd=repo, capture_output=True, text=True, timeout=300,
    )
    try:
        with open(report_path) as f:
            report = json.load(f)
    except (OSError, ValueError):
        log(
            f"# fleet_day tenant-day run failed (exit {proc.returncode}): "
            f"{proc.stderr[-400:]}"
        )
        return {"fleet_day_tenant_isolation_pass": False}
    clauses = {
        c["clause"]: bool(c["passed"])
        for c in report["verdict"].get("clauses", [])
    }
    victims = [
        r for r in report.get("tenants", []) if not r.get("quota_shed")
    ]
    victim_avail = min(
        (r.get("availability") for r in victims if r.get("availability") is not None),
        default=None,
    )
    victim_p99 = max(
        (r.get("p99_ms") for r in victims if r.get("p99_ms") is not None),
        default=None,
    )
    out = {
        "fleet_day_tenant_isolation_pass": clauses.get(
            "tenant_isolation", False
        ),
        "fleet_day_tenant_victim_availability": victim_avail,
        "fleet_day_tenant_victim_p99_ms": victim_p99,
        "fleet_day_tenants": report.get("tenants"),
    }
    log(
        f"# fleet_day tenant isolation="
        f"{'PASS' if out['fleet_day_tenant_isolation_pass'] else 'FAIL'} "
        f"victim_availability={victim_avail} victim_p99={victim_p99}ms"
    )
    return out


def serving_p50_concurrent(model, num_users, clients=32, per_client=40):
    """p50/p99 across 32 concurrent keep-alive clients hitting a real
    asyncio server + micro-batched /queries.json route.  Server AND load
    generator each run in their own fresh process; the MEDIAN round by p99
    of 3 is reported (single shared core — any one round can be eaten by
    unrelated scheduling; median is robust without cherry-picking)."""
    import subprocess
    import tempfile

    with tempfile.NamedTemporaryFile(suffix=".npz", delete=False) as f:
        np.savez(
            f,
            U=np.asarray(model.user_factors, np.float32),
            V=np.asarray(model.item_factors, np.float32),
        )
        blob_path = f.name
    srv = subprocess.Popen(
        [sys.executable, "-c", _SERVER_SCRIPT, blob_path],
        stdin=subprocess.PIPE,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    try:
        # handshake with timeout; a dead child must surface its traceback
        import threading as _threading

        port_line: list = []
        reader = _threading.Thread(
            target=lambda: port_line.append(srv.stdout.readline()), daemon=True
        )
        reader.start()
        reader.join(timeout=120)
        if not port_line or not port_line[0].strip():
            srv.kill()
            _, err = srv.communicate(timeout=10)
            raise RuntimeError(f"bench server failed to start: {err[-1000:]}")
        port = int(port_line[0])
        # spawn the load generator (all 3 rounds in one process) BEFORE
        # deprioritizing this process, so it never inherits a degraded
        # priority — avoids both the unprivileged-renice trap and
        # preexec_fn's fork-in-threads hazard
        n_rounds = 3
        client = subprocess.Popen(
            [
                sys.executable,
                "-m",
                "predictionio_tpu.replay.workload",
                str(port),
                str(clients),
                str(per_client),
                str(num_users),
                str(n_rounds),
            ],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        # deprioritize THIS process while the rounds run: its runtime's
        # background threads keep burning cycles even though the parent just
        # waits, and on a shared core they tax the server+client.  Only
        # attempted when a probe proves the priority can be RESTORED
        # (lowering nice needs privilege).
        prio0 = None
        try:
            cur = os.getpriority(os.PRIO_PROCESS, 0)
            os.setpriority(os.PRIO_PROCESS, 0, cur + 1)
            os.setpriority(os.PRIO_PROCESS, 0, cur)  # probe restore
            os.setpriority(os.PRIO_PROCESS, 0, 19)
            prio0 = cur
        except (OSError, AttributeError):
            pass
        try:
            out, err = client.communicate(timeout=600)
        finally:
            if prio0 is not None:
                try:
                    os.setpriority(os.PRIO_PROCESS, 0, prio0)
                except OSError:
                    pass
        if client.returncode != 0:
            raise RuntimeError(f"bench client failed: {err[-500:]}")
        rounds = [
            json.loads(line) for line in out.strip().splitlines()[-n_rounds:]
        ]
        log(
            "# concurrent rounds: "
            + " ".join(
                f"p50={r['p50_ms']:.2f}/p99={r['p99_ms']:.2f}" for r in rounds
            )
        )
        # MEDIAN round by p99: robust to one scheduler-noise round without
        # cherry-picking the best (single shared core)
        med = sorted(rounds, key=lambda r: r["p99_ms"])[len(rounds) // 2]
        hist: dict = {}
        hotpath: dict = {}
        try:
            # communicate(input=...) writes the stop line AND closes stdin;
            # closing stdin first makes communicate() raise ValueError on
            # the already-closed pipe (and silently lose stderr)
            _, err = srv.communicate(input="\n", timeout=10)
            for line in err.splitlines():
                if line.startswith("waves "):
                    log(f"# microbatch {line}")
                elif line.startswith("metrics "):
                    hist = json.loads(line[len("metrics "):])
                    log("# serving_histograms "
                        + json.dumps(hist, sort_keys=True))
                elif line.startswith("hotpath "):
                    hotpath = json.loads(line[len("hotpath "):])
                    from predictionio_tpu.obs.hotpath import (
                        render_hotpath_text,
                    )

                    for ln in render_hotpath_text(hotpath).splitlines():
                        log("# serving_hotpath " + ln)
                elif line.startswith("alerts "):
                    # the default alert pack's verdict on this very run —
                    # a firing rule here means the thresholds would have
                    # paged on the bench load (informational, ungated)
                    log("# serving_alerts " + line[len("alerts "):].strip())
        except Exception:
            srv.kill()
        return med["p50_ms"], med["p99_ms"], hist, hotpath
    finally:
        if srv.poll() is None:
            srv.kill()
        os.unlink(blob_path)


def _sharded_worker(n_dev: int, scale: float) -> dict:
    """The sharded scaling section's body: trains ALS on the N-device data
    mesh (sharded factor state), binds the factor tables model-parallel
    through a ShardPlan, serves waves through the sharded top-k kernel, and
    returns timings + per-device bytes.  Runs in whichever process owns the
    N devices (see :func:`bench_sharded_section`)."""
    import jax

    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.recommendation.engine import (
        ALSAlgorithm, ALSAlgorithmParams, ALSModel, Query,
    )
    from predictionio_tpu.obs.disttrace import set_process_name
    from predictionio_tpu.obs.logging import (
        reset_request_context,
        set_request_context,
    )
    from predictionio_tpu.obs.timeline import collect_trace
    from predictionio_tpu.ops.als import ALSParams, train_als
    from predictionio_tpu.parallel.mesh import MeshConfig, make_mesh
    from predictionio_tpu.parallel.placement import LAST_KERNEL_SHAPES

    assert len(jax.devices()) >= n_dev, (len(jax.devices()), n_dev)
    nu = max(int(20000 * scale), 512)
    ni = max(int(4000 * scale), 256)
    nnz = max(int(400000 * scale), 20000)
    rng = np.random.default_rng(7)
    ui = rng.integers(0, nu, nnz).astype(np.int32)
    ii = rng.integers(0, ni, nnz).astype(np.int32)
    r = np.clip(rng.normal(3.5, 1.0, nnz), 0.5, 5.0).astype(np.float32)
    p = ALSParams(rank=16, num_iterations=10, chunk_size=1 << 14)
    mesh = make_mesh(
        MeshConfig(axes={"data": n_dev}), devices=jax.devices()[:n_dev]
    )

    # opt into the per-iteration training track and bind a trace id for it
    # — the step-timeline fragments folded into the result below
    set_process_name("bench-sharded")
    os.environ["PIO_TRAIN_STEP_TIMELINE"] = "1"
    ctx_tokens = set_request_context("benchsteps", "benchsteps")
    try:
        t0 = time.perf_counter()
        state = train_als(ui, ii, r, nu, ni, p, mesh=mesh)
        jax.block_until_ready(state.user_factors)
        train_s = time.perf_counter() - t0
        # the training step timeline: every als.train_step[i] fragment the
        # traced mesh train emitted, rendered as Chrome trace-event JSON
        # (Perfetto-loadable)
        try:
            tl = collect_trace("benchsteps", include_local=True)
            step_timeline = {
                "steps": sum(1 for x in tl.nodes.values()
                             if x.name.startswith("als.train_step")),
                "chrome_trace": tl.to_chrome_trace(),
            }
        except Exception as e:
            step_timeline = {"steps": 0, "error": str(e)}
    finally:
        reset_request_context(ctx_tokens)
        os.environ.pop("PIO_TRAIN_STEP_TIMELINE", None)

    # bind the tables model-parallel and serve sharded waves
    uv = BiMap.from_keys(np.array([f"u{i}" for i in range(nu)]))
    iv = BiMap.from_keys(np.array([f"i{i}" for i in range(ni)]))
    algo = ALSAlgorithm(ALSAlgorithmParams(rank=16, shard_serving=True))
    blob = algo.make_persistent_model(
        None, ALSModel(np.asarray(state.user_factors),
                       np.asarray(state.item_factors), uv, iv))
    model = algo.load_persistent_model(None, blob)
    if model.shards is not None and len(jax.devices()) > n_dev:
        # the host exposes MORE devices than --devices N (pre-set
        # virtual-device flag, real multi-chip slice): load binds the whole
        # mesh, so rebind onto exactly the first N or every sharded_*
        # metric is mislabeled
        from predictionio_tpu.parallel.placement import ShardPlan, bind_shards
        model.shards = bind_shards(
            ShardPlan.from_dict(blob["shard_plan"]),
            {"user_factors": blob["user_factors"],
             "item_factors": blob["item_factors"]},
            devices=jax.devices()[:n_dev],
        )
    attr = model.shards.attribution() if model.shards is not None else {}

    queries = [(q, Query(user=f"u{q % nu}", num=10)) for q in range(32)]
    algo.batch_predict(model, queries)  # compile
    lats = []
    for _ in range(30):
        t0 = time.perf_counter()
        algo.batch_predict(model, queries)
        lats.append((time.perf_counter() - t0) * 1000)
    lats.sort()
    return {
        "devices": n_dev,
        "platform": jax.devices()[0].platform,
        "nnz": nnz, "num_users": nu, "num_items": ni,
        "train_s": round(train_s, 3),
        "wave32_p50_ms": round(lats[len(lats) // 2], 3),
        "wave32_p99_ms": round(lats[int(len(lats) * 0.99)], 3),
        "per_device_factor_bytes": {
            d: e["bytes"] for d, e in sorted(attr.items())},
        "kernel_shapes": LAST_KERNEL_SHAPES.get("als.sharded_topk"),
        "step_timeline": step_timeline,
    }


def bench_sharded_section(n_devices: int, scale: float) -> dict:
    """`python bench.py --devices N`: the N-device scaling section.

    A device belongs to one process, and this one initialized its backend
    in ``main()``: on an accelerator the section runs HERE, on N of the
    devices this process holds, and fewer than N is an error (the section
    lands in ``failed_sections``) — never a CPU figure under a chip run's
    ``sharded_*`` keys.  Only a parent that is itself on the CPU (the
    ``PIO_BENCH_SCALE`` host rehearsal) measures on an N-virtual-device
    CPU mesh, in a child interpreter because the device-count flag only
    applies at backend init.  The result's ``platform`` is therefore
    always the parent's.
    """
    import subprocess

    import jax

    devices = jax.devices()
    if devices[0].platform != "cpu":
        if len(devices) < n_devices:
            raise RuntimeError(
                f"--devices {n_devices}: this process holds "
                f"{len(devices)} {devices[0].platform} device(s)"
            )
        return _sharded_worker(n_devices, scale)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    proc = subprocess.run(
        [
            sys.executable, "-c",
            "import json, sys, bench; print(json.dumps("
            "bench._sharded_worker(int(sys.argv[1]), float(sys.argv[2]))))",
            str(n_devices), str(scale),
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=900,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0:
        # XLA background threads occasionally abort at interpreter exit
        # ("terminate called without an active exception") AFTER the worker
        # printed its result line — the measurements are complete, only the
        # teardown crashed, so accept a fully-emitted result
        try:
            res = json.loads(lines[-1]) if lines else None
        except ValueError:
            res = None
        if isinstance(res, dict) and "wave32_p99_ms" in res:
            return res
        raise RuntimeError(
            f"sharded section worker failed: {proc.stderr[-1000:]}"
        )
    return json.loads(lines[-1])


def main() -> int:
    import types

    import jax

    from predictionio_tpu.utils.runtime import configure_compile_cache

    # persistent compile cache: the second bench run on a box skips the
    # compile cost for unchanged programs
    configure_compile_cache()

    from predictionio_tpu.ops.als import ALSParams, train_als
    from predictionio_tpu.parallel.mesh import MeshConfig, make_mesh

    # Sectioned run: one failed model path (an HBM OOM, a crashed worker)
    # must cost THAT section's numbers, not the whole round's.  Every
    # section records into `metrics` as soon as a figure exists; the final
    # JSON line always prints, listing whatever failed — and the process
    # then exits non-zero.  PIO_BENCH_FAIL_SECTION=<name> injects a failure
    # at section entry so the degradation path itself is testable.
    metrics: dict = {}
    failed: list = []
    C = types.SimpleNamespace()

    def run_section(name: str, fn) -> bool:
        try:
            if os.environ.get("PIO_BENCH_FAIL_SECTION") == name:
                raise RuntimeError(
                    f"injected failure (PIO_BENCH_FAIL_SECTION={name})"
                )
            fn()
            return True
        except Exception as e:  # noqa: BLE001 — a bench section may die
            failed.append(name)
            msg = str(e).split("\n", 1)[0][:300]
            log(f"# SECTION {name} FAILED ({type(e).__name__}): {msg}")
            return False

    platform = jax.devices()[0].platform
    scale_env = os.environ.get("PIO_BENCH_SCALE")
    if platform != "tpu" and scale_env is None:
        # a measurement path that finds no chip fails; it does not shrink
        # itself onto the CPU.  A host rehearsal names its scale.
        raise SystemExit(
            f"bench.py: no TPU (jax platform is {platform!r}); set "
            "PIO_BENCH_SCALE explicitly to rehearse on the host"
        )
    scale = float(scale_env or "1.0")

    nnz = int(20_000_000 * scale)
    num_users = max(int(138_493 * scale), 64)
    num_items = max(int(26_744 * scale), 48)
    budget_s = 60.0 * max(scale, 1e-6)

    def sec_data():
        t0 = time.perf_counter()
        user_idx, item_idx, rating = make_movielens_like(
            nnz, num_users, num_items
        )
        (C.tr_u, C.tr_i, C.tr_r), (C.te_u, C.te_i) = holdout_split(
            user_idx, item_idx, rating, np.random.default_rng(7)
        )
        log(
            f"# platform={platform} devices={len(jax.devices())} nnz={nnz} "
            f"train={len(C.tr_r)} test={len(C.te_u)} "
            f"gen={time.perf_counter()-t0:.1f}s"
        )

    n_dev = len(jax.devices())
    C.mesh = make_mesh(MeshConfig(axes={"data": n_dev})) if n_dev > 1 else None
    C.params = ALSParams(rank=10, reg=0.01, seed=3)

    def sec_als_train():
        mesh, params = C.mesh, C.params
        tr_u, tr_i, tr_r = C.tr_u, C.tr_i, C.tr_r

        # Warmup: compile + one epoch (epoch cost tracked on stderr).
        t0 = time.perf_counter()
        device_sync(
            train_als(
                tr_u, tr_i, tr_r, num_users, num_items,
                params=ALSParams(rank=10, reg=0.01, seed=3, num_iterations=1),
                mesh=mesh,
            ).user_factors
        )
        warm_s = time.perf_counter() - t0

        # COLD train: host staging (sort + block-pad + device upload, the
        # Spark partition-and-cache role) + the compiled 20-iteration
        # program.  The staging cache is cleared first so this is a true
        # from-raw-COO number.
        from predictionio_tpu.ops import als as _als_mod

        _als_mod._STAGE_CACHE.clear()
        t0 = time.perf_counter()
        state = train_als(
            tr_u, tr_i, tr_r, num_users, num_items, params=params, mesh=mesh
        )
        device_sync(state.user_factors)
        C.train_cold_s = time.perf_counter() - t0
        metrics["train_cold_s"] = round(C.train_cold_s, 3)

        # WARM trains, MEDIAN of 3 with all runs + spread reported: staged
        # data reused (retrains/sweeps on the same ratings, the common
        # case), robust to one co-tenant-noise run without best-of-N
        # cherry-picking
        train_runs = []
        for _ in range(3):
            t0 = time.perf_counter()
            state = train_als(
                tr_u, tr_i, tr_r, num_users, num_items, params=params,
                mesh=mesh,
            )
            device_sync(state.user_factors)
            train_runs.append(time.perf_counter() - t0)
        C.train_s = sorted(train_runs)[1]
        train_spread = max(train_runs) - min(train_runs)
        assert np.isfinite(np.asarray(state.user_factors)).all()
        C.state = state
        metrics["train_runs_s"] = [round(t, 3) for t in train_runs]
        log(
            f"# warmup(compile+1ep)={warm_s:.2f}s train(20 iter) "
            f"cold={C.train_cold_s:.2f}s warm median={C.train_s:.2f}s (runs: "
            + ", ".join(f"{t:.2f}" for t in train_runs)
            + f", spread={train_spread:.2f}s; cold = staging+train from raw "
            f"COO, warm = staged-data retrain)"
        )

        # Roofline accounting for the pallas train path (single-device
        # TPU): HBM bytes and MXU flops per iteration from the actual
        # staged plan vs the platform peak table, so "where the time goes"
        # is a measured claim, not a vibe.  The arithmetic lives in
        # obs/device.py (als_plan_roofline) — the serving process reports
        # the same numbers live at /efficiency.json.
        from predictionio_tpu.obs.device import (
            als_plan_roofline,
            device_peaks,
            utilization_frac,
        )
        from predictionio_tpu.ops.als import LAST_PLAN_INFO

        per_iter = (
            als_plan_roofline(LAST_PLAN_INFO) if platform == "tpu" else None
        )
        if per_iter is not None:
            pi = LAST_PLAN_INFO
            gb = per_iter["gb_per_iter"]
            fl = per_iter["tflop_eq_per_iter"]
            peaks = device_peaks()
            it_s = C.train_s / C.params.num_iterations
            metrics["roofline_gb_per_iter"] = round(gb, 2)
            metrics["roofline_achieved_gb_s"] = round(gb / it_s, 1)
            metrics["roofline_tflop_eq_per_iter"] = round(fl, 3)
            metrics["roofline_achieved_tflop_s"] = round(fl / it_s, 2)
            metrics["roofline_hbm_utilization_frac"] = round(
                utilization_frac(gb / it_s, peaks.hbm_gbps), 4
            )
            metrics["roofline_mxu_utilization_frac"] = round(
                utilization_frac(fl / it_s, peaks.tflops), 4
            )
            metrics["als_pallas_mode"] = pi.get("mode", "?")
            if "stage_s" in pi:
                # host staging share of the cold number (sort + block-pad
                # + narrow-encoded upload submission)
                metrics["als_stage_s"] = pi["stage_s"]
            log(
                f"# roofline/iter: ~{gb:.1f} GB moved -> {gb / it_s:.0f} GB/s "
                f"achieved (HBM peak ~{peaks.hbm_gbps:.0f}); one-hot MXU "
                f"{fl:.2f} TFLOP(eq) -> {fl / it_s:.1f} TFLOP/s (peak "
                f"~{peaks.tflops:.0f}); iter={it_s * 1000:.0f} ms; "
                f"mode={pi.get('mode')}"
            )

    def sec_als_rank32():
        mesh = C.mesh
        tr_u, tr_i, tr_r = C.tr_u, C.tr_i, C.tr_r
        # rank=32 variant: the MXU actually matters at this width
        # (row_width(32)=1152 lanes, 9x the rank-10 flat row)
        rank32_iters = 5
        p32 = ALSParams(rank=32, reg=0.01, seed=3, num_iterations=1)
        device_sync(
            train_als(tr_u, tr_i, tr_r, num_users, num_items, params=p32,
                      mesh=mesh).user_factors
        )
        t0 = time.perf_counter()
        s32 = train_als(
            tr_u, tr_i, tr_r, num_users, num_items,
            params=ALSParams(rank=32, reg=0.01, seed=3,
                             num_iterations=rank32_iters),
            mesh=mesh,
        )
        device_sync(s32.user_factors)
        rank32_iter_s = (time.perf_counter() - t0) / rank32_iters
        assert np.isfinite(np.asarray(s32.user_factors)).all()
        metrics["als_rank32_iter_s"] = round(rank32_iter_s, 3)
        log(f"# rank32 iter={rank32_iter_s:.2f}s ({rank32_iters} iters timed)")

    def sec_als_uniform():
        mesh = C.mesh
        tr_u, tr_r = C.tr_u, C.tr_r
        # Distribution-robustness probe: the same kernel on uniformly-
        # sampled data of identical size.  The pallas one-hot accumulation
        # processes a fixed tile count regardless of index skew; this line
        # proves it on every run.  Two-call diff cancels the one-time host
        # prep (sort+pad) and any compile from the per-epoch figure.
        rng_u = np.random.default_rng(5)
        uu = rng_u.integers(0, num_users, len(tr_u)).astype(np.int64)
        ui = rng_u.integers(0, num_items, len(tr_u)).astype(np.int64)

        def _timed_uniform(iters):
            t0 = time.perf_counter()
            device_sync(
                train_als(
                    uu, ui, tr_r, num_users, num_items,
                    params=ALSParams(rank=10, reg=0.01, seed=3,
                                     num_iterations=iters),
                    mesh=mesh,
                ).user_factors
            )
            return time.perf_counter() - t0

        _timed_uniform(1)  # compile for these shapes
        t1 = _timed_uniform(1)
        t5 = _timed_uniform(5)
        ep_uniform = max(t5 - t1, 0.0) / 4
        skew = (
            f"{C.train_s / C.params.num_iterations:.2f}s"
            if hasattr(C, "train_s") else "n/a"
        )
        log(
            f"# epoch_time skewed={skew} uniform={ep_uniform:.2f}s "
            f"(distribution-robustness; prep+compile excluded via "
            f"two-call diff)"
        )

    def sec_als_quality():
        mesh = C.mesh
        tr_u, tr_i, tr_r = C.tr_u, C.tr_i, C.tr_r
        # Quality probe: top-N ranking MAP@10.  Explicit rating-prediction
        # ALS is a poor top-N ranker (well known); the ranking-quality
        # number tracked by BASELINE uses implicit-feedback ALS on binary
        # positives (rating >= 4, the reference templates' train-with-
        # rate-event thresholding), vs a popularity baseline for context.
        # Untimed — the timed headline above keeps reference hyperparams.
        t0 = time.perf_counter()
        pos_mask = tr_r >= 4.0
        C.pos_mask = pos_mask
        imp = train_als(
            tr_u[pos_mask], tr_i[pos_mask],
            np.ones(int(pos_mask.sum()), np.float32),
            num_users, num_items,
            params=ALSParams(
                rank=10, num_iterations=20, reg=0.01, seed=3,
                implicit_prefs=True, alpha=2.0, chunk_size=1 << 18,
            ),
            mesh=mesh,
        )
        device_sync(imp.user_factors)
        imp_train_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        C.map10, C.prec10, n_eval = compute_ranking_metrics(
            np.asarray(imp.user_factors), np.asarray(imp.item_factors),
            tr_u, tr_i, C.te_u, C.te_i,
        )
        pop = np.bincount(tr_i, minlength=num_items).astype(np.float32)
        C.map_pop, C.prec_pop, _ = compute_ranking_metrics(
            np.ones((num_users, 1), np.float32),
            pop[:, None],
            tr_u, tr_i, C.te_u, C.te_i,
            max_eval_users=4000,
        )
        metrics["map_at_10"] = round(C.map10, 4)
        metrics["precision_at_10"] = round(C.prec10, 4)
        metrics["map_at_10_popularity_baseline"] = round(C.map_pop, 4)
        log(
            f"# MAP@10={C.map10:.4f} Precision@10={C.prec10:.4f} "
            f"eval_users={n_eval} popularity-baseline MAP@10={C.map_pop:.4f} "
            f"P@10={C.prec_pop:.4f} implicit_train={imp_train_s:.1f}s "
            f"metrics={time.perf_counter()-t0:.1f}s"
        )

    def sec_ncf():
        mesh = C.mesh
        tr_u, tr_i, tr_r = C.tr_u, C.tr_i, C.tr_r
        # NCF flagship: epochs/s on the on-device pipeline (one XLA
        # dispatch per epoch: device-side shuffle + in-step negative
        # sampling + lax.scan), ranking quality on the same held-out split
        # as the ALS number, and serving p50 through the NCF template's
        # predict path.
        from predictionio_tpu.ops.ncf import NCFParams, train_ncf

        pos_mask = getattr(C, "pos_mask", None)
        if pos_mask is None:
            pos_mask = tr_r >= 4.0
        ncf_u = tr_u[pos_mask].astype(np.int32)
        ncf_i = tr_i[pos_mask].astype(np.int32)
        # Config notes from the round-3/4/5 sweeps on this generator:
        # - sampled-negative SGD (bpr/softmax, K in {1,8,64}, ±bias,
        #   ±neg_power) plateaus at MAP@10 ~0.0225 vs implicit-ALS 0.0307
        #   on the SAME binary positives: sampled objectives only
        #   approximate the whole-catalog problem.
        # - round 5 added whole-catalog heads on the pure-GMF tower
        #   (mlp_layers=()): full_softmax peaks ~0.027 from scratch (2
        #   epochs, then overfits), wals (the iALS objective by SGD)
        #   reaches 0.0293 at d=10.
        # - the shipped flagship config is the NCF paper's §3.4.1
        #   pretraining recipe with implicit ALS as the GMF pretrainer
        #   (exact alternating solves on the pallas path, seconds) + 1
        #   epoch of low-lr full_softmax fine-tune: MAP@10 0.0307 with
        #   BETTER Precision@10 than pure ALS (0.0739 vs 0.0732).
        ncf_cfg = dict(
            embed_dim=10, mlp_layers=(), loss="full_softmax",
            learning_rate=1e-4, batch_size=8192, item_bias=True, seed=3,
        )
        t0 = time.perf_counter()
        als_pre = train_als(
            ncf_u.astype(np.int64), ncf_i.astype(np.int64),
            np.ones(len(ncf_u), np.float32), num_users, num_items,
            params=ALSParams(rank=10, num_iterations=20, reg=0.01, seed=3,
                             implicit_prefs=True, alpha=2.0),
            mesh=mesh,
        )
        device_sync(als_pre.user_factors)
        ncf_pretrain_s = time.perf_counter() - t0
        ncf_init = {
            "user_emb": np.asarray(als_pre.user_factors),
            "item_emb": np.asarray(als_pre.item_factors),
        }
        # warmup compile of the fine-tune epoch
        t0 = time.perf_counter()
        device_sync(
            train_ncf(ncf_u, ncf_i, num_users, num_items,
                      params=NCFParams(num_epochs=1, **ncf_cfg),
                      mesh=mesh, initial_params=ncf_init).params["out_b"]
        )
        ncf_warm_s = time.perf_counter() - t0
        ncf_epochs = 1
        t0 = time.perf_counter()
        ncf_state = train_ncf(
            ncf_u, ncf_i, num_users, num_items,
            params=NCFParams(num_epochs=ncf_epochs, **ncf_cfg), mesh=mesh,
            initial_params=ncf_init)
        device_sync(ncf_state.params["out_b"])
        C.ncf_state = ncf_state
        ncf_eps = ncf_epochs / (time.perf_counter() - t0)
        metrics["ncf_epochs_per_s"] = round(ncf_eps, 4)
        metrics["ncf_pretrain_s"] = round(ncf_pretrain_s, 1)
        log(
            f"# ncf als-pretrain={ncf_pretrain_s:.1f}s "
            f"warmup={ncf_warm_s:.1f}s epochs_per_s={ncf_eps:.3f} "
            f"(positives={len(ncf_u)} users={num_users} items={num_items} "
            f"d=10 pure-GMF full_softmax fine-tune epochs={ncf_epochs})"
        )
        t0 = time.perf_counter()
        ncf_map10, ncf_prec10, ncf_n_eval = ncf_ranking_metrics(
            ncf_state.params, tr_u, tr_i, C.te_u, C.te_i, num_items
        )
        metrics["ncf_map_at_10"] = round(ncf_map10, 4)
        metrics["ncf_precision_at_10"] = round(ncf_prec10, 4)
        als_q = (
            f"{C.map10:.4f}/{C.prec10:.4f}" if hasattr(C, "map10") else "n/a"
        )
        pop_q = (
            f"{C.map_pop:.4f}/{C.prec_pop:.4f}"
            if hasattr(C, "map_pop") else "n/a"
        )
        log(
            f"# ncf MAP@10={ncf_map10:.4f} P@10={ncf_prec10:.4f} "
            f"eval_users={ncf_n_eval} (vs als {als_q}, popularity {pop_q}; "
            f"metrics={time.perf_counter() - t0:.1f}s)"
        )

    def sec_ncf_serving():
        from predictionio_tpu.models.ncf.engine import _score_topk_batch

        ncf_state = C.ncf_state
        ncf_model = build_ncf_model(ncf_state, num_users, num_items)
        rtt_ms = dispatch_rtt_ms()
        metrics["dispatch_rtt_ms"] = round(rtt_ms, 3)
        ncf_p50 = ncf_serving_p50(ncf_model, num_users, n=60)
        ncf_dev_ms = ncf_solo_device_ms(ncf_state.params, num_items,
                                        num_users)
        metrics["ncf_serving_p50_ms"] = round(ncf_p50, 3)
        metrics["ncf_solo_device_ms"] = round(ncf_dev_ms, 3)
        # solo e2e wall INCLUDING dispatch through the pipelined async path
        solo_e2e = ncf_solo_e2e_p50(ncf_model, num_users)
        metrics["serving_solo_e2e_p50_ms"] = round(solo_e2e, 3)
        log(
            f"# serving_solo_e2e_p50={solo_e2e:.3f}ms (pipelined async "
            f"dispatch, depth 4; vs dispatch RTT p50 above)"
        )
        # device-level wave cost: 50 DISTINCT 32-query micro-batch waves
        # dispatched back-to-back with one final sync — pipelining
        # amortizes the dispatch round trip out of the measurement, so the
        # per-wave figure is the device's cost per wave of 32 queries
        import jax.numpy as _jnp

        waves = [
            _jnp.asarray((np.arange(32) * 131 + w * 37) % num_users,
                         _jnp.int32)
            for w in range(51)
        ]
        device_sync(
            _score_topk_batch(ncf_state.params, waves[0], num_items, K)[0]
        )
        t0 = time.perf_counter()
        outs = [
            _score_topk_batch(ncf_state.params, w, num_items, K)
            for w in waves[1:]
        ]
        # in-order single-device queue: the LAST wave being ready proves
        # all 50 executed
        device_sync(outs[-1][0])
        ncf_wave32_ms = (time.perf_counter() - t0) / 50 * 1000
        metrics["ncf_wave32_pipelined_ms"] = round(ncf_wave32_ms, 3)
        # serving-section utilization: XLA's own cost model for the wave
        # program vs the per-wave wall clock — how much of the chip one
        # 32-query wave actually uses (the headroom ROADMAP item 3 spends)
        from predictionio_tpu.obs.device import (
            device_peaks,
            jit_cost_analysis,
            utilization_frac,
        )

        cost = jit_cost_analysis(
            _score_topk_batch, ncf_state.params, waves[0], num_items, K
        )
        if cost is not None:
            peaks = device_peaks()
            wave_s = ncf_wave32_ms / 1000.0
            gbps = cost["bytes"] / wave_s / 1e9
            tflops = cost["flops"] / wave_s / 1e12
            metrics["ncf_wave32_achieved_gb_s"] = round(gbps, 2)
            metrics["ncf_wave32_achieved_tflop_s"] = round(tflops, 4)
            metrics["ncf_wave32_hbm_utilization_frac"] = round(
                utilization_frac(gbps, peaks.hbm_gbps), 4
            )
            metrics["ncf_wave32_mxu_utilization_frac"] = round(
                utilization_frac(tflops, peaks.tflops), 4
            )
        log(
            f"# ncf serving: solo wall p50={ncf_p50:.1f}ms; dispatch "
            f"RTT p50={rtt_ms:.1f}ms; solo DEVICE cost={ncf_dev_ms:.2f}"
            f"ms/query (pipelined, target <10ms) "
            f"wave32_pipelined={ncf_wave32_ms:.3f}ms "
            f"(~{ncf_wave32_ms / 32:.3f}ms/query batched)"
        )

    def sec_event_store():
        # event-data plane proof at benchmark scale — parallel sharded
        # bulk write, dictionary-decoded shard scan, watermarked
        # compaction with checksum parity, per-user history point reads,
        # and (at train scale) an ALS iteration trained from the scanned
        # columns (the PEventStore seam end to end).  ``--events-scale
        # 100`` runs the slow 100M-row mode instead of the train arrays.
        metrics.update(
            bench_event_store(
                C.tr_u, C.tr_i, C.tr_r, num_users, num_items,
                events_scale_m=events_scale_m,
            )
        )

    def sec_als_serving():
        model = build_als_model(C.state, num_users, num_items)
        p50_single = serving_p50_single(model, num_users)
        p50_conc, p99_conc, hist, hotpath = serving_p50_concurrent(
            model, num_users
        )
        metrics["serving_p50_ms"] = round(p50_single, 3)
        metrics["serving_p50_concurrent32_ms"] = round(p50_conc, 3)
        metrics["serving_p99_concurrent32_ms"] = round(p99_conc, 3)
        if hist:
            # decomposed serving latency: request p50/p95/p99 by
            # route/status + queue-wait vs device-time from the registry
            metrics["serving_histograms"] = hist
        if hotpath:
            # per-stage host attribution of the same run (/hotpath.json
            # shape): the ROADMAP item 3 perf arc starts from these numbers
            metrics["serving_hotpath"] = hotpath
        log(
            f"# serving_p50={p50_single:.3f}ms "
            f"serving_p50_concurrent32={p50_conc:.3f}ms "
            f"p99_concurrent32={p99_conc:.3f}ms (target <10ms)"
        )
        # repeat-entity factor-cache effectiveness: two passes over the
        # same 100 users through the engine solo path — pass 2 should be
        # ~all hits (the millions-of-users common case is repeat entities)
        from predictionio_tpu.models.recommendation.engine import (
            ALSAlgorithm,
            Query as ALSQuery,
        )
        from predictionio_tpu.parallel import device_cache

        algo = ALSAlgorithm()
        s0 = device_cache.stats()
        for _ in range(2):
            for u in range(100):
                algo.predict(model, ALSQuery(user=str(u), num=K))
        s1 = device_cache.stats()
        hits = s1["hits_total"] - s0["hits_total"]
        gets = hits + s1["misses_total"] - s0["misses_total"]
        metrics["factor_cache_hit_rate"] = round(
            hits / gets if gets else 0.0, 4
        )
        log(f"# factor_cache_hit_rate={metrics['factor_cache_hit_rate']}")

    def sec_fused_topk():
        # fused score+top-k roofline: 50 pipelined 32-query launches with
        # one dependent sync (dispatch RTT amortized out), vs the kernel's
        # analytic bytes/flops — pallas bodies are opaque to XLA
        # cost_analysis, same as the ALS train kernel
        import jax.numpy as _jnp

        from predictionio_tpu.obs.device import (
            device_peaks,
            utilization_frac,
        )
        from predictionio_tpu.ops.topk import (
            fused_topk_batch,
            fused_topk_roofline,
        )

        U = _jnp.asarray(np.asarray(C.state.user_factors))
        V = _jnp.asarray(np.asarray(C.state.item_factors))
        rank = int(V.shape[1])
        kf = 16
        waves = [
            _jnp.asarray((np.arange(32) * 131 + w * 37) % num_users,
                         _jnp.int32)
            for w in range(51)
        ]
        device_sync(fused_topk_batch(U[waves[0]], V, kf,
                                     name="bench.fused_topk"))
        t0 = time.perf_counter()
        outs = [
            fused_topk_batch(U[w], V, kf, name="bench.fused_topk")
            for w in waves[1:]
        ]
        device_sync(outs[-1])
        per_launch_s = (time.perf_counter() - t0) / 50
        rl = fused_topk_roofline(32, rank, int(V.shape[0]), kf)
        peaks = device_peaks()
        gbps = rl["bytes"] / per_launch_s / 1e9
        metrics["fused_topk_wave32_ms"] = round(per_launch_s * 1000, 3)
        metrics["fused_topk_achieved_gb_s"] = round(gbps, 2)
        metrics["fused_topk_hbm_utilization_frac"] = round(
            utilization_frac(gbps, peaks.hbm_gbps), 4
        )
        log(
            f"# fused_topk wave32={per_launch_s * 1000:.3f}ms "
            f"achieved={gbps:.1f} GB/s "
            f"({metrics['fused_topk_hbm_utilization_frac']:.1%} of HBM "
            f"peak ~{peaks.hbm_gbps:.0f})"
        )

    def sec_cost_attribution():
        # schema v7: who-costs-what — per-query attributed device cost
        # through the metered solo path, the metering tax (same loop with
        # and without ledger billing), attribution conservation (ledger
        # totals vs what the loop measured), and the event-visibility
        # freshness echo from the event_store section's compaction
        from predictionio_tpu.models.recommendation.engine import (
            ALSAlgorithm,
            Query as ALSQuery,
        )
        from predictionio_tpu.obs.costs import CostLedger, request_cost
        from predictionio_tpu.obs.metrics import REGISTRY, MetricsRegistry

        model = build_als_model(C.state, num_users, num_items)
        algo = ALSAlgorithm()
        ledger = CostLedger(window_s=3600.0, registry=MetricsRegistry())
        measured = {"s": 0.0}

        def run_loop(n, metered):
            laps = []
            for u in range(n):
                t0 = time.perf_counter()
                if metered:
                    with request_cost(
                        "bench-als", "/queries.json", "als", ledger=ledger
                    ) as rec:
                        t1 = time.perf_counter()
                        algo.predict(
                            model, ALSQuery(user=str(u % 100), num=K)
                        )
                        d = time.perf_counter() - t1
                        rec.add(device_s=d)
                    measured["s"] += d
                else:
                    algo.predict(model, ALSQuery(user=str(u % 100), num=K))
                laps.append(time.perf_counter() - t0)
            laps.sort()
            return laps

        run_loop(8, metered=False)  # warm compile + factor cache
        n = 200
        plain = run_loop(n, metered=False)
        billed = run_loop(n, metered=True)
        p50_plain = plain[n // 2] * 1000
        p50_billed = billed[n // 2] * 1000
        overhead_pct = (
            (p50_billed - p50_plain) / p50_plain * 100 if p50_plain else 0.0
        )
        block: dict = {
            "als_requests": n,
            "als_p50_unmetered_ms": round(p50_plain, 3),
            "als_p50_metered_ms": round(p50_billed, 3),
        }
        # NCF rides along when its section trained a model this run
        if hasattr(C, "ncf_state"):
            from predictionio_tpu.models.ncf.engine import (
                NCFAlgorithm,
                Query as NCFQuery,
            )

            ncf_model = build_ncf_model(C.ncf_state, num_users, num_items)
            ncf_algo = NCFAlgorithm()
            n_ncf = 60
            for u in range(4):
                ncf_algo.predict(ncf_model, NCFQuery(user=str(u), num=K))
            for u in range(n_ncf):
                with request_cost(
                    "bench-ncf", "/queries.json", "ncf", ledger=ledger
                ) as rec:
                    t1 = time.perf_counter()
                    ncf_algo.predict(
                        ncf_model, NCFQuery(user=str(u % 100), num=K)
                    )
                    d = time.perf_counter() - t1
                    rec.add(device_s=d)
                measured["s"] += d
            block["ncf_requests"] = n_ncf
        snap = ledger.snapshot()
        attributed_s = 0.0
        for row in snap["totals"]:
            dev_us = row["device_s"] / max(row["requests"], 1) * 1e6
            attributed_s += row["device_s"]
            if row["app"] == "bench-als":
                metrics["cost_als_device_us_per_query"] = round(dev_us, 1)
            elif row["app"] == "bench-ncf":
                metrics["cost_ncf_device_us_per_query"] = round(dev_us, 1)
        coverage = attributed_s / measured["s"] if measured["s"] else 0.0
        metrics["cost_metering_overhead_pct"] = round(overhead_pct, 2)
        metrics["cost_attribution_coverage_frac"] = round(coverage, 4)
        fam = REGISTRY.get("pio_event_visibility_lag_p99_seconds")
        if fam is not None:
            vals = [g.value for _, g in fam.series()]
            if vals:
                metrics["events_visibility_lag_p99_s"] = round(
                    max(vals), 3
                )
        metrics["cost_attribution"] = block
        log(
            f"# cost_attribution: als="
            f"{metrics.get('cost_als_device_us_per_query', 0)}us/query "
            f"ncf={metrics.get('cost_ncf_device_us_per_query', 'n/a')}"
            f"us/query metering_overhead={overhead_pct:+.2f}% "
            f"coverage={coverage:.4f} visibility_p99="
            f"{metrics.get('events_visibility_lag_p99_s', 'n/a')}s"
        )

    def sec_provenance_capture():
        # the always-on decision-record tax: the full solo-path capture
        # sequence (open scope, binding + cache + answer notes, finalize
        # into the ring) measured standalone — the acceptance bound is
        # p50 < 50 us, gated by tier-1 as well as compared here
        from predictionio_tpu.obs import provenance

        store = provenance.ProvenanceStore()

        class _Req:
            path = "/queries.json"

        class _Resp:
            status = 200

        class _Span:
            request_id = "bench-rid"
            trace_id = "bench-tid"

        req, resp, span = _Req(), _Resp(), _Span()
        rendered = {
            "itemScores": [
                {"item": f"m{i}", "score": 0.5 - i * 0.01} for i in range(10)
            ]
        }
        binding_notes = {
            "instance_id": "bench-inst",
            "variant": "default",
            "role": "live",
            "generation": {
                "instance": "bench-inst",
                "checksum": "0" * 64,
                "status": "live",
                "shard_axes": None,
                "engine": {
                    "id": "default", "version": "default",
                    "variant": "default",
                },
            },
        }

        def one_capture():
            token = provenance.begin_capture(deep=False)
            try:
                provenance.note(payload={"user": "u1", "num": 10})
                provenance.note(**binding_notes)
                provenance.note(
                    cache={"hits": 1, "misses": 0,
                           "generation": "bench-inst"}
                )
                provenance.note_answer(rendered)
                provenance.finalize_record(
                    store, "bench", req, resp, 0.001, span
                )
            finally:
                provenance.end_capture(token)

        for _ in range(200):  # warm allocator + ring
            one_capture()
        n = 3000
        laps = []
        for _ in range(n):
            t0 = time.perf_counter()
            one_capture()
            laps.append(time.perf_counter() - t0)
        laps.sort()
        p50_us = laps[n // 2] * 1e6
        p99_us = laps[int(n * 0.99)] * 1e6
        metrics["provenance_capture_p50_us"] = round(p50_us, 2)
        metrics["provenance_capture_p99_us"] = round(p99_us, 2)
        log(
            f"# provenance_capture: p50={p50_us:.2f}us p99={p99_us:.2f}us "
            f"(budget: p50 < 50us always-on)"
        )

    # --events-scale N: run the event-store section over N MILLION
    # synthetic rows instead of the train arrays (the slow 100M-row data-
    # plane mode; only runs when explicitly requested)
    events_scale_m = None
    if "--events-scale" in sys.argv:
        events_scale_m = float(
            sys.argv[sys.argv.index("--events-scale") + 1]
        )

    # --devices N: the sharded scaling section (model-parallel serving +
    # data-parallel train over an N-device mesh, on this process's devices)
    shard_devices = 0
    if "--devices" in sys.argv:
        shard_devices = int(sys.argv[sys.argv.index("--devices") + 1])
    timeline_out = None
    if "--timeline" in sys.argv:
        timeline_out = sys.argv[sys.argv.index("--timeline") + 1]
    # --fleet N: router + N replica subprocesses on this host (the
    # router-overhead gate; replicas pin to cpu — this section measures
    # the CPU-tier proxy hop, not device serving)
    fleet_replicas = 0
    if "--fleet" in sys.argv:
        fleet_replicas = int(sys.argv[sys.argv.index("--fleet") + 1])
    # --day: the scripted production-day section (pio day over real
    # replica subprocesses; seeds its own PIO_HOME, so it runs even
    # without a trained state in this process)
    run_day = "--day" in sys.argv

    def sec_fleet():
        metrics.update(
            bench_fleet_section(C.state, num_users, fleet_replicas)
        )

    def sec_fleet_day():
        metrics.update(bench_fleet_day_section(max(fleet_replicas, 2)))

    def sec_sharded():
        res = bench_sharded_section(
            shard_devices,
            float(os.environ.get("PIO_BENCH_SHARD_SCALE", min(scale, 0.05))),
        )
        metrics["sharded_devices"] = res["devices"]
        metrics["sharded_platform"] = res["platform"]
        metrics["sharded_train_s"] = res["train_s"]
        metrics["sharded_serving_p50_ms"] = res["wave32_p50_ms"]
        metrics["sharded_serving_p99_ms"] = res["wave32_p99_ms"]
        metrics["sharded"] = res
        per_dev = res.get("per_device_factor_bytes") or {}
        log(
            f"# sharded devices={res['devices']} train={res['train_s']:.2f}s "
            f"wave32 p50={res['wave32_p50_ms']:.2f}ms "
            f"p99={res['wave32_p99_ms']:.2f}ms "
            f"per-device factor bytes={sorted(set(per_dev.values()))}"
        )
        # --timeline OUT.json: dump the per-iteration training step
        # timeline (Chrome trace-event JSON, Perfetto-loadable)
        tl = res.get("step_timeline") or {}
        if timeline_out and tl.get("chrome_trace"):
            with open(timeline_out, "w") as f:
                json.dump(tl["chrome_trace"], f)
            log(
                f"# sharded step timeline: {tl.get('steps', 0)} training "
                f"steps -> {timeline_out}"
            )

    if run_section("data", sec_data):
        run_section("als_train", sec_als_train)
        run_section("als_rank32", sec_als_rank32)
        run_section("als_uniform", sec_als_uniform)
        run_section("als_quality", sec_als_quality)
        if run_section("ncf", sec_ncf):
            run_section("ncf_serving", sec_ncf_serving)
        run_section("event_store", sec_event_store)
        if hasattr(C, "state"):
            run_section("als_serving", sec_als_serving)
            run_section("fused_topk", sec_fused_topk)
            run_section("cost_attribution", sec_cost_attribution)
        else:
            failed.append("als_serving")
            log("# SECTION als_serving SKIPPED: no trained ALS state")
    run_section("provenance_capture", sec_provenance_capture)
    if shard_devices > 1:
        run_section("sharded", sec_sharded)
    if fleet_replicas > 0:
        if hasattr(C, "state"):
            run_section("fleet", sec_fleet)
        else:
            failed.append("fleet")
            log("# SECTION fleet SKIPPED: no trained ALS state")
    if run_day:
        run_section("fleet_day", sec_fleet_day)

    from predictionio_tpu.obs.device import BENCH_SCHEMA_VERSION

    train_s = getattr(C, "train_s", None)
    out = {
        # schema_version gates `pio bench --compare`: version-less lines
        # predate the regression gate and are refused (exit 2)
        "schema_version": BENCH_SCHEMA_VERSION,
        "metric": "als_ml20m_train_time"
        if scale == 1.0
        else f"als_ml20m_train_time_scale{scale:g}",
        "value": round(train_s, 3) if train_s is not None else None,
        "unit": "s",
        "vs_baseline": round(budget_s / train_s, 3)
        if train_s is not None else None,
    }
    out.update(metrics)
    # every full-score-row top-k fallback any section hit (the fused menu
    # should cover them all: the gateable claim is this staying 0)
    from predictionio_tpu.obs.metrics import REGISTRY

    fam = REGISTRY.get("pio_topk_full_row_fallback_total")
    out["topk_full_row_fallbacks"] = (
        int(sum(c.value for _, c in fam.series())) if fam is not None else 0
    )
    if failed:
        out["failed_sections"] = failed
    print(json.dumps(out))
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
