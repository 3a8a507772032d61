"""Recommendation engine template: explicit-feedback ALS on a TPU mesh.

Parity with the reference template (examples/scala-parallel-recommendation/
customize-serving/src/main/scala/): DataSource reads ``rate``/``buy`` events
(buy = implicit 4.0 rating, DataSource.scala), the Preparator builds the
BiMap id vocab + COO rating arrays (the ALSAlgorithm.scala:52-72 role), the
ALS algorithm trains sharded factors and serves jit-compiled
``topk(U[u] @ V.T)`` queries, and ``read_eval`` provides the k-fold split of
DataSource.scala:63-81.  Default hyperparams rank=10/numIterations=20/
lambda=0.01/seed=3 mirror the template's engine.json.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.core import (
    Algorithm,
    DataSource,
    EngineContext,
    Engine,
    FirstServing,
    Preparator,
    SanityCheckError,
    Serving,
)
from predictionio_tpu.core.engine import engine_factory
from predictionio_tpu.core.warmstart import align_warm_factors, find_warm_start
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.data.storage.base import CodedColumn
from predictionio_tpu.obs import device as device_obs
from predictionio_tpu.obs import provenance
from predictionio_tpu.obs.tracing import trace
from predictionio_tpu.ops.als import ALSParams, ALSState, train_als
from predictionio_tpu.ops.topk import (
    SCORE_PRECISION,
    fused_supported,
    fused_topk_batch,
    host_topk,
    host_topk_batch,
    note_full_row_fallback,
)
from predictionio_tpu.parallel import device_cache

log = logging.getLogger(__name__)

# ---------------------------------------------------------------------------
# Data types
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Query:
    user: str
    num: int = 10


@dataclass(frozen=True)
class ItemScore:
    item: str
    score: float


@dataclass(frozen=True)
class PredictedResult:
    item_scores: tuple[ItemScore, ...] = ()

    def to_json_dict(self) -> dict[str, Any]:
        return {
            "itemScores": [
                {"item": s.item, "score": s.score} for s in self.item_scores
            ]
        }


@dataclass
class TrainingData:
    """Raw (user, item, rating) triples as columnar arrays.  From a store
    that offers its codes the two id columns are ``CodedColumn``s: they
    index, iterate and compare as the object arrays they stand for, and
    the Preparator reads the codes."""

    users: np.ndarray | CodedColumn  # object[str]
    items: np.ndarray | CodedColumn  # object[str]
    ratings: np.ndarray  # float32

    def sanity_check(self):
        if len(self.ratings) == 0:
            raise SanityCheckError(
                "TrainingData has no ratings — check appName/eventNames"
            )


@dataclass
class PreparedData:
    """Vocab-mapped COO ratings ready for device staging."""

    user_vocab: BiMap
    item_vocab: BiMap
    user_idx: np.ndarray  # int32
    item_idx: np.ndarray  # int32
    ratings: np.ndarray  # float32


# ---------------------------------------------------------------------------
# DataSource
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class EvalParams:
    """k-fold eval config (reference DataSourceEvalParams, DataSource.scala:35)."""

    k_fold: int = 5
    query_num: int = 10
    rating_threshold: float = 4.0


@dataclass(frozen=True)
class DataSourceParams:
    app_name: str = "default"
    channel_name: str | None = None
    eval_params: EvalParams | None = None
    buy_rating: float = 4.0  # implicit rating assigned to `buy` events


class RatingsDataSource(DataSource):
    params_class = DataSourceParams

    def __init__(self, params: DataSourceParams | None = None):
        self.params = params or DataSourceParams()

    def _read(self, ctx: EngineContext) -> TrainingData:
        # what is read below, and ALS takes its ratings in any order
        asked = {
            "columns": ("entity_id", "target_entity_id", "properties"),
            "ordered": False,
        }
        frame = ctx.p_event_store.find(
            self.params.app_name,
            channel_name=self.params.channel_name,
            entity_type="user",
            target_entity_type="item",
            event_names=["rate", "buy"],
            **asked,
        )
        with trace("datasource.columns") as span:
            # each column as the store's codes where the frame kept them,
            # else as the object array: either compares and masks like an
            # array, and property_column parses each distinct document once
            event, users, items = (
                frame.column(c)
                for c in ("event", "entity_id", "target_entity_id")
            )
            ratings = frame.property_column("rating", default=np.nan)
            # buy events carry no rating property -> fixed implicit rating
            is_buy = event == "buy"
            if is_buy.any():
                ratings = np.where(is_buy, self.params.buy_rating, ratings)
            keep = np.isnan(ratings)
            np.logical_not(keep, out=keep)
            if not keep.all():  # else three copies of what is there
                users, items, ratings = users[keep], items[keep], ratings[keep]
            td = TrainingData(
                users=users, items=items,
                ratings=ratings.astype(np.float32, copy=False),
            )
            span.tags = tags = {
                "rows_in": len(keep), "rows_kept": len(td.ratings),
                # "codes" if no pointer a row was made of what was read
                "path": (
                    "objects"
                    if any(
                        frame.coded(c) is None
                        for c in ("event", *asked["columns"])
                    )
                    else "codes"
                ),
            }
            del event, users, items
            # the frame's other columns go here, inside the span that made
            # them redundant: freeing 20 M decoded rows is not free
            del frame, is_buy
        log.info(
            "read %d ratings of %d events (asked the store for %s, "
            "ordered=%s)", tags["rows_kept"], tags["rows_in"],
            ", ".join(asked["columns"]), asked["ordered"],
            extra={"read": {**asked, **tags}},
        )
        return td

    def read_training(self, ctx: EngineContext) -> TrainingData:
        return self._read(ctx)

    def read_eval(self, ctx: EngineContext):
        ep = self.params.eval_params
        if ep is None:
            raise ValueError(
                "DataSourceParams.eval_params must be set for evaluation"
            )
        td = self._read(ctx)
        n = len(td.ratings)
        fold_of = np.arange(n) % ep.k_fold  # zipWithUniqueId % kFold analog
        out = []
        for f in range(ep.k_fold):
            train_mask = fold_of != f
            test_mask = ~train_mask
            train = TrainingData(
                users=td.users[train_mask],
                items=td.items[train_mask],
                ratings=td.ratings[train_mask],
            )
            # group test ratings >= threshold per user => relevant item sets
            test_u = td.users[test_mask]
            test_i = td.items[test_mask]
            test_r = td.ratings[test_mask]
            relevant: dict[str, set] = {}
            for u, i, r in zip(test_u, test_i, test_r):
                if r >= ep.rating_threshold:
                    relevant.setdefault(u, set()).add(i)
            qa = [
                (Query(user=u, num=ep.query_num), frozenset(items))
                for u, items in sorted(relevant.items())
            ]
            out.append((train, {"fold": f}, qa))
        return out


# ---------------------------------------------------------------------------
# Preparator
# ---------------------------------------------------------------------------


class RatingsPreparator(Preparator):
    def __init__(self, params: Any = None):
        pass

    def prepare(self, ctx: EngineContext, td: TrainingData) -> PreparedData:
        with trace("prepare.vocab") as span:
            users = BiMap.factorize(td.users)
            items = BiMap.factorize(td.items)
            user_vocab, item_vocab = users.vocab, items.vocab
            paths = {users.path, items.path}
            span.tags = tags = {
                # "codes" where both columns came coded from the store;
                # "loop" if either fell back to Python over every row
                "path": (
                    "loop" if "loop" in paths
                    else users.path if len(paths) == 1 else "factorize"
                ),
                "rows": len(users.codes),
                "users": len(user_vocab),
                "items": len(item_vocab),
                # keys Python hashed: distinct objects, or every row
                "user_keys_hashed": users.hashed,
                "item_keys_hashed": items.hashed,
            }
        with trace("prepare.index") as span:
            user_idx = users.codes.astype(np.int32, copy=False)
            item_idx = items.codes.astype(np.int32, copy=False)
            span.tags = {"rows": len(user_idx), "path": tags["path"]}
            # the int64 codes go inside the span that made them redundant
            del users, items
        log.info(
            "prepared %(rows)d rows by %(path)s: %(users)d users, %(items)d "
            "items", tags, extra={"prepare": tags},
        )
        return PreparedData(
            user_vocab=user_vocab,
            item_vocab=item_vocab,
            user_idx=user_idx,
            item_idx=item_idx,
            ratings=td.ratings,
        )


# ---------------------------------------------------------------------------
# ALS algorithm
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ALSAlgorithmParams:
    rank: int = 10
    num_iterations: int = 20
    reg: float = 0.01
    seed: int = 3
    chunk_size: int = 1 << 19
    #: serve the item table factor-sharded over the mesh ``model`` axis:
    #: the persisted model records a ShardPlan, ``deploy`` re-binds it onto
    #: the serving host's devices, and batch waves run the sharded top-k
    #: (per-device partial top-k + k-winner merge — no device ever holds a
    #: full-catalog score row).  Single-device hosts ignore the plan.
    shard_serving: bool = False

    # reference engine.json spellings (customize-serving/engine.json:14-21)
    params_aliases = {"lambda": "reg"}


@dataclass
class ALSModel:
    """Factors + vocab; device arrays while serving, numpy when persisted."""

    user_factors: Any  # [num_users, rank]
    item_factors: Any  # [num_items, rank]
    user_vocab: BiMap
    item_vocab: BiMap
    #: factor-sharded serving state (parallel.placement.BoundShards) when a
    #: ShardPlan was re-bound at deploy; None = single-device serving
    shards: Any = None

    def sanity_check(self):
        uf = self.host_factors()[0]
        if not np.isfinite(uf).all():
            raise SanityCheckError("ALS user factors contain non-finite values")

    def host_factors(self) -> tuple[np.ndarray, np.ndarray]:
        """Host numpy replica of (U, V) for solo-query serving — the P2L
        local-model pattern (P2LAlgorithm.scala:46-76).  Cached; excluded
        from pickled state so checkpoints don't double-store the factors."""
        cache = getattr(self, "_host_cache", None)
        if cache is None:
            cache = (
                np.asarray(self.user_factors),
                np.asarray(self.item_factors),
            )
            self._host_cache = cache
        return cache

    def __getstate__(self):
        d = dict(self.__dict__)
        d.pop("_host_cache", None)
        d["shards"] = None  # device placement never rides in a pickle
        return d


class ALSAlgorithm(Algorithm):
    """Explicit-feedback ALS (reference ALSAlgorithm.scala:52 train,
    :97 predict via recommendProducts top-N)."""

    flavor = "P2L"
    params_class = ALSAlgorithmParams
    query_class = Query

    def __init__(self, params: ALSAlgorithmParams | None = None):
        self.params = params or ALSAlgorithmParams()

    def _als_params(self) -> ALSParams:
        p = self.params
        return ALSParams(
            rank=p.rank,
            num_iterations=p.num_iterations,
            reg=p.reg,
            seed=p.seed,
            chunk_size=p.chunk_size,
            implicit_prefs=False,
        )

    def train(self, ctx: EngineContext, pd: PreparedData) -> ALSModel:
        state = train_als(
            pd.user_idx,
            pd.item_idx,
            pd.ratings,
            num_users=len(pd.user_vocab),
            num_items=len(pd.item_vocab),
            params=self._als_params(),
            mesh=ctx.mesh if ctx.mesh.devices.size > 1 else None,
            init_factors=self._warm_start_init(ctx, pd),
        )
        model = ALSModel(
            user_factors=state.user_factors,
            item_factors=state.item_factors,
            user_vocab=pd.user_vocab,
            item_vocab=pd.item_vocab,
        )
        # the factors' one way back to the host: the sanity check and the
        # persisted model both read this replica
        with trace("als.fetch") as span:
            span.tags = {"bytes": sum(f.nbytes for f in model.host_factors())}
        return model

    def _warm_start_init(
        self, ctx: EngineContext, pd: PreparedData
    ) -> tuple[np.ndarray, np.ndarray] | None:
        """Previous-generation factors mapped through the old→new vocab —
        the lifecycle controller's incremental-retrain seed.  Entities
        present in both generations keep their trained rows; new entities
        get the standard random init.  Anything unusable (different rank,
        foreign persisted shape) degrades to a cold start."""
        prev = find_warm_start(
            ctx, ("user_factors", "item_factors", "user_vocab", "item_vocab")
        )
        if prev is None:
            return None
        rank = self.params.rank
        Uw = np.asarray(prev["user_factors"], np.float32)
        Vw = np.asarray(prev["item_factors"], np.float32)
        if Uw.ndim != 2 or Uw.shape[1] != rank or Vw.shape[1] != rank:
            return None
        rng = np.random.default_rng(self.params.seed)
        U0 = align_warm_factors(
            Uw, BiMap.from_state(prev["user_vocab"]), pd.user_vocab, rng
        )
        V0 = align_warm_factors(
            Vw, BiMap.from_state(prev["item_vocab"]), pd.item_vocab, rng
        )
        return U0, V0

    def predict(self, model: ALSModel, query: Query) -> PredictedResult:
        """Solo-query path: host numpy replica (P2L local-model serving).

        A [n_items] matvec + argpartition is ~0.1 ms at ML-20M scale and
        keeps p50 flat even when the device queue is congested; concurrent
        queries coalesce into the device ``batch_predict`` path via the
        serving MicroBatcher instead.  Repeat users skip the factor gather
        entirely: their row comes from the per-model factor cache
        (parallel/device_cache.py), so the flight entry's gather stage is
        ~0 on a hit — and a generation swap swaps the cache with the model,
        so a stale row can never serve."""
        provenance.note(engine_path="als.host_replica")
        cache = device_cache.model_cache(model)
        row = cache.get(query.user)
        if row is None:
            with device_obs.wave_stage("host_gather"):
                uidx = model.user_vocab.get(query.user)
                if uidx is None:
                    # unknown user (reference returns empty)
                    provenance.note(unknown_entity=query.user)
                    return PredictedResult()
                row = model.host_factors()[0][uidx]
            cache.put(query.user, row)
        else:
            device_obs.note_cache_hit()
        k = min(query.num, len(model.item_vocab))
        V = model.host_factors()[1]
        scores, idx = host_topk(V @ row, k)
        return PredictedResult(
            item_scores=tuple(
                ItemScore(item=model.item_vocab.inverse(int(i)), score=float(s))
                for i, s in zip(idx, scores)
            )
        )

    # -- sharded serving (parallel.placement) --------------------------------

    def serving_shard_plan(self, model: ALSModel):
        """The declarative layout serving re-binds at deploy: both factor
        tables row-sharded over the ``model`` axis (recorded in the
        persisted model AND the generation manifest)."""
        if not self.params.shard_serving:
            return None
        from predictionio_tpu.parallel.placement import ShardPlan

        return ShardPlan.model_parallel(
            ["user_factors", "item_factors"],
            rows={
                "user_factors": len(model.user_vocab),
                "item_factors": len(model.item_vocab),
            },
        )

    def _sharded_topk(self, model: ALSModel, uidx: np.ndarray, k: int):
        """One wave through the factor-sharded kernel: gather the user rows
        (collective lookup from the sharded user table), per-shard partial
        top-k over each device's item rows, k-winner merge.  Shapes are
        padded to the same power-of-two menu as the NCF wave path so client
        ``num`` sweeps cannot storm the compile cache."""
        from predictionio_tpu.parallel.placement import (
            build_sharded_topk,
            gather_rows,
            run_observed_wave,
        )

        bound = model.shards
        n_items = len(model.item_vocab)
        with device_obs.wave_stage("host_gather"):
            b = max(1 << (len(uidx) - 1).bit_length(), 8)
            k_pad = min(max(1 << (k - 1).bit_length(), 16), n_items)
            padded = np.zeros(b, np.int32)
            padded[: len(uidx)] = uidx
        sig = (b, k_pad, n_items, bound.n_shards) + tuple(
            bound.arrays["item_factors"].shape
        )
        # per-shard FUSED local top-k when the shape is on the menu: each
        # device's local [B, rows_local] score block never materializes
        # (only the fused kernel's tile-wide slab) — proof in both
        # LAST_KERNEL_SHAPES hooks.  Off the menu, the score-then-top_k
        # local path still runs and is counted as a full-row fallback.
        rows_local = int(bound.arrays["item_factors"].shape[0]) // max(
            bound.n_shards, 1
        )
        use_fused = fused_supported(b, min(k_pad, rows_local), rows_local)
        if not use_fused:
            note_full_row_fallback(b, k_pad, n_items, "als.sharded_topk")

        def _fused_local(item_local, q, kc, limit):
            packed = fused_topk_batch(
                q, item_local, kc, limit=limit,
                name="als.sharded_topk.fused",
            )
            return packed[0], packed[1].astype(jnp.int32)

        kernel = bound.kernel(
            (b, k_pad),
            lambda: build_sharded_topk(
                bound.mesh,
                bound.plan,
                lambda item_local, q: jnp.matmul(
                    q, item_local.T, precision=SCORE_PRECISION
                ),
                ["item_factors"],
                n_items=n_items,
                k=k_pad,
                name="als.sharded_topk",
                local_topk_fn=_fused_local if use_fused else None,
            ),
        )

        def compute(uidx_dev):
            q_rows = gather_rows(
                bound.mesh, bound.arrays["user_factors"], uidx_dev
            )
            packed_dev = kernel(bound.arrays["item_factors"], q_rows)
            return packed_dev, (bound.arrays["item_factors"], q_rows)

        packed = run_observed_wave(
            "als.sharded_topk",
            kernel=kernel,
            sig=sig,
            host_input=padded,
            compute=compute,
            shard_arrays={
                n: bound.arrays[n] for n in ("user_factors", "item_factors")
            },
        )
        return packed[0], packed[1].astype(np.int64)

    #: waves below this go through the host replica (latency-bound micro-
    #: batches); at/above it the one [B, rank] x [rank, n_items] device
    #: matmul wins (throughput-bound eval batches)
    DEVICE_BATCH_MIN = 512

    def _split_known(self, model: ALSModel, queries):
        known = [(i, model.user_vocab.get(q.user)) for i, q in queries]
        rows = [
            (i, u, q)
            for (i, q), (_, u) in zip(queries, known)
            if u is not None
        ]
        missing = [
            (i, PredictedResult())
            for (i, q), (_, u) in zip(queries, known)
            if u is None
        ]
        return rows, missing

    def _render_rows(self, model: ALSModel, rows, top_s, top_i):
        out = []
        for row, (i, _, q) in enumerate(rows):
            n = min(q.num, len(model.item_vocab))
            out.append(
                (
                    i,
                    PredictedResult(
                        item_scores=tuple(
                            ItemScore(
                                item=model.item_vocab.inverse(int(ii)),
                                score=float(ss),
                            )
                            for ii, ss in zip(top_i[row, :n], top_s[row, :n])
                        )
                    ),
                )
            )
        return out

    def _host_topk_rows(self, model: ALSModel, rows, k: int):
        """Host-replica wave: per-entity user rows from the factor cache
        (repeat entities skip the gather — counted on the wave timeline),
        misses gathered once and cached, then one [B, rank] x [rank, n]
        numpy matmul + batched top-k."""
        cache = device_cache.model_cache(model)
        qrows: list[Any] = [None] * len(rows)
        miss_j: list[int] = []
        hits = 0
        for j, (_, _, q) in enumerate(rows):
            row = cache.get(q.user)
            if row is None:
                miss_j.append(j)
            else:
                qrows[j] = row
                hits += 1
        if hits:
            device_obs.note_cache_hit(hits)
        if miss_j:
            with device_obs.wave_stage("host_gather"):
                Uh = model.host_factors()[0]
                for j in miss_j:
                    row = np.array(Uh[rows[j][1]])
                    qrows[j] = row
                    cache.put(rows[j][2].user, row)
        Vh = model.host_factors()[1]
        return host_topk_batch(np.stack(qrows) @ Vh.T, k)

    def _device_topk(self, model: ALSModel, uidx: np.ndarray, k: int):
        """Dispatch the device top-k WITHOUT blocking; returns the fence
        callable that blocks, reads back, and hands over (top_s, top_i) —
        the async half the MicroBatcher pipeline overlaps.  Fused kernel
        when the shape is on the menu (no [B, n_items] score row, see
        ops/topk.py); otherwise the materialized-row kernel, counted."""
        eff = device_obs.default_efficiency()
        with device_obs.wave_stage("h2d"):
            # count the bytes that actually cross: numpy factors
            # (a freshly persisted model) upload whole matrices,
            # device-resident factors upload nothing
            uploaded = uidx.nbytes + sum(
                a.nbytes
                for a in (model.user_factors, model.item_factors)
                if not hasattr(a, "devices")
            )
            U = jnp.asarray(model.user_factors)
            V = jnp.asarray(model.item_factors)
            uidx_dev = jnp.asarray(uidx)
            device_obs.note_transfer("h2d", uploaded)
        from predictionio_tpu.ops.topk import fused_topk_roofline

        if fused_supported(len(uidx), k, int(V.shape[0])):
            # factor shapes are part of the key — two deployed models
            # (different rank / vocab) must not share cost entries
            sig = ("fused", len(uidx), k) + tuple(U.shape) + tuple(V.shape)
            device_obs.default_recompiles().note_signature(
                "als.fused_topk", sig
            )
            packed = fused_topk_batch(
                U[uidx_dev], V, k, name="als.fused_topk"
            )

            def fence():
                with device_obs.wave_stage("compute"):
                    packed.block_until_ready()
                device_obs.note_wave_device(
                    device_obs.device_label(packed)
                )
                # pallas bodies are opaque to XLA cost_analysis: the
                # analytic roofline stands in
                device_obs.note_wave_cost(
                    "als.fused_topk",
                    fused_topk_roofline(
                        len(uidx), int(U.shape[1]), int(V.shape[0]), k
                    ),
                )
                with device_obs.wave_stage("d2h"):
                    arr = np.asarray(packed)
                    device_obs.note_transfer("d2h", arr.nbytes)
                return arr[0], arr[1].astype(np.int64)

            return fence
        note_full_row_fallback(
            len(uidx), k, int(V.shape[0]), "als.batch_topk"
        )
        sig = (len(uidx), k) + tuple(U.shape) + tuple(V.shape)
        device_obs.default_recompiles().note_signature("als.batch_topk", sig)
        eff.capture_cost(
            "als.batch_topk", _device_score_topk, U, V, uidx_dev, k,
            signature=sig, defer=True,
        )
        t_dev = time.perf_counter()
        top = _device_score_topk(U, V, uidx_dev, k)

        def fence_full():
            with device_obs.wave_stage("compute"):
                top[0].block_until_ready()
            compute_s = time.perf_counter() - t_dev
            device_obs.note_wave_device(device_obs.device_label(top[0]))
            device_obs.note_wave_cost(
                "als.batch_topk", eff.cached_cost("als.batch_topk", sig)
            )
            with device_obs.wave_stage("d2h"):
                top_s, top_i = np.asarray(top[0]), np.asarray(top[1])
                device_obs.note_transfer(
                    "d2h", top_s.nbytes + top_i.nbytes
                )
            eff.observe("als.batch_topk", compute_s, signature=sig)
            return top_s, top_i

        return fence_full

    def batch_predict(self, model: ALSModel, queries):
        """Vectorized path: one fused (or [B, rank] x [rank, n_items])
        device dispatch, or the host replica below DEVICE_BATCH_MIN."""
        rows, out = self._split_known(model, queries)
        if rows:
            uidx = np.asarray([u for _, u, _ in rows], np.int32)
            k = max(min(q.num, len(model.item_vocab)) for _, _, q in rows)
            if model.shards is not None:
                provenance.note(engine_path="als.sharded_topk")
                top_s, top_i = self._sharded_topk(model, uidx, k)
            elif len(rows) >= self.DEVICE_BATCH_MIN:
                provenance.note(engine_path="als.device_topk")
                top_s, top_i = self._device_topk(model, uidx, k)()
            else:
                provenance.note(engine_path="als.host_replica")
                top_s, top_i = self._host_topk_rows(model, rows, k)
            out.extend(self._render_rows(model, rows, top_s, top_i))
        return out

    def dispatch_batch(self, model: ALSModel, indexed_queries):
        """The MicroBatcher pipeline's async half (docs/performance.md):
        vocab gather and the device dispatch run NOW (no blocking); the
        returned finalize fences, reads back, and renders.  Declines
        (None) for sharded serving (synchronous settle clock) and for
        host-replica waves (no dispatch to overlap — and the worker being
        busy is what drives natural batching)."""
        iq = list(indexed_queries)
        if model.shards is not None or len(iq) < self.DEVICE_BATCH_MIN:
            # sharded waves: the settle clock is synchronous by design.
            # Host-replica waves: there is no device dispatch to overlap,
            # and moving the CPU scoring off the worker would DESTROY
            # natural batching (the worker being busy is what lets queue
            # pressure coalesce the next wave) — measured: wave sizes
            # collapse to 1 and concurrent p50 regresses 7x.  Decline;
            # the wave computes inline on the worker as before.
            return None
        with device_obs.wave_stage("host_gather"):
            rows, missing = self._split_known(model, iq)
        if not rows:
            return lambda: list(missing)
        uidx = np.asarray([u for _, u, _ in rows], np.int32)
        k = max(min(q.num, len(model.item_vocab)) for _, _, q in rows)
        if len(rows) < self.DEVICE_BATCH_MIN:
            return None  # mostly-unknown wave fell under the device floor
        provenance.note(engine_path="als.device_topk")
        fence = self._device_topk(model, uidx, k)

        def finalize():
            top_s, top_i = fence()
            return missing + self._render_rows(model, rows, top_s, top_i)

        return finalize

    # -- persistence ---------------------------------------------------------
    def make_persistent_model(self, ctx: EngineContext, model: ALSModel):
        out = {
            "user_factors": model.host_factors()[0],
            "item_factors": model.host_factors()[1],
            "user_vocab": model.user_vocab.to_state(),
            "item_vocab": model.item_vocab.to_state(),
        }
        plan = self.serving_shard_plan(model)
        if plan is not None:
            # the model carries its own layout: deploy re-binds this plan
            # onto whatever mesh the serving host has
            out["shard_plan"] = plan.to_dict()
        return out

    def load_persistent_model(self, ctx: EngineContext, data) -> ALSModel:
        from predictionio_tpu.parallel.placement import (
            ShardPlan,
            bind_shards,
        )

        plan = ShardPlan.from_dict(data.get("shard_plan"))
        if plan is not None and len(jax.devices()) > 1:
            # re-bind the recorded layout onto the CURRENT mesh (re-sharding
            # on device-count mismatch); the unsharded host copies stay for
            # the solo-query path and sanity checks
            Uh = np.asarray(data["user_factors"])
            Vh = np.asarray(data["item_factors"])
            model = ALSModel(
                user_factors=Uh,
                item_factors=Vh,
                user_vocab=BiMap.from_state(data["user_vocab"]),
                item_vocab=BiMap.from_state(data["item_vocab"]),
                shards=bind_shards(
                    plan, {"user_factors": Uh, "item_factors": Vh}
                ),
            )
            from predictionio_tpu.parallel.mesh import meter_shards

            meter_shards("als.serving_factors", model.shards.arrays)
            return model
        return ALSModel(
            user_factors=jnp.asarray(data["user_factors"]),
            item_factors=jnp.asarray(data["item_factors"]),
            user_vocab=BiMap.from_state(data["user_vocab"]),
            item_vocab=BiMap.from_state(data["item_vocab"]),
        )


@partial(jax.jit, static_argnames=("k",))
def _device_score_topk(U, V, uidx, k: int):
    """The serving top-k as ONE compiled program ([B, rank] gather +
    [B, rank] x [rank, n_items] matmul + top-k) instead of three eager
    dispatches — and a jit entry point the device-efficiency layer can run
    ``cost_analysis()`` against (obs/device.py)."""
    # [B, n_items]
    scores = jnp.matmul(U[uidx], V.T, precision=SCORE_PRECISION)
    return jax.lax.top_k(scores, k)


class RecommendationServing(FirstServing):
    pass


@engine_factory("recommendation")
def recommendation_engine() -> Engine:
    return Engine(
        {"": RatingsDataSource, "ratings": RatingsDataSource},
        {"": RatingsPreparator, "ratings": RatingsPreparator},
        {"als": ALSAlgorithm},
        {"": RecommendationServing, "first": RecommendationServing},
    )
