"""E-commerce recommendation engine template.

Parity with examples/scala-parallel-ecommercerecommendation
(train-with-rate-event; ECommAlgorithm.scala, 649 LoC): implicit/explicit ALS
with business rules evaluated at serving time —

  - known user: dot-product scores over candidate items
    (predictKnownUser), one masked matmul + top-k on device;
  - cold user: cosine similarity to recently-viewed item features
    (predictSimilar) read LIVE from the event store;
  - no signal at all: popularity (buy-count) fallback (predictDefault);
  - blacklists (genBlackList): seen items (live LEventStore read of the
    user's seenEvents), the ``constraint/unavailableItems`` ``$set`` entity
    (latest event wins), and the query's own blackList;
  - category / whiteList candidate filtering (isCandidateItem).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.core.base import (
    Algorithm,
    DataSource,
    EngineContext,
    Preparator,
    SanityCheckError,
    Serving,
)
from predictionio_tpu.core.engine import Engine, engine_factory
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.filters import CategoryIndex, exclude_mask
from predictionio_tpu.obs import device as device_obs
from predictionio_tpu.obs import provenance
from predictionio_tpu.ops.als import ALSParams, train_als
from predictionio_tpu.ops.similarity import cosine_topk, dot_topk
from predictionio_tpu.resilience.degrade import mark_degraded


@dataclass(frozen=True)
class Query:
    user: str
    num: int = 10
    categories: tuple[str, ...] | None = None
    white_list: tuple[str, ...] | None = None
    black_list: tuple[str, ...] | None = None


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
class Item:
    categories: tuple[str, ...] = ()


@dataclass
class TrainingData:
    users: list[str]
    items: dict[str, Item]
    # interaction columns (entity/target/event/rating/time)
    int_users: np.ndarray = field(default_factory=lambda: np.empty(0, object))
    int_items: np.ndarray = field(default_factory=lambda: np.empty(0, object))
    int_events: np.ndarray = field(default_factory=lambda: np.empty(0, object))
    int_ratings: np.ndarray = field(default_factory=lambda: np.empty(0, np.float32))
    int_times: np.ndarray = field(default_factory=lambda: np.empty(0, np.int64))

    def sanity_check(self):
        if not self.items:
            raise SanityCheckError("no $set item events found")
        if len(self.int_items) == 0:
            raise SanityCheckError("no interaction events found")


PreparedData = TrainingData


@dataclass(frozen=True)
class DataSourceParams:
    app_name: str = "default"
    channel_name: str | None = None
    #: interaction events read for training ("view" + "buy" + optional "rate")
    event_names: tuple[str, ...] = ("view", "buy")


class ECommDataSource(DataSource):
    params_class = DataSourceParams

    def __init__(self, params: DataSourceParams | None = None):
        self.params = params or DataSourceParams()

    def read_training(self, ctx: EngineContext) -> TrainingData:
        store = ctx.p_event_store
        users = sorted(
            store.aggregate_properties(
                self.params.app_name, "user", channel_name=self.params.channel_name
            )
        )
        items = {
            item_id: Item(categories=tuple(props.get_or_else("categories", [])))
            for item_id, props in store.aggregate_properties(
                self.params.app_name, "item", channel_name=self.params.channel_name
            ).items()
        }
        frame = store.find(
            self.params.app_name,
            channel_name=self.params.channel_name,
            entity_type="user",
            target_entity_type="item",
            event_names=list(self.params.event_names),
        )
        ratings = np.ones(len(frame), np.float32)
        r = frame.property_column("rating")
        has_r = ~np.isnan(r)
        ratings[has_r] = r[has_r]
        return TrainingData(
            users=users,
            items=items,
            int_users=frame.entity_id,
            int_items=frame.target_entity_id,
            int_events=frame.event,
            int_ratings=ratings,
            int_times=frame.event_time_ms,
        )


class ECommPreparator(Preparator):
    def __init__(self, params: Any = None):
        pass

    def prepare(self, ctx: EngineContext, td: TrainingData) -> PreparedData:
        return td


def latest_rating_per_pair(u, i, ratings, times, n_items: int):
    """genMLlibRating semantics: latest rating wins per (user, item)
    (ECommAlgorithm.scala train-with-rate-event genMLlibRating).

    Vectorized group-reduce: lexsort by (pair-key, time) — both sorts
    stable — then keep each key group's LAST row, which is exactly the
    entry a sequential "overwrite in time order" loop would retain (time
    ties resolve to the later event, as dict insertion did).  No per-event
    Python work, so 20M-event streams reduce in seconds.
    """
    if len(u) == 0:
        return (
            np.empty(0, np.int32),
            np.empty(0, np.int32),
            np.empty(0, np.float32),
        )
    key = u.astype(np.int64) * n_items + i
    order = np.lexsort((times, key))
    ks = key[order]
    last = np.flatnonzero(np.r_[ks[1:] != ks[:-1], True])
    ku = ks[last]
    return (
        (ku // n_items).astype(np.int32),
        (ku % n_items).astype(np.int32),
        np.asarray(ratings)[order][last].astype(np.float32),
    )


@dataclass(frozen=True)
class ECommAlgorithmParams:
    app_name: str = "default"
    unseen_only: bool = True
    seen_events: tuple[str, ...] = ("buy", "view")
    similar_events: tuple[str, ...] = ("view",)
    rank: int = 10
    num_iterations: int = 20
    reg: float = 0.01
    seed: int = 3
    #: events used to build the training matrix; "rate" keeps its rating
    train_events: tuple[str, ...] = ("view", "buy")

    params_aliases = {"lambda": "reg"}


@dataclass
class ECommModel:
    user_factors: Any  # [n_users, rank]
    item_factors: Any  # [n_items, rank]
    popular_counts: np.ndarray  # [n_items] buy counts
    user_vocab: BiMap
    item_vocab: BiMap
    items: dict[str, Item]

    def sanity_check(self):
        if not np.isfinite(np.asarray(self.item_factors)).all():
            raise SanityCheckError("item factors are not finite")


class ECommAlgorithm(Algorithm):
    flavor = "P2L"
    params_class = ECommAlgorithmParams
    query_class = Query

    def __init__(self, params: ECommAlgorithmParams | None = None):
        self.params = params or ECommAlgorithmParams()

    # -- train ---------------------------------------------------------------
    def train(self, ctx: EngineContext, pd: PreparedData) -> ECommModel:
        p = self.params
        user_vocab = BiMap.from_keys(pd.users)
        item_vocab = BiMap.from_keys(sorted(pd.items))
        u = user_vocab.to_index_array(pd.int_users, missing=-1)
        i = item_vocab.to_index_array(pd.int_items, missing=-1)
        train_mask = (
            (u >= 0) & (i >= 0) & np.isin(pd.int_events, list(p.train_events))
        )
        if not train_mask.any():
            raise SanityCheckError("no valid training interactions")
        lu, li, lr = latest_rating_per_pair(
            u[train_mask],
            i[train_mask],
            pd.int_ratings[train_mask],
            pd.int_times[train_mask],
            len(item_vocab),
        )
        state = train_als(
            lu,
            li,
            lr,
            num_users=len(user_vocab),
            num_items=len(item_vocab),
            params=ALSParams(
                rank=p.rank,
                num_iterations=p.num_iterations,
                reg=p.reg,
                implicit_prefs=True,
                seed=p.seed,
            ),
            mesh=ctx.mesh if ctx.mesh.devices.size > 1 else None,
        )
        # trainDefault: buy-count popularity fallback scores
        pop = np.zeros(len(item_vocab), np.int64)
        buy_mask = (i >= 0) & (pd.int_events == "buy")
        np.add.at(pop, i[buy_mask], 1)
        return ECommModel(
            user_factors=state.user_factors,
            item_factors=state.item_factors,
            popular_counts=pop,
            user_vocab=user_vocab,
            item_vocab=item_vocab,
            items=dict(pd.items),
        )

    # -- business rules ------------------------------------------------------
    def _gen_black_list(self, ctx: EngineContext, query: Query) -> set[str]:
        """Seen events + unavailableItems constraint + query blackList
        (ECommAlgorithm.genBlackList).

        The live event-store reads here are the hot path's dependency on
        the storage fleet: when the store is unreachable (or the circuit
        breaker is open, which fails in ~0 ms), the query still answers
        from the model alone — marked degraded, never errored (the
        reference template's timeout-to-empty-list semantics, made
        visible)."""
        seen: set[str] = set()
        watermark = None
        store = ctx.l_event_store
        if self.params.unseen_only:
            try:
                for e in store.find_by_entity(
                    self.params.app_name,
                    entity_type="user",
                    entity_id=query.user,
                    event_names=list(self.params.seen_events),
                    target_entity_type="item",
                ):
                    if e.target_entity_id is not None:
                        seen.add(e.target_entity_id)
                    if watermark is None or e.event_time > watermark:
                        watermark = e.event_time
            except Exception:
                mark_degraded("seen_filter")
                seen = set()  # timeout semantics: empty seen list
        unavailable: set[str] = set()
        try:
            latest = store.find_by_entity(
                self.params.app_name,
                entity_type="constraint",
                entity_id="unavailableItems",
                event_names=["$set"],
                limit=1,
                latest=True,
            )
            for e in latest:
                unavailable = set(e.properties.get_or_else("items", []))
        except Exception:
            mark_degraded("unavailable_items")
            unavailable = set()
        provenance.note(
            filters={
                "seen": len(seen),
                "unavailable": len(unavailable),
                "black_list": len(query.black_list or ()),
            }
        )
        if watermark is not None:
            # newest event-history timestamp the answer depended on: the
            # freshness watermark a replay CANNOT honor once later events
            # land (documented replay caveat for live-read engines)
            provenance.note(event_watermark=watermark.isoformat())
        provenance.note_deep(
            seen_items=provenance.clip(seen),
            unavailable_items=provenance.clip(unavailable),
        )
        return seen | unavailable | set(query.black_list or ())

    def _recent_items(self, ctx: EngineContext, query: Query) -> list[str]:
        """Latest 10 similar-events targets for the user (getRecentItems).
        Store unreachable -> no recent signal: the cold-user path falls
        through to popularity, marked degraded."""
        try:
            events = list(
                ctx.l_event_store.find_by_entity(
                    self.params.app_name,
                    entity_type="user",
                    entity_id=query.user,
                    event_names=list(self.params.similar_events),
                    target_entity_type="item",
                    limit=10,
                    latest=True,
                )
            )
            recent = [e.target_entity_id for e in events if e.target_entity_id]
            provenance.note(filters_recent=len(recent))
            if events:
                # latest=True: the first event is the newest consulted
                provenance.note(
                    event_watermark=events[0].event_time.isoformat()
                )
            provenance.note_deep(recent_items=provenance.clip(recent))
            return recent
        except Exception:
            mark_degraded("recent_items")
            return []

    def _exclude_mask(
        self, model: ECommModel, query: Query, black: set[str]
    ) -> np.ndarray:
        index = getattr(model, "_category_index", None)
        if index is None:
            index = model._category_index = CategoryIndex(
                model.item_vocab,
                {k: v.categories for k, v in model.items.items()},
            )
        return exclude_mask(
            model.item_vocab,
            category_index=index,
            white_list=query.white_list,
            black_list=black,
            categories=query.categories,
        )

    def _user_row(self, model: ECommModel, user: str):
        """The user's factor row as a DEVICE-resident array, cached per
        model: the cold path materializes the whole host copy of the user
        table and re-uploads one row per query — a repeat user skips both
        transfers entirely (the row never leaves HBM between requests).
        The cache dies with the model object, so a generation swap can
        never serve a stale row (parallel/device_cache.py)."""
        from predictionio_tpu.parallel import device_cache

        cache = device_cache.model_cache(model)
        row = cache.get(user)
        if row is not None:
            device_obs.note_cache_hit()
            return row
        uidx = model.user_vocab.get(user)
        if uidx is None:
            return None
        with device_obs.wave_stage("host_gather"):
            row = jnp.asarray(np.asarray(model.user_factors)[uidx])
        cache.put(user, row)
        return row

    # -- predict -------------------------------------------------------------
    def predict(self, model: ECommModel, query: Query) -> PredictedResult:
        # NOTE: serving-time event-store reads put a storage RTT inside the
        # query path, exactly like the reference template (SURVEY.md §3.2).
        ctx = self._serving_ctx()
        black = self._gen_black_list(ctx, query)
        exclude = self._exclude_mask(model, query, black)
        k = min(query.num, len(model.item_vocab))
        qrow = self._user_row(model, query.user)
        if qrow is not None:
            provenance.note(engine_path="ecomm.dot_topk")
            scores, idx = dot_topk(
                qrow,
                jnp.asarray(model.item_factors),
                jnp.asarray(exclude),
                k,
            )
            return self._to_result(model, scores, idx)
        recent = [
            i
            for x in self._recent_items(ctx, query)
            if (i := model.item_vocab.get(x)) is not None
        ]
        if recent:
            provenance.note(engine_path="ecomm.cosine_topk")
            qf = jnp.asarray(np.asarray(model.item_factors)[recent], jnp.float32)
            scores, idx = cosine_topk(
                qf, jnp.asarray(model.item_factors), jnp.asarray(exclude), k
            )
            return self._to_result(model, scores, idx)
        # popularity fallback
        provenance.note(engine_path="ecomm.popularity")
        pop = np.where(exclude, -1, model.popular_counts)
        order = np.argsort(-pop, kind="stable")[:k]
        return PredictedResult(
            item_scores=tuple(
                ItemScore(item=model.item_vocab.inverse(int(j)), score=float(pop[j]))
                for j in order
                if pop[j] >= 0
            )
        )

    def _serving_ctx(self) -> EngineContext:
        if not hasattr(self, "_ctx"):
            self._ctx = EngineContext(mode="serving")
        return self._ctx

    def _to_result(self, model: ECommModel, scores, idx) -> PredictedResult:
        out = []
        for s, j in zip(np.asarray(scores), np.asarray(idx)):
            if not np.isfinite(s):
                continue
            out.append(
                ItemScore(item=model.item_vocab.inverse(int(j)), score=float(s))
            )
        return PredictedResult(item_scores=tuple(out))

    # -- persistence ---------------------------------------------------------
    def make_persistent_model(self, ctx, model: ECommModel):
        return {
            "user_factors": np.asarray(jax.device_get(model.user_factors)),
            "item_factors": np.asarray(jax.device_get(model.item_factors)),
            "popular_counts": model.popular_counts,
            "user_vocab": model.user_vocab.to_state(),
            "item_vocab": model.item_vocab.to_state(),
            "items": {k: v.categories for k, v in model.items.items()},
        }

    def load_persistent_model(self, ctx, data) -> ECommModel:
        return ECommModel(
            user_factors=jnp.asarray(data["user_factors"]),
            item_factors=jnp.asarray(data["item_factors"]),
            popular_counts=np.asarray(data["popular_counts"]),
            user_vocab=BiMap.from_state(data["user_vocab"]),
            item_vocab=BiMap.from_state(data["item_vocab"]),
            items={k: Item(categories=tuple(v)) for k, v in data["items"].items()},
        )


class ECommServing(Serving):
    def __init__(self, params: Any = None):
        pass

    def serve(self, query, predictions):
        return predictions[0]


@engine_factory("ecommerce")
def ecommerce_engine() -> Engine:
    return Engine(
        ECommDataSource,
        ECommPreparator,
        {"ecomm": ECommAlgorithm},
        ECommServing,
    )
