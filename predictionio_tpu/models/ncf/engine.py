"""Deep recommendation template: NCF / two-tower with sharded embeddings.

The pypio deep-rec configuration (BASELINE.json configs[4]).  Reuses the
recommendation template's event schema (rate/buy user->item events,
DataSource parity with examples/scala-parallel-recommendation) but trains
the NCF two-tower model of ops/ncf.py: embedding tables row-sharded over the
mesh ``model`` axis, batches over ``data``, BPR loss, one compiled step.

Query/result shapes match the recommendation template ({user, num} ->
{itemScores}) so the serving stack and evaluation metrics apply unchanged.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.core.base import Algorithm, EngineContext, SanityCheckError
from predictionio_tpu.obs import device as device_obs
from predictionio_tpu.obs import provenance
from predictionio_tpu.core.engine import Engine, engine_factory
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.recommendation.engine import (
    ItemScore,
    PredictedResult,
    PreparedData,
    Query,
    RatingsDataSource,
    RatingsPreparator,
    RecommendationServing,
)
from predictionio_tpu.ops.ncf import (
    NCFParams,
    NCFState,
    score_all_items,
    train_ncf,
)


@dataclass(frozen=True)
class NCFAlgorithmParams:
    embed_dim: int = 32
    mlp_layers: tuple[int, ...] = (64, 32, 16)
    learning_rate: float = 1e-3
    num_epochs: int = 5
    batch_size: int = 8192
    positive_threshold: float = 4.0  # ratings >= this are positives
    negatives_per_positive: int = 1  # K sampled negatives per step
    neg_power: float = 0.0  # see ops.ncf.NCFParams.neg_power
    #: "bpr" | "softmax" | "full_softmax" | "wals" (whole-catalog losses
    #: need mlp_layers=())
    loss: str = "bpr"
    item_bias: bool = True  # learned per-item score offset
    weight_decay: float = 0.0  # AdamW decoupled decay (0 = plain Adam)
    #: iALS confidence weight (loss="wals" and the "als" pretrainer)
    alpha: float = 2.0
    #: serve the embedding tables factor-sharded over the mesh ``model``
    #: axis (ShardPlan recorded in the persisted model + generation
    #: manifest; re-bound by deploy).  The MLP head stays replicated; each
    #: device scores only its item rows and shards exchange k winners.
    shard_serving: bool = False
    #: "" (random init) or "als": pretrain the GMF tables with implicit
    #: ALS (rank = embed_dim, exact alternating solves — seconds on the
    #: pallas path) before SGD fine-tuning.  The NCF paper's §3.4.1
    #: pretraining recipe with ALS as the GMF pretrainer; requires
    #: mlp_layers=().  Measured on the ML-20M bench protocol: sampled
    #: losses plateau at MAP@10 ~0.0225, whole-catalog SGD from scratch
    #: reaches ~0.029, ALS-init + 1 epoch full_softmax matches/exceeds
    #: the pure-ALS 0.0307 with better Precision@10.
    pretrain: str = ""
    seed: int = 3

    def __post_init__(self):
        if self.pretrain not in ("", "als"):
            raise ValueError(f"unknown pretrain {self.pretrain!r}")
        if self.pretrain == "als" and self.mlp_layers:
            raise ValueError(
                "pretrain='als' initializes the pure-GMF tables: set "
                "mlpLayers to []"
            )


@partial(jax.jit, static_argnames=("n_items", "k"))
def _score_topk_batch(params, user_idx, n_items: int, k: int):
    """A whole micro-batch wave in ONE dispatch: [B] users -> top-k each.

    One device round trip per wave instead of per query — under
    concurrency the dispatch overhead amortizes B-fold (the reason the
    MicroBatcher exists).  Callers pad ``user_idx`` to a power of two so
    at most log2(max_batch) variants ever compile.  Output is ONE packed
    [2, B, k] f32 array (row 0 = scores, row 1 = item indices) instead of a
    (scores, indices) pair: fetching two separate outputs costs two
    device->host transfers, the packed layout one.  f32 holds item ids
    exactly up to 2^24.
    """
    with jax.named_scope("ncf.score"):
        scores = jax.vmap(lambda u: score_all_items(params, u))(user_idx)
    with jax.named_scope("ncf.topk"):
        masked = jnp.where(
            jnp.arange(scores.shape[1])[None, :] < n_items, scores, -jnp.inf
        )
        s, i = jax.lax.top_k(masked, k)
        return jnp.stack([s, i.astype(jnp.float32)])


def _packable_n_items(model: "NCFModel") -> int:
    """The packed [scores | indices] f32 transfer holds item ids exactly
    only below 2^24; beyond that the roundtrip would silently return wrong
    items, so refuse loudly (catalogs that big need an int32 output path)."""
    n_items = len(model.item_vocab)
    if n_items >= 1 << 24:
        raise ValueError(
            f"{n_items} items exceeds the f32-exact id range of the packed "
            "top-k transfer (2^24)"
        )
    return n_items


def _host_score_topk(hp: dict, uidx: int, n_items: int, k: int, ue=None):
    """numpy replica of ops.ncf.score_all_items + top-k for ONE user.

    Solo queries serve from the host: a device dispatch costs a full
    device round trip per query, while this [n_items, hidden] numpy MLP
    needs none.  The wave path (batch_predict /
    _score_topk_batch) stays on device where batching amortizes the
    dispatch.  Mirrors the ALS template's host-replica solo serving.
    ``ue`` (the user's embedding row) may arrive pre-gathered from the
    factor cache — repeat users skip the table read entirely."""
    if "out_w" not in hp:  # pure GMF (mlp_layers=())
        if ue is None:
            ue = hp["user_emb"][uidx]
        score = hp["item_emb"] @ ue + hp["out_b"][0]
    else:
        d = hp["user_emb"].shape[1] // 2
        n_full = hp["item_emb"].shape[0]
        if ue is None:
            ue = hp["user_emb"][uidx]
        gmf = ue[None, :d] * hp["item_emb"][:, :d]
        h = np.concatenate(
            [np.broadcast_to(ue[d:], (n_full, d)), hp["item_emb"][:, d:]],
            axis=-1,
        )
        for layer in hp["mlp"]:
            h = np.maximum(h @ layer["w"] + layer["b"], 0.0)
        score = (
            np.concatenate([gmf, h], axis=-1) @ hp["out_w"] + hp["out_b"]
        )[:, 0]
    bias = hp.get("item_bias")
    if bias is not None:
        score = score + bias
    score = score[:n_items]  # drop table padding rows
    k = min(k, n_items)
    top = np.argpartition(-score, k - 1)[:k]
    top = top[np.argsort(-score[top], kind="stable")]
    return score[top], top


@dataclass
class NCFModel:
    state: NCFState
    user_vocab: BiMap
    item_vocab: BiMap
    #: factor-sharded serving state (parallel.placement.BoundShards) when a
    #: ShardPlan was re-bound at deploy; None = single-device serving
    shards: Any = None

    def sanity_check(self):
        leaf = np.asarray(self.state.params["user_emb"])
        if not np.isfinite(leaf).all():
            raise SanityCheckError("NCF embeddings are not finite")

    @property
    def host_params(self) -> dict:
        """Lazily-materialized host (numpy) replica of the serving
        pytree, built once per deployed model for the solo-query path."""
        hp = getattr(self, "_host_params", None)
        if hp is None:
            hp = jax.tree.map(np.asarray, self.state.params)
            self._host_params = hp
        return hp


class NCFAlgorithm(Algorithm):
    """flavor P: the model trains AND can serve mesh-sharded; persistence
    gathers the pytree to host numpy (make_persistent_model)."""

    flavor = "P"
    params_class = NCFAlgorithmParams
    query_class = Query

    def __init__(self, params: NCFAlgorithmParams | None = None):
        self.params = params or NCFAlgorithmParams()

    def train(self, ctx: EngineContext, pd: PreparedData) -> NCFModel:
        p = self.params
        positives = pd.ratings >= p.positive_threshold
        if not positives.any():
            raise SanityCheckError(
                f"no positive interactions (rating >= {p.positive_threshold})"
            )
        mesh = ctx.mesh if ctx.mesh.devices.size > 1 else None
        # warm start from the previous generation's embedding tables (the
        # lifecycle controller's incremental retrain): the same §3.4.1
        # pretraining recipe, with last generation's trained tables in the
        # ALS pretrainer's role — takes precedence over re-running ALS
        initial = self._warm_start_initial(ctx, pd)
        if initial is None and p.pretrain == "als":
            from predictionio_tpu.ops.als import ALSParams, train_als

            als = train_als(
                pd.user_idx[positives],
                pd.item_idx[positives],
                np.ones(int(positives.sum()), np.float32),
                len(pd.user_vocab),
                len(pd.item_vocab),
                params=ALSParams(
                    rank=p.embed_dim, num_iterations=20, reg=0.01,
                    seed=p.seed, implicit_prefs=True, alpha=p.alpha,
                ),
                mesh=mesh,
            )
            initial = {
                "user_emb": np.asarray(als.user_factors),
                "item_emb": np.asarray(als.item_factors),
            }
        state = train_ncf(
            pd.user_idx[positives],
            pd.item_idx[positives],
            n_users=len(pd.user_vocab),
            n_items=len(pd.item_vocab),
            params=NCFParams(
                embed_dim=p.embed_dim,
                mlp_layers=tuple(p.mlp_layers),
                learning_rate=p.learning_rate,
                num_epochs=p.num_epochs,
                batch_size=p.batch_size,
                negatives_per_positive=p.negatives_per_positive,
                neg_power=p.neg_power,
                loss=p.loss,
                item_bias=p.item_bias,
                weight_decay=p.weight_decay,
                alpha=p.alpha,
                seed=p.seed,
            ),
            mesh=mesh,
            initial_params=initial,
        )
        return NCFModel(
            state=state, user_vocab=pd.user_vocab, item_vocab=pd.item_vocab
        )

    def _warm_start_initial(self, ctx: EngineContext, pd: PreparedData):
        """Previous-generation GMF/packed embedding tables mapped through
        the old→new vocab (core.warmstart) — None when absent or when the
        embedding width changed (cold start is always safe)."""
        from predictionio_tpu.core.warmstart import (
            align_warm_factors,
            find_warm_start,
        )

        prev = find_warm_start(
            ctx, ("params", "user_vocab", "item_vocab")
        )
        if prev is None or not isinstance(prev.get("params"), dict):
            return None
        params = prev["params"]
        user_emb = params.get("user_emb")
        item_emb = params.get("item_emb")
        if user_emb is None or item_emb is None:
            return None
        d = self.params.embed_dim
        user_emb = np.asarray(user_emb)
        item_emb = np.asarray(item_emb)
        if user_emb.ndim != 2 or user_emb.shape[1] < d or item_emb.shape[1] < d:
            return None
        rng = np.random.default_rng(self.params.seed)
        return {
            # the GMF half packs first ([:, :d]) in the packed layout, so
            # slicing recovers it from either a pure-GMF or packed table
            "user_emb": align_warm_factors(
                user_emb[:, :d], BiMap.from_state(prev["user_vocab"]),
                pd.user_vocab, rng,
            ),
            "item_emb": align_warm_factors(
                item_emb[:, :d], BiMap.from_state(prev["item_vocab"]),
                pd.item_vocab, rng,
            ),
        }

    def predict(self, model: NCFModel, query: Query) -> PredictedResult:
        """Solo query from the HOST replica: no device dispatch, so no
        per-query device round trip (the wave path in batch_predict stays
        on device, where batching amortizes it).  Repeat users serve their
        embedding row from the per-model factor cache — the vocab + table
        gather is skipped entirely on a hit (flight gather stage ~ 0)."""
        from predictionio_tpu.parallel import device_cache

        provenance.note(engine_path="ncf.host_replica")
        cache = device_cache.model_cache(model)
        hit = cache.get(query.user)
        if hit is None:
            with device_obs.wave_stage("host_gather"):
                uidx = model.user_vocab.get(query.user)
                if uidx is None:
                    provenance.note(unknown_entity=query.user)
                    return PredictedResult()
                uidx = int(uidx)
                # host_params is the numpy replica: a row .copy() here is
                # a 40-byte memcpy, not a device sync
                ue = model.host_params["user_emb"][uidx].copy()
            cache.put(query.user, (uidx, ue))
        else:
            uidx, ue = hit
            device_obs.note_cache_hit()
        n_items = len(model.item_vocab)
        k = min(query.num, n_items)
        scores, items = _host_score_topk(
            model.host_params, uidx, n_items, k, ue=ue
        )
        return PredictedResult(
            item_scores=tuple(
                ItemScore(item=model.item_vocab.inverse(int(i)), score=float(s))
                for s, i in zip(scores, items)
                if np.isfinite(s)
            )
        )

    #: device dispatch width for batch serving; bulk callers (batchpredict
    #: jobs, evaluation folds) are chunked to this so the vmapped MLP
    #: activations stay [32, n_items, hidden] regardless of input size
    MAX_WAVE = 32

    def batch_predict(self, model: NCFModel, indexed_queries):
        """Vectorized wave serving: one device dispatch per MAX_WAVE chunk
        (queries with different ``num`` or unknown users are handled
        per-row on the host after the shared top-k)."""
        iq = list(indexed_queries)
        out = []
        for c0 in range(0, len(iq), self.MAX_WAVE):
            out.extend(self._predict_wave(model, iq[c0 : c0 + self.MAX_WAVE]))
        return out

    # -- sharded serving (parallel.placement) --------------------------------

    def serving_shard_plan(self, model: NCFModel):
        """Embedding tables (and the per-item bias) row-sharded over the
        ``model`` axis; the MLP head replicates.  Recorded in the persisted
        model + generation manifest; deploy re-binds it."""
        if not self.params.shard_serving:
            return None
        from predictionio_tpu.parallel.placement import ShardPlan

        sharded = ["user_emb", "item_emb"]
        ndims = {}
        if model.state.params.get("item_bias") is not None:
            sharded.append("item_bias")
            ndims["item_bias"] = 1
        return ShardPlan.model_parallel(
            sharded,
            rows={
                "user_emb": len(model.user_vocab),
                "item_emb": len(model.item_vocab),
                "item_bias": len(model.item_vocab),
            },
            ndims=ndims,
        )

    def _sharded_packed_topk(self, model: NCFModel, padded, n_items, k, b):
        """The sharded wave kernel: collective user-row lookup from the
        sharded user table, then per-shard MLP scoring over ONLY the item
        rows each device owns + k-winner merge (no device ever builds a
        [B, n_items] score row — per-shard shapes are recorded in
        ``placement.LAST_KERNEL_SHAPES['ncf.sharded_topk']``)."""
        from predictionio_tpu.ops.ncf import score_users_vs_items
        from predictionio_tpu.parallel.placement import (
            build_sharded_topk,
            gather_rows,
            run_observed_wave,
        )

        bound = model.shards
        sig = (b, k, n_items, bound.n_shards) + tuple(
            bound.arrays["user_emb"].shape
        )
        has_bias = bound.arrays.get("item_bias") is not None
        head = {
            n: bound.arrays[n]
            for n in ("mlp", "out_w", "out_b")
            if n in bound.arrays
        }

        def build():
            if has_bias:
                local = lambda item_emb, item_bias, h, q: (  # noqa: E731
                    score_users_vs_items(h, q, item_emb, item_bias)
                )
                names = ["item_emb", "item_bias", "__head__"]
            else:
                local = lambda item_emb, h, q: (  # noqa: E731
                    score_users_vs_items(h, q, item_emb, None)
                )
                names = ["item_emb", "__head__"]
            return build_sharded_topk(
                bound.mesh, bound.plan, local, names,
                n_items=n_items, k=k, name="ncf.sharded_topk",
            )

        kernel = bound.kernel((b, k), build)
        args = (bound.arrays["item_emb"],) + (
            (bound.arrays["item_bias"],) if has_bias else ()
        )

        def compute(users_dev):
            q_rows = gather_rows(
                bound.mesh, bound.arrays["user_emb"], users_dev
            )
            packed_dev = kernel(*args, head, q_rows)
            return packed_dev, args + (head, q_rows)

        return run_observed_wave(
            "ncf.sharded_topk",
            kernel=kernel,
            sig=sig,
            host_input=padded,
            compute=compute,
            shard_arrays={
                n: bound.arrays[n] for n in bound.plan.specs
                if bound.arrays.get(n) is not None
            },
        )

    def _predict_wave(self, model: NCFModel, iq):
        if not iq:
            return []
        if model.shards is None:
            # the synchronous wave IS the async half fenced immediately:
            # ONE copy of the dispatch logic (gather, pow2 menu,
            # signature, h2d, cost capture) serves both the pipelined and
            # inline paths, so they can never silently diverge.  The wave
            # is <= MAX_WAVE and unsharded here, so dispatch never
            # declines.
            return self.dispatch_batch(model, iq)()
        provenance.note(engine_path="ncf.sharded_topk")
        n_items = _packable_n_items(model)
        with device_obs.wave_stage("host_gather"):
            uidx = np.array(
                [model.user_vocab.get(q.user, -1) for _, q in iq], np.int32
            )
            # round BOTH static shapes up to powers of two (b >= 32,
            # k >= 16): a novel client `num` or odd wave size must never
            # trigger a fresh XLA compile mid-serving — results are sliced
            # per query below
            want_k = min(max(q.num for _, q in iq), n_items)
            k = min(max(1 << (want_k - 1).bit_length(), 16), n_items)
            b = max(1 << (len(iq) - 1).bit_length(), 32)
            padded = np.zeros(b, np.int32)
            padded[: len(iq)] = np.maximum(uidx, 0)
        packed = self._sharded_packed_topk(model, padded, n_items, k, b)
        return self._render_wave(model, iq, uidx, packed)

    def _render_wave(self, model: NCFModel, iq, uidx, packed):
        top_s = packed[0]
        top_i = packed[1].astype(np.int64)
        out = []
        for row, (i, q) in enumerate(iq):
            if uidx[row] < 0:
                out.append((i, PredictedResult()))
                continue
            out.append(
                (
                    i,
                    PredictedResult(
                        item_scores=tuple(
                            ItemScore(
                                item=model.item_vocab.inverse(int(ii)),
                                score=float(ss),
                            )
                            for ss, ii in zip(
                                top_s[row][: q.num], top_i[row][: q.num]
                            )
                            if np.isfinite(ss)
                        )
                    ),
                )
            )
        return out

    def dispatch_batch(self, model: NCFModel, indexed_queries):
        """The MicroBatcher pipeline's async half: vocab gather + pow2
        padding + h2d + the wave kernel dispatch run NOW without blocking;
        the returned finalize fences (block_until_ready), reads the packed
        winners back, and renders.  Declines (None) for sharded serving
        (the settle clock is synchronous) and waves past MAX_WAVE."""
        iq = list(indexed_queries)
        if not iq or len(iq) > self.MAX_WAVE or model.shards is not None:
            return None
        provenance.note(engine_path="ncf.device_wave")
        n_items = _packable_n_items(model)
        with device_obs.wave_stage("host_gather"):
            uidx = np.array(
                [model.user_vocab.get(q.user, -1) for _, q in iq], np.int32
            )
            want_k = min(max(q.num for _, q in iq), n_items)
            k = min(max(1 << (want_k - 1).bit_length(), 16), n_items)
            b = max(1 << (len(iq) - 1).bit_length(), 32)
            padded = np.zeros(b, np.int32)
            padded[: len(iq)] = np.maximum(uidx, 0)
        eff = device_obs.default_efficiency()
        sig = (b, k, n_items) + tuple(model.state.params["user_emb"].shape)
        device_obs.default_recompiles().note_signature(
            "ncf.batch_predict", sig
        )
        with device_obs.wave_stage("h2d"):
            users_dev = jnp.asarray(padded)
            device_obs.note_transfer("h2d", padded.nbytes)
        eff.capture_cost(
            "ncf.batch_predict", _score_topk_batch, model.state.params,
            users_dev, n_items, k, signature=sig, defer=True,
        )
        t_dev = time.perf_counter()
        packed_dev = _score_topk_batch(model.state.params, users_dev,
                                       n_items, k)

        def finalize():
            with device_obs.wave_stage("compute"):
                packed_dev.block_until_ready()
            # dispatch-to-ready: under pipelining this window overlaps the
            # NEXT wave's dispatch — that overlap IS the win the stage
            # clocks prove
            compute_s = time.perf_counter() - t_dev
            device_obs.note_wave_device(device_obs.device_label(packed_dev))
            device_obs.note_wave_cost(
                "ncf.batch_predict",
                eff.cached_cost("ncf.batch_predict", sig),
            )
            with device_obs.wave_stage("d2h"):
                packed = np.asarray(packed_dev)
                device_obs.note_transfer("d2h", packed.nbytes)
            eff.observe("ncf.batch_predict", compute_s, signature=sig)
            return self._render_wave(model, iq, uidx, packed)

        return finalize

    def make_persistent_model(self, ctx: EngineContext, model: NCFModel):
        out = {
            "params": jax.tree_util.tree_map(
                lambda x: np.asarray(jax.device_get(x)), model.state.params
            ),
            "n_users": model.state.n_users,
            "n_items": model.state.n_items,
            "config": model.state.config,
            "user_vocab": model.user_vocab.to_state(),
            "item_vocab": model.item_vocab.to_state(),
        }
        plan = self.serving_shard_plan(model)
        if plan is not None:
            out["shard_plan"] = plan.to_dict()
        return out

    def load_persistent_model(self, ctx: EngineContext, data) -> NCFModel:
        params = data["params"]
        if "user_gmf" in params:
            # migrate pre-packed checkpoints (four [n, d] tables) into the
            # packed [n, 2d] layout so older saved models keep deploying
            params = {
                "user_emb": np.concatenate(
                    [params["user_gmf"], params["user_mlp"]], axis=1
                ),
                "item_emb": np.concatenate(
                    [params["item_gmf"], params["item_mlp"]], axis=1
                ),
                "mlp": params["mlp"],
                "out_w": params["out_w"],
                "out_b": params["out_b"],
            }
        from predictionio_tpu.parallel.placement import (
            ShardPlan,
            bind_shards,
        )

        plan = ShardPlan.from_dict(data.get("shard_plan"))
        if plan is not None and len(jax.devices()) > 1:
            # re-bind the recorded layout onto the CURRENT mesh: tables
            # shard, the MLP head replicates.  ``state.params`` stays a
            # HOST pytree (solo path + sanity checks); the sharded device
            # copies live in ``shards``.
            host = jax.tree_util.tree_map(np.asarray, params)
            shards = bind_shards(plan, host)
            from predictionio_tpu.parallel.mesh import meter_shards

            meter_shards(
                "ncf.serving_tables",
                {n: shards.arrays[n] for n in plan.specs
                 if shards.arrays.get(n) is not None},
            )
            return NCFModel(
                state=NCFState(
                    params=host,
                    n_users=data["n_users"],
                    n_items=data["n_items"],
                    config=data["config"],
                ),
                user_vocab=BiMap.from_state(data["user_vocab"]),
                item_vocab=BiMap.from_state(data["item_vocab"]),
                shards=shards,
            )
        return NCFModel(
            state=NCFState(
                params=jax.tree_util.tree_map(jnp.asarray, params),
                n_users=data["n_users"],
                n_items=data["n_items"],
                config=data["config"],
            ),
            user_vocab=BiMap.from_state(data["user_vocab"]),
            item_vocab=BiMap.from_state(data["item_vocab"]),
        )


@engine_factory("ncf")
def ncf_engine() -> Engine:
    return Engine(
        RatingsDataSource,
        RatingsPreparator,
        {"ncf": NCFAlgorithm},
        RecommendationServing,
    )
