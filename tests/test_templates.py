"""Template parity tests: similarproduct, classification, ecommerce.

Each mirrors the reference template's data shapes
(examples/scala-parallel-*): $set entity events + interaction events in real
storage, full train through the Engine, and business-rule assertions on
predict.
"""

import numpy as np
import pytest

from predictionio_tpu.core.base import EngineContext
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.tools import commands as cmd


def _insert(storage, app_id, events):
    storage.l_events().insert_batch(events, app_id)


def _set_event(etype, eid, props=None):
    return Event(
        event="$set",
        entity_type=etype,
        entity_id=eid,
        properties=DataMap(props or {}),
    )


def _interaction(event, user, item, props=None):
    return _interaction_t(event, user, "item", item, props)


def _interaction_t(event, user, target_type, target_id, props=None):
    return Event(
        event=event,
        entity_type="user",
        entity_id=user,
        target_entity_type=target_type,
        target_entity_id=target_id,
        properties=DataMap(props or {}),
    )


@pytest.fixture()
def similar_app(storage):
    d = cmd.app_new(storage, "similar")
    rng = np.random.default_rng(7)
    events = []
    for u in range(12):
        events.append(_set_event("user", f"u{u}"))
    for i in range(10):
        cat = "catA" if i < 5 else "catB"
        events.append(_set_event("item", f"i{i}", {"categories": [cat]}))
    # two taste clusters: users 0-5 view items 0-4, users 6-11 view items 5-9
    for u in range(12):
        base = 0 if u < 6 else 5
        for i in range(5):
            events.append(_interaction("view", f"u{u}", f"i{base + i}"))
    _insert(storage, d.app.id, events)
    return storage


class TestSimilarProduct:
    def _train(self, storage, algo="als", algo_params=None):
        from predictionio_tpu.models.similarproduct import similarproduct_engine

        engine = similarproduct_engine()
        params = engine.params_from_json(
            {
                "datasource": {"params": {"appName": "similar"}},
                "algorithms": [{"name": algo, "params": algo_params or {}}],
            }
        )
        ctx = EngineContext(storage=storage)
        _, _, algos, _ = engine.instantiate(params)
        models = engine.train(ctx, params)
        return algos[0], models[0]

    def test_als_clusters(self, similar_app):
        from predictionio_tpu.models.similarproduct import Query

        algo, model = self._train(
            similar_app, "als", {"rank": 6, "numIterations": 10}
        )
        result = algo.predict(model, Query(items=("i0",), num=4))
        assert result.item_scores
        # similar items come from the same taste cluster (items 1-4)
        top = {s.item for s in result.item_scores[:2]}
        assert top <= {"i1", "i2", "i3", "i4"}
        # query item itself is excluded
        assert "i0" not in {s.item for s in result.item_scores}

    def test_category_filters(self, similar_app):
        from predictionio_tpu.models.similarproduct import Query

        algo, model = self._train(
            similar_app, "als", {"rank": 6, "numIterations": 10}
        )
        result = algo.predict(
            model, Query(items=("i0",), num=8, categories=("catA",))
        )
        assert all(s.item in {"i1", "i2", "i3", "i4"} for s in result.item_scores)
        result = algo.predict(
            model, Query(items=("i0",), num=8, category_black_list=("catA",))
        )
        assert all(s.item.startswith("i") and int(s.item[1:]) >= 5
                   for s in result.item_scores)

    def test_white_black_lists(self, similar_app):
        from predictionio_tpu.models.similarproduct import Query

        algo, model = self._train(
            similar_app, "als", {"rank": 6, "numIterations": 10}
        )
        result = algo.predict(
            model, Query(items=("i0",), num=8, white_list=("i1", "i2"))
        )
        assert {s.item for s in result.item_scores} <= {"i1", "i2"}
        result = algo.predict(
            model, Query(items=("i0",), num=8, black_list=("i1",))
        )
        assert "i1" not in {s.item for s in result.item_scores}

    def test_unknown_items_empty(self, similar_app):
        from predictionio_tpu.models.similarproduct import Query

        algo, model = self._train(similar_app, "als", {"numIterations": 2})
        assert algo.predict(model, Query(items=("nope",))).item_scores == ()

    def test_cooccurrence(self, similar_app):
        from predictionio_tpu.models.similarproduct import Query

        algo, model = self._train(similar_app, "cooccurrence", {"n": 5})
        result = algo.predict(model, Query(items=("i0",), num=4))
        # co-viewed with i0 by cluster-1 users: i1..i4, each 6 co-viewers
        assert {s.item for s in result.item_scores} == {"i1", "i2", "i3", "i4"}
        assert all(s.score == 6.0 for s in result.item_scores)

    def test_persistence_roundtrip(self, similar_app):
        from predictionio_tpu.models.similarproduct import Query

        algo, model = self._train(similar_app, "als", {"numIterations": 3})
        ctx = EngineContext(storage=similar_app)
        blob = algo.make_persistent_model(ctx, model)
        loaded = algo.load_persistent_model(ctx, blob)
        q = Query(items=("i0",), num=3)
        assert [s.item for s in algo.predict(model, q).item_scores] == [
            s.item for s in algo.predict(loaded, q).item_scores
        ]


@pytest.fixture()
def classification_app(storage):
    d = cmd.app_new(storage, "cls")
    rng = np.random.default_rng(11)
    events = []
    # multinomial NB is scale-invariant: classes must differ in feature
    # *proportions*, so give each label a distinct dominant attribute
    for n in range(60):
        label = float(n % 2)
        center = np.array([8.0, 1.0, 1.0]) if label else np.array([1.0, 1.0, 8.0])
        attrs = np.clip(rng.normal(center, 0.5), 0.1, None)
        events.append(
            _set_event(
                "user",
                f"u{n}",
                {
                    "plan": label,
                    "attr0": float(attrs[0]),
                    "attr1": float(attrs[1]),
                    "attr2": float(attrs[2]),
                },
            )
        )
    _insert(storage, d.app.id, events)
    return storage


class TestClassification:
    def _train(self, storage, algo, algo_params=None):
        from predictionio_tpu.models.classification import classification_engine

        engine = classification_engine()
        params = engine.params_from_json(
            {
                "datasource": {"params": {"appName": "cls"}},
                "algorithms": [{"name": algo, "params": algo_params or {}}],
            }
        )
        ctx = EngineContext(storage=storage)
        _, _, algos, _ = engine.instantiate(params)
        return algos[0], engine.train(ctx, params)[0]

    def test_naive_bayes_separates(self, classification_app):
        from predictionio_tpu.models.classification import Query

        algo, model = self._train(classification_app, "naive", {"lambda": 1.0})
        assert algo.predict(model, Query(8.0, 1.0, 1.0)).label == 1.0
        assert algo.predict(model, Query(1.0, 1.0, 8.0)).label == 0.0

    def test_logreg_separates(self, classification_app):
        from predictionio_tpu.models.classification import Query

        algo, model = self._train(classification_app, "logreg")
        assert algo.predict(model, Query(8.0, 1.0, 1.0)).label == 1.0
        assert algo.predict(model, Query(1.0, 1.0, 8.0)).label == 0.0

    def test_evaluation_sweep(self, classification_app):
        """Accuracy metric + lambda sweep (reference Evaluation.scala)."""
        from predictionio_tpu.core.base import EngineContext
        from predictionio_tpu.core.workflow import run_evaluation
        from predictionio_tpu.eval.evaluator import MetricEvaluator
        from predictionio_tpu.models.classification import (
            Accuracy,
            classification_engine,
            engine_params_list,
        )

        result = run_evaluation(
            classification_engine(),
            engine_params_list(app_name="cls", eval_k=3, lams=(1.0, 100.0)),
            MetricEvaluator(Accuracy()),
            ctx=EngineContext(storage=classification_app, mode="eval"),
            storage=classification_app,
        )
        assert len(result.records) == 2
        assert result.best.score > 0.8
        # the evaluation instance row was persisted
        done = classification_app.evaluation_instances().get_completed()
        assert len(done) == 1 and "Accuracy" in done[0].evaluator_results

    def test_persistence_roundtrip(self, classification_app):
        from predictionio_tpu.models.classification import Query

        ctx = EngineContext(storage=classification_app)
        for name in ("naive", "logreg"):
            algo, model = self._train(classification_app, name)
            loaded = algo.load_persistent_model(
                ctx, algo.make_persistent_model(ctx, model)
            )
            q = Query(7.0, 1.0, 2.0)
            assert algo.predict(model, q).label == algo.predict(loaded, q).label


@pytest.fixture()
def ecomm_app(storage):
    d = cmd.app_new(storage, "ecomm")
    events = []
    for u in range(10):
        events.append(_set_event("user", f"u{u}"))
    for i in range(8):
        cat = "electronics" if i < 4 else "books"
        events.append(_set_event("item", f"i{i}", {"categories": [cat]}))
    # cluster taste: users 0-4 view/buy items 0-3; users 5-9 view items 4-7
    for u in range(10):
        base = 0 if u < 5 else 4
        for i in range(4):
            events.append(_interaction("view", f"u{u}", f"i{base + i}"))
    for u in range(5):
        events.append(_interaction("buy", f"u{u}", "i0"))
    _insert(storage, d.app.id, events)
    return storage, d


class TestECommerce:
    def _train(self, storage, extra=None):
        from predictionio_tpu.models.ecommerce import ecommerce_engine

        engine = ecommerce_engine()
        params = engine.params_from_json(
            {
                "datasource": {"params": {"appName": "ecomm"}},
                "algorithms": [
                    {
                        "name": "ecomm",
                        "params": {
                            "appName": "ecomm",
                            "rank": 6,
                            "numIterations": 8,
                            **(extra or {}),
                        },
                    }
                ],
            }
        )
        ctx = EngineContext(storage=storage)
        _, _, algos, _ = engine.instantiate(params)
        return algos[0], engine.train(ctx, params)[0]

    def test_known_user_unseen_only(self, ecomm_app):
        storage, _ = ecomm_app
        from predictionio_tpu.models.ecommerce import Query

        algo, model = self._train(storage)
        result = algo.predict(model, Query(user="u0", num=8))
        # u0 has seen i0-i3 (view) — unseenOnly blacklists them
        seen = {"i0", "i1", "i2", "i3"}
        assert result.item_scores
        assert not ({s.item for s in result.item_scores} & seen)

    def test_unavailable_items_constraint(self, ecomm_app):
        storage, d = ecomm_app
        from predictionio_tpu.models.ecommerce import Query

        algo, model = self._train(storage, {"unseenOnly": False})
        storage.l_events().insert(
            Event(
                event="$set",
                entity_type="constraint",
                entity_id="unavailableItems",
                properties=DataMap({"items": ["i1", "i2"]}),
            ),
            d.app.id,
        )
        result = algo.predict(model, Query(user="u0", num=8))
        assert not ({s.item for s in result.item_scores} & {"i1", "i2"})

    def test_cold_user_similar_fallback(self, ecomm_app):
        storage, d = ecomm_app
        from predictionio_tpu.models.ecommerce import Query

        algo, model = self._train(storage)
        # coldu has view events but no $set → not in the user vocab
        storage.l_events().insert(
            _interaction("view", "coldu", "i4"), d.app.id
        )
        result = algo.predict(model, Query(user="coldu", num=3))
        assert result.item_scores  # predictSimilar path answered
        assert "i4" not in {s.item for s in result.item_scores}  # seen → excluded

    def test_unknown_user_popularity_fallback(self, ecomm_app):
        storage, _ = ecomm_app
        from predictionio_tpu.models.ecommerce import Query

        algo, model = self._train(storage, {"unseenOnly": False})
        result = algo.predict(model, Query(user="nobody", num=3))
        # i0 is the only bought item → top popularity
        assert result.item_scores[0].item == "i0"
        assert result.item_scores[0].score == 5.0

    def test_category_filter(self, ecomm_app):
        storage, _ = ecomm_app
        from predictionio_tpu.models.ecommerce import Query

        algo, model = self._train(storage, {"unseenOnly": False})
        result = algo.predict(
            model, Query(user="u0", num=8, categories=("books",))
        )
        assert result.item_scores
        assert {s.item for s in result.item_scores} <= {"i4", "i5", "i6", "i7"}


class TestLikeAlgorithm:
    def test_dislike_is_negative_signal(self, storage):
        """Latest like/dislike wins; dislikes train as preference-0
        (LikeAlgorithm.scala -> MLlib trainImplicit negative rating)."""
        from predictionio_tpu.models.similarproduct import (
            Query,
            similarproduct_engine,
        )

        d = cmd.app_new(storage, "similar")
        events = []
        for u in range(8):
            events.append(_set_event("user", f"u{u}"))
        for i in range(6):
            events.append(_set_event("item", f"i{i}"))
        # everyone likes i0+i1; i2 is liked then disliked by the same users
        for u in range(8):
            events.append(_interaction("like", f"u{u}", "i0"))
            events.append(_interaction("like", f"u{u}", "i1"))
            events.append(_interaction("like", f"u{u}", "i2"))
            events.append(_interaction("dislike", f"u{u}", "i2"))
        for u in range(4):
            events.append(_interaction("like", f"u{u}", "i3"))
        _insert(storage, d.app.id, events)

        engine = similarproduct_engine()
        params = engine.params_from_json(
            {
                "datasource": {
                    "params": {
                        "appName": "similar",
                        "eventNames": ["like", "dislike"],
                    }
                },
                "algorithms": [
                    {"name": "likealgo", "params": {"rank": 4, "numIterations": 10}}
                ],
            }
        )
        ctx = EngineContext(storage=storage)
        _, _, algos, _ = engine.instantiate(params)
        model = engine.train(ctx, params)[0]
        result = algos[0].predict(model, Query(items=("i0",), num=5))
        items = [s.item for s in result.item_scores]
        # i1 (liked by all) must outrank i2 (disliked by all, latest event)
        assert "i1" in items
        assert "i2" not in items[:1]


class TestRecommendedUser:
    """recommended-user variant: similar USERS for a set of users, trained
    on user-views-USER events with the target-side factors as viewed-user
    features (examples/scala-parallel-similarproduct/recommended-user)."""

    @pytest.fixture()
    def social_app(self, storage):
        d = cmd.app_new(storage, "social")
        events = [_set_event("user", f"u{u}") for u in range(12)]
        # two communities: users 0-5 view each other, users 6-11 likewise
        for u in range(12):
            lo = 0 if u < 6 else 6
            for v in range(lo, lo + 6):
                if v != u:
                    events.append(
                        _interaction_t("view", f"u{u}", "user", f"u{v}")
                    )
        _insert(storage, d.app.id, events)
        return storage

    def _train(self, storage):
        from predictionio_tpu.models.similarproduct import recommendeduser_engine

        engine = recommendeduser_engine()
        params = engine.params_from_json(
            {
                "datasource": {"params": {"appName": "social",
                                          "targetEntityType": "user"}},
                "algorithms": [
                    {"name": "als",
                     "params": {"rank": 6, "numIterations": 10}}
                ],
            }
        )
        ctx = EngineContext(storage=storage)
        _, _, algos, _ = engine.instantiate(params)
        models = engine.train(ctx, params)
        return algos[0], models[0]

    def test_similar_users_from_same_community(self, social_app):
        from predictionio_tpu.models.similarproduct import UserQuery

        algo, model = self._train(social_app)
        result = algo.predict(model, UserQuery(users=("u0",), num=4))
        assert result.item_scores
        top = {s.item for s in result.item_scores[:3]}
        assert top <= {f"u{n}" for n in range(1, 6)}, top
        # query user never recommended back
        assert "u0" not in {s.item for s in result.item_scores}
        # only positive similarities are returned (reference score>0 filter)
        assert all(s.score > 0 for s in result.item_scores)

    def test_black_and_white_lists(self, social_app):
        from predictionio_tpu.models.similarproduct import UserQuery

        algo, model = self._train(social_app)
        r = algo.predict(
            model, UserQuery(users=("u0",), num=6, black_list=("u1", "u2"))
        )
        assert {"u1", "u2"}.isdisjoint({s.item for s in r.item_scores})
        r = algo.predict(
            model, UserQuery(users=("u0",), num=6, white_list=("u3", "u4"))
        )
        assert {s.item for s in r.item_scores} <= {"u3", "u4"}

    def test_unknown_users_empty(self, social_app):
        from predictionio_tpu.models.similarproduct import UserQuery

        algo, model = self._train(social_app)
        assert algo.predict(model, UserQuery(users=("nope",))).item_scores == ()

    def test_persistence_roundtrip(self, social_app):
        from predictionio_tpu.models.similarproduct import UserQuery

        algo, model = self._train(social_app)
        data = algo.make_persistent_model(None, model)
        loaded = algo.load_persistent_model(None, data)
        a = algo.predict(model, UserQuery(users=("u7",), num=3))
        b = algo.predict(loaded, UserQuery(users=("u7",), num=3))
        assert [s.item for s in a.item_scores] == [s.item for s in b.item_scores]


#: every ``params_aliases`` table at commit af3b99e: the JSON spellings the
#: engines' params took then.  All but ``lambda`` are the camelCase of their
#: field, which ``extract_params`` now derives (ISSUE 44)
SPELLINGS = {
    "similarproduct.Query": {
        "categoryBlackList": "category_black_list", "whiteList": "white_list",
        "blackList": "black_list"},
    "similarproduct.DataSourceParams": {
        "appName": "app_name", "channelName": "channel_name",
        "eventNames": "event_names", "targetEntityType": "target_entity_type"},
    "similarproduct.ALSAlgorithmParams": {
        "numIterations": "num_iterations", "lambda": "reg"},
    "similarproduct.UserQuery": {
        "whiteList": "white_list", "blackList": "black_list"},
    "sequence.SequenceDataSourceParams": {
        "appName": "app_name", "channelName": "channel_name",
        "eventNames": "event_names", "entityType": "entity_type",
        "targetEntityType": "target_entity_type"},
    "sequence.SequencePreparatorParams": {
        "rowLen": "row_len", "maxLen": "max_len", "rowsPerStep": "rows_per_step",
        "vocabSize": "vocab_size", "vocabStart": "vocab_start"},
    "sequence.SequenceAlgorithmParams": {
        "moeSharedExpertColumns": "moe_shared_expert_columns",
        "routedScalingFactor": "routed_scaling_factor",
        "totalUtSteps": "total_ut_steps", "exitBeta": "exit_beta",
        "moeNumPrimaryExperts": "moe_num_primary_experts",
        "moeExpertsHeld": "moe_experts_held", "moeExpertStart": "moe_expert_start",
        "moeNumActivePrimaryExperts": "moe_num_active_primary_experts",
        "moeFfnHiddenSize": "moe_ffn_hidden_size",
        "slidingWindowSize": "sliding_window_size",
        "numKeyValueHeads": "num_key_value_heads", "ropeTheta": "rope_theta",
        "mambaNHeads": "mamba_n_heads", "mambaNGroups": "mamba_n_groups",
        "mambaDHead": "mamba_d_head", "mambaDState": "mamba_d_state",
        "mambaDConv": "mamba_d_conv", "mambaChunkSize": "mamba_chunk_size",
        "embeddingMultiplier": "embedding_multiplier",
        "lmHeadMultiplier": "lm_head_multiplier",
        "ssmInMultiplier": "ssm_in_multiplier", "ssmMultipliers": "ssm_multipliers",
        "ssmOutMultiplier": "ssm_out_multiplier",
        "attentionInMultiplier": "attention_in_multiplier",
        "attentionOutMultiplier": "attention_out_multiplier",
        "keyMultiplier": "key_multiplier", "mlpMultipliers": "mlp_multipliers",
        "hiddenSize": "hidden_size", "layerTypes": "layer_types",
        "numAttentionHeads": "num_attention_heads", "headDim": "head_dim",
        "linearNumHeads": "linear_num_heads",
        "linearKeyHeadDim": "linear_key_head_dim",
        "linearValueHeadDim": "linear_value_head_dim",
        "linearConvKernelDim": "linear_conv_kernel_dim",
        "linearAllowNegEigval": "linear_allow_neg_eigval",
        "intermediateSize": "intermediate_size", "vocabSize": "vocab_size",
        "vocabStart": "vocab_start", "rmsNormEps": "rms_norm_eps",
        "rowsPerStep": "rows_per_step", "stepsPerRetrain": "steps_per_retrain",
        "learningRate": "learning_rate", "adamEps": "adam_eps",
        "weightDecay": "weight_decay"},
    "ncf.NCFAlgorithmParams": {
        "embedDim": "embed_dim", "mlpLayers": "mlp_layers",
        "learningRate": "learning_rate", "numEpochs": "num_epochs",
        "batchSize": "batch_size", "positiveThreshold": "positive_threshold",
        "negativesPerPositive": "negatives_per_positive", "negPower": "neg_power",
        "itemBias": "item_bias", "weightDecay": "weight_decay",
        "shardServing": "shard_serving"},
    "ecommerce.Query": {"whiteList": "white_list", "blackList": "black_list"},
    "ecommerce.DataSourceParams": {
        "appName": "app_name", "channelName": "channel_name",
        "eventNames": "event_names"},
    "ecommerce.ECommAlgorithmParams": {
        "appName": "app_name", "unseenOnly": "unseen_only",
        "seenEvents": "seen_events", "similarEvents": "similar_events",
        "numIterations": "num_iterations", "lambda": "reg",
        "trainEvents": "train_events"},
    "recommendation.DataSourceParams": {
        "appName": "app_name", "channelName": "channel_name",
        "evalParams": "eval_params"},
    "recommendation.ALSAlgorithmParams": {
        "lambda": "reg", "numIterations": "num_iterations",
        "shardServing": "shard_serving"},
    "external.ExternalAlgorithmParams": {"featureColumns": "feature_columns"},
    "classification.DataSourceParams": {"appName": "app_name", "evalK": "eval_k"},
    "classification.NaiveBayesParams": {"lambda": "lam"},
    "classification.LogisticRegressionParams": {
        "learningRate": "learning_rate", "numIterations": "num_iterations",
        "lambda": "reg"},
}


def _sample(hint):
    """A JSON value ``extract_params`` coerces to ``hint``."""
    import dataclasses
    import types
    import typing

    origin, args = typing.get_origin(hint), typing.get_args(hint)
    if origin in (typing.Union, types.UnionType):
        return _sample(next(a for a in args if a is not type(None)))
    if origin in (list, tuple, set):
        return [_sample(args[0])] if args else []
    if dataclasses.is_dataclass(hint):
        return {}
    # 8192: a row length and a history length the sequence Preparator takes
    return {bool: True, int: 8192, float: 0.5, str: "a"}.get(hint, "a")


@pytest.mark.parametrize("where", SPELLINGS)
def test_every_json_spelling_an_engine_took_still_lands_in_its_field(where):
    import dataclasses
    import importlib

    from predictionio_tpu.utils.params import ParamsError, _class_info, extract_params

    module, name = where.split(".")
    cls = getattr(
        importlib.import_module(f"predictionio_tpu.models.{module}.engine"), name)
    hints = _class_info(cls)[1]
    required = {
        f.name: _sample(hints[f.name]) for f in dataclasses.fields(cls)
        if f.default is dataclasses.MISSING
        and f.default_factory is dataclasses.MISSING}
    changed = 0
    for json_name, field in SPELLINGS[where].items():
        value = _sample(hints[field])
        got = extract_params(cls, {**required, json_name: value})
        assert got == extract_params(cls, {**required, field: value}), json_name
        changed += got != extract_params(cls, required)
    assert changed  # a sample is not every field's default: the value was read
    with pytest.raises(ParamsError, match="unknown fields"):
        extract_params(cls, {**required, "noSuchField": 1})
