"""Classification engine template.

Parity with examples/scala-parallel-classification/add-algorithm: user
entities carry ``$set`` properties attr0/attr1/attr2 (features) and ``plan``
(label); ``naive`` is MLlib-semantics multinomial Naive Bayes
(NaiveBayesAlgorithm.scala:40-56) on segment-sum statistics, and ``logreg``
(softmax regression, a compiled lax.scan GD loop) stands in for the
reference's RandomForest as the second algorithm.

Query {attr0, attr1, attr2} -> PredictedResult(label).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import numpy as np

import jax.numpy as jnp

from predictionio_tpu.core.base import (
    Algorithm,
    DataSource,
    EngineContext,
    IdentityPreparator,
    SanityCheckError,
    Serving,
)
from predictionio_tpu.core.engine import Engine, engine_factory
from predictionio_tpu.ops.classifiers import (
    LogisticRegressionModel,
    NaiveBayesModel,
    logreg_scores,
    naive_bayes_scores,
    train_logistic_regression,
    train_naive_bayes,
)


@dataclass(frozen=True)
class Query:
    attr0: float = 0.0
    attr1: float = 0.0
    attr2: float = 0.0


@dataclass(frozen=True)
class PredictedResult:
    label: float

    def to_json_dict(self) -> dict[str, Any]:
        return {"label": self.label}


@dataclass(frozen=True)
class ActualResult:
    label: float


@dataclass
class TrainingData:
    features: np.ndarray  # [n, 3] float32
    labels: np.ndarray  # [n] float32

    def sanity_check(self):
        if len(self.labels) == 0:
            raise SanityCheckError(
                "no labeled points — need $set user events with "
                "plan/attr0/attr1/attr2 properties"
            )


PreparedData = TrainingData


@dataclass(frozen=True)
class DataSourceParams:
    app_name: str = "default"
    eval_k: int | None = None


_ATTRS = ("attr0", "attr1", "attr2")


class ClassificationDataSource(DataSource):
    params_class = DataSourceParams

    def __init__(self, params: DataSourceParams | None = None):
        self.params = params or DataSourceParams()

    def _read(self, ctx: EngineContext) -> TrainingData:
        props = ctx.p_event_store.aggregate_properties(
            self.params.app_name, "user", required=["plan", *_ATTRS]
        )
        rows = sorted(props.items())
        feats = np.array(
            [[float(p.get(a)) for a in _ATTRS] for _, p in rows], np.float32
        ).reshape(-1, 3)
        labels = np.array([float(p.get("plan")) for _, p in rows], np.float32)
        return TrainingData(features=feats, labels=labels)

    def read_training(self, ctx: EngineContext) -> TrainingData:
        return self._read(ctx)

    def read_eval(self, ctx: EngineContext):
        from predictionio_tpu.e2.evaluation import split_data

        k = self.params.eval_k
        if k is None:
            raise ValueError("DataSourceParams.eval_k must be set for evaluation")
        td = self._read(ctx)
        rows = list(zip(td.features, td.labels))
        return split_data(
            k,
            rows,
            {},
            training_data_creator=lambda sel: TrainingData(
                features=np.array([x for x, _ in sel], np.float32).reshape(-1, 3),
                labels=np.array([y for _, y in sel], np.float32),
            ),
            query_creator=lambda d: Query(
                attr0=float(d[0][0]), attr1=float(d[0][1]), attr2=float(d[0][2])
            ),
            actual_creator=lambda d: ActualResult(label=float(d[1])),
        )


def _encode_labels(labels: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    classes = np.unique(labels)
    idx = np.searchsorted(classes, labels)
    return classes, idx.astype(np.int32)


@dataclass(frozen=True)
class NaiveBayesParams:
    lam: float = 1.0

    params_aliases = {"lambda": "lam"}


class NaiveBayesAlgorithm(Algorithm):
    flavor = "P2L"
    params_class = NaiveBayesParams
    query_class = Query

    def __init__(self, params: NaiveBayesParams | None = None):
        self.params = params or NaiveBayesParams()

    def train(self, ctx: EngineContext, pd: PreparedData) -> NaiveBayesModel:
        classes, y_idx = _encode_labels(pd.labels)
        pi, theta = train_naive_bayes(
            pd.features, y_idx, len(classes), lam=self.params.lam
        )
        return NaiveBayesModel(pi=pi, theta=theta, labels=classes)

    def predict(self, model: NaiveBayesModel, query: Query) -> PredictedResult:
        x = jnp.asarray([[query.attr0, query.attr1, query.attr2]], jnp.float32)
        scores = naive_bayes_scores(model.pi, model.theta, x)
        return PredictedResult(
            label=float(model.labels[int(np.argmax(np.asarray(scores)[0]))])
        )

    def batch_predict(self, model, queries):
        x = jnp.asarray(
            [[q.attr0, q.attr1, q.attr2] for _, q in queries], jnp.float32
        )
        best = np.argmax(np.asarray(naive_bayes_scores(model.pi, model.theta, x)), 1)
        return [
            (i, PredictedResult(label=float(model.labels[b])))
            for (i, _), b in zip(queries, best)
        ]

    def make_persistent_model(self, ctx, model: NaiveBayesModel):
        return {
            "pi": np.asarray(model.pi),
            "theta": np.asarray(model.theta),
            "labels": np.asarray(model.labels),
        }

    def load_persistent_model(self, ctx, data) -> NaiveBayesModel:
        return NaiveBayesModel(
            pi=jnp.asarray(data["pi"]),
            theta=jnp.asarray(data["theta"]),
            labels=np.asarray(data["labels"]),
        )


@dataclass(frozen=True)
class LogisticRegressionParams:
    reg: float = 0.0
    learning_rate: float = 0.5
    num_iterations: int = 300

    params_aliases = {"lambda": "reg"}


class LogisticRegressionAlgorithm(Algorithm):
    """The XLA-idiomatic second algorithm (reference adds RandomForest here,
    RandomForestAlgorithm.scala — tree ensembles map poorly onto the MXU,
    a compiled softmax-GD program is the TPU-native counterpart)."""

    flavor = "P2L"
    params_class = LogisticRegressionParams
    query_class = Query

    def __init__(self, params: LogisticRegressionParams | None = None):
        self.params = params or LogisticRegressionParams()

    def train(self, ctx: EngineContext, pd: PreparedData) -> LogisticRegressionModel:
        classes, y_idx = _encode_labels(pd.labels)
        p = self.params
        w, b = train_logistic_regression(
            pd.features,
            y_idx,
            len(classes),
            reg=p.reg,
            learning_rate=p.learning_rate,
            num_iterations=p.num_iterations,
        )
        return LogisticRegressionModel(w=w, b=b, labels=classes)

    def predict(self, model, query: Query) -> PredictedResult:
        x = jnp.asarray([[query.attr0, query.attr1, query.attr2]], jnp.float32)
        scores = logreg_scores(model.w, model.b, x)
        return PredictedResult(
            label=float(model.labels[int(np.argmax(np.asarray(scores)[0]))])
        )

    def batch_predict(self, model, queries):
        x = jnp.asarray(
            [[q.attr0, q.attr1, q.attr2] for _, q in queries], jnp.float32
        )
        best = np.argmax(np.asarray(logreg_scores(model.w, model.b, x)), 1)
        return [
            (i, PredictedResult(label=float(model.labels[b])))
            for (i, _), b in zip(queries, best)
        ]

    def make_persistent_model(self, ctx, model):
        return {
            "w": np.asarray(model.w),
            "b": np.asarray(model.b),
            "labels": np.asarray(model.labels),
        }

    def load_persistent_model(self, ctx, data) -> LogisticRegressionModel:
        return LogisticRegressionModel(
            w=jnp.asarray(data["w"]),
            b=jnp.asarray(data["b"]),
            labels=np.asarray(data["labels"]),
        )


class ClassificationServing(Serving):
    def __init__(self, params: Any = None):
        pass

    def serve(self, query, predictions):
        return predictions[0]


@engine_factory("classification")
def classification_engine() -> Engine:
    return Engine(
        ClassificationDataSource,
        IdentityPreparator,
        {"naive": NaiveBayesAlgorithm, "logreg": LogisticRegressionAlgorithm},
        ClassificationServing,
    )
