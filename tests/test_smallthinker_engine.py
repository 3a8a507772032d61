"""The SmallThinker block behind the DASE contract (ISSUE 32): ``pio train``
on an engine.json whose layers are ``global_attention_moe`` /
``sliding_attention_moe`` -> persisted model -> ``load_models`` -> ``predict``
held to the plain reference, the routing counters in the training record and
under ``stages["counters"]``."""

from __future__ import annotations

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.core import EngineContext
from predictionio_tpu.core.engine import resolve_engine_factory
from predictionio_tpu.core.persistence import load_models
from predictionio_tpu.core.workflow import run_train
from predictionio_tpu.models.recommendation.engine import Query
from predictionio_tpu.models.sequence import engine as seq
from st_reference import SHARE, reference
from test_sequence_engine import SPANS, _Stages, store  # noqa: F401  (a fixture)

KINDS = ["global_attention_moe", "sliding_attention_moe"]
VARIANT = {
    "datasource": {"params": {"appName": "seq"}},
    "preparator": {"params": {
        "rowLen": 128, "maxLen": 128, "rowsPerStep": 2, "vocabSize": 128}},
    "algorithms": [{"name": "moe", "params": {
        "hiddenSize": 64, "layerTypes": KINDS, "numAttentionHeads": 2,
        "numKeyValueHeads": 1, "headDim": 16, "ropeTheta": 1500000,
        "slidingWindowSize": 16, "moeNumPrimaryExperts": 16, "moeExpertsHeld": 4,
        "moeExpertStart": 0, "moeNumActivePrimaryExperts": 4,
        "moeFfnHiddenSize": 32, "vocabSize": 128, "rmsNormEps": 1e-6,
        "rowsPerStep": 2, "stepsPerRetrain": 2}}],
}
#: the reference's group for VARIANT: ``st_reference.SHARE`` with all 128 rows
MODEL = {**SHARE, "vocab_rows_held": 128}


@pytest.fixture()
def trained(store, monkeypatch):  # noqa: F811
    # tiles of 8 pairs: the tiny rows' 1.0 pairs a token and expert fill them
    configured = seq.SequenceAlgorithm.seq_config
    monkeypatch.setattr(
        seq.SequenceAlgorithm, "seq_config",
        lambda self: dataclasses.replace(configured(self), moe_tile=8))
    rt, data = store
    seen = _Stages()
    log = logging.getLogger("predictionio_tpu.workflow")
    log.addHandler(seen)
    level = log.level
    log.setLevel(logging.INFO)
    try:
        engine = resolve_engine_factory("sequence")()
        params = engine.params_from_json(VARIANT)
        instance = run_train(
            engine, params, engine_factory="sequence", storage=rt,
            ctx=EngineContext(storage=rt))
    finally:
        log.removeHandler(seen)
        log.setLevel(level)
    assert instance.status == "COMPLETED"
    return rt, data, engine, params, instance, seen.stages


def test_engine_json_reaches_the_blocks_configuration():
    engine = resolve_engine_factory("sequence")()
    algo = engine.instantiate(engine.params_from_json(VARIANT))[2][0]
    cfg = algo.seq_config()
    assert list(cfg.layer_types) == KINDS
    assert (cfg.heads, cfg.kv_heads, cfg.rope_theta, cfg.window) == (2, 1, 1.5e6, 16)
    assert (cfg.experts, cfg.experts_held, cfg.expert_start, cfg.experts_per_token,
            cfg.expert_width) == (16, 4, 0, 4, 32)
    assert cfg.token_multiple == 128
    from predictionio_tpu.ops import seqmodel

    assert seqmodel.PROBE_NAME[cfg.layer_types[0]] == "moe_probe"
    shapes = seqmodel.param_shapes(cfg)
    assert shapes["layer1.router"] == (64, 16)  # the router whole
    assert shapes["layer0.experts_gate"] == (4, 64, 32)
    assert shapes["layer0.experts_down"] == (4, 32, 64)
    assert "layer0.gate" not in shapes and "layer0.q_norm" not in shapes
    # the blocks this engine had keep theirs: no expert, no window
    olmo = seq.SequenceAlgorithm().seq_config()
    assert (olmo.experts, olmo.window, olmo.token_multiple) == (0, 0, 64)
    with pytest.raises(ValueError, match="experts' sizes"):
        seq.SequenceAlgorithm(seq.SequenceAlgorithmParams(
            layer_types=("global_attention_moe",))).seq_config()


def test_train_persist_load_predict_round_trip(trained):
    rt, (users, items, _), engine, params, instance, _ = trained
    (data,) = load_models(rt.models(), instance.id)
    record = data["training_record"]
    assert len(record["loss"]) == 2 and np.isfinite(record["loss"]).all()
    assert record["loss"][0] == pytest.approx(np.log(128), rel=0.02)
    assert set(record["tensor_grad_norm"]) == set(data["params"])
    assert data["params"]["layer1.experts_up"].shape == (4, 64, 32)
    # the first layer's experts along the seeded vector and every layer's
    # choices, for the first step's rows
    assert record["moe_probe"].shape == (2, 128, 1) and "ssd_probe" not in record
    assert record["choices"].shape == (2, 2, 128, 4)
    assert record["choices"].min() >= 0 and record["choices"].max() < 16
    # the routing counters, a step and layer, summed on the device
    assert record["moe_expert_pairs"].shape == (2, 2, 4)
    assert (record["moe_pairs_held"] == record["moe_expert_pairs"].sum(-1)).all()
    model_tokens = np.asarray(record["moe_pairs_total"]) // 4
    assert (model_tokens[:, 0] == model_tokens[:, 1]).all() and model_tokens.min() > 0
    # a quarter of the experts held: about a quarter of the pairs computed
    share = record["moe_pairs_held"].sum() / record["moe_pairs_total"].sum()
    assert 0.15 < share < 0.35
    # the first step's choices ARE the counters' pairs (step 0, both layers)
    first_rows = record["choices"]  # [rows, layers, T, k]
    algo = engine.instantiate(params)[2][0]
    model = algo.load_persistent_model(EngineContext(storage=rt), data)
    assert model.config == algo.seq_config()
    seen = {f"i{i}" for i in items}
    answer = algo.predict(model, Query(user=f"u{users[0]}", num=5))
    assert len(answer.item_scores) == 5
    scores = [s.score for s in answer.item_scores]
    assert scores == sorted(scores, reverse=True)
    assert {s.item for s in answer.item_scores} <= seen  # never a padding row
    # the answer against the PLAIN reference: every held expert densely, the
    # full masked score matrix, float32, over the history alone
    e = model.entity_vocab[f"u{users[0]}"]
    hist = model.history_tokens[model.history_offsets[e] : model.history_offsets[e + 1]]
    w = {k: jnp.asarray(v) for k, v in data["params"].items()}
    with jax.default_matmul_precision("highest"):
        h, choices = reference.final_hidden(MODEL, w, jnp.asarray(hist))
        want = np.asarray(w["head"] @ h[-1])[: len(model.item_vocab)]
    assert choices.shape == (2, len(hist), 4)
    got = {s.item: s.score for s in answer.item_scores}
    for item, score in got.items():
        assert score == pytest.approx(want[model.item_vocab[item]], abs=5e-3)
    assert max(scores) == pytest.approx(want.max(), abs=5e-3)
    assert first_rows.dtype.kind == "i"


def test_counters_reach_the_stages_extra_and_the_trace_ring(trained):
    """Seconds stay where they were; what the spans COUNTED is under the one
    key ``counters``, and on the span in ``/traces.json``'s ring."""
    from predictionio_tpu.obs.tracing import recent_traces

    rt, _, _, _, instance, stages = trained
    for name in SPANS + ("train.algorithm.moe", "train.persist.save_models"):
        assert name in stages and stages[name] >= 0, name
    counters = stages["counters"]
    assert all(isinstance(v, (int, float)) for v in counters.values())
    (data,) = load_models(rt.models(), instance.id)
    record = data["training_record"]
    assert counters["moe_experts_held"] == 4
    assert counters["moe_pairs_total"] == int(record["moe_pairs_total"].sum())
    assert counters["moe_pairs_held"] == int(record["moe_pairs_held"].sum())
    # the pair buffers' rows in live tiles (the buffer work done) of all they
    # have: a step and layer in the record, the retrain's sums and share here
    assert record["moe_rows_live"].shape == record["moe_rows_planned"].shape == (2, 2)
    assert counters["moe_rows_live"] == int(record["moe_rows_live"].sum()) > 0
    assert counters["moe_rows_planned"] == int(record["moe_rows_planned"].sum())
    assert counters["moe_rows_live_pct"] == pytest.approx(
        100.0 * counters["moe_rows_live"] / counters["moe_rows_planned"])
    assert (record["moe_rows_live"] > 0).all()  # a tile an expert at the least
    assert (record["moe_rows_live"] <= record["moe_rows_planned"]).all()
    for s in range(2):
        for layer in range(2):
            at = f".step{s}.layer{layer}"
            assert counters["moe_pairs_held" + at] == record["moe_pairs_held"][s, layer]
            assert counters["moe_expert_pairs_max" + at] == record[
                "moe_expert_pairs"][s, layer].max()
    # every other name of ``stages`` is still seconds (or the list of the
    # names that ran side by side)
    assert all(isinstance(v, (int, float)) for k, v in stages.items()
               if k not in ("counters", "parallel"))
    root = next(t for t in recent_traces(5) if t.get("request_id") == instance.id)

    def find(node, name):
        if node["name"] == name:
            return node
        return next(
            (hit for c in node.get("children", []) if (hit := find(c, name))), None)

    assert find(root, "seq.fetch")["counters"] == counters
    assert find(root, "seq.device_loop")["block"] == "+".join(KINDS)


def test_the_stage_readers_read_what_they_read(trained):
    """``readers/stage_seconds.py`` and ``stage_residual.py`` see seconds as
    before beside the new key; ``stage_counter.py`` reads the counters."""
    from benchmark.readers import stage_counter, stage_residual, stage_seconds

    stages = trained[-1]
    evidence = {"retrain": {"stages": stages}}
    assert stage_seconds.read(evidence, {"prefixes": ["seq.device_loop"]}) == stages[
        "seq.device_loop"]
    assert stage_residual.read(evidence, {"spans": ["train.algorithm.moe"]}) == (
        stages["total"] - stages["train.algorithm.moe"])
    ratio = stage_counter.read(evidence, {
        "peak": "moe_expert_pairs_max", "sum": "moe_pairs_held",
        "parts": "moe_experts_held"})
    assert 1.0 <= ratio <= 4.0
    assert stage_counter.read(evidence, {"key": "moe_pairs_held"}) == stages[
        "counters"]["moe_pairs_held"]
    # a program without counters (the parent's): nothing to read, no error
    bare = {"retrain": {"stages": {k: v for k, v in stages.items() if k != "counters"}}}
    assert stage_counter.read(bare, {"key": "moe_pairs_held"}) is None
    assert stage_counter.read({"retrain": {}}, {"key": "x"}) is None
