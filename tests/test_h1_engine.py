"""The Falcon-H1 block behind the DASE contract (ISSUE 30): ``pio train`` on
an engine.json whose layers are ``parallel_ssm_attention`` -> persisted model
-> ``load_models`` -> ``predict``, the spans a retrain opens, and what the
engine's import costs the other engines."""

from __future__ import annotations

import logging
import subprocess
import sys

import numpy as np
import pytest

from predictionio_tpu.core import EngineContext
from predictionio_tpu.core.engine import resolve_engine_factory
from predictionio_tpu.core.persistence import load_models
from predictionio_tpu.core.workflow import run_train
from predictionio_tpu.models.recommendation.engine import Query
from predictionio_tpu.models.sequence import engine as seq
from test_sequence_engine import SPANS, _Stages, store  # noqa: F401  (a fixture)

VARIANT = {
    "datasource": {"params": {"appName": "seq"}},
    "preparator": {"params": {
        "rowLen": 64, "maxLen": 64, "rowsPerStep": 2, "vocabSize": 128}},
    "algorithms": [{"name": "ssd", "params": {
        "hiddenSize": 64, "layerTypes": ["parallel_ssm_attention"] * 2,
        "numAttentionHeads": 4, "numKeyValueHeads": 2, "headDim": 16,
        "ropeTheta": 100000000000, "mambaNHeads": 4, "mambaNGroups": 2,
        "mambaDHead": 8, "mambaDState": 16, "mambaDConv": 4, "mambaChunkSize": 16,
        "intermediateSize": 16, "vocabSize": 128, "rmsNormEps": 1e-5,
        "embeddingMultiplier": 5.65, "lmHeadMultiplier": 0.25,
        "ssmInMultiplier": 0.5, "ssmMultipliers": [0.7, 0.5, 0.35, 1.4, 0.8],
        "ssmOutMultiplier": 0.3, "attentionInMultiplier": 1,
        "attentionOutMultiplier": 0.4, "keyMultiplier": 0.6,
        "mlpMultipliers": [0.7, 0.2], "rowsPerStep": 2, "stepsPerRetrain": 2}}],
}


@pytest.fixture()
def trained(store):  # noqa: F811
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
    assert set(cfg.layer_types) == {"parallel_ssm_attention"}
    assert (cfg.heads, cfg.kv_heads, cfg.rope_theta) == (4, 2, 1e11)
    assert (cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_head_dim, cfg.ssm_state,
            cfg.ssm_chunk) == (4, 2, 8, 16, 16)
    assert cfg.mup.ssm_zones == (0.7, 0.5, 0.35, 1.4, 0.8)
    assert (cfg.mup.mlp_gate, cfg.mup.mlp_down, cfg.mup.key) == (0.7, 0.2, 0.6)
    assert cfg.token_multiple == 16
    # the block this engine had keeps its configuration: no multiplier, no
    # state-space sizes, the probe under its own name
    from predictionio_tpu.ops import seqmodel

    olmo = seq.SequenceAlgorithm().seq_config()
    assert olmo.mup == seqmodel.MuP() and olmo.ssm_heads == 0
    assert olmo.token_multiple == 64
    assert seqmodel.PROBE_NAME[olmo.layer_types[0]] == "delta_rule_probe"
    with pytest.raises(ValueError, match="need the ssm_"):
        seq.SequenceAlgorithm(seq.SequenceAlgorithmParams(
            layer_types=("parallel_ssm_attention",))).seq_config()


def test_train_persist_load_predict_round_trip(trained):
    rt, (users, items, _), engine, params, instance, _ = trained
    (data,) = load_models(rt.models(), instance.id)
    record = data["training_record"]
    assert len(record["loss"]) == 2 and np.isfinite(record["loss"]).all()
    assert record["loss"][0] == pytest.approx(np.log(128), rel=0.02)
    assert set(record["tensor_grad_norm"]) == set(data["params"])
    assert "layer1.ssm_conv_bias" in data["params"] and "layer0.ssm_d" in data["params"]
    # the first layer's state space along the seeded vector, the first step's rows
    assert record["ssd_probe"].shape == (2, 64, 4) and "delta_rule_probe" not in record
    algo = engine.instantiate(params)[2][0]
    model = algo.load_persistent_model(EngineContext(storage=rt), data)
    assert model.config == algo.seq_config()
    seen = {f"i{i}" for i in items}
    answer = algo.predict(model, Query(user=f"u{users[0]}", num=5))
    assert len(answer.item_scores) == 5
    scores = [s.score for s in answer.item_scores]
    assert scores == sorted(scores, reverse=True)
    assert {s.item for s in answer.item_scores} <= seen  # never a padding row
    # the answer is the scaled head over the history's last hidden state
    from predictionio_tpu.ops import seqmodel

    e = model.entity_vocab[f"u{users[0]}"]
    hist = model.history_tokens[model.history_offsets[e] : model.history_offsets[e + 1]]
    tokens = np.zeros((1, 64), np.int32)
    segments = np.full((1, 64), seq.PAD_SEGMENT, np.int32)
    tokens[0, : len(hist)], segments[0, : len(hist)] = hist, 0
    h = seqmodel.hidden_states(
        model.config, data["params"], tokens, segments)[0, len(hist) - 1]
    want = 0.25 * np.asarray(data["params"]["head"] @ h)[: len(model.item_vocab)]
    top = np.argsort(-want, kind="stable")[:5]
    assert [s.item for s in answer.item_scores] == [
        model.item_vocab.inverse(int(j)) for j in top]
    np.testing.assert_allclose(scores, want[top], rtol=2e-2, atol=2e-3)


def test_every_span_of_the_engine_appears_once_in_stages(trained):
    stages = trained[-1]
    for name in SPANS + ("train.algorithm.ssd", "train.persist.save_models",
                         "train.datasource.read", "train.preparator.prepare"):
        assert name in stages and stages[name] >= 0, name
    assert "parallel" not in stages  # nothing ran side by side


def test_the_device_loop_says_which_block_it_ran(trained):
    from predictionio_tpu.obs.tracing import recent_traces

    instance = trained[-2]
    root = next(t for t in recent_traces(5) if t.get("request_id") == instance.id)

    def find(node, name):
        if node["name"] == name:
            return node
        return next(
            (hit for c in node.get("children", []) if (hit := find(c, name))), None)

    assert find(root, "seq.device_loop")["block"] == "parallel_ssm_attention"


def test_importing_the_engines_still_loads_no_kernel_code():
    code = (
        "import sys, predictionio_tpu.models\n"
        "bad = [m for m in sys.modules if m.startswith(('jax.experimental.pallas',"
        " 'predictionio_tpu.ops.gdn', 'predictionio_tpu.ops.ssd',"
        " 'predictionio_tpu.ops.seqmodel'))]\n"
        "print(bad)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "[]"
