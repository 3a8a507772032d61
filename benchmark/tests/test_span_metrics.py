"""The per-layer metrics that read the retrain's span tree (ISSUE 24): their
files resolve, their readers leave a program without the spans alone, and the
CPU rehearsal of the retrain cell reports every one."""

import importlib

import pytest

from benchmark import run as harness
from benchmark.readers import plan_info_scaled, stage_residual, stage_seconds
from benchmark.tests import span_report
from benchmark.tests.test_rehearsal import run_cell

CELL = "als-ml20m.retrain"
SPAN_METRICS = {
    "scan_s": "eventstore.scan", "sort_s": "eventstore.sort",
    "decode_s": "eventstore.decode", "columns_s": "datasource.columns",
    "vocab_s": "prepare.vocab", "index_s": "prepare.index",
    "persist_s": "train.persist.save_models",
    "als_plan_s": "als.stage.plan", "als_permute_s": "als.stage.permute",
    "als_upload_s": "als.stage.upload",
}
NEW = set(SPAN_METRICS) | {"als_loop_s", "als_upload_gb", "host_unnamed_s"}

#: what the PARENT of this PR hands the readers: the DASE stages alone
PARENT_STAGES = {
    "train.datasource.read": 11.8, "train.preparator.prepare": 11.8,
    "train.algorithm.als": 10.9, "train.persist.save_models": 0.02,
    "total": 34.9, "jax_compile": 0.0,
}


def spec(name):
    return harness.load_json(harness.BENCH / "layer_metrics" / f"{name}.json")


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_resolves(name):
    manifest = harness.load_json(harness.REPO / "BENCHMARK.json")
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["moves"] == "retrain_s"
    assert entry["better"] == "lower"
    reader = importlib.import_module(f"benchmark.readers.{spec(name)['reader']}")
    # nothing to read is nothing reported, never an error
    assert reader.read({}, spec(name)["args"]) is None
    assert reader.read({"retrain": {}}, spec(name)["args"]) is None


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_metric_reads_its_span_alone(name):
    stages = {**PARENT_STAGES, "als.stage": 6.0, "parallel": ["als.stage.plan"]}
    evidence = {"retrain": {"stages": stages}}
    args = spec(name)["args"]
    if name != "persist_s":  # the one span the parent has
        assert stage_seconds.read(evidence, args) is None
    stages[SPAN_METRICS[name]] = 1.25
    assert stage_seconds.read(evidence, args) == 1.25


def test_stage_residual():
    args = spec("host_unnamed_s")["args"]
    # a parent and its children are never both on the list
    assert "als.stage" in args["spans"]
    assert not any(s.startswith("als.stage.") for s in args["spans"])
    assert not any(s.startswith("train.") and "persist" not in s
                   for s in args["spans"])
    assert stage_residual.read({"retrain": {"stages": None}}, args) is None
    # the parent's program: only the one span that was always there -> the
    # rest of the retrain reads as unnamed, which it is
    assert stage_residual.read(
        {"retrain": {"stages": PARENT_STAGES}}, args
    ) == pytest.approx(34.9 - 0.02)
    # no span of the list at all: nothing to read
    assert stage_residual.read(
        {"retrain": {"stages": {"total": 3.0, "jax_compile": 0.1}}}, args
    ) is None
    stages = {"total": 10.0, "train.algorithm.als": 5.0, "als.stage": 2.0,
              "als.stage.plan": 1.5, "als.device_loop": 2.5,
              "eventstore.scan": 4.0, "train.persist.save_models": 1.0,
              "parallel": ["als.stage.plan"]}
    assert stage_residual.read(
        {"retrain": {"stages": stages}}, args) == pytest.approx(0.5)


def test_plan_info_scaled():
    args = spec("als_upload_gb")["args"]
    assert plan_info_scaled.read(
        {"retrain": {"plan_info": {"stage_s": 6.0}}}, args) is None
    assert plan_info_scaled.read(
        {"retrain": {"plan_info": {"upload_bytes": 250_000_000}}}, args
    ) == pytest.approx(0.25)


def test_rehearsal_reports_every_new_metric(tmp_path):
    _, res, _ = run_cell(tmp_path, CELL, trace=True, seconds=1.0)
    assert res["correct"] is True
    assert NEW <= set(res["metrics"])
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # the parts of a block against the block (host clock, same retrain)
    read = m["scan_s"] + m["sort_s"] + m["decode_s"] + m["columns_s"]
    assert read <= m["read_s"] and m["read_s"] - read <= 0.05 * m["read_s"] + 0.005
    prep = m["vocab_s"] + m["index_s"]
    assert prep <= m["prepare_s"] and m["prepare_s"] - prep <= 0.05 * m["prepare_s"] + 0.005
    assert 0 <= m["host_unnamed_s"] < m["read_s"] + m["prepare_s"] + m["algo_s"]
    assert m["als_loop_s"] <= m["algo_s"] and m["als_upload_gb"] > 0


# -- the builder's script ----------------------------------------------------


def test_scope_of():
    assert span_report.scope_of(
        "jit(steps)/while/body/als.user_half/als.solve/mul"
    ) == "als.user_half/als.solve"
    assert span_report.scope_of("jit(steps)/als.weights/stack") == "als.weights"
    assert span_report.scope_of("jit(steps)/while") == "(no scope)"
    assert span_report.scope_of(None) == "(no scope)"


def test_tf_op_is_read_from_the_event_metadata():
    fixture = harness.BENCH / "tests" / "data" / "tiny_tpu.xplane.pb"
    names = span_report.op_names_by_event(str(fixture))
    assert list(names.values()) == ["jit(step)/dot_general:"]
    assert next(iter(names)).startswith("%fusion = ")
    by = span_report.busy_by_scope(str(fixture))
    assert by["busy_s"] == pytest.approx(90.4e-6, rel=0.01)
    assert by["self_time_s"] == pytest.approx(by["busy_s"], rel=0.01)
    assert by["under_als_scopes_s"] == 0.0


def test_idle_goes_to_the_innermost_span_and_adds_up(monkeypatch):
    ms = 1_000_000
    device = [[(40 * ms, 50 * ms, "%a = x"), (60 * ms, 90 * ms, "%b = y")]]
    host = [
        (0, 100 * ms, "workflow.run_train"),
        (5 * ms, 30 * ms, "train.datasource.read"),
        (5 * ms, 20 * ms, "eventstore.scan"),
        (20 * ms, 28 * ms, "eventstore.decode"),
        # two sides at once: the shorter takes its part first
        (30 * ms, 38 * ms, "als.stage.plan"),
        (30 * ms, 36 * ms, "als.stage.plan"),
        (38 * ms, 95 * ms, "als.device_loop"),
    ]
    monkeypatch.setattr(span_report, "_planes", lambda path: (device, host))
    r = span_report.idle_by_span("unused")
    by = dict((k.split(" (")[0], v) for k, v in r["by_span"])
    assert r["idle_s"] == pytest.approx(0.060)
    assert by["eventstore.scan"] == pytest.approx(0.015)
    assert by["eventstore.decode"] == pytest.approx(0.008)
    assert by["train.datasource.read"] == pytest.approx(0.002)
    assert by["als.stage.plan"] == pytest.approx(0.008)
    assert by["als.device_loop"] == pytest.approx(0.002 + 0.010 + 0.005)
    assert by["workflow.run_train"] == pytest.approx(0.005 + 0.005)
    assert sum(by.values()) + r["unattributed_s"] == pytest.approx(r["idle_s"])
    assert r["under_leaf_spans_s"] == pytest.approx(0.048)
