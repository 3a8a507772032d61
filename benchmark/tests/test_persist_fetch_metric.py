"""``persist_fetch_s`` (ISSUE 41): the per-layer metric that reads the span
``persist.fetch`` the model store's part writers open around the wait for a
part's copy off the device.  Its file resolves, the manifest lists the five
sequence cells, the reader leaves a program without the span alone (the ALS
cell's host factors; the parent, whose ``seq.fetch`` carried the weights), and
the CPU rehearsal of a sequence cell whose embedding and head are of part size
reports it beside ``seq_fetch_s`` and ``persist_s``."""

import json

import pytest

from benchmark import run as harness
from benchmark.readers import stage_residual, stage_seconds
from benchmark.tests.test_span_metrics import spec
from benchmark.tests.tiny_sequence import CELL, CONFIG, tiny_sequence_root

NAME = "persist_fetch_s"

#: what the PARENT of this PR hands the readers from a sequence retrain: the
#: weights under ``seq.fetch``, the writers' spans without a child
PARENT_STAGES = {
    "train.datasource.read": 1.19, "train.preparator.prepare": 0.17,
    "train.algorithm.gdn": 8.0, "train.persist.save_models": 2.63,
    "seq.init": 0.31, "seq.device_loop": 6.5, "seq.fetch": 1.21,
    "persist.part": 2.62, "parallel": ["persist.part"],
    "total": 12.0, "jax_compile": 0.0,
}
#: and this PR's program: four writers, the longest 0.4 s in fetches
STAGES = {
    **PARENT_STAGES, "seq.fetch": 0.01, "persist.fetch": 0.4,
    "parallel": ["persist.fetch", "persist.part"], "total": 11.0,
}


def manifest_entry(name):
    manifest = harness.load_json(harness.REPO / "BENCHMARK.json")
    return next(m for m in manifest["per_layer"] if m["name"] == name)


def test_the_metric_resolves_and_lists_the_sequence_cells():
    entry = manifest_entry(NAME)
    assert entry == {
        "name": NAME, "unit": "s", "better": "lower", "source": "program_span",
        "layer": "Workflow", "moves": "retrain_s",
        "workloads": manifest_entry("seq_fetch_s")["workloads"]}
    assert len(entry["workloads"]) == 5
    assert "als-ml20m.retrain" not in entry["workloads"]
    # the last entry of its list: nothing that was there moved
    manifest = harness.load_json(harness.REPO / "BENCHMARK.json")
    assert manifest["per_layer"][-1]["name"] == NAME
    assert spec(NAME)["reader"] == "stage_seconds"
    assert spec(NAME)["args"] == {"prefixes": ["persist.fetch"]}


@pytest.mark.parametrize("evidence", [
    {}, {"retrain": {}}, {"retrain": {"stages": None}},
    {"retrain": {"stages": PARENT_STAGES}},
], ids=["nothing", "no-stages", "stages-none", "parent"])
def test_a_program_without_the_span_reports_nothing(evidence):
    assert stage_seconds.read(evidence, spec(NAME)["args"]) is None


def test_the_metric_reads_its_span_alone():
    evidence = {"retrain": {"stages": STAGES}}
    assert stage_seconds.read(evidence, spec(NAME)["args"]) == 0.4
    # and no accepted metric reads the new span: the write keeps its own
    # prefix, the copy that stayed in ``train()`` its own
    assert stage_seconds.read(evidence, spec("persist_s")["args"]) == 2.63
    assert stage_seconds.read(evidence, spec("seq_fetch_s")["args"]) == 0.01
    # a child of a span on the residual's list is never on the list itself
    spans = spec("host_unnamed_s")["args"]["spans"]
    assert "train.persist.save_models" in spans
    assert not any(s.startswith("persist.") for s in spans)
    assert stage_residual.read(evidence, spec("host_unnamed_s")["args"]) == (
        stage_residual.read(
            {"retrain": {"stages": {**STAGES, "persist.fetch": 0.9}}},
            spec("host_unnamed_s")["args"]))


def test_rehearsal_of_a_sequence_cell_reports_the_metric(tmp_path):
    """The tiny cell with 4096 vocabulary rows: the embedding and the head are
    1 MiB each, parts of their own, fetched inside their writers."""
    manifest, root = tiny_sequence_root(tmp_path)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    cfg = json.loads((tmp_path / entry["file"]).read_text())
    cfg["vocab_size"] = 4096
    cfg["engine_json"]["preparator"]["params"]["vocabSize"] = 4096
    cfg["engine_json"]["algorithms"][0]["params"]["vocabSize"] = 4096
    (tmp_path / entry["file"]).write_text(json.dumps(cfg))
    res, compared = harness.execute(
        manifest, CELL, 2**31 + 4101, 1.0, True, "cpu", tmp_path / "work", root)
    assert res["attempted"] >= 1 and res["failed"] == 0
    by = {c.name: c for c in compared}
    assert by["compilations_inside_window"].value == 0
    assert all(c.ok for n, c in by.items() if n.startswith((
        "instance_completed", "tensor_shapes", "weights_finite",
        "optimizer_steps", "positions_trained")))
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert {NAME, "seq_fetch_s", "persist_s"} <= set(m)
    assert res["metrics"][NAME]["unit"] == "s"
    assert 0 <= m[NAME] <= m["persist_s"]
