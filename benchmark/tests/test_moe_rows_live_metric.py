"""``moe_rows_live_pct`` (ISSUE 42, landed by PR 43): the per-layer metric
that reads what the routed layers counted of their pair buffers, the rows in
live tiles over the rows the buffers have.  Its file resolves on the reader that was there, the
manifest lists the two routed cells and nothing that was there moved, a
program that does not count it (the parent, whose ``stages`` has the routing
counters alone) is left alone, and the CPU rehearsals of the two routed cells
report it."""

import pytest

from benchmark import run as harness
from benchmark.readers import stage_counter
from benchmark.tests import tiny_nemotron, tiny_smallthinker
from benchmark.tests.test_span_metrics import spec

NAME = "moe_rows_live_pct"
CELLS = ["smallthinker-21b-ep4.retrain", "nemotron3-nano-30b-ep8.retrain"]

#: what the PARENT of this PR counts in a routed retrain
PARENT_COUNTERS = {
    "moe_routed_layers": 4, "moe_experts_held": 16, "moe_pairs_total": 3_144_576,
    "moe_pairs_held": 586_080, "moe_pairs_held.step0.layer0": 55_964,
    "moe_expert_pairs_max.step0.layer0": 4_100,
}


def manifest_entry(name):
    manifest = harness.load_json(harness.REPO / "BENCHMARK.json")
    return next(m for m in manifest["per_layer"] if m["name"] == name)


def test_the_metric_resolves_and_lists_the_routed_cells():
    assert manifest_entry(NAME) == {
        "name": NAME, "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "Sequence train", "moves": "retrain_s", "workloads": CELLS}
    assert manifest_entry("moe_load_peak_ratio")["workloads"] == CELLS
    # after everything that was there, whose order is PR 41's
    names = [m["name"] for m in harness.load_json(
        harness.REPO / "BENCHMARK.json")["per_layer"]]
    assert names.index(NAME) == 59 and names.index("persist_fetch_s") == 58
    assert names[55:58] == [
        "relu2_experts_roofline_pct", "nemotron_ssd_chunk_roofline_pct",
        "moe_shared_device_s"]
    assert spec(NAME)["reader"] == "stage_counter"
    assert spec(NAME)["args"] == {"key": NAME}


@pytest.mark.parametrize("evidence", [
    {}, {"retrain": {}}, {"retrain": {"stages": None}},
    {"retrain": {"stages": {"seq.fetch": 0.05}}},
    {"retrain": {"stages": {"counters": PARENT_COUNTERS}}},
], ids=["nothing", "no-stages", "stages-none", "no-counters", "parent"])
def test_a_program_that_does_not_count_it_reports_nothing(evidence):
    assert stage_counter.read(evidence, spec(NAME)["args"]) is None


def test_the_metric_reads_its_counter_alone():
    counters = {**PARENT_COUNTERS, "moe_rows_live": 1_024, "moe_rows_planned": 4_096,
                NAME: 25.0}
    evidence = {"retrain": {"stages": {"counters": counters}}}
    assert stage_counter.read(evidence, spec(NAME)["args"]) == 25.0
    # and the accepted metric on the same reader reads what it read
    assert stage_counter.read(evidence, spec("moe_load_peak_ratio")["args"]) == (
        stage_counter.read({"retrain": {"stages": {"counters": PARENT_COUNTERS}}},
                           spec("moe_load_peak_ratio")["args"]))


@pytest.mark.parametrize("tiny", [
    (tiny_smallthinker.CELL, tiny_smallthinker.tiny_smallthinker_root),
    (tiny_nemotron.CELL, tiny_nemotron.tiny_nemotron_root),
], ids=["smallthinker", "nemotron"])
def test_the_routed_cells_report_it(tmp_path, tiny):
    cell, make_root = tiny
    manifest, root = make_root(tmp_path)
    res, compared = harness.execute(
        manifest, cell, 2**31 + 4201, 1.0, True, "cpu", tmp_path / "work", root)
    assert res["correct"] is True, [c.line() for c in compared if not c.ok]
    assert {NAME, "moe_load_peak_ratio"} <= set(res["metrics"])
    # tiles of 256 rows at rows of 128 tokens: every held expert's pairs lie
    # in one tile, the buffer has more (the worst case's and one an expert)
    assert 0.0 < res["metrics"][NAME]["value"] < 100.0
