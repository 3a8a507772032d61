"""The per-layer metrics that read the retrain's span tree (ISSUE 24) and the
device's time by named scope (ISSUE 35): their files resolve, their readers
leave a program without the spans or scopes alone, and the CPU rehearsal of
the retrain cell reports every span metric."""

import importlib

import pytest

from benchmark import run as harness
from benchmark.readers import plan_info_scaled, stage_residual, stage_seconds
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
    assert CELL in entry["workloads"] and entry["moves"] == "retrain_s"
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


# -- device time by named scope (ISSUE 35) -------------------------------------

SEQ = ["olmo-hybrid-7b-tp2.retrain", "falcon-h1-34b-tp4.retrain",
       "smallthinker-21b-ep4.retrain"]
#: metric -> (the arguments its file gives the reader, the cells that list it)
SCOPE_METRICS = {
    "seq_attn_device_s": ({"scopes": ["seq.attn"]}, SEQ),
    # the routed block has no MLP: its experts are seq.moe
    "seq_mlp_device_s": ({"scopes": ["seq.mlp"]}, SEQ[:2]),
    "seq_loss_device_s": ({"scopes": ["seq.loss"]}, SEQ),
    "seq_embed_device_s": ({"scopes": ["seq.embed"]}, SEQ),
    "seq_recompute_device_s": ({"pass": "recompute"}, SEQ),
    "device_unscoped_s": ({"scopes": ["(no scope)"]}, [CELL] + SEQ),
    "seq_gdn_device_s": ({"scopes": ["seq.gdn"]}, SEQ[:1]),
    "seq_ssm_device_s": ({"scopes": ["seq.ssm"]}, SEQ[1:2]),
    "seq_moe_device_s": ({"scopes": ["seq.moe"]}, SEQ[2:]),
    "gdn_chunk_device_s": ({"scopes": ["gdn.chunk"]}, SEQ[:1]),
    "gdn_chunk_roofline_pct": ({"scopes": ["gdn.chunk"]}, SEQ[:1]),
    "ssd_chunk_device_s": ({"scopes": ["ssm.chunk"]}, SEQ[1:2]),
    "ssd_chunk_roofline_pct": ({"scopes": ["ssm.chunk"]}, SEQ[1:2]),
    "moe_experts_device_s": ({"scopes": ["moe.experts"]}, SEQ[2:]),
    "moe_experts_roofline_pct": ({"scopes": ["moe.experts"]}, SEQ[2:]),
    "als_accumulate_device_s": ({"scopes": ["als.accumulate"]}, [CELL]),
    "als_solve_device_s": ({"scopes": ["als.solve"]}, [CELL]),
}
#: a reduced trace's rows as the programs write their scopes (seconds made up)
SCOPES = [
    ["seq.gdn/gdn.chunk", "backward", 0.6], ["seq.gdn/gdn.chunk", "forward", 0.2],
    ["seq.gdn/gdn.chunk", "recompute", 0.3], ["seq.gdn/gdn.intra", "recompute", 0.5],
    ["seq.ssm/ssm.chunk", "backward", 0.03], ["seq.ssm", "forward", 0.1],
    ["seq.moe/moe.experts", "backward", 0.7], ["seq.moe/moe.route", "recompute", 0.9],
    ["seq.attn/attn.causal", "forward", 0.11], ["seq.mlp", "recompute", 1.5],
    ["seq.loss", "forward", 1.1], ["seq.embed", "forward", 0.2],
    ["als.user_half/als.accumulate", "forward", 2.2],
    ["als.item_half/als.accumulate", "forward", 2.1],
    ["als.user_half/als.solve", "forward", 0.02], ["(no scope)", "forward", 0.4],
    ["(no scope)", "recompute", 0.05],
]
EXPECTED = {
    "seq_attn_device_s": 0.11, "seq_mlp_device_s": 1.5, "seq_loss_device_s": 1.1,
    "seq_embed_device_s": 0.2, "seq_recompute_device_s": 0.3 + 0.5 + 0.9 + 1.5 + 0.05,
    "device_unscoped_s": 0.45, "seq_gdn_device_s": 1.6, "seq_ssm_device_s": 0.13,
    "seq_moe_device_s": 1.6, "gdn_chunk_device_s": 1.1, "ssd_chunk_device_s": 0.03,
    "moe_experts_device_s": 0.7, "als_accumulate_device_s": 4.3,
    "als_solve_device_s": 0.02,
}


@pytest.mark.parametrize("name", sorted(SCOPE_METRICS))
def test_scope_metric_resolves_and_reads_its_scope_alone(name):
    manifest = harness.load_json(harness.REPO / "BENCHMARK.json")
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    args, cells = SCOPE_METRICS[name]
    assert entry["workloads"] == cells and entry["moves"] == "retrain_s"
    assert entry["layer"] == "Kernel" and entry["source"] == "device_trace"
    share = name.endswith("_roofline_pct")
    assert (entry["unit"], entry["better"]) == (
        ("%", "higher") if share else ("s", "lower"))
    assert spec(name)["args"] == args
    reader = importlib.import_module(f"benchmark.readers.{spec(name)['reader']}")
    # nothing to read is nothing reported, never an error and never a 0
    assert reader.read({}, args) is None
    assert reader.read({"trace": {"scopes": []}}, args) is None
    assert reader.read(
        {"trace": {"scopes": [["seq.adamw", "forward", 1.0]]}}, args) is None
    if not share:  # the three shares: test_*_cell.py, with their configurations
        assert reader.read({"trace": {"scopes": SCOPES}}, args) == pytest.approx(
            EXPECTED[name])


def test_a_scope_the_trace_cannot_show_waits_outside_the_manifest():
    """``seq.adamw``: the compiler fuses the update with the step's norms and
    probes under the probe's op name, so a listed metric would be absent."""
    manifest = harness.load_json(harness.REPO / "BENCHMARK.json")
    assert "seq_adamw_device_s" not in {m["name"] for m in manifest["per_layer"]}
    assert "NOT in BENCHMARK.json" in spec("seq_adamw_device_s")["what"]


def test_the_sequence_cells_report_the_shared_spans_under_one_name():
    manifest = harness.load_json(harness.REPO / "BENCHMARK.json")
    by = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("scan_s", "sort_s", "decode_s", "vocab_s", "persist_s"):
        assert by[name]["workloads"] == [CELL] + SEQ
    assert "seq_persist_s" not in by
    assert not (harness.BENCH / "layer_metrics" / "seq_persist_s.json").exists()
