"""The sequence cell rehearsed on the CPU at a tiny size: the real harness,
kind, worker, reference (its replay in a child) and readers, through
``run.execute`` with the platform ``cpu``; and the same run on a program
broken underneath (no reset at segment boundaries; the delta rule's state in
bfloat16), which must come out not correct by the numbers built to catch
it."""

import json
from pathlib import Path

import pytest

from benchmark import run as harness
from benchmark.tests.tiny_sequence import CELL, tiny_sequence_root

SHIM = Path(__file__).parent / "shim_sequence"
SEED = 2**31 + 2601

SPANS = {"seq_group_s", "seq_pack_s", "seq_init_s", "seq_loop_s", "seq_fetch_s",
         # the spans every retrain opens, under the names the ALS cell reads them by
         "scan_s", "sort_s", "decode_s", "vocab_s", "persist_s"}


def test_sequence_retrain_cell(tmp_path):
    manifest, root = tiny_sequence_root(tmp_path)
    res, compared = harness.execute(
        manifest, CELL, SEED, 1.0, True, "cpu", tmp_path / "work", root)
    by = {c.name: c for c in compared}
    assert res["correct"] is True, [c.line() for c in compared if not c.ok]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert by["compilations_inside_window"].value == 0
    for app in ("bench-a", "bench-b"):
        if f"optimizer_steps[{app}]" in by:
            assert by[f"optimizer_steps[{app}]"].value == 2
            assert by[f"positions_trained_gap[{app}]"].value == 0
            assert by[f"vocabulary_first_seen_bijection[{app}]"].ok
            assert by[f"loss_step1_rel_gap[{app}]"].value < 0.01
            assert by[f"grad_probe_gap_rms[{app}]"].value < 0.2
            assert by[f"delta_rule_probe_rel_gap[{app}]"].value < 1e-5
    # the spans of the engine reach the harness by name; the stages of the
    # shared workflow are read by the metrics that were there
    assert SPANS | {"read_s", "prepare_s", "algo_s"} <= set(res["metrics"])
    # no device plane on the CPU: the device readers find nothing to read
    assert not {"gdn_chunk_device_s", "gdn_chunk_roofline_pct", "seq_mfu_pct",
                "train_device_busy_s"} & set(res["metrics"])
    json.dumps(res)


def test_untraced_run_reports_the_end_to_end_metrics(tmp_path):
    manifest, root = tiny_sequence_root(tmp_path)
    res, _ = harness.execute(
        manifest, CELL, 7, 0.5, False, "cpu", tmp_path / "work", root)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"retrain_s", "setup_s"}


def test_no_reset_at_segment_boundaries_is_not_correct(tmp_path, monkeypatch):
    monkeypatch.setenv("PYTHONPATH", str(SHIM))
    monkeypatch.setenv("BENCH_TEST_BREAK", "seq_no_reset")
    manifest, root = tiny_sequence_root(tmp_path)
    res, compared = harness.execute(
        manifest, CELL, 7, 0.5, False, "cpu", tmp_path / "work", root)
    by = {c.name: c for c in compared}
    assert res["correct"] is False
    broken = [c for c in compared if not c.ok]
    # the first step's rows are whole histories (the longest pack first):
    # the leak is in the later steps' gradients and in the weights
    assert {c.name.split("[")[0] for c in broken} >= {
        "grad_probe_gap_later_steps_rms", "update_rel_l2_max"}
    # what is not broken still holds
    assert all(c.ok for n, c in by.items() if n.startswith((
        "instance_completed", "tensor_shapes", "weights_finite",
        "vocabulary_first_seen", "optimizer_steps", "positions_trained")))


def test_a_bfloat16_state_is_not_correct(tmp_path, monkeypatch):
    """The precision below the stated one, in the delta rule's carried state:
    caught by the one number built for it, and by no other."""
    monkeypatch.setenv("PYTHONPATH", str(SHIM))
    monkeypatch.setenv("BENCH_TEST_BREAK", "seq_bf16_state")
    manifest, root = tiny_sequence_root(tmp_path)
    res, compared = harness.execute(
        manifest, CELL, 7, 0.5, False, "cpu", tmp_path / "work", root)
    assert res["correct"] is False
    broken = {c.name.split("[")[0] for c in compared if not c.ok}
    assert broken == {"delta_rule_probe_rel_gap"}
    worst = max(c.value for c in compared if c.name.startswith("delta_rule_probe"))
    assert worst > 2 * 5e-4


def test_readers_count_what_the_configuration_says():
    from benchmark.readers import device_op_prefix, gdn_roofline, seq_mfu

    cfg = harness.load_json(harness.BENCH / "configs" / "olmo-hybrid-7b-tp2.json")
    per_token = seq_mfu.forward_flops_per_token(cfg, 8192)
    assert 1.2e9 < per_token < 1.3e9  # ISSUE 26: 1.22 GFLOP a token forward
    evidence = {
        "config": cfg, "device": {"kind": "TPU v5 lite"},
        "peaks": harness.load_json(harness.BENCH / "peaks.json"),
        "trace": {"busy_s": 10.0, "ops_by_name": [
            ["fusion.1", 2.0], ["gdn_chunk_fwd.3", 0.5], ["gdn_chunk_bwd.7", 1.0]],
            "scopes": [["seq.gdn/gdn.chunk", "forward", 0.5],
                       ["seq.gdn/gdn.chunk", "recompute", 0.6],
                       ["seq.gdn/gdn.chunk", "backward", 1.4],
                       ["seq.gdn/gdn.intra", "forward", 2.0]]},
    }
    assert device_op_prefix.read(evidence, {"prefix": "gdn_chunk_"}) == 1.5
    # the share: one forward and one backward of all 15 heads of the three
    # linear layers over the 16 rows, over ALL time under the scope (2.5 s)
    share = gdn_roofline.read(evidence, {"scopes": ["gdn.chunk"]})
    least_s = sum(
        max(flops / 197e12, nbytes / 819e9)
        for flops, nbytes in (gdn_roofline.site_least(kind, 16, 45, 8192, 64, 96, 192)
                              for kind in ("fwd", "bwd")))
    assert share == pytest.approx(100 * least_s / 2.5) and 0 < share < 100
    # a call site is one group of heads, as the program runs them and as
    # tier-1 compiles the kernels for the chip (5 heads x 128 chunks a call)
    from predictionio_tpu.ops import gdn

    assert cfg["delta_rule_heads_per_call"] == gdn.heads_per_block(
        cfg["linear_num_value_heads"]) == 5
    flops, nbytes = gdn_roofline.site_least("fwd", 16, 5, 8192, 64, 96, 192)
    calls = 16 * 5 * 128
    assert flops == calls * 2 * 64 * 192 * (3 * 96 + 64)
    assert nbytes == calls * 4 * (3 * 64 * 96 + 2 * 64 * 192 + 64 * 64)
    assert 0 < seq_mfu.read(evidence, {}) < 100
    # the parent's program has no such kernels: nothing to read, no error
    evidence["trace"].update(
        ops_by_name=[["fusion.1", 2.0]], scopes=[["seq.mlp", "forward", 2.0]])
    assert device_op_prefix.read(evidence, {"prefix": "gdn_chunk_"}) is None
    assert gdn_roofline.read(evidence, {"scopes": ["gdn.chunk"]}) is None
    als = harness.load_json(harness.BENCH / "configs" / "als-ml20m.json")
    assert seq_mfu.read({**evidence, "config": als}, {}) is None
