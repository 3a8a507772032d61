"""The Ouro cell rehearsed on the CPU at a tiny size: the real harness, kind,
worker, reference (its replay in a child) and readers, through
``run.execute`` with the platform ``cpu``; the same run on a program broken
underneath (the carried state in bfloat16; the gate's path detached; the
head's logits in bfloat16), which
must come out not correct by the numbers built to catch it; and the FLOP
count of ``ouro_mfu`` against a hand count."""

import json
from pathlib import Path

import pytest

from benchmark import run as harness
from benchmark.tests.tiny_ouro import CELL, tiny_ouro_root

SHIM = Path(__file__).parent / "shim_ouro"
SEED = 2**31 + 3801

SPANS = {"seq_group_s", "seq_pack_s", "seq_init_s", "seq_loop_s", "seq_fetch_s",
         # the spans every retrain opens, under the names the ALS cell reads them by
         "scan_s", "sort_s", "decode_s", "vocab_s", "persist_s"}


def test_ouro_retrain_cell(tmp_path):
    manifest, root = tiny_ouro_root(tmp_path)
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
            assert by[f"loop_layer_applications_gap[{app}]"].value == 0
            assert by[f"vocabulary_first_seen_bijection[{app}]"].ok
            assert by[f"carry_probe_rel_gap[{app}]"].value < 1e-6
            assert by[f"head_probe_rel_gap[{app}]"].value < 1e-6
    # the engine's spans reach the harness by name, whichever block trained
    assert SPANS | {"read_s", "prepare_s", "algo_s"} <= set(res["metrics"])
    # the loop's counter: 4 passes x 2 layers x 2 rows x 2 steps
    assert res["metrics"]["loop_layer_applications"]["value"] == 32
    # no device plane on the CPU: the device readers find nothing to read
    assert not {"ouro_mfu_pct", "seq_exit_device_s", "loop_last_pass_device_s",
                "train_device_busy_s"} & set(res["metrics"])
    json.dumps(res)


def test_untraced_run_reports_the_end_to_end_metrics(tmp_path):
    manifest, root = tiny_ouro_root(tmp_path)
    res, _ = harness.execute(
        manifest, CELL, 7, 0.5, False, "cpu", tmp_path / "work", root)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"retrain_s", "setup_s"}


def test_a_bfloat16_carried_state_is_not_correct(tmp_path, monkeypatch):
    """The precision below the stated one, in the state handed from pass to
    pass: caught by the carried state's probe (and, at this size, by the
    exits' own numbers: the exit reads the rounded state too)."""
    monkeypatch.setenv("PYTHONPATH", str(SHIM))
    monkeypatch.setenv("BENCH_TEST_BREAK", "ouro_bf16_carry")
    manifest, root = tiny_ouro_root(tmp_path)
    res, compared = harness.execute(
        manifest, CELL, 7, 0.5, False, "cpu", tmp_path / "work", root)
    assert res["correct"] is False
    broken = {c.name.split("[")[0] for c in compared if not c.ok}
    assert "carry_probe_rel_gap" in broken
    worst = max(c.value for c in compared if c.name.startswith("carry_probe"))
    assert worst > 10 * 3e-6


def test_a_detached_gate_is_not_correct(tmp_path, monkeypatch):
    monkeypatch.setenv("PYTHONPATH", str(SHIM))
    monkeypatch.setenv("BENCH_TEST_BREAK", "ouro_gate_detached")
    manifest, root = tiny_ouro_root(tmp_path)
    res, compared = harness.execute(
        manifest, CELL, 7, 0.5, False, "cpu", tmp_path / "work", root)
    by = {c.name: c for c in compared}
    assert res["correct"] is False
    broken = {c.name.split("[")[0] for c in compared if not c.ok}
    # the gate's own gradient is gone: its norm, its probes, its update
    assert {"grad_norm_step1_rel_gap_max", "grad_probe_gap_exit_rms",
            "update_rel_l2_exit_max"} <= broken
    # what is not broken still holds: the forward pass is the sound one
    assert all(c.ok for n, c in by.items() if n.startswith((
        "instance_completed", "tensor_shapes", "weights_finite",
        "vocabulary_first_seen", "optimizer_steps", "positions_trained",
        "loop_layer_applications", "loss_step1", "exit_probe", "carry_probe")))


def test_bfloat16_logits_are_not_correct(tmp_path, monkeypatch):
    """The precision below the stated one, in the head's product: no sum over
    a step sees it (a position's error is a thousandth of its loss and
    averages out), the head's product made again from the kept exit states
    does."""
    monkeypatch.setenv("PYTHONPATH", str(SHIM))
    monkeypatch.setenv("BENCH_TEST_BREAK", "ouro_bf16_logits")
    manifest, root = tiny_ouro_root(tmp_path)
    res, compared = harness.execute(
        manifest, CELL, 7, 0.5, False, "cpu", tmp_path / "work", root)
    assert res["correct"] is False
    assert {c.name.split("[")[0] for c in compared if not c.ok} == {
        "head_probe_rel_gap"}
    worst = max(c.value for c in compared if c.name.startswith("head_probe"))
    assert worst > 10 * 3e-6


def test_ouro_mfu_counts_what_the_configuration_says():
    from benchmark.readers import h1_mfu, ouro_mfu, st_mfu

    cfg = harness.load_json(harness.BENCH / "configs" / "ouro-2.6b-d8.json")
    # a layer's matmul parameters, and the held count the file states
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert layer == 51_380_224
    assert cfg["share"]["parameters_held"] == 8 * (layer + 4 * 2048) + (
        2 * 49152 * 2048 + 2048 + 2049) == 612_438_017
    # one token and no pair, one layer application: the layer, the four
    # exits' heads and gates
    assert ouro_mfu.forward_flops(cfg, 1, 0, 1) == 2.0 * layer + 4 * (
        2.0 * 49152 * 2048 + 2.0 * 2048)
    # one pair, 32 applications: scores and values of 16 heads of 128
    assert ouro_mfu.forward_flops(cfg, 0, 1, 32) == 32 * 4.0 * 16 * 128
    # the cell's own retrain (CPU, the repo's generator: the configuration's
    # ``data.measured``): ISSUE 38's 12.3 GFLOP a trained token and ~0.05
    # PFLOP of attention
    tokens, pairs = 65_491, 63_402_307
    forward = ouro_mfu.forward_flops(cfg, tokens, pairs, 32)
    assert 3 * (forward - pairs * 32 * 4.0 * 16 * 128) / tokens == pytest.approx(
        12.28e9, rel=1e-3)
    assert 3 * pairs * 32 * 4.0 * 16 * 128 == pytest.approx(0.0499e15, rel=1e-2)
    evidence = {
        "config": cfg, "device": {"kind": "TPU v5 lite"},
        "peaks": harness.load_json(harness.BENCH / "peaks.json"),
        "trace": {"busy_s": 10.0},
        "retrain": {"stages": {"counters": {
            "loop_layer_applications": 256, "loop_tokens": tokens,
            "loop_attention_pairs": pairs}}},
    }
    share = ouro_mfu.read(evidence, {})
    assert share == pytest.approx(100 * 3 * forward / 197e12 / 10.0)
    assert 40 < share < 50
    # a program that counts none of it (the parent's): nothing to read
    assert ouro_mfu.read({**evidence, "retrain": {"stages": {"total": 1.0}}}, {}) is None
    assert ouro_mfu.read({**evidence, "trace": None}, {}) is None
    # each block's utilisation reads its own configuration and no other
    for other in ("olmo-hybrid-7b-tp2", "falcon-h1-34b-tp4",
                  "smallthinker-21b-ep4", "als-ml20m"):
        body = harness.load_json(harness.BENCH / "configs" / f"{other}.json")
        assert ouro_mfu.read({**evidence, "config": body}, {}) is None
    # (``seq_mfu`` takes any configuration with ``layer_types`` for the Olmo
    # block's; its metric lists the Olmo cell alone, so it never reads this one)
    for reader in (h1_mfu, st_mfu):
        assert reader.read(evidence, {}) is None
