"""The SmallThinker cell rehearsed on the CPU at a tiny size: the real
harness, kind, worker, reference (its replay in a child) and readers, through
``run.execute`` with the platform ``cpu``; and the same run on a program
broken underneath (the sliding layers without their window; pairs dropped at
a capacity of 1.25), which must come out not correct by the numbers built to
catch it."""

import json
from pathlib import Path

import pytest

from benchmark import run as harness
from benchmark.tests.tiny_smallthinker import CELL, tiny_smallthinker_root

SHIM = Path(__file__).parent / "shim_st"
SEED = 2**31 + 3201

SPANS = {"seq_group_s", "seq_pack_s", "seq_init_s", "seq_loop_s", "seq_fetch_s",
         # the spans every retrain opens, under the names the ALS cell reads them by
         "scan_s", "sort_s", "decode_s", "vocab_s", "persist_s"}


def test_smallthinker_retrain_cell(tmp_path):
    manifest, root = tiny_smallthinker_root(tmp_path)
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
            assert by[f"moe_pairs_total_gap[{app}]"].value == 0
            assert by[f"route_flip_share_layer1[{app}]"].value == 0
            assert by[f"moe_probe_rel_gap[{app}]"].value < 2e-4
    # the engine's spans reach the harness by name, whichever block trained,
    # and the routing counters through the ``stages`` extra
    assert SPANS | {"read_s", "prepare_s", "algo_s", "moe_load_peak_ratio"} <= set(
        res["metrics"])
    assert 1.0 <= res["metrics"]["moe_load_peak_ratio"]["value"] <= 4.0
    # no device plane on the CPU: the device readers find nothing to read
    assert not {"st_mfu_pct", "h1_mfu_pct", "seq_mfu_pct", "train_device_busy_s"} & set(
        res["metrics"])
    json.dumps(res)


def test_untraced_run_reports_the_end_to_end_metrics(tmp_path):
    manifest, root = tiny_smallthinker_root(tmp_path)
    res, _ = harness.execute(
        manifest, CELL, 7, 0.5, False, "cpu", tmp_path / "work", root)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"retrain_s", "setup_s"}


def _broken(tmp_path, monkeypatch, what):
    monkeypatch.setenv("PYTHONPATH", str(SHIM))
    monkeypatch.setenv("BENCH_TEST_BREAK", what)
    manifest, root = tiny_smallthinker_root(tmp_path)
    res, compared = harness.execute(
        manifest, CELL, 7, 0.5, False, "cpu", tmp_path / "work", root)
    assert res["correct"] is False
    exact = ("instance_completed", "tensor_shapes", "weights_finite",
             "vocabulary_first_seen", "optimizer_steps", "positions_trained",
             "moe_pairs_total", "route_flip_share_layer1")
    by = {c.name: c for c in compared}
    assert all(c.ok for n, c in by.items() if n.startswith(exact))
    return {c.name.split("[")[0] for c in compared if not c.ok}, by


def test_sliding_layers_without_their_window_are_not_correct(tmp_path, monkeypatch):
    """Histories longer than the window see too far back: attention's
    gradients and the weights move; the first layer is global and its router
    reads exact inputs, so its choices and the expert probe stay."""
    broken, by = _broken(tmp_path, monkeypatch, "st_no_window")
    assert broken >= {"grad_probe_gap_attention_rms", "update_rel_l2_max"}
    assert all(c.ok for n, c in by.items() if n.startswith("moe_probe"))


def test_pairs_dropped_at_a_capacity_are_not_correct(tmp_path, monkeypatch):
    """The fault a capacity factor makes: caught by the number built for it
    (the first layer's experts against the dense reference), which nothing
    else of the first layer can explain."""
    broken, by = _broken(tmp_path, monkeypatch, "st_dropped_pairs")
    assert "moe_probe_rel_gap" in broken
    worst = max(c.value for c in by.values() if c.name.startswith("moe_probe"))
    assert worst > 20 * 1e-3


def test_readers_count_what_the_configuration_says(monkeypatch):
    from benchmark.readers import (
        device_op_prefix, h1_mfu, moe_roofline, st_mfu, stage_counter)

    cfg = harness.load_json(harness.BENCH / "configs" / "smallthinker-21b-ep4.json")
    segments = st_mfu.trained_segments(cfg)
    assert len(segments) == 256 and sum(segments) == 258_048  # nothing cut
    assert max(segments) == 16_226 and sum(n > 4096 for n in segments) == 9
    assert st_mfu.pairs_of_segment(5, None) == 15 == st_mfu.pairs_of_segment(5, 8)
    assert st_mfu.pairs_of_segment(5, 2) == 3 + 3 * 2  # 1 + 2 + 2 + 2 + 2
    # ISSUE 32's arithmetic, per token: 1,567 and 1,231 pairs a layer
    assert round(sum(st_mfu.pairs_of_segment(n, None) for n in segments) / 258_048) == 1567
    assert round(sum(st_mfu.pairs_of_segment(n, 4096) for n in segments) / 258_048) == 1231
    # counters as a retrain of the cell writes them: 1.5 pairs a token held
    counters = {"moe_pairs_total": 4 * 6 * 258_048, "moe_pairs_held": 4 * 387_072,
                "moe_experts_held": 16}
    per_token = st_mfu.forward_flops(cfg, counters, segments) / 258_048
    assert 225e6 < per_token < 235e6  # ISSUE 32: 230 MFLOP a token
    experts = 4 * 1.5 * 2 * 3 * 2560 * 768
    router = 4 * 2 * 2560 * 64
    proj = 4 * 2 * 2560 * 128 * (2 * 7 + 2 * 1)
    head = 2 * 18_992 * 2560
    scores = 2 * 2 * 7 * 128 * (
        sum(st_mfu.pairs_of_segment(n, None) for n in segments)
        + 3 * sum(st_mfu.pairs_of_segment(n, 4096) for n in segments)) / 258_048
    assert abs(per_token - (experts + router + proj + head + scores)) < 1.0
    assert round(experts / 1e6, 1) == 70.8 and round(head / 1e6, 1) == 97.2
    evidence = {
        "config": cfg, "device": {"kind": "TPU v5 lite"},
        "peaks": harness.load_json(harness.BENCH / "peaks.json"),
        "retrain": {"stages": {"total": 8.0, "counters": counters}},
        "trace": {"busy_s": 3.0, "ops_by_name": [
            ["fusion.1", 0.5], ["moe_gmm_gate_up.3", 0.02], ["moe_tgmm_down.7", 0.03]],
            "scopes": [["seq.moe/moe.experts", "forward", 0.1],
                       ["seq.moe/moe.experts", "recompute", 0.1],
                       ["seq.moe/moe.experts", "backward", 0.3],
                       ["seq.moe/moe.route", "forward", 1.0]]},
    }
    mfu = st_mfu.read(evidence, {})
    assert mfu == pytest.approx(100.0 * 3 * per_token * 258_048 / 197e12 / 3.0)
    assert 25 < mfu < 35
    assert device_op_prefix.read(evidence, {"prefix": "moe_"}) == 0.05
    # the share: the six products of the counted pairs of each of the four
    # layers, over ALL time under the scope (0.5 s)
    pairs = 387_072.0
    share = moe_roofline.read(evidence, {"scopes": ["moe.experts"]})
    least_s = 4 * sum(
        max(flops / 197e12, nbytes / 819e9)
        for flops, nbytes in (moe_roofline.site_least(name, pairs, 2560, 768, 16)
                              for name in moe_roofline.PRODUCTS))
    assert len(moe_roofline.PRODUCTS) == 6
    assert share == pytest.approx(100 * least_s / 0.5) and 0 < share < 100
    flops, nbytes = moe_roofline.site_least("moe_gmm_gate_up.3", pairs, 2560, 768, 16)
    assert flops == 2 * pairs * 2560 * 1536
    assert nbytes == pairs * (2560 * 2 + 1536 * 4) + 16 * 2560 * 1536 * 2
    flops, nbytes = moe_roofline.site_least("moe_tgmm_down.7", pairs, 2560, 768, 16)
    assert flops == 2 * pairs * 2560 * 768
    assert nbytes == pairs * (2560 + 768) * 2 + 16 * 2560 * 768 * 4
    # the grouped products as the program calls them at the configuration's
    # sizes: one call a product, the pairs' buffer sized for the worst case
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import moe

    seen = []
    monkeypatch.setattr(moe, "gmm", lambda lhs, rhs, plan, **kw: (
        seen.append((kw["name"], lhs.shape, rhs.shape)),
        jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32))[1])
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    jax.eval_shape(
        lambda m, logits, valid, g, u, d: moe.experts_layer(
            m, logits, valid, g, u, d, k=6, start=0, tile=256, dtype=jnp.bfloat16),
        sds(16384, 2560), sds(16384, 64), jax.ShapeDtypeStruct((16384,), jnp.bool_),
        sds(16, 2560, 768), sds(16, 2560, 768), sds(16, 768, 2560))
    assert seen == [("moe_gmm_gate_up", (102_400, 2560), (16, 2560, 1536)),
                    ("moe_gmm_down", (102_400, 768), (16, 768, 2560))]
    # the parent's program has no such kernels and counts nothing: nothing to
    # read, no error
    scopes = evidence["trace"]["scopes"]
    evidence["trace"].update(
        ops_by_name=[["fusion.1", 2.0]], scopes=[["seq.attn", "forward", 2.0]])
    assert device_op_prefix.read(evidence, {"prefix": "moe_"}) is None
    assert moe_roofline.read(evidence, {"scopes": ["moe.experts"]}) is None
    evidence["retrain"]["stages"].pop("counters")
    evidence["trace"]["scopes"] = scopes
    assert moe_roofline.read(evidence, {"scopes": ["moe.experts"]}) is None
    assert st_mfu.read(evidence, {}) is None
    assert stage_counter.read(evidence, {"key": "moe_pairs_held"}) is None
    # each block's utilisation reads its own configuration and no other
    falcon = harness.load_json(harness.BENCH / "configs" / "falcon-h1-34b-tp4.json")
    evidence["retrain"]["stages"]["counters"] = counters
    assert st_mfu.read({**evidence, "config": falcon}, {}) is None
    assert h1_mfu.read(evidence, {}) is None
