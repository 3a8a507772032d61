"""The Nemotron-H cell rehearsed on the CPU at a tiny size: the real harness,
kind, worker, reference (its replay in a child) and readers, through
``run.execute`` with the platform ``cpu``; the same run on a program broken
underneath (the shared expert left out; a softmax over the chosen logits; the
experts' weight gradients rounded to bfloat16), which must come out not
correct by the numbers built to catch it; and the three new readers' counts
against hand counts."""

import json
from pathlib import Path

import pytest

from benchmark import run as harness
from benchmark.tests.tiny_nemotron import CELL, tiny_nemotron_root

SHIM = Path(__file__).parent / "shim_nemotron"
SEED = 2**31 + 4001

SPANS = {"seq_group_s", "seq_pack_s", "seq_init_s", "seq_loop_s", "seq_fetch_s",
         "scan_s", "sort_s", "decode_s", "vocab_s", "persist_s"}


def test_nemotron_retrain_cell(tmp_path):
    manifest, root = tiny_nemotron_root(tmp_path)
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
            assert by[f"route_flip_clear_share[{app}]"].value == 0
            assert by[f"moe_probe_rel_gap[{app}]"].value < 1e-3
            assert by[f"moe_grad_probe_rel_gap[{app}]"].value < 1e-4
            assert by[f"ssd_probe_rel_gap[{app}]"].value < 1e-4
            assert f"route_flip_share_layer2[{app}]" in by  # two routed layers
            assert f"route_flip_share_layer3[{app}]" not in by
    assert SPANS | {"read_s", "prepare_s", "algo_s", "moe_load_peak_ratio"} <= set(
        res["metrics"])
    assert 1.0 <= res["metrics"]["moe_load_peak_ratio"]["value"] <= 4.0
    # no device plane on the CPU: the device readers find nothing to read
    assert not {"nemotron_mfu_pct", "relu2_experts_roofline_pct",
                "nemotron_ssd_chunk_roofline_pct", "moe_shared_device_s",
                "train_device_busy_s"} & set(res["metrics"])
    json.dumps(res)


def test_untraced_run_reports_the_end_to_end_metrics(tmp_path):
    manifest, root = tiny_nemotron_root(tmp_path)
    res, _ = harness.execute(
        manifest, CELL, 7, 0.5, False, "cpu", tmp_path / "work", root)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"retrain_s", "setup_s"}


@pytest.mark.parametrize("what, caught", [
    ("nemotron_no_shared", "moe_probe_rel_gap"),
    ("nemotron_softmax", "moe_probe_rel_gap"),
    ("nemotron_bf16_tgmm", "moe_grad_probe_rel_gap"),
])
def test_a_program_broken_underneath_is_not_correct(tmp_path, monkeypatch, what, caught):
    monkeypatch.setenv("PYTHONPATH", str(SHIM))
    monkeypatch.setenv("BENCH_TEST_BREAK", what)
    manifest, root = tiny_nemotron_root(tmp_path)
    res, compared = harness.execute(
        manifest, CELL, 7, 0.5, False, "cpu", tmp_path / "work", root)
    assert res["correct"] is False
    exact = ("instance_completed", "tensor_shapes", "weights_finite",
             "vocabulary_first_seen", "optimizer_steps", "positions_trained",
             "moe_pairs_total", "ssd_probe")
    by = {c.name: c for c in compared}
    assert all(c.ok for n, c in by.items() if n.startswith(exact))
    assert caught in {c.name.split("[")[0] for c in compared if not c.ok}


def test_readers_count_what_the_configuration_says(monkeypatch):
    from benchmark.readers import (
        moe_roofline, nemotron_mfu, nemotron_ssd_roofline, relu2_moe_roofline,
        ssd_roofline, st_mfu)

    cfg = harness.load_json(harness.BENCH / "configs" / "nemotron3-nano-30b-ep8.json")
    assert nemotron_mfu.layer_kinds(cfg) == "MEMEM*EME"
    segments = st_mfu.trained_segments(cfg)
    assert len(segments) == 508 and sum(segments) == 262_080
    assert max(segments) == 8192 and min(segments) == 24  # the shortest lie in row 33
    pairs_qk = sum(n * (n + 1) // 2 for n in segments)
    assert pairs_qk == 181_022_625
    # counters as a retrain of the cell writes them: 0.75 pairs a token held
    tokens = 262_080
    held = 4 * 196_560
    counters = {"moe_pairs_total": 4 * 6 * tokens, "moe_pairs_held": held,
                "moe_experts_held": 16, "moe_routed_layers": 4}
    by_kind = nemotron_mfu.forward_flops_by_kind(cfg, counters, segments)
    mamba = 2 * 2688 * (1288 + 512) + 2 * 128 * 128 + 8 * (2 * 128 * 64 + 4 * 128 * 64)
    assert by_kind["M"] == 4 * tokens * mamba and 10.0e6 < mamba < 10.2e6
    assert by_kind["*"] == tokens * 2 * 2688 * 128 * 10 + 4 * 4 * 128 * pairs_qk
    assert by_kind["E"] == 4 * tokens * 2 * 2688 * (128 + 2 * 464) + held * 4 * 2688 * 1856
    assert by_kind["head"] == tokens * 2 * 16384 * 2688
    per_token = sum(by_kind.values()) / tokens
    assert 205e6 < per_token < 225e6  # ISSUE 40: 219 MFLOP a token
    assert 0.38 < by_kind["head"] / sum(by_kind.values()) < 0.43  # the head's 40 %
    evidence = {
        "config": cfg, "device": {"kind": "TPU v5 lite"},
        "peaks": harness.load_json(harness.BENCH / "peaks.json"),
        "retrain": {"stages": {"total": 9.0, "counters": counters}},
        "trace": {"busy_s": 4.0, "scopes": [
            ["seq.moe/moe.experts", "forward", 0.1],
            ["seq.moe/moe.experts", "recompute", 0.1],
            ["seq.moe/moe.experts", "backward", 0.3],
            ["seq.moe/moe.shared", "forward", 0.05],
            ["seq.ssm/ssm.chunk", "forward", 0.1],
            ["seq.ssm/ssm.chunk", "backward", 0.3]]},
    }
    mfu = nemotron_mfu.read(evidence, {})
    assert mfu == pytest.approx(100.0 * 3 * per_token * tokens / 197e12 / 4.0)
    assert 15 < mfu < 25
    # the experts' share: six products of the counted pairs of each of the
    # four routed layers, over ALL time under the scope (0.5 s)
    pairs = held / 4
    share = relu2_moe_roofline.read(evidence, {"scopes": ["moe.experts"]})
    least_s = 4 * sum(
        max(flops / 197e12, nbytes / 819e9)
        for flops, nbytes in (relu2_moe_roofline.site_least(name, pairs, 2688, 1856, 16)
                              for name in relu2_moe_roofline.PRODUCTS))
    assert len(relu2_moe_roofline.PRODUCTS) == 6
    assert share == pytest.approx(100 * least_s / 0.5) and 0 < share < 100
    flops, nbytes = relu2_moe_roofline.site_least("moe_gmm_up.3", pairs, 2688, 1856, 16)
    assert flops == 2 * pairs * 2688 * 1856
    assert nbytes == pairs * (2688 * 2 + 1856 * 4) + 16 * 2688 * 1856 * 2
    flops, nbytes = relu2_moe_roofline.site_least("moe_gmm_up_dlhs", pairs, 2688, 1856, 16)
    assert nbytes == pairs * (1856 * 2 + 2688 * 4) + 16 * 2688 * 1856 * 2
    flops, nbytes = relu2_moe_roofline.site_least("moe_gmm_down", pairs, 2688, 1856, 16)
    assert nbytes == pairs * (1856 * 2 + 2688 * 4) + 16 * 2688 * 1856 * 2
    flops, nbytes = relu2_moe_roofline.site_least("moe_tgmm_up.7", pairs, 2688, 1856, 16)
    assert nbytes == pairs * (2688 + 1856) * 2 + 16 * 2688 * 1856 * 4
    # the state space's: ssd_roofline's count with this configuration's keys
    # and its FOUR M layers, over ssm.chunk (0.4 s)
    ssd = nemotron_ssd_roofline.read(evidence, {"scopes": ["ssm.chunk"]})
    fwd = ssd_roofline.site_least("fwd", 32, 8, 1, 8192, 128, 64, 128)
    bwd = ssd_roofline.site_least("bwd", 32, 8, 1, 8192, 128, 64, 128)
    assert fwd[0] == 32 * 64 * 8 * 4 * 128 * 128 * 64
    want = 4 * sum(max(f / 197e12, b / 819e9) for f, b in (fwd, bwd))
    assert ssd == pytest.approx(100 * want / 0.4) and 0 < ssd < 100
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
        lambda m, logits, valid, u, d, b: moe.experts_layer(
            m, logits, valid, None, u, d, k=6, start=0, tile=256,
            dtype=jnp.bfloat16, bias=b, scale=2.5),
        sds(8192, 2688), sds(8192, 128), jax.ShapeDtypeStruct((8192,), jnp.bool_),
        sds(16, 2688, 1856), sds(16, 1856, 2688), sds(128))
    assert moe.plan_rows(8192, 6, 16, 256) == 53_248
    assert seen == [("moe_gmm_up", (53_248, 2688), (16, 2688, 1856)),
                    ("moe_gmm_down", (53_248, 1856), (16, 1856, 2688))]
    assert (moe._tgmm_block(1856), moe._tgmm_block(2688)) == (640, 896)
    # a program without the scopes or the counters (the parent's), and a
    # configuration of another block: nothing to read, no error
    scopes = evidence["trace"]["scopes"]
    evidence["trace"]["scopes"] = [["seq.attn", "forward", 2.0]]
    assert relu2_moe_roofline.read(evidence, {"scopes": ["moe.experts"]}) is None
    assert nemotron_ssd_roofline.read(evidence, {"scopes": ["ssm.chunk"]}) is None
    evidence["trace"]["scopes"] = scopes
    evidence["retrain"]["stages"].pop("counters")
    assert relu2_moe_roofline.read(evidence, {"scopes": ["moe.experts"]}) is None
    assert nemotron_mfu.read(evidence, {}) is None
    evidence["retrain"]["stages"]["counters"] = counters
    other = harness.load_json(harness.BENCH / "configs" / "smallthinker-21b-ep4.json")
    assert nemotron_mfu.read({**evidence, "config": other}, {}) is None
    assert relu2_moe_roofline.read(
        {**evidence, "config": other}, {"scopes": ["moe.experts"]}) is None
    assert nemotron_ssd_roofline.read(
        {**evidence, "config": other}, {"scopes": ["ssm.chunk"]}) is None
    # and the older blocks' readers read nothing of this configuration
    assert st_mfu.read(evidence, {}) is None
    assert moe_roofline.PRODUCTS != relu2_moe_roofline.PRODUCTS
