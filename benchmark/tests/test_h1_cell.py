"""The Falcon-H1 cell rehearsed on the CPU at a tiny size: the real harness,
kind, worker, reference (its replay in a child) and readers, through
``run.execute`` with the platform ``cpu``; and the same run on a program
broken underneath (no reset at segment boundaries; the state space's state in
bfloat16), which must come out not correct by the numbers built to catch
it."""

import json
from pathlib import Path

import pytest

from benchmark import run as harness
from benchmark.tests.tiny_h1 import CELL, tiny_h1_root

SHIM = Path(__file__).parent / "shim_h1"
SEED = 2**31 + 3001

SPANS = {"seq_group_s", "seq_pack_s", "seq_init_s", "seq_loop_s", "seq_fetch_s",
         # the spans every retrain opens, under the names the ALS cell reads them by
         "scan_s", "sort_s", "decode_s", "vocab_s", "persist_s"}


def test_falcon_h1_retrain_cell(tmp_path):
    manifest, root = tiny_h1_root(tmp_path)
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
            assert by[f"ssd_probe_rel_gap[{app}]"].value < 1e-5
    # the engine's spans reach the harness by name, whichever block trained
    assert SPANS | {"read_s", "prepare_s", "algo_s"} <= set(res["metrics"])
    # no device plane on the CPU: the device readers find nothing to read
    assert not {"h1_mfu_pct", "seq_mfu_pct", "train_device_busy_s"} & set(res["metrics"])
    json.dumps(res)


def test_untraced_run_reports_the_end_to_end_metrics(tmp_path):
    manifest, root = tiny_h1_root(tmp_path)
    res, _ = harness.execute(
        manifest, CELL, 7, 0.5, False, "cpu", tmp_path / "work", root)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"retrain_s", "setup_s"}


def test_no_reset_at_segment_boundaries_is_not_correct(tmp_path, monkeypatch):
    monkeypatch.setenv("PYTHONPATH", str(SHIM))
    monkeypatch.setenv("BENCH_TEST_BREAK", "h1_no_reset")
    manifest, root = tiny_h1_root(tmp_path)
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
        "vocabulary_first_seen", "optimizer_steps", "positions_trained",
        "ssd_probe")))


def test_a_bfloat16_state_is_not_correct(tmp_path, monkeypatch):
    """The precision below the stated one, in the state space's carried state:
    caught by the one number built for it, and by no other."""
    monkeypatch.setenv("PYTHONPATH", str(SHIM))
    monkeypatch.setenv("BENCH_TEST_BREAK", "h1_bf16_state")
    manifest, root = tiny_h1_root(tmp_path)
    res, compared = harness.execute(
        manifest, CELL, 7, 0.5, False, "cpu", tmp_path / "work", root)
    assert res["correct"] is False
    broken = {c.name.split("[")[0] for c in compared if not c.ok}
    assert broken == {"ssd_probe_rel_gap"}
    worst = max(c.value for c in compared if c.name.startswith("ssd_probe"))
    assert worst > 1.5 * 5e-4


def test_readers_count_what_the_configuration_says(monkeypatch):
    from benchmark.readers import device_op_prefix, h1_mfu, seq_mfu, ssd_roofline

    cfg = harness.load_json(harness.BENCH / "configs" / "falcon-h1-34b-tp4.json")
    per_token = h1_mfu.forward_flops_per_token(cfg, 8192)
    assert 1.2e9 < per_token < 1.3e9  # ISSUE 30: 2 x 602.5 M and a few MFLOP
    matmul = 2.0 * (4 * (
        5120 * 2568 + 1024 * 5120 + 5120 * 128 * (2 * 5 + 2) + 3 * 5120 * 5376)
        + 32640 * 5120)
    assert matmul == 2.0 * 602_439_680
    assert per_token - matmul == 4 * (
        2 * 2 * 5 * 128 * 8193 / 2 + 8 * 4 * 256 * 128 + 2 * 128 * 256 + 8 * 2 * 128 * 128)
    evidence = {
        "config": cfg, "device": {"kind": "TPU v5 lite"},
        "peaks": harness.load_json(harness.BENCH / "peaks.json"),
        "trace": {"busy_s": 10.0, "ops_by_name": [
            ["fusion.1", 2.0], ["ssd_chunk_fwd.3", 0.05], ["ssd_chunk_bwd.7", 0.1]],
            "scopes": [["seq.ssm/ssm.chunk", "forward", 0.05],
                       ["seq.ssm/ssm.chunk", "recompute", 0.05],
                       ["seq.ssm/ssm.chunk", "backward", 0.1],
                       ["seq.ssm/ssm.intra", "forward", 2.0]]},
    }
    assert 0 < h1_mfu.read(evidence, {}) < 100
    assert device_op_prefix.read(evidence, {"prefix": "ssd_chunk_"}) == 0.15000000000000002
    # the share: one forward and one backward of the 8 held heads of each of
    # the four layers over the 16 rows, over ALL time under the scope (0.2 s)
    share = ssd_roofline.read(evidence, {"scopes": ["ssm.chunk"]})
    least_s = 4 * sum(
        max(flops / 197e12, nbytes / 819e9)
        for flops, nbytes in (ssd_roofline.site_least(kind, 16, 8, 1, 8192, 128, 128, 256)
                              for kind in ("fwd", "bwd")))
    assert share == pytest.approx(100 * least_s / 0.2) and 0 < share < 100
    flops, nbytes = ssd_roofline.site_least("fwd", 16, 8, 1, 8192, 128, 128, 256)
    chunks = 16 * 64
    assert flops == chunks * 8 * 4 * 128 * 256 * 128
    assert nbytes == chunks * 4 * (8 * 2 * 128 * 128 + 2 * 128 * 256)
    flops, nbytes = ssd_roofline.site_least("bwd", 16, 8, 1, 8192, 128, 128, 256)
    assert flops == chunks * 8 * 8 * 128 * 256 * 128
    assert nbytes == chunks * 4 * (8 * (3 * 128 * 128 + 256 * 128) + 4 * 128 * 256)
    # a call site is one layer's held heads over one row, as the program
    # calls the kernel at the configuration's sizes
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import ssd

    seen = []
    monkeypatch.setattr(ssd, "_fwd_call", lambda hpg, cc, bt, xe, av, interpret: (
        seen.append((hpg, cc.shape, bt.shape, xe.shape)),
        (jnp.zeros(xe.shape), jnp.zeros(xe.shape[:2] + (cc.shape[-1], xe.shape[-1]))))[1])
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    H, G, P, N = (cfg[k] for k in (
        "mamba_n_heads", "mamba_n_groups", "mamba_d_head", "mamba_d_state"))
    jax.eval_shape(
        lambda x, dt, a, b, c, seg: ssd.ssd(
            x, dt, a, b, c, seg, cfg["mamba_chunk_size"], "pallas"),
        sds(1, 8192, H, P), sds(1, 8192, H), sds(H), sds(1, 8192, G, N),
        sds(1, 8192, G, N), jax.ShapeDtypeStruct((1, 8192), jnp.int32))
    assert seen == [(8, (1, 64, 128, 256), (1, 64, 256, 128), (8, 64, 128, 128))]
    # the parent's program has no such kernels: nothing to read, no error
    evidence["trace"].update(
        ops_by_name=[["fusion.1", 2.0]], scopes=[["seq.mlp", "forward", 2.0]])
    assert device_op_prefix.read(evidence, {"prefix": "ssd_chunk_"}) is None
    assert ssd_roofline.read(evidence, {"scopes": ["ssm.chunk"]}) is None
    # each block's utilisation reads its own configuration and no other
    olmo = harness.load_json(harness.BENCH / "configs" / "olmo-hybrid-7b-tp2.json")
    assert h1_mfu.read({**evidence, "config": olmo}, {}) is None
    als = harness.load_json(harness.BENCH / "configs" / "als-ml20m.json")
    assert h1_mfu.read({**evidence, "config": als}, {}) is None
    assert seq_mfu.read({**evidence, "config": als}, {}) is None
