"""CPU rehearsals of each traffic kind at a tiny size: the real harness
(``run.execute``), kinds, worker, readers and children (``pio train`` / ``pio
deploy``), called as functions with the platform ``cpu`` — never through
``run.py``'s command line, which demands the chip.  What only the chip can
show (Mosaic kernels, device planes in the trace, device memory) is read off
the chip runs; here the same fields are checked for their CPU values."""

import json
import subprocess
import sys

import pytest

from benchmark import run as harness
from benchmark.tests.tiny import add_als_serve_cell, tiny_root

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
SEED = 2**31 + 12345  # the driver's seeds are large


def run_cell(tmp_path, cell, trace, seconds=2.0, mutate=None):
    manifest, root = tiny_root(tmp_path)
    if mutate:
        mutate(manifest, root)
    result, compared = harness.execute(
        manifest, cell, SEED, seconds, trace, "cpu", tmp_path / "work", root
    )
    assert not (tmp_path / "work").exists()  # the run's world is a throwaway
    return manifest, result, compared


def reported(manifest, cell, key):
    return {m["name"] for m in manifest[key] if harness.applies(m, cell)}


@pytest.mark.parametrize("trace", [False, True])
def test_retrain_job(tmp_path, trace):
    cell = "als-ml20m.retrain"
    manifest, res, compared = run_cell(tmp_path, cell, trace, seconds=1.0)
    assert RESULT_KEYS <= set(res) and res["correct"] is True
    assert res["attempted"] >= 2 and res["failed"] == 0  # 0.4 s retrains
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    by = {c.name: c for c in compared}
    assert by["compilations_inside_window"].value == 0
    # the last retrain of each app is held to the reference
    for app in ("bench-a", "bench-b"):
        assert by[f"last_halfstep_gap_max[{app}]"].value < 1e-3  # f32 scatter step
        assert 0 < by[f"user_fixedpoint_gap_median[{app}]"].value < 0.05
        assert by[f"train_rmse[{app}]"].value < 0.6
    if trace:
        assert set(res["metrics"]) <= reported(manifest, cell, "per_layer")
        # host-clock stages are read; the CPU trace has no device plane, so
        # the device readers find nothing and their metrics are left out
        assert {"read_s", "prepare_s", "algo_s"} <= set(res["metrics"])
        assert "train_device_busy_s" not in res["metrics"]
        assert "train_reserved_gb" not in res["metrics"]  # no statistics here
        assert res["device"]["busy_s"] == 0 and res["device"]["window_s"] > 0
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(res["metrics"]) == reported(manifest, cell, "end_to_end")
        assert res["metrics"]["retrain_s"]["value"] > 0
        assert res["metrics"]["setup_s"]["value"] > res["metrics"]["retrain_s"]["value"]
    json.dumps(res, allow_nan=False)  # the last line is JSON
    # ... and ends with every number compared beside its limit
    assert list(res)[-1] == "compared" and set(res["compared"]) == set(by)
    value, op, limit = res["compared"]["compilations_inside_window"]
    assert (value, op, limit) == (0.0, "<=", 0.0)


@pytest.mark.parametrize(
    "cell,path",
    [
        ("ncf-ml20m.serve-steady", "ncf.device_wave"),
        ("als-ml20m.serve-steady", "als.host_replica"),
        ("ncf-ml20m.serve-overload", "ncf.device_wave"),
    ],
)
def test_serve_open_loop(tmp_path, cell, path):
    manifest, res, compared = run_cell(
        tmp_path, cell, trace=False, mutate=add_als_serve_cell)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 300  # 150 qps x 2 s, the same for every seed
    assert set(res["metrics"]) == reported(manifest, cell, "end_to_end")
    assert all(m["value"] > 0 for m in res["metrics"].values())
    by = {c.name: c for c in compared}
    assert by[f"answers_explained_by_{path}"].value >= 1
    assert by["answers_compared"].value == 64
    assert by["served_score_gap_max"].value < 1e-5


def test_serve_traced(tmp_path):
    cell = "ncf-ml20m.serve-steady"
    manifest, res, _ = run_cell(tmp_path, cell, trace=True, seconds=3.0)
    assert res["correct"] is True
    assert set(res["metrics"]) <= reported(manifest, cell, "per_layer")
    assert {"gen_lag_p95_ms.steady", "http_ms.steady", "queue_wait_ms.steady",
            "wave_fn_ms.steady", "p95_ms.steady", "p99_ms.steady"} <= set(res["metrics"])
    # read, and small: the sandbox's shared cores lag the generator by a
    # millisecond now and then (the sweep's 1 ms rule is for the chip's host)
    assert res["metrics"]["gen_lag_p95_ms.steady"]["value"] < 5.0
    assert res["device"]["window_s"] > 0.5  # the capture ran in the server


def test_a_new_cell_is_files_and_one_entry(tmp_path):
    """A throwaway fourth configuration, a mix and a per-layer metric in a
    temp directory + manifest entries: no file of the harness is touched."""

    def add(manifest, root):
        cfg = harness.load_json(root / "configs" / "als-ml20m.json")
        cfg["name"] = "als-r8"
        cfg["engine_json"]["algorithms"][0]["params"]["rank"] = 8
        (root / "configs" / "als-r8.json").write_text(json.dumps(cfg))
        mix = harness.load_json(root / "traffic" / "serve-steady.json")
        mix["num"] = 5
        mix["rate_qps"] = 80.0  # a mix may fix its own rate
        (root / "traffic" / "serve-num5.json").write_text(json.dumps(mix))
        (root / "layer_metrics" / "wave_size.num5.json").write_text(json.dumps({
            "reader": "histogram_mean",
            "args": {"family": "pio_microbatch_batch_size", "scale": 1.0},
        }))
        manifest["configs"].append({
            "name": "als-r8", "source": "test", "reduced": [], "why": "test",
            "file": "benchmark/configs/als-r8.json"})
        manifest["workloads"].append({
            "name": "als-r8.serve-num5", "config": "als-r8",
            "traffic": "serve-num5", "chips": 1, "why": "test"})
        for m in manifest["end_to_end"]:
            if m["name"] == "serve_p50_ms":
                m["workloads"].append("als-r8.serve-num5")
        manifest["per_layer"].append({
            "name": "wave_size.num5", "unit": "queries", "better": "higher",
            "source": "program_counter", "layer": "MicroBatcher",
            "moves": "serve_p50_ms", "workloads": ["als-r8.serve-num5"]})

    for trace in (False, True):
        _, res, compared = run_cell(
            tmp_path, "als-r8.serve-num5", trace, mutate=add)
        assert res["correct"] is True and res["attempted"] == 160
        if trace:
            assert set(res["metrics"]) == {"wave_size.num5"}
        else:
            assert set(res["metrics"]) == {"serve_p50_ms", "setup_s"}


def test_a_new_check_is_a_file_and_a_cell_entry(tmp_path, monkeypatch):
    """An existing configuration under an existing mix whose check the
    harness lacks (NCF retrains): a reference module of its own, named by the
    new cell's file under ``cells/``, and one ``workloads`` entry."""
    import benchmark.references as refs

    (tmp_path / "refs").mkdir()
    (tmp_path / "refs" / "ncf_tables.py").write_text(
        "import numpy as np\n"
        "from benchmark.reference import Compared\n"
        "def check_retrain(ctx, model, status, user_idx, item_idx, rating):\n"
        "    emb = np.asarray(model['params']['user_emb'])\n"
        "    return [Compared('instance_completed', float(status == 'COMPLETED'), 1.0, 'min'),\n"
        "            Compared('user_rows', float(len(emb)), float(len(np.unique(user_idx))), 'min')]\n"
    )
    monkeypatch.setattr(refs, "__path__", list(refs.__path__) + [str(tmp_path / "refs")])
    cell = "ncf-ml20m.retrain"

    def add(manifest, root):
        manifest["workloads"].append({
            "name": cell, "config": "ncf-ml20m", "traffic": "retrain",
            "chips": 1, "why": "test"})
        for m in manifest["end_to_end"]:
            if m["name"] == "retrain_s":
                m["workloads"].append(cell)
        (root / "cells" / f"{cell}.json").write_text(
            json.dumps({"reference": "ncf_tables"}))

    _, res, compared = run_cell(tmp_path, cell, False, seconds=0.5, mutate=add)
    by = {c.name: c for c in compared}
    assert res["attempted"] >= 1 and set(res["metrics"]) == {"retrain_s", "setup_s"}
    assert by["instance_completed[bench-b]"].ok and by["user_rows[bench-b]"].ok
    # what the cell would still have to settle (PERF.md section 7): an NCF
    # retrain on the second app compiles inside the window, which the kind's
    # own comparison reports
    assert res["correct"] is by["compilations_inside_window"].ok


def test_without_a_chip_run_py_prints_no_result():
    """The command line demands the chip: here its first chip child dies,
    the exit code is non-zero and stdout ends without a result object."""
    p = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"),
         "--workload", "als-ml20m.retrain", "--seed", str(SEED),
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=600,
    )
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert not (harness.BENCH / ".work" / "als-ml20m.retrain").exists()
