"""BENCHMARK.json against the driver's contract, as far as it can be read
without the driver: names, units, lengths, and every file a cell names."""

import importlib
import json
import re

import pytest

from benchmark import run as harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.REPO / "BENCHMARK.json")


def one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert len(json.dumps(manifest)) < 64 * 1024
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    assert len(manifest["command"]) <= 32
    assert all(one_line(w) for w in manifest["command"])
    # the full check has to fit with all 24 cells
    cells = 24
    runs = 2 + 14 * cells
    budget = runs * (manifest["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert budget <= 43200


def test_configs(manifest):
    names = [c["name"] for c in manifest["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"]) and one_line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert PATH.match(c["file"]) and (harness.REPO / c["file"]).is_file()
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert c["name"] in used
        body = harness.load_json(harness.REPO / c["file"])
        assert body["reduced"] == c["reduced"] and body["source"] == c["source"]
        assert "engine_json" in body
        # the configuration's plain reference is a file of its own
        importlib.import_module(f"benchmark.references.{body['reference']['kind']}")


def test_workloads_name_files_that_exist(manifest):
    names = [w["name"] for w in manifest["workloads"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(names) // 4)
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and one_line(w["why"])
        cell, config, params = harness.load_cell(manifest, w["name"])
        importlib.import_module(f"benchmark.kinds.{params['kind']}")
        ref = importlib.import_module(
            "benchmark.references."
            + (params.get("reference") or config["reference"]["kind"]))
        assert callable(getattr(
            ref, "check_retrain" if params["kind"] == "retrain_job" else "served"))


def test_metrics(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m.get("workloads", cells)) <= cells
    layers = set()
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert one_line(m["layer"]) and m["moves"] in e2e
        layers.add(m["layer"])
        # each listed cell reports the metric this one moves
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m["workloads"]) <= set(moved)
        spec = harness.load_json(
            harness.BENCH / "layer_metrics" / f"{m['name']}.json")
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        assert callable(reader.read)
    # every cell reports setup_s, another end-to-end metric and a layer metric
    for cell in cells:
        others = [m for m in manifest["end_to_end"]
                  if m["name"] != "setup_s" and harness.applies(m, cell)]
        assert others, cell
        assert any(harness.applies(m, cell) for m in manifest["per_layer"]), cell


def test_files_under_paths_have_admitted_names(manifest):
    import subprocess

    listed = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard",
         *manifest["paths"]],
        cwd=harness.REPO, capture_output=True, text=True, check=True,
    ).stdout.split()
    assert listed
    for path in listed:
        assert PATH.match(path), path


def test_run_py_names_no_cell_config_mix_or_metric(manifest):
    text = (harness.BENCH / "run.py").read_text()
    names = (
        [w["name"] for w in manifest["workloads"]]
        + [c["name"] for c in manifest["configs"]]
        + [w["traffic"] for w in manifest["workloads"]]
        + [m["name"] for m in manifest["end_to_end"] if m["name"] != "setup_s"]
        + [m["name"] for m in manifest["per_layer"]]
    )
    for name in names:
        assert not re.search(rf"(?<![\w.\-]){re.escape(name)}(?![\w.\-])", text), name
