"""A tiny copy of the benchmark's data files in a throwaway directory, for the
CPU rehearsals: the real kinds, readers and harness, sizes a CPU test can hold.
Nothing here is reachable from ``run.py``'s command line."""

from __future__ import annotations

import json
from pathlib import Path

from benchmark import run as harness

TINY_DATA = {"nnz": 60_000, "num_users": 600, "num_items": 200}
TINY_FIT_LIMITS = {
    "user_fixedpoint_gap_median_limit": 0.1,
}


def tiny_root(tmp: Path, rate_qps: float = 150.0) -> tuple[dict, Path]:
    """(manifest, root): every configuration cut to ``TINY_DATA``, the thin
    store to 5,000 ratings, the one-chip Pallas path not demanded."""
    manifest = harness.load_json(harness.REPO / "BENCHMARK.json")
    # the serve cells the memory floor took out of the manifest come back
    # the way a later PR would add them: as entries
    later = harness.load_json(harness.BENCH / "tests" / "later_cells.json")
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        manifest[key] += later[key]
    root = tmp / "benchmark"
    for sub in ("configs", "traffic", "cells", "layer_metrics"):
        (root / sub).mkdir(parents=True, exist_ok=True)
    for entry in manifest["configs"]:
        cfg = harness.load_json(harness.REPO / entry["file"])
        cfg["data"].update(TINY_DATA)
        # the CPU trains on the scatter step, not the chip's kernel
        if "train" in cfg:
            cfg["train"]["als_path"] = "als.train_step"
            # the fit's limits belong to a size: at this one twenty
            # iterations leave the user side 0.005-0.03 from its fixed point
            cfg["reference"].update(TINY_FIT_LIMITS)
        (tmp / entry["file"]).write_text(json.dumps(cfg))
    for path in (harness.BENCH / "traffic").glob("*.json"):
        tr = harness.load_json(path)
        if "store" in tr:
            tr["store"]["nnz"] = 5_000
            tr["checked_answers"] = 64
            tr["warmup"] = {"sequential": 3, "seconds": 0.5}
            tr["trace"] = {"start_s": 0.5, "seconds": 1.0}
        (root / "traffic" / path.name).write_text(json.dumps(tr))
    for w in manifest["workloads"]:
        if (harness.BENCH / "cells" / f"{w['name']}.json").is_file():
            (root / "cells" / f"{w['name']}.json").write_text(
                json.dumps({"rate_qps": rate_qps})
            )
    for path in (harness.BENCH / "layer_metrics").glob("*.json"):
        (root / "layer_metrics" / path.name).write_text(path.read_text())
    (root / "peaks.json").write_text((harness.BENCH / "peaks.json").read_text())
    return manifest, root


ALS_SERVE = "als-ml20m.serve-steady"


def add_als_serve_cell(manifest: dict, root: Path) -> None:
    """The cell PERF.md keeps for later (served ALS answers from the host
    replica, so its traced run shows no device operation and the contract
    refuses it): added back here the way a later PR would add it, by a
    ``workloads`` entry, metric listings and a ``cells`` file."""
    manifest["workloads"].append({
        "name": ALS_SERVE, "config": "als-ml20m", "traffic": "serve-steady",
        "chips": 1, "why": "test: the host-replica serve path"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] == "serve_p50_ms" or (
            m["name"].endswith(".steady") and m["source"] != "device_trace"
        ):
            m["workloads"].append(ALS_SERVE)
    (root / "cells" / f"{ALS_SERVE}.json").write_text(
        json.dumps({"rate_qps": 150.0}))
