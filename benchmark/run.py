#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data.  ``BENCHMARK.json`` names the cell's
configuration and traffic mix, the end-to-end metrics it reports and the
per-layer metrics that list it; from those names alone this file finds

    the manifest's ``file`` of the configuration     sizes, engine.json, reference limits
    traffic/<traffic>.json                           the mix: its ``kind`` and parameters
    cells/<cell>.json            (optional)          parameters fixed for this cell (its rate)
    kinds/<kind>.py                                  drives set-up, window and check
    layer_metrics/<metric>.json                      which reader, with what arguments
    readers/<reader>.py                              evidence -> one number, or nothing

so a later PR adds a cell, a mix, a metric or a whole kind by adding files and
manifest entries.  No cell, configuration, mix or metric is named in here.

The process never initializes a JAX backend: the chip belongs to one child at
a time (``proc.py``), started under ``JAX_PLATFORMS=tpu``, so without a chip
the child dies and this exits non-zero with no result line.  The last line of
stdout is the result object; everything else goes on earlier lines.
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import sys
import time
import traceback
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable

T_START = time.perf_counter()

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

#: the platform the children must get; there is no other
PLATFORM = "tpu"


@dataclass
class Ctx:
    """What a traffic kind gets."""

    run: Any
    config: dict
    params: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    say: Callable[[str], None]


def say(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:8.2f}s] {msg}", flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(manifest: dict, name: str, root: Path = BENCH) -> tuple[dict, dict, dict]:
    """(cell entry, configuration, traffic parameters) by the cell's name."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = load_json(root.parent / cfg_entry["file"])
    config.setdefault("name", cell["config"])
    params = load_json(root / "traffic" / f"{cell['traffic']}.json")
    override = root / "cells" / f"{name}.json"
    if override.is_file():
        params = {**params, **load_json(override)}
    return cell, config, params


def layer_metrics(manifest: dict, cell: dict, evidence: dict, root: Path = BENCH) -> dict:
    """Every per-layer metric that lists the cell (or lists none and moves a
    metric the cell reports), read by its own reader; a reader that finds
    nothing to read leaves its metric out."""
    reported = {
        m["name"] for m in manifest["end_to_end"] if applies(m, cell["name"])
    }
    out = {}
    for m in manifest["per_layer"]:
        if not applies(m, cell["name"]) or m["moves"] not in reported:
            continue
        spec = load_json(root / "layer_metrics" / f"{m['name']}.json")
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(evidence, spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(manifest: dict, workload: str, seed: int, seconds: float, trace: bool,
            platform: str, work: Path, root: Path = BENCH) -> tuple[dict, list]:
    """One run of one cell -> (result object, the comparisons made)."""
    from benchmark import proc, trace_reduce

    cell, config, params = load_cell(manifest, workload, root)
    kind = importlib.import_module(f"benchmark.kinds.{params['kind']}")
    run = proc.Run(work, platform)
    try:
        res = kind.run(Ctx(run, config, params, seed, seconds, trace, T_START, say))
        device = dict(res["device"])
        proc.require(
            device["count"] >= cell["chips"],
            f"the cell asks for {cell['chips']} chip(s), JAX found {device['count']}",
        )
        evidence = res["evidence"]
        evidence["config"] = config
        evidence["params"] = params
        breakdown = None
        if trace:
            t0 = time.perf_counter()
            reduced = trace_reduce.reduce_trace(
                trace_reduce.find_xplane(res["trace_dir"]),
                **(res.get("trace_spans") or {}),
            )
            scalars = {k: v for k, v in reduced.items() if not isinstance(v, list)}
            scalars["reduce_s"] = round(time.perf_counter() - t0, 3)
            say(f"trace: {json.dumps(scalars)}")
            evidence["trace"] = reduced
            evidence["peaks"] = load_json(root / "peaks.json")
            evidence["device"] = device
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
    finally:
        run.close()

    for c in sorted(res["compared"], key=lambda c: not c.ok):
        say(c.line())
    if trace:
        metrics = layer_metrics(manifest, cell, evidence, root)
    else:
        values = {**res["end_to_end"], "setup_s": res["setup_s"]}
        metrics = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in manifest["end_to_end"]
            if applies(m, cell["name"])
        }
    result = {
        "correct": all(c.ok for c in res["compared"]),
        "attempted": int(res["attempted"]),
        "failed": int(res["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    # last in the line: each number compared beside its limit, those over
    # their limit last of all (the driver's record keeps the line's end)
    result["compared"] = {
        # a reading that is not finite is not JSON: it goes as its name
        c.name: [float(c.value) if math.isfinite(c.value) else repr(float(c.value)),
                 ">=" if c.sense == "min" else "<=", float(c.limit)]
        for c in sorted(res["compared"], key=lambda c: not c.ok)
    }
    return result, res["compared"]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest = load_json(REPO / "BENCHMARK.json")
    try:
        result, compared = execute(
            manifest, args.workload, args.seed, args.seconds, bool(args.trace),
            PLATFORM, BENCH / ".work" / args.workload,
        )
    except Exception:
        # no result line: a run that could not measure reports nothing
        traceback.print_exc()
        return 1
    print(json.dumps(result), flush=True)
    # and as the last lines of standard error, where the driver's record of
    # a run that is not correct keeps them; those over their limit last
    for c in sorted(compared, key=lambda c: not c.ok):
        print(c.line(), file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
