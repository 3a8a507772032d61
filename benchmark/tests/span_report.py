#!/usr/bin/env python3
"""A builder's look at ONE traced retrain: everything ``trace_reduce.py``
reduces the trace to (every operation by name, device time by named scope
and pass, idle time by the program's innermost span), which the result line
carries only as the per-layer metrics and the ten longest of each.

On the chip, one traced run of a cell through the harness itself; the
reduction is kept at the moment the harness makes it, before the run's
directory goes:

    chiprun -- python3 benchmark/tests/span_report.py run als-ml20m.retrain SEED

writes ``chiprun_out/span_report/<cell>.<seed>.json`` (the reduction, the
result line, the trace's size) and, where it is under 24 MB gzipped, the
trace beside it.  On a trace file that is already there:

    python3 benchmark/tests/span_report.py file PATH.xplane.pb [ROOT SPAN ...]
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import trace_reduce  # noqa: E402

KEEP_TRACE_BYTES = 24 << 20


def report(path: str, **spans) -> dict:
    t0 = time.perf_counter()
    reduced = trace_reduce.reduce_trace(path, **spans)
    return {
        "trace_file": path,
        "trace_bytes": os.path.getsize(path),
        "reduce_s": time.perf_counter() - t0,
        "reduced": reduced,
    }


def run_cell(cell: str, seed: int, seconds: float) -> int:
    """One traced run through the harness's own ``execute``; the report is
    what the harness's own call of the reducer returned."""
    from benchmark import run as harness

    out = REPO / "chiprun_out" / "span_report"
    out.mkdir(parents=True, exist_ok=True)
    found: dict = {}
    reduce_trace = trace_reduce.reduce_trace

    def reduce_and_keep(path, *args, **kwargs):
        # the trace first: a reduction that fails leaves it to look at
        packed = out / f"{cell}.{seed}.xplane.pb.gz"
        with open(path, "rb") as src, gzip.open(packed, "wb", 6) as dst:
            shutil.copyfileobj(src, dst)
        found.update(trace_bytes=os.path.getsize(path),
                     trace_gz_bytes=packed.stat().st_size, reduce_args=kwargs)
        if found["trace_gz_bytes"] > KEEP_TRACE_BYTES:
            packed.unlink()
        t0 = time.perf_counter()
        reduced = reduce_trace(path, *args, **kwargs)
        found.update(reduce_s=time.perf_counter() - t0, reduced=reduced)
        return reduced

    trace_reduce.reduce_trace = reduce_and_keep
    try:
        manifest = harness.load_json(REPO / "BENCHMARK.json")
        result, _ = harness.execute(
            manifest, cell, seed, seconds, True, harness.PLATFORM,
            harness.BENCH / ".work" / cell,
        )
        found["result"] = result
    finally:
        trace_reduce.reduce_trace = reduce_trace
        (out / f"{cell}.{seed}.json").write_text(json.dumps(found, indent=1))
    print(json.dumps(result))
    return 0


def main(argv: list[str]) -> int:
    if argv[:1] == ["file"]:
        spans = {"root": argv[2], "spans": argv[2:]} if argv[2:] else {}
        print(json.dumps(report(argv[1], **spans), indent=1))
        return 0
    if argv[:1] == ["run"]:
        manifest = json.loads((REPO / "BENCHMARK.json").read_text())
        seconds = float(argv[3]) if len(argv) > 3 else manifest["run_seconds"]
        return run_cell(argv[1], int(argv[2]), seconds)
    print(__doc__)
    return 2


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
