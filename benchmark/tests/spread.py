#!/usr/bin/env python3
"""Spread of each metric over the runs kept by ``run_many.py``: the distance
between the first and third quartile (``statistics.quantiles(values, n=4)``)
as a share of the median — the number a bound is set from (about five times
the widest spread over the cells, never under 1 %).

    python3 benchmark/tests/spread.py chiprun_out/runs/SET1.jsonl [SET2.jsonl ...]
"""

from __future__ import annotations

import json
import statistics
import sys


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths: list[str]) -> int:
    for path in paths:
        by: dict[tuple[str, str], list[float]] = {}
        rows = [json.loads(line) for line in open(path)]
        bad = [r for r in rows if not r["result"] or not r["result"]["correct"]]
        for r in rows:
            if not r["result"]:
                continue
            for name, m in r["result"]["metrics"].items():
                by.setdefault((r["workload"], name), []).append(m["value"])
        print(f"== {path}: {len(rows)} runs, {len(bad)} not correct or failed")
        for (cell, name), vals in sorted(by.items()):
            if len(vals) < 2:
                print(f"{cell} {name}: {vals}")
                continue
            # the first run of a call compiles: setup_s is judged without it
            kept = vals[1:] if name == "setup_s" and len(vals) > 2 else vals
            print(
                f"{cell} {name}: n={len(kept)} median={statistics.median(kept):.6g} "
                f"min={min(kept):.6g} max={max(kept):.6g} spread={spread(kept):.4%}"
            )
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
