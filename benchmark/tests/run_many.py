#!/usr/bin/env python3
"""Run the benchmark's command several times in one call on the chip and keep
every run's output: ``chiprun_out/runs/<tag>.log`` (everything) and
``chiprun_out/runs/<tag>.jsonl`` (one line per run: the arguments, the exit
code, wall seconds and the result object).

    chiprun --timeout 3000 -- python3 benchmark/tests/run_many.py TAG \
        WORKLOAD:SEED:SECONDS:TRACE [WORKLOAD:SEED:SECONDS:TRACE ...]
"""

from __future__ import annotations

import json
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def main(argv: list[str]) -> int:
    tag, specs = argv[0], argv[1:]
    out = REPO / "chiprun_out" / "runs"
    out.mkdir(parents=True, exist_ok=True)
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    worst = 0
    with open(out / f"{tag}.log", "w") as log, open(out / f"{tag}.jsonl", "w") as rows:
        for spec in specs:
            workload, seed, seconds, trace = spec.split(":")
            cmd = manifest["command"] + [
                "--workload", workload, "--seed", seed,
                "--seconds", seconds, "--trace", trace,
            ]
            t0 = time.perf_counter()
            p = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True)
            wall = time.perf_counter() - t0
            log.write(f"===== {' '.join(cmd)} -> {p.returncode} in {wall:.1f} s\n")
            log.write(p.stdout + "\n----- stderr\n" + p.stderr[-6000:] + "\n")
            log.flush()
            lines = p.stdout.strip().splitlines()
            result = None
            if p.returncode == 0 and lines:
                result = json.loads(lines[-1])
            row = {"workload": workload, "seed": int(seed), "seconds": float(seconds),
                   "trace": int(trace), "rc": p.returncode, "wall_s": wall,
                   "result": result}
            rows.write(json.dumps(row) + "\n")
            rows.flush()
            info = [l for l in lines[:-1] if "compared" in l or "setup" in l or "retrain " in l or "window:" in l or "trace:" in l]
            print("\n".join(info[-30:]))
            print(json.dumps(row))
            if p.returncode != 0:
                print(p.stdout[-1500:], p.stderr[-3000:])
            worst = max(worst, abs(p.returncode))
    return 1 if worst else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
