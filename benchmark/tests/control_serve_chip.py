#!/usr/bin/env python3
"""The serve cells' control, on the chip at the cells' own size.

    chiprun --timeout 1800 -- python3 benchmark/tests/control_serve_chip.py \
        --workload <serve cell> [--seconds 3] SEED [SEED ...]

For each seed: the cell's own set-up and a short window at the cell's own rate
(``kinds/serve_open_loop.run``), whose answers the cell's check holds to the
float32 reference — the SOUND reading.  Then the control: for the same checked
users, the reference computed in one bfloat16 pass (tables rounded to bf16,
what the TPU's DEFAULT precision does to a float32 contraction) is put in the
program's place and held to the same check.  Readings of both go to stdout and
``chiprun_out/control/serve_<cell>.jsonl``; exit 0 when every sound run was
correct and every control was not.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--platform", default=None)
    ap.add_argument("--root", default=None, help="a rehearsal's data files")
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args(argv)

    from benchmark import proc, reference
    from benchmark import run as harness
    from benchmark.kinds import serve_open_loop as kind

    manifest = harness.load_json(REPO / "BENCHMARK.json")
    _, config, params = harness.load_cell(
        manifest, args.workload, Path(args.root) if args.root else harness.BENCH)
    num = int(params["num"])
    tol = float(config["reference"]["score_tolerance"])
    out_dir = REPO / "chiprun_out" / "control"
    out_dir.mkdir(parents=True, exist_ok=True)
    good = 0
    with open(out_dir / f"serve_{args.workload}.jsonl", "a") as rows:
        for seed in args.seeds:
            run = proc.Run(
                harness.BENCH / ".work" / f"control-{args.workload}",
                args.platform or harness.PLATFORM,
            )
            try:
                ctx = harness.Ctx(run, config, params, seed, args.seconds,
                                  False, time.perf_counter(), harness.say)
                res = kind.run(ctx)
                sound = {c.name: [c.value, c.limit, c.ok] for c in res["compared"]}
                model = run.persisted_model(res["evidence"]["instance"])
            finally:
                run.close()
            served = reference.load(config["reference"]["kind"]).served
            ref, control = served(model), served(model, lower_precision=True)
            answers = [
                (u, reference.top_items(control, u, num))
                for u in res["evidence"]["checked_users"]
            ]
            ctl = reference.compare_topk(answers, ref, num, tol)
            for c in ctl:
                harness.say("control " + c.line())
            row = {
                "workload": args.workload, "seed": seed,
                "sound_correct": all(v[2] for v in sound.values()),
                "sound": sound,
                "control_correct": all(c.ok for c in ctl),
                "control": {c.name: [c.value, c.limit, c.ok] for c in ctl},
            }
            good += row["sound_correct"] and not row["control_correct"]
            print(json.dumps(row), flush=True)
            rows.write(json.dumps(row) + "\n")
            rows.flush()
    return 0 if good == len(args.seeds) else 1


if __name__ == "__main__":
    raise SystemExit(main())
