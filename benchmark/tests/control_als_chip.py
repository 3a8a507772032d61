#!/usr/bin/env python3
"""The retrain check's readings, on the chip at the cell's own size, in ONE
process that holds the chip: what sound fits give over many seeds, and what
the controls give.

    chiprun --timeout 1500 -- python3 benchmark/tests/control_als_chip.py \
        [--first-seed 2001] [--sound 12] [--bf16 3] [--iterations 10:3] [--plain-reg 1]

For every reading: the configuration's ratings for the seed (who-rated-what is
drawn once; seed k reads app k % 2, the second app's scale reversed as in the
cell), ``ops.als.train_als`` called the way ``ALSAlgorithm.train`` calls it —
the engine's own ``_als_params()`` from the configuration's engine.json — and
the cell's own check (``references/als.check_retrain``) on the factors.  The
event store, the Preparator and persistence are skipped (they do not touch the
numbers; whole runs of the cell give the same readings through them, PERF.md
section 2), so a reading costs ~17 s, not a whole retrain with its writes.

    sound        the program as configured (``hilo`` accumulator, 20 iterations)
    bf16         ``pallas_precision="bf16"``: one MXU pass, the precision below
    iterations   ``num_iterations=N``: iterations left out
    plain-reg    ``scale_reg_with_count=False``: another regulariser than ALS-WR

No option is added to the program or to ``run.py``: the parameters are set
here, in this process.  Readings go to stdout and
``chiprun_out/control/als_readings.jsonl``; exit 0 when every sound reading
was correct and every control was not.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--first-seed", type=int, default=2001)
    ap.add_argument("--sound", type=int, default=12)
    ap.add_argument("--bf16", type=int, default=3)
    ap.add_argument("--iterations", default="10:3", help="N:seeds")
    ap.add_argument("--plain-reg", type=int, default=1)
    ap.add_argument("--root", default=None, help="a rehearsal's data files")
    args = ap.parse_args(argv)

    import numpy as np

    from benchmark import datagen, reference
    from benchmark import run as harness
    from benchmark.kinds import retrain_job

    manifest = harness.load_json(REPO / "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["traffic"] == "retrain")
    _, config, params = harness.load_cell(
        manifest, cell["name"], Path(args.root) if args.root else harness.BENCH)
    os.environ.setdefault("JAX_PLATFORMS", harness.PLATFORM)

    from predictionio_tpu.models.recommendation import engine
    from predictionio_tpu.ops import als
    from predictionio_tpu.utils.runtime import configure_compile_cache

    configure_compile_cache()
    algo = config["engine_json"]["algorithms"][0]["params"]
    base = engine.ALSAlgorithm(engine.ALSAlgorithmParams(
        rank=algo["rank"], num_iterations=algo["numIterations"],
        reg=algo["lambda"], seed=algo["seed"]))._als_params()
    n_iter, n_iter_seeds = (int(x) for x in args.iterations.split(":"))
    plans = (
        [("sound", {})] * args.sound
        + [("bf16", {"pallas_precision": "bf16"})] * args.bf16
        + [(f"iterations{n_iter}", {"num_iterations": n_iter})] * n_iter_seeds
        + [("plain-reg", {"scale_reg_with_count": False})] * args.plain_reg
    )
    data = config["data"]
    ref = reference.load(config["reference"]["kind"])
    out_dir = REPO / "chiprun_out" / "control"
    out_dir.mkdir(parents=True, exist_ok=True)
    as_expected = 0
    with open(out_dir / "als_readings.jsonl", "a") as rows:
        for n, (label, change) in enumerate(plans):
            seed = args.first_seed + n
            app = seed % 2
            u, i, r = datagen.make_movielens_like(
                data["nnz"], data["num_users"], data["num_items"], seed,
                data["structure_seed"])
            r = retrain_job.ratings_of(app, r)
            if n == 0:  # who rated what is the same for every seed
                users, u_at = np.unique(u, return_inverse=True)
                items, i_at = np.unique(i, return_inverse=True)
                u_at, i_at = u_at.astype(np.int32), i_at.astype(np.int32)
            t0 = time.perf_counter()
            state = als.train_als(
                u_at, i_at, r,
                num_users=len(users), num_items=len(items),
                params=dataclasses.replace(base, **change))
            model = {
                "user_factors": np.asarray(state.user_factors),
                "item_factors": np.asarray(state.item_factors),
                "user_vocab": [datagen.user_name(x) for x in users],
                "item_vocab": [datagen.item_name(x) for x in items],
            }
            train_s = time.perf_counter() - t0
            ctx = harness.Ctx(None, config, params, seed, 0.0, False,
                              time.perf_counter(), harness.say)
            compared = ref.check_retrain(ctx, model, "COMPLETED", u, i, r)
            correct = all(c.ok for c in compared)
            as_expected += correct == (label == "sound")
            row = {"label": label, "seed": seed, "app": retrain_job.APPS[app],
                   "train_s": train_s, "path": dict(als.LAST_PLAN_INFO).get("mode"),
                   "precision": dict(als.LAST_PLAN_INFO).get("precision"),
                   "correct": correct,
                   "compared": {c.name: [c.value, c.limit, c.ok] for c in compared}}
            print(json.dumps(row), flush=True)
            rows.write(json.dumps(row) + "\n")
            rows.flush()
    return 0 if as_expected == len(plans) else 1


if __name__ == "__main__":
    raise SystemExit(main())
