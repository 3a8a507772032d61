#!/usr/bin/env python3
"""One seed of the ALS cell under the glass, on the chip at the cell's own
size, in ONE process that holds the chip: where a fit's user rows lie against
their float64 half-step, for EVERY rated user and not the check's 256, by the
row's rating count, and what the program's other accumulator precision
(``highest``, six passes: exact float32) and more iterations do to them.

    chiprun --timeout 1500 -- python3 benchmark/tests/probe_als_seed_chip.py \
        --seeds 340205840:1,340205840:0,340205841:1 [--plans hilo20,highest20,hilo19,hilo40]

``seed:app`` as in the cell (app 1 reads the scale reversed).  A plan is an
accumulator precision and an iteration count.  ``ops.als.train_als`` is called
the way ``control_als_chip.py`` calls it; the check's own ``Compared`` rows are
printed beside the whole-population readings.  Readings go to stdout and
``chiprun_out/probe/als_seed.jsonl``.

The program draws its initial factors BY TABLE POSITION, and a retrain through
the event store finds users and items in the store's scan order, not in the
generator's.  ``--through-store 1`` makes the first ``seed:app`` one whole
retrain of the cell's own kind first (worker child, parquet store, Preparator,
persisted model: ``kinds/retrain_job``'s pieces), prints that model's readings
as plan ``store``, takes the model's vocabulary as the order of positions for
every direct call after it (who rated what is the same for every seed, so the
order is too), and says how far the first direct fit lies from the persisted
one.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

COUNT_BINS = ((1, 3), (4, 10), (11, 100), (101, 10**9))


def quantiles(x) -> dict:
    import numpy as np

    return {
        "median": float(np.median(x)), "p95": float(np.quantile(x, 0.95)),
        "p99": float(np.quantile(x, 0.99)), "max": float(np.max(x)),
    }


def positions(vocab: list, ids):
    """(the generator's ids in the vocabulary's order, each id's position)."""
    import numpy as np

    order = np.array([int(k[1:]) for k in vocab], np.int64)
    pos = np.full(order.max() + 1, -1, np.int64)
    pos[order] = np.arange(len(order))
    return order, pos[ids]


def retrain_through_store(config: dict, platform: str, word: str) -> dict:
    """One retrain of ``seed:app`` the cell's own way; the persisted model."""
    from benchmark import datagen, proc
    from benchmark import run as harness
    from benchmark.kinds import retrain_job

    seed, app = (int(x) for x in word.split(":"))
    data = config["data"]
    run = proc.Run(harness.BENCH / ".work" / "probe_als_seed", platform)
    try:
        worker = retrain_job.Worker(run)
        try:
            u, i, r = datagen.make_movielens_like(
                data["nnz"], data["num_users"], data["num_items"], seed,
                data["structure_seed"])
            datagen.write_events(
                run.storage, retrain_job.APPS[app], u, i,
                retrain_job.ratings_of(app, r), data["num_users"],
                data["num_items"])
            variant = run.write_engine_json(
                f"{config['name']}-{app}", config, retrain_job.APPS[app])
            res = worker.retrain(variant)
            harness.say(f"through the store: retrain {res['seconds']:.2f} s, "
                        f"path {res['als_path']} ({res['als_mode']})")
        finally:
            worker.stop()
        model = run.persisted_model(res["instance"])
        return {k: (list(v) if k.endswith("vocab") else v) for k, v in model.items()}
    finally:
        run.close()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="340205840:1,340205840:0,340205841:1")
    ap.add_argument("--plans", default="hilo20,highest20,hilo19,hilo40",
                    help="for the first seed:app")
    ap.add_argument("--rest-plans", default="hilo20", help="for the others")
    ap.add_argument("--root", default=None, help="a rehearsal's data files")
    ap.add_argument("--through-store", type=int, default=0)
    ap.add_argument("--platform", default=None, help="cpu: a rehearsal")
    args = ap.parse_args(argv)

    import numpy as np

    from benchmark import datagen
    from benchmark import run as harness
    from benchmark.kinds import retrain_job
    from benchmark.references import als as ref

    manifest = harness.load_json(REPO / "BENCHMARK.json")
    _, config, params = harness.load_cell(
        manifest, "als-ml20m.retrain",
        Path(args.root) if args.root else harness.BENCH)
    platform = args.platform or harness.PLATFORM
    os.environ.setdefault("JAX_PLATFORMS", platform)
    data = config["data"]
    stored = None
    if args.through_store:
        # before this process touches JAX: the chip is the worker's
        stored = retrain_through_store(config, platform, args.seeds.split(",")[0])

    from predictionio_tpu.models.recommendation import engine
    from predictionio_tpu.ops import als
    from predictionio_tpu.utils.runtime import configure_compile_cache

    configure_compile_cache()
    algo = config["engine_json"]["algorithms"][0]["params"]
    base = engine.ALSAlgorithm(engine.ALSAlgorithmParams(
        rank=algo["rank"], num_iterations=algo["numIterations"],
        reg=algo["lambda"], seed=algo["seed"]))._als_params()
    reg = float(algo["lambda"])
    out_dir = REPO / "chiprun_out" / "probe"
    out_dir.mkdir(parents=True, exist_ok=True)
    def plans_of(words: str) -> list:
        out = []
        for word in words.split(","):
            precision, iters = re.fullmatch(r"([a-z]+)(\d+)", word).groups()
            out.append((word, {"pallas_precision": precision,
                               "num_iterations": int(iters)}))
        return out

    first_word = args.seeds.split(",")[0]
    users = None
    drawn = (None, None)
    with open(out_dir / "als_seed.jsonl", "a") as rows:
        for word in args.seeds.split(","):
            seed, app = (int(x) for x in word.split(":"))
            if seed != drawn[0]:
                drawn = (seed, datagen.make_movielens_like(
                    data["nnz"], data["num_users"], data["num_items"], seed,
                    data["structure_seed"]))
            u, i, r = drawn[1]
            r = retrain_job.ratings_of(app, r)
            if users is None:  # who rated what is the same for every seed
                if stored is None:
                    users, u_at = np.unique(u, return_inverse=True)
                    items, i_at = np.unique(i, return_inverse=True)
                else:  # positions as the store's scan gave them
                    users, u_at = positions(stored["user_vocab"], u)
                    items, i_at = positions(stored["item_vocab"], i)
                u_at, i_at = u_at.astype(np.int32), i_at.astype(np.int32)
                n_user = np.bincount(u_at, minlength=len(users))
                n_item = np.bincount(i_at, minlength=len(items))
                all_users = np.arange(len(users))
            fits = {}
            todo = plans_of(args.plans if word == first_word else args.rest_plans)
            if stored is not None and word == first_word:
                todo.insert(0, ("store", None))
            for label, change in todo:
                t0 = time.perf_counter()
                if change is None:
                    U = np.asarray(stored["user_factors"], np.float32)
                    V = np.asarray(stored["item_factors"], np.float32)
                else:
                    state = als.train_als(
                        u_at, i_at, r, num_users=len(users), num_items=len(items),
                        params=dataclasses.replace(base, **change))
                    U = np.asarray(state.user_factors)
                    V = np.asarray(state.item_factors)
                train_s = time.perf_counter() - t0
                fits[label] = (U, V)
                model = {
                    "user_factors": U, "item_factors": V,
                    "user_vocab": [datagen.user_name(x) for x in users],
                    "item_vocab": [datagen.item_name(x) for x in items],
                }
                ctx = harness.Ctx(None, config, params, seed, 0.0, False,
                                  time.perf_counter(), harness.say)
                compared = ref.check_retrain(ctx, model, "COMPLETED", u, i, r)
                gaps = ref.halfstep_gaps(U, V, u_at, i_at, r, all_users, reg)
                tail = gaps > 3 * np.median(gaps)
                by_count = {
                    f"n{lo}-{hi if hi < 10**9 else ''}": {
                        "rows": int(((n_user >= lo) & (n_user <= hi)).sum()),
                        **quantiles(gaps[(n_user >= lo) & (n_user <= hi)]),
                    } for lo, hi in COUNT_BINS
                }
                worst = np.argsort(-gaps)[:12]
                row = {
                    "seed": seed, "app": retrain_job.APPS[app], "plan": label,
                    "train_s": train_s,
                    "path": dict(als.LAST_PLAN_INFO).get("mode"),
                    "precision": dict(als.LAST_PLAN_INFO).get("precision"),
                    "compared": {c.name: [c.value, c.limit, c.ok] for c in compared},
                    "all_users": {"rows": len(gaps), **quantiles(gaps)},
                    "share_over_limit_max": float((gaps > 0.027).mean()),
                    # rows three times the median off: how many, how active
                    "tail": {
                        "rows": int(tail.sum()),
                        "n": quantiles(n_user[tail]) if tail.any() else None,
                    },
                    "by_count": by_count,
                    "worst": [
                        {"user": int(j), "n": int(n_user[j]), "gap": float(gaps[j]),
                         "norm": float(np.linalg.norm(U[j]))} for j in worst
                    ],
                }
                # against the first plan's fit: how far the tables lie apart
                first = fits[todo[0][0]]
                if label != todo[0][0]:
                    for name, a, b in (("user", U, first[0]), ("item", V, first[1])):
                        d = np.linalg.norm(a - b, axis=1) / np.maximum(
                            np.linalg.norm(b, axis=1), 1e-30)
                        row[f"{name}_rows_from_{todo[0][0]}"] = quantiles(d)
                    far = np.argsort(-d)[:8]  # d: the item rows
                    row["items_farthest"] = [
                        {"item": int(j), "n": int(n_item[j]), "moved": float(d[j]),
                         "norm": float(np.linalg.norm(V[j]))} for j in far
                    ]
                print(json.dumps(row), flush=True)
                rows.write(json.dumps(row) + "\n")
                rows.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
