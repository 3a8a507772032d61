#!/usr/bin/env python3
"""Find a serve cell's knee once, on the chip, in one set-up.

    chiprun --timeout 1500 -- python3 benchmark/sweep.py --workload <serve cell> \
        --seed 11 --start 100 [--step 1.25] [--seconds 10] [--stop 6000]

The cell's own set-up (thin store, ``pio train``, ``pio deploy``, warm-up), then
the cell's generator at rates rising by ``--step``, ``--seconds`` each.  A rate
HOLDS when >= 99 % of its requests answered 200, p95 from the due time is at
most the traffic file's ``limit_ms``, p95 of the last third is at most 1.5 x
p95 of the first third (no growing backlog) and the generator's own p95 lag is
under 1 ms.  The knee is the highest rate that holds; the sweep stops after
two rates in a row that do not.  The table goes to stdout and to
``chiprun_out/sweep/<cell>.json``; the builder writes it into PERF.md and the
cell's rate — a stated share of the knee, two significant digits, rounded
down — into ``cells/<cell>.json`` with the share and why.  Not part of a
benchmark run.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def holds(s: dict, limit_ms: float) -> dict:
    checks = {
        "answered_99pct": s["ok"] >= 0.99 * s["n"],
        "p95_within_limit": s["p95_ms"] <= limit_ms,
        "no_growing_backlog": s["p95_last_third_ms"] <= 1.5 * s["p95_first_third_ms"],
        "generator_on_time": s["lag_p95_ms"] < 1.0,
    }
    return {**checks, "holds": all(checks.values())}


async def sweep(ctx, base, host, port, users, rates, seconds) -> list[dict]:
    from benchmark import loadgen, promjson
    from benchmark.kinds import serve_open_loop as kind

    tr = ctx.params
    num = int(tr["num"])
    await kind.warm_sequential(base, users, int(tr["warmup"]["sequential"]), num)
    gen = loadgen.OpenLoop(host, port, tr["inflight_cap"], tr["timeout_s"])
    await gen.preopen(int(tr["preopen"]))
    w_at, w_ranks = loadgen.make_schedule(
        rates[0], 3.0, len(users), tr["zipf_s"], ctx.seed + 1
    )
    await gen.run(w_at, kind.payloads_for(users, w_ranks, num, host, port))
    rows, misses = [], 0
    for n, rate in enumerate(rates):
        at, ranks = loadgen.make_schedule(
            rate, seconds, len(users), tr["zipf_s"], ctx.seed + 10 + n
        )
        before = await asyncio.to_thread(kind.scrape, base)
        out = await gen.run(at, kind.payloads_for(users, ranks, num, host, port))
        after = await asyncio.to_thread(kind.scrape, base)
        s = kind.summarize(out, ctx.say, f"rate {rate:g}", float(tr["limit_ms"]))
        def delta(key):
            return (promjson.series_total(after, "pio_microbatch_batch_size", key)
                    - promjson.series_total(before, "pio_microbatch_batch_size", key))

        n_waves = delta("count")
        row = {
            "rate_qps": rate, **{k: s[k] for k in (
                "n", "ok", "p50_ms", "p95_ms", "p99_ms", "p95_first_third_ms",
                "p95_last_third_ms", "lag_p95_ms", "qps_ok")},
            "wave_size_mean": delta("sum") / n_waves if n_waves else None,
            **holds(s, float(tr["limit_ms"])),
        }
        rows.append(row)
        misses = 0 if row["holds"] else misses + 1
        if misses >= 2:
            break
        await asyncio.sleep(1.0)
    await gen.close()
    return rows


def run_sweep(manifest, workload, seed, rates, seconds, platform, work, root=BENCH) -> dict:
    """Set-up once, then every rate; the report that goes into PERF.md."""
    from benchmark import proc, reference
    from benchmark import run as harness
    from benchmark.kinds import serve_open_loop as kind

    cell, config, params = harness.load_cell(manifest, workload, root)
    run = proc.Run(work, platform)
    ctx = harness.Ctx(run, config, params, seed, seconds, False,
                      time.perf_counter(), harness.say)
    try:
        instance, variant, _ = kind.setup_model(ctx)
        ref = reference.load(config["reference"]["kind"]).served(
            run.persisted_model(instance))
        users = kind.users_by_rank(ref, seed)
        with proc.deployed(run, "deploy", variant, instance) as (base, _):
            host, port = "127.0.0.1", int(base.rsplit(":", 1)[1])
            rows = asyncio.run(
                sweep(ctx, base, host, port, users, rates, seconds))
    finally:
        run.close()
    held = [row["rate_qps"] for row in rows if row["holds"]]
    return {"workload": workload, "seed": seed, "seconds": seconds,
            "knee_qps": max(held) if held else None, "rows": rows}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--step", type=float, default=1.25)
    ap.add_argument("--stop", type=float, default=6000.0)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from benchmark import run as harness

    rates, r = [], args.start
    while r <= args.stop:
        rates.append(float(int(r)))
        r *= args.step
    report = run_sweep(
        harness.load_json(REPO / "BENCHMARK.json"), args.workload, args.seed,
        rates, args.seconds, harness.PLATFORM,
        BENCH / ".work" / f"sweep-{args.workload}",
    )
    out = REPO / "chiprun_out" / "sweep"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{args.workload}.json").write_text(json.dumps(report, indent=1))
    cols = ("rate_qps", "ok", "n", "p50_ms", "p95_ms", "p99_ms",
            "p95_first_third_ms", "p95_last_third_ms", "lag_p95_ms", "qps_ok",
            "wave_size_mean", "holds")
    print(" | ".join(cols))
    for row in report["rows"]:
        print(" | ".join(
            f"{row[c]:.3f}" if isinstance(row[c], float) else str(row[c])
            for c in cols))
    print(json.dumps({"knee_qps": report["knee_qps"]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
