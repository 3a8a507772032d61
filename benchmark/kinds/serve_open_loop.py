"""Traffic kind ``serve_open_loop``: ``pio deploy`` under open-loop queries.

Set-up: a thin event store from the seed (every user and item named once, so
the tables have the configuration's shape), ``pio train`` as a child, ``pio
deploy`` as a child with an access key (arms ``/debug/profile``), the serve
path warmed at the cell's own rate, connections opened.  Window: the schedule
of ``loadgen.make_schedule`` at the cell's fixed ``rate_qps`` for ``seconds``;
it closes when the last request due inside it is answered.  Afterwards: the
server is stopped and a seeded sample of the window's answers is compared with
the numpy reference over the persisted tables.

End to end (host clock, from the DUE time): ``serve_p50_ms``, ``serve_qps``
(answers with status 200 per second of the window, drain included).  The
manifest says which a cell reports.  The tail (p95, p99, the share of requests
answered within the mix's ``limit_ms``) is in the generator's record, which
per-layer readers read.

Which engine path answered is read from the server's own provenance ring: its
newest records every ``explained.every_s`` seconds while the window runs (one
light GET beside hundreds of queries a second) and once more when it has
closed, so a path that answers only part of the window shows.
"""

from __future__ import annotations

import asyncio
import json
import time

import numpy as np

from benchmark import datagen, loadgen, proc, promjson, reference
from benchmark.proc import require

APP = "bench"


def scrape(base: str) -> dict:
    status, fams = proc.http(
        "GET", f"{base}/metrics.json?accessKey={proc.ACCESS_KEY}", timeout=30
    )
    require(status == 200, f"/metrics.json answered {status}")
    return fams


def note_paths(base: str, limit: int, seen: dict[str, str]) -> None:
    """The engine path of the server's newest ``limit`` answers, by request."""
    status, body = proc.http(
        "GET", f"{base}/explain.json?limit={limit}&accessKey={proc.ACCESS_KEY}"
    )
    require(status == 200, f"/explain.json answered {status}: {body}")
    for rec in body["records"]:
        if rec.get("path") == "/queries.json":
            seen[rec["request_id"]] = rec.get("engine_path") or "none"


async def watch_paths(base: str, every_s: float, limit: int, seen: dict) -> None:
    while True:
        await asyncio.sleep(every_s)
        await asyncio.to_thread(note_paths, base, limit, seen)


def summarize(out: loadgen.Outcome, say, label: str, limit_ms: float) -> dict:
    lat = out.latency_ms()
    lag = out.lag_ms()
    n = len(lat)
    third = max(n // 3, 1)
    res = {
        "n": n,
        "ok": int(out.ok.sum()),
        "p50_ms": loadgen.percentile(lat, 50),
        "p95_ms": loadgen.percentile(lat, 95),
        "p99_ms": loadgen.percentile(lat, 99),
        "max_ms": float(lat.max()),
        "within_limit_pct": float(100.0 * (lat <= limit_ms).mean()),
        "p95_first_third_ms": loadgen.percentile(lat[:third], 95),
        "p95_last_third_ms": loadgen.percentile(lat[-third:], 95),
        "lag_p50_ms": loadgen.percentile(lag, 50),
        "lag_p95_ms": loadgen.percentile(lag, 95),
        "lag_max_ms": float(lag.max()),
        "wall_s": out.wall_s,
        "qps_ok": float(out.ok.sum() / out.wall_s),
        "status": {
            str(k): int(v)
            for k, v in zip(*np.unique(out.status, return_counts=True))
        },
    }
    say(f"{label}: {json.dumps(res)}")
    return res


def payloads_for(users: np.ndarray, ranks: np.ndarray, num: int, host: str, port: int):
    bodies = {}
    out = []
    for r in ranks:
        p = bodies.get(r)
        if p is None:
            body = json.dumps({"user": users[r], "num": num}).encode()
            p = bodies[r] = loadgen.http_post_bytes(
                host, port, "/queries.json", body
            )
        out.append(p)
    return out


def users_by_rank(ref, seed: int) -> np.ndarray:
    """The users the model knows, Zipf rank -> user through a permutation
    drawn from the seed."""
    users = np.array(list(ref.user_index), object)
    return users[np.random.default_rng([seed, 3]).permutation(len(users))]


async def warm_sequential(base: str, users, n: int, num: int) -> None:
    """The first answers compile the wave program and fill the host caches."""
    for u in users[:n]:
        status, body = await asyncio.to_thread(
            proc.http, "POST", base + "/queries.json", {"user": u, "num": num}
        )
        require(status == 200, f"warm-up query answered {status}: {body}")


def setup_model(ctx) -> tuple[str, object, dict]:
    """Thin store -> ``pio train`` child -> (instance id, engine.json path,
    train report)."""
    run, cfg, tr = ctx.run, ctx.config, ctx.params
    data = cfg["data"]
    t0 = time.perf_counter()
    u, i, r = datagen.thin_ratings(
        tr["store"]["nnz"], data["num_users"], data["num_items"], ctx.seed,
        data["structure_seed"],
    )
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    datagen.write_events(
        run.storage, APP, u, i, r, data["num_users"], data["num_items"]
    )
    write_s = time.perf_counter() - t0
    variant = run.write_engine_json(cfg["name"], cfg, APP)
    t0 = time.perf_counter()
    instance, report = proc.train_child(run, "train", variant, timeout=900)
    ctx.say(
        f"setup: generate {gen_s:.2f} s, write {write_s:.2f} s, pio train "
        f"{time.perf_counter() - t0:.2f} s (compile {report['compile_s']} s, "
        f"stages {json.dumps(report['stages'])})"
    )
    return instance, variant, report


def run(ctx) -> dict:
    cfg, tr = ctx.config, ctx.params
    rate = float(tr["rate_qps"])
    instance, variant, train_report = setup_model(ctx)
    model = ctx.run.persisted_model(instance)
    ref = reference.load(cfg["reference"]["kind"]).served(model)
    require(ref.finite, "persisted tables are not finite")
    shape_ok = (
        len(ref.user_index) == cfg["data"]["num_users"]
        and len(ref.item_index) == cfg["data"]["num_items"]
    )
    users = users_by_rank(ref, ctx.seed)
    num = int(tr["num"])

    t0 = time.perf_counter()
    with proc.deployed(ctx.run, "deploy", variant, instance) as (base, child):
        ctx.say(f"setup: pio deploy serving after {time.perf_counter() - t0:.2f} s")
        started = proc.check_startup(ctx.run, child, "deploy")
        host, port = "127.0.0.1", int(base.rsplit(":", 1)[1])
        result = asyncio.run(
            _drive(ctx, base, host, port, users, rate, num)
        )
        fams_after = result.pop("fams_after")
    out: loadgen.Outcome = result.pop("outcome")
    ranks = result.pop("ranks")

    # -- correct -------------------------------------------------------------
    compared = []
    answered = np.flatnonzero(out.ok)
    bad_shape = 0
    parsed: dict[int, list] = {}
    for j in answered:
        try:
            items = json.loads(out.bodies[j])["itemScores"]
            scores = [float(e["score"]) for e in items]
            if len(items) != num or not np.isfinite(scores).all():
                bad_shape += 1
            parsed[j] = items
        except (ValueError, KeyError, TypeError):
            bad_shape += 1
    compared.append(reference.Compared(
        "answers_without_num_finite_items", float(bad_shape), 0.0))
    k = min(int(tr["checked_answers"]), len(answered))
    pick = np.random.default_rng([ctx.seed, 4]).choice(
        answered, size=k, replace=False
    ) if k else []
    sample = [(users[ranks[j]], parsed.get(j, [])) for j in pick]
    tol = float(cfg["reference"]["score_tolerance"])
    compared += reference.compare_topk(sample, ref, num, tol)
    compared.append(reference.Compared("answers_compared", float(k), 1.0, "min"))
    paths: dict[str, int] = {}
    for path in result.pop("explained").values():
        paths[path] = paths.get(path, 0) + 1
    ctx.say(f"answers explained, by engine path: {json.dumps(paths)}")
    expected = cfg["serve"]["engine_path"]
    others = sum(v for p, v in paths.items() if p != expected)
    compared.append(reference.Compared(
        f"answers_explained_by_{expected}", float(paths.get(expected, 0)), 1.0, "min"))
    compared.append(reference.Compared(
        "answers_explained_by_another_path", float(others), 0.0))
    compared.append(reference.Compared(
        "table_shape_as_configured", float(shape_ok), 1.0, "min"))
    compared.append(reference.Compared(
        "compilations_inside_window", float(result["compiles_in_window"]), 0.0))

    s = result["summary"]
    return {
        "end_to_end": {
            "serve_p50_ms": s["p50_ms"],
            "serve_qps": s["qps_ok"],
        },
        "setup_s": result["setup_s"],
        "attempted": s["n"],
        "failed": s["n"] - s["ok"],
        "compared": compared,
        "device": {
            "platform": started["platform"],
            "kind": started["device_kind"],
            "count": started["device_count"],
            "memory_peak_bytes": max(
                int(train_report["peak_bytes_in_use"] or 0),
                int(promjson.series_max(fams_after, "pio_jax_device_memory_bytes")),
            ),
        },
        "trace_dir": result.get("trace_dir"),
        "evidence": {
            "generator": s,
            "metrics_before": result["fams_before"],
            "metrics_after": fams_after,
            "trace_metrics_before": result.get("trace_fams_before"),
            "trace_metrics_after": result.get("trace_fams_after"),
            "engine_paths": paths,
            "instance": instance,
            "checked_users": [u for u, _ in sample],
        },
    }


async def _traced_span(ctx, gen, base, host, port, users, rate, num, res) -> None:
    """The same traffic for a few seconds more with the profiler capturing in
    the served process (``POST /debug/profile``), AFTER the window: the
    capture's Python tracer slows the server several-fold, so the window's
    counters and latencies are taken without it, and only what the device did
    (busy time, busy time a wave) is read from this span."""
    tr = ctx.params
    trace_dir = str(ctx.run.work / "trace")
    t_len = float(tr["trace"]["seconds"])
    at, ranks = loadgen.make_schedule(
        rate, t_len, len(users), tr["zipf_s"], ctx.seed + 2
    )
    payloads = payloads_for(users, ranks, num, host, port)
    res["trace_fams_before"] = await asyncio.to_thread(scrape, base)
    status, body = await asyncio.to_thread(
        proc.http, "POST",
        f"{base}/debug/profile?seconds={t_len}&dir={trace_dir}"
        f"&accessKey={proc.ACCESS_KEY}",
    )
    require(status == 202, f"/debug/profile answered {status}: {body}")
    traced = await gen.run(at, payloads)
    summarize(traced, ctx.say, "traced span (profiler on, judged by nothing)",
              float(tr["limit_ms"]))
    for _ in range(1200):
        _, st = await asyncio.to_thread(
            proc.http, "GET", f"{base}/debug/profile?accessKey={proc.ACCESS_KEY}"
        )
        if not st.get("running"):
            break
        await asyncio.sleep(0.1)
    require(
        not (st.get("last") or {}).get("error"),
        f"profiler capture failed: {st.get('last')}",
    )
    res["trace_fams_after"] = await asyncio.to_thread(scrape, base)
    res["trace_dir"] = trace_dir


async def _drive(ctx, base, host, port, users, rate, num) -> dict:
    """Warm-up, the window, and the scrapes around it (one event loop)."""
    tr = ctx.params
    gen = loadgen.OpenLoop(host, port, tr["inflight_cap"], tr["timeout_s"])
    await warm_sequential(base, users, int(tr["warmup"]["sequential"]), num)
    await gen.preopen(int(tr["preopen"]))
    w_at, w_ranks = loadgen.make_schedule(
        rate, float(tr["warmup"]["seconds"]), len(users), tr["zipf_s"],
        ctx.seed + 1,
    )
    warm = await gen.run(w_at, payloads_for(users, w_ranks, num, host, port))
    summarize(warm, ctx.say, "warm-up", float(tr["limit_ms"]))
    at, ranks = loadgen.make_schedule(
        rate, ctx.seconds, len(users), tr["zipf_s"], ctx.seed
    )
    payloads = payloads_for(users, ranks, num, host, port)
    fams_before = await asyncio.to_thread(scrape, base)
    res: dict = {"fams_before": fams_before, "ranks": ranks}

    # the store and the models written during set-up reach the disk now, not
    # as a write-back burst under the window
    await asyncio.to_thread(ctx.run.flush_to_disk)
    res["setup_s"] = time.perf_counter() - ctx.t_start
    ctx.say(f"setup_s {res['setup_s']:.3f}; window opens: {len(at)} requests "
            f"at {rate:g} qps over {ctx.seconds:g} s")
    explained: dict[str, str] = {}
    ex = tr["explained"]
    watcher = asyncio.create_task(
        watch_paths(base, float(ex["every_s"]), int(ex["newest"]), explained))
    try:
        out = await gen.run(at, payloads)
    finally:
        watcher.cancel()
    res["fams_after"] = await asyncio.to_thread(scrape, base)
    res["compiles_in_window"] = (
        promjson.compile_events(res["fams_after"])
        - promjson.compile_events(fams_before)
    )
    res["outcome"] = out
    res["summary"] = summarize(out, ctx.say, "window", float(tr["limit_ms"]))
    await asyncio.to_thread(note_paths, base, int(ex["newest"]), explained)
    if ctx.trace:
        await _traced_span(ctx, gen, base, host, port, users, rate, num, res)
        await asyncio.to_thread(note_paths, base, int(ex["newest"]), explained)
    await gen.close()
    res["explained"] = explained
    return res
