"""Traffic kind ``retrain_job``: whole retrains, back to back, in one worker
process that holds the chip.

Set-up: the worker started (interpreter, imports, TPU init: first, so that a
run without the chip ends in seconds), the configuration's ratings from the
seed, bulk-written to a throwaway parquet event store under two apps — the
same (user, item) events, the second with the rating scale reversed — and one
whole warm-up retrain.  Two apps because the
program keeps the staged device arrays of the LAST data set it trained on
(``ops/als._STAGE_CACHE``): an operator's next retrain sees new events, so the
window alternates the apps and every retrain stages its data like a first one,
with every program already compiled (the kernel shapes follow the indices,
which the apps share).

Window: retrains start while less than ``seconds`` have passed and the one in
flight always finishes.  ``retrain_s`` is the window's length on the harness's
clock over the retrains completed in it (commands to the worker back to back:
events in the store -> COMPLETED instance with persisted models), so a
retrain that stalls is in it however many fit.

Afterwards the last retrain of EACH app (with two retrains a window: every
one) is held to the plain reference the cell names — ``cells/<cell>.json``
``reference``, else the configuration's ``reference.kind`` — by that module's
``check_retrain``.
"""

from __future__ import annotations

import dataclasses
import json
import subprocess
import sys
import threading
import time

import numpy as np

from benchmark import datagen, proc, reference
from benchmark.proc import BENCH, require

APPS = ("bench-a", "bench-b")
#: the span that holds a retrain's other spans (``core/workflow.run_train``)
ROOT_SPAN = "workflow.run_train"


def ratings_of(app_index: int, rating: np.ndarray) -> np.ndarray:
    """App 0 holds the generated ratings; app 1 the scale reversed (0.5..5
    in half stars stays 0.5..5)."""
    return rating if app_index == 0 else (5.5 - rating).astype(np.float32)


class Worker:
    """The child that holds the chip; one JSON line each way per command."""

    def __init__(self, run: proc.Run, start_timeout: float = 300.0):
        self.proc = run.spawn(
            "worker",
            [sys.executable, str(BENCH / "workers" / "retrain_worker.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        )
        self.out = proc.ChildOutput(run, "worker")
        self.device = self._reply(start_timeout)["device"]

    def _reply(self, timeout: float) -> dict:
        box: list[str] = []
        reader = threading.Thread(
            target=lambda: box.append(self.proc.stdout.readline()), daemon=True
        )
        reader.start()
        reader.join(timeout)
        require(box and box[0], f"worker gave no reply:\n{self.out.tail()}")
        return json.loads(box[0])

    def retrain(self, engine_json, trace_dir=None, timeout: float = 1100.0) -> dict:
        self.proc.stdin.write(json.dumps({
            "cmd": "retrain", "engine_json": str(engine_json),
            "trace_dir": trace_dir,
        }) + "\n")
        self.proc.stdin.flush()
        t0 = time.perf_counter()
        res = self._reply(timeout)
        res["seconds"] = time.perf_counter() - t0
        require(res.get("ok"), f"retrain failed: {res}\n{self.out.tail()}")
        return res

    def stop(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.write('{"cmd": "exit"}\n')
                self.proc.stdin.flush()
                self.proc.wait(timeout=60)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()


def run(ctx) -> dict:
    cfg, tr = ctx.config, ctx.params
    data = cfg["data"]
    nu, ni = data["num_users"], data["num_items"]
    # the worker first: a run that finds no chip ends here, in seconds
    worker = Worker(ctx.run)
    try:
        dev = worker.device
        t0 = time.perf_counter()
        user_idx, item_idx, rating = datagen.make_movielens_like(
            data["nnz"], nu, ni, ctx.seed, data["structure_seed"]
        )
        gen_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        datagen.write_events(
            ctx.run.storage, APPS[0], user_idx, item_idx, rating, nu, ni)
        write_s = time.perf_counter() - t0
        variants = [
            ctx.run.write_engine_json(f"{cfg['name']}-{n}", cfg, app)
            for n, app in enumerate(APPS)
        ]
        # the second app is written while the warm-up retrain runs on the
        # first: both are set-up, and the write is host work of its own
        t0 = time.perf_counter()
        writer = threading.Thread(
            target=datagen.write_events,
            args=(ctx.run.storage, APPS[1], user_idx, item_idx,
                  ratings_of(1, rating), nu, ni),
        )
        writer.start()
        warm = worker.retrain(variants[0])
        writer.join()
        ctx.say(
            f"setup: generate {gen_s:.2f} s, write {write_s:.2f} s, warm-up "
            f"retrain + second write {time.perf_counter() - t0:.2f} s "
            f"(retrain {warm['seconds']:.2f} s, compile {warm['compile_s']:.2f} s, "
            f"{warm['compiles']} compilations)"
        )
        # 2 x 20 M events written during set-up reach the disk now, not as a
        # write-back burst under the window's scans
        ctx.run.flush_to_disk()
        setup_s = time.perf_counter() - ctx.t_start
        ctx.say(f"setup_s {setup_s:.3f}; window opens")
        t_open = time.perf_counter()
        done = []
        n = 0
        while time.perf_counter() - t_open < ctx.seconds or not done:
            n += 1
            app = n % 2  # the warm-up used app 0
            trace_dir = None
            if ctx.trace and not done:
                trace_dir = str(ctx.run.work / "trace")
            res = worker.retrain(variants[app], trace_dir)
            res["app"] = app
            done.append(res)
            ctx.say(
                f"retrain {n}: {res['seconds']:.3f} s, path {res['als_path']} "
                f"({res['als_mode']}), {res['compiles']} compilations, stages "
                f"{json.dumps(res['stages'])}, plan stage_s "
                f"{res['plan_info'].get('stage_s')}"
            )
        window_s = time.perf_counter() - t_open
        ctx.say(f"window: {len(done)} retrains in {window_s:.3f} s; device "
                f"memory {json.dumps(done[-1]['memory'])}")
    finally:
        worker.stop()

    ref = reference.load(tr.get("reference") or cfg["reference"]["kind"])
    compared = []
    for app in sorted({r["app"] for r in done}):
        last = next(r for r in reversed(done) if r["app"] == app)
        instance = ctx.run.storage.engine_instances().get(last["instance"])
        checks = ref.check_retrain(
            ctx, ctx.run.persisted_model(last["instance"]),
            instance.status if instance else "MISSING",
            user_idx, item_idx, ratings_of(app, rating),
        )
        compared += [
            dataclasses.replace(c, name=f"{c.name}[{APPS[app]}]") for c in checks
        ]
    compiles = sum(r["compiles"] for r in done)
    compared.append(reference.Compared(
        "compilations_inside_window", float(compiles), 0.0))
    expected = cfg.get("train", {}).get("als_path")
    if expected:
        compared.append(reference.Compared(
            f"retrains_on_{expected}",
            float(sum(r["als_path"] == expected for r in done)),
            float(len(done)), "min"))
    traced = done[0] if ctx.trace else None
    return {
        "end_to_end": {
            "retrain_s": window_s / len(done),
        },
        "setup_s": setup_s,
        "attempted": len(done),
        "failed": 0,
        "compared": compared,
        "device": {
            "platform": dev["platform"],
            "kind": dev["device_kind"],
            "count": dev["device_count"],
            # arrays in use + the running program's reserved temporaries,
            # at one instant (workers/retrain_worker._MemoryWatch)
            "memory_peak_bytes": max(
                r["memory"]["peak_bytes_held"] for r in done
            ),
        },
        "trace_dir": str(ctx.run.work / "trace") if traced else None,
        # the program's spans, by which the reducer shares out the idle time:
        # the names of ``stages`` that are seconds, and the root
        "trace_spans": {
            "root": ROOT_SPAN,
            "spans": [ROOT_SPAN] + sorted(
                name for name, secs in (traced["stages"] or {}).items()
                if isinstance(secs, (int, float))),
        } if traced else None,
        "evidence": {
            # the traced retrain where there is one, else the last
            "retrain": traced or done[-1],
            "retrains": done,
        },
    }
