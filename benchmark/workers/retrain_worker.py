#!/usr/bin/env python3
"""The one process that holds the chip in a retrain cell.

Reads one JSON command per line on stdin, answers one JSON line on stdout:

    {"cmd": "retrain", "engine_json": PATH, "trace_dir": PATH | null}
    {"cmd": "exit"}

A retrain is the CLI's own train path: ``build_parser().parse_args(["train",
"--engine-json", PATH])`` and the verb function it names (``do_train``), i.e.
``pio train`` minus interpreter start, imports and TPU init, which this
process pays once before it says ``ready``.  From the program it takes its
spans and counters only: the workflow's stage seconds (the ``stages`` extra of
its log record), ``ops.als.LAST_PLAN_INFO``, the compile listener's counts and
the device's memory statistics.  With ``trace_dir`` the retrain runs inside
``jax.profiler.start_trace/stop_trace`` (host tracers at their lowest level:
the trace is read for the device planes).

Device memory: this runtime's allocator counts arrays under ``bytes_in_use``
and what a running program has reserved for its temporaries under
``bytes_reserved``, and keeps a peak of each, not of their sum (read on the
v5e, PERF.md section 6: a program with a 2 GiB temporary over 2 GiB of
arguments left ``peak_bytes_in_use`` at 2.03 GiB and ``peak_bytes_reserved`` at
2.00 GiB).  So ``_MemoryWatch`` reads both every 50 ms while a retrain runs and
keeps the largest sum seen at ONE instant on the fullest device; the two
lifetime peaks are reported beside it.
"""

from __future__ import annotations

import contextlib
import io
import json
import logging
import sys
import threading
import time


class _MemoryWatch(threading.Thread):
    """Largest ``bytes_in_use + bytes_reserved`` seen at one instant on any
    local device, sampled every ``period`` seconds until ``stop()``."""

    def __init__(self, period: float = 0.05):
        super().__init__(daemon=True)
        import jax

        self.devices = jax.local_devices()
        self.period = period
        self.held = 0
        self.samples = 0
        self._done = threading.Event()

    def sample(self) -> None:
        for d in self.devices:
            s = d.memory_stats() or {}
            self.held = max(
                self.held, s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0)
            )
        self.samples += 1

    def run(self) -> None:
        while not self._done.wait(self.period):
            self.sample()

    def stop(self) -> int:
        self._done.set()
        self.join()
        self.sample()
        return self.held


class _Stages(logging.Handler):
    """Keeps the last ``stages`` / ``als_path`` extras the program logged."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.last: dict = {}

    def emit(self, record: logging.LogRecord) -> None:
        for key in ("stages", "als_path", "mode"):
            if hasattr(record, key):
                self.last[key] = getattr(record, key)


def _compile_events() -> int:
    """Backend compilations (or cache retrievals) this process has made."""
    from predictionio_tpu.obs.metrics import REGISTRY

    fam = REGISTRY.get("pio_jax_compile_total")
    if fam is None:
        return 0
    return int(
        sum(
            child.value
            for labels, child in fam.series()
            if labels[0].endswith("backend_compile_duration")
        )
    )


def _memory(watch: _MemoryWatch) -> dict:
    held = watch.held
    stats = [d.memory_stats() or {} for d in watch.devices]

    def peak(key: str) -> int:
        return max((s.get(key, 0) for s in stats), default=0)

    return {
        # never more than the device held at one instant: the sampled sum,
        # or a lifetime peak of one of its two parts where that is larger
        "peak_bytes_held": max(
            held, peak("peak_bytes_in_use"), peak("peak_bytes_reserved")
        ),
        "sampled_bytes_held": held,
        "samples": watch.samples,
        "peak_bytes_in_use": peak("peak_bytes_in_use"),
        "peak_bytes_reserved": peak("peak_bytes_reserved"),
        "bytes_limit": stats[0].get("bytes_limit") if stats else None,
    }


def _retrain(cmd: dict, parser, seen: _Stages) -> dict:
    import jax

    from benchmark.proc import trained_instances
    from predictionio_tpu.obs.tracing import jax_compile_stats
    from predictionio_tpu.ops import als

    args = parser.parse_args(["train", "--engine-json", cmd["engine_json"]])
    seen.last.clear()
    als.LAST_PLAN_INFO.pop("stage_s", None)
    compiles0, compile_s0 = _compile_events(), jax_compile_stats()["compile_s"]
    trace_dir = cmd.get("trace_dir")
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    stdout = io.StringIO()
    watch = _MemoryWatch()
    watch.start()
    t0 = time.perf_counter()
    try:
        # the verb prints its instance id on stdout, which here is the
        # reply channel
        with contextlib.redirect_stdout(stdout):
            rc = args.fn(args)
    finally:
        wall_s = time.perf_counter() - t0
        watch.stop()
        if trace_dir:
            jax.profiler.stop_trace()
    ids = trained_instances(stdout.getvalue())
    return {
        "ok": rc == 0 and bool(ids),
        "instance": ids[-1] if ids else None,
        "wall_s": wall_s,
        "stages": seen.last.get("stages"),
        "als_path": seen.last.get("als_path"),
        "als_mode": seen.last.get("mode"),
        "plan_info": dict(als.LAST_PLAN_INFO),
        "compiles": _compile_events() - compiles0,
        "compile_s": jax_compile_stats()["compile_s"] - compile_s0,
        "memory": _memory(watch),
    }


def main() -> int:
    reply = sys.stdout
    # nothing but replies on the reply channel
    sys.stdout = sys.stderr
    from predictionio_tpu.obs.logging import configure_logging
    from predictionio_tpu.tools.cli import _device_startup, build_parser
    from predictionio_tpu.utils.runtime import describe_devices

    configure_logging()
    seen = _Stages()
    logging.getLogger("predictionio_tpu").addHandler(seen)
    parser = build_parser()
    # cache directory, compile listener, and the backend itself: a worker
    # that cannot get its platform dies here
    _device_startup("train")
    dev = describe_devices()
    print(json.dumps({"ready": True, "device": dev}), file=reply, flush=True)
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd.get("cmd") == "exit":
            break
        try:
            res = _retrain(cmd, parser, seen)
        except Exception as e:  # the parent decides what a failure means
            import traceback

            traceback.print_exc()
            res = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        print(json.dumps(res), file=reply, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
