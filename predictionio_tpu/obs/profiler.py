"""On-demand JAX profiling + runtime gauges for a live server.

``POST /debug/profile?seconds=N`` starts a ``jax.profiler`` trace capture
(device planes + the program's spans; ``&python=1`` adds Python frames, at
the server's expense) on a running server without restarting it — the "grab
a profile of the slow fleet member right now" workflow (DrJAX's profiling
emphasis; the Spark job UI role in the reference).  ``start_trace`` runs on the request thread (it
only arms collection, and a failure must surface as the HTTP status); the
capture *wait* and ``stop_trace`` run on a dedicated background thread so
the request thread answers immediately — a stalled profiler must never hold
an event-loop executor slot for N seconds.

:func:`sample_runtime_gauges` refreshes compile-cache / device-memory /
live-buffer gauges; the metrics exposition route calls it on each scrape so
the gauges are current without a sampler thread.
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
import weakref
from typing import Any

from predictionio_tpu.obs import device as device_obs
from predictionio_tpu.obs.metrics import REGISTRY, MetricsRegistry
from predictionio_tpu.utils.runtime import backend_initialized

#: upper bound on one capture; profiles are for debugging, not surveillance
MAX_CAPTURE_SECONDS = 300.0


class ProfilerUnsupported(RuntimeError):
    """jax.profiler is unavailable or refused to start on this backend."""


class ProfilerBusy(RuntimeError):
    """A capture is already in flight (jax allows one trace at a time)."""


def _start_trace(out_dir: str, python_tracer: bool = False) -> None:
    """Indirection point (tests stub these; jax imports stay lazy).

    Host tracer at level 1: the program's spans (``obs/tracing.trace``
    opens a ``TraceAnnotation`` per span while a session is open) are in
    the capture beside the device planes.  The Python tracer is OFF unless
    asked for: it hooks every call of every thread, and on a live server
    it cut 480 answers/s to 155-240 and timed requests out (PERF.md
    section 6) — the capture then measures its own cost."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 1 if python_tracer else 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=opts)


def _stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()


class ProfilerController:
    """One capture at a time, finished off-thread.

    ``start`` arms the trace and hands the wait+stop to a daemon thread;
    ``status`` reports the in-flight capture or the last finished one.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._running: dict[str, Any] | None = None
        self._last: dict[str, Any] | None = None
        self._wakeup = threading.Event()

    def start(
        self,
        seconds: float,
        out_dir: str | None = None,
        python_tracer: bool = False,
    ) -> dict[str, Any]:
        if not 0 < seconds <= MAX_CAPTURE_SECONDS:
            raise ValueError(
                f"seconds must be in (0, {MAX_CAPTURE_SECONDS:g}]"
            )
        out_dir = out_dir or os.path.join(
            tempfile.gettempdir(), "pio-profile"
        )
        with self._lock:
            if self._running is not None:
                raise ProfilerBusy(
                    f"capture already running into {self._running['dir']}"
                )
            self._running = {
                "dir": out_dir,
                "seconds": seconds,
                "python_tracer": python_tracer,
                "started": time.time(),
            }
        try:
            _start_trace(out_dir, python_tracer)
        except Exception as e:
            with self._lock:
                self._running = None
            raise ProfilerUnsupported(
                f"jax profiler unavailable on this backend: {e}"
            ) from e
        self._wakeup.clear()
        threading.Thread(
            target=self._finish,
            args=(seconds, out_dir),
            name="pio-profiler",
            daemon=True,
        ).start()
        return {
            "profiling": True, "seconds": seconds, "dir": out_dir,
            "python_tracer": python_tracer,
        }

    def _finish(self, seconds: float, out_dir: str) -> None:
        # paced by an Event, not a sleep poll: interruptible and lint-clean
        self._wakeup.wait(seconds)
        error: str | None = None
        try:
            _stop_trace()
        except Exception as e:
            error = f"{type(e).__name__}: {e}"
        with self._lock:
            done = self._running or {}
            self._running = None
            self._last = {
                "dir": out_dir,
                "seconds": seconds,
                "started": done.get("started"),
                "finished": time.time(),
                "error": error,
            }

    def status(self) -> dict[str, Any]:
        with self._lock:
            return {
                "running": self._running is not None,
                "current": dict(self._running) if self._running else None,
                "last": dict(self._last) if self._last else None,
            }


#: the process-wide controller — jax tracing is global, so one per process
PROFILER = ProfilerController()

#: last-seen pjit-cache size per registry, so a scrape can turn the size
#: gauge into a growth COUNTER (cache growth == fresh XLA compiles — the
#: scrape-level recompile signal that needs no call-site attribution)
_cache_size_seen: "weakref.WeakKeyDictionary[MetricsRegistry, int]" = (
    weakref.WeakKeyDictionary()
)

#: the per-device ``memory_stats`` walk crosses into the backend per device
#: — the one probe here whose cost scales with topology — so scrapes
#: arriving within this window reuse the cached gauge values instead of
#: re-walking (two Prometheus scrapers a second apart must not double the
#: backend chatter)
MEMSTATS_MIN_INTERVAL_S = 1.0

#: monotonic time of the last memory_stats walk, per registry
_memstats_last: "weakref.WeakKeyDictionary[MetricsRegistry, float]" = (
    weakref.WeakKeyDictionary()
)


def sample_runtime_gauges(registry: MetricsRegistry | None = None) -> bool:
    """Refresh JAX runtime gauges: live device buffers (count + bytes),
    per-device memory stats where the backend reports them (TPU does, CPU
    returns None), jit/pjit executable-cache entries PLUS their growth
    since the last scrape (``pio_jax_compile_cache_growth_total`` — cache
    growth is compiles happening), and the process-cumulative host<->device
    transfer tallies the device-efficiency layer keeps
    (``pio_device_transfer_bytes{direction}``).  Every probe is
    individually fenced — telemetry must never break a scrape — and the
    whole call is a no-op returning False unless this process has ALREADY
    initialized a jax backend (importing jax is not enough: the event
    server does): a scrape of the admin/dashboard/event/storage daemons
    must not trigger a multi-second backend init (or contend for the TPU
    the serving process exclusively holds) just to report empty gauges.

    The call self-meters into ``pio_runtime_sample_seconds`` (this runs on
    EVERY scrape, so its cost must be a metric, not a guess), and the
    per-device ``memory_stats`` walk — the only probe whose cost scales
    with device count — is skipped when the previous walk was under
    :data:`MEMSTATS_MIN_INTERVAL_S` ago; the gauges simply keep their
    cached values between walks.
    """
    reg = registry or REGISTRY
    if not backend_initialized():
        return False
    import jax

    t_start = time.perf_counter()
    try:
        arrs = jax.live_arrays()
        reg.gauge(
            "pio_jax_live_buffer_count", "Live jax.Array buffers in process"
        ).set(len(arrs))
        reg.gauge(
            "pio_jax_live_buffer_bytes", "Bytes held by live jax.Arrays"
        ).set(sum(getattr(a, "nbytes", 0) for a in arrs))
    except Exception:
        pass
    now = time.monotonic()
    last_walk = _memstats_last.get(reg)
    if last_walk is None or now - last_walk >= MEMSTATS_MIN_INTERVAL_S:
        _memstats_last[reg] = now
        try:
            fam = reg.gauge(
                "pio_jax_device_memory_bytes",
                "Backend-reported bytes in use per device",
                labelnames=("device",),
            )
            for d in jax.local_devices():
                stats = getattr(d, "memory_stats", lambda: None)()
                if stats and "bytes_in_use" in stats:
                    fam.labels(str(d.id)).set(stats["bytes_in_use"])
        except Exception:
            pass
    try:
        from jax._src import pjit as _pjit  # no public cache-size API yet

        size = 0
        for name in (
            "_cpp_pjit_cache_fun_only",
            "_cpp_pjit_cache_explicit_attributes",
        ):
            cache = getattr(_pjit, name, None)
            if cache is not None:
                size += cache.size()
        reg.gauge(
            "pio_jax_pjit_cache_entries",
            "Compiled executables held by the pjit caches",
        ).set(size)
        last = _cache_size_seen.get(reg)
        if last is not None and size > last:
            reg.counter(
                "pio_jax_compile_cache_growth_total",
                "pjit-cache entries added between scrapes (fresh compiles)",
            ).inc(size - last)
        _cache_size_seen[reg] = size
    except Exception:
        pass
    try:
        fam = reg.gauge(
            "pio_device_transfer_bytes",
            "Process-cumulative host<->device transfer bytes by direction",
            labelnames=("direction",),
        )
        for direction, total in device_obs.transfer_totals().items():
            fam.labels(direction).set(total)
    except Exception:
        pass
    reg.histogram(
        "pio_runtime_sample_seconds",
        "Cost of one sample_runtime_gauges pass (runs on every /metrics "
        "scrape)",
    ).observe(time.perf_counter() - t_start)
    return True
