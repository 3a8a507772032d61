"""Device-efficiency observability: roofline attribution from real XLA
costs and recompile accounting.

The serving/training substrate already times everything (spans, MicroBatcher
waves) but none of those numbers say how well the *device* is used.  This
module is the runtime counterpart:

- :func:`jit_cost_analysis` captures ``lowered.compile().cost_analysis()``
  (FLOPs, bytes accessed) for a jitted entry point — the XLA cost model's
  own numbers, not estimates;
- :class:`EfficiencyTracker` joins those costs with the wall-clock the
  callers already measure and exports live achieved-vs-peak gauges
  (``pio_device_achieved_gbps{fn}``, ``pio_device_achieved_tflops{fn}``,
  ``pio_device_utilization_frac{fn,resource}``) against a per-platform
  peak table (:func:`device_peaks`, overridable via
  ``PIO_DEVICE_PEAK_GBPS`` / ``PIO_DEVICE_PEAK_TFLOPS``);
- :class:`RecompileTracker` counts compiles per (fn, abstract-shape
  signature) and detects recompile *storms* — many distinct signatures for
  one fn inside a sliding window, the runtime counterpart of the
  PIO-JAX004 static rule (a client sweeping ``num`` through the NCF wave
  path churns the padded top-k width and recompiles per value);
- a contextvar *wave timeline* (:func:`wave_timeline` / :func:`wave_stage`)
  lets engines split a MicroBatcher wave's opaque ``device_s`` into
  host-gather / H2D / device-compute / D2H, so a slow query is attributable
  to transfer vs compute vs queue.

Import-light by design: servers that never touch an accelerator (event
ingest, admin, dashboard) import this module through ``obs.http`` — nothing
here imports jax at module scope, and every jax probe is gated on the
process having initialized a backend already
(``utils.runtime.backend_initialized`` — the same no-TPU-init guarantee
``obs.profiler`` keeps).
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
import os
import statistics
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Mapping

from predictionio_tpu.obs.metrics import (
    REGISTRY,
    STAGE_BUCKETS,
    MetricsRegistry,
)
from predictionio_tpu.utils.runtime import backend_initialized

log = logging.getLogger("predictionio_tpu.device")

# ---------------------------------------------------------------------------
# peak table

#: Published peak HBM bandwidth (GB/s) and dense-matmul throughput (TFLOP/s,
#: bf16 for TPUs) per device kind, most specific prefix wins.  The CPU row is
#: a DDR-class placeholder so utilization fractions stay meaningful (and
#: test-assertable) on the CPU backend; override per deployment with
#: PIO_DEVICE_PEAK_GBPS / PIO_DEVICE_PEAK_TFLOPS.
PEAK_TABLE: dict[str, tuple[float, float]] = {
    "tpu v4": (1228.0, 275.0),
    "tpu v5 lite": (819.0, 197.0),
    "tpu v5e": (819.0, 197.0),
    "tpu v5p": (2765.0, 459.0),
    "cpu": (25.0, 0.5),
    "gpu": (900.0, 100.0),
}


@dataclass(frozen=True)
class DevicePeaks:
    """Peak rates one ``achieved / peak`` division away from a fraction."""

    hbm_gbps: float
    tflops: float
    #: table key, "env", or "unknown" (a device the table does not list:
    #: both peaks are 0 and no utilization is reported against them)
    source: str


def _platform_kind() -> str:
    """Device-kind string of the device this process computes on, WITHOUT
    initializing a backend: a process that has not touched one (jax never
    imported, or imported and never used — the event server, the dashboard)
    computes on the host, and asking ``jax.devices()`` there would claim
    the chip the serving process needs.  CPU and GPU kinds are model names
    ('nvidia a100...') and resolve by platform; a TPU resolves by its own
    kind only, so a generation the table does not list stays unknown."""
    if not backend_initialized():
        return "cpu"
    import jax

    d = jax.devices()[0]
    if d.platform in ("cpu", "gpu"):
        return d.platform
    return str(d.device_kind).lower()


def device_peaks(kind: str | None = None) -> DevicePeaks:
    """Resolve the peak row for ``kind`` (default: the live platform).

    ``PIO_DEVICE_PEAK_GBPS`` / ``PIO_DEVICE_PEAK_TFLOPS`` override the table
    per deployment — read at call time so an operator can correct a
    co-tenanted or down-clocked chip without a restart.
    """
    kind = (kind or _platform_kind()).lower()
    gbps = tflops = 0.0
    source = "unknown"
    for prefix in sorted(PEAK_TABLE, key=len, reverse=True):
        if kind.startswith(prefix):
            gbps, tflops = PEAK_TABLE[prefix]
            source = prefix
            break
    env_gbps = os.environ.get("PIO_DEVICE_PEAK_GBPS")
    env_tflops = os.environ.get("PIO_DEVICE_PEAK_TFLOPS")
    if env_gbps or env_tflops:
        # source flips to "env" only when an override actually parsed — a
        # typo'd value must not make the snapshot CLAIM a correction that
        # was silently ignored
        try:
            gbps = float(env_gbps) if env_gbps else gbps
            source = "env" if env_gbps else source
        except ValueError:
            pass
        try:
            tflops = float(env_tflops) if env_tflops else tflops
            source = "env" if env_tflops else source
        except ValueError:
            pass
    return DevicePeaks(hbm_gbps=float(gbps), tflops=float(tflops),
                       source=source)


def achieved_gbps(bytes_moved: float, seconds: float) -> float:
    """Achieved HBM bandwidth in GB/s for ``bytes_moved`` over ``seconds``."""
    return bytes_moved / seconds / 1e9 if seconds > 0 else 0.0


def achieved_tflops(flops: float, seconds: float) -> float:
    """Achieved TFLOP/s for ``flops`` executed over ``seconds``."""
    return flops / seconds / 1e12 if seconds > 0 else 0.0


def utilization_frac(achieved: float, peak: float) -> float:
    """``achieved / peak`` with a zero-peak guard (fractions, not %)."""
    return achieved / peak if peak > 0 else 0.0


def device_label(x: Any) -> str:
    """``platform:id`` label of the device holding ``x`` (a jax array), or
    ``"host"`` when it has no device set — safe on plain numpy."""
    try:
        devices = getattr(x, "devices", None)
        if devices is None:
            return "host"
        d = next(iter(devices()))
        return f"{d.platform}:{d.id}"
    except Exception:
        return "host"


# ---------------------------------------------------------------------------
# XLA cost capture


def jit_cost_analysis(jitted: Any, *args: Any, **kwargs: Any) -> dict | None:
    """FLOPs / bytes-accessed of one jitted call, from XLA's own cost model.

    Runs the AOT path (``jitted.lower(...).compile().cost_analysis()``) for
    the given concrete arguments.  That compile is out-of-band — it does NOT
    populate the jit cache — so callers cache the result per abstract-shape
    signature (:meth:`EfficiencyTracker.capture_cost`) and only pay it once
    per signature, the same cardinality the jit cache itself grows at (and
    the persistent compilation cache, when configured, absorbs the repeat).
    Returns ``{"flops": float, "bytes": float}`` or None when the backend
    reports no cost model; never raises — telemetry must not break serving.
    """
    try:
        lowered = jitted.lower(*args, **kwargs)
        analysis = lowered.compile().cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else None
        if not isinstance(analysis, Mapping):
            return None
        flops = float(analysis.get("flops", 0.0) or 0.0)
        nbytes = float(analysis.get("bytes accessed", 0.0) or 0.0)
        if flops <= 0.0 and nbytes <= 0.0:
            return None
        return {"flops": flops, "bytes": nbytes}
    except Exception:
        return None


def signature_of(*args: Any) -> tuple:
    """Abstract-shape signature of concrete call args: ``(shape, dtype)``
    for array-likes, ``repr`` for everything else — the recompile key."""
    sig = []
    for a in args:
        shape = getattr(a, "shape", None)
        if shape is not None:
            sig.append((tuple(shape), str(getattr(a, "dtype", "?"))))
        else:
            sig.append(repr(a))
    return tuple(sig)


# ---------------------------------------------------------------------------
# efficiency tracker


class EfficiencyTracker:
    """Join per-fn XLA costs with caller-measured device seconds.

    ``record_cost`` stores FLOPs/bytes per (fn, signature) — from
    :func:`jit_cost_analysis` or an analytic plan (the pallas roofline) —
    and ``observe`` converts one timed execution into achieved-vs-peak
    gauges plus cumulative FLOP/byte counters.  All state under one lock;
    the observe path is two dict reads and four gauge sets.
    """

    def __init__(
        self,
        registry: MetricsRegistry | None = None,
        peaks: DevicePeaks | None = None,
    ):
        self._lock = threading.Lock()
        self._registry = registry or REGISTRY
        self._peaks = peaks
        #: (fn, signature) -> {"flops", "bytes", "source"}
        self._costs: dict[tuple[str, tuple], dict[str, Any]] = {}
        #: (fn, signature) -> in-flight deferred capture thread
        self._pending: dict[tuple[str, tuple], threading.Thread] = {}
        #: fn -> the signature of the most recent record/observe
        self._last_sig: dict[str, tuple] = {}
        #: fn -> {"calls", "seconds", "flops", "bytes"} cumulative
        self._totals: dict[str, dict[str, float]] = {}
        reg = self._registry
        self._g_gbps = reg.gauge(
            "pio_device_achieved_gbps",
            "Achieved HBM bandwidth per jitted entry point (GB/s)",
            labelnames=("fn",),
        )
        self._g_tflops = reg.gauge(
            "pio_device_achieved_tflops",
            "Achieved matmul throughput per jitted entry point (TFLOP/s)",
            labelnames=("fn",),
        )
        self._g_util = reg.gauge(
            "pio_device_utilization_frac",
            "Achieved / peak fraction per entry point and resource",
            labelnames=("fn", "resource"),
        )
        self._c_flops = reg.counter(
            "pio_device_flops_total",
            "Cumulative FLOPs executed per entry point (cost-model)",
            labelnames=("fn",),
        )
        self._c_bytes = reg.counter(
            "pio_device_bytes_total",
            "Cumulative bytes accessed per entry point (cost-model)",
            labelnames=("fn",),
        )

    def record_cost(
        self,
        fn: str,
        flops: float,
        nbytes: float,
        signature: tuple = (),
        source: str = "cost_analysis",
    ) -> None:
        """Install the per-call cost of ``fn`` at ``signature``."""
        with self._lock:
            self._costs[(fn, signature)] = {
                "flops": float(flops),
                "bytes": float(nbytes),
                "source": source,
            }
            self._last_sig[fn] = signature

    def capture_cost(
        self, fn: str, jitted: Any, *args: Any,
        signature: tuple | None = None, defer: bool = False, **kwargs: Any,
    ) -> dict | None:
        """Capture ``fn``'s XLA cost ONCE per signature (cached thereafter).

        Returns the cost dict (possibly cached) or None when the backend has
        no cost model.  The once-per-signature discipline keeps the AOT
        compile off the steady-state hot path.

        ``defer=True`` (the serving-path mode) runs the first capture on a
        daemon thread and returns None immediately: the out-of-band AOT
        analysis compile must not stall a wave under its deadline — it runs
        CONCURRENTLY with the jit cache's own compile of the same signature,
        and the cost lands before the next wave of that shape.  Tests drain
        with :meth:`flush`.
        """
        sig = signature_of(*args) if signature is None else signature
        key = (fn, sig)
        with self._lock:
            cached = self._costs.get(key)
            if cached is not None:
                self._last_sig[fn] = sig
                return dict(cached)
            if defer and key in self._pending:
                return None
        if defer:

            def work() -> None:
                try:
                    cost = jit_cost_analysis(jitted, *args, **kwargs)
                    if cost is not None:
                        self.record_cost(
                            fn, cost["flops"], cost["bytes"], signature=sig
                        )
                finally:
                    with self._lock:
                        self._pending.pop(key, None)

            thread = threading.Thread(
                target=work, name="pio-cost-capture", daemon=True
            )
            # locked RE-check before insert: the cheap check above dropped
            # the lock (so the steady-state cache-hit path allocates no
            # Thread), and two concurrent first waves must not both spawn
            # capture threads — the loser's cleanup would pop the winner's
            # _pending entry and flush() would return early
            with self._lock:
                cached = self._costs.get(key)
                if cached is not None:
                    self._last_sig[fn] = sig
                    return dict(cached)
                if key in self._pending:
                    return None
                self._pending[key] = thread
            thread.start()
            return None
        cost = jit_cost_analysis(jitted, *args, **kwargs)
        if cost is None:
            return None
        self.record_cost(fn, cost["flops"], cost["bytes"], signature=sig)
        with self._lock:
            return dict(self._costs[key])

    def cached_cost(self, fn: str, signature: tuple) -> dict | None:
        """The recorded cost for (fn, signature), if it has landed."""
        with self._lock:
            cost = self._costs.get((fn, signature))
            return dict(cost) if cost is not None else None

    def flush(self, timeout: float = 10.0) -> bool:
        """Join outstanding deferred captures (tests and batch callers);
        True when none remain in flight."""
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                threads = list(self._pending.values())
            if not threads:
                return True
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return False
            threads[0].join(remaining)

    def observe(
        self, fn: str, seconds: float, signature: tuple | None = None
    ) -> None:
        """One timed execution of ``fn``: update achieved/utilization gauges
        and cumulative counters using the cost recorded for ``signature``
        (default: the most recent one for ``fn``).  No-op without a cost —
        timing alone cannot place a point on the roofline."""
        if seconds <= 0:
            return
        with self._lock:
            sig = self._last_sig.get(fn) if signature is None else signature
            cost = self._costs.get((fn, sig if sig is not None else ()))
            if cost is None:
                return
            totals = self._totals.setdefault(
                fn, {"calls": 0.0, "seconds": 0.0, "flops": 0.0, "bytes": 0.0}
            )
            totals["calls"] += 1
            totals["seconds"] += seconds
            totals["flops"] += cost["flops"]
            totals["bytes"] += cost["bytes"]
        gbps = achieved_gbps(cost["bytes"], seconds)
        tflops = achieved_tflops(cost["flops"], seconds)
        peaks = self._peaks or device_peaks()
        self._g_gbps.labels(fn).set(gbps)
        self._g_tflops.labels(fn).set(tflops)
        if peaks.source != "unknown":
            self._g_util.labels(fn, "hbm").set(
                utilization_frac(gbps, peaks.hbm_gbps)
            )
            self._g_util.labels(fn, "mxu").set(
                utilization_frac(tflops, peaks.tflops)
            )
        self._c_flops.labels(fn).inc(cost["flops"])
        self._c_bytes.labels(fn).inc(cost["bytes"])

    def snapshot(self) -> dict[str, Any]:
        """Per-fn costs, cumulative achieved rates, and utilization — the
        ``/efficiency.json`` body."""
        peaks = self._peaks or device_peaks()
        with self._lock:
            costs = {k: dict(v) for k, v in self._costs.items()}
            totals = {k: dict(v) for k, v in self._totals.items()}
        fns: dict[str, Any] = {}
        for (fn, _sig), cost in costs.items():
            entry = fns.setdefault(
                fn,
                {
                    "signatures": 0,
                    "flops_per_call": 0.0,
                    "bytes_per_call": 0.0,
                    "source": cost["source"],
                },
            )
            entry["signatures"] += 1
            # the largest signature's cost is the representative one
            entry["flops_per_call"] = max(
                entry["flops_per_call"], cost["flops"]
            )
            entry["bytes_per_call"] = max(
                entry["bytes_per_call"], cost["bytes"]
            )
        for fn, t in totals.items():
            entry = fns.setdefault(fn, {"signatures": 0, "source": "?"})
            gbps = achieved_gbps(t["bytes"], t["seconds"])
            tflops = achieved_tflops(t["flops"], t["seconds"])
            entry.update(
                calls=int(t["calls"]),
                seconds_total=round(t["seconds"], 6),
                flops_total=t["flops"],
                bytes_total=t["bytes"],
                achieved_gbps=round(gbps, 3),
                achieved_tflops=round(tflops, 6),
            )
            if peaks.source != "unknown":
                entry.update(
                    utilization_hbm=round(
                        utilization_frac(gbps, peaks.hbm_gbps), 6
                    ),
                    utilization_mxu=round(
                        utilization_frac(tflops, peaks.tflops), 6
                    ),
                )
        return {
            "platform": _platform_kind(),
            "peaks": {
                "hbm_gbps": peaks.hbm_gbps,
                "tflops": peaks.tflops,
                "source": peaks.source,
            },
            "functions": fns,
        }


# ---------------------------------------------------------------------------
# recompile accounting


class RecompileTracker:
    """Compile events keyed by (fn, abstract-shape signature), with a storm
    detector: N distinct signatures for one fn inside a sliding window means
    traffic is churning shapes and every wave pays an XLA compile — the
    runtime counterpart of the PIO-JAX004 static rule.

    Thresholds come from ``PIO_RECOMPILE_STORM_N`` (distinct signatures,
    default 4) and ``PIO_RECOMPILE_STORM_WINDOW_S`` (default 60) at
    construction.  ``now`` parameters exist so tests drive a frozen clock.
    """

    def __init__(
        self,
        registry: MetricsRegistry | None = None,
        storm_threshold: int | None = None,
        window_s: float | None = None,
    ):
        self._lock = threading.Lock()
        if storm_threshold is None:
            storm_threshold = int(
                os.environ.get("PIO_RECOMPILE_STORM_N", "4")
            )
        if window_s is None:
            window_s = float(
                os.environ.get("PIO_RECOMPILE_STORM_WINDOW_S", "60")
            )
        self.storm_threshold = max(storm_threshold, 2)
        self.window_s = window_s
        #: fn -> every signature ever seen (compile-cache cardinality)
        self._seen: dict[str, set] = {}
        #: fn -> deque of (t, signature) for NEW signatures in the window
        self._recent: dict[str, deque] = {}
        #: fn -> storm-active-until timestamp
        self._storm_until: dict[str, float] = {}
        reg = registry or REGISTRY
        self._c_recompiles = reg.counter(
            "pio_jax_recompile_total",
            "New (fn, abstract shapes) signatures seen — one per XLA compile",
            labelnames=("fn",),
        )
        self._c_storms = reg.counter(
            "pio_recompile_storm_total",
            "Recompile storms detected (distinct signatures over threshold "
            "inside the window)",
            labelnames=("fn",),
        )

    def note_signature(
        self, fn: str, signature: tuple, now: float | None = None
    ) -> bool:
        """Record a call signature; returns True when it is NEW for ``fn``
        (i.e. this call compiled).  Trips the storm counter + a structured
        warning when distinct new signatures inside the window reach the
        threshold."""
        t = time.monotonic() if now is None else now
        with self._lock:
            seen = self._seen.setdefault(fn, set())
            if signature in seen:
                return False
            seen.add(signature)
            recent = self._recent.setdefault(fn, deque())
            recent.append((t, signature))
            while recent and recent[0][0] < t - self.window_s:
                recent.popleft()
            distinct = len(recent)
            storming = distinct >= self.storm_threshold
            was_storming = self._storm_until.get(fn, 0.0) > t
            if storming:
                self._storm_until[fn] = t + self.window_s
        self._c_recompiles.labels(fn).inc()
        if storming and not was_storming:
            self._c_storms.labels(fn).inc()
            log.warning(
                "recompile storm: %d distinct shape signatures for %s "
                "inside %.0fs — traffic is churning shapes and every wave "
                "pays an XLA compile (pad inputs to a fixed menu of shapes; "
                "see PIO-JAX004)",
                distinct,
                fn,
                self.window_s,
                extra={
                    "fn": fn,
                    "distinct_signatures": distinct,
                    "window_s": self.window_s,
                },
            )
        return True

    def active_storms(self, now: float | None = None) -> dict[str, Any]:
        """Functions currently inside a storm window.  ``signatures`` is the
        IN-WINDOW distinct count the storm was detected on (what the
        operator warning cites); ``total_signatures`` the lifetime tally."""
        t = time.monotonic() if now is None else now
        with self._lock:
            return {
                fn: {
                    "until_s": round(until - t, 3),
                    "signatures": len(
                        [1 for ts, _ in self._recent.get(fn, ())
                         if ts >= t - self.window_s]
                    ),
                    "total_signatures": len(self._seen.get(fn, ())),
                }
                for fn, until in self._storm_until.items()
                if until > t
            }

    def snapshot(self, now: float | None = None) -> dict[str, Any]:
        with self._lock:
            fns = {
                fn: {
                    "signatures": len(sigs),
                    "recent_window": len(self._recent.get(fn, ())),
                }
                for fn, sigs in self._seen.items()
            }
        return {
            "threshold": self.storm_threshold,
            "window_s": self.window_s,
            "functions": fns,
            "active_storms": self.active_storms(now),
        }


# ---------------------------------------------------------------------------
# wave timeline: the 4-way device_s split

#: the stages a wave decomposes into; anything unattributed lands in "other"
WAVE_STAGES: tuple[str, ...] = ("host_gather", "h2d", "compute", "d2h")


class WaveTimeline:
    """Per-wave accumulator engines mark stages into (contextvar-scoped)."""

    __slots__ = (
        "stages", "device", "fn", "flops", "bytes", "transfers", "shards",
        "shard_seconds", "cache_hits", "cache_misses", "cache_miss_bytes",
        "storage_bytes",
    )

    def __init__(self):
        self.stages: dict[str, float] = {}
        self.device: str = "host"
        self.fn: str | None = None
        self.flops: float = 0.0
        self.bytes: float = 0.0
        self.transfers: dict[str, float] = {}
        #: factor-cache hits inside this wave (note_cache_hit): a repeat
        #: entity whose gather was skipped — flows into per-item meta as
        #: ``cache_hits`` so flight entries prove gather ~ 0 on a hit
        self.cache_hits: int = 0
        #: ... and the misses, with the bytes their resolving fetch moved
        #: (note_cache_miss / note_cache_fill): the cost ledger bills a hit
        #: as ≈0 bytes and a miss as its fetch bytes (obs/costs.py)
        self.cache_misses: int = 0
        self.cache_miss_bytes: float = 0.0
        #: event-store bytes read inside this wave (costs.note_storage_read
        #: lands here when no request record is bound — the wave total is
        #: prorated back to members through per-item meta)
        self.storage_bytes: float = 0.0
        #: per-device byte/shard attribution of a SHARDED wave (filled by
        #: note_wave_shards; flows into per-item meta -> flight entries)
        self.shards: dict[str, dict[str, float]] = {}
        #: per-device settle seconds of a SHARDED wave (filled by
        #: note_shard_seconds; the straggler board's and the distributed
        #: timeline's per-shard signal)
        self.shard_seconds: dict[str, float] = {}


_timeline_var: contextvars.ContextVar[WaveTimeline | None] = (
    contextvars.ContextVar("pio_wave_timeline", default=None)
)

#: process-cumulative transfer byte tallies (mirrored to gauges on scrape by
#: obs.profiler.sample_runtime_gauges so isolated registries see them too)
_transfer_lock = threading.Lock()
_transfer_totals: dict[str, float] = {"h2d": 0.0, "d2h": 0.0}


@contextlib.contextmanager
def wave_timeline():
    """Open a wave scope; the MicroBatcher wraps ``batch_fn`` in one so the
    engine's :func:`wave_stage` marks land on the dispatching wave."""
    tl = WaveTimeline()
    token = _timeline_var.set(tl)
    try:
        yield tl
    finally:
        _timeline_var.reset(token)


def current_timeline() -> WaveTimeline | None:
    return _timeline_var.get()


@contextlib.contextmanager
def wave_stage(name: str):
    """Time a block into the current wave's ``name`` stage (no-op without an
    open timeline, e.g. an engine's batch_predict called outside serving)."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        tl = _timeline_var.get()
        if tl is not None:
            tl.stages[name] = (
                tl.stages.get(name, 0.0) + time.perf_counter() - t0
            )


def note_wave_device(label: str) -> None:
    """Attach the executing device's label to the current wave."""
    tl = _timeline_var.get()
    if tl is not None:
        tl.device = label


def note_cache_hit(n: int = 1) -> None:
    """Record ``n`` factor-cache hits on the current wave (no-op outside a
    wave scope) — the per-request twin of pio_factor_cache_hits_total."""
    tl = _timeline_var.get()
    if tl is not None:
        tl.cache_hits += n


def note_cache_miss(n: int = 1) -> None:
    """Record ``n`` factor-cache misses on the current wave — each one paid
    the real gather its hit-twin skipped."""
    tl = _timeline_var.get()
    if tl is not None:
        tl.cache_misses += n


def note_cache_fill(nbytes: float) -> None:
    """Record the bytes a cache-miss fetch moved into the cache on the
    current wave (the miss side of the cost ledger's hit-vs-miss split)."""
    tl = _timeline_var.get()
    if tl is not None:
        tl.cache_miss_bytes += float(nbytes)


def note_wave_cost(fn: str, cost: Mapping[str, float] | None) -> None:
    """Attach the wave's entry-point name and per-call cost (flows into the
    flight-recorder entry of any slow/errored request the wave served)."""
    tl = _timeline_var.get()
    if tl is not None:
        tl.fn = fn
        if cost:
            tl.flops = float(cost.get("flops", 0.0))
            tl.bytes = float(cost.get("bytes", 0.0))


def note_wave_shards(attribution: Mapping[str, Mapping[str, float]]) -> None:
    """Attach a sharded wave's per-device attribution (the
    ``parallel.mesh.meter_shards`` map) to the current timeline: every
    flight entry of a sharded wave answers "which devices participated and
    how many bytes each held"."""
    tl = _timeline_var.get()
    if tl is not None and attribution:
        tl.shards = {k: dict(v) for k, v in attribution.items()}


def note_shard_seconds(shard_seconds: Mapping[str, float]) -> None:
    """Attach a sharded wave's per-device settle seconds to the current
    timeline (flows into per-item meta as ``wave_shard_seconds`` and the
    distributed timeline's per-shard device tracks)."""
    tl = _timeline_var.get()
    if tl is not None and shard_seconds:
        tl.shard_seconds = {k: float(v) for k, v in shard_seconds.items()}


def note_transfer(
    direction: str, nbytes: int, registry: MetricsRegistry | None = None
) -> None:
    """Account ``nbytes`` moved host<->device (``h2d`` / ``d2h``): bumps the
    process tally + the registry counter, and the current wave's split."""
    with _transfer_lock:
        _transfer_totals[direction] = (
            _transfer_totals.get(direction, 0.0) + nbytes
        )
    (registry or REGISTRY).counter(
        "pio_device_transfer_bytes_total",
        "Cumulative host<->device transfer bytes by direction",
        labelnames=("direction",),
    ).labels(direction).inc(nbytes)
    tl = _timeline_var.get()
    if tl is not None:
        tl.transfers[direction] = tl.transfers.get(direction, 0.0) + nbytes


def transfer_totals() -> dict[str, float]:
    """Process-cumulative h2d/d2h byte tallies (scrape-time mirror)."""
    with _transfer_lock:
        return dict(_transfer_totals)


def split_breakdown(
    tl: WaveTimeline | None, device_s: float
) -> dict[str, float]:
    """Decompose ``device_s`` into the 4 marked stages plus ``other`` (the
    unattributed remainder, clamped at zero) — the parts sum to ``device_s``
    whenever the marked stages fit inside it, which they do by construction
    (stages are timed inside the batch_fn window ``device_s`` brackets)."""
    stages = dict(tl.stages) if tl is not None else {}
    out = {name: round(stages.get(name, 0.0), 6) for name in WAVE_STAGES}
    marked = sum(stages.get(name, 0.0) for name in WAVE_STAGES)
    out["other"] = round(max(device_s - marked, 0.0), 6)
    return out


# ---------------------------------------------------------------------------
# straggler & imbalance detection


class StragglerBoard:
    """Per-wave shard-time skew tracking and straggler attribution.

    Every sharded wave reports its per-device settle seconds
    (``placement.run_observed_wave`` measures them shard by shard); the
    board computes the wave's **skew fraction** — ``max / median - 1`` over
    the participating devices, 0.0 for a perfectly balanced wave — into
    ``pio_shard_skew_frac{fn}``, keeps a rolling per-device scoreboard
    (waves participated, waves slowest, cumulative seconds), and flags a
    **straggler** when ONE device is the slowest with skew above
    ``skew_threshold`` for ``patience`` consecutive waves (a single slow
    wave is noise; the same device dragging every wave is a sick chip, a
    co-tenant, or an imbalanced placement).  Byte imbalance
    (``max / mean - 1`` over per-device bytes, from ``shard_attribution``)
    rides along as ``pio_shard_bytes_imbalance_frac{fn}``.

    Thresholds come from ``PIO_SHARD_SKEW_THRESHOLD`` (default 0.5: the
    slowest shard runs 1.5x the median) and ``PIO_SHARD_SKEW_PATIENCE``
    (default 3 consecutive waves).  ``snapshot`` is the ``/shards.json``
    scoreboard body.
    """

    def __init__(
        self,
        registry: MetricsRegistry | None = None,
        skew_threshold: float | None = None,
        patience: int | None = None,
    ):
        if skew_threshold is None:
            try:
                skew_threshold = float(
                    os.environ.get("PIO_SHARD_SKEW_THRESHOLD", "0.5")
                )
            except ValueError:
                skew_threshold = 0.5
        if patience is None:
            try:
                patience = int(os.environ.get("PIO_SHARD_SKEW_PATIENCE", "3"))
            except ValueError:
                patience = 3
        self.skew_threshold = skew_threshold
        self.patience = max(patience, 1)
        self._lock = threading.Lock()
        #: fn -> scoreboard state (all mutation under _lock)
        self._fns: dict[str, dict[str, Any]] = {}
        reg = registry or REGISTRY
        self._g_skew = reg.gauge(
            "pio_shard_skew_frac",
            "Last sharded wave's max/median shard-time skew (0 = balanced)",
            labelnames=("fn",),
        )
        self._g_bytes_imbalance = reg.gauge(
            "pio_shard_bytes_imbalance_frac",
            "Per-device bytes max/mean imbalance of a sharded array group",
            labelnames=("fn",),
        )
        self._c_stragglers = reg.counter(
            "pio_shard_straggler_total",
            "Straggler flags raised (one device slowest past the skew "
            "threshold for `patience` consecutive waves)",
            labelnames=("fn", "device"),
        )

    def record_wave(
        self,
        fn: str,
        shard_seconds: Mapping[str, float],
        shard_bytes: Mapping[str, float] | None = None,
    ) -> float:
        """Record one sharded wave's per-device seconds (and optionally the
        per-device byte attribution); returns the wave's skew fraction."""
        secs = {str(k): float(v) for k, v in shard_seconds.items() if v >= 0}
        if len(secs) < 2:
            return 0.0
        med = statistics.median(secs.values())
        slowest = max(secs, key=secs.get)  # type: ignore[arg-type]
        skew = (secs[slowest] / med - 1.0) if med > 0 else 0.0
        breach = skew > self.skew_threshold
        flagged = False
        with self._lock:
            entry = self._fns.setdefault(
                fn,
                {
                    "waves": 0,
                    "last_skew": 0.0,
                    "last_max_device": None,
                    "streak_device": None,
                    "streak": 0,
                    "straggler": None,
                    "devices": {},
                },
            )
            entry["waves"] += 1
            entry["last_skew"] = round(skew, 6)
            entry["last_max_device"] = slowest
            for dev, s in secs.items():
                d = entry["devices"].setdefault(
                    dev, {"waves": 0, "slowest": 0, "seconds": 0.0}
                )
                d["waves"] += 1
                d["seconds"] = round(d["seconds"] + s, 6)
            entry["devices"][slowest]["slowest"] += 1
            if breach:
                if entry["streak_device"] == slowest:
                    entry["streak"] += 1
                else:
                    entry["streak_device"] = slowest
                    entry["streak"] = 1
                if (
                    entry["streak"] >= self.patience
                    and entry["straggler"] != slowest
                ):
                    entry["straggler"] = slowest
                    flagged = True
            else:
                entry["streak_device"] = None
                entry["streak"] = 0
                entry["straggler"] = None
        self._g_skew.labels(fn).set(skew)
        if shard_bytes:
            vals = [float(v) for v in shard_bytes.values()]
            mean = sum(vals) / len(vals) if vals else 0.0
            imbalance = (max(vals) / mean - 1.0) if mean > 0 else 0.0
            self._g_bytes_imbalance.labels(fn).set(imbalance)
        if flagged:
            self._c_stragglers.labels(fn, slowest).inc()
            log.warning(
                "shard straggler: device %s is the slowest shard of %s for "
                "%d consecutive waves (skew %.0f%% over the median, "
                "threshold %.0f%%) — check chip health / co-tenancy / "
                "placement balance (/shards.json has the scoreboard)",
                slowest,
                fn,
                self.patience,
                skew * 100.0,
                self.skew_threshold * 100.0,
                extra={
                    "fn": fn,
                    "device": slowest,
                    "skew_frac": round(skew, 4),
                    "patience": self.patience,
                },
            )
        return skew

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            fns = {
                fn: {
                    **{k: v for k, v in e.items() if k != "devices"},
                    "devices": {d: dict(v) for d, v in e["devices"].items()},
                }
                for fn, e in self._fns.items()
            }
        return {
            "skew_threshold": self.skew_threshold,
            "patience": self.patience,
            "functions": fns,
        }

    def clear(self) -> None:
        with self._lock:
            self._fns.clear()


# ---------------------------------------------------------------------------
# process defaults + the /efficiency.json body

#: process-global trackers: device telemetry is per-process like the jit
#: cache and the profiler — servers with isolated registries still share
#: the one accelerator
DEVICE_EFFICIENCY = EfficiencyTracker()
RECOMPILES = RecompileTracker()
STRAGGLERS = StragglerBoard()


def default_stragglers() -> StragglerBoard:
    return STRAGGLERS


def default_efficiency() -> EfficiencyTracker:
    return DEVICE_EFFICIENCY


def default_recompiles() -> RecompileTracker:
    return RECOMPILES


def shard_snapshot(registry: MetricsRegistry | None = None) -> dict[str, Any]:
    """Per-device shard attribution as recorded by
    ``parallel.mesh.meter_shards``: ``{fn: {device: {bytes, waves,
    seconds}}}`` plus the participating-device list (the "mesh shape" an
    operator sees).  Empty when nothing sharded has run."""
    reg = registry or REGISTRY
    out: dict[str, dict[str, dict[str, float]]] = {}
    fam_bytes = reg.get("pio_shard_bytes")
    if fam_bytes is not None:
        for (fn, device), child in fam_bytes.series():
            out.setdefault(fn, {})[device] = {
                "bytes": float(getattr(child, "value", 0.0))
            }
    fam_secs = reg.get("pio_shard_seconds")
    if fam_secs is not None:
        for (fn, device), child in fam_secs.series():
            entry = out.setdefault(fn, {}).setdefault(device, {})
            entry["waves"] = int(getattr(child, "count", 0))
            entry["seconds"] = round(float(getattr(child, "sum", 0.0)), 6)
    devices = sorted({d for per_fn in out.values() for d in per_fn})
    return {"devices": devices, "functions": out}


def shards_snapshot(
    registry: MetricsRegistry | None = None,
    stragglers: StragglerBoard | None = None,
) -> dict[str, Any]:
    """The ``GET /shards.json`` body: per-device placement attribution
    (bytes/waves/seconds per fn) plus the rolling straggler scoreboard —
    the one scrape that answers "which device is dragging the mesh"."""
    return {
        "shards": shard_snapshot(registry),
        "stragglers": (stragglers or STRAGGLERS).snapshot(),
    }


def device_snapshot(
    efficiency: EfficiencyTracker | None = None,
    recompiles: RecompileTracker | None = None,
) -> dict[str, Any]:
    """The ``GET /efficiency.json`` body: achieved-vs-peak per entry point,
    recompile accounting (with any active storm), transfer tallies, and the
    per-device shard attribution of any sharded model."""
    snap = (efficiency or DEVICE_EFFICIENCY).snapshot()
    snap["recompiles"] = (recompiles or RECOMPILES).snapshot()
    snap["transfers"] = {
        f"{k}_bytes": v for k, v in transfer_totals().items()
    }
    snap["shards"] = shard_snapshot()
    return snap


#: buckets for the per-stage wave histograms — reuse the stage range
WAVE_STAGE_BUCKETS = STAGE_BUCKETS
