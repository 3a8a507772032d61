"""Lightweight per-stage spans feeding the metrics registry.

``trace("stage")`` is a context manager that times its block, records the
duration into the ``pio_span_seconds{span="stage"}`` histogram, and builds a
parent/child tree through a context-local span stack — nested ``trace``
blocks become children of the enclosing one.  Finished ROOT spans
additionally land in a bounded ring buffer (:func:`recent_traces`) so "what
did the last train run spend its time on" is answerable without a metrics
backend.

This is deliberately not OpenTelemetry: no export, no sampling — a span is a
(name, duration, children) record and one histogram observation.  Spans DO
carry the contextvar ``request_id`` (obs/logging.py) when one is bound, so a
``/traces.json`` entry correlates with the ``X-Pio-Request-Id`` response
header and the matching ``/logs.json`` lines.  The HTTP front ends open one
cheap unrecorded root span per request (``record=False``: ring only, no
histogram); the second-scale stages — DASE train stages, JAX compiles, batch
predict, eval folds — use recorded spans.

**The profiler's clock.**  While a ``jax.profiler`` session is open, each
span also opens a ``jax.profiler.TraceAnnotation`` of its name, so the
program's spans land on the ``/host:CPU`` plane of the capture, beside the
device planes and on their clock: a device-idle gap is then named for the
span the host was in.  The annotation opens and closes with the ``Span`` (no
second clock, no second span type), only in a process that has ALREADY
imported ``jax`` (a span never imports it), and only while
``TraceAnnotation.is_enabled()``: an untraced process pays one flag read per
span.

**Other threads.**  A thread (or executor worker) starts with an empty span
stack.  ``trace(name, parent=span)`` opens a span there as a child of
``span``: it gets a stack of its own, inherits the parent's request and trace
ids, and attaches to ``span.children`` when it closes — the parent's own
stack is never shared between threads.
"""

from __future__ import annotations

import contextvars
import sys
import threading
import time
from collections import deque
from typing import Any

from predictionio_tpu.obs.disttrace import (
    collect as _collect_fragments,
    get_parent_span,
    new_span_id,
)
from predictionio_tpu.obs.logging import get_request_id, get_trace_id
from predictionio_tpu.obs.metrics import (
    REGISTRY,
    STAGE_BUCKETS,
    TRAIN_BUCKETS,
    MetricsRegistry,
)

#: the span stack is a ContextVar (not a threading.local) so nesting is
#: correct both across threads AND across interleaved asyncio tasks — two
#: concurrent requests on one event loop must not adopt each other's spans
_stack_var: contextvars.ContextVar[list["Span"] | None] = (
    contextvars.ContextVar("pio_span_stack", default=None)
)

#: ``jax.profiler.TraceAnnotation``, looked up the first time a span opens in
#: a process that has imported jax; never imported from here
_trace_annotation: Any = None


def _open_annotation(name: str) -> Any:
    """The span's annotation on the profiler's clock, entered — or None when
    jax is not loaded, no profiler session is open, or anything fails
    (telemetry must never break the traced block)."""
    global _trace_annotation
    cls = _trace_annotation
    try:
        if cls is None:
            jax = sys.modules.get("jax")
            if jax is None:
                return None
            cls = _trace_annotation = jax.profiler.TraceAnnotation
        if not cls.is_enabled():
            return None
        annotation = cls(name)
        annotation.__enter__()
        return annotation
    except Exception:
        return None


#: ring of the most recent finished root spans (as dicts), newest last
_ring: deque[dict[str, Any]] = deque(maxlen=256)
_ring_lock = threading.Lock()


class Span:
    """One timed block.  ``duration_s`` is valid after the block exits."""

    __slots__ = (
        "name", "start_s", "duration_s", "children", "error",
        "request_id", "tags", "span_id", "parent_id", "trace_id",
        "start_ts", "thread_id",
    )

    def __init__(self, name: str):
        self.name = name
        self.start_s = 0.0
        self.duration_s = 0.0
        self.children: list[Span] = []
        self.error: str | None = None
        #: correlation id captured from the request context at entry
        self.request_id: str | None = None
        #: small free-form annotations (route, status, ...) — keep it small;
        #: every root span's dict lands in the trace ring.  What a span COUNTED
        #: goes under the one tag ``counters`` (a dict of numbers): the train
        #: workflow sums those into the ``stages`` extra's ``counters``
        self.tags: dict[str, Any] | None = None
        #: distributed-tracing identity (obs/disttrace.py): a per-span id,
        #: the cross-process parent (root spans adopt X-Pio-Parent-Span),
        #: the trace this span belongs to, and a wall-clock start so
        #: fragments from different processes align on one timeline
        self.span_id: str = ""
        self.parent_id: str | None = None
        self.trace_id: str | None = None
        self.start_ts: float = 0.0
        #: the thread the span ran on: same-named spans of several threads
        #: ran side by side, and their seconds do not add up to wall time
        self.thread_id: int = 0

    def to_dict(self) -> dict[str, Any]:
        d: dict[str, Any] = {
            "name": self.name,
            "duration_s": round(self.duration_s, 9),
        }
        if self.request_id:
            d["request_id"] = self.request_id
        if self.trace_id:
            d["trace_id"] = self.trace_id
        if self.tags:
            d.update(self.tags)
        if self.error:
            d["error"] = self.error
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d

    def breakdown(self) -> dict[str, float]:
        """Flat child-name → seconds map (duplicate names accumulate)."""
        out: dict[str, float] = {}
        for c in self.children:
            out[c.name] = out.get(c.name, 0.0) + c.duration_s
        return out


class trace:
    """Context manager: ``with trace("train.prepare") as span: ...``

    ``record=False`` skips the span-duration histogram; ``ring=False``
    keeps a ROOT span out of the recent-traces ring (for high-volume
    infrastructure spans like storage round trips that would otherwise
    evict real request traces from ``/traces.json``) — cross-process
    fragment collection is unaffected by either.

    ``parent=span`` opens the span as a child of ``span`` from ANOTHER
    thread than the one ``span`` is open on (a pool worker): the block gets
    a span stack of its own, so nothing is shared with the parent's thread
    but the finished child, appended to ``span.children`` at exit."""

    __slots__ = (
        "span", "_registry", "_record", "_ring", "_parent", "_token",
        "_annotation",
    )

    def __init__(
        self,
        name: str,
        registry: MetricsRegistry | None = None,
        record: bool = True,
        ring: bool = True,
        parent: Span | None = None,
    ):
        self.span = Span(name)
        self._registry = registry or REGISTRY
        self._record = record
        self._ring = ring
        self._parent = parent
        self._token = None
        self._annotation = None

    def __enter__(self) -> Span:
        span = self.span
        parent = self._parent
        span.span_id = new_span_id()
        if parent is not None:
            # this thread's own stack, with the span as its bottom: spans
            # nested in the block become its children, never the parent's
            self._token = _stack_var.set([span])
            span.request_id = parent.request_id
            span.trace_id = parent.trace_id
            span.parent_id = parent.span_id
        else:
            stack = _stack_var.get()
            if stack is None:
                stack = []
                _stack_var.set(stack)
            span.request_id = get_request_id()
            span.trace_id = get_trace_id()
            if not stack:
                # a ROOT span parents to the cross-process caller (the span
                # id adopted from X-Pio-Parent-Span); children parent in-tree
                span.parent_id = get_parent_span()
            stack.append(span)
        span.thread_id = threading.get_ident()
        self._annotation = _open_annotation(span.name)
        span.start_ts = time.time()
        span.start_s = time.perf_counter()
        return span

    def __exit__(self, exc_type, exc, tb) -> None:
        self.span.duration_s = time.perf_counter() - self.span.start_s
        if self._annotation is not None:
            try:
                self._annotation.__exit__(exc_type, exc, tb)
            except Exception:
                pass  # telemetry must never break the traced block
        if exc is not None:
            self.span.error = f"{type(exc).__name__}: {exc}"
        stack = _stack_var.get() or []
        stack.pop()
        if self._parent is not None:
            _stack_var.reset(self._token)
            # list.append is atomic: two workers may close at once
            self._parent.children.append(self.span)
        elif stack:
            stack[-1].children.append(self.span)
        else:
            if self._ring:
                with _ring_lock:
                    _ring.append(self.span.to_dict())
            if self.span.trace_id:
                try:
                    # flatten the finished tree into cross-process fragments
                    # (bounded per-process store served at /spans.json)
                    _collect_fragments(self.span)
                except Exception:
                    pass  # telemetry must never break the traced block
        if self._record:
            self._registry.histogram(
                "pio_span_seconds",
                "Duration of named stages (trace spans)",
                labelnames=("span",),
                buckets=TRAIN_BUCKETS,
            ).labels(self.span.name).observe(self.span.duration_s)
        return None


def current_span() -> Span | None:
    stack = _stack_var.get()
    return stack[-1] if stack else None


def observe_span(
    name: str, seconds: float, registry: MetricsRegistry | None = None
) -> None:
    """Record an externally-timed duration as if it were a span (used by the
    JAX compile-time listener, which reports durations, not blocks)."""
    (registry or REGISTRY).histogram(
        "pio_span_seconds",
        "Duration of named stages (trace spans)",
        labelnames=("span",),
        buckets=TRAIN_BUCKETS,
    ).labels(name).observe(seconds)


def recent_traces(n: int = 20) -> list[dict[str, Any]]:
    """The most recent finished root spans, newest first."""
    with _ring_lock:
        items = list(_ring)
    return items[::-1][:n]


def clear_traces() -> None:
    with _ring_lock:
        _ring.clear()


_jax_listener_installed = False
_jax_listener_lock = threading.Lock()


def install_jax_compile_listener() -> bool:
    """Forward JAX compilation-event durations into the registry.

    Registers a ``jax.monitoring`` duration listener that records
    ``/jax/core/compile``-family events into ``pio_jax_compile_seconds`` —
    this is how a training run's stage breakdown separates XLA compile time
    from execute time — and counts them into ``pio_jax_compile_total`` so
    the device-efficiency layer (obs/device.py) can report cumulative
    compile activity next to its per-(fn, shapes) recompile attribution.
    Idempotent.
    """
    global _jax_listener_installed
    with _jax_listener_lock:
        if _jax_listener_installed:
            return True
        from jax import monitoring

        def _on_duration(event: str, duration: float, **kwargs) -> None:
            # trace + lowering + backend compile (which, on a persistent-
            # cache hit, is the retrieval).  NOT the compilation_cache
            # family: its "compile_time_saved_sec" is time NOT spent.
            if not event.startswith("/jax/core/compile/"):
                return
            try:
                REGISTRY.histogram(
                    "pio_jax_compile_seconds",
                    "XLA compile time by jax monitoring event",
                    labelnames=("event",),
                    buckets=STAGE_BUCKETS,
                ).labels(event).observe(duration)
                REGISTRY.counter(
                    "pio_jax_compile_total",
                    "XLA compile events by jax monitoring event name",
                    labelnames=("event",),
                ).labels(event).inc()
            except Exception:
                pass  # telemetry must never break compilation

        def _on_cache_event(event: str, **kwargs) -> None:
            # persistent-cache traffic: compile_requests_use_cache (every
            # cacheable compile), cache_hits (retrieved), cache_misses
            # (compiled, then written) — a warm second run shows no misses
            if not event.startswith("/jax/compilation_cache/"):
                return
            REGISTRY.counter(
                "pio_jax_compile_cache_events_total",
                "Persistent compilation cache events by name",
                labelnames=("event",),
            ).labels(event.rsplit("/", 1)[1]).inc()

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_cache_event)
        _jax_listener_installed = True
        return True


def jax_compile_stats() -> dict:
    """What the listener has recorded so far: total compile seconds (trace
    + lowering + backend compile or cache retrieval) and the persistent
    cache's event counts."""
    secs = REGISTRY.get("pio_jax_compile_seconds")
    cache = REGISTRY.get("pio_jax_compile_cache_events_total")
    return {
        "compile_s": (
            sum(child.sum for _, child in secs.series()) if secs else 0.0
        ),
        "cache": (
            {labels[0]: int(child.value) for labels, child in cache.series()}
            if cache
            else {}
        ),
    }
