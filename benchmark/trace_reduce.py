"""From a profiler trace (``*.xplane.pb``) to device busy time, the device's
time by operation and by the program's named scope, and its idle time by what
the host was doing.  The one reduction every cell and every later PR uses.

``jax.profiler.ProfileData`` reads the file with nothing but jaxlib: no
backend is initialized, so the harness process can reduce a trace that the
child holding the chip wrote.

What a TPU trace looks like (one v5e, jax 0.9.0; ``tests/data`` keeps one):
plane ``/device:TPU:<n>`` per chip with lines ``XLA Modules`` (one event per
program execution), ``XLA Ops`` (one per operation inside it), ``Async XLA
Ops`` (DMA that overlaps the ops) and ``TC Overlay``; host threads are lines
of plane ``/host:CPU``.  Times are nanoseconds on one clock.

Operations nest on the op line (a ``while`` spans its body's operations):
operations are counted by SELF time, so they add up to busy, by name
(``ops_by_name``; ``device_ops`` is its first ten) and by scope (``scopes``).

The scope of an operation is what the program wrote around it with
``jax.named_scope``.  It is in the HLO ``op_name``, which the trace keeps as
the ``tf_op`` stat of each op event's METADATA; ``ProfileData`` does not hand
event metadata out, so ``op_names_by_event`` reads it from the file's own
bytes.  Two limits: a fusion carries ONE op name (its root's), so a fusion
the compiler built across two scopes counts under one of them; and a
``while`` counts by self time, its body's operations under their own names.

busy   = length of the union of the ``XLA Ops`` intervals of a device plane
         (of ``XLA Modules`` where a plane has no op line), averaged over the
         device planes that ran anything;
window = first start to last end over the device planes' events AND the host
         plane's events, i.e. the traced span — a device that ran nothing for
         the first half of the capture was idle for it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Iterable

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINES = ("XLA Ops", "XLA Modules")
HOST_PLANE = "/host:CPU"
#: idle gaps shorter than this are the device's own op-to-op hand-over
MIN_GAP_NS = 1_000
#: the longest gaps of a trace are attributed to host activity, no more
GAPS_ATTRIBUTED = 200
#: an operation the program put no ``named_scope`` around
NO_SCOPE = "(no scope)"
#: idle time while none of the program's spans was open
OUTSIDE_SPANS = "(outside the program's spans)"
#: idle time under the spans past the ``top`` largest, so the list adds up
OTHER_SPANS = "(other spans)"
#: a component the program wrote: ``family.part`` (``seq.attn``, ``gdn.chunk``,
#: ``als.user_half``), whole between slashes or inside a wrapper (``jvp(seq.loss)``);
#: ``state['params']['layer0.q']`` (an argument's name) is none
SCOPE_PART = re.compile(r"(?<![^/(])[A-Za-z_]\w*(?:\.[A-Za-z_]\w*)+(?![^/)])")


def find_xplane(trace_dir: str) -> str:
    """The newest ``*.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def merged(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """The (start, end) intervals with overlapping ones joined, in order."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_length(intervals: Iterable[tuple[float, float]]) -> float:
    """Total length covered by (start, end) intervals."""
    return float(sum(e - s for s, e in merged(intervals)))


def self_times(ops: list[tuple[float, float, str]]) -> list[tuple[str, float]]:
    """(name, self time) of each event of one line: its duration minus the
    events nested in it (a ``while`` spans the operations of its body)."""
    out: list[list] = []
    stack: list[int] = []  # indices into out of the events still open
    ends: list[float] = []
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and s >= ends[-1]:
            stack.pop()
            ends.pop()
        if stack:
            out[stack[-1]][1] -= min(e, ends[-1]) - s
        out.append([name, e - s])
        stack.append(len(out) - 1)
        ends.append(e)
    return [(name, max(t, 0.0)) for name, t in out]


def short_name(op: str) -> str:
    """``%fusion.3 = f32[..] fusion(...), kind=...`` -> ``fusion.3``; names
    without that shape pass through (cut to 80 characters)."""
    m = re.match(r"^%?([\w.\-]+)\s*=", op)
    return (m.group(1) if m else op)[:80]


# -- the scope of an operation ------------------------------------------------


def _varint(buf, i: int) -> tuple[int, int]:
    value, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf):
    """(field number, wire type, value) of one protobuf message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {wire}")
        yield field, wire, value


def op_names_by_event(path: str) -> dict[str, str]:
    """{XLA op event name: its ``tf_op`` stat} over the device planes of an
    ``*.xplane.pb`` (tsl/profiler/protobuf/xplane.proto: XSpace.planes = 1;
    XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5;
    XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1, .str_value =
    5, .ref_value = 7; XStatMetadata.id = 1, .name = 2)."""
    out: dict[str, str] = {}
    with open(path, "rb") as f:
        space = memoryview(f.read())  # slices of it copy nothing
    for field, _, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, _, v in _fields(plane):
            if f == 2:
                name = str(v, "utf-8", "replace")
            elif f == 4:  # map entry: key = 1, value = 2
                events += [mv for mf, _, mv in _fields(v) if mf == 2]
            elif f == 5:
                for mf, _, mv in _fields(v):
                    if mf == 2:
                        meta = dict((a, c) for a, _, c in _fields(mv))
                        stat_names[meta.get(1, 0)] = str(
                            meta.get(2, b""), "utf-8", "replace")
        if not DEVICE_PLANE.match(name):
            continue
        for ev in events:
            ev_name, tf_op = "", None
            for f, _, v in _fields(ev):
                if f == 2:
                    ev_name = str(v, "utf-8", "replace")
                elif f == 5:
                    stat = dict((a, c) for a, _, c in _fields(v))
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    if 5 in stat:
                        tf_op = str(stat[5], "utf-8", "replace")
                    elif 7 in stat:  # a reference to a stat metadata's name
                        tf_op = stat_names.get(stat[7], "")
            if tf_op is not None:
                out[ev_name] = tf_op
    return out


def scope_of(op_name: str | None) -> tuple[str, str]:
    """(scope path, pass) of an operation's op name.

    The path: the ``family.part`` components the program wrote, in order, each
    once (an inlined ``jit`` repeats its caller's whole prefix), found inside
    the differentiation wrappers too::

        jit(steps)/while/body/als.user_half/als.solve/mul   als.user_half/als.solve
        jit(f)/jvp(seq.gdn)/checkpoint/gdn.chunk/gdn_chunk_fwd     seq.gdn/gdn.chunk
        jit(f)/transpose(jvp(jvp()))/checkpoint/seq.mlp/dot_general          seq.mlp
        jit(f)/jit(_normal)/jit(_uniform)/add                             (no scope)

    The pass: ``recompute`` where the name holds ``rematted_computation`` (the
    forward a ``jax.checkpoint`` runs again inside the backward), else
    ``backward`` where it holds ``transpose(``, else ``forward``.  Where the
    compiler joined several names with ``;`` the first that has a scope
    counts."""
    name = (op_name or "").rpartition(":")[0] or (op_name or "")
    parts: list[str] = []
    for joined in name.split(";"):
        for part in SCOPE_PART.findall(joined):
            if part not in parts:
                parts.append(part)
        if parts:
            name = joined
            break
    if "rematted_computation" in name:
        which = "recompute"
    elif "transpose(" in name:
        which = "backward"
    else:
        which = "forward"
    return "/".join(parts) or NO_SCOPE, which


# -- idle time by the program's spans -------------------------------------------


def idle_by_span(busy: list[list[tuple[float, float]]], t_min: float, t_max: float,
                 spans: list[tuple[float, float, str, int]], root: str | None
                 ) -> dict[str, float]:
    """{span name: idle nanoseconds} over [t_min, t_max]: every instant the
    device ran nothing goes to the innermost of the program's spans open at
    that instant, so the parts add up to the idle time exactly (the mean over
    the device planes of ``busy``, each a merged interval list).

    ``spans``: (start, end, name, host thread) of the program's spans.
    Innermost at an instant: of the spans open on the thread that opened
    ``root`` the one opened last; a span open on ANOTHER thread (the program
    opens those with ``parent=``: the staging pool's, the model store's
    writers') takes the instant only where it lies inside that one, i.e.
    where the root's thread has nothing deeper open.  With no span open on the
    root's thread the instant is ``OUTSIDE_SPANS``; a trace without the root
    treats every thread as the root's."""
    import numpy as np

    root_threads = {line for _, _, name, line in spans if name == root}
    edges = np.unique(np.clip(
        np.array([t_min, t_max] + [t for s, e, _, _ in spans for t in (s, e)],
                 dtype=np.float64), t_min, t_max))
    covered = np.zeros(len(edges))
    for intervals in busy:
        starts = np.array([s for s, _ in intervals], dtype=np.float64)
        ends = np.array([e for _, e in intervals], dtype=np.float64)
        before = np.concatenate([[0.0], np.cumsum(ends - starts)])
        i = np.searchsorted(starts, edges, side="right")
        # busy time before each edge: the whole intervals that start before
        # it, less what the last of them has left after it
        last_end = np.where(i > 0, ends[np.maximum(i - 1, 0)], -np.inf)
        covered += before[i] - np.maximum(last_end - edges, 0.0)
    idle = np.diff(edges) - np.diff(covered) / max(len(busy), 1)

    def latest(open_spans):
        """The span opened last; of two opened together the shorter."""
        return max(open_spans, key=lambda sp: (sp[0], -sp[1]), default=None)

    out: dict[str, float] = {}
    for a, b, gap in zip(edges[:-1], edges[1:], idle):
        mid = (a + b) / 2
        open_ = [sp for sp in spans if sp[0] <= mid < sp[1]]
        inner = latest(
            sp for sp in open_ if not root_threads or sp[3] in root_threads)
        if inner is not None and root_threads:
            inner = latest(
                sp for sp in open_ if sp[3] not in root_threads
                and inner[0] <= sp[0] and sp[1] <= inner[1]) or inner
        name = inner[2] if inner else OUTSIDE_SPANS
        out[name] = out.get(name, 0.0) + float(gap)
    return out


def idle_by_host_event(spans: list[tuple[float, float]], t_min: float, t_max: float,
                       host_events: list[tuple[float, float, str]]) -> dict[str, float]:
    """{host event name: idle seconds} where the program hands no span names
    over: the longest gaps of one device's merged busy ``spans``, each named
    by the most specific host event that spans most of it."""
    edges = [t_min] + [t for span in spans for t in span] + [t_max]
    gaps = sorted(
        ((edges[i + 1] - edges[i], edges[i], edges[i + 1])
         for i in range(0, len(edges), 2) if edges[i + 1] - edges[i] >= MIN_GAP_NS),
        reverse=True,
    )[:GAPS_ATTRIBUTED]
    idle: dict[str, float] = {}
    if host_events:
        import numpy as np

        hs = np.array([h[0] for h in host_events])
        he = np.array([h[1] for h in host_events])
    for length, g0, g1 in gaps:
        name = "(no host event)"
        if host_events:
            cover = np.minimum(he, g1) - np.maximum(hs, g0)
            # the most specific host event that spans most of the gap; else
            # the one that covers the most of it
            most = np.flatnonzero(cover >= 0.5 * length)
            if len(most):
                name = host_events[most[np.argmin((he - hs)[most])]][2]
            elif cover.max() > 0:
                name = host_events[int(np.argmax(cover))][2]
        idle[name[:80]] = idle.get(name[:80], 0.0) + length / 1e9
    return idle


def reduce_trace(path: str, top: int = 10, spans: Iterable[str] | None = None,
                 root: str | None = None) -> dict:
    """{"busy_s", "window_s", "devices", "op_events", "module_events",
    "ops_by_name": [[name, self seconds], ...] of every operation,
    "device_ops": its first ``top``, "scopes": [[scope path, pass, self
    seconds], ...] (``scope_of``), "unscoped_ops": the ``top`` operations of
    ``(no scope)``, "idle_gaps": [[name, seconds], ...]} from one
    ``*.xplane.pb``.  Operations and scopes are summed over the device
    planes, ``busy_s`` is their mean: over one plane each adds up to it.

    ``spans``: the names of the program's spans (host annotations), ``root``
    the one that holds the others.  With them the idle time is shared out by
    ``idle_by_span`` (every name under ``idle_by_span``; ``idle_gaps`` the
    ``top`` largest, the last of them ``OTHER_SPANS`` where more names had a
    share, so that it adds up to ``window_s - busy_s``); without them each of
    the longest gaps is named by one host event (``idle_by_host_event``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    span_names = set(spans or ())
    device_planes = []
    host_events: list[tuple[float, float, str]] = []
    span_events: list[tuple[float, float, str, int]] = []
    t_min, t_max = float("inf"), float("-inf")
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            ops = []
            for name in OP_LINES:
                if name in lines:
                    ops = [
                        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in lines[name].events
                    ]
                    if ops:
                        break
            modules = (
                len(list(lines["XLA Modules"].events))
                if "XLA Modules" in lines else 0
            )
            device_planes.append((plane.name, ops, modules))
            for s, e, _ in ops:
                t_min, t_max = min(t_min, s), max(t_max, e)
        elif plane.name == HOST_PLANE:
            for thread, line in enumerate(plane.lines):
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    t_min, t_max = min(t_min, s), max(t_max, e)
                    if ev.duration_ns > 0:
                        host_events.append((s, e, ev.name))
                        if ev.name in span_names:
                            span_events.append((s, e, ev.name, thread))
    active = [p for p in device_planes if p[1]]
    if not active:
        return {
            "busy_s": 0.0,
            "window_s": max(t_max - t_min, 0.0) / 1e9 if host_events else 0.0,
            "devices": 0, "op_events": 0, "module_events": 0,
            "ops_by_name": [], "device_ops": [], "scopes": [], "unscoped_ops": [],
            "idle_gaps": [],
        }
    busy_spans = [merged((s, e) for s, e, _ in ops) for _, ops, _ in active]
    busy = sum(float(sum(e - s for s, e in plane)) for plane in busy_spans)
    scope_by_event = {
        name: scope_of(tf_op) for name, tf_op in op_names_by_event(path).items()
    }
    by_op: dict[str, float] = {}
    by_scope: dict[tuple[str, str], float] = {}
    unscoped: dict[str, float] = {}
    for _, ops, _ in active:
        for name, self_ns in self_times(ops):
            key = short_name(name)
            by_op[key] = by_op.get(key, 0.0) + self_ns
            scope = scope_by_event.get(name, (NO_SCOPE, "forward"))
            by_scope[scope] = by_scope.get(scope, 0.0) + self_ns
            if scope[0] == NO_SCOPE:
                unscoped[key] = unscoped.get(key, 0.0) + self_ns
    ops_by_name = [
        [k, v / 1e9] for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])
    ]
    out = {
        "busy_s": busy / len(active) / 1e9,
        "window_s": (t_max - t_min) / 1e9,
        "devices": len(active),
        "op_events": sum(len(ops) for _, ops, _ in active),
        "module_events": sum(m for _, _, m in active),
        "ops_by_name": ops_by_name,
        "device_ops": ops_by_name[:top],
        "scopes": [
            [path, which, v / 1e9]
            for (path, which), v in sorted(by_scope.items(), key=lambda kv: -kv[1])
        ],
        "unscoped_ops": [
            [k, v / 1e9]
            for k, v in sorted(unscoped.items(), key=lambda kv: -kv[1])[:top]
        ],
    }
    if span_names:
        shares = idle_by_span(busy_spans, t_min, t_max, span_events, root)
        ranked = sorted(
            ([k[:80], v / 1e9] for k, v in shares.items()), key=lambda kv: -kv[1])
        out["idle_by_span"] = ranked
        if len(ranked) > top:
            rest = sum(v for _, v in ranked[top - 1:])
            ranked = ranked[:top - 1] + [[OTHER_SPANS, rest]]
        out["idle_gaps"] = ranked
    else:
        # the first active device's longest gaps
        idle = idle_by_host_event(busy_spans[0], t_min, t_max, host_events)
        out["idle_gaps"] = [
            [k, v] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])[:top]
        ]
    return out
