"""From a profiler trace (``*.xplane.pb``) to device busy time, idle gaps and
the longest operations.  The one reduction every cell and every later PR uses.

``jax.profiler.ProfileData`` reads the file with nothing but jaxlib: no
backend is initialized, so the harness process can reduce a trace that the
child holding the chip wrote.

What a TPU trace looks like (one v5e, jax 0.9.0; ``tests/data`` keeps one):
plane ``/device:TPU:<n>`` per chip with lines ``XLA Modules`` (one event per
program execution), ``XLA Ops`` (one per operation inside it), ``Async XLA
Ops`` (DMA that overlaps the ops) and ``TC Overlay``; host threads are lines
of plane ``/host:CPU``.  Times are nanoseconds on one clock.

Operations nest on the op line (a ``while`` spans its body's operations): the
longest operations are ranked by SELF time, so they add up to busy.

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


def reduce_trace(path: str, top: int = 10) -> dict:
    """{"busy_s", "window_s", "devices", "op_events", "module_events",
    "device_ops": [[name, seconds], ...], "idle_gaps": [[host activity,
    seconds], ...]} from one ``*.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_planes = []
    host_events: list[tuple[float, float, str]] = []
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
            for line in plane.lines:
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    t_min, t_max = min(t_min, s), max(t_max, e)
                    if ev.duration_ns > 0:
                        host_events.append((s, e, ev.name))
    active = [p for p in device_planes if p[1]]
    if not active:
        return {
            "busy_s": 0.0,
            "window_s": max(t_max - t_min, 0.0) / 1e9 if host_events else 0.0,
            "devices": 0, "op_events": 0, "module_events": 0,
            "device_ops": [], "idle_gaps": [],
        }
    busy = sum(union_length((s, e) for s, e, _ in ops) for _, ops, _ in active)
    by_op: dict[str, float] = {}
    for _, ops, _ in active:
        for name, self_ns in self_times(ops):
            key = short_name(name)
            by_op[key] = by_op.get(key, 0.0) + self_ns
    device_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]

    # the longest gaps of the first active device, each named by the host
    # event that covers most of it (the host's own names; the program
    # writes no annotations yet), summed by that name
    spans = merged((s, e) for s, e, _ in active[0][1])
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
    return {
        "busy_s": busy / len(active) / 1e9,
        "window_s": (t_max - t_min) / 1e9,
        "devices": len(active),
        "op_events": sum(len(ops) for _, ops, _ in active),
        "module_events": sum(m for _, _, m in active),
        "device_ops": [[k, v / 1e9] for k, v in device_ops],
        "idle_gaps": [
            [k, v] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])[:top]
        ],
    }
