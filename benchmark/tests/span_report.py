#!/usr/bin/env python3
"""A builder's reading of ONE traced retrain, beside the harness's own
reduction (``trace_reduce.py`` is the yardstick and is not touched):

* device-idle seconds by the program's leaf span — every idle interval of the
  device inside the retrain (the ``workflow.run_train`` annotation) goes to the
  innermost program span open on the host at that instant, so the parts add up
  to the idle time exactly;
* device-busy seconds by ``jax.named_scope`` — each ``XLA Ops`` event's self
  time under the ``als.*`` components of its ``tf_op`` stat (the HLO
  ``op_name`` metadata), which ``jax.profiler.ProfileData`` does not expose:
  the stat sits on the event's METADATA, read here from the file's own bytes.

On the chip, one traced run of a cell through the harness itself; the trace
is reduced where the harness reduces it, before the run's directory goes:

    chiprun -- python3 benchmark/tests/span_report.py run als-ml20m.retrain SEED

writes ``chiprun_out/span_report/<cell>.json`` (both tables, the result line,
the trace's size).  On a trace file that is already there:

    python3 benchmark/tests/span_report.py file PATH.xplane.pb
"""

from __future__ import annotations

import json
import os
import re
import sys
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import trace_reduce  # noqa: E402

#: host annotations that are the program's spans (obs/tracing.py)
PROGRAM_SPAN = re.compile(
    r"^(workflow|train|eventstore|datasource|prepare|als)\.[\w.]+$"
)
ROOT = "workflow.run_train"
#: spans that only hold other spans: idle time left under one of these is
#: time no leaf names
PARENTS = re.compile(
    r"^(workflow\.run_train|train\.(datasource|preparator|algorithm)\..*)$"
)


# -- the file's own bytes: event metadata -> tf_op ---------------------------


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf: bytes):
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
    space = Path(path).read_bytes()
    for field, _, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, _, v in _fields(plane):
            if f == 2:
                name = v.decode("utf-8", "replace")
            elif f == 4:  # map entry: key = 1, value = 2
                events += [mv for mf, _, mv in _fields(v) if mf == 2]
            elif f == 5:
                for mf, _, mv in _fields(v):
                    if mf == 2:
                        meta = dict((a, c) for a, _, c in _fields(mv))
                        stat_names[meta.get(1, 0)] = meta.get(
                            2, b"").decode("utf-8", "replace")
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        for ev in events:
            ev_name, tf_op = "", None
            for f, _, v in _fields(ev):
                if f == 2:
                    ev_name = v.decode("utf-8", "replace")
                elif f == 5:
                    stat = dict((a, c) for a, _, c in _fields(v))
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    if 5 in stat:
                        tf_op = stat[5].decode("utf-8", "replace")
                    elif 7 in stat:  # a reference to a stat metadata's name
                        tf_op = stat_names.get(stat[7], "")
            if tf_op is not None:
                out[ev_name] = tf_op
    return out


def scope_of(tf_op: str | None) -> str:
    """``jit(steps)/while/body/als.user_half/als.solve/mul`` ->
    ``als.user_half/als.solve``; nothing of the kind -> ``(no scope)``."""
    parts = [p for p in (tf_op or "").split("/") if p.startswith("als.")]
    return "/".join(parts) or "(no scope)"


# -- the two tables ----------------------------------------------------------


def _planes(path: str):
    from jax.profiler import ProfileData

    device, host = [], []
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                ops = [
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in lines["XLA Ops"].events
                ]
                if ops:
                    device.append(ops)
        elif plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0 and PROGRAM_SPAN.match(ev.name):
                        host.append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        )
    return device, host


def busy_by_scope(path: str, top: int = 6) -> dict:
    device, _ = _planes(path)
    tf_ops = op_names_by_event(path)
    by_scope: dict[str, float] = {}
    ops_of: dict[str, dict[str, float]] = {}
    busy = 0.0
    for ops in device:
        busy += trace_reduce.union_length((s, e) for s, e, _ in ops)
        for name, self_ns in trace_reduce.self_times(ops):
            scope = scope_of(tf_ops.get(name))
            by_scope[scope] = by_scope.get(scope, 0.0) + self_ns
            short = trace_reduce.short_name(name)
            if "tpu_custom_call" in name:
                short += " (Pallas kernel)"
            inside = ops_of.setdefault(scope, {})
            inside[short] = inside.get(short, 0.0) + self_ns
    n = max(len(device), 1)
    scoped = sum(v for k, v in by_scope.items() if k != "(no scope)")

    def table(d, limit=None):
        rows = sorted(d.items(), key=lambda kv: -kv[1])[:limit]
        return [[k, v / n / 1e9] for k, v in rows]

    return {
        "busy_s": busy / n / 1e9,
        "self_time_s": sum(by_scope.values()) / n / 1e9,
        "under_als_scopes_s": scoped / n / 1e9,
        "under_als_scopes_share": scoped / max(sum(by_scope.values()), 1.0),
        "events_with_tf_op": len(tf_ops),
        "by_scope": table(by_scope),
        # the longest operations inside each scope, by self time
        "ops_by_scope": {k: table(v, top) for k, v in ops_of.items()},
    }


def idle_by_span(path: str) -> dict:
    device, host = _planes(path)
    roots = [h for h in host if h[2] == ROOT]
    if not device or not roots:
        return {"idle_s": 0.0, "by_span": [], "spans_on_host_plane": sorted(
            {h[2] for h in host})}
    w0, w1 = roots[0][0], roots[0][1]
    busy = trace_reduce.merged((s, e) for s, e, _ in device[0])
    edges = [w0] + [t for span in busy for t in span] + [w1]
    idle = np.array(
        [(max(edges[i], w0), min(edges[i + 1], w1))
         for i in range(0, len(edges), 2)
         if min(edges[i + 1], w1) > max(edges[i], w0)],
        dtype=np.float64,
    ).reshape(-1, 2)
    total = float((idle[:, 1] - idle[:, 0]).sum())
    by_span: dict[str, float] = {}
    # innermost first: a span takes the idle time inside it that no shorter
    # span has taken
    for s, e, name in sorted(host, key=lambda h: h[1] - h[0]):
        lo = np.maximum(idle[:, 0], s)
        hi = np.minimum(idle[:, 1], e)
        hit = hi > lo
        if not hit.any():
            continue
        by_span[name] = by_span.get(name, 0.0) + float((hi - lo)[hit].sum())
        left = idle[hit & (idle[:, 0] < s)]
        right = idle[hit & (idle[:, 1] > e)]
        idle = np.concatenate([
            idle[~hit],
            np.column_stack([left[:, 0], np.full(len(left), s)]),
            np.column_stack([np.full(len(right), e), right[:, 1]]),
        ])
    leaf = sum(v for k, v in by_span.items() if not PARENTS.match(k))
    return {
        "retrain_window_s": (w1 - w0) / 1e9,
        "idle_s": total / 1e9,
        "under_leaf_spans_s": leaf / 1e9,
        "under_leaf_spans_share": leaf / max(total, 1.0),
        "unattributed_s": float((idle[:, 1] - idle[:, 0]).sum()) / 1e9,
        "by_span": [
            [k + (" (parent: no leaf open)" if PARENTS.match(k) else ""), v / 1e9]
            for k, v in sorted(by_span.items(), key=lambda kv: -kv[1])
        ],
        "spans_on_host_plane": sorted({h[2] for h in host}),
    }


def report(path: str) -> dict:
    return {
        "trace_file": path,
        "trace_bytes": os.path.getsize(path),
        "idle_by_span": idle_by_span(path),
        "busy_by_scope": busy_by_scope(path),
    }


def run_cell(cell: str, seed: int, seconds: float) -> int:
    """One traced run through the harness's own ``execute``; the report is
    made at the moment the harness reduces the trace."""
    from benchmark import run as harness

    out = REPO / "chiprun_out" / "span_report"
    out.mkdir(parents=True, exist_ok=True)
    found: dict = {}
    reduce_trace = trace_reduce.reduce_trace

    def reduce_and_report(path, *args, **kwargs):
        try:
            found.update(report(path))
        except Exception as e:  # the harness's run goes on
            import traceback

            traceback.print_exc()
            found["error"] = f"{type(e).__name__}: {e}"
        return reduce_trace(path, *args, **kwargs)

    trace_reduce.reduce_trace = reduce_and_report
    try:
        manifest = harness.load_json(REPO / "BENCHMARK.json")
        result, _ = harness.execute(
            manifest, cell, seed, seconds, True, harness.PLATFORM,
            harness.BENCH / ".work" / cell,
        )
    finally:
        trace_reduce.reduce_trace = reduce_trace
    found["result"] = result
    (out / f"{cell}.json").write_text(json.dumps(found, indent=1))
    print(json.dumps({k: v for k, v in found.items() if k != "result"}, indent=1))
    print(json.dumps(result))
    return 0 if "error" not in found else 1


def main(argv: list[str]) -> int:
    if argv[:1] == ["file"]:
        print(json.dumps(report(argv[1]), indent=1))
        return 0
    if argv[:1] == ["run"]:
        manifest = json.loads((REPO / "BENCHMARK.json").read_text())
        seconds = float(argv[3]) if len(argv) > 3 else manifest["run_seconds"]
        return run_cell(argv[1], int(argv[2]), seconds)
    print(__doc__)
    return 2


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
