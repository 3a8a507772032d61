"""The trace reduction on a recorded v5e trace (``record_trace.py``: five
executions of one jitted step, 14 KB) and on hand-made intervals."""

import json
from pathlib import Path

import pytest

from benchmark import run as harness
from benchmark import trace_reduce
from benchmark.readers import device_op_prefix, device_scope_seconds

FIXTURE = Path(__file__).parent / "data" / "tiny_tpu.xplane.pb"
#: what the reducer of PR 34 (commit aaf69d8) returns for the fixture, every
#: digit: ``python3 -c "... json.dump(reduce_trace(FIXTURE))"`` at that commit
PARENT = json.loads((FIXTURE.parent / "tiny_tpu.reduced_pr34.json").read_text())
#: host events of the fixture that nest like a program's spans: the python
#: thread's call of the jitted step, and the runtime's execute under it on
#: another thread
ROOT, INNER = "PjitFunction(step)", "PJRT_LoadedExecutable_Execute"


def test_union_length():
    assert trace_reduce.union_length([]) == 0.0
    assert trace_reduce.union_length([(0, 10), (5, 12), (20, 21)]) == 13
    assert trace_reduce.union_length([(5, 6), (0, 10)]) == 10
    assert trace_reduce.merged([(5, 6), (0, 10), (10, 11), (30, 31)]) == [
        (0, 11), (30, 31)]


def test_self_times_subtract_nested_events():
    ops = [(0, 100, "while"), (10, 40, "body.1"), (40, 90, "body.2"),
           (50, 60, "inner"), (200, 210, "after")]
    assert dict(trace_reduce.self_times(ops)) == {
        "while": 20, "body.1": 30, "body.2": 40, "inner": 10, "after": 10}


def test_short_name():
    assert trace_reduce.short_name(
        "%fusion.3 = f32[8]{0} fusion(f32[8] %x), kind=kLoop") == "fusion.3"
    assert trace_reduce.short_name("jit_step(123)") == "jit_step(123)"


def test_recorded_tpu_trace():
    r = trace_reduce.reduce_trace(str(FIXTURE))
    assert r["devices"] == 1
    # five executions, three ops each (copy-start, copy-done, fusion)
    assert r["module_events"] == 5 and r["op_events"] == 15
    # read off the trace by hand: the five fusions take 13-19 us each
    assert r["busy_s"] == pytest.approx(90.4e-6, rel=0.01)
    assert r["busy_s"] < r["window_s"] < 0.1
    names = [n for n, _ in r["device_ops"]]
    assert names[0] == "fusion" and set(names) == {"fusion", "copy-done", "copy-start"}
    assert sum(s for _, s in r["device_ops"]) == pytest.approx(r["busy_s"], rel=0.01)
    # the chip waits for the host between executions
    assert r["idle_gaps"] and r["idle_gaps"][0][0].startswith("PjitFunction")
    assert sum(s for _, s in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] + 1e-9


def test_the_parents_keys_read_the_same_to_the_last_digit():
    r = trace_reduce.reduce_trace(str(FIXTURE))
    assert {k: r[k] for k in PARENT} == PARENT
    assert set(PARENT) == {"busy_s", "window_s", "devices", "op_events",
                           "module_events", "device_ops", "idle_gaps"}
    # with span names handed over everything but the idle gaps stays
    named = trace_reduce.reduce_trace(str(FIXTURE), spans=[ROOT, INNER], root=ROOT)
    assert {k: named[k] for k in PARENT if k != "idle_gaps"} == {
        k: v for k, v in PARENT.items() if k != "idle_gaps"}


def test_every_operation_and_every_scope_adds_up_to_busy():
    r = trace_reduce.reduce_trace(str(FIXTURE))
    assert r["device_ops"] == r["ops_by_name"][:10]
    assert sum(s for _, s in r["ops_by_name"]) == pytest.approx(r["busy_s"], rel=1e-9)
    evidence = {"trace": r}
    firsts = {path.split("/")[0] for path, _, _ in r["scopes"]}
    by_first = [
        device_scope_seconds.read(evidence, {"scopes": [first], "first": True})
        for first in firsts
    ]
    assert sum(by_first) == pytest.approx(r["busy_s"], rel=1e-9)
    # the fixture's program wrote no scope: its one fusion is ``jit(step)/dot_general``
    assert r["scopes"] == [[trace_reduce.NO_SCOPE, "forward", pytest.approx(r["busy_s"])]]
    assert r["unscoped_ops"] == r["ops_by_name"]
    assert device_scope_seconds.read(evidence, {"scopes": ["seq.attn"]}) is None
    assert device_scope_seconds.read(evidence, {"pass": "recompute"}) is None
    assert device_scope_seconds.read(evidence, {}) == pytest.approx(r["busy_s"])
    assert device_op_prefix.read(evidence, {"prefix": "copy-"}) == pytest.approx(
        sum(s for n, s in r["ops_by_name"] if n.startswith("copy-")))


def test_tf_op_is_read_from_the_event_metadata():
    names = trace_reduce.op_names_by_event(str(FIXTURE))
    assert list(names.values()) == ["jit(step)/dot_general:"]
    assert next(iter(names)).startswith("%fusion = ")


@pytest.mark.parametrize("op_name, scope", [
    ("jit(steps)/while/body/als.user_half/als.solve/mul",
     ("als.user_half/als.solve", "forward")),
    ("jit(steps)/als.weights/stack", ("als.weights", "forward")),
    ("jit(steps)/while", ("(no scope)", "forward")),
    (None, ("(no scope)", "forward")),
    # the trace's stat is ``op_name:op_type``
    ("jit(step)/dot_general:", ("(no scope)", "forward")),
    ("jit(f)/jvp(seq.loss)/mul", ("seq.loss", "forward")),
    ("jit(<unknown>)/jvp(seq.gdn)/checkpoint/gdn.intra/jit(_where)/select_n",
     ("seq.gdn/gdn.intra", "forward")),
    ("jit(<unknown>)/transpose(jvp(jvp()))/checkpoint/seq.mlp/dot_general",
     ("seq.mlp", "backward")),
    ("jit(f)/transpose(jvp(seq.attn))/attn.rope/mul", ("seq.attn/attn.rope", "backward")),
    ("jit(<unknown>)/transpose(jvp(jvp()))/checkpoint/rematted_computation/seq.mlp/dot_general",
     ("seq.mlp", "recompute")),
    # a checkpoint inside the scope, recomputed in the backward
    ("jit(<unknown>)/transpose(jvp(jvp()))/checkpoint/seq.gdn/checkpoint/"
     "rematted_computation/gdn.conv/jit(silu)/logistic",
     ("seq.gdn/gdn.conv", "recompute")),
    ("jit(<unknown>)/transpose(jvp(jvp()))/checkpoint/rematted_computation",
     ("(no scope)", "recompute")),
    # a Pallas kernel's name, and the library's under its wrappers
    ("jit(<unknown>)/transpose(jvp(jvp()))/checkpoint/seq.gdn/checkpoint/gdn.chunk/gdn_chunk_bwd",
     ("seq.gdn/gdn.chunk", "backward")),
    ("jit(<unknown>)/jvp(seq.attn)/attn.causal/vmap(vmap(jit(_splash_attention)))/"
     "splash_mqa_fwd_segmented_residuals/splash_mqa_fwd_segmented_residuals",
     ("seq.attn/attn.causal", "forward")),
    ("jit(<unknown>)/transpose(jvp(jvp()))/checkpoint/seq.attn/attn.window/"
     "vmap(vmap(jit(_splash_attention)))/hsd,hsd->hs", ("seq.attn/attn.window", "backward")),
    # an inlined jit repeats its caller's prefix: each component once
    ("jit(<unknown>)/jvp(seq.moe)/moe.route/jit(searchsorted)/jit(<unknown>)/"
     "jvp(seq.moe)/moe.route/jit(searchsorted)/vmap()/closed_call/while/body/closed_call/add",
     ("seq.moe/moe.route", "forward")),
    # names the compiler joined: the first that has a scope
    ("jit(<unknown>)/jvp()/broadcast_in_dim;jit(<unknown>)/jvp(seq.ssm)/ssm.intra/reshape",
     ("seq.ssm/ssm.intra", "forward")),
    ("jit(<unknown>)/jit(_normal)/jit(_normal_real)/jit(_uniform)/add", ("(no scope)", "forward")),
    ("jit(wave)/ncf.score/dot_general", ("ncf.score", "forward")),
    # an argument's name is no scope (the copy of a parameter into a layout)
    ("state['params']['layer0.q']:", ("(no scope)", "forward")),
    ("jit(f)/jvp(seq.attn/attn.rope)/mul", ("seq.attn/attn.rope", "forward")),
])
def test_scope_of(op_name, scope):
    assert trace_reduce.scope_of(op_name) == scope


def test_idle_with_span_names_adds_up_and_without_them_is_the_parents():
    r = trace_reduce.reduce_trace(str(FIXTURE), spans=[ROOT, INNER], root=ROOT)
    idle = dict(r["idle_gaps"])
    assert r["idle_gaps"] == r["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9)
    # the chip waits for the host between the five executions, outside any
    # call of the step; inside a call the execute on the runtime's thread is
    # deeper than the python thread's call and takes its part
    assert set(idle) == {trace_reduce.OUTSIDE_SPANS, ROOT, INNER}
    assert idle[trace_reduce.OUTSIDE_SPANS] > idle[INNER] > idle[ROOT] > 0
    # more names than the list holds: the last entry carries the rest
    short = trace_reduce.reduce_trace(str(FIXTURE), top=2, spans=[ROOT, INNER], root=ROOT)
    assert [n for n, _ in short["idle_gaps"]] == [
        trace_reduce.OUTSIDE_SPANS, trace_reduce.OTHER_SPANS]
    assert sum(s for _, s in short["idle_gaps"]) == pytest.approx(sum(idle.values()))
    assert "idle_by_span" not in trace_reduce.reduce_trace(str(FIXTURE))


def test_idle_goes_to_the_innermost_span_and_adds_up():
    ms = 1_000_000
    busy = [[(40 * ms, 50 * ms), (60 * ms, 90 * ms)]]
    main, pool_a, pool_b, stray = 0, 1, 2, 3
    spans = [
        (0, 100 * ms, "workflow.run_train", main),
        (5 * ms, 30 * ms, "train.datasource.read", main),
        (5 * ms, 20 * ms, "eventstore.scan", main),
        (20 * ms, 28 * ms, "eventstore.decode", main),
        (30 * ms, 38 * ms, "als.stage", main),
        # two sides at once on the pool's threads: the one opened last
        (30 * ms, 38 * ms, "als.stage.plan", pool_a),
        (31 * ms, 36 * ms, "als.stage.permute", pool_b),
        (38 * ms, 95 * ms, "als.device_loop", main),
        # another thread's span that is NOT inside the root thread's
        # innermost (it straddles two of them): it takes nothing
        (25 * ms, 45 * ms, "stray", stray),
    ]
    by = trace_reduce.idle_by_span(
        busy, -10 * ms, 110 * ms, spans, "workflow.run_train")
    by = {k: v / 1e9 for k, v in by.items()}
    assert by[trace_reduce.OUTSIDE_SPANS] == pytest.approx(0.020)
    assert by["eventstore.scan"] == pytest.approx(0.015)
    assert by["eventstore.decode"] == pytest.approx(0.008)
    assert by["train.datasource.read"] == pytest.approx(0.002)
    assert by["als.stage.plan"] == pytest.approx(0.001 + 0.002)
    assert by["als.stage.permute"] == pytest.approx(0.005)
    assert "als.stage" not in by and "stray" not in by
    assert by["als.device_loop"] == pytest.approx(0.002 + 0.010 + 0.005)
    assert by["workflow.run_train"] == pytest.approx(0.005 + 0.005)
    assert sum(by.values()) == pytest.approx(0.120 - 0.040)
    # two devices: each instant's idle share is the planes' mean
    two = trace_reduce.idle_by_span(
        busy + [[(0, 100 * ms)]], 0, 100 * ms, spans[:1], "workflow.run_train")
    assert two == {"workflow.run_train": pytest.approx((60 * ms + 0) / 2)}
    # a trace without the root: every thread is the root's
    flat = trace_reduce.idle_by_span(busy, 0, 100 * ms, spans[1:], None)
    assert flat["als.stage.permute"] == pytest.approx(5 * ms)
    assert flat["stray"] > 0


def test_find_xplane(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace_reduce.find_xplane(str(tmp_path))
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "a.xplane.pb").write_bytes(FIXTURE.read_bytes())
    assert trace_reduce.find_xplane(str(tmp_path)).endswith("a.xplane.pb")
