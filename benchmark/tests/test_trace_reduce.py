"""The trace reduction on a recorded v5e trace (``record_trace.py``: five
executions of one jitted step, 14 KB) and on hand-made intervals."""

from pathlib import Path

import pytest

from benchmark import trace_reduce

FIXTURE = Path(__file__).parent / "data" / "tiny_tpu.xplane.pb"


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


def test_find_xplane(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace_reduce.find_xplane(str(tmp_path))
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "a.xplane.pb").write_bytes(FIXTURE.read_bytes())
    assert trace_reduce.find_xplane(str(tmp_path)).endswith("a.xplane.pb")
