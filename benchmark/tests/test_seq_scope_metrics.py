"""The four per-layer metrics that read the scopes ISSUE 36 gave the rest of
a sequence retrain's device time (``seq.step``, ``seq.stream``,
``seq.accumulate``, ``seq.init``): each file names the reader and its scope,
the manifest lists the three sequence cells, the reader picks the scope's
rows from a canned ``scopes`` list and reads nothing where a trace has none
(the parent's program: its line leaves the metric out)."""

import pytest

from benchmark import run as harness
from benchmark.readers import device_scope_seconds
from benchmark.tests.test_span_metrics import SCOPES, SEQ, spec

NEW_SCOPES = {
    "seq_step_device_s": "seq.step", "seq_stream_device_s": "seq.stream",
    "seq_accumulate_device_s": "seq.accumulate", "seq_init_device_s": "seq.init",
}
#: rows as the three programs write them: the stream in all three passes
ROWS = SCOPES + [
    ["seq.step", "forward", 0.12], ["seq.stream", "forward", 0.1],
    ["seq.stream", "recompute", 0.02], ["seq.stream", "backward", 0.2],
    ["seq.accumulate", "forward", 0.05], ["seq.init", "forward", 0.03],
]
EXPECTED = {
    "seq_step_device_s": 0.12, "seq_stream_device_s": 0.32,
    "seq_accumulate_device_s": 0.05, "seq_init_device_s": 0.03,
}


@pytest.mark.parametrize("name", sorted(NEW_SCOPES))
def test_new_scope_metric_resolves_and_reads_its_scope_alone(name):
    manifest = harness.load_json(harness.REPO / "BENCHMARK.json")
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert entry == {
        "name": name, "unit": "s", "better": "lower", "source": "device_trace",
        "layer": "Kernel", "moves": "retrain_s", "workloads": SEQ}
    assert spec(name)["reader"] == "device_scope_seconds"
    args = spec(name)["args"]
    assert args == {"scopes": [NEW_SCOPES[name]]}
    # the parent's traces hold none of the four: nothing reported, no error
    assert device_scope_seconds.read({}, args) is None
    assert device_scope_seconds.read({"trace": {"scopes": SCOPES}}, args) is None
    assert device_scope_seconds.read(
        {"trace": {"scopes": ROWS}}, args) == pytest.approx(EXPECTED[name])


def test_the_new_scopes_take_nothing_from_the_accepted_metrics():
    """No row of the new scopes holds an accepted metric's component, and
    ``(no scope)`` keeps its file: it is the guard now."""
    from benchmark.tests.test_span_metrics import EXPECTED as BEFORE, SCOPE_METRICS

    for name, want in BEFORE.items():
        args = SCOPE_METRICS[name][0]
        assert device_scope_seconds.read(
            {"trace": {"scopes": ROWS}}, args) == pytest.approx(
                want + (0.02 if name == "seq_recompute_device_s" else 0.0))
    assert len(harness.load_json(harness.REPO / "BENCHMARK.json")["per_layer"]) == 50
