"""The harness on a broken program: everything of a run but the look for a
chip (``run.execute`` on the CPU at a tiny size), with the timed path broken
underneath by ``shim/sitecustomize.py`` in the children — and ``correct``
comes out false, by the comparison that is there to catch it."""

from pathlib import Path

import pytest

from benchmark import run as harness
from benchmark.tests.tiny import add_als_serve_cell, tiny_root

SHIM = Path(__file__).parent / "shim"


@pytest.fixture()
def broken(monkeypatch):
    def set_break(what):
        monkeypatch.setenv("PYTHONPATH", str(SHIM))
        monkeypatch.setenv("BENCH_TEST_BREAK", what)

    return set_break


def test_retrain_with_a_stale_item_table_is_not_correct(tmp_path, broken):
    broken("train")
    manifest, root = tiny_root(tmp_path)
    res, compared = harness.execute(
        manifest, "als-ml20m.retrain", 7, 0.5, False, "cpu",
        tmp_path / "work", root,
    )
    by = {c.name: c for c in compared}
    assert res["correct"] is False
    gap = by["last_halfstep_gap_median[bench-b]"]  # the window's one retrain
    assert not gap.ok and gap.value == pytest.approx(0.01, rel=0.05)
    # what is not broken still holds
    assert by["instance_completed[bench-b]"].ok
    assert by["factors_finite[bench-b]"].ok


def test_retrain_that_leaves_iterations_out_is_not_correct(tmp_path, broken):
    """One iteration where the configuration says twenty: the item side
    still solves its normal equations (it is updated last), so only the user
    side's distance from its fixed point tells."""
    broken("iterations")
    manifest, root = tiny_root(tmp_path)
    res, compared = harness.execute(
        manifest, "als-ml20m.retrain", 7, 0.5, False, "cpu",
        tmp_path / "work", root,
    )
    by = {c.name: c for c in compared}
    assert res["correct"] is False
    assert by["last_halfstep_gap_median[bench-b]"].ok
    gap = by["user_fixedpoint_gap_median[bench-b]"]
    assert not gap.ok and gap.value > 3 * gap.limit


def test_serving_altered_scores_is_not_correct(tmp_path, broken):
    broken("serve")
    manifest, root = tiny_root(tmp_path)
    add_als_serve_cell(manifest, root)
    res, compared = harness.execute(
        manifest, "als-ml20m.serve-steady", 7, 1.0, False, "cpu",
        tmp_path / "work", root,
    )
    by = {c.name: c for c in compared}
    assert res["correct"] is False
    assert not by["served_score_gap_max"].ok
    assert by["served_score_gap_max"].value == pytest.approx(1e-3, rel=0.05)
    assert by["answers_without_num_finite_items"].ok
    assert res["failed"] == 0  # every answer was a 200: only the check sees it
