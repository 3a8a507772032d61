"""Test-only: breaks the SmallThinker block's timed path underneath the
harness, in the children of ``test_smallthinker_cell.py`` (which put this
directory on their PYTHONPATH and say what to break in ``BENCH_TEST_BREAK``).
Without that variable it does nothing.

    st_no_window     the sliding layers attend over the whole segment: the
                     window is left out (rotary positions stay)
    st_dropped_pairs an expert keeps 1.25 x the mean load of a row and drops
                     the (token, expert) pairs past it
"""

import os

if os.environ.get("BENCH_TEST_BREAK") == "st_no_window":
    from predictionio_tpu.ops import seqmodel

    _attend = seqmodel._attend

    def _no_window(cfg, q, k, v, seg, window=None):
        return _attend(cfg, q, k, v, seg, None if window is None else 1 << 30)

    seqmodel._attend = _no_window

if os.environ.get("BENCH_TEST_BREAK") == "st_dropped_pairs":
    from benchmark.tests.control_smallthinker_chip import plan_with_a_capacity
    from predictionio_tpu.ops import moe

    moe.make_plan = plan_with_a_capacity(1.25, 16)
