"""Test-only: breaks the Falcon-H1 block's timed path underneath the harness,
in the children of ``test_h1_cell.py`` (which put this directory on their
PYTHONPATH and say what to break in ``BENCH_TEST_BREAK``).  Without that
variable it does nothing.

    h1_no_reset    the layers see one segment a row: no reset of the state
                   space's state, of the convolution, of attention or of the
                   positions at a segment's start, so neighbours in a packed
                   row leak (the loss still counts the real positions)
    h1_bf16_state  the state space's carried state rounded to bfloat16 after
                   every chunk: the precision below the float32 the
                   configuration states
"""

import os

if os.environ.get("BENCH_TEST_BREAK") == "h1_no_reset":
    from predictionio_tpu.ops import seqmodel

    _trunk = seqmodel.trunk

    def _one_segment(cfg, params, x, seg, remat=False):
        return _trunk(cfg, params, x, seg * 0, remat)

    seqmodel.trunk = _one_segment

if os.environ.get("BENCH_TEST_BREAK") == "h1_bf16_state":
    from benchmark.tests.control_h1_chip import scan_with_a_bfloat16_state
    from predictionio_tpu.ops import ssd

    ssd.chunk_scan = scan_with_a_bfloat16_state
