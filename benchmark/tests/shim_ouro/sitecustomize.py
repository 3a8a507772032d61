"""Test-only: breaks the Ouro block's timed path underneath the harness, in
the children of ``test_ouro_cell.py`` (which put this directory on their
PYTHONPATH and say what to break in ``BENCH_TEST_BREAK``).  Without that
variable it does nothing.

    ouro_bf16_carry     the state handed from pass to pass rounded to
                        bfloat16: the precision below the float32 the
                        configuration states
    ouro_gate_detached  no gradient through the exit distribution
    ouro_bf16_logits    the head's logits rounded to bfloat16: what a bfloat16
                        accumulation type gives
"""

import os

if os.environ.get("BENCH_TEST_BREAK") == "ouro_bf16_carry":
    from benchmark.tests.control_ouro_chip import rounded_to_bfloat16
    from predictionio_tpu.ops import seqmodel

    seqmodel.loop_pass = rounded_to_bfloat16(seqmodel.loop_pass)

if os.environ.get("BENCH_TEST_BREAK") == "ouro_gate_detached":
    from benchmark.tests.control_ouro_chip import detached
    from predictionio_tpu.ops import seqmodel

    seqmodel.exit_log_probs = detached(seqmodel.exit_log_probs)

if os.environ.get("BENCH_TEST_BREAK") == "ouro_bf16_logits":
    from benchmark.tests.control_ouro_chip import to_bfloat16
    from predictionio_tpu.ops import seqmodel

    _scaled = seqmodel._scaled
    # the tiny cell's vocabulary rows: the logits are the one array that wide
    seqmodel._scaled = lambda x, m: _scaled(
        to_bfloat16(x) if x.shape[-1] == 128 else x, m)
