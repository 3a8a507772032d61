"""Test-only: breaks the Nemotron-H stack's timed path underneath the
harness, in the children of ``test_nemotron_cell.py`` (which put this
directory on their PYTHONPATH and say what to break in ``BENCH_TEST_BREAK``).
Without that variable it does nothing.

    nemotron_no_shared    the shared expert is left out of every E layer
    nemotron_softmax      the chosen experts' weights are a softmax over their
                          logits (sum 1) in place of the normalised sigmoid
                          scores times 2.5
    nemotron_bf16_tgmm    the experts' weight gradients (``tgmm``'s results)
                          rounded to bfloat16
"""

import os

if os.environ.get("BENCH_TEST_BREAK") == "nemotron_no_shared":
    import jax.numpy as jnp

    from predictionio_tpu.ops import seqmodel

    seqmodel.shared_expert = lambda p, h: jnp.zeros_like(h)

if os.environ.get("BENCH_TEST_BREAK") == "nemotron_softmax":
    import jax

    from predictionio_tpu.ops import moe

    def _softmax_over_the_chosen(logits, bias, k, scale):
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(s + bias, k)
        return idx.astype("int32"), jax.nn.softmax(
            jax.numpy.take_along_axis(logits, idx, axis=-1), axis=-1)

    moe.route_sigmoid = _softmax_over_the_chosen

if os.environ.get("BENCH_TEST_BREAK") == "nemotron_bf16_tgmm":
    from predictionio_tpu.ops import moe

    _tgmm = moe.tgmm
    moe.tgmm = lambda *a, **kw: _tgmm(*a, **kw).astype("bfloat16").astype("float32")
