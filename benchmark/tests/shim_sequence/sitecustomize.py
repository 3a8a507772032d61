"""Test-only: breaks the sequence engine's timed path underneath the harness,
in the children of ``test_sequence_cell.py`` (which put this directory on
their PYTHONPATH and say what to break in ``BENCH_TEST_BREAK``).  Without that
variable it does nothing.

    seq_no_reset   the layers see one segment a row: no reset of the delta
                   rule's state, of the convolution or of attention at a
                   segment's start, so neighbours in a packed row leak (the
                   loss still counts the real positions)
    seq_bf16_state the delta rule's carried state rounded to bfloat16 after
                   every chunk: the precision below the float32 the
                   configuration states (in chunks of 8 tokens: the tiny
                   size's rows of 64 are ONE chunk of the configured 64, and
                   a state that is never carried is never rounded)
"""

import os

if os.environ.get("BENCH_TEST_BREAK") == "seq_no_reset":
    from predictionio_tpu.ops import seqmodel

    _trunk = seqmodel.trunk

    def _one_segment(cfg, params, x, seg, remat=False):
        return _trunk(cfg, params, x, seg * 0, remat)

    seqmodel.trunk = _one_segment

if os.environ.get("BENCH_TEST_BREAK") == "seq_bf16_state":
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import gdn

    def _rounded_scan(W, U, Qg, P, Kd, a):
        def step(S, x):
            w, u, qg, p, kd, ac = x
            v_new = u - gdn._mm(w, S)
            o = gdn._mm(qg, S) + gdn._mm(p, v_new)
            S = ac[..., None, None] * S + gdn._mm(jnp.swapaxes(kd, -1, -2), v_new)
            return S.astype(jnp.bfloat16).astype(jnp.float32), o

        xs = tuple(jnp.moveaxis(x, 2, 0) for x in (W, U, Qg, P, Kd, a))
        S0 = jnp.zeros(W.shape[:2] + (W.shape[-1], U.shape[-1]), jnp.float32)
        return jnp.moveaxis(jax.lax.scan(step, S0, xs)[1], 0, 2)

    _rule = gdn.gated_delta_rule

    def _in_chunks_of_eight(q, k, v, g, beta, seg, chunk=64, impl=None):
        return _rule(q, k, v, g, beta, seg, 8, impl)

    gdn.chunk_scan = _rounded_scan
    gdn.gated_delta_rule = _in_chunks_of_eight
