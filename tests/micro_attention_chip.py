#!/usr/bin/env python3
"""The micro-run that chose ``ops/seqmodel.ATTN_BLOCK`` and
``ATTN_BLOCK_COMPUTE`` (the builder's script; nothing here is part of a test
or of a benchmark run):

    chiprun --timeout 1800 -- python3 tests/micro_attention_chip.py [shapes]

One row of each sequence cell's attention through ``seqmodel._attend`` as the
row program calls it (q [1, T, H, 128], k and v as held), forward and forward
+ backward, timed as ``benchmark/tests/micro_smallthinker_chip.py`` times its
parts (``timed``: the best of three after a warm-up), with splash attention's
blocks (queries, keys, keys a product; forward, ``dkv`` and ``dq`` alike)
set to each of ``BLOCKS`` in turn, and once through the library's flash
kernel at its default blocks of 128 with k and v repeated (what every
un-windowed layer ran before PR 34):

    olmo        T 8192, 15 heads on 15, causal
    falcon      T 8192, 5 heads on 1, causal
    st_global   T 16384, 7 heads on 1, causal
    st_window   T 16384, 7 heads on 1, window 4096

Results: stdout and ``chiprun_out/micro/attention.jsonl``.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

SHAPES = {
    "olmo": (8192, 15, 15, None),
    "falcon": (8192, 5, 1, None),
    "st_global": (16384, 7, 1, None),
    "st_window": (16384, 7, 1, 4096),
}
#: (block_q, block_kv, block_kv_compute); the fused backward is left out: at
#: 512 it plans 0.94 GB of temporaries for one KV head of st_global where the
#: two-kernel backward plans 0.6 MB (compiled for a described v5e, no chip)
BLOCKS = [(512, 512, 512), (512, 512, 256), (256, 512, 256), (512, 1024, 512),
          (1024, 512, 512), (1024, 1024, 512), (1024, 1024, 1024),
          (512, 2048, 512), (1024, 2048, 512)]


def main(argv):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu import flash_attention as fa
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    from benchmark.tests.micro_sequence_chip import timed
    from predictionio_tpu.ops import seqmodel
    from predictionio_tpu.utils.runtime import configure_compile_cache

    configure_compile_cache()
    out_dir = REPO / "chiprun_out" / "micro"
    out_dir.mkdir(parents=True, exist_ok=True)
    rows = open(out_dir / "attention.jsonl", "a")
    dev = jax.devices()[0]
    interpret = dev.platform != "tpu"  # a rehearsal on the CPU

    def emit(**row):
        row["device"] = dev.device_kind
        print(json.dumps(row), flush=True)
        rows.write(json.dumps(row) + "\n")
        rows.flush()

    def kernel_with(bq, bkv, bc):
        def build(T, rep, window, interpret):
            of_head = (sm.CausalMask((T, T)) if window is None
                       else sm.LocalMask((T, T), (window - 1, 0), 0))
            q, kv, c = min(bq, T), min(bkv, T), min(bc, T)
            with jax.ensure_compile_time_eval():
                return sk.make_splash_mqa_single_device(
                    sm.MultiHeadMask([of_head] * rep), interpret=interpret,
                    block_sizes=sk.BlockSizes(
                        block_q=q, block_kv=kv, block_kv_compute=c, block_q_dkv=q,
                        block_kv_dkv=kv, block_kv_dkv_compute=c, block_q_dq=q,
                        block_kv_dq=kv))
        return build

    def flash(cfg, q, k, v, seg, window=None):
        B, T, H, d = q.shape
        k, v = seqmodel._repeat_kv(k, v, H // k.shape[2])
        q, k, v = (t.transpose(0, 2, 1, 3).astype(jnp.bfloat16) for t in (q, k, v))
        o = fa.flash_attention(
            q, k, v, segment_ids=fa.SegmentIds(q=seg, kv=seg), causal=True,
            sm_scale=d ** -0.5)
        return o.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(B, T, H * d)

    cfg = seqmodel.SeqConfig(
        hidden=128, layer_types=(seqmodel.FULL,), heads=1, head_dim=128, lin_heads=1,
        lin_key_dim=8, lin_value_dim=8, conv_width=4, mlp_cols=8, vocab_rows=8,
        attn_impl="interpret" if interpret else "flash")
    rng = np.random.default_rng(0)
    for name in argv or list(SHAPES):
        T, H, KV, window = SHAPES[name]
        if interpret:
            T //= 16
        q = jnp.asarray(rng.standard_normal((1, T, H, 128)).astype(np.float32))
        k, v = (jnp.asarray(rng.standard_normal((1, T, KV, 128)).astype(np.float32))
                for _ in range(2))
        seg = np.zeros((1, T), np.int32)
        seg[0, T - T // 100:] = 1  # as the other micro's row: one long history
        seg = jnp.asarray(seg)

        def both(attend, form):
            fwd = jax.jit(lambda q, k, v: attend(cfg, q, k, v, seg, window))
            grad = jax.jit(jax.grad(
                lambda q, k, v: attend(cfg, q, k, v, seg, window).sum(),
                argnums=(0, 1, 2)))
            try:
                fwd_s, out = timed(fwd, q, k, v)
                both_s, _ = timed(grad, q, k, v)
            except (ValueError, jax.errors.JaxRuntimeError) as e:  # a block the compiler refuses
                emit(shape=name, form=form, error=str(e)[:200])
                return None
            emit(shape=name, T=T, heads=H, kv_heads=KV, window=window, form=form,
                 forward_s=fwd_s, forward_backward_s=both_s)
            return out

        want = None
        if window is None and not interpret:
            want = both(flash, "flash_128")
        for blocks in BLOCKS:
            seqmodel._splash_kernel = kernel_with(*blocks)
            got = both(seqmodel._attend, "splash_%d_%d_%d" % blocks)
            if want is not None and got is not None:
                emit(shape=name, form="splash_%d_%d_%d" % blocks,
                     max_abs_gap_to_flash=float(jnp.abs(got - want).max()),
                     max_abs=float(jnp.abs(want).max()))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
