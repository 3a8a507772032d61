#!/usr/bin/env python3
"""What the Nemotron-H stack's unaligned shapes cost on the chip, in one
process (the builder's script; nothing here is part of a run):

    chiprun --timeout 1500 -- python3 benchmark/tests/micro_nemotron_chip.py [parts]

    experts  a row's routed experts (8192 tokens, 6 of 128 experts a token, 16
             held, tiles of 256 pairs, hidden 2688; seeded logits, so ~384
             pairs an expert) forward and forward + backward, at the published
             expert width F = 1856 (14.5 lane tiles: ``gmm`` takes a block of
             the full width, ``tgmm`` covers it with blocks of 640) beside
             F = 1920 (15 tiles) and F = 1792 (14 tiles); each with the FLOPs
             and bytes ``readers/relu2_moe_roofline.site_least`` counts and
             the share of the roofline reached
    ssd      the sequential pass's kernels alone (``ssd.chunk_pallas`` on
             prepared chunks of one row of 8192 tokens, state 128, one group)
             forward and forward + backward at 8 heads of P = 64 (half a lane
             tile a head) beside 4 heads of P = 128: equal channels
    train    a training row and the optimiser step as the engine runs them,
             and the device's memory statistics after them

Results: stdout and ``chiprun_out/micro/nemotron.jsonl``.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def main(argv):
    parts = argv or ["experts", "ssd", "train"]
    import jax
    import jax.numpy as jnp

    from benchmark import run as harness
    from benchmark.readers import relu2_moe_roofline, ssd_roofline
    from benchmark.tests.micro_sequence_chip import timed
    from predictionio_tpu.models.sequence import engine as seq
    from predictionio_tpu.ops import moe, seqmodel, ssd
    from predictionio_tpu.utils.params import extract_params
    from predictionio_tpu.utils.runtime import configure_compile_cache

    configure_compile_cache()
    cfg_file = harness.load_json(
        harness.BENCH / "configs" / "nemotron3-nano-30b-ep8.json")
    algo = seq.SequenceAlgorithm(extract_params(
        seq.SequenceAlgorithmParams,
        cfg_file["engine_json"]["algorithms"][0]["params"]))
    cfg = algo.seq_config()
    peaks = harness.load_json(harness.BENCH / "peaks.json")["devices"]
    peak = peaks[jax.devices()[0].device_kind]
    out_dir = REPO / "chiprun_out" / "micro"
    out_dir.mkdir(parents=True, exist_ok=True)
    rows = open(out_dir / "nemotron.jsonl", "a")

    def emit(**row):
        print(json.dumps(row), flush=True)
        rows.write(json.dumps(row) + "\n")
        rows.flush()

    def least_s(pieces):
        return sum(max(f / peak["bf16_flops_per_s"], b / peak["hbm_bytes_per_s"])
                   for f, b in pieces)

    T, D = 8192, cfg.hidden
    if "experts" in parts:
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        m = jax.random.normal(ks[0], (T, D))
        logits = jax.random.normal(ks[1], (T, cfg.experts))
        bias = jnp.zeros((cfg.experts,))
        valid = jnp.ones((T,), bool)
        for F in (1856, 1920, 1792):
            up = 0.02 * jax.random.normal(ks[2], (cfg.experts_held, D, F))
            down = 0.02 * jax.random.normal(ks[3], (cfg.experts_held, F, D))

            def layer(m, up, down):
                out, _, counts = moe.experts_layer(
                    m, logits, valid, None, up, down, k=cfg.experts_per_token,
                    start=0, tile=cfg.moe_tile, dtype=jnp.bfloat16, bias=bias,
                    scale=cfg.routed_scale)
                return out.sum(), counts

            fwd = jax.jit(layer)
            both = jax.jit(jax.value_and_grad(layer, argnums=(0, 1, 2), has_aux=True))
            pairs = float(fwd(m, up, down)[1].sum())
            fwd_s = timed(lambda: fwd(m, up, down))[0]
            both_s = timed(lambda: both(m, up, down))[0]
            work = [relu2_moe_roofline.site_least(
                name, pairs, D, F, cfg.experts_held)
                for name in relu2_moe_roofline.PRODUCTS]
            emit(part="experts", width=F, pairs=pairs, forward_s=fwd_s,
                 forward_backward_s=both_s,
                 forward_least_s=least_s(work[:2]), all_least_s=least_s(work),
                 forward_roofline_pct=100 * least_s(work[:2]) / fwd_s,
                 forward_backward_roofline_pct=100 * least_s(work) / both_s,
                 tgmm_blocks={"up": moe._tgmm_block(F), "down": moe._tgmm_block(D)})
    if "ssd" in parts:
        NC, C, N = T // cfg.ssm_chunk, cfg.ssm_chunk, cfg.ssm_state
        ks = jax.random.split(jax.random.PRNGKey(1), 4)
        for H, P in ((8, 64), (4, 128)):
            cc = jax.random.normal(ks[0], (1, 1, NC, C, N))
            bc = jax.random.normal(ks[1], (1, 1, NC, C, N))
            xe = jax.random.normal(ks[2], (1, H, NC, C, P))
            ac = jax.random.uniform(ks[3], (1, H, NC), minval=0.5, maxval=1.0)
            fwd = jax.jit(lambda *p: ssd.chunk_pallas(*p, False).sum())
            both = jax.jit(jax.value_and_grad(
                lambda *p: ssd.chunk_pallas(*p, False).sum(), argnums=(0, 1, 2, 3)))
            fwd_s = timed(lambda: fwd(cc, bc, xe, ac))[0]
            both_s = timed(lambda: both(cc, bc, xe, ac))[0]
            f, b = (ssd_roofline.site_least(kind, 1, H, 1, T, C, P, N)
                    for kind in ("fwd", "bwd"))
            emit(part="ssd", heads=H, head_dim=P, state=N,
                 heads_per_block=ssd.heads_per_block(H), forward_s=fwd_s,
                 forward_backward_s=both_s, backward_s=both_s - fwd_s,
                 forward_least_s=least_s([f]), backward_least_s=least_s([b]),
                 forward_roofline_pct=100 * least_s([f]) / fwd_s,
                 backward_roofline_pct=100 * least_s([b]) / max(both_s - fwd_s, 1e-9))
    if "train" in parts:
        import numpy as np

        state, acc = seqmodel.init_state(cfg, 3)
        jax.block_until_ready(state)
        accumulate, apply = seqmodel.train_programs(cfg, seqmodel.AdamW())
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_rows, T).astype(np.int32))
        seg = jnp.asarray(np.repeat(np.arange(16), T // 16).astype(np.int32))
        times = []
        import time

        for _ in range(4):
            t0 = time.perf_counter()
            state, acc, probe = accumulate(state, acc, tokens, seg)
            jax.block_until_ready(acc["loss"])
            times.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        state, acc, record = apply(state, acc)
        jax.block_until_ready(state["t"])
        first_apply = time.perf_counter() - t0
        t0 = time.perf_counter()
        state, acc, record = apply(state, acc)
        jax.block_until_ready(state["t"])
        stats = jax.local_devices()[0].memory_stats() or {}
        emit(part="train", row_first_s=times[0], row_s=min(times[1:]),
             step_first_s=first_apply, step_s=time.perf_counter() - t0,
             memory={k: stats.get(k) for k in (
                 "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
                 "peak_bytes_reserved", "bytes_limit")})
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
