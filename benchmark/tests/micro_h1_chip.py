#!/usr/bin/env python3
"""Timings of the Falcon-H1 block's parts on the chip at the cell's size, in
one process (the builder's script; nothing here is part of a run):

    chiprun --timeout 1800 -- python3 benchmark/tests/micro_h1_chip.py [parts]

    ssd      one row [8192 tokens, 8 heads of 128, state 256, one group] of the
             state space: token by token (the reference's ``lax.scan``,
             forward only), the chunked form with the ``lax.scan`` over chunks
             and with the Pallas kernels, forward and forward + backward; then
             the kernels ALONE (``ssd.chunk_pallas`` on prepared chunks):
             forward, backward (forward + backward less forward), the FLOPs
             and bytes ``readers/ssd_roofline.site_least`` counts for one call
             and the share of the roofline they reach (what the cell's trace
             cannot show: the call sites are far below the reducer's ten)
    train    a training row (forward, recomputation, backward) and the
             optimiser step as the engine runs them; the device's memory
             statistics after them
    fetch    the weights' device-to-host copy

Results: stdout and ``chiprun_out/micro/h1.jsonl``.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def main(argv):
    parts = argv or ["ssd", "train", "fetch"]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference
    from benchmark import run as harness
    from benchmark.readers import ssd_roofline
    from benchmark.tests.micro_sequence_chip import timed
    from predictionio_tpu.models.sequence import engine as seq
    from predictionio_tpu.ops import seqmodel, ssd
    from predictionio_tpu.utils.params import extract_params
    from predictionio_tpu.utils.runtime import configure_compile_cache

    configure_compile_cache()
    cfg_file = harness.load_json(harness.BENCH / "configs" / "falcon-h1-34b-tp4.json")
    algo = seq.SequenceAlgorithm(extract_params(
        seq.SequenceAlgorithmParams,
        cfg_file["engine_json"]["algorithms"][0]["params"]))
    cfg = algo.seq_config()
    ref = reference.load("falcon_h1")
    peaks = harness.load_json(harness.BENCH / "peaks.json")["devices"]
    out_dir = REPO / "chiprun_out" / "micro"
    out_dir.mkdir(parents=True, exist_ok=True)
    rows = open(out_dir / "h1.jsonl", "a")
    dev = jax.devices()[0]

    def emit(**row):
        row["device"] = dev.device_kind
        print(json.dumps(row), flush=True)
        rows.write(json.dumps(row) + "\n")
        rows.flush()

    T = cfg_file["engine_json"]["preparator"]["params"]["rowLen"]
    H, P, G, N, C = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                     cfg.ssm_state, cfg.ssm_chunk)
    rng = np.random.default_rng(0)
    if "ssd" in parts:
        x = (0.05 * rng.standard_normal((1, T, H, P))).astype(np.float32)
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (1, T, H))).astype(np.float32)
        a = -rng.uniform(1.0, 16.0, H).astype(np.float32)
        b = (0.05 * rng.standard_normal((1, T, G, N))).astype(np.float32)
        c = (0.2 * rng.standard_normal((1, T, G, N))).astype(np.float32)
        seg = np.zeros((1, T), np.int32)
        seg[0, 3000:] = 1
        args = tuple(jnp.asarray(t) for t in (x, dt, a, b, c, seg))
        with jax.default_matmul_precision("highest"):
            token = jax.jit(lambda x, dt, a, b, c: ref.selective_scan(
                x[0], dt[0], a, b[0], c[0]))
            s, _ = timed(token, *args[:5], repeat=1)
        emit(part="ssd", form="token_by_token_scan_forward", seconds=s)
        for impl in ("scan", "pallas"):
            f = jax.jit(lambda *p, impl=impl: ssd.ssd(*p, chunk=C, impl=impl))
            s, o = timed(f, *args)
            emit(part="ssd", form=f"chunked_{impl}_forward", seconds=s)
            fb = jax.jit(jax.grad(
                lambda *p, impl=impl: ssd.ssd(*p, args[5], chunk=C, impl=impl).sum(),
                argnums=(0, 1, 2, 3, 4)))
            s, _ = timed(fb, *args[:5])
            emit(part="ssd", form=f"chunked_{impl}_forward_backward", seconds=s)
        # the two segments of the row, each alone, token by token
        with jax.default_matmul_precision("highest"):
            alone = jnp.concatenate([
                ref.selective_scan(x[0, :3000], dt[0, :3000], a, b[0, :3000], c[0, :3000]),
                ref.selective_scan(x[0, 3000:], dt[0, 3000:], a, b[0, 3000:], c[0, 3000:])])
        emit(part="ssd", form="chunked_pallas_vs_token_by_token_max_abs_gap",
             value=float(jnp.abs(o[0] - alone).max()), scale=float(jnp.abs(alone).max()))
        # the kernels alone, on the chunks the layer hands them
        _, chunks = jax.jit(lambda *p: ssd.intra(*p, C))(*args)
        kernel = jax.jit(lambda *p: ssd.chunk_pallas(*p, False))
        fwd_s, _ = timed(kernel, *chunks, repeat=5)
        both = jax.jit(jax.grad(
            lambda *p: ssd.chunk_pallas(*p, False).sum(), argnums=(0, 1, 2, 3)))
        both_s, _ = timed(both, *chunks, repeat=5)
        peak = peaks[dev.device_kind]
        for kind, seconds in (("fwd", fwd_s), ("bwd", both_s - fwd_s)):
            flops, nbytes = ssd_roofline.site_least(kind, 1, H, G, T, C, P, N)
            least = max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
            emit(part="ssd", form=f"ssd_chunk_{kind}_kernel_alone", seconds=seconds,
                 flops=flops, bytes=nbytes, least_s=least,
                 bound="bytes" if nbytes / peak["hbm_bytes_per_s"] >= flops / peak[
                     "bf16_flops_per_s"] else "flops",
                 roofline_pct=100.0 * least / seconds)
    if "train" in parts:
        opt = seqmodel.AdamW()
        tok = jnp.asarray(rng.integers(0, cfg.vocab_rows, T).astype(np.int32))
        sg = jnp.asarray(np.repeat(np.arange(8), T // 8).astype(np.int32))
        t0 = time.perf_counter()
        state, acc = seqmodel.init_state(cfg, 3)
        jax.block_until_ready((state, acc))
        init_s = time.perf_counter() - t0
        accumulate, apply = seqmodel.train_programs(cfg, opt)
        t0 = time.perf_counter()
        state, acc, _ = accumulate(state, acc, tok, sg)
        jax.block_until_ready(acc)
        first_s = time.perf_counter() - t0
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            state, acc, _ = accumulate(state, acc, tok, sg)
            jax.block_until_ready(acc)
            times.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        state, acc, rec = apply(state, acc)
        jax.block_until_ready(state)
        apply_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        state, acc, rec = apply(state, acc)
        jax.block_until_ready(state)
        apply_s = time.perf_counter() - t0
        emit(part="train", init_s=init_s,
             row_first_s=first_s, row_s=min(times), rows=times,
             apply_first_s=apply_first, apply_s=apply_s,
             loss=float(rec["loss"]), memory=dev.memory_stats())
        if "fetch" in parts:
            t0 = time.perf_counter()
            host = {k: np.asarray(v) for k, v in state["params"].items()}
            emit(part="fetch", seconds=time.perf_counter() - t0,
                 bytes=int(sum(v.nbytes for v in host.values())))
            del host
        del state, acc
        seqmodel.train_programs.cache_clear()
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
