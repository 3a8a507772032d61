#!/usr/bin/env python3
"""Timings of the sequence engine's parts on the chip at the cell's size, in
one process (the builder's script; nothing here is part of a run):

    chiprun --timeout 1800 -- python3 benchmark/tests/micro_sequence_chip.py [parts]

    delta    one row [8192 tokens, 15 heads, 96 / 192] of the delta rule,
             forward only: token by token (the reference's ``lax.scan``), the
             chunkwise form with the ``lax.scan`` over chunks, and with the
             Pallas kernel; then forward + backward of the two chunkwise forms
    train    a training row (forward, recomputation, backward) and the
             optimiser step as the engine runs them; the device's memory
             statistics after them (with the
             ``lax.scan`` sequential pass the row program is refused by the
             compiler: its differentiated form needs 120 MB more than the
             12.8 GB of state leave)
    fetch    the weights' device-to-host copy

Results: stdout and ``chiprun_out/micro/sequence.jsonl``.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def timed(fn, *args, repeat=3):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best, out


def main(argv):
    parts = argv or ["delta", "train", "fetch"]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference
    from benchmark import run as harness
    from predictionio_tpu.models.sequence import engine as seq
    from predictionio_tpu.ops import gdn, seqmodel
    from predictionio_tpu.utils.params import extract_params
    from predictionio_tpu.utils.runtime import configure_compile_cache

    configure_compile_cache()
    cfg_file = harness.load_json(harness.BENCH / "configs" / "olmo-hybrid-7b-tp2.json")
    algo = seq.SequenceAlgorithm(extract_params(
        seq.SequenceAlgorithmParams,
        cfg_file["engine_json"]["algorithms"][0]["params"]))
    cfg = algo.seq_config()
    ref = reference.load("olmo_hybrid")
    out_dir = REPO / "chiprun_out" / "micro"
    out_dir.mkdir(parents=True, exist_ok=True)
    rows = open(out_dir / "sequence.jsonl", "a")
    dev = jax.devices()[0]

    def emit(**row):
        row["device"] = dev.device_kind
        print(json.dumps(row), flush=True)
        rows.write(json.dumps(row) + "\n")
        rows.flush()

    T, H, dk, dv = 8192, cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim
    rng = np.random.default_rng(0)
    if "delta" in parts:
        q = rng.standard_normal((1, T, H, dk)).astype(np.float32)
        k = rng.standard_normal((1, T, H, dk)).astype(np.float32)
        q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
        k /= np.linalg.norm(k, axis=-1, keepdims=True)
        v = rng.standard_normal((1, T, H, dv)).astype(np.float32)
        g = (-0.05 * np.abs(rng.standard_normal((1, T, H)))).astype(np.float32)
        beta = (2 / (1 + np.exp(-rng.standard_normal((1, T, H))))).astype(np.float32)
        seg = np.zeros((1, T), np.int32)
        seg[0, 3000:] = 1
        args = tuple(jnp.asarray(x) for x in (q, k, v, g, beta, seg))
        with jax.default_matmul_precision("highest"):
            token = jax.jit(lambda q, k, v, g, b: ref.delta_rule(q[0], k[0], v[0], g[0], b[0]))
            s, o_tok = timed(token, *args[:5], repeat=1)
        emit(part="delta", form="token_by_token_scan_forward", seconds=s)
        for impl in ("scan", "pallas"):
            f = jax.jit(lambda *a, impl=impl: gdn.gated_delta_rule(*a, chunk=64, impl=impl))
            s, o = timed(f, *args)
            emit(part="delta", form=f"chunkwise_{impl}_forward", seconds=s)
            fb = jax.jit(jax.grad(
                lambda *a, impl=impl: gdn.gated_delta_rule(*a[:5], args[5], chunk=64, impl=impl).sum(),
                argnums=(0, 1, 2, 3, 4)))
            s, _ = timed(fb, *args[:5])
            emit(part="delta", form=f"chunkwise_{impl}_forward_backward", seconds=s)
        # the two segments of the row, each alone, token by token
        with jax.default_matmul_precision("highest"):
            alone = jnp.concatenate([
                ref.delta_rule(*(x[0, :3000] for x in args[:5])),
                ref.delta_rule(*(x[0, 3000:] for x in args[:5]))])
        emit(part="delta", form="chunkwise_pallas_vs_token_by_token_max_abs_gap",
             value=float(jnp.abs(o[0] - alone).max()), scale=float(jnp.abs(alone).max()))
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
