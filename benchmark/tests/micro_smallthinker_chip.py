#!/usr/bin/env python3
"""Timings of the SmallThinker block's parts on the chip at the cell's size,
in one process (the builder's script; nothing here is part of a run):

    chiprun --timeout 1800 -- python3 benchmark/tests/micro_smallthinker_chip.py [parts]

(``--config=FILE`` as the first argument: a rehearsal's configuration file.)

    moe      one row [16384 tokens, hidden 2560] through the held experts (16
             of 64 of width 768, 6 a token, the router's logits random: 1.5
             pairs a token held on average): the grouped kernels ALONE on the
             row's plan (``moe_gmm_gate_up``, ``moe_gmm_down``, and from the
             layer's forward + backward less its forward the four backward
             products), the FLOPs and bytes ``readers/moe_roofline.site_least``
             counts for one call from the COUNTED pairs, and the share of the
             roofline they reach (what the cell's trace cannot show while the
             call sites are below the reducer's ten); the whole layer forward
             and forward + backward (dispatch, products, combine); the same
             with every token choosing the same six held experts (the worst
             case the buffers are sized for: 4 x the pairs)
    attn     a layer's attention over a row of segments as long as the cell's
             (one of 16226 and short ones): the global kind (flash, 7 query
             heads, KV repeated) and the sliding kind (splash, window 4096,
             multi-query), forward and forward + backward
    train    a training row (forward, recomputation, backward) and the
             optimiser step as the engine runs them; the device's memory
             statistics after them
    fetch    the weights' device-to-host copy

Results: stdout and ``chiprun_out/micro/st.jsonl``.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def main(argv):
    config_path = None
    if argv and argv[0].startswith("--config="):
        config_path, argv = argv[0].split("=", 1)[1], argv[1:]
    parts = argv or ["moe", "attn", "train", "fetch"]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import run as harness
    from benchmark.readers import moe_roofline
    from benchmark.tests.micro_sequence_chip import timed
    from predictionio_tpu.models.sequence import engine as seq
    from predictionio_tpu.ops import moe, seqmodel
    from predictionio_tpu.utils.params import extract_params
    from predictionio_tpu.utils.runtime import configure_compile_cache

    configure_compile_cache()
    cfg_file = harness.load_json(
        config_path or harness.BENCH / "configs" / "smallthinker-21b-ep4.json")
    algo = seq.SequenceAlgorithm(extract_params(
        seq.SequenceAlgorithmParams,
        cfg_file["engine_json"]["algorithms"][0]["params"]))
    cfg = algo.seq_config()
    peaks = harness.load_json(harness.BENCH / "peaks.json")["devices"]
    out_dir = REPO / "chiprun_out" / "micro"
    out_dir.mkdir(parents=True, exist_ok=True)
    rows = open(out_dir / "st.jsonl", "a")
    dev = jax.devices()[0]
    # a rehearsal on the CPU reads its shares against the chip's peaks: they
    # mean nothing there, the code path is what is rehearsed
    peak = peaks.get(dev.device_kind) or peaks["TPU v5 lite"]

    def emit(**row):
        row["device"] = dev.device_kind
        print(json.dumps(row), flush=True)
        rows.write(json.dumps(row) + "\n")
        rows.flush()

    T = cfg_file["engine_json"]["preparator"]["params"]["rowLen"]
    D, F, E, held, k = (cfg.hidden, cfg.expert_width, cfg.experts, cfg.experts_held,
                        cfg.experts_per_token)
    rng = np.random.default_rng(0)
    if "moe" in parts:
        m = jnp.asarray(rng.standard_normal((T, D)).astype(np.float32))
        gate, up = (jnp.asarray(0.02 * rng.standard_normal((held, D, F)).astype(np.float32))
                    for _ in range(2))
        down = jnp.asarray(0.02 * rng.standard_normal((held, F, D)).astype(np.float32))
        valid = jnp.ones((T,), bool)
        same = np.zeros((T, E), np.float32)
        same[:, :k] = 5.0 - np.arange(k)
        for load, logits in (
                ("random", jnp.asarray(rng.standard_normal((T, E)).astype(np.float32))),
                ("all_tokens_choose_the_same_six", jnp.asarray(same))):
            idx, w = moe.route(logits, k)
            plan = jax.jit(lambda idx: moe.make_plan(idx, valid, 0, held, cfg.moe_tile))(idx)
            pairs = float(plan.counts.sum())
            xs = jnp.take(m.astype(jnp.bfloat16), plan.row_token, axis=0, mode="fill",
                          fill_value=0)
            both = jnp.concatenate([gate, up], axis=2).astype(jnp.bfloat16)
            a = jnp.take(jnp.asarray(rng.standard_normal((T, F)), jnp.bfloat16),
                         plan.row_token, axis=0, mode="fill", fill_value=0)
            wide = jnp.concatenate([a, a], axis=1)
            sites = {
                "moe_gmm_gate_up": (jax.jit(lambda xs, w, p: moe.gmm(
                    xs, w, p, name="moe_gmm_gate_up")), (xs, both, plan)),
                "moe_gmm_down": (jax.jit(lambda a, w, p: moe.gmm(
                    a, w, p, name="moe_gmm_down")), (a, down.astype(jnp.bfloat16), plan)),
                "moe_gmm_gate_up_dlhs": (jax.jit(lambda g, w, p: moe.gmm(
                    g, w, p, transpose_rhs=True, name="moe_gmm_gate_up_dlhs")),
                    (wide, both, plan)),
                "moe_tgmm_gate_up": (jax.jit(lambda xs, g, p: moe.tgmm(
                    xs, g, p, held, name="moe_tgmm_gate_up")), (xs, wide, plan)),
                "moe_tgmm_down": (jax.jit(lambda a, g, p: moe.tgmm(
                    a, g, p, held, name="moe_tgmm_down")), (a, xs, plan)),
            }
            for name, (call, args) in sites.items():
                seconds, _ = timed(call, *args, repeat=5)
                flops, nbytes = moe_roofline.site_least(name, pairs, D, F, held)
                least = max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
                emit(part="moe", load=load, form=f"{name}_kernel_alone", pairs=pairs,
                     tiles_active=int(plan.n_active[0]), seconds=seconds, flops=flops,
                     bytes=nbytes, least_s=least,
                     bound="bytes" if nbytes / peak["hbm_bytes_per_s"] >= flops / peak[
                         "bf16_flops_per_s"] else "flops",
                     roofline_pct=100.0 * least / seconds)

            def layer(m, logits, gate, up, down):
                return moe.experts_layer(
                    m, logits, valid, gate, up, down, k=k, start=0, tile=cfg.moe_tile,
                    dtype=jnp.bfloat16)[0]

            fwd_s, out = timed(jax.jit(layer), m, logits, gate, up, down)
            both_s, _ = timed(jax.jit(jax.grad(
                lambda *p: layer(*p).sum(), argnums=(0, 1, 2, 3, 4))),
                m, logits, gate, up, down)
            flops = pairs * 2.0 * 3 * D * F
            emit(part="moe", load=load, form="experts_layer", pairs=pairs,
                 forward_s=fwd_s, forward_backward_s=both_s, forward_flops=flops,
                 forward_mfu_pct=100.0 * flops / peak["bf16_flops_per_s"] / fwd_s,
                 forward_backward_mfu_pct=100.0 * 3 * flops / peak[
                     "bf16_flops_per_s"] / both_s,
                 finite=bool(jnp.isfinite(out).all()))
    if "attn" in parts:
        h = jnp.asarray(rng.standard_normal((1, T, D)).astype(np.float32))
        seg = np.zeros((1, T), np.int32)
        seg[0, T - T // 100:] = 1
        seg = jnp.asarray(seg)
        params = {n: jnp.asarray(0.02 * rng.standard_normal(s).astype(np.float32))
                  for n, s in (("q", (D, cfg.heads * cfg.head_dim)),
                               ("k", (D, cfg.kv_heads * cfg.head_dim)),
                               ("v", (D, cfg.kv_heads * cfg.head_dim)),
                               ("o", (cfg.heads * cfg.head_dim, D)))}
        for kind in (seqmodel.GLOBAL_MOE, seqmodel.SLIDING_MOE):
            f = jax.jit(lambda p, h, kind=kind: seqmodel.routed_attention(
                cfg, kind, p, h, seg))
            fwd_s, _ = timed(f, params, h)
            both_s, _ = timed(jax.jit(jax.grad(
                lambda p, h, kind=kind: seqmodel.routed_attention(
                    cfg, kind, p, h, seg).sum(), argnums=(0, 1))), params, h)
            emit(part="attn", kind=kind, forward_s=fwd_s, forward_backward_s=both_s)
    if "train" in parts:
        opt = seqmodel.AdamW()
        tok = jnp.asarray(rng.integers(0, cfg.vocab_rows, T).astype(np.int32))
        sg = np.repeat(np.arange(8), T // 8).astype(np.int32)
        sg[: T // 2] = 0  # one history of half the row, past the window
        sg = jnp.asarray(sg)
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
