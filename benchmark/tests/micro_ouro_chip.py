#!/usr/bin/env python3
"""Timings of the Ouro block's training step on the chip at the cell's size,
in one process (the builder's script; nothing here is part of a run):

    chiprun --timeout 1800 -- python3 benchmark/tests/micro_ouro_chip.py [grains]

For each recomputation grain, one optimiser step as the engine runs it (the
step's rows through ``accumulate_row``, then ``apply_step``), timed after its
compile, with the device's memory statistics after it:

    layer    every layer APPLICATION is recomputed in the backward pass: 32
             residual streams are kept (the program as it is)
    pass     a PASS is recomputed, and inside its backward each of its layers
             again: 4 + 8 streams are kept, the forward runs three times

Results: stdout and ``chiprun_out/micro/ouro.jsonl``.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def main(argv):
    grains = argv or ["layer", "pass"]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import datagen
    from benchmark import run as harness
    from benchmark.references.olmo_hybrid import histories, rows_of
    from predictionio_tpu.models.sequence import engine as seq
    from predictionio_tpu.ops import seqmodel
    from predictionio_tpu.utils.params import extract_params
    from predictionio_tpu.utils.runtime import configure_compile_cache

    configure_compile_cache()
    body = harness.load_json(harness.BENCH / "configs" / "ouro-2.6b-d8.json")
    algo = seq.SequenceAlgorithm(extract_params(
        seq.SequenceAlgorithmParams, body["engine_json"]["algorithms"][0]["params"]))
    cfg, p = algo.seq_config(), algo.params
    data, row_len = body["data"], body["engine_json"]["preparator"]["params"]["rowLen"]
    u, i, _ = datagen.make_movielens_like(
        data["nnz"], data["num_users"], data["num_items"], 1, data["structure_seed"])
    hist = histories(u, i, row_len)
    # the first step's rows as the Preparator packs them (ids as generated)
    tokens = np.zeros((p.rows_per_step, row_len), np.int32)
    segs = np.full((p.rows_per_step, row_len), seqmodel.PAD_SEGMENT, np.int32)
    for r, row in enumerate(rows_of([len(h) for h in hist], row_len)[: p.rows_per_step]):
        at = 0
        for j in row:
            tokens[r, at : at + len(hist[j])] = hist[j]
            segs[r, at : at + len(hist[j])] = j
            at += len(hist[j])
    out_dir = REPO / "chiprun_out" / "micro"
    out_dir.mkdir(parents=True, exist_ok=True)
    one_pass = seqmodel.loop_pass

    def by_pass(cfg, params, x, seg, remat=False):
        if not remat:
            return one_pass(cfg, params, x, seg)
        return jax.checkpoint(
            lambda params, x: one_pass(cfg, params, x, seg, True))(params, x)

    try:
        with open(out_dir / "ouro.jsonl", "a") as rows:
            for grain in grains:
                seqmodel.loop_pass = by_pass if grain == "pass" else one_pass
                seqmodel.train_programs.cache_clear()
                state, acc = seqmodel.init_state(cfg, p.seed)
                opt = seqmodel.AdamW()
                tok, seg = jnp.asarray(tokens[None]), jnp.asarray(segs[None])
                times = []
                for _ in range(3):  # the first compiles
                    t0 = time.perf_counter()
                    state, acc, records, _ = seqmodel.train_steps(
                        cfg, opt, state, acc, tok, seg)
                    jax.block_until_ready(state["params"])
                    times.append(time.perf_counter() - t0)
                stats = jax.local_devices()[0].memory_stats() or {}
                row = {"grain": grain, "step_s": times,
                       "loss": float(records[0]["loss"]),
                       **{k: stats.get(k) for k in (
                           "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
                           "peak_bytes_reserved", "bytes_limit")}}
                print(json.dumps(row), flush=True)
                rows.write(json.dumps(row) + "\n")
                del state, acc
    finally:
        seqmodel.loop_pass = one_pass
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
