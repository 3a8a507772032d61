#!/usr/bin/env python3
"""The Falcon-H1 cell's check read on the chip at the cell's own size, in ONE
process that holds the chip: the replay once, then the program as configured
and the program changed seven ways, each held to the replay by the cell's own
comparison (``references/falcon_h1.compare_model``).

    chiprun --timeout 3000 -- python3 benchmark/tests/control_h1_chip.py \
        [--only sound,bf16_state,...] [--root DIR] [--platform tpu]

The store, the read and persistence are skipped (they do not touch the
numbers): the configuration's events go through the engine's own Preparator
and ``SequenceAlgorithm.train`` the way the workflow calls them.

    sound             the program as configured
    positions_run_on  positions count on across the segments of a packed row.
                      NOT a fault: rotary scores depend on the distance of two
                      positions alone, so this must come out correct (it is
                      here to show that, on the chip)
    bf16_state        the state space's carried state rounded to bfloat16
                      after every chunk (the precision below float32)
    no_reset          no reset at segment boundaries: neighbours in a packed
                      row leak through the state, the convolution and attention
    no_rope           no rotary positions in the attention mixer (the fault
                      about positions that a check CAN see)
    no_ssm_out_mult   ssm_out_multiplier left out
    norm_before_gate  RMSNorm(y) * SiLU(z) in place of RMSNorm(y * SiLU(z))
    one_step_fewer    stepsPerRetrain - 1 optimiser steps

No option is added to the program: each fault is set here, in this process,
around the one call.  Readings go to stdout and
``chiprun_out/control/h1_readings.jsonl``; exit 0 when ``sound`` and
``positions_run_on`` were correct and every control was not.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

CORRECT = ("sound", "positions_run_on")
CONTROLS = ("bf16_state", "no_reset", "no_rope", "no_ssm_out_mult",
            "norm_before_gate", "one_step_fewer")
CONFIG = "falcon-h1-34b-tp4"


def scan_with_a_bfloat16_state(cc, bc, xe, ac):
    """``ssd.chunk_scan`` with the carried state rounded to bfloat16 after
    every chunk (the CPU rehearsals' form of ``bf16_state``)."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import ssd

    H = xe.shape[1]

    def step(S, inp):
        c, b, x, a = inp
        o = ssd._mm(c, S)
        S = a[..., None, None] * S + ssd._mm(jnp.swapaxes(b, -1, -2), x)
        return S.astype(jnp.bfloat16).astype(jnp.float32), o

    xs = tuple(jnp.moveaxis(t, 2, 0) for t in (
        ssd._per_head(cc, H), ssd._per_head(bc, H), xe, ac))
    S0 = jnp.zeros(xe.shape[:2] + (cc.shape[-1], xe.shape[-1]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(step, S0, xs)[1], 0, 2)


@contextlib.contextmanager
def fault(name: str):
    """The program with one thing changed, for the length of the block."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import seqmodel, ssd

    saved = {}
    seqmodel.train_programs.cache_clear()  # the sound run's programs

    def patch(module, attr, value):
        saved[(module, attr)] = getattr(module, attr)
        setattr(module, attr, value)

    if name == "bf16_state":
        from jax.experimental import pallas as pl

        def fwd_rounded(hb, c_ref, bt_ref, xe_ref, a_ref, o_ref, s_ref, s_scr):
            """``ssd._fwd_kernel`` with the carried state rounded."""
            @pl.when(pl.program_id(1) == 0)
            def _():
                s_scr[...] = jnp.zeros_like(s_scr)

            c, bt = c_ref[0, 0], bt_ref[0, 0]
            for h in range(hb):
                S = s_scr[h]
                s_ref[h, 0] = S
                o_ref[h, 0] = ssd._dot(c, S)
                s_scr[h] = (
                    a_ref[h, 0] * S + ssd._dot(bt, xe_ref[h, 0])
                ).astype(jnp.bfloat16).astype(jnp.float32)

        # the kernel on the chip, the scan where the program takes the scan
        patch(ssd, "_fwd_kernel", fwd_rounded)
        patch(ssd, "chunk_scan", scan_with_a_bfloat16_state)
    elif name == "no_reset":
        trunk = seqmodel.trunk
        patch(seqmodel, "trunk",
              lambda cfg, p, x, seg, remat=False: trunk(cfg, p, x, seg * 0, remat))
    elif name == "positions_run_on":
        patch(seqmodel, "segment_positions", lambda seg: jnp.broadcast_to(
            jnp.arange(seg.shape[1]), seg.shape))
    elif name == "no_rope":
        patch(seqmodel, "rope", lambda x, pos, theta: x)
    elif name == "norm_before_gate":
        def before(y, z, w, eps, axis_name=None):
            return seqmodel.rmsnorm(y, w, eps) * jax.nn.silu(z)

        patch(seqmodel, "gated_group_norm", before)
    try:
        yield
    finally:
        for (module, attr), value in saved.items():
            setattr(module, attr, value)
        seqmodel.train_programs.cache_clear()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=",".join(CORRECT + CONTROLS))
    ap.add_argument("--root", default=None, help="a rehearsal's data files")
    ap.add_argument("--platform", default=None)
    args = ap.parse_args(argv)

    import numpy as np

    from benchmark import datagen, reference
    from benchmark import run as harness

    os.environ.setdefault("JAX_PLATFORMS", args.platform or harness.PLATFORM)
    manifest = harness.load_json(REPO / "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["config"] == CONFIG)
    root = Path(args.root) if args.root else harness.BENCH
    if args.root:  # a rehearsal keeps its configuration beside its data files
        entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
        config = harness.load_json(root.parent / entry["file"])
    else:
        _, config, _ = harness.load_cell(manifest, cell["name"], root)

    import jax

    from predictionio_tpu.core.base import EngineContext
    from predictionio_tpu.core.engine import resolve_engine_factory
    from predictionio_tpu.models.sequence import engine as seq
    from predictionio_tpu.utils.runtime import configure_compile_cache

    configure_compile_cache()
    ref = reference.load(config["reference"]["kind"])
    data = config["data"]
    u, i, _ = datagen.make_movielens_like(
        data["nnz"], data["num_users"], data["num_items"], 1, data["structure_seed"])
    out_dir = REPO / "chiprun_out" / "control"
    out_dir.mkdir(parents=True, exist_ok=True)

    # the engine's own DataSource output, without the store: the events are
    # in time order as the generator wrote them
    users = np.array([datagen.user_name(x) for x in u], object)
    first = np.unique(u, return_index=True)[1]
    order_of = np.empty(int(u.max()) + 1, np.int64)
    order_of[u[np.sort(first)]] = np.arange(len(first))
    codes = order_of[u]
    td = seq.SequenceData(
        entities=users[np.sort(first)],
        offsets=np.concatenate([[0], np.cumsum(np.bincount(codes))]).astype(np.int64),
        order=np.argsort(codes, kind="stable"),
        items=np.array([datagen.item_name(x) for x in i], object),
    )
    engine = resolve_engine_factory(config["engine_factory"])()
    params = engine.params_from_json(config["engine_json"])
    _, prep, algos, _ = engine.instantiate(params)
    ctx = EngineContext()
    pd = prep.prepare(ctx, td)
    ids = ref.vocabulary_ids(
        {"item_vocab": pd.item_vocab.to_state()}, i, config["share"]["vocab_start"])
    assert ids is not None, "the Preparator's vocabulary is not first-seen order"

    work = out_dir / "h1_work"
    work.mkdir(exist_ok=True)
    np.savez(work / "replay_data.npz", user_idx=u, item_ids=ids)
    job = ref.job_of(config, jax.devices()[0].platform, work / "replay_data.npz", work)
    t0 = time.perf_counter()
    res = ref.replay_job(job, harness.say)
    final = res.pop("final")
    harness.say(f"replay: {res['replay_s']:.1f} s of it the steps, "
                f"{time.perf_counter() - t0:.1f} s in all")

    as_expected = 0
    labels = args.only.split(",")
    with open(out_dir / "h1_readings.jsonl", "a") as rows:
        for label in labels:
            algo = algos[0]
            if label == "one_step_fewer":
                algo = seq.SequenceAlgorithm(dataclasses.replace(
                    algo.params, steps_per_retrain=algo.params.steps_per_retrain - 1))
            elif label == "no_ssm_out_mult":
                algo = seq.SequenceAlgorithm(dataclasses.replace(
                    algo.params, ssm_out_multiplier=1.0))
            t0 = time.perf_counter()
            with fault(label):
                model = algo.make_persistent_model(ctx, algo.train(ctx, pd))
            train_s = time.perf_counter() - t0
            details: dict = {}
            compared = ref.compare_model(
                config, model, res, final.__getitem__, harness.say, details)
            correct = all(c.ok for c in compared)
            as_expected += correct == (label in CORRECT)
            stats = [d.memory_stats() or {} for d in jax.local_devices()]
            row = {"label": label, "train_s": train_s, "correct": correct,
                   "loss": [float(x) for x in model["training_record"]["loss"]],
                   "compared": {c.name: [c.value, c.limit, c.ok] for c in compared},
                   "peak_bytes_in_use": max(s.get("peak_bytes_in_use", 0) for s in stats),
                   "peak_bytes_reserved": max(s.get("peak_bytes_reserved", 0) for s in stats),
                   "details": details}
            for c in compared:
                harness.say(f"{label}: {c.line()}")
            print(json.dumps({k: v for k, v in row.items() if k != "details"}), flush=True)
            rows.write(json.dumps(row) + "\n")
            rows.flush()
            del model
    return 0 if as_expected == len(labels) else 1


if __name__ == "__main__":
    raise SystemExit(main())
