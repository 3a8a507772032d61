#!/usr/bin/env python3
"""The sequence cell's check read on the chip at the cell's own size, in ONE
process that holds the chip: the replay once, then the program as configured
and the program broken five ways, each held to the replay by the cell's own
comparison (``references/olmo_hybrid.compare_model``).

    chiprun --timeout 3000 -- python3 benchmark/tests/control_sequence_chip.py \
        [--only sound,beta1,...] [--root DIR] [--platform tpu]

The store, the read and persistence are skipped (they do not touch the
numbers): the configuration's events go through the engine's own Preparator
and ``SequenceAlgorithm.train`` the way the workflow calls them.

    sound          the program as configured
    bf16_state     the delta rule's carried state rounded to bfloat16 after
                   every chunk (the precision below float32)
    beta1          beta = sigmoid(.) without the factor 2 of
                   linear_allow_neg_eigval
    no_reset       no reset at segment boundaries: neighbours in a packed row
                   leak through the state, the convolution and attention
    one_step_fewer stepsPerRetrain - 1 optimiser steps
    int8_mlp       the MLP's three matrices rounded to int8 (symmetric, one
                   scale a tensor) before its products

Each control has the number built to catch it among the cell's own
(``bf16_state``: ``delta_rule_probe_rel_gap``, the first layer's delta-rule
output recorded by the row program against the recurrence on the same inputs;
the configuration file's ``reference.why``).

No option is added to the program: each fault is set here, in this process,
around the one call.  Readings go to stdout and
``chiprun_out/control/sequence_readings.jsonl``; exit 0 when the sound reading
was correct and every control was not.
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

CONTROLS = ("bf16_state", "beta1", "no_reset", "one_step_fewer", "int8_mlp")


@contextlib.contextmanager
def fault(name: str):
    """The program with one thing wrong, for the length of the block."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import gdn, seqmodel

    saved = {}
    seqmodel.train_programs.cache_clear()  # the sound run's programs

    def patch(module, attr, value):
        saved[(module, attr)] = getattr(module, attr)
        setattr(module, attr, value)

    if name == "bf16_state":
        from jax.experimental import pallas as pl

        def fwd_rounded(hb, w_ref, u_ref, qg_ref, p_ref, kdt_ref, a_ref,
                        o_ref, s_ref, s_scr):
            """``gdn._fwd_kernel`` with the carried state rounded."""
            @pl.when(pl.program_id(1) == 0)
            def _():
                s_scr[...] = jnp.zeros_like(s_scr)

            for h in range(hb):
                S = s_scr[h]
                s_ref[h, 0] = S
                v_new = u_ref[h, 0] - gdn._dot(w_ref[h, 0], S)
                o_ref[h, 0] = gdn._dot(qg_ref[h, 0], S) + gdn._dot(p_ref[h, 0], v_new)
                s_scr[h] = (
                    a_ref[h, 0] * S + gdn._dot(kdt_ref[h, 0], v_new)
                ).astype(jnp.bfloat16).astype(jnp.float32)

        def scan_rounded(W, U, Qg, P, Kd, a):
            def step(S, x):
                w, u, qg, p, kd, ac = x
                v_new = u - gdn._mm(w, S)
                o = gdn._mm(qg, S) + gdn._mm(p, v_new)
                S = ac[..., None, None] * S + gdn._mm(jnp.swapaxes(kd, -1, -2), v_new)
                return S.astype(jnp.bfloat16).astype(jnp.float32), o

            xs = tuple(jnp.moveaxis(x, 2, 0) for x in (W, U, Qg, P, Kd, a))
            S0 = jnp.zeros(W.shape[:2] + (W.shape[-1], U.shape[-1]), jnp.float32)
            return jnp.moveaxis(jax.lax.scan(step, S0, xs)[1], 0, 2)

        # the kernel on the chip (the scan's own backward does not fit beside
        # 12.8 GB of state there), the scan where the program takes the scan
        patch(gdn, "_fwd_kernel", fwd_rounded)
        patch(gdn, "chunk_scan", scan_rounded)
    elif name == "no_reset":
        trunk = seqmodel.trunk
        patch(seqmodel, "trunk",
              lambda cfg, p, x, seg, remat=False: trunk(cfg, p, x, seg * 0, remat))
    elif name == "int8_mlp":
        mlp = seqmodel.mlp

        def int8(w):
            scale = jnp.max(jnp.abs(w)) / 127.0
            return w + jax.lax.stop_gradient(jnp.round(w / scale) * scale - w)

        patch(seqmodel, "mlp", lambda cfg, p, x: mlp(
            cfg, {**p, **{n: int8(p[n]) for n in ("gate", "up", "down")}}, x))
    try:
        yield
    finally:
        for (module, attr), value in saved.items():
            setattr(module, attr, value)
        seqmodel.train_programs.cache_clear()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=",".join(("sound",) + CONTROLS))
    ap.add_argument("--root", default=None, help="a rehearsal's data files")
    ap.add_argument("--platform", default=None)
    args = ap.parse_args(argv)

    import numpy as np

    from benchmark import datagen, reference
    from benchmark import run as harness

    os.environ.setdefault("JAX_PLATFORMS", args.platform or harness.PLATFORM)
    manifest = harness.load_json(REPO / "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["config"].startswith("olmo-hybrid"))
    _, config, _ = harness.load_cell(
        manifest, cell["name"], Path(args.root) if args.root else harness.BENCH)

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

    work = out_dir / "work"
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
    with open(out_dir / "sequence_readings.jsonl", "a") as rows:
        for label in labels:
            algo = algos[0]
            if label == "one_step_fewer":
                algo = seq.SequenceAlgorithm(dataclasses.replace(
                    algo.params, steps_per_retrain=algo.params.steps_per_retrain - 1))
            elif label == "beta1":
                algo = seq.SequenceAlgorithm(dataclasses.replace(
                    algo.params, linear_allow_neg_eigval=False))
            t0 = time.perf_counter()
            with fault(label) if label in CONTROLS else contextlib.nullcontext():
                model = algo.make_persistent_model(ctx, algo.train(ctx, pd))
            train_s = time.perf_counter() - t0
            details: dict = {}
            compared = ref.compare_model(
                config, model, res, final.__getitem__, harness.say, details)
            correct = all(c.ok for c in compared)
            as_expected += correct == (label == "sound")
            row = {"label": label, "train_s": train_s, "correct": correct,
                   "loss": [float(x) for x in model["training_record"]["loss"]],
                   "compared": {c.name: [c.value, c.limit, c.ok] for c in compared},
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
