#!/usr/bin/env python3
"""The Ouro cell's check read on the chip at the cell's own size, in ONE
process that holds the chip: the replay once, then the program as configured
and the program changed eight ways, each held to the replay by the cell's own
comparison (``references/ouro.compare_model``).

    chiprun --timeout 3000 -- python3 benchmark/tests/control_ouro_chip.py \
        [--only sound,three_passes,...] [--root DIR] [--platform tpu]

The store, the read and persistence are skipped (they do not touch the
numbers): the configuration's events go through the engine's own Preparator
and ``SequenceAlgorithm.train`` the way the workflow calls them.

    sound              the program as configured
    three_passes       totalUtSteps 3 in place of 4
    gate_detached      the gate's path detached: ``stop_gradient`` on the exit
                       distribution, so the gate learns nothing and the trunk
                       nothing through it
    beta_zero          exitBeta 0: the entropy term left out
    bf16_carried_state the state handed from pass to pass (the final norm's
                       output, which the exit reads too) rounded to bfloat16:
                       the precision below the float32 the configuration states
    no_norm_between    the final norm left out BETWEEN passes: an exit reads
                       the normed state, the next pass the un-normed one
    no_out_norm        the sandwich's second norms left out: x + f(N(x))
    bf16_logits        the head's logits rounded to bfloat16 (what a bfloat16
                       accumulation type gives on this chip, whose matrix unit
                       sums in float32 whatever is asked: the product's output
                       is rounded)
    one_step_fewer     stepsPerRetrain - 1 optimiser steps

No option is added to the program: each fault is set here, in this process,
around the one call.  Readings go to stdout and
``chiprun_out/control/ouro_readings.jsonl``; exit 0 when ``sound`` was correct
and every control was not.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

CORRECT = ("sound",)
CONTROLS = ("three_passes", "gate_detached", "beta_zero", "bf16_carried_state",
            "no_norm_between", "no_out_norm", "bf16_logits", "one_step_fewer")
CONFIG = "ouro-2.6b-d8"


def to_bfloat16(x):
    """x rounded to bfloat16's 8 exponent and 7 mantissa bits, still float32.
    ``reduce_precision`` and not a pair of casts: the chip's compiler takes a
    float32 -> bfloat16 -> float32 round trip out (it may keep excess
    precision), and call 2c's control then read as the sound program."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def rounded_to_bfloat16(f):
    """``f`` with its result rounded to bfloat16 (and handed on as float32)."""
    @functools.wraps(f)
    def rounded(*args, **kw):
        return to_bfloat16(f(*args, **kw))

    return rounded


def detached(f):
    """``f`` with no gradient through its result."""
    import jax

    @functools.wraps(f)
    def cut(*args, **kw):
        return jax.lax.stop_gradient(f(*args, **kw))

    return cut


def forward_without_the_norm_between_passes(cfg, inner, x, seg):
    """``seqmodel.loop_forward`` whose passes hand on the UN-normed stream:
    the final norm is applied for the exit alone (a pass's ``vjp`` then takes
    two cotangents, the stream's and the exit state's)."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import seqmodel

    def one_pass(p, x):
        for i, kind in enumerate(cfg.layer_types):
            x, _ = jax.checkpoint(functools.partial(seqmodel.layer, cfg, kind))(
                seqmodel.layer_params(p, i), x, seg)
        return x, seqmodel.rmsnorm(x, p["final_norm"], cfg.eps)

    states, carried, pass_vjps = [], [], []
    for t in range(cfg.loop_steps):
        if t:
            carried.append(seqmodel.carried_mean_square(x))
        (x, state), pass_vjp = jax.vjp(one_pass, inner, x)
        states.append(state)
        pass_vjps.append(pass_vjp)
    return jnp.stack(states), jnp.stack(carried, axis=-1), pass_vjps


def backward_without_the_norm_between_passes(pass_vjps, dstates, gsum):
    """``seqmodel.loop_backward`` for those passes."""
    import jax.numpy as jnp

    dx = jnp.zeros_like(dstates[0])
    for t in reversed(range(len(pass_vjps))):
        dinner, dx = pass_vjps[t]((dx, dstates[t]))
        gsum = {k: gsum[k] + g for k, g in dinner.items()}
    return gsum, dx


def layer_without_its_out_norms(cfg, kind, p, x, seg):
    """``seqmodel.layer``'s sandwich branch as a plain pre-norm layer: a
    sublayer's output is added as it is.  The second norms are still computed
    and multiplied by zero: left out altogether, the chip's compiler finds
    110 MB too little for THIS program (calls 2c and 3) where the sound one
    fits; with them the plan is the sound program's."""
    import jax.numpy as jnp

    from predictionio_tpu.ops import seqmodel

    h = seqmodel.rmsnorm(x, p["input_norm"], cfg.eps)
    a = seqmodel.grouped_query_attention(cfg, p, h, seg)
    x = x + (a + 0.0 * seqmodel.rmsnorm(a, p["attn_out_norm"], cfg.eps))
    h = seqmodel.rmsnorm(x, p["pre_ff_norm"], cfg.eps)
    y = seqmodel.mlp(cfg, p, h)
    out = x + (y + 0.0 * seqmodel.rmsnorm(y, p["mlp_out_norm"], cfg.eps))
    return out, jnp.zeros(x.shape[:2] + (0,))


@contextlib.contextmanager
def fault(name: str, vocab_rows: int = 0):
    """The program with one thing changed, for the length of the block."""
    import jax.numpy as jnp

    from predictionio_tpu.ops import seqmodel

    saved = {}
    seqmodel.train_programs.cache_clear()  # the sound run's programs

    def patch(attr, value):
        saved[attr] = getattr(seqmodel, attr)
        setattr(seqmodel, attr, value)

    if name == "gate_detached":
        patch("exit_log_probs", detached(seqmodel.exit_log_probs))
    elif name == "bf16_carried_state":
        patch("loop_pass", rounded_to_bfloat16(seqmodel.loop_pass))
    elif name == "no_norm_between":
        patch("loop_forward", forward_without_the_norm_between_passes)
        patch("loop_backward", backward_without_the_norm_between_passes)
    elif name == "no_out_norm":
        patch("layer", layer_without_its_out_norms)
    elif name == "bf16_logits":
        scaled = seqmodel._scaled

        def logits_rounded(x, m):
            # the loss scales two arrays a block, the logits and their
            # gradient; the second is cast to bfloat16 straight after anyway
            if x.shape[-1] == vocab_rows and x.dtype == jnp.float32:
                x = to_bfloat16(x)
            return scaled(x, m)

        patch("_scaled", logits_rounded)
    try:
        yield
    finally:
        for attr, value in saved.items():
            setattr(seqmodel, attr, value)
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

    work = out_dir / "ouro_work"
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
    with open(out_dir / "ouro_readings.jsonl", "a") as rows:
        for label in labels:
            algo = algos[0]
            if label == "one_step_fewer":
                algo = seq.SequenceAlgorithm(dataclasses.replace(
                    algo.params, steps_per_retrain=algo.params.steps_per_retrain - 1))
            elif label == "three_passes":
                algo = seq.SequenceAlgorithm(dataclasses.replace(
                    algo.params, total_ut_steps=algo.params.total_ut_steps - 1))
            elif label == "beta_zero":
                algo = seq.SequenceAlgorithm(dataclasses.replace(
                    algo.params, exit_beta=0.0))
            t0 = time.perf_counter()
            with fault(label, config["vocab_size"]):
                model = algo.make_persistent_model(ctx, algo.train(ctx, pd))
            train_s = time.perf_counter() - t0
            details: dict = {}
            compared = ref.compare_model(
                config, model, res, final.__getitem__, harness.say, details)
            correct = all(c.ok for c in compared)
            as_expected += correct == (label in CORRECT)
            stats = [d.memory_stats() or {} for d in jax.local_devices()]
            record = model["training_record"]
            row = {"label": label, "train_s": train_s, "correct": correct,
                   "loss": [float(x) for x in record["loss"]],
                   "loss_by_exit": np.asarray(record["loss_by_exit"]).tolist(),
                   "exit_mass": np.asarray(record["exit_mass"]).tolist(),
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
