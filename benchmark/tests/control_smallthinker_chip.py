#!/usr/bin/env python3
"""The SmallThinker cell's check read on the chip at the cell's own size, in
ONE process that holds the chip: the replay once, then the program as
configured and the program changed nine ways, each held to the replay by the
cell's own comparison (``references/smallthinker.compare_model``).

    chiprun --timeout 3400 -- python3 benchmark/tests/control_smallthinker_chip.py \
        [--only sound,no_window,...] [--root DIR] [--platform tpu]

The store, the read and persistence are skipped (they do not touch the
numbers): the configuration's events go through the engine's own Preparator
and ``SequenceAlgorithm.train`` the way the workflow calls them.

    sound              the program as configured
    no_window          the sliding layers without their window (full causal
                       attention within the segment; rotary kept)
    rope_on_global     rotary positions on the global layer too
    top5               five experts a token in place of six
    softmax_all        the chosen experts' weights taken from a softmax over
                       all 64, not renormalised over the six
    capacity_1.25      an expert takes 1.25 x the mean load (a row's tokens x
                       6 / 64) and drops the pairs past it
    router_reads_m     the router reads the stream AFTER attention (the
                       experts' own input) and not the layer's normed input
    silu               SiLU in place of ReLU in the experts' gate
    bf16_accumulation  the grouped products' results rounded to bfloat16 (what
                       a bfloat16 accumulator hands on): the precision below
    one_step_fewer     stepsPerRetrain - 1 optimiser steps

No option is added to the program: each fault is set here, in this process,
around the one call.  Readings go to stdout and
``chiprun_out/control/st_readings.jsonl``; exit 0 when ``sound`` was correct
and every control was not.
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

CORRECT = ("sound",)
CONTROLS = ("no_window", "rope_on_global", "top5", "softmax_all", "capacity_1.25",
            "router_reads_m", "silu", "bf16_accumulation", "one_step_fewer")
CONFIG = "smallthinker-21b-ep4"


def plan_with_a_capacity(factor: float, experts: int):
    """``moe.make_plan`` under a capacity: an expert keeps its first
    ``factor x tokens x k / experts`` pairs (token order) and the rest are
    dropped: they lie on no row."""
    import jax.numpy as jnp

    from predictionio_tpu.ops import moe

    make_plan = moe.make_plan

    def capped(idx, valid, start, held, tile):
        plan = make_plan(idx, valid, start, held, tile)
        rows = plan.row_token.shape[0]
        cap = int(factor * idx.shape[0] * idx.shape[1] / experts)
        tiles = jnp.maximum(-(-plan.counts // tile), 1)
        first = (jnp.cumsum(tiles) - tiles) * tile
        group = plan.tile_group[jnp.minimum(plan.dest, rows - 1) // tile]
        over = plan.dest - first[group] >= cap
        return plan._replace(dest=jnp.where(over, rows, plan.dest))

    return capped


def route_with_a_softmax_over_all(logits, k):
    import jax
    import jax.numpy as jnp

    idx = jax.lax.top_k(logits, k)[1]
    w = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), idx, axis=-1)
    return idx.astype(jnp.int32), w


def gmm_rounded_to_bfloat16():
    """``reduce_precision`` and not a cast there and back: the chip's compiler
    takes the pair of casts out (call 1 of PR 32 read this control equal to
    ``sound`` to the last digit)."""
    import jax

    from predictionio_tpu.ops import moe

    gmm, tgmm = moe.gmm, moe.tgmm

    def rounded(f):
        return lambda *a, **kw: jax.lax.reduce_precision(
            f(*a, **kw), exponent_bits=8, mantissa_bits=7)

    return rounded(gmm), rounded(tgmm)


def attention_with_rotary_everywhere():
    """``seqmodel.routed_attention`` with the global kind's q and k rotated."""
    from predictionio_tpu.ops import seqmodel

    sound = seqmodel.routed_attention

    def rotated(cfg, kind, p, h, seg):
        if kind != seqmodel.GLOBAL_MOE:
            return sound(cfg, kind, p, h, seg)
        B, T, _ = h.shape
        d = cfg.head_dim
        q, k, v = (seqmodel.mm(h, p[n]).reshape(B, T, -1, d) for n in ("q", "k", "v"))
        pos = seqmodel.segment_positions(seg)
        q, k = (seqmodel.rope(t, pos, cfg.rope_theta) for t in (q, k))
        k, v = seqmodel._repeat_kv(k, v, q.shape[2] // k.shape[2])
        return seqmodel.mm(seqmodel._attend(cfg, q, k, v, seg), p["o"])

    return rotated


def layer_whose_router_reads_m():
    """``seqmodel.routed_layer`` with the logits made from the experts' own
    input (after attention); the probe still feeds the experts ``h``."""
    import functools

    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import moe, seqmodel

    def late(cfg, kind, p, x, seg):
        B, T, D = x.shape
        h = seqmodel.rmsnorm(x, p["input_norm"], cfg.eps)
        x = x + seqmodel.routed_attention(cfg, kind, p, h, seg)
        m = seqmodel.rmsnorm(x, p["post_norm"], cfg.eps)
        experts = functools.partial(
            moe.experts_layer, valid=(seg != seqmodel.PAD_SEGMENT).reshape(-1),
            gate=p["experts_gate"], up=p["experts_up"], down=p["experts_down"],
            k=cfg.experts_per_token, start=cfg.expert_start, tile=cfg.moe_tile,
            dtype=seqmodel.MATMUL_DTYPE, impl=cfg.moe_impl)
        logits = seqmodel.mm_f32(m, p["router"]).reshape(B * T, -1)
        y, choices, pairs = experts(m.reshape(B * T, D), logits)
        hs = jax.lax.stop_gradient(h)
        probe = jax.lax.stop_gradient(jnp.matmul(
            experts(hs.reshape(B * T, D),
                    seqmodel.mm_f32(hs, p["router"]).reshape(B * T, -1))[0],
            seqmodel.moe_probe_vector(D), precision=seqmodel.HIGHEST))
        return x + y.reshape(B, T, D), (
            probe.reshape(B, T, 1), choices.reshape(B, T, -1), pairs)

    return late


@contextlib.contextmanager
def fault(name: str, experts: int = 64):
    """The program with one thing changed, for the length of the block."""
    import jax

    from predictionio_tpu.ops import moe, seqmodel

    saved = {}
    seqmodel.train_programs.cache_clear()  # the sound run's programs

    def patch(module, attr, value):
        saved[(module, attr)] = getattr(module, attr)
        setattr(module, attr, value)

    if name == "rope_on_global":
        patch(seqmodel, "routed_attention", attention_with_rotary_everywhere())
    elif name == "softmax_all":
        patch(moe, "route", route_with_a_softmax_over_all)
    elif name == "capacity_1.25":
        patch(moe, "make_plan", plan_with_a_capacity(1.25, experts))
    elif name == "router_reads_m":
        patch(seqmodel, "routed_layer", layer_whose_router_reads_m())
    elif name == "silu":
        patch(moe, "act", jax.nn.silu)
        patch(moe, "act_grad", jax.vmap(jax.vmap(jax.grad(jax.nn.silu))))
    elif name == "bf16_accumulation":
        gmm, tgmm = gmm_rounded_to_bfloat16()
        patch(moe, "gmm", gmm)
        patch(moe, "tgmm", tgmm)
    try:
        yield
    finally:
        for (module, attr), value in saved.items():
            setattr(module, attr, value)
        seqmodel.train_programs.cache_clear()


def changed_params(label: str, params):
    """The faults that are another configuration, not another program."""
    if label == "one_step_fewer":
        return dataclasses.replace(
            params, steps_per_retrain=params.steps_per_retrain - 1)
    if label == "no_window":  # wider than any row: the band is the triangle
        return dataclasses.replace(params, sliding_window_size=1 << 30)
    if label == "top5":
        return dataclasses.replace(
            params,
            moe_num_active_primary_experts=params.moe_num_active_primary_experts - 1)
    return params


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

    work = out_dir / "st_work"
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
    experts = config["share"]["published"]["moe_num_primary_experts"]
    with open(out_dir / "st_readings.jsonl", "a") as rows:
        for label in labels:
            algo = seq.SequenceAlgorithm(changed_params(label, algos[0].params))
            t0 = time.perf_counter()
            with fault(label, experts):
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
                   "moe_pairs_held": np.asarray(record["moe_pairs_held"]).tolist(),
                   "moe_expert_pairs_max": np.asarray(
                       record["moe_expert_pairs"]).max(-1).tolist(),
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
