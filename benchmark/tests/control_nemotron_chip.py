#!/usr/bin/env python3
"""The Nemotron-H cell's check read on the chip at the cell's own size, in ONE
process that holds the chip: the replay once, then the program as configured
and the program changed eleven ways, each held to the replay by the cell's own
comparison (``references/nemotron_h.compare_model``).

    chiprun --timeout 3400 -- python3 benchmark/tests/control_nemotron_chip.py \
        [--only sound,softmax_chosen,...] [--root DIR] [--platform tpu]

The store, the read and persistence are skipped (they do not touch the
numbers): the configuration's events go through the engine's own Preparator
and ``SequenceAlgorithm.train`` the way the workflow calls them.

    sound              the program as configured
    softmax_chosen     the chosen experts' weights a softmax over their logits
                       (they sum to 1) in place of the normalised sigmoid
                       scores times 2.5
    scale_1            the scale 1 in place of 2.5
    no_normalisation   the weights 2.5 x the chosen scores, not divided by
                       their sum
    no_shared          the shared expert left out
    relu               relu in place of relu^2, routed and shared experts
    gated              a gated expert, relu(W_gate m) * (W_up m), the gate
                       matrix the up matrix with its columns rolled by one
    rope               rotary positions (restarting at a segment) in the
                       attention layer
    bf16_state         the state space's carried state rounded to bfloat16
                       after every chunk
    bf16_router        the router's scores rounded to bfloat16 before the
                       choice and the weights
    bf16_accumulation  the grouped products' results rounded to bfloat16 (what
                       a bfloat16 accumulator hands on): the precision below
    norm_before_gate   the state space's norm applied BEFORE the gate

No option is added to the program: each fault is set here, in this process,
around the one call.  Readings go to stdout and
``chiprun_out/control/nemotron_readings.jsonl``; exit 0 when ``sound`` was
correct and every control was not.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

CORRECT = ("sound",)
CONTROLS = ("softmax_chosen", "scale_1", "no_normalisation", "no_shared", "relu",
            "gated", "rope", "bf16_state", "bf16_router", "bf16_accumulation",
            "norm_before_gate")
CONFIG = "nemotron3-nano-30b-ep8"


def _rounded(x):
    """``reduce_precision`` and not a cast there and back: the chip's compiler
    takes the pair of casts out."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def routes() -> dict:
    """``moe.route_sigmoid`` changed: name -> function."""
    import jax
    import jax.numpy as jnp

    def pick(logits, bias, k, scores=lambda s: s):
        s = scores(jax.nn.sigmoid(logits))
        _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
        return idx.astype(jnp.int32), jnp.take_along_axis(s, idx, axis=-1)

    def softmax_chosen(logits, bias, k, scale):
        idx, _ = pick(logits, bias, k)
        return idx, jax.nn.softmax(jnp.take_along_axis(logits, idx, axis=-1), axis=-1)

    def no_normalisation(logits, bias, k, scale):
        idx, chosen = pick(logits, bias, k)
        return idx, scale * chosen

    def bf16_router(logits, bias, k, scale):
        idx, chosen = pick(logits, bias, k, _rounded)
        return idx, scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)

    return {"softmax_chosen": softmax_chosen, "no_normalisation": no_normalisation,
            "bf16_router": bf16_router}


def gated_ffn():
    """``moe.relu2_ffn`` as a GATED expert over the same two tensors: the
    gate matrix is the up matrix with its columns rolled by one, so
    ``relu(u[j - 1]) * u[j]`` stands where ``relu(u[j])^2`` does."""
    import jax.numpy as jnp

    from predictionio_tpu.ops import moe

    def gated(static, m, w, up, down, plan):
        return moe.expert_ffn(
            static, m, w, jnp.roll(up, 1, axis=2), up, down, plan)

    return gated


def attention_with_rotary():
    """``seqmodel.routed_attention`` with the attention layer's q and k
    rotated, positions restarting at a segment."""
    from predictionio_tpu.ops import seqmodel

    def rotated(cfg, kind, p, h, seg):
        B, T, _ = h.shape
        d = cfg.head_dim
        q, k, v = (seqmodel.mm(h, p[n]).reshape(B, T, -1, d) for n in ("q", "k", "v"))
        pos = seqmodel.segment_positions(seg)
        q, k = (seqmodel.rope(t, pos, cfg.rope_theta) for t in (q, k))
        return seqmodel.mm(seqmodel._attend(cfg, q, k, v, seg), p["o"])

    return rotated


@contextlib.contextmanager
def fault(name: str):
    """The program with one thing changed, for the length of the block."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import moe, seqmodel, ssd

    saved = {}
    seqmodel.train_programs.cache_clear()  # the sound run's programs
    seqmodel.experts_probe.clear_cache()

    def patch(module, attr, value):
        saved[(module, attr)] = getattr(module, attr)
        setattr(module, attr, value)

    if name in routes():
        patch(moe, "route_sigmoid", routes()[name])
    elif name == "no_shared":
        patch(seqmodel, "shared_expert", lambda p, h: jnp.zeros_like(h))
    elif name == "relu":
        patch(moe, "act2", lambda u: jnp.maximum(u, 0.0))
        patch(moe, "act2_grad", lambda u: (u > 0).astype(u.dtype))
    elif name == "gated":
        tgmm = moe.tgmm
        patch(moe, "relu2_ffn", gated_ffn())
        # gate | up is 3712 columns wide: no block of 768 divides it
        patch(moe, "tgmm", lambda *a, **kw: tgmm(
            *a, **{**kw, "block_n": moe.COVER_BLOCK}))
        patch(seqmodel, "shared_expert", lambda p, h: seqmodel.mm(
            jnp.maximum(jnp.roll(seqmodel.mm(h, p["shared_up"]), 1, axis=-1), 0.0)
            * seqmodel.mm(h, p["shared_up"]), p["shared_down"]))
    elif name == "rope":
        patch(seqmodel, "routed_attention", attention_with_rotary())
    elif name == "bf16_state":
        from jax.experimental import pallas as pl

        from benchmark.tests.control_h1_chip import scan_with_a_bfloat16_state

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
    elif name == "bf16_accumulation":
        gmm, tgmm = moe.gmm, moe.tgmm
        patch(moe, "gmm", lambda *a, **kw: _rounded(gmm(*a, **kw)))
        patch(moe, "tgmm", lambda *a, **kw: _rounded(tgmm(*a, **kw)))
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
        seqmodel.experts_probe.clear_cache()


def changed_params(label: str, params):
    """The faults that are another configuration, not another program."""
    if label == "scale_1":
        return dataclasses.replace(params, routed_scaling_factor=1.0)
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

    work = out_dir / "nemotron_work"
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
    with open(out_dir / "nemotron_readings.jsonl", "a") as rows:
        for label in labels:
            algo = seq.SequenceAlgorithm(changed_params(label, algos[0].params))
            t0 = time.perf_counter()
            try:
                with fault(label):
                    model = algo.make_persistent_model(ctx, algo.train(ctx, pd))
            except Exception:  # a changed program the chip cannot hold: say so, go on
                traceback.print_exc()
                rows.write(json.dumps({"label": label, "error": traceback.format_exc()[-2000:]}) + "\n")
                rows.flush()
                print(json.dumps({"label": label, "error": True}), flush=True)
                continue
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
                   "bytes_limit": max(s.get("bytes_limit", 0) for s in stats),
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
