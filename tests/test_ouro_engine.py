"""The Ouro block (ISSUE 38) at a tiny size on the CPU: the looped trunk, the
exits and their loss against the plain reference
(``benchmark/references/ouro.py`` through ``ouro_reference``: hidden 64, 4
heads of 16, 3 layers run 4 times, 512 items, rows of 256 packed from several
segments), and the block behind the DASE contract (``pio train`` on an
engine.json of the new kind -> persisted model -> ``load_models`` ->
``predict``).

There is no share test: the configuration's cut is in depth alone, every
width, head and vocabulary row is held whole by one chip, so there are no
parts to add up."""

from __future__ import annotations

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ouro_reference import (
    TINY, pack, random_weights, reference, seq_config, untied, untied_tensors)
from predictionio_tpu.core import EngineContext
from predictionio_tpu.core.engine import resolve_engine_factory
from predictionio_tpu.core.persistence import load_models
from predictionio_tpu.core.workflow import run_train
from predictionio_tpu.models.recommendation.engine import Query
from predictionio_tpu.models.sequence import engine as seq
from predictionio_tpu.ops import seqmodel
from test_sequence_engine import _Stages, store  # noqa: F401  (a fixture)

ROW = 256
SEGMENTS = (100, 60, 37, 41)  # 18 tokens of padding close the row


@pytest.fixture()
def f32_matmuls(monkeypatch):
    """The program's large products in float32, as the reference's are: what
    is left between the two is rounding, not the configuration's bf16."""
    monkeypatch.setattr(seqmodel, "MATMUL_DTYPE", jnp.float32)
    with jax.default_matmul_precision("highest"):
        yield


def _row(seed=7, lengths=SEGMENTS):
    rng = np.random.default_rng(seed)
    segs = [rng.integers(0, TINY["vocab_rows"], n).astype(np.int32) for n in lengths]
    tok, seg = pack(segs, ROW)
    return segs, jnp.asarray(tok)[None], jnp.asarray(seg)[None]


def _reference_step(m, w, segs, tensors_of=reference.layer_tensors):
    """The reference over the segments one at a time -> (the parts' sums,
    the objective's gradient)."""
    def total(w):
        parts = [
            reference.segment_losses(
                m, w, jnp.asarray(s), jnp.ones(len(s), bool), tensors_of)
            for s in segs]
        sums = {k: sum(p[k] for p in parts)
                for k in ("loss", "by_exit", "mass", "entropy")}
        per = {k: jnp.concatenate([p[k] for p in parts]) for k in ("p", "carried")}
        return sums["loss"], {**sums, **per}

    with jax.default_matmul_precision("highest"):
        (_, parts), grads = jax.jit(jax.value_and_grad(total, has_aux=True))(w)
    return parts, grads


def _program_step(cfg, w, tok, seg):
    return jax.jit(lambda w: seqmodel.row_grads(
        cfg, w, tok, seg, jax.tree.map(jnp.zeros_like, w)))(w)


@pytest.fixture(scope="module")
def packed_step():
    segs, tok, seg = _row()
    w = random_weights(TINY, 3)
    return (segs, tok, seg, w) + _reference_step(TINY, w, segs)


def test_program_is_the_reference_on_a_packed_step(f32_matmuls, packed_step):
    """Loss, every tensor's gradient (the gate's two among them), every
    exit's loss and mass, the entropy, and a position's exit distribution and
    carried mean squares, of one packed row against the reference, which sees
    the four segments one at a time."""
    segs, tok, seg, w, want, want_g = packed_step
    cfg = seq_config(TINY)
    loss, count, got, probe = _program_step(cfg, w, tok, seg)
    n = sum(len(s) for s in segs)
    assert float(count) == n - len(segs)
    assert float(loss) == pytest.approx(float(want["loss"]), rel=1e-5)
    assert set(got) == set(want_g) == set(seqmodel.param_shapes(cfg))
    assert {"exit_gate", "exit_gate_bias"} <= set(got)
    for name in want_g:
        gap = float(jnp.linalg.norm(got[name] - want_g[name]))
        assert gap <= 1e-4 * float(jnp.linalg.norm(want_g[name])), name
    np.testing.assert_allclose(probe["exit_loss"], want["by_exit"], rtol=1e-5)
    np.testing.assert_allclose(probe["exit_mass"], want["mass"], rtol=1e-5)
    assert float(probe["exit_entropy"]) == pytest.approx(
        float(want["entropy"]), rel=1e-5)
    assert probe["exit_probe"].shape == (1, ROW, 4)
    assert probe["carry_probe"].shape == (1, ROW, 3)
    np.testing.assert_allclose(probe["exit_probe"][0, :n], want["p"], atol=1e-5)
    np.testing.assert_allclose(probe["carry_probe"][0, :n], want["carried"], rtol=1e-5)
    # the gates spread the exits: the comparison above holds a distribution,
    # not a corner of one
    assert 0.05 < float(want["mass"].min()) / (n - len(segs))


def test_exit_distribution_sums_to_one_and_the_last_pass_takes_the_rest(
        f32_matmuls, packed_step):
    segs, tok, seg, w, _, _ = packed_step
    cfg = seq_config(TINY)
    p = np.asarray(_program_step(cfg, w, tok, seg)[3]["exit_probe"][0])
    np.testing.assert_allclose(p.sum(-1), 1.0, atol=1e-6)
    assert (p > 0).all()
    states = seqmodel.trunk(cfg, w, seqmodel.embed(cfg, w["embed"], tok), seg)[0]
    lam = np.asarray(jax.nn.sigmoid(
        jnp.einsum("rbtd,d->rbt", states, w["exit_gate"]) + w["exit_gate_bias"]))[:, 0]
    np.testing.assert_allclose(p[:, 0], lam[0], atol=1e-5)
    np.testing.assert_allclose(p[:, 1], lam[1] * (1 - lam[0]), atol=1e-5)
    np.testing.assert_allclose(p[:, 3], np.prod(1 - lam[:3], axis=0), atol=1e-5)


def test_program_in_its_stated_precision_stays_near_the_reference(packed_step):
    """bf16 products, f32 accumulation: the loss to 1e-3; the gradients keep
    their direction."""
    segs, tok, seg, w, want, want_g = packed_step
    loss, _, got, _ = _program_step(seq_config(TINY), w, tok, seg)
    assert float(loss) == pytest.approx(float(want["loss"]), rel=1e-3)
    cos = [
        float(jnp.vdot(got[k], want_g[k])
              / (jnp.linalg.norm(got[k]) * jnp.linalg.norm(want_g[k])))
        for k in want_g
    ]
    assert min(cos) > 0.9


def test_a_shared_layers_gradient_is_the_sum_over_its_four_uses(f32_matmuls, packed_step):
    """The tie: against an UNTIED reference (four copies of the stack, one a
    pass) the program's gradient of a shared tensor is the sum of the four
    copies' gradients; what is not in the stack is the same tensor."""
    segs, tok, seg, w, _, _ = packed_step
    _, free = _reference_step(TINY, untied(w, TINY), segs, untied_tensors)
    got = _program_step(seq_config(TINY), w, tok, seg)[2]
    for name, g in got.items():
        if name.startswith("layer"):
            parts = [free[f"pass{t}.{name}"] for t in range(TINY["passes"])]
            # each pass gives a part of its own: none is the whole
            assert all(float(jnp.linalg.norm(p)) > 0 for p in parts), name
            want = sum(parts)
        else:
            want = free[name]
        gap = float(jnp.linalg.norm(g - want))
        assert gap <= 1e-4 * float(jnp.linalg.norm(want)), name


@pytest.mark.parametrize("case", ["one_pass", "gate_always_leaves_at_once"])
def test_the_loop_reduces_to_one_pass_of_the_stack_with_one_loss(f32_matmuls, case):
    """``R = 1`` has no gate and one loss; ``R = 4`` with ``beta = 0`` and a
    gate whose bias is huge puts all mass on the first exit.  Both are the
    loss and the stack's gradients of one pass through the reference's
    layers, with a plain cross-entropy written out here."""
    segs, tok, seg = _row(11)
    one = {**TINY, "passes": 1, "exit_beta": 0.0}
    w1 = random_weights(one, 5)

    def plain(w):
        total = 0.0
        for s in segs:
            s = jnp.asarray(s)
            h = reference.exit_states(one, w, s)[0]
            logits = h[:-1] @ w["head"].T
            total += jnp.sum(
                jax.nn.logsumexp(logits, axis=-1)
                - jnp.take_along_axis(logits, s[1:, None], axis=-1)[:, 0])
        return total

    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(plain))(w1)
    if case == "one_pass":
        m, w = one, w1
        assert "exit_gate" not in seqmodel.param_shapes(seq_config(m))
    else:
        m = {**TINY, "exit_beta": 0.0}
        w = {**w1, "exit_gate": jnp.zeros(64), "exit_gate_bias": jnp.float32(40.0)}
    loss, _, got, probe = _program_step(seq_config(m), w, tok, seg)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    for name, g in want_g.items():
        gap = float(jnp.linalg.norm(got[name] - g))
        assert gap <= 1e-4 * float(jnp.linalg.norm(g)), name
    if case != "one_pass":
        assert np.isfinite(np.asarray(got["exit_gate"])).all()
        np.testing.assert_allclose(probe["exit_probe"][0, :, 0], 1.0, atol=1e-6)


@pytest.mark.parametrize("which", [0, 2])
def test_a_segments_loss_and_gradients_do_not_depend_on_its_neighbours(
        f32_matmuls, which):
    """Rotary positions restart and the mask holds: the segment alone in a
    row gives the per-position records it has in the packed row, and the
    packed row's loss and gradients are the sums over its segments alone."""
    segs, tok, seg = _row(13)
    w = random_weights(TINY, 5)
    cfg = seq_config(TINY)
    loss, _, grads, probe = _program_step(cfg, w, tok, seg)
    alone = [
        _program_step(cfg, w, *(jnp.asarray(a)[None] for a in pack([s], ROW)))
        for s in segs]
    assert float(loss) == pytest.approx(sum(float(a[0]) for a in alone), rel=1e-5)
    for name, g in grads.items():
        want = sum(a[2][name] for a in alone)
        assert float(jnp.linalg.norm(g - want)) <= 1e-4 * float(jnp.linalg.norm(want)), name
    at, n = sum(len(s) for s in segs[:which]), len(segs[which])
    np.testing.assert_allclose(
        probe["exit_probe"][0, at : at + n], alone[which][3]["exit_probe"][0, :n],
        atol=2e-5)


def test_training_steps_are_the_references_adamw(f32_matmuls, monkeypatch):
    """Four optimiser steps of one row through ``train_steps`` against the
    reference's written-out AdamW over the same segments: the weights, and
    every step's record with the exits' keys.  The replay pads a segment to
    one of three lengths (16, 64, 256 here) and takes its loss 48 positions
    at a time, which divides none of them: the blocks change no number."""
    monkeypatch.setattr(reference, "LOSS_BLOCK", 48)
    rng = np.random.default_rng(9)
    rows = [[rng.integers(0, 512, n).astype(np.int32) for n in ns]
            for ns in ((120, 90), (256,), (37, 59, 140), (133, 101))]
    packed = [pack(r, ROW) for r in rows]
    tokens = jnp.asarray(np.stack([p[0] for p in packed]).reshape(4, 1, ROW))
    segs = jnp.asarray(np.stack([p[1] for p in packed]).reshape(4, 1, ROW))
    cfg = seq_config(TINY)
    opt = seqmodel.AdamW()
    state, acc = seqmodel.init_state(cfg, 3)
    for name, v in reference.initial_weights(TINY, 3).items():
        np.testing.assert_allclose(
            state["params"][name], v, rtol=1e-6, err_msg=name)  # one rule, twice
    w0 = {k: jnp.array(v) for k, v in state["params"].items()}
    state, acc, records, probes = seqmodel.train_steps(cfg, opt, state, acc, tokens, segs)
    assert len(probes) == 1  # the first step's row
    assert probes[0]["exit_probe"].shape == (ROW, 4)
    assert probes[0]["carry_probe"].shape == (ROW, 3)
    hist = [s for r in rows for s in r]
    steps = [[0, 1], [2], [3, 4, 5], [6, 7]]
    ref_opt = {"lr": opt.lr, "beta1": opt.b1, "beta2": opt.b2, "eps": opt.eps,
               "weight_decay": opt.weight_decay}
    w_ref, ref_records, first = reference.replay(
        TINY, ref_opt, 3, hist, steps, 4, say=lambda *_: None)
    want_p, want_c, _ = reference.first_step_probes(TINY, hist, [[0, 1]], ROW, first)
    real = np.isfinite(want_p[0, :, 0])
    assert real.sum() == 210
    np.testing.assert_allclose(probes[0]["exit_probe"][real], want_p[0][real], atol=1e-5)
    np.testing.assert_allclose(probes[0]["carry_probe"][real], want_c[0][real], rtol=1e-5)
    seen: list = []
    for got, want in zip(records, ref_records):
        assert float(got["loss"]) == pytest.approx(want["loss"], rel=1e-5)
        assert float(got["tokens"]) == want["tokens"]
        assert float(got["grad_norm"]) == pytest.approx(want["grad_norm"], rel=1e-4)
        np.testing.assert_allclose(got["loss_by_exit"], want["loss_by_exit"], rtol=1e-5)
        np.testing.assert_allclose(got["exit_mass"], want["exit_mass"], rtol=1e-4)
        assert float(got["exit_entropy"]) == pytest.approx(want["exit_entropy"], rel=1e-4)
        assert int(got["loop_layer_applications"]) == 4 * 3  # one row a step
        assert int(got["loop_tokens"]) == want["tokens"] + len(steps[len(seen)])
        assert int(got["loop_attention_pairs"]) == sum(
            len(hist[j]) * (len(hist[j]) + 1) // 2 for j in steps[len(seen)])
        seen.append(got)
        for k, v in want["tensor_grad_probe"].items():
            assert float(got["tensor_grad_probe"][k]) == pytest.approx(
                v, abs=5e-4 * want["tensor_grad_norm"][k]), k
    for k, v in w_ref.items():
        moved = float(jnp.linalg.norm(v - w0[k]))
        assert float(jnp.linalg.norm(state["params"][k] - v)) <= 0.03 * moved + 1e-9, k
    assert float(acc["count"]) == 0 and int(state["t"]) == 4
    assert float(acc["exit_loss"].sum()) == 0 and int(acc["layer_applications"]) == 0
    # the gate's vector is decayed like any matrix; its bias and the norms not
    assert seqmodel.decays("exit_gate") and not seqmodel.decays("exit_gate_bias")
    assert all(seqmodel.decays(k) != reference.no_decay(k) for k in w_ref)


def test_a_bfloat16_carried_state_is_seen_by_the_carry_probe_alone(monkeypatch):
    """The control twin: the state handed from pass to pass rounded to
    bfloat16 (the precision below the float32 the configuration states) moves
    the carried state's mean square a thousand times further from the
    reference's than the sound program's own bf16 products leave it."""
    segs, tok, seg = _row(17)
    w = reference.initial_weights(TINY, 3)
    want = _reference_step(TINY, w, segs)[0]["carried"]
    n = len(want)

    def gap():
        got = _program_step(seq_config(TINY), w, tok, seg)[3]["carry_probe"][0, :n]
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    sound = gap()
    one_pass = seqmodel.loop_pass
    monkeypatch.setattr(
        seqmodel, "loop_pass",
        lambda *a, **kw: jax.lax.reduce_precision(one_pass(*a, **kw), 8, 7))
    assert sound < 1e-6 < 1e-5 < gap()


def test_the_kept_exit_states_give_the_head_probe_again(monkeypatch):
    """At the configured precision (bfloat16 inputs, float32 sums): the
    cross-entropies the record keeps at a few positions are the head's
    product of the exit states it keeps beside them, to float32's own sums,
    whatever the trunk rounded before; logits rounded to bfloat16 (the
    control twin: what a bfloat16 accumulation type gives) are a thousand
    times further off."""
    segs, tok, seg = _row(23)
    w = random_weights(TINY, 5)
    cfg = seq_config(TINY)
    at = reference.head_probe_positions(ROW, seqmodel.HEAD_PROBE_POSITIONS)
    nxt, weight = seqmodel.next_item_targets(tok, seg)
    targets = np.where(np.asarray(weight)[0, at] > 0, np.asarray(nxt)[0, at], -1)
    assert (targets >= 0).sum() > 20

    def gap():
        probe = _program_step(cfg, w, tok, seg)[3]
        assert probe["head_probe"].shape == (1, len(at), 4)
        assert probe["head_probe_state"].shape == (1, len(at), 4, 64)
        want = reference.head_again(probe["head_probe_state"][0], w["head"], targets)
        return reference._rel_l2(probe["head_probe"][0], want)

    sound = gap()
    scaled = seqmodel._scaled
    monkeypatch.setattr(
        seqmodel, "_scaled", lambda x, m: scaled(
            jax.lax.reduce_precision(x, 8, 7) if x.shape[-1] == 512 else x, m))
    assert sound < 1e-6 < 1e-4 < gap()


# ---------------------------------------------------------------------------
# the configuration


def test_the_kinds_fix_what_may_be_looped():
    base = dict(hidden=64, heads=4, head_dim=16, lin_heads=2, lin_key_dim=8,
                lin_value_dim=16, conv_width=4, mlp_cols=16, vocab_rows=128)
    with pytest.raises(ValueError, match="unknown layer types"):
        seqmodel.SeqConfig(layer_types=("looped_attention",), **base)
    with pytest.raises(ValueError, match="only a stack of sandwich_attention"):
        seqmodel.SeqConfig(layer_types=("full_attention",), loop_steps=4, **base)
    with pytest.raises(ValueError, match="at least 1"):
        seqmodel.SeqConfig(layer_types=("sandwich_attention",), loop_steps=0, **base)
    # one pass of the new kind is a plain stack: no gate, the old record
    cfg = seqmodel.SeqConfig(layer_types=("sandwich_attention",) * 2, **base)
    assert "exit_gate" not in seqmodel.param_shapes(cfg)
    assert set(jax.eval_shape(lambda: seqmodel.init_state(cfg, 3))[1]) == {
        "g", "loss", "count"}


@pytest.mark.parametrize("block", ["olmo_hybrid", "falcon_h1", "smallthinker"])
def test_the_other_blocks_keep_their_accumulator_and_their_record(block):
    """``loop_steps`` 1 adds nothing to the three blocks this engine had:
    the same accumulator, the same record keys, no gate."""
    from test_sequence_scopes import _config

    cfg = _config(block)
    assert cfg.loop_steps == 1
    state, acc = jax.eval_shape(lambda: seqmodel.init_state(cfg, 3))
    routed = ({"expert_pairs", "pairs_total", "rows_live", "rows_planned"}
              if block == "smallthinker" else set())
    assert set(acc) == {"g", "loss", "count"} | routed
    assert not [k for k in state["params"] if k.startswith("exit_")]
    record = jax.eval_shape(
        lambda s, a: seqmodel.apply_step(seqmodel.AdamW(), s, a), state, acc)[2]
    assert set(record) == {
        "loss", "tokens", "grad_norm", "tensor_grad_norm", "tensor_grad_probe"} | (
        {"moe_expert_pairs", "moe_pairs_held", "moe_pairs_total", "moe_rows_live",
         "moe_rows_planned"} if routed else set())


# ---------------------------------------------------------------------------
# behind the DASE contract

VARIANT = {
    "datasource": {"params": {"appName": "seq"}},
    "preparator": {"params": {
        "rowLen": 64, "maxLen": 64, "rowsPerStep": 2, "vocabSize": 128}},
    "algorithms": [{"name": "loop", "params": {
        "hiddenSize": 64, "layerTypes": ["sandwich_attention"] * 2,
        "numAttentionHeads": 4, "numKeyValueHeads": 4, "headDim": 16,
        "ropeTheta": 1000000, "intermediateSize": 48, "vocabSize": 128,
        "rmsNormEps": 1e-6, "totalUtSteps": 4, "exitBeta": 0.1,
        "rowsPerStep": 2, "stepsPerRetrain": 2}}],
}


@pytest.fixture()
def trained(store):  # noqa: F811
    rt, data = store
    seen = _Stages()
    log = logging.getLogger("predictionio_tpu.workflow")
    log.addHandler(seen)
    level = log.level
    log.setLevel(logging.INFO)
    try:
        engine = resolve_engine_factory("sequence")()
        params = engine.params_from_json(VARIANT)
        instance = run_train(
            engine, params, engine_factory="sequence", storage=rt,
            ctx=EngineContext(storage=rt))
    finally:
        log.removeHandler(seen)
        log.setLevel(level)
    assert instance.status == "COMPLETED"
    return rt, data, engine, params, instance, seen.stages


def pd_segments(rt, params, engine):
    """The segment ids of the rows the four optimiser-step rows trained."""
    ds, prep, _, _ = engine.instantiate(params)
    ctx = EngineContext(storage=rt)
    return prep.prepare(ctx, ds.read_training(ctx)).segments[:4]


def test_engine_json_reaches_the_blocks_configuration():
    engine = resolve_engine_factory("sequence")()
    algo = engine.instantiate(engine.params_from_json(VARIANT))[2][0]
    cfg = algo.seq_config()
    assert set(cfg.layer_types) == {"sandwich_attention"}
    assert (cfg.loop_steps, cfg.exit_beta, cfg.rope_theta) == (4, 0.1, 1e6)
    assert (cfg.heads, cfg.kv_heads, cfg.mlp_cols) == (4, 4, 48)
    assert seqmodel.PROBE_NAME[cfg.layer_types[0]] == "exit_probe"
    assert seqmodel.num_params(cfg) == 2 * 128 * 64 + 2 * (
        4 * 64 * 64 + 3 * 64 * 48 + 4 * 64) + 64 + 64 + 1
    # the blocks this engine had are not looped
    assert seq.SequenceAlgorithm().seq_config().loop_steps == 1


def test_train_persist_load_predict_round_trip(trained):
    rt, (users, items, _), engine, params, instance, stages = trained
    (data,) = load_models(rt.models(), instance.id)
    record = data["training_record"]
    assert len(record["loss"]) == 2 and np.isfinite(record["loss"]).all()
    assert set(record["tensor_grad_norm"]) == set(data["params"])
    assert data["params"]["exit_gate"].shape == (64,)
    assert data["params"]["exit_gate_bias"].shape == ()
    # the exits' record: per step and exit, and per position of the first
    # step's rows
    assert record["loss_by_exit"].shape == record["exit_mass"].shape == (2, 4)
    np.testing.assert_allclose(record["loss_by_exit"], np.log(128), rtol=0.05)
    np.testing.assert_allclose(record["exit_mass"].sum(-1), 1.0, atol=1e-5)
    assert record["exit_entropy"].shape == (2,) and (record["exit_entropy"] > 1).all()
    assert record["loop_layer_applications"].tolist() == [16, 16]
    assert record["exit_probe"].shape == (2, 64, 4)
    assert record["carry_probe"].shape == (2, 64, 3)
    assert record["head_probe"].shape == (2, 32, 4)
    assert record["head_probe_state"].shape == (2, 32, 4, 64)
    assert not {"delta_rule_probe", "ssd_probe", "moe_probe"} & set(record)
    # the loss is the exits' expected loss less beta times the entropy
    np.testing.assert_allclose(
        record["loss"],
        (record["loss_by_exit"] * record["exit_mass"]).sum(-1)
        - 0.1 * record["exit_entropy"], rtol=2e-3)
    # the counter reaches the stage breakdown; the spans are tagged
    assert stages["counters"]["loop_layer_applications"] == 32
    assert "train.algorithm.loop" in stages
    from predictionio_tpu.obs.tracing import recent_traces

    root = next(t for t in recent_traces(5) if t.get("request_id") == instance.id)

    def find(node, name):
        if node["name"] == name:
            return node
        return next(
            (hit for c in node.get("children", []) if (hit := find(c, name))), None)

    loop = find(root, "seq.device_loop")
    assert (loop["block"], loop["loop_steps"], loop["exits"]) == (
        "sandwich_attention", 4, 4)
    assert find(root, "seq.fetch")["loop_steps"] == 4
    tokens = int((pd_segments(rt, params, engine) != seq.PAD_SEGMENT).sum())
    assert stages["counters"]["loop_tokens"] == tokens
    assert tokens <= stages["counters"]["loop_attention_pairs"] <= tokens * 65 // 2
    # predict serves from the LAST pass's state through the one head
    algo = engine.instantiate(params)[2][0]
    model = algo.load_persistent_model(EngineContext(storage=rt), data)
    assert model.config == algo.seq_config()
    answer = algo.predict(model, Query(user=f"u{users[0]}", num=5))
    assert len(answer.item_scores) == 5
    scores = [s.score for s in answer.item_scores]
    assert scores == sorted(scores, reverse=True)
    assert {s.item for s in answer.item_scores} <= {f"i{i}" for i in items}
    e = model.entity_vocab[f"u{users[0]}"]
    hist = model.history_tokens[model.history_offsets[e] : model.history_offsets[e + 1]]
    tokens = np.zeros((1, 64), np.int32)
    segments = np.full((1, 64), seq.PAD_SEGMENT, np.int32)
    tokens[0, : len(hist)], segments[0, : len(hist)] = hist, 0
    states = seqmodel.trunk(
        model.config, data["params"],
        seqmodel.embed(model.config, data["params"]["embed"], tokens), segments)[0]
    assert states.shape == (4, 1, 64, 64)
    want = np.asarray(
        data["params"]["head"] @ states[3, 0, len(hist) - 1])[: len(model.item_vocab)]
    top = np.argsort(-want, kind="stable")[:5]
    assert [s.item for s in answer.item_scores] == [
        model.item_vocab.inverse(int(j)) for j in top]
    np.testing.assert_allclose(scores, want[top], rtol=2e-2, atol=2e-3)
    # and not from an earlier pass
    early = np.asarray(data["params"]["head"] @ states[0, 0, len(hist) - 1])
    assert not np.allclose(early[top], want[top], rtol=2e-2, atol=2e-3)
