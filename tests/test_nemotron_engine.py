"""The Nemotron-H stack (ISSUE 40) at a tiny size on the CPU: layers of ONE
sublayer each (``state_space``, ``grouped_attention``,
``shared_routed_experts``) against the plain reference
(``benchmark/references/nemotron_h.py`` through ``nemotron_reference``) --
loss, every gradient, the routing record, both probes and four AdamW steps --
the sigmoid routing rule and the two-matrix experts on their own, the share
test of the model-configs guide (eight shares' parts add up to the uncut
reference's layer), packed rows against their segments alone, and the stack
behind the DASE contract: ``pio train`` -> persisted model -> ``predict``."""

from __future__ import annotations

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nemotron_reference import (
    CHIPS, NINE, SHARE, TINY, WHOLE, pack, random_weights, reference, seq_config,
    share_of)
from predictionio_tpu.core import EngineContext
from predictionio_tpu.core.engine import resolve_engine_factory
from predictionio_tpu.core.persistence import load_models
from predictionio_tpu.core.workflow import run_train
from predictionio_tpu.models.recommendation.engine import Query
from predictionio_tpu.models.sequence import engine as seq
from predictionio_tpu.ops import moe, seqmodel
from test_sequence_engine import SPANS, _Stages, store  # noqa: F401  (a fixture)

SEGMENTS = (61, 90, 23, 40)  # 214 tokens of a row of 256


@pytest.fixture()
def f32_matmuls(monkeypatch):
    """The program's large products in float32, as the reference's are: what
    is left between the two is rounding, not the configuration's bf16."""
    monkeypatch.setattr(seqmodel, "MATMUL_DTYPE", jnp.float32)
    with jax.default_matmul_precision("highest"):
        yield


def _row(seed=2, lengths=SEGMENTS, row_len=256):
    rng = np.random.default_rng(seed)
    segs = [rng.integers(0, 512, n).astype(np.int32) for n in lengths]
    tok, seg = pack(segs, row_len)
    return segs, jnp.asarray(tok)[None], jnp.asarray(seg)[None]


def _reference_step(m, w, segs):
    def total(w):
        parts = [
            reference.segment_loss_sum(m, w, jnp.asarray(s), jnp.ones(len(s), bool))
            for s in segs]
        return sum(p[0] for p in parts), [p[1][0] for p in parts]

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(total, has_aux=True))(w)


# ---------------------------------------------------------------------------
# the routing rule and the two-matrix experts (ops/moe.py)


def test_a_selection_bias_moves_the_choice_and_not_the_weights():
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    s = np.asarray(jax.nn.sigmoid(logits))
    idx0, w0 = moe.route_sigmoid(logits, jnp.zeros(16), 3, 2.5)
    idx, w = moe.route_sigmoid(logits, bias, 3, 2.5)
    # the choice follows score + bias ...
    want = np.argsort(-(s + np.asarray(bias)), axis=-1, kind="stable")[:, :3]
    assert (np.sort(np.asarray(idx), -1) == np.sort(want, -1)).all()
    assert (np.sort(np.asarray(idx), -1) != np.sort(np.asarray(idx0), -1)).any()
    # ... the weights the UNBIASED scores of the chosen, normalised, times 2.5
    chosen = np.take_along_axis(s, np.asarray(idx), axis=-1)
    np.testing.assert_allclose(
        w, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w0).sum(-1), 2.5, rtol=1e-6)


def test_the_routers_gradient_is_the_dense_forms_and_none_reaches_the_bias():
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    logits = jax.random.normal(ks[0], (32, 16))
    bias = 0.2 * jax.random.normal(ks[1], (16,))
    y = jax.random.normal(ks[2], (32, 16))  # what each expert would add

    def routed(logits, bias):
        idx, w = moe.route_sigmoid(logits, bias, 3, 2.5)
        return jnp.sum(w * jnp.take_along_axis(y, idx, axis=-1))

    def dense(logits, bias):
        s = jax.nn.sigmoid(logits)
        rank = jnp.argsort(jnp.argsort(-(s + bias), axis=-1), axis=-1)
        chose = jax.lax.stop_gradient(rank < 3)
        w = 2.5 * jnp.where(chose, s, 0.0) / (
            jnp.sum(jnp.where(chose, s, 0.0), -1, keepdims=True) + 1e-20)
        return jnp.sum(w * y)

    got, got_b = jax.grad(routed, argnums=(0, 1))(logits, bias)
    want = jax.grad(dense)(logits, bias)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    assert not np.asarray(got_b).any()


def _relu2_dense(m, logits, bias, valid, up, down, k, start, scale):
    """Every held expert over every token, weighted by the token's choice."""
    idx, w = moe.route_sigmoid(logits, bias, k, scale)
    r = jnp.maximum(jnp.einsum("td,edf->tef", m, up), 0.0)
    y = jnp.einsum("tef,efd->ted", r * r, down)
    held = start + jnp.arange(up.shape[0])
    chose = jnp.sum(
        jnp.where(idx[:, :, None] == held, w[:, :, None], 0.0), axis=1) * valid[:, None]
    return jnp.einsum("te,ted->td", chose, y)


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_two_matrix_experts_are_the_dense_form_with_their_own_backward(
        impl, monkeypatch):
    """Forward and ``relu2_ffn``'s own backward against ``jax.grad`` of the
    dense form, at a width (24) that the weight gradient's blocks (16) do not
    divide, with an expert that gets no pair and padding that makes none."""
    monkeypatch.setattr(moe, "COVER_BLOCK", 16)
    N, D, F, E, held, k, start = 48, 32, 24, 8, 4, 3, 2
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    m = jax.random.normal(ks[0], (N, D))
    logits = jax.random.normal(ks[1], (N, E)).at[:, 3].set(-30.0)  # expert 3: no pair
    bias = 0.1 * jax.random.normal(ks[2], (E,))
    up = 0.3 * jax.random.normal(ks[3], (held, D, F))
    down = 0.3 * jax.random.normal(ks[4], (held, F, D))
    g = jax.random.normal(ks[5], (N, D))
    valid = jnp.arange(N) < 40

    def layer(m, logits, up, down):
        out, idx, counts = moe.experts_layer(
            m, logits, valid, None, up, down, k=k, start=start, tile=8,
            dtype=jnp.float32, impl=impl, bias=bias, scale=2.5)
        return jnp.sum(out * g), (out, idx, counts)

    def dense(m, logits, up, down):
        out = _relu2_dense(m, logits, bias, valid, up, down, k, start, 2.5)
        return jnp.sum(out * g), out

    with jax.default_matmul_precision("highest"):
        (_, (out, idx, counts)), got = jax.value_and_grad(
            layer, argnums=(0, 1, 2, 3), has_aux=True)(m, logits, up, down)
        (_, want_out), want = jax.value_and_grad(
            dense, argnums=(0, 1, 2, 3), has_aux=True)(m, logits, up, down)
    assert int(counts[1]) == 0 and int(counts.sum()) > 0  # expert 3 is held, idle
    assert not np.asarray(out[40:]).any()
    np.testing.assert_allclose(out, want_out, rtol=1e-4, atol=1e-5)
    for a, b, name in zip(got, want, ("m", "logits", "up", "down")):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)
    assert not np.asarray(got[2][1]).any()  # the idle expert's gradient: written, zero


def test_the_kinds_are_refused_without_their_sizes():
    base = dataclasses.asdict(seq_config(TINY))
    base["mup"] = seqmodel.MuP()
    with pytest.raises(ValueError, match="shared expert's columns"):
        seqmodel.SeqConfig(**{**base, "shared_cols": 0})
    with pytest.raises(ValueError, match="need the ssm_"):
        seqmodel.SeqConfig(**{**base, "ssm_heads": 0})
    with pytest.raises(ValueError, match="experts' sizes"):
        seqmodel.SeqConfig(**{**base, "experts": 0})
    with pytest.raises(ValueError, match="unknown layer types"):
        seqmodel.SeqConfig(**{**base, "layer_types": ("mamba",)})


# ---------------------------------------------------------------------------
# the program against the plain reference


@pytest.fixture(scope="module")
def packed_step():
    segs, tok, seg = _row()
    w = random_weights(TINY, 3)
    (loss, choices), grads = _reference_step(TINY, w, segs)
    return segs, tok, seg, w, loss, grads, choices


@pytest.mark.parametrize("impl", [("xla", "scan"), ("interpret", "interpret")])
def test_program_is_the_reference_on_a_packed_step(f32_matmuls, packed_step, impl):
    """Loss, the routed layers' choices and pairs, and every tensor's gradient
    of one packed row against the reference, which sees the four segments one
    at a time, runs the state space token by token and applies every held
    expert densely."""
    segs, tok, seg, w, want_loss, want, want_choices = packed_step
    cfg = seq_config(TINY, moe_impl=impl[0], ssm_impl=impl[1])
    loss, count, got, aux = jax.jit(
        lambda w: seqmodel.row_grads(cfg, w, tok, seg, jax.tree.map(jnp.zeros_like, w))
    )(w)
    assert float(count) == sum(len(s) - 1 for s in segs)
    # the experts' probe is no part of the row program (``experts_probe``)
    assert set(aux) == {"ssd_probe", "choices", "expert_pairs"}
    assert aux["ssd_probe"].shape == (1, 256, 4)
    assert aux["choices"].shape == (1, 2, 256, 3)  # two routed layers of five
    at = 0
    for s, c in zip(segs, want_choices):
        mine = np.sort(np.asarray(aux["choices"][0, :, at : at + len(s)]), -1)
        assert (mine == np.sort(np.asarray(c), -1)).all()
        at += len(s)
    chosen = np.concatenate([np.asarray(c) for c in want_choices], axis=1)
    held = (chosen >= 4) & (chosen < 12)
    assert aux["expert_pairs"].shape == (2, 8)
    assert aux["expert_pairs"].sum(-1).tolist() == held.sum((1, 2)).tolist()
    for e in range(8):  # pairs by expert
        assert aux["expert_pairs"][:, e].tolist() == (chosen == 4 + e).sum((1, 2)).tolist()
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert set(got) == set(want) == set(seqmodel.param_shapes(cfg))
    for name in want:
        gap = float(jnp.linalg.norm(got[name] - want[name]))
        assert gap <= 2e-4 * float(jnp.linalg.norm(want[name])) + 1e-9, name
    assert not np.asarray(got["layer1.router_bias"]).any()


def test_the_published_patterns_first_nine_layers_are_the_references(f32_matmuls):
    segs, tok, seg = _row(6, (100, 77, 60))
    w = random_weights(NINE, 4, gain=1.0)
    (want_loss, want_choices), want = _reference_step(NINE, w, segs)
    cfg = seq_config(NINE)
    assert [k[0] for k in cfg.layer_types].count("s") == 8  # 4 M + 4 E
    loss, _, got, aux = jax.jit(
        lambda w: seqmodel.row_grads(cfg, w, tok, seg, jax.tree.map(jnp.zeros_like, w))
    )(w)
    assert aux["choices"].shape == (1, 4, 256, 3) and aux["expert_pairs"].shape == (4, 8)
    same = total = 0
    at = 0
    for s, c in zip(segs, want_choices):
        mine = np.sort(np.asarray(aux["choices"][0, :, at : at + len(s)]), -1)
        same += int((mine == np.sort(np.asarray(c), -1)).all(-1).sum())
        total += mine.shape[0] * mine.shape[1]
        at += len(s)
    assert same >= total - 2  # a near-tie deep in the stack may fall either way
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-4)
    if same == total:
        for name in want:
            gap = float(jnp.linalg.norm(got[name] - want[name]))
            assert gap <= 1e-3 * float(jnp.linalg.norm(want[name])) + 1e-9, name


def test_program_in_its_stated_precision_stays_near_the_reference(packed_step):
    """bf16 products, f32 accumulation: the loss to 3e-3; the gradients keep
    their direction (a choice or two flips under the roundings)."""
    segs, tok, seg, w, want_loss, want, _ = packed_step
    loss, _, got, _ = jax.jit(lambda w: seqmodel.row_grads(
        seq_config(TINY), w, tok, seg, jax.tree.map(jnp.zeros_like, w)))(w)
    assert float(loss) == pytest.approx(float(want_loss), rel=3e-3)
    cos = [
        float(jnp.vdot(got[k], want[k])
              / (jnp.linalg.norm(got[k]) * jnp.linalg.norm(want[k])))
        for k in want if not k.endswith("router_bias")
    ]
    assert min(cos) > 0.8 and np.median(cos) > 0.99


def test_packed_rows_equal_their_segments_alone(f32_matmuls):
    """The convolution and the state restart at a segment, the mask holds,
    the experts' dispatch mixes no tokens, and there is no position to
    restart: a segment's loss and gradients do not depend on what else is in
    the row."""
    segs, tok, seg = _row(4)
    w = random_weights(TINY, 5)
    cfg = seq_config(TINY)
    zeros = jax.tree.map(jnp.zeros_like, w)
    grads = jax.jit(lambda w, t, s: seqmodel.row_grads(cfg, w, t, s, zeros)[:3])
    loss, count, packed = grads(w, tok, seg)
    hidden = seqmodel.hidden_states(cfg, w, tok, seg)[0]
    alone_loss, alone = 0.0, jax.tree.map(jnp.zeros_like, w)
    at = 0
    for s in segs:
        t1, s1 = (jnp.asarray(a)[None] for a in pack([s], 256))
        np.testing.assert_allclose(
            hidden[at : at + len(s)],
            seqmodel.hidden_states(cfg, w, t1, s1)[0, : len(s)], atol=2e-4)
        part, _, g = grads(w, t1, s1)
        alone_loss += float(part)
        alone = jax.tree.map(jnp.add, alone, g)
        at += len(s)
    assert float(loss) == pytest.approx(alone_loss, rel=1e-5)
    for name in packed:
        gap = float(jnp.linalg.norm(packed[name] - alone[name]))
        assert gap <= 1e-4 * float(jnp.linalg.norm(alone[name])) + 1e-9, name


def _probe_gaps(monkeypatch, fault: str | None) -> tuple[float, float, float]:
    """Relative L2 gaps between the probes the training programs record (in
    their stated precision: bf16 products) and the reference's, over one step
    of two packed rows from the seeded initial weights: the state space's, the
    experts' forward, and the widest of the experts' backward's three."""
    rng = np.random.default_rng(11)
    rows = [[rng.integers(0, 512, n).astype(np.int32) for n in ns]
            for ns in ((80, 100, 40), (256,))]
    cfg = seq_config(TINY)
    w = seqmodel.init_params(cfg, 3)
    for name, v in reference.initial_weights(TINY, 3).items():
        np.testing.assert_allclose(w[name], v, rtol=1e-6, err_msg=name)  # one rule, twice
    rounded = lambda f: lambda *a, **kw: f(*a, **kw).astype(  # noqa: E731
        jnp.bfloat16).astype(jnp.float32)
    if fault == "bf16_accumulation":
        monkeypatch.setattr(moe, "gmm", rounded(moe.gmm))
    elif fault == "bf16_accumulation_backward":
        monkeypatch.setattr(moe, "tgmm", rounded(moe.tgmm))
    elif fault == "no_shared_expert":
        monkeypatch.setattr(seqmodel, "shared_expert", lambda p, h: jnp.zeros_like(h))
    seqmodel.experts_probe.clear_cache()
    got = {"ssd_probe": [], "moe_probe": []}
    for r in rows:
        tok, seg = (jnp.asarray(a) for a in pack(r, 256))
        aux = jax.jit(lambda w, tok=tok, seg=seg: seqmodel.row_grads(
            cfg, w, tok[None], seg[None], jax.tree.map(jnp.zeros_like, w))[3])(w)
        mine = seqmodel.experts_probe(
            cfg, len(got["moe_probe"]) == 0, w["embed"],
            seqmodel.layer_params(w, 1), tok, seg)
        got["ssd_probe"].append(aux["ssd_probe"][0])
        got["moe_probe"].append(mine["moe_probe"])
        if len(got["moe_probe"]) == 1:
            backward = mine["moe_grad_probe"]
        else:  # the backward is made for the first row alone
            assert not any(np.asarray(v).any() for v in mine["moe_grad_probe"].values())
    hist = [s for r in rows for s in r]
    ssd, routed, grads = reference.first_step_probes(
        TINY, 3, hist, [[0, 1, 2], [3]], 256)
    assert ssd.shape == (2, 256, 4) and routed.shape == (2, 256, 1)
    assert {k: v.shape for k, v in grads.items()} == {
        "up": (8, 24), "down": (8, 24), "input": (64,)}
    gaps = []
    for name, want in (("ssd_probe", ssd), ("moe_probe", routed)):
        real = np.isfinite(want)
        assert real.sum() == (80 + 100 + 40 + 256) * want.shape[-1]  # NaN on padding only
        err = (np.stack(got[name]) - want)[real]
        gaps.append(float(np.linalg.norm(err) / np.linalg.norm(want[real])))
    gaps.append(max(
        float(np.linalg.norm(np.asarray(backward[k]) - v) / np.linalg.norm(v))
        for k, v in grads.items()))
    return tuple(gaps)


def test_recorded_probes_are_the_references(monkeypatch):
    """What the benchmark's check holds the recurrence and the expert path
    by: the first layer's ``S_t C_t``, the first routed layer's ``f`` on the
    normed embedding rows and that layer's experts' gradients on the first
    row, recorded under the programs' bf16 products, are the reference's on
    the same inputs in the same products; the grouped products' results
    rounded to bfloat16 (the forward's, or the weight gradients' alone), or
    the shared expert left out, are far off."""
    ssd, routed, backward = _probe_gaps(monkeypatch, None)
    assert ssd < 1e-4 and routed < 1e-3 and backward < 1e-3
    assert _probe_gaps(monkeypatch, "bf16_accumulation")[1] > 2 * max(routed, 5e-4)
    monkeypatch.undo()
    forward, faulty = _probe_gaps(monkeypatch, "bf16_accumulation_backward")[1:]
    assert forward == routed and faulty > 2 * max(backward, 5e-4)
    monkeypatch.undo()
    assert _probe_gaps(monkeypatch, "no_shared_expert")[1] > 0.1
    monkeypatch.undo()
    seqmodel.experts_probe.clear_cache()


def test_reference_experts_backward_written_out_is_the_dense_forms_gradient():
    """``moe_grad_probe`` writes the experts' backward out (so that it can
    round where the stated precision rounds): with float32 products it is
    ``jax.grad`` of the dense form's probe sum."""
    rng = np.random.default_rng(12)
    tok = jnp.asarray(rng.integers(0, 512, 96).astype(np.int32))
    w = random_weights(TINY, 4)
    w["layer1.router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    p = reference.layer_tensors(w, 1)
    D = TINY["hidden_size"]
    key = jax.random.PRNGKey(reference.PROBE_SEED)
    r = jax.random.normal(jax.random.fold_in(key, 2 ** 20 + 2), (D,))
    q = jax.random.normal(jax.random.fold_in(key, 2 ** 20 + 3), (D,))
    with jax.default_matmul_precision("highest"):
        x0 = reference.embed(TINY, w["embed"], tok)
        h = reference.rmsnorm(x0, p["input_norm"], TINY["layer_norm_epsilon"])
        idx, wt, _ = reference.route(TINY, h @ p["router"], p["router_bias"])
        wt = wt * (jnp.arange(96) < 70)[:, None]

        def total(h, up, down):
            return jnp.sum(reference.routed_experts(
                TINY, {"experts_up": up, "experts_down": down}, h, idx, wt) @ r)

        dh, dup, ddown = jax.grad(total, argnums=(0, 1, 2))(
            h, p["experts_up"], p["experts_down"])
        want = {"up": jnp.einsum("edf,d->ef", dup, q),
                "down": jnp.einsum("efd,d->ef", ddown, q), "input": dh.sum(0)}
        monkey = pytest.MonkeyPatch()
        monkey.setattr(reference, "bf16_product", jnp.matmul)
        try:
            got = reference.moe_grad_probe(TINY, w, tok, 70)
        finally:
            monkey.undo()
    for name in want:
        np.testing.assert_allclose(got[name], want[name], rtol=1e-4, atol=1e-6,
                                   err_msg=name)


@pytest.mark.parametrize(
    "part", ["experts", "state_space", "attention", "stack", "embed", "head"])
def test_the_eight_shares_add_up_to_the_uncut_reference(f32_matmuls, part):
    """Model-configs guide, section 4: each chip computes the part of the
    result its own group's heads, its query heads, its experts, its columns
    of the shared expert and its vocabulary rows give; the eight chips' parts
    add up to what the uncut reference gives.  The router's scores, the
    selection bias and the norms are what every chip computes alike; the
    residual is counted once.  The gated norm's group is ONE chip's channels,
    so no statistic of the state space crosses chips."""
    w_whole = random_weights(WHOLE, 7)
    shares = [share_of(w_whole, chip) for chip in range(CHIPS)]
    cfgs = [seq_config({**SHARE, "expert_start": 2 * c, "vocab_start": 64 * c})
            for c in range(CHIPS)]
    rng = np.random.default_rng(8)
    T = 40
    x = jnp.asarray(rng.standard_normal((T, 64)).astype(np.float32))
    seg = jnp.zeros((1, T), jnp.int32)
    tokens = jnp.asarray(rng.integers(0, 512, T).astype(np.int32))
    layer_of = {"state_space": 0, "experts": 1, "attention": 2}

    def f_of(i, cfg, w, x):
        """What chip's share of layer i adds to the stream."""
        kind = cfg.layer_types[i]
        return seqmodel.sublayer(
            cfg, kind, seqmodel.layer_params(w, i), x[None], seg)[0][0] - x

    if part in layer_of:
        i = layer_of[part]
        for w in shares:  # alike on every chip
            for name in ("input_norm", "router", "router_bias"):
                if f"layer{i}.{name}" in w:
                    np.testing.assert_array_equal(
                        w[f"layer{i}.{name}"], w_whole[f"layer{i}.{name}"])
        parts = [f_of(i, cfg, w, x) for cfg, w in zip(cfgs, shares)]
        want = reference.block(
            WHOLE, WHOLE["layer_kinds"][i], reference.layer_tensors(w_whole, i), x)[0] - x
    elif part == "stack":
        # the three layers in turn, each layer's parts summed (the
        # deployment's all-reduce) onto the stream
        got = want = x
        for i, kind in enumerate(WHOLE["layer_kinds"]):
            got = got + sum(f_of(i, cfg, w, got) for cfg, w in zip(cfgs, shares))
            want = reference.block(
                WHOLE, kind, reference.layer_tensors(w_whole, i), want)[0]
        np.testing.assert_allclose(got, want, atol=2e-4 * float(jnp.abs(want).max()))
        return
    elif part == "embed":
        parts = [seqmodel.embed(cfg, w["embed"], tokens)
                 for cfg, w in zip(cfgs, shares)]
        want = reference.embed(WHOLE, w_whole["embed"], tokens)
    else:
        parts = [x @ w["head"].T for w in shares]
        want = x @ w_whole["head"].T
    got = jnp.concatenate(parts, axis=-1) if part == "head" else sum(parts)
    np.testing.assert_allclose(got, want, atol=1e-4 * float(jnp.abs(want).max()))
    # and a share alone is NOT the layer: what the other chips hold is left out
    if part != "head":
        assert float(jnp.abs(parts[1] - want).max()) > 1e-3


def test_training_steps_are_the_references_adamw(f32_matmuls):
    """Four optimiser steps of one row through ``train_steps`` against the
    reference's written-out AdamW over the same segments, with both probes'
    shapes and the routing counters the step's accumulator summed beside the
    gradients over the TWO routed layers of five."""
    rng = np.random.default_rng(9)
    rows = [[rng.integers(0, 512, n).astype(np.int32) for n in ns]
            for ns in ((90, 120), (256,), (30, 50, 100), (128, 100))]
    packed = [pack(r, 256) for r in rows]
    tokens = jnp.asarray(np.stack([p[0] for p in packed]).reshape(4, 1, 256))
    segs = jnp.asarray(np.stack([p[1] for p in packed]).reshape(4, 1, 256))
    cfg = seq_config(TINY)
    opt = seqmodel.AdamW()
    state, acc = seqmodel.init_state(cfg, 3)
    assert acc["expert_pairs"].shape == (2, 8)
    # a seeded non-zero selection bias: held through the steps, never moved
    bias = {f"layer{i}.router_bias": np.asarray(0.05 * jax.random.normal(
        jax.random.PRNGKey(i), (16,))) for i in (1, 4)}
    state["params"].update({k: jnp.asarray(b) for k, b in bias.items()})  # donated
    state, acc, records, probes = seqmodel.train_steps(cfg, opt, state, acc, tokens, segs)
    assert len(probes) == 1
    assert probes[0]["ssd_probe"].shape == (256, 4)
    assert probes[0]["moe_probe"].shape == (256, 1)
    assert {k: v.shape for k, v in probes[0]["moe_grad_probe"].items()} == {
        "up": (8, 24), "down": (8, 24), "input": (64,)}
    assert probes[0]["choices"].shape == (2, 256, 3)
    hist = [s for r in rows for s in r]
    steps, at = [], 0
    for r in rows:
        steps.append(list(range(at, at + len(r))))
        at += len(r)
    ref_opt = {"lr": opt.lr, "beta1": opt.b1, "beta2": opt.b2, "eps": opt.eps,
               "weight_decay": opt.weight_decay}
    initial = reference.initial_weights

    def with_bias(m, seed):
        return {**initial(m, seed), **{k: jnp.asarray(b) for k, b in bias.items()}}

    reference.initial_weights = with_bias
    try:
        w, ref_records, first = reference.replay(
            TINY, ref_opt, 3, hist, steps, 4, say=lambda s: None)
    finally:
        reference.initial_weights = initial
    for got, want in zip(records, ref_records):
        assert float(got["loss"]) == pytest.approx(want["loss"], rel=1e-5)
        assert float(got["tokens"]) == want["tokens"]
        assert got["moe_pairs_held"].tolist() == want["moe_pairs_held"]
        assert got["moe_pairs_total"].tolist() == [want["moe_pairs_total"]] * 2
        assert got["moe_expert_pairs"].sum(-1).tolist() == want["moe_pairs_held"]
        assert float(got["grad_norm"]) == pytest.approx(want["grad_norm"], rel=1e-3)
        assert float(got["tensor_grad_norm"]["layer1.router_bias"]) == 0.0
    at = 0
    for j in steps[0]:
        mine = np.asarray(probes[0]["choices"][:, at : at + len(hist[j])])
        assert (np.sort(mine, -1) == np.sort(first[j][0], -1)).all()
        assert first[j][1].shape == (2, len(hist[j])) and (first[j][1] >= 0).all()
        at += len(hist[j])
    start = with_bias(TINY, 3)
    for name, v in w.items():
        moved = float(jnp.linalg.norm(v - start[name]))
        gap = float(jnp.linalg.norm(state["params"][name] - v))
        assert gap <= 0.05 * moved + 1e-7, name
    for name, b in bias.items():  # no gradient, no decay: where it was
        np.testing.assert_array_equal(state["params"][name], b)
    assert int(acc["pairs_total"]) == 0 and not np.asarray(acc["expert_pairs"]).any()


# ---------------------------------------------------------------------------
# behind the DASE contract

KINDS = ["state_space", "shared_routed_experts", "state_space",
         "grouped_attention", "shared_routed_experts"]
VARIANT = {
    "datasource": {"params": {"appName": "seq"}},
    "preparator": {"params": {
        "rowLen": 128, "maxLen": 128, "rowsPerStep": 2, "vocabSize": 128}},
    "algorithms": [{"name": "hybrid", "params": {
        "hiddenSize": 64, "layerTypes": KINDS, "numAttentionHeads": 4,
        "numKeyValueHeads": 2, "headDim": 16, "mambaNHeads": 4, "mambaNGroups": 2,
        "mambaDHead": 8, "mambaDState": 16, "mambaDConv": 4, "mambaChunkSize": 16,
        "moeNumPrimaryExperts": 16, "moeExpertsHeld": 4, "moeExpertStart": 0,
        "moeNumActivePrimaryExperts": 3, "moeFfnHiddenSize": 24,
        "moeSharedExpertColumns": 40, "routedScalingFactor": 2.5, "vocabSize": 128,
        "rmsNormEps": 1e-5, "rowsPerStep": 2, "stepsPerRetrain": 2}}],
}
#: the reference's group for VARIANT
MODEL = {**TINY, "experts_held": 4, "expert_start": 0, "vocab_rows_held": 128}


@pytest.fixture()
def trained(store, monkeypatch):  # noqa: F811
    configured = seq.SequenceAlgorithm.seq_config
    monkeypatch.setattr(
        seq.SequenceAlgorithm, "seq_config",
        lambda self: dataclasses.replace(configured(self), moe_tile=8))
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


def test_engine_json_reaches_the_stacks_configuration():
    engine = resolve_engine_factory("sequence")()
    algo = engine.instantiate(engine.params_from_json(VARIANT))[2][0]
    cfg = algo.seq_config()
    assert list(cfg.layer_types) == KINDS
    assert (cfg.heads, cfg.kv_heads, cfg.head_dim) == (4, 2, 16)
    assert (cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_head_dim, cfg.ssm_state,
            cfg.ssm_chunk) == (4, 2, 8, 16, 16)
    assert (cfg.experts, cfg.experts_held, cfg.experts_per_token, cfg.expert_width,
            cfg.shared_cols, cfg.routed_scale) == (16, 4, 3, 24, 40, 2.5)
    assert cfg.mup == seqmodel.MuP() and cfg.token_multiple == 128
    shapes = seqmodel.param_shapes(cfg)
    assert shapes["layer1.router"] == (64, 16) and shapes["layer4.router_bias"] == (16,)
    assert shapes["layer1.experts_up"] == (4, 64, 24)
    assert shapes["layer1.shared_down"] == (40, 64)
    assert shapes["layer0.ssm_in"] == (64, 2 * 32 + 2 * 32 + 4)
    assert shapes["layer3.k"] == (64, 32)
    # ONE sublayer a layer: no gate, no second norm, no MLP beside a mixer
    for name in ("layer1.experts_gate", "layer0.q", "layer0.pre_ff_norm",
                 "layer3.post_norm", "layer3.gate"):
        assert name not in shapes, name
    assert not seqmodel.decays("layer1.router_bias") and seqmodel.decays("layer1.router")


def test_the_four_older_blocks_keep_their_configuration():
    """The new options default to what the four blocks ran: their ``SeqConfig``
    is the one they had (no shared column, scale 1)."""
    olmo = seq.SequenceAlgorithm().seq_config()
    assert (olmo.shared_cols, olmo.routed_scale) == (0, 1.0)
    assert seq._loop_tags(olmo) == {}
    assert seqmodel.MOE_KINDS == ("global_attention_moe", "sliding_attention_moe")


def test_train_persist_load_predict_round_trip(trained):
    rt, (users, items, _), engine, params, instance, _ = trained
    (data,) = load_models(rt.models(), instance.id)
    record = data["training_record"]
    assert len(record["loss"]) == 2 and np.isfinite(record["loss"]).all()
    assert record["loss"][0] == pytest.approx(np.log(128), rel=0.02)
    assert set(record["tensor_grad_norm"]) == set(data["params"])
    assert data["params"]["layer4.experts_down"].shape == (4, 24, 64)
    # BOTH probes of the first step's rows, and the two routed layers' choices
    assert record["ssd_probe"].shape == (2, 128, 4)
    assert record["moe_probe"].shape == (2, 128, 1)
    # the experts' backward on the FIRST row alone
    assert record["moe_grad_probe"]["up"].shape == (2, 4, 24)
    assert record["moe_grad_probe"]["up"][0].any()
    assert not record["moe_grad_probe"]["up"][1].any()
    assert record["choices"].shape == (2, 2, 128, 3)
    assert record["choices"].min() >= 0 and record["choices"].max() < 16
    # the routing counters, a step and ROUTED layer (two of the five layers)
    assert record["moe_expert_pairs"].shape == (2, 2, 4)
    assert (record["moe_pairs_held"] == record["moe_expert_pairs"].sum(-1)).all()
    tokens = np.asarray(record["moe_pairs_total"]) // 3
    assert (tokens[:, 0] == tokens[:, 1]).all() and tokens.min() > 0
    share = record["moe_pairs_held"].sum() / record["moe_pairs_total"].sum()
    assert 0.1 < share < 0.4  # a quarter of the experts held
    assert not data["params"]["layer1.router_bias"].any()
    algo = engine.instantiate(params)[2][0]
    model = algo.load_persistent_model(EngineContext(storage=rt), data)
    assert model.config == algo.seq_config()
    seen = {f"i{i}" for i in items}
    answer = algo.predict(model, Query(user=f"u{users[0]}", num=5))
    assert len(answer.item_scores) == 5
    scores = [s.score for s in answer.item_scores]
    assert scores == sorted(scores, reverse=True)
    assert {s.item for s in answer.item_scores} <= seen  # never a padding row
    # the answer against the PLAIN reference over the history alone
    e = model.entity_vocab[f"u{users[0]}"]
    hist = model.history_tokens[model.history_offsets[e] : model.history_offsets[e + 1]]
    w = {k: jnp.asarray(v) for k, v in data["params"].items()}
    with jax.default_matmul_precision("highest"):
        h, choices, _ = reference.final_hidden(MODEL, w, jnp.asarray(hist))
        want = np.asarray(w["head"] @ h[-1])[: len(model.item_vocab)]
    assert choices.shape == (2, len(hist), 3)
    for s in answer.item_scores:
        assert s.score == pytest.approx(want[model.item_vocab[s.item]], abs=5e-3)
    assert max(scores) == pytest.approx(want.max(), abs=5e-3)


def test_counters_and_tags_reach_the_stages_extra_and_the_trace_ring(trained):
    from predictionio_tpu.obs.tracing import recent_traces

    rt, _, _, _, instance, stages = trained
    for name in SPANS + ("train.algorithm.hybrid", "train.persist.save_models"):
        assert name in stages and stages[name] >= 0, name
    counters = stages["counters"]
    (data,) = load_models(rt.models(), instance.id)
    record = data["training_record"]
    assert counters["moe_routed_layers"] == 2 and counters["moe_experts_held"] == 4
    assert counters["moe_pairs_total"] == int(record["moe_pairs_total"].sum())
    assert counters["moe_pairs_held"] == int(record["moe_pairs_held"].sum())
    # the pair buffers' rows in live tiles (the buffer work done) of all they
    # have: a step and layer in the record, the retrain's sums and share here
    assert record["moe_rows_live"].shape == record["moe_rows_planned"].shape == (2, 2)
    assert counters["moe_rows_live"] == int(record["moe_rows_live"].sum()) > 0
    assert counters["moe_rows_planned"] == int(record["moe_rows_planned"].sum())
    assert counters["moe_rows_live_pct"] == pytest.approx(
        100.0 * counters["moe_rows_live"] / counters["moe_rows_planned"])
    assert (record["moe_rows_live"] > 0).all()  # a tile an expert at the least
    assert (record["moe_rows_live"] <= record["moe_rows_planned"]).all()
    for s in range(2):
        for layer in range(2):
            at = f".step{s}.layer{layer}"
            assert counters["moe_pairs_held" + at] == record["moe_pairs_held"][s, layer]
            assert counters["moe_expert_pairs_max" + at] == record[
                "moe_expert_pairs"][s, layer].max()
    root = next(t for t in recent_traces(5) if t.get("request_id") == instance.id)

    def find(node, name):
        if node["name"] == name:
            return node
        return next(
            (hit for c in node.get("children", []) if (hit := find(c, name))), None)

    assert find(root, "seq.fetch")["counters"] == counters
    loop = find(root, "seq.device_loop")
    assert loop["block"] == "state_space+shared_routed_experts+grouped_attention"
    assert (loop["layers_state_space"], loop["layers_attention"],
            loop["layers_experts"]) == (2, 1, 2)
