"""The sequence engine's mathematics at a tiny size on the CPU (ISSUE 26):
the chunkwise gated delta rule against its token-by-token recurrence, the
program against the plain reference (``benchmark/references/olmo_hybrid.py``
through ``seq_reference``), packed rows against the same segments alone, and
the share test of the model-configs guide (the two halves' parts add up to
the uncut reference)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import gdn, seqmodel
from seq_reference import (
    FULL, HALF, pack, random_weights, reference, seq_config)

SEGMENTS = (13, 27, 5, 11)  # boundaries at 13, 40, 45: inside chunks of 8


@pytest.fixture()
def f32_matmuls(monkeypatch):
    """The program's large products in float32, as the reference's are: what
    is left between the two is rounding, not the configuration's bf16."""
    monkeypatch.setattr(seqmodel, "MATMUL_DTYPE", jnp.float32)
    with jax.default_matmul_precision("highest"):
        yield


def _delta_inputs(seed=0, B=2, T=64, H=2, dk=8, dv=16):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, T, H, dk)).astype(np.float32)
    k = rng.standard_normal((B, T, H, dk)).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((B, T, H, dv)).astype(np.float32)
    g = (-0.3 * np.abs(rng.standard_normal((B, T, H)))).astype(np.float32)
    # beta in (0, 2): negative eigenvalues of I - beta k k^T
    beta = (2 / (1 + np.exp(-2 * rng.standard_normal((B, T, H))))).astype(np.float32)
    seg = np.zeros((B, T), np.int32)
    seg[0, 13:], seg[0, 40:] = 1, 2
    if B > 1:
        seg[1, 5:], seg[1, 60:] = 1, -1
    return tuple(jnp.asarray(x) for x in (q, k, v, g, beta)), jnp.asarray(seg)


def _token_by_token(args, seg):
    """The reference's recurrence, one segment at a time."""
    rows = []
    for b in range(seg.shape[0]):
        s = np.asarray(seg[b])
        cuts = [0] + (np.flatnonzero(np.diff(s)) + 1).tolist() + [len(s)]
        rows.append(jnp.concatenate([
            reference.delta_rule(*(x[b, lo:hi] for x in args))
            for lo, hi in zip(cuts, cuts[1:])]))
    return jnp.stack(rows)


@pytest.mark.parametrize("impl", ["scan", "interpret"])
def test_chunkwise_delta_rule_is_the_recurrence(impl):
    args, seg = _delta_inputs()
    assert float(args[4].max()) > 1.5  # beyond sigmoid's range: the factor 2
    with jax.default_matmul_precision("highest"):
        want = _token_by_token(args, seg)
        got = gdn.gated_delta_rule(*args, seg, chunk=8, impl=impl)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("impl", ["scan", "interpret"])
def test_chunkwise_delta_rule_gradient_is_the_recurrences(impl):
    args, seg = _delta_inputs(1)
    weights = jnp.asarray(np.random.default_rng(2).standard_normal(
        args[2].shape).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        want = jax.grad(
            lambda *a: (_token_by_token(a, seg) * weights).sum(), argnums=range(5))(*args)
        got = jax.grad(
            lambda *a: (gdn.gated_delta_rule(*a, seg, chunk=8, impl=impl) * weights).sum(),
            argnums=range(5))(*args)
    for name, a, b in zip("qkvgb", got, want):
        np.testing.assert_allclose(
            a, b, atol=2e-5 * float(jnp.abs(b).max()), err_msg=name)


@pytest.mark.parametrize("alike", [0.9, 1.0])
def test_chunkwise_delta_rule_holds_when_a_chunks_keys_are_alike(alike):
    """One chunk of 64 tokens whose keys are (nearly) one direction, beta near
    2 and no decay: the same item again and again.  ``(I + A)^-1`` by its
    Neumann product loses float32 here (its powers of A reach 1e17); the
    block-doubling form does not."""
    rng = np.random.default_rng(4)
    T, H, dk, dv = 64, 2, 8, 16
    base = rng.standard_normal((1, 1, H, dk))
    k = alike * base + (1 - alike) * rng.standard_normal((1, T, H, dk))
    k = (k / np.linalg.norm(k, axis=-1, keepdims=True)).astype(np.float32)
    q = rng.standard_normal((1, T, H, dk)).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    v = rng.standard_normal((1, T, H, dv)).astype(np.float32)
    g = np.full((1, T, H), -1e-3, np.float32)
    beta = np.full((1, T, H), 1.9, np.float32)
    args = tuple(jnp.asarray(x) for x in (q, k, v, g, beta))
    seg = jnp.zeros((1, T), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = _token_by_token(args, seg)
        got = gdn.gated_delta_rule(*args, seg, chunk=64, impl="scan")
    np.testing.assert_allclose(got, want, atol=1e-4 * float(jnp.abs(want).max()))


def _scan_with_a_bfloat16_state(W, U, Qg, P, Kd, a):
    """``gdn.chunk_scan`` with the carried state rounded to bfloat16 after
    every chunk: the precision below the one the configuration states."""
    def step(S, x):
        w, u, qg, p, kd, ac = x
        v_new = u - gdn._mm(w, S)
        o = gdn._mm(qg, S) + gdn._mm(p, v_new)
        S = ac[..., None, None] * S + gdn._mm(jnp.swapaxes(kd, -1, -2), v_new)
        return S.astype(jnp.bfloat16).astype(jnp.float32), o

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (W, U, Qg, P, Kd, a))
    S0 = jnp.zeros(W.shape[:2] + (W.shape[-1], U.shape[-1]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(step, S0, xs)[1], 0, 2)


def test_a_bfloat16_state_would_miss_the_recurrence_by_far():
    """The control twin of the two tests above: the same sequential pass with
    the carried state rounded to bfloat16 after every chunk is ~100 x their
    tolerance away."""
    args, seg = _delta_inputs()
    with jax.default_matmul_precision("highest"):
        want = _token_by_token(args, seg)
        o = _scan_with_a_bfloat16_state(*gdn.intra(*args, seg, 8))
    got = o.transpose(0, 2, 3, 1, 4).reshape(want.shape)
    assert float(jnp.abs(got - want).max()) > 50 * 2e-5 * float(jnp.abs(want).max())


def _probe_gap(monkeypatch, broken: bool) -> float:
    """Relative L2 gap between the delta-rule probe the row program records
    (in its stated precision: bf16 products) and the reference's, over one
    step of two packed rows from the seeded initial weights."""
    rng = np.random.default_rng(11)
    rows = [[rng.integers(0, 64, n).astype(np.int32) for n in ns]
            for ns in ((20, 30, 9), (64,))]
    cfg = seq_config(HALF, gdn_impl="scan")
    w = seqmodel.init_params(cfg, 3)
    if broken:
        monkeypatch.setattr(gdn, "chunk_scan", _scan_with_a_bfloat16_state)
    got = []
    for r in rows:
        tok, seg = pack(r, 64)
        got.append(jax.jit(lambda w, tok=tok, seg=seg: seqmodel.row_grads(
            cfg, w, jnp.asarray(tok)[None], jnp.asarray(seg)[None],
            jax.tree.map(jnp.zeros_like, w))[3]["delta_rule_probe"])(w)[0])
    hist = [s for r in rows for s in r]
    want = reference.first_step_probe(HALF, 3, hist, [[0, 1, 2], [3]], 64)
    assert want.shape == (2, 64, 2)
    real = np.isfinite(want)
    assert real.sum() == 2 * (20 + 30 + 9 + 64)  # NaN on the padding only
    err = (np.stack(got) - want)[real]
    return float(np.linalg.norm(err) / np.linalg.norm(want[real]))


def test_recorded_delta_rule_probe_is_the_recurrences(monkeypatch):
    """What the benchmark's check holds the state's precision by: the first
    layer's delta-rule output along the seeded vector, recorded by the row
    program under its bf16 products, is the reference's recurrence on the same
    inputs to float32 rounding; with the state carried in bfloat16 it is
    far off."""
    sound = _probe_gap(monkeypatch, broken=False)
    assert sound < 2e-5
    assert _probe_gap(monkeypatch, broken=True) > 20 * max(sound, 1e-5)


def test_linear_heads_go_in_groups_of_the_kernels_block():
    """Six heads run as two groups of three (``gdn.heads_per_block``): the
    groups' parts add up to the reference's layer over all six."""
    six = {**FULL, "linear_heads_held": 6}
    w = random_weights(six, 12)
    assert gdn.heads_per_block(6) == 3 and gdn.heads_per_block(15) == 5
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.standard_normal((24, 64)).astype(np.float32))
    p = seqmodel.layer_params(w, 0)
    with jax.default_matmul_precision("highest"):
        want = reference.linear_attention(six, reference.layer_tensors(w, 0), x)
    got, probe = seqmodel.linear_attention(
        dataclasses.replace(seq_config(six), gdn_impl="scan"), p, x[None],
        jnp.zeros((1, 24), jnp.int32))
    assert probe.shape == (1, 24, 6)
    cos = float(jnp.vdot(got[0], want) / (jnp.linalg.norm(got) * jnp.linalg.norm(want)))
    assert cos > 0.999  # bf16 products against float32


def test_a_segment_boundary_is_a_reset_not_a_decay():
    """The state after a boundary is that of the segment run alone: the first
    token of the second segment reads nothing of the first."""
    args, seg = _delta_inputs(3, B=1)
    alone = reference.delta_rule(*(x[0, 13:40] for x in args))
    packed = gdn.gated_delta_rule(*args, seg, chunk=8, impl="scan")[0, 13:40]
    np.testing.assert_allclose(packed, alone, atol=1e-5)


def _row(seed=1):
    rng = np.random.default_rng(seed)
    segs = [rng.integers(0, 64, n).astype(np.int32) for n in SEGMENTS]
    tok, seg = pack(segs, 64)
    return segs, jnp.asarray(tok)[None], jnp.asarray(seg)[None]


@pytest.fixture(scope="module")
def packed_step():
    """One packed row of four segments, the seeded initial weights, and the
    reference's loss and gradients over the segments one at a time."""
    segs, tok, seg = _row()
    w = reference.initial_weights(HALF, 3)

    def total(w):
        return sum(
            reference.segment_loss_sum(HALF, w, jnp.asarray(s), jnp.ones(len(s), bool))
            for s in segs)

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(total))(w)
    return segs, tok, seg, w, loss, grads


@pytest.mark.parametrize("impl", ["scan", "interpret"])
def test_program_is_the_reference_on_a_packed_step(f32_matmuls, packed_step, impl):
    """Loss and every tensor's gradient of one packed row against the
    reference, which sees the four segments one at a time."""
    segs, tok, seg, w, want_loss, want = packed_step
    cfg = seq_config(HALF, gdn_impl=impl)
    loss, count, got, _ = jax.jit(
        lambda w: seqmodel.row_grads(cfg, w, tok, seg, jax.tree.map(jnp.zeros_like, w))
    )(w)
    assert float(count) == sum(len(s) - 1 for s in segs)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert set(got) == set(want) == set(seqmodel.param_shapes(cfg))
    for name in want:
        gap = float(jnp.linalg.norm(got[name] - want[name]))
        assert gap <= 1e-4 * float(jnp.linalg.norm(want[name])), name


def test_program_in_its_stated_precision_stays_near_the_reference(packed_step):
    """bf16 products, f32 accumulation: the loss to 1e-3; the gradients keep
    their direction (a randomly initialised post-norm net amplifies rounding
    layer by layer: what the benchmark's limits are set from)."""
    segs, tok, seg, w, want_loss, want = packed_step
    loss, _, got, _ = jax.jit(lambda w: seqmodel.row_grads(
        seq_config(HALF), w, tok, seg, jax.tree.map(jnp.zeros_like, w)))(w)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-3)
    cos = [
        float(jnp.vdot(got[k], want[k])
              / (jnp.linalg.norm(got[k]) * jnp.linalg.norm(want[k])))
        for k in want
    ]
    assert min(cos) > 0.9


def test_packed_rows_equal_their_segments_alone(f32_matmuls):
    """No leak through the delta rule's state, the convolution or attention:
    the hidden states of a packed row are those of each segment in a row of
    its own."""
    segs, tok, seg = _row(4)
    w = random_weights(HALF, 5)
    cfg = seq_config(HALF)
    packed = seqmodel.hidden_states(cfg, w, tok, seg)[0]
    at = 0
    for s in segs:
        t1, s1 = pack([s], 64)
        alone = seqmodel.hidden_states(
            cfg, w, jnp.asarray(t1)[None], jnp.asarray(s1)[None])[0, : len(s)]
        np.testing.assert_allclose(packed[at : at + len(s)], alone, atol=2e-4)
        at += len(s)


def _halves(w_full):
    """The two chips' slices of the whole tiny model's tensors."""
    full_cfg, half_cfg = seq_config(FULL), seq_config(HALF)
    out = []
    for chip in (0, 1):
        w = {}
        for name, shape in seqmodel.param_shapes(half_cfg).items():
            t = w_full[name]
            index = tuple(
                slice(chip * h, (chip + 1) * h) if h != f else slice(None)
                for h, f in zip(shape, t.shape))
            w[name] = t[index]
        out.append(w)
    return full_cfg, half_cfg, out


@pytest.mark.parametrize("part", ["linear_attention", "full_attention", "mlp", "embed", "head"])
def test_the_two_shares_add_up_to_the_uncut_reference(f32_matmuls, part):
    """Model-configs guide, section 4: each chip computes the part of the
    result its own heads, MLP columns and vocabulary rows give; the parts of
    both chips add up to what the uncut reference gives for the whole layer
    (the head: its logits side by side)."""
    w_full = random_weights(FULL, 7)
    full_cfg, half_cfg, halves = _halves(w_full)
    rng = np.random.default_rng(8)
    T = 24
    x = jnp.asarray(rng.standard_normal((T, 64)).astype(np.float32))
    seg = jnp.zeros((1, T), jnp.int32)
    tokens = jnp.asarray(rng.integers(0, 128, T).astype(np.int32))
    layer = {"linear_attention": 0, "full_attention": 3, "mlp": 0}.get(part)
    parts = []
    for chip, w in enumerate(halves):
        cfg = dataclasses.replace(half_cfg, vocab_start=64 * chip)
        p = seqmodel.layer_params(w, layer) if layer is not None else None
        if part == "linear_attention":
            parts.append(seqmodel.linear_attention(cfg, p, x[None], seg)[0][0])
        elif part == "full_attention":
            parts.append(seqmodel.full_attention(cfg, p, x[None], seg)[0])
        elif part == "mlp":
            parts.append(seqmodel.mlp(cfg, p, x[None])[0])
        elif part == "embed":
            parts.append(seqmodel.embed(cfg, w["embed"], tokens))
        else:
            parts.append(x @ w["head"].T)
    pf = reference.layer_tensors(w_full, layer) if layer is not None else None
    if part == "linear_attention":
        want, got = reference.linear_attention(FULL, pf, x), parts[0] + parts[1]
    elif part == "full_attention":
        want, got = reference.full_attention(FULL, pf, x), parts[0] + parts[1]
    elif part == "mlp":
        want, got = reference.mlp(pf, x), parts[0] + parts[1]
    elif part == "embed":
        want, got = reference.embed(FULL, w_full["embed"], tokens), parts[0] + parts[1]
    else:
        want, got = x @ w_full["head"].T, jnp.concatenate(parts, axis=-1)
    np.testing.assert_allclose(got, want, atol=1e-4 * float(jnp.abs(want).max()))
    # and a share alone is NOT the layer: what the other chip holds is left out
    if part != "head":
        assert float(jnp.abs(parts[0] - want).max()) > 1e-3


def test_training_steps_are_the_references_adamw(f32_matmuls):
    """Two optimiser steps of two rows through ``train_steps`` against the
    reference's written-out AdamW over the same segments."""
    rng = np.random.default_rng(9)
    rows = [[rng.integers(0, 64, n).astype(np.int32) for n in ns]
            for ns in ((20, 30), (64,), (7, 9, 40), (33, 31))]
    packed = [pack(r, 64) for r in rows]
    tokens = jnp.asarray(np.stack([p[0] for p in packed]).reshape(2, 2, 64))
    segs = jnp.asarray(np.stack([p[1] for p in packed]).reshape(2, 2, 64))
    cfg = seq_config(HALF)
    opt = seqmodel.AdamW()
    state, acc = seqmodel.init_state(cfg, 3)
    w0 = {k: jnp.array(v) for k, v in state["params"].items()}
    state, acc, records, probes = seqmodel.train_steps(cfg, opt, state, acc, tokens, segs)
    # the first step's rows
    assert len(probes) == 2 and probes[0]["delta_rule_probe"].shape == (64, 2)
    hist = [s for r in rows for s in r]
    steps = [[0, 1, 2], [3, 4, 5, 6, 7]]
    ref_opt = {"lr": opt.lr, "beta1": opt.b1, "beta2": opt.b2, "eps": opt.eps,
               "weight_decay": opt.weight_decay}
    w_ref, ref_records = reference.replay(
        HALF, ref_opt, 3, hist, steps, 2, say=lambda *_: None)
    for got, want in zip(records, ref_records):
        assert float(got["loss"]) == pytest.approx(want["loss"], rel=1e-5)
        assert float(got["tokens"]) == want["tokens"]
        assert float(got["grad_norm"]) == pytest.approx(want["grad_norm"], rel=1e-4)
        for k, v in want["tensor_grad_probe"].items():
            assert float(got["tensor_grad_probe"][k]) == pytest.approx(
                v, abs=2e-4 * want["tensor_grad_norm"][k]), k
    for k, v in w_ref.items():
        moved = float(jnp.linalg.norm(v - w0[k]))
        assert float(jnp.linalg.norm(state["params"][k] - v)) <= 0.02 * moved + 1e-9, k
    assert float(acc["count"]) == 0 and int(state["t"]) == 2


def test_reference_groups_histories_as_the_preparator_packs_them():
    from predictionio_tpu.models.sequence.engine import pack_first_fit_decreasing

    rng = np.random.default_rng(10)
    lengths = rng.integers(1, 64, 40)
    rows = pack_first_fit_decreasing(lengths, 64)
    assert all(sum(lengths[j] for j in r) <= 64 for r in rows)
    assert sorted(j for r in rows for j in r) == list(range(40))
    assert reference.rows_of(lengths.tolist(), 64) == rows
    assert reference.steps_of(rows, 2) == [
        sum(rows[s : s + 2], []) for s in range(0, len(rows), 2)]
