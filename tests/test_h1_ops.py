"""The Falcon-H1 block's mathematics at a tiny size on the CPU (ISSUE 30): the
chunked state space against its token-by-token recurrence, the program
against the plain reference (``benchmark/references/falcon_h1.py`` through
``h1_reference``), packed rows against the same segments alone, and the share
test of the model-configs guide (the four shares' parts add up to the uncut
reference, the gated norm's statistic exchanged over a mapped axis)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h1_reference import (
    CHIPS, SHARE, WHOLE, pack, random_weights, reference, seq_config, share_of)
from predictionio_tpu.ops import seqmodel, ssd

SEGMENTS = (13, 27, 5, 11)  # boundaries at 13, 40, 45: one AT a chunk's edge


@pytest.fixture()
def f32_matmuls(monkeypatch):
    """The program's large products in float32, as the reference's are: what
    is left between the two is rounding, not the configuration's bf16."""
    monkeypatch.setattr(seqmodel, "MATMUL_DTYPE", jnp.float32)
    with jax.default_matmul_precision("highest"):
        yield


def _ssd_inputs(seed=0, B=2, T=64, H=4, P=8, G=2, N=16, dt_max=0.5, a_max=16.0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((B, T, H, P)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(dt_max), (B, T, H))).astype(np.float32)
    a = -rng.uniform(1.0, a_max, H).astype(np.float32)
    b = rng.standard_normal((B, T, G, N)).astype(np.float32)
    c = rng.standard_normal((B, T, G, N)).astype(np.float32)
    seg = np.zeros((B, T), np.int32)
    seg[0, 13:], seg[0, 40:] = 1, 2  # inside a chunk of 8, and at its edge
    if B > 1:
        seg[1, 8:], seg[1, 16:], seg[1, 60:] = 1, 2, -1
    return tuple(jnp.asarray(t) for t in (x, dt, a, b, c)), jnp.asarray(seg)


def _token_by_token(args, seg):
    """The reference's recurrence, one segment at a time."""
    x, dt, a, b, c = args
    rows = []
    for r in range(seg.shape[0]):
        s = np.asarray(seg[r])
        cuts = [0] + (np.flatnonzero(np.diff(s)) + 1).tolist() + [len(s)]
        rows.append(jnp.concatenate([
            reference.selective_scan(x[r, lo:hi], dt[r, lo:hi], a, b[r, lo:hi], c[r, lo:hi])
            for lo, hi in zip(cuts, cuts[1:])]))
    return jnp.stack(rows)


@pytest.mark.parametrize("dt_max", [0.5, 30.0])
@pytest.mark.parametrize("impl", ["scan", "interpret"])
def test_chunked_state_space_is_the_recurrence(impl, dt_max):
    """``dt_max`` 30: Delta A down to -480 a token, so a chunk's own decay
    (and most of ``L``) underflows to exactly 0."""
    args, seg = _ssd_inputs(dt_max=dt_max)
    if dt_max > 1:
        assert float(jnp.exp(jnp.sum((args[1] * args[2])[0, :8], axis=0)).min()) == 0.0
    with jax.default_matmul_precision("highest"):
        want = _token_by_token(args, seg)
        got = ssd.ssd(*args, seg, chunk=8, impl=impl)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("impl", ["scan", "interpret"])
def test_chunked_state_space_gradient_is_the_recurrences(impl):
    args, seg = _ssd_inputs(1)
    weights = jnp.asarray(np.random.default_rng(2).standard_normal(
        args[0].shape).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        want = jax.grad(
            lambda *p: (_token_by_token(p, seg) * weights).sum(), argnums=range(5))(*args)
        got = jax.grad(
            lambda *p: (ssd.ssd(*p, seg, chunk=8, impl=impl) * weights).sum(),
            argnums=range(5))(*args)
    for name, g, w in zip(("x", "dt", "a", "b", "c"), got, want):
        np.testing.assert_allclose(
            g, w, atol=2e-5 * float(jnp.abs(w).max()), err_msg=name)


def test_the_kernels_fetch_b_and_c_once_a_group():
    """Eight heads in two groups: a grid step works on four heads of ONE group
    (``heads_per_block``), and the groups' B / C gradients add up over their
    heads' blocks."""
    assert [ssd.heads_per_block(n) for n in (8, 4, 2, 3)] == [4, 4, 2, 1]
    args, seg = _ssd_inputs(5, B=1, H=16, G=2)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda b, c: ssd.ssd(
            *args[:3], b, c, seg, 8, "scan").sum(), argnums=(0, 1))(*args[3:])
        got = jax.grad(lambda b, c: ssd.ssd(
            *args[:3], b, c, seg, 8, "interpret").sum(), argnums=(0, 1))(*args[3:])
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.abs(w).max()))


def _scan_with_a_bfloat16_state(cc, bc, xe, ac):
    """``ssd.chunk_scan`` with the carried state rounded to bfloat16 after
    every chunk: the precision below the one the configuration states."""
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


def test_a_bfloat16_state_would_miss_the_recurrence_by_far(monkeypatch):
    """The control twin of the tests above: the same sequential pass with the
    carried state rounded to bfloat16 after every chunk is ~50 x their
    tolerance away."""
    args, seg = _ssd_inputs(dt_max=0.05, a_max=2.0)  # a state that lasts
    with jax.default_matmul_precision("highest"):
        want = _token_by_token(args, seg)
        monkeypatch.setattr(ssd, "chunk_scan", _scan_with_a_bfloat16_state)
        got = ssd.ssd(*args, seg, chunk=8, impl="scan")
    assert float(jnp.abs(got - want).max()) > 50 * 2e-5 * float(jnp.abs(want).max())


def test_a_segment_boundary_is_a_reset_not_a_decay():
    args, seg = _ssd_inputs(3, B=1)
    x, dt, a, b, c = args
    alone = reference.selective_scan(x[0, 13:40], dt[0, 13:40], a, b[0, 13:40], c[0, 13:40])
    packed = ssd.ssd(*args, seg, chunk=8, impl="scan")[0, 13:40]
    np.testing.assert_allclose(packed, alone, atol=1e-5 * float(jnp.abs(alone).max()))


def test_positions_restart_at_every_segment():
    _, seg = pack([np.arange(n) for n in SEGMENTS], 64)
    pos = np.asarray(seqmodel.segment_positions(jnp.asarray(seg)[None]))[0]
    want = np.concatenate([np.arange(n) for n in SEGMENTS + (64 - sum(SEGMENTS),)])
    assert pos.tolist() == want.tolist()


def test_rotary_attention_sees_only_the_distance():
    """Why "positions that run on across segments" is no fault this check
    could catch: rotary scores depend on ``pos_t - pos_s`` alone, so a segment
    whose positions start at 40 attends as one whose positions start at 0."""
    rng = np.random.default_rng(6)
    q, k = (jnp.asarray(rng.standard_normal((1, 12, 2, 16)).astype(np.float32))
            for _ in range(2))
    pos = jnp.arange(12)[None]

    def scores(p):
        return jnp.einsum(
            "bthd,bshd->bhts", seqmodel.rope(q, p, 1e4), seqmodel.rope(k, p, 1e4))

    np.testing.assert_allclose(scores(pos), scores(pos + 40), atol=2e-5)
    assert float(jnp.abs(scores(pos) - scores(pos * 0)).max()) > 0.1


def _row(seed=1):
    rng = np.random.default_rng(seed)
    segs = [rng.integers(0, 32, n).astype(np.int32) for n in SEGMENTS]
    tok, seg = pack(segs, 64)
    return segs, jnp.asarray(tok)[None], jnp.asarray(seg)[None]


@pytest.fixture(scope="module")
def packed_step():
    """One packed row of four segments, random weights, and the reference's
    loss and gradients over the segments one at a time."""
    segs, tok, seg = _row()
    w = random_weights(SHARE, 3)

    def total(w):
        return sum(
            reference.segment_loss_sum(SHARE, w, jnp.asarray(s), jnp.ones(len(s), bool))
            for s in segs)

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(total))(w)
    return segs, tok, seg, w, loss, grads


@pytest.mark.parametrize("impl", ["scan", "interpret"])
def test_program_is_the_reference_on_a_packed_step(f32_matmuls, packed_step, impl):
    """Loss and every tensor's gradient of one packed row against the
    reference, which sees the four segments one at a time."""
    segs, tok, seg, w, want_loss, want = packed_step
    cfg = seq_config(SHARE, ssm_impl=impl)
    loss, count, got, probe = jax.jit(
        lambda w: seqmodel.row_grads(cfg, w, tok, seg, jax.tree.map(jnp.zeros_like, w))
    )(w)
    assert float(count) == sum(len(s) - 1 for s in segs)
    assert probe["ssd_probe"].shape == (1, 64, SHARE["ssm_heads_held"])
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert set(got) == set(want) == set(seqmodel.param_shapes(cfg))
    for name in want:
        gap = float(jnp.linalg.norm(got[name] - want[name]))
        assert gap <= 1e-4 * float(jnp.linalg.norm(want[name])), name


def test_program_in_its_stated_precision_stays_near_the_reference(packed_step):
    """bf16 products, f32 accumulation: the loss to 1e-3; the gradients keep
    their direction."""
    segs, tok, seg, w, want_loss, want = packed_step
    loss, _, got, _ = jax.jit(lambda w: seqmodel.row_grads(
        seq_config(SHARE), w, tok, seg, jax.tree.map(jnp.zeros_like, w)))(w)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-3)
    cos = [
        float(jnp.vdot(got[k], want[k])
              / (jnp.linalg.norm(got[k]) * jnp.linalg.norm(want[k])))
        for k in want
    ]
    assert min(cos) > 0.9


def test_packed_rows_equal_their_segments_alone(f32_matmuls):
    """No leak through the state, the convolution or attention, and positions
    that restart: the hidden states of a packed row are those of each segment
    in a row of its own."""
    segs, tok, seg = _row(4)
    w = random_weights(SHARE, 5)
    cfg = seq_config(SHARE)
    packed = seqmodel.hidden_states(cfg, w, tok, seg)[0]
    at = 0
    for s in segs:
        t1, s1 = pack([s], 64)
        alone = seqmodel.hidden_states(
            cfg, w, jnp.asarray(t1)[None], jnp.asarray(s1)[None])[0, : len(s)]
        np.testing.assert_allclose(packed[at : at + len(s)], alone, atol=2e-4)
        at += len(s)


def _probe_gap(monkeypatch, broken: bool) -> float:
    """Relative L2 gap between the state-space probe the row program records
    (in its stated precision: bf16 products) and the reference's, over one
    step of two packed rows from the seeded initial weights."""
    rng = np.random.default_rng(11)
    rows = [[rng.integers(0, 32, n).astype(np.int32) for n in ns]
            for ns in ((20, 30, 9), (64,))]
    cfg = seq_config(SHARE, ssm_impl="scan")
    w = seqmodel.init_params(cfg, 3)
    for name, v in reference.initial_weights(SHARE, 3).items():
        np.testing.assert_allclose(w[name], v, rtol=1e-6, err_msg=name)  # one rule, twice
    if broken:
        monkeypatch.setattr(ssd, "chunk_scan", _scan_with_a_bfloat16_state)
    got = []
    for r in rows:
        tok, seg = pack(r, 64)
        got.append(jax.jit(lambda w, tok=tok, seg=seg: seqmodel.row_grads(
            cfg, w, jnp.asarray(tok)[None], jnp.asarray(seg)[None],
            jax.tree.map(jnp.zeros_like, w))[3]["ssd_probe"])(w)[0])
    hist = [s for r in rows for s in r]
    want = reference.first_step_probe(SHARE, 3, hist, [[0, 1, 2], [3]], 64)
    assert want.shape == (2, 64, 2)
    real = np.isfinite(want)
    assert real.sum() == 2 * (20 + 30 + 9 + 64)  # NaN on the padding only
    err = (np.stack(got) - want)[real]
    return float(np.linalg.norm(err) / np.linalg.norm(want[real]))


def test_recorded_state_space_probe_is_the_recurrences(monkeypatch):
    """What the benchmark's check holds the state's precision by: the first
    layer's ``S_t C_t`` along the seeded vector, recorded by the row program
    under its bf16 products, is the reference's recurrence on the same inputs
    to float32 rounding; with the state carried in bfloat16 it is far off."""
    sound = _probe_gap(monkeypatch, broken=False)
    assert sound < 2e-5
    assert _probe_gap(monkeypatch, broken=True) > 20 * max(sound, 1e-5)


@pytest.mark.parametrize("part", ["state_space", "attention", "mlp", "embed", "head"])
def test_the_four_shares_add_up_to_the_uncut_reference(f32_matmuls, part):
    """Model-configs guide, section 4: each chip computes the part of the
    result its own heads, MLP columns and vocabulary rows give; the parts of
    the four chips add up to what the uncut reference gives for the whole
    layer (the head: its logits side by side).  The state space: the two chips
    of a group both hold its B and C (counted once: each uses them for its own
    heads), and the gated norm's mean square is summed over the pair under a
    mapped axis, the one exchange inside a mixer."""
    w_whole = random_weights(WHOLE, 7)
    shares = [share_of(w_whole, chip) for chip in range(CHIPS)]
    cfg = seq_config(SHARE)
    rng = np.random.default_rng(8)
    T = 24
    x = jnp.asarray(rng.standard_normal((T, 64)).astype(np.float32))
    seg = jnp.zeros((1, T), jnp.int32)
    tokens = jnp.asarray(rng.integers(0, 128, T).astype(np.int32))
    pw = reference.layer_tensors(w_whole, 0)
    layers = [seqmodel.layer_params(w, 0) for w in shares]
    if part == "state_space":
        def pair(p):  # the two chips that share a group, as one mapped axis
            return jax.vmap(
                lambda p: seqmodel.state_space_mixer(cfg, p, x[None], seg, "tp")[0][0],
                axis_name="tp")(p)

        stack = lambda ps: jax.tree.map(lambda *t: jnp.stack(t), *ps)  # noqa: E731
        parts = list(pair(stack(layers[:2]))) + list(pair(stack(layers[2:])))
        want = reference.state_space_mixer(WHOLE, pw, x)
        # without the exchange a chip norms over its own channels: not the layer
        alone = sum(seqmodel.state_space_mixer(cfg, p, x[None], seg)[0][0] for p in layers)
        assert float(jnp.abs(alone - want).max()) > 1e-2 * float(jnp.abs(want).max())
    elif part == "attention":
        parts = [seqmodel.grouped_query_attention(cfg, p, x[None], seg)[0] for p in layers]
        want = reference.attention(WHOLE, pw, x)
    elif part == "mlp":
        parts = [seqmodel.mlp(cfg, p, x[None])[0] for p in layers]
        want = reference.mlp(WHOLE, pw, x)
    elif part == "embed":
        parts = [seqmodel.embed(
            dataclasses.replace(cfg, vocab_start=32 * chip), w["embed"], tokens)
            for chip, w in enumerate(shares)]
        want = reference.embed(WHOLE, w_whole["embed"], tokens)
    else:
        parts = [x @ w["head"].T for w in shares]
        want = x @ w_whole["head"].T
    got = jnp.concatenate(parts, axis=-1) if part == "head" else sum(parts)
    np.testing.assert_allclose(got, want, atol=1e-4 * float(jnp.abs(want).max()))
    # and a share alone is NOT the layer: what the other chips hold is left out
    if part != "head":
        assert float(jnp.abs(parts[0] - want).max()) > 1e-3


def test_training_steps_are_the_references_adamw(f32_matmuls):
    """Four optimiser steps of one row through ``train_steps`` against the
    reference's written-out AdamW over the same segments."""
    rng = np.random.default_rng(9)
    rows = [[rng.integers(0, 32, n).astype(np.int32) for n in ns]
            for ns in ((20, 30), (64,), (7, 9, 40), (33, 31))]
    packed = [pack(r, 64) for r in rows]
    tokens = jnp.asarray(np.stack([p[0] for p in packed]).reshape(4, 1, 64))
    segs = jnp.asarray(np.stack([p[1] for p in packed]).reshape(4, 1, 64))
    cfg = seq_config(SHARE)
    opt = seqmodel.AdamW()
    state, acc = seqmodel.init_state(cfg, 3)
    w0 = {k: jnp.array(v) for k, v in state["params"].items()}
    state, acc, records, probes = seqmodel.train_steps(cfg, opt, state, acc, tokens, segs)
    # the first step's row
    assert len(probes) == 1 and probes[0]["ssd_probe"].shape == (64, 2)
    hist = [s for r in rows for s in r]
    steps = [[0, 1], [2], [3, 4, 5], [6, 7]]
    ref_opt = {"lr": opt.lr, "beta1": opt.b1, "beta2": opt.b2, "eps": opt.eps,
               "weight_decay": opt.weight_decay}
    w_ref, ref_records = reference.replay(
        SHARE, ref_opt, 3, hist, steps, 4, say=lambda *_: None)
    for got, want in zip(records, ref_records):
        assert float(got["loss"]) == pytest.approx(want["loss"], rel=1e-5)
        assert float(got["tokens"]) == want["tokens"]
        assert float(got["grad_norm"]) == pytest.approx(want["grad_norm"], rel=1e-4)
        for k, v in want["tensor_grad_probe"].items():
            assert float(got["tensor_grad_probe"][k]) == pytest.approx(
                v, abs=5e-4 * want["tensor_grad_norm"][k]), k
    for k, v in w_ref.items():
        moved = float(jnp.linalg.norm(v - w0[k]))
        assert float(jnp.linalg.norm(state["params"][k] - v)) <= 0.03 * moved + 1e-9, k
    assert float(acc["count"]) == 0 and int(state["t"]) == 4
    # D and the convolution's bias are not decayed; the projections are
    assert not seqmodel.decays("layer0.ssm_d") and not seqmodel.decays("layer0.ssm_conv_bias")
    assert seqmodel.decays("layer0.ssm_in") and seqmodel.decays("layer0.ssm_out")
    assert all(seqmodel.decays(k) != reference.no_decay(k) for k in w_ref)
