"""The SmallThinker block's mathematics at a tiny size on the CPU (ISSUE 32):
the grouped expert path against a dense einsum over all experts, that no pair
is dropped at any imbalance, the windowed attention against a dense mask, the
program against the plain reference (``benchmark/references/smallthinker.py``
through ``st_reference``), packed rows against the same segments alone, and
the share test of the model-configs guide (the four shares' parts, the router
and the norms counted once, add up to the uncut reference's layer)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import moe, seqmodel
from st_reference import (
    CHIPS, SHARE, WHOLE, pack, random_weights, reference, seq_config, share_of)

SEGMENTS = (13, 27, 5, 11)  # one longer than the window of 16, three shorter


@pytest.fixture()
def f32_matmuls(monkeypatch):
    """The program's large products in float32, as the reference's are: what
    is left between the two is rounding, not the configuration's bf16."""
    monkeypatch.setattr(seqmodel, "MATMUL_DTYPE", jnp.float32)
    with jax.default_matmul_precision("highest"):
        yield


# ---------------------------------------------------------------------------
# ops/moe.py


def _experts(seed=0, N=64, D=32, F=16, E=8, held=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (
        jax.random.normal(ks[0], (N, D)), jax.random.normal(ks[1], (N, E)),
        0.2 * jax.random.normal(ks[2], (held, D, F)),
        0.2 * jax.random.normal(ks[3], (held, D, F)),
        0.2 * jax.random.normal(ks[4], (held, F, D)),
        jax.random.normal(ks[5], (N, D)))


def _dense(m, logits, valid, gate, up, down, k, start):
    """Every held expert over every token, weighted by the token's choice."""
    top, idx = jax.lax.top_k(logits, k)
    w = jax.nn.softmax(top, axis=-1)
    y = jnp.einsum(
        "tef,efd->ted",
        jnp.maximum(jnp.einsum("td,edf->tef", m, gate), 0.0)
        * jnp.einsum("td,edf->tef", m, up), down)
    held = start + jnp.arange(gate.shape[0])
    chose = jnp.sum(
        jnp.where(idx[:, :, None] == held, w[:, :, None], 0.0), axis=1) * valid[:, None]
    return jnp.einsum("te,ted->td", chose, y)


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("start", [0, 2, 4])
def test_grouped_experts_are_the_dense_einsum(impl, start):
    """Forward and every gradient (the stream's, the router's through the
    weights, the three stacked tensors') of the held experts' part, for a
    share at the front, across and at the end of the router's width."""
    m, logits, gate, up, down, r = _experts()
    valid = jnp.arange(64) < 60
    k = 3

    def grouped(m, logits, gate, up, down):
        return moe.experts_layer(
            m, logits, valid, gate, up, down, k=k, start=start, tile=8,
            dtype=jnp.float32, impl=impl)

    def dense(m, logits, gate, up, down):
        return _dense(m, logits, valid, gate, up, down, k, start)

    with jax.default_matmul_precision("highest"):
        out, idx, pairs = grouped(m, logits, gate, up, down)
        np.testing.assert_allclose(out, dense(m, logits, gate, up, down), atol=2e-6)
        got = jax.grad(lambda *a: jnp.sum(grouped(*a)[0] * r), argnums=range(5))(
            m, logits, gate, up, down)
        want = jax.grad(lambda *a: jnp.sum(dense(*a) * r), argnums=range(5))(
            m, logits, gate, up, down)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5)
    # the counters: the pairs of each held expert, padding left out
    chosen = np.asarray(idx)[:60]
    assert pairs.tolist() == [int((chosen == start + e).sum()) for e in range(4)]


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_no_pair_is_dropped_when_every_token_chooses_the_same_experts(impl):
    """The worst imbalance: all 64 tokens choose the same three experts, all
    held here.  The buffer holds every pair (its rows are sized for it), two
    experts hold none, and the result is still the dense one."""
    m, _, gate, up, down, _ = _experts(1)
    logits = jnp.tile(jnp.array([[5.0, 0.0, 4.0, 0.1, 3.0, 0.2, 0.3, 0.4]]), (64, 1))
    valid = jnp.ones(64, bool)
    with jax.default_matmul_precision("highest"):
        out, idx, pairs = moe.experts_layer(
            m, logits, valid, gate, up, down, k=3, start=0, tile=8,
            dtype=jnp.float32, impl=impl)
        want = _dense(m, logits, valid, gate, up, down, 3, 0)
    assert pairs.tolist() == [64, 0, 64, 0] and pairs.sum() == 128
    plan = moe.make_plan(idx, valid, 0, 4, 8)
    assert moe.plan_rows(64, 3, 4, 8) == 64 * 3 + 4 * 8
    held = np.asarray(plan.dest)[np.asarray(idx) < 4]
    assert len(set(held.tolist())) == 128 and held.max() < plan.row_token.shape[0]
    np.testing.assert_allclose(out, want, atol=2e-6)
    # a capacity would have dropped them: 1.25 x the mean load is 60 of 64
    assert float(jnp.abs(want).max()) > 1e-2


def _live_load(load: str):
    """(tokens, logits [N, 32]) of a layer of 16 held experts of 32, 3 a
    token, in tiles of 4 pairs: ``none`` no token chooses a held expert (16
    tiles of zeros, one window of 16); ``even`` seeded logits; ``same`` every
    token chooses the same three held experts (61 of the buffer's 64 tiles
    are live: every window runs); ``odd`` the same on 60 tokens, whose 61
    tiles the window does not divide (the last window starts early)."""
    N = 60 if load == "odd" else 64
    logits = jax.random.normal(jax.random.PRNGKey(7), (N, 32))
    if load == "none":
        logits = logits.at[:, :16].add(-50.0)
    elif load != "even":
        logits = 1e-3 * logits + jnp.zeros((N, 32)).at[:, jnp.array([1, 5, 11])].set(4.0)
    return N, logits


def _live_case(load: str, form: str, seed: int):
    """(plan, run) of that load: ``run(impl)`` gives ``out`` and every
    gradient of ``expert_ffn`` (``gated``) or ``relu2_ffn`` under the plan."""
    N, logits = _live_load(load)
    held, k, tile, D, F = 16, 3, 4, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    m, dout = jax.random.normal(ks[0], (N, D)), jax.random.normal(ks[1], (N, D))
    gate, up = (0.2 * jax.random.normal(key, (held, D, F)) for key in ks[2:4])
    down = 0.2 * jax.random.normal(ks[4], (held, F, D))
    idx, w = moe.route(logits, k)
    plan = moe.make_plan(idx, jnp.ones(N, bool), 0, held, tile)

    def run(impl):
        static = (jnp.bfloat16, impl)
        if form == "gated":
            out, vjp = jax.vjp(
                lambda *a: moe.expert_ffn(static, *a, plan), m, w, gate, up, down)
        else:
            out, vjp = jax.vjp(
                lambda *a: moe.relu2_ffn(static, *a, plan), m, w, up, down)
        return (out,) + vjp(dout)

    return plan, run


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("form", ["gated", "relu2"])
@pytest.mark.parametrize("load", ["none", "even", "same", "odd"])
def test_live_windows_are_the_whole_buffer_bit_for_bit(monkeypatch, load, form, impl):
    """The gathers into the pair buffer run over windows of ``WINDOW_TILES``
    tiles, as many as hold the live tiles (``moe._live_windows``), and the
    maps between the products over the live tiles, one kernel step a tile
    (``moe._tile_maps``): ``out`` and every gradient of ``expert_ffn`` and
    ``relu2_ffn`` are bit for bit those of ONE window that is the whole
    buffer and of maps of the whole buffer (every row gathered and mapped, a
    pair on it or not), but the weights' gradient under the kernels: a row's
    ``<da, a>`` is a float32 sum over the expert width, which a kernel makes
    over its tile in its own order (the same terms; the last bits move)."""
    plan, run = _live_case(load, form, 3)
    N, tile = plan.dest.shape[0], 4
    tiles = plan.tile_group.shape[0]
    assert tiles == (61 if load == "odd" else 64)
    live = int(plan.n_active[0])
    assert live == int(moe.expert_tiles(plan.counts, tile).sum())
    if load == "none":
        assert live == 16 and int(plan.counts.sum()) == 0
    elif load == "even":
        assert 16 < live < 48
    else:
        assert live == 3 * (N // tile) + 13 > tiles - 16  # the last window too

    monkeypatch.setattr(moe, "WINDOW_TILES", 16)
    windows = run(impl)
    monkeypatch.setattr(moe, "WINDOW_TILES", tiles)
    monkeypatch.setattr(moe, "_tile_maps", lambda plan, impl, fn, *ins, name: fn(*ins))
    whole = run(impl)
    assert len(windows) == (6 if form == "gated" else 5)
    for at, (got, want) in enumerate(zip(windows, whole)):
        assert got.dtype == want.dtype == jnp.float32
        got, want = np.asarray(got), np.asarray(want)
        if at == 2 and impl == "interpret":  # (out, dm, dw, ...)
            np.testing.assert_allclose(got, want, rtol=0, atol=1e-6 * np.abs(want).max())
        else:
            np.testing.assert_array_equal(got, want)
    if load != "none":
        assert all(float(jnp.abs(g).max()) > 0 for g in windows)


@pytest.mark.parametrize("form", ["gated", "relu2"])
@pytest.mark.parametrize("load", ["none", "even", "same", "odd"])
def test_nothing_reads_a_row_the_live_windows_did_not_write(monkeypatch, load, form):
    """Beside the kernels the loops' buffers start as the allocator hands them
    (``moe._buffer``: a kernel that writes nothing).  With every such buffer
    started as NaN instead, ``out`` and every gradient are finite and bit for
    bit what they are from zeros: the products skip the tiles past the live
    ones, the maps stay within their rows, and no token's ``dest`` points
    past the live rows."""
    _, run = _live_case(load, form, 4)
    monkeypatch.setattr(
        moe, "_buffer", lambda shape, dtype, impl: jnp.zeros(shape, dtype))
    zeros = run("interpret")
    monkeypatch.setattr(
        moe, "_buffer", lambda shape, dtype, impl: jnp.full(shape, jnp.nan, dtype))
    poisoned = run("interpret")
    for got, want in zip(poisoned, zeros):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_weights_are_a_softmax_over_the_chosen():
    logits = jax.random.normal(jax.random.PRNGKey(2), (10, 8))
    idx, w = moe.route(logits, 3)
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)
    order = np.argsort(-np.asarray(logits), axis=-1)[:, :3]
    assert (np.sort(np.asarray(idx), -1) == np.sort(order, -1)).all()
    full = jax.nn.softmax(logits, axis=-1)
    picked = jnp.take_along_axis(full, idx, axis=-1)
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True), atol=1e-6)


# ---------------------------------------------------------------------------
# the windowed attention


def _masked_scores_reference(q, k, v, seg, window):
    """Token by token: query t over the keys of its segment at distance under
    ``window``."""
    B, H, T, d = q.shape
    out = np.zeros((B, H, T, d), np.float32)
    for b in range(B):
        for t in range(T):
            keys = [s for s in range(t + 1)
                    if seg[b, s] == seg[b, t] and t - s < window]
            s_ = np.einsum("hd,hsd->hs", q[b, :, t], k[b][:, keys]) * d ** -0.5
            p = np.exp(s_ - s_.max(-1, keepdims=True))
            out[b, :, t] = np.einsum("hs,hsd->hd", p / p.sum(-1, keepdims=True), v[b][:, keys])
    return out


def test_window_mask_is_the_dense_mask():
    """Segments longer (40, 30) and shorter (9, 5) than the window of 16."""
    rng = np.random.default_rng(0)
    q, k, v = (rng.standard_normal((2, 2, 64, 8)).astype(np.float32) for _ in range(3))
    seg = np.zeros((2, 64), np.int32)
    seg[0, 40:], seg[0, 49:] = 1, -1
    seg[1, 30:], seg[1, 35:] = 1, 2
    got = seqmodel._dense_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(seg), 8 ** -0.5, 16)
    np.testing.assert_allclose(got, _masked_scores_reference(q, k, v, seg, 16), atol=2e-5)
    # a window as long as the row is full attention within the segment
    full = seqmodel._dense_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(seg), 8 ** -0.5)
    np.testing.assert_allclose(full, _masked_scores_reference(q, k, v, seg, 64), atol=2e-5)
    assert float(jnp.abs(got - full).max()) > 1e-2


@pytest.fixture()
def attn_blocks(monkeypatch):
    """Sets splash attention's blocks for a test; ``_splash_kernel``'s cache
    is keyed by the row, not by them, so it is emptied before and after."""
    def set_blocks(block, compute):
        monkeypatch.setattr(seqmodel, "ATTN_BLOCK", block)
        monkeypatch.setattr(seqmodel, "ATTN_BLOCK_COMPUTE", compute)
        monkeypatch.setattr(seqmodel, "MATMUL_DTYPE", jnp.float32)
        seqmodel._splash_kernel.cache_clear()

    yield set_blocks
    seqmodel._splash_kernel.cache_clear()


def _assert_kernel_is_the_dense_mask(rng, shape, kv_heads, seg, window, repeat=1):
    """``_attend`` over random q ``shape`` = [B, T, H, d] and k, v of
    ``kv_heads`` (handed on repeated ``repeat`` times) under the Pallas
    interpreter against the ``jax.numpy`` branch: forward and the gradients
    of q, k and v."""
    B, T, H, d = shape
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, h, d)).astype(np.float32))
               for h in (H, kv_heads, kv_heads))
    cfg = seq_config(SHARE)

    def attend(impl):
        c = dataclasses.replace(cfg, attn_impl=impl)
        return lambda q, k, v: seqmodel._attend(
            c, q, *seqmodel._repeat_kv(k, v, repeat), jnp.asarray(seg), window)

    with jax.default_matmul_precision("highest"):
        want, vjp = jax.vjp(attend("dense"), q, k, v)
        got, vjp_k = jax.vjp(attend("interpret"), q, k, v)
        assert got.shape == want.shape == (B, T, H * d)
        np.testing.assert_allclose(got, want, atol=2e-5)
        r = jnp.asarray(rng.standard_normal(want.shape).astype(np.float32))
        for g, w in zip(vjp_k(r), vjp(r)):
            np.testing.assert_allclose(g, w, atol=1e-4)


@pytest.mark.parametrize("layout", ["held", "repeated"])
@pytest.mark.parametrize("rep", [1, 5, 7])
@pytest.mark.parametrize("mask", ["causal", "window"])
def test_splash_kernel_is_the_dense_mask(attn_blocks, mask, rep, layout):
    """The chip's branch: blocks of 128 over a row of 512 with three segments
    and padding at its end (blocks outside the causal mask, or outside the
    window of 100, are skipped), ``rep`` query heads on each of 2 KV heads, k
    and v as held or already repeated for their query heads."""
    attn_blocks(128, 128)
    seg = np.zeros((1, 512), np.int32)
    seg[0, 300:], seg[0, 330:], seg[0, 500:] = 1, 2, -1
    _assert_kernel_is_the_dense_mask(
        np.random.default_rng(1), (1, 512, 2 * rep, 16), 2, seg,
        100 if mask == "window" else None, rep if layout == "repeated" else 1)


@pytest.mark.parametrize("T", [64, 192, 640])
@pytest.mark.parametrize("mask", ["causal", "window"])
def test_splash_kernel_on_a_row_its_blocks_do_not_divide(attn_blocks, mask, T):
    """Row lengths are multiples of ``token_multiple`` alone, and serving pads
    a short history up to that: a row under the kernel's 128 lanes (64), one
    under a block that is no power of two (192: one block of 256, of two
    products), and one past a block that the block does not divide (640 = 2.5
    blocks) are padded at their end inside ``_splash_attention`` and read the
    dense mask's numbers at their own length."""
    attn_blocks(256, 128)
    seg = np.zeros((2, T), np.int32)
    seg[0, T // 3:], seg[1, T // 2:], seg[1, T - 7:] = 1, 1, -1
    _assert_kernel_is_the_dense_mask(
        np.random.default_rng(2), (2, T, 3, 16), 1, seg,
        40 if mask == "window" else None)


def test_no_rotary_on_the_global_kind(monkeypatch):
    """Rotary positions are the sliding kind's alone; and both kinds read the
    router BEFORE attention, from the normed input."""
    calls = []
    rope = seqmodel.rope
    monkeypatch.setattr(seqmodel, "rope", lambda x, pos, theta: (
        calls.append(theta), rope(x, pos, theta))[1])
    cfg = seq_config(SHARE)
    w = random_weights(SHARE, 3)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 32, 64)), jnp.float32)
    seg = jnp.zeros((1, 32), jnp.int32)
    seqmodel.layer(cfg, seqmodel.GLOBAL_MOE, seqmodel.layer_params(w, 0), x, seg)
    assert calls == []
    choices = seqmodel.layer(
        cfg, seqmodel.SLIDING_MOE, seqmodel.layer_params(w, 1), x, seg)[1]["choices"]
    assert calls == [1.5e6, 1.5e6]  # q and k
    p = seqmodel.layer_params(w, 1)
    h = seqmodel.rmsnorm(x, p["input_norm"], cfg.eps)[0]
    want = jax.lax.top_k(jnp.matmul(h, p["router"], precision="highest"), 4)[1]
    assert (np.sort(np.asarray(choices[0]), -1) == np.sort(np.asarray(want), -1)).all()


def test_unknown_kinds_are_refused_by_name():
    with pytest.raises(ValueError, match="sliding_attention_moe"):
        dataclasses.replace(seq_config(SHARE), layer_types=("moe",))
    with pytest.raises(ValueError, match="experts' sizes"):
        dataclasses.replace(seq_config(SHARE), experts_held=0)
    with pytest.raises(ValueError, match="need a window"):
        dataclasses.replace(seq_config(SHARE), window=0)


# ---------------------------------------------------------------------------
# the program against the plain reference


def _row(seed=1):
    rng = np.random.default_rng(seed)
    segs = [rng.integers(0, 32, n).astype(np.int32) for n in SEGMENTS]
    tok, seg = pack(segs, 128)
    return segs, jnp.asarray(tok)[None], jnp.asarray(seg)[None]


@pytest.fixture(scope="module")
def packed_step():
    """One packed row of four segments, random weights, and the reference's
    loss, choices and gradients over the segments one at a time."""
    segs, tok, seg = _row()
    w = random_weights(SHARE, 3)

    def total(w):
        parts = [
            reference.segment_loss_sum(SHARE, w, jnp.asarray(s), jnp.ones(len(s), bool))
            for s in segs]
        return sum(p[0] for p in parts), [p[1] for p in parts]

    with jax.default_matmul_precision("highest"):
        (loss, choices), grads = jax.jit(jax.value_and_grad(total, has_aux=True))(w)
    return segs, tok, seg, w, loss, grads, choices


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_program_is_the_reference_on_a_packed_step(f32_matmuls, packed_step, impl):
    """Loss, every layer's choices and every tensor's gradient of one packed
    row against the reference, which sees the four segments one at a time and
    applies every held expert densely."""
    segs, tok, seg, w, want_loss, want, want_choices = packed_step
    cfg = seq_config(SHARE, moe_impl=impl)
    loss, count, got, aux = jax.jit(
        lambda w: seqmodel.row_grads(cfg, w, tok, seg, jax.tree.map(jnp.zeros_like, w))
    )(w)
    assert float(count) == sum(len(s) - 1 for s in segs)
    assert aux["moe_probe"].shape == (1, 128, 1)
    assert aux["choices"].shape == (1, 2, 128, 4)
    at = 0
    for s, c in zip(segs, want_choices):
        mine = np.sort(np.asarray(aux["choices"][0, :, at : at + len(s)]), -1)
        assert (mine == np.sort(np.asarray(c), -1)).all()
        at += len(s)
    held = np.concatenate([np.asarray(c) for c in want_choices], axis=1) < 4
    assert aux["expert_pairs"].sum(-1).tolist() == held.sum((1, 2)).tolist()
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert set(got) == set(want) == set(seqmodel.param_shapes(cfg))
    for name in want:
        gap = float(jnp.linalg.norm(got[name] - want[name]))
        assert gap <= 1e-4 * float(jnp.linalg.norm(want[name])), name


def test_program_in_its_stated_precision_stays_near_the_reference(packed_step):
    """bf16 products, f32 accumulation: the loss to 2e-3; the gradients keep
    their direction."""
    segs, tok, seg, w, want_loss, want, _ = packed_step
    loss, _, got, _ = jax.jit(lambda w: seqmodel.row_grads(
        seq_config(SHARE), w, tok, seg, jax.tree.map(jnp.zeros_like, w)))(w)
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-3)
    cos = [
        float(jnp.vdot(got[k], want[k])
              / (jnp.linalg.norm(got[k]) * jnp.linalg.norm(want[k])))
        for k in want
    ]
    assert min(cos) > 0.9


def test_packed_rows_equal_their_segments_alone(f32_matmuls):
    """No leak through attention, its window or the experts' dispatch, and
    positions that restart: the hidden states of a packed row are those of
    each segment in a row of its own."""
    segs, tok, seg = _row(4)
    w = random_weights(SHARE, 5)
    cfg = seq_config(SHARE)
    packed = seqmodel.hidden_states(cfg, w, tok, seg)[0]
    at = 0
    for s in segs:
        t1, s1 = pack([s], 128)
        alone = seqmodel.hidden_states(
            cfg, w, jnp.asarray(t1)[None], jnp.asarray(s1)[None])[0, : len(s)]
        np.testing.assert_allclose(packed[at : at + len(s)], alone, atol=2e-4)
        at += len(s)


def _probe_gap(monkeypatch, fault: str | None) -> float:
    """Relative L2 gap between the expert probe the row program records (in
    its stated precision: bf16 products) and the reference's, over one step of
    two packed rows from the seeded initial weights."""
    rng = np.random.default_rng(11)
    rows = [[rng.integers(0, 32, n).astype(np.int32) for n in ns]
            for ns in ((20, 30, 9), (128,))]
    cfg = seq_config(SHARE)
    w = seqmodel.init_params(cfg, 3)
    for name, v in reference.initial_weights(SHARE, 3).items():
        np.testing.assert_allclose(w[name], v, rtol=1e-6, err_msg=name)  # one rule, twice
    if fault == "capacity":
        # pairs past 1.25 x the mean load of an expert are dropped
        make_plan = moe.make_plan

        def capped(idx, valid, start, held, tile):
            plan = make_plan(idx, valid, start, held, tile)
            cap = int(1.25 * idx.shape[0] * idx.shape[1] / cfg.experts)
            first = jnp.concatenate(
                [jnp.zeros(1, jnp.int32),
                 jnp.cumsum(jnp.maximum(-(-plan.counts // tile), 1))[:-1]]) * tile
            group = plan.tile_group[jnp.minimum(plan.dest, plan.row_token.shape[0] - 1) // tile]
            over = plan.dest - first[group] >= cap
            return plan._replace(dest=jnp.where(over, plan.row_token.shape[0], plan.dest))

        monkeypatch.setattr(moe, "make_plan", capped)
    elif fault == "bf16_accumulation":
        gmm = moe.gmm
        monkeypatch.setattr(moe, "gmm", lambda lhs, rhs, plan, **kw: gmm(
            lhs, rhs, plan, **kw).astype(jnp.bfloat16).astype(jnp.float32))
    got = []
    for r in rows:
        tok, seg = pack(r, 128)
        got.append(jax.jit(lambda w, tok=tok, seg=seg: seqmodel.row_grads(
            cfg, w, jnp.asarray(tok)[None], jnp.asarray(seg)[None],
            jax.tree.map(jnp.zeros_like, w))[3]["moe_probe"])(w)[0])
    hist = [s for r in rows for s in r]
    want = reference.first_step_probe(SHARE, 3, hist, [[0, 1, 2], [3]], 128)
    assert want.shape == (2, 128, 1)
    real = np.isfinite(want)
    assert real.sum() == 20 + 30 + 9 + 128  # NaN on the padding only
    err = (np.stack(got) - want)[real]
    return float(np.linalg.norm(err) / np.linalg.norm(want[real]))


def test_recorded_expert_probe_is_the_dense_references(monkeypatch):
    """What the benchmark's check holds the expert path by: the first layer's
    experts on its normed input along the seeded vector, recorded by the row
    program under its bf16 products, is the dense reference on the same inputs
    in the same products; a pair dropped at a capacity of 1.25, or the
    products' results rounded to bfloat16, are far off."""
    sound = _probe_gap(monkeypatch, None)
    assert sound < 5e-4
    assert _probe_gap(monkeypatch, "capacity") > 20 * max(sound, 1e-4)
    monkeypatch.undo()
    assert _probe_gap(monkeypatch, "bf16_accumulation") > 5 * max(sound, 1e-4)


@pytest.mark.parametrize("part", ["experts", "attention", "layer", "embed", "head"])
def test_the_four_shares_add_up_to_the_uncut_reference(f32_matmuls, part):
    """Model-configs guide, section 4: each chip computes the part of the
    result its own heads, experts and vocabulary rows give; the parts of the
    four chips add up to what the uncut reference gives.  The router's logits
    and the norms are what every chip computes alike: counted once.  A whole
    layer: the attention's parts summed (the deployment's all-reduce), one
    norm of that stream, then the experts' parts summed."""
    w_whole = random_weights(WHOLE, 7)
    shares = [share_of(w_whole, chip) for chip in range(CHIPS)]
    cfgs = [seq_config({**SHARE, "expert_start": 4 * c, "vocab_start": 32 * c})
            for c in range(CHIPS)]
    rng = np.random.default_rng(8)
    T = 40  # longer than the window
    x = jnp.asarray(rng.standard_normal((T, 64)).astype(np.float32))
    seg = jnp.zeros((1, T), jnp.int32)
    tokens = jnp.asarray(rng.integers(0, 128, T).astype(np.int32))
    kind, name = seqmodel.SLIDING_MOE, "sliding"
    pw = reference.layer_tensors(w_whole, 1)
    layers = [seqmodel.layer_params(w, 1) for w in shares]
    for p in layers:  # alike on every chip
        np.testing.assert_array_equal(p["router"], pw["router"])
        np.testing.assert_array_equal(p["post_norm"], pw["post_norm"])
    h = reference.rmsnorm(x, pw["input_norm"], 1e-6)

    def experts_of(c, p, m, logits):
        return moe.experts_layer(
            m, logits, jnp.ones(T, bool), p["experts_gate"], p["experts_up"],
            p["experts_down"], k=4, start=4 * c, tile=8, dtype=jnp.float32)[0]

    if part == "experts":
        logits = h @ pw["router"]
        parts = [experts_of(c, p, x, logits) for c, p in enumerate(layers)]
        want = reference.experts(WHOLE, pw, x, *reference.route(WHOLE, logits))
    elif part == "attention":
        parts = [seqmodel.routed_attention(cfg, kind, p, h[None], seg)[0]
                 for cfg, p in zip(cfgs, layers)]
        want = reference.attention(WHOLE, pw, h, name)
    elif part == "layer":
        x1 = x + sum(seqmodel.routed_attention(cfg, kind, p, h[None], seg)[0]
                     for cfg, p in zip(cfgs, layers))
        m = reference.rmsnorm(x1, pw["post_norm"], 1e-6)
        logits = h @ pw["router"]
        parts = [experts_of(c, p, m, logits) for c, p in enumerate(layers)]
        parts[0] = parts[0] + x1  # the stream itself, once
        want = reference.block(WHOLE, name, pw, x)[0]
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
    reference's written-out AdamW over the same segments, with the routing
    counters the step's accumulator summed beside the gradients."""
    rng = np.random.default_rng(9)
    rows = [[rng.integers(0, 32, n).astype(np.int32) for n in ns]
            for ns in ((20, 30), (128,), (7, 9, 40), (33, 31))]
    packed = [pack(r, 128) for r in rows]
    tokens = jnp.asarray(np.stack([p[0] for p in packed]).reshape(4, 1, 128))
    segs = jnp.asarray(np.stack([p[1] for p in packed]).reshape(4, 1, 128))
    cfg = seq_config(SHARE)
    opt = seqmodel.AdamW()
    state, acc = seqmodel.init_state(cfg, 3)
    state, acc, records, probes = seqmodel.train_steps(cfg, opt, state, acc, tokens, segs)
    assert len(probes) == 1 and probes[0]["moe_probe"].shape == (128, 1)
    assert probes[0]["choices"].shape == (2, 128, 4)
    hist = [s for r in rows for s in r]
    steps, at = [], 0
    for r in rows:
        steps.append(list(range(at, at + len(r))))
        at += len(r)
    ref_opt = {"lr": opt.lr, "beta1": opt.b1, "beta2": opt.b2, "eps": opt.eps,
               "weight_decay": opt.weight_decay}
    w, ref_records, first = reference.replay(
        SHARE, ref_opt, 3, hist, steps, 4, say=lambda s: None)
    for got, want in zip(records, ref_records):
        assert float(got["loss"]) == pytest.approx(want["loss"], rel=1e-5)
        assert float(got["tokens"]) == want["tokens"]
        assert got["moe_pairs_held"].tolist() == want["moe_pairs_held"]
        assert got["moe_pairs_total"].tolist() == [want["moe_pairs_total"]] * 2
        assert got["moe_expert_pairs"].sum(-1).tolist() == want["moe_pairs_held"]
        # one row a step: the live tiles of its two layers' pair buffers (an
        # expert's pairs in whole tiles, one tile for an expert with none)
        tile = cfg.moe_tile
        tiles = np.maximum(-(-np.asarray(got["moe_expert_pairs"]) // tile), 1).sum(-1)
        assert got["moe_rows_live"].tolist() == (tile * tiles).tolist()
        assert got["moe_rows_planned"].tolist() == [
            moe.plan_rows(128, cfg.experts_per_token, cfg.experts_held, tile)] * 2
        assert float(got["grad_norm"]) == pytest.approx(want["grad_norm"], rel=1e-3)
    # the first step's choices, by history, are the program's by row
    at = 0
    for j in steps[0]:
        mine = np.asarray(probes[0]["choices"][:, at : at + len(hist[j])])
        assert (np.sort(mine, -1) == np.sort(first[j], -1)).all()
        at += len(hist[j])
    for name, v in w.items():
        moved = float(jnp.linalg.norm(v - reference.initial_weights(SHARE, 3)[name]))
        gap = float(jnp.linalg.norm(state["params"][name] - v))
        assert gap <= 0.05 * moved + 1e-7, name
    # the accumulator is zeroed between steps, counters and all
    assert int(acc["pairs_total"]) == 0 and not np.asarray(acc["expert_pairs"]).any()
    assert int(acc["rows_planned"]) == 0 and not np.asarray(acc["rows_live"]).any()
