"""The Falcon-H1 block's plain reference: Mamba-2 state-space heads beside
grouped-query attention on one normed input, pre-norm, muP's forward
multipliers; next-item training with AdamW.

Straight ``jax.numpy`` in float32 with ``jax.default_matmul_precision
("highest")``; the state space token by token exactly as the configuration
writes it, attention with a full masked score matrix, ONE SEGMENT AT A TIME (a
segment is one entity's history, so positions start at 0 by themselves): no
packing, no chunks, no cache, no kernels.  Gradients by ``jax.grad``, AdamW
written out.  Nothing of the program is imported; what no model's mathematics
enters (how histories are grouped into optimiser steps, the seeded gradient
probe, the sampled rows, the vocabulary's order) is shared with
``references/olmo_hybrid.py``.

The published description is ``modeling_falcon_h1.py`` of the transformers
library (huggingface.co/tiiuae/Falcon-H1-34B-Instruct); each departure is
noted at its line.  Per token t of a segment, hidden x in R^D, ``m`` the
configuration's ``model`` group (``model_group``), every multiplier by its
config key, ``h = RMSNorm(x; input_norm)``:

    u        = (W_in (ssm_in_multiplier h)) * mup_vector     zones z | x | B | C | dt
    x, B, C  = SiLU(conv1d(x | B | C) + bias)                depthwise, causal, width 4
    Delta_t  = softplus(dt_t + dt_bias)        A = -exp(A_log)
    S_t      = exp(Delta_t A) S_(t-1) + Delta_t x_t B_t^T    per head, S_0 = 0
    y_t      = S_t C_t + D x_t
    ssm      = ssm_out_multiplier W_out GroupRMSNorm(y * SiLU(z))
    q, k, v  = W_q h', W_k h', W_v h',  h' = attention_in_multiplier h,  k *= key_multiplier
    attn     = attention_out_multiplier W_o softmax(RoPE(q) RoPE(k)^T / sqrt(d)) v
    x        = x + ssm + attn
    x        = x + down_multiplier W_down(W_up h2 * SiLU(gate_multiplier W_gate h2)),
               h2 = RMSNorm(x; pre_ff_norm)

The share (model-configs guide, section 4): the tensors are the slices one of
``chips`` chips holds, every function computes what those slices give, an item
id outside the held vocabulary rows embeds to zero, logits and loss run over
the held rows.  One statistic crosses chips, the gated norm's mean square (a
group's channels lie on two chips): ``gated_group_norm`` takes the mapped axis
to sum it over; ``None`` here, as in the program, so the mean is over the
channels held.

``check_retrain`` replays the configured optimiser steps from the seeded
initial weights in ONE child process on the chip (the benchmark's worker has
released it by then) and holds the persisted model to the replay.  One number
is not the replay's: ``ssd_probe`` runs the FIRST layer's state space token by
token on inputs projected in the configuration's stated bf16 product, so that
the program's record of the same quantity differs by the recurrence alone
(chunks, kernel, the precision of the carried state).
"""

from __future__ import annotations

import functools
import json
import math
import os
import sys
import time
from pathlib import Path

import numpy as np

_REPO = Path(__file__).resolve().parents[2]
if str(_REPO) not in sys.path:  # run as a script: the replay's child
    sys.path.insert(0, str(_REPO))

from benchmark.references.olmo_hybrid import (  # noqa: E402
    PROBE_SEED, bf16_product, buckets_for, grad_probe, histories, rmsnorm,
    rows_of, sampled_rows, silu, steps_of, vocabulary_ids)

MLP = ("gate", "up", "down")
ATTENTION = ("q", "k", "v", "o")
DECAY = ("ssm_a_log", "ssm_dt_bias", "ssm_d")
#: muP's forward multipliers, under the published config's keys
MULTIPLIERS = (
    "embedding_multiplier", "lm_head_multiplier", "ssm_in_multiplier",
    "ssm_multipliers", "ssm_out_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "mlp_multipliers")


# ---------------------------------------------------------------------------
# shapes and the seeded initial weights (the rule of the configuration file)


def zone_widths(m: dict) -> tuple:
    """Columns of the state-space projection's zones z, x, B, C, dt."""
    ch = m["ssm_heads_held"] * m["mamba_d_head"]
    bc = m["ssm_groups_held"] * m["mamba_d_state"]
    return ch, ch, bc, bc, m["ssm_heads_held"]


def tensor_shapes(m: dict) -> dict:
    """Flat name -> held shape, in the order the initialisation counts."""
    D, hd = m["hidden_size"], m["head_dim"]
    A, KV = m["attention_heads_held"], m["kv_heads_held"]
    H, F, V, K = (m["ssm_heads_held"], m["mlp_columns_held"],
                  m["vocab_rows_held"], m["mamba_d_conv"])
    ch, _, bc, _, _ = zone_widths(m)
    out = {"embed": (V, D)}
    for i in range(m["num_layers"]):
        p = f"layer{i}."
        out.update({
            p + "input_norm": (D,), p + "ssm_in": (D, sum(zone_widths(m))),
            p + "ssm_conv": (K, ch + 2 * bc), p + "ssm_conv_bias": (ch + 2 * bc,),
            p + "ssm_a_log": (H,), p + "ssm_d": (H,), p + "ssm_dt_bias": (H,),
            p + "ssm_norm": (ch,), p + "ssm_out": (ch, D),
            p + "q": (D, A * hd), p + "k": (D, KV * hd), p + "v": (D, KV * hd),
            p + "o": (A * hd, D), p + "pre_ff_norm": (D,),
            p + "gate": (D, F), p + "up": (D, F), p + "down": (F, D),
        })
    out["final_norm"] = (D,)
    out["head"] = (V, D)
    return out


def initial_weights(m: dict, seed: int) -> dict:
    """Tensor number n draws from ``fold_in(PRNGKey(seed), n)`` at its held
    shape (the configuration's ``initialisation``)."""
    import jax
    import jax.numpy as jnp

    base = jax.random.PRNGKey(seed)
    out = {}
    for n, (name, shape) in enumerate(tensor_shapes(m).items()):
        key = jax.random.fold_in(base, n)
        leaf = name.split(".")[-1]
        if leaf.endswith("norm") or leaf == "ssm_d":
            w = jnp.ones(shape, jnp.float32)
        elif "conv" in leaf:
            bound = 1.0 / math.sqrt(m["mamba_d_conv"])
            w = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        elif leaf == "ssm_a_log":
            w = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
        elif leaf == "ssm_dt_bias":
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            dt = jnp.maximum(dt, 1e-4)
            w = dt + jnp.log(-jnp.expm1(-dt))
        else:
            w = 0.02 * jax.random.normal(key, shape, jnp.float32)
        out[name] = w
    return out


# ---------------------------------------------------------------------------
# the layers, for ONE segment: x [T, D]; ``valid`` [T] marks real tokens
# (a segment is padded at its END to a length the replay compiles once;
# nothing after a token can reach it)


def causal_conv(x, w, bias):
    """y_t = bias + sum_j w[j] x[t - (K - 1) + j], zero before the segment."""
    import jax.numpy as jnp

    K = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], axis=0)
    return sum(xp[j : j + x.shape[0]] * w[j] for j in range(K)) + bias


#: tokens between the states the backward pass of ``selective_scan`` keeps
STATE_EVERY = 64


def selective_scan(x, dt, a, b, c):
    """Token by token.  x: [T, H, P]; dt: [T, H]; a: [H]; b, c: [T, G, N]
    (head n reads group ``n // (H / G)``) -> ``S_t C_t`` [T, H, P].  For the
    backward pass the state is kept every ``STATE_EVERY`` tokens and the
    tokens between are run again (8192 states of one segment would be 8 GB);
    that changes no number."""
    import jax
    import jax.numpy as jnp

    T, H, P = x.shape
    G, N = b.shape[1:]

    def step(S, inp):
        xt, dtt, bt, ct = inp
        bh, ch = jnp.repeat(bt, H // G, axis=0), jnp.repeat(ct, H // G, axis=0)
        S = jnp.exp(dtt * a)[:, None, None] * S + (
            (dtt[:, None] * xt)[:, :, None] * bh[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, ch)

    S0 = jnp.zeros((H, P, N), jnp.float32)
    xs = (x, dt, b, c)
    if T % STATE_EVERY or T <= STATE_EVERY:
        return jax.lax.scan(step, S0, xs)[1]
    blocks = tuple(t.reshape((T // STATE_EVERY, STATE_EVERY) + t.shape[1:]) for t in xs)
    _, y = jax.lax.scan(
        jax.checkpoint(lambda S, xb: jax.lax.scan(step, S, xb)), S0, blocks)
    return y.reshape(T, H, P)


def gated_group_norm(y, z, w, eps, axis_name=None):
    """``RMSNorm(y * SiLU(z))`` over the last axis: the channels of one group
    that are held here (``mamba_rms_norm``, ``mamba_norm_before_gate: false``;
    the grouped form is transformers' FalconH1RMSNormGated, not the config's).
    Departure: the published group has 2048 channels, this share holds 1024 of
    them, and the mean square over the whole group is the one statistic that
    crosses chips; ``axis_name`` sums it over the mapped axis of the chips that
    share the group (the uncut model in the share test), ``None`` on one chip."""
    import jax
    import jax.numpy as jnp

    g = y * silu(z)
    ss, n = jnp.sum(g * g, axis=-1, keepdims=True), g.shape[-1]
    if axis_name is not None:
        ss, n = jax.lax.psum(ss, axis_name), n * jax.lax.psum(1, axis_name)
    return g / jnp.sqrt(ss / n + eps) * w


def ssm_inputs(m, p, h, product=None):
    """What the state space reads: x [T, H, P], Delta [T, H], B, C [T, G, N],
    and the gate z [T, H P].  ``product`` is how the projection is made
    (default: the plain float32 product)."""
    import jax.numpy as jnp

    product = product or jnp.matmul
    T = h.shape[0]
    H, P, G, N = (m["ssm_heads_held"], m["mamba_d_head"], m["ssm_groups_held"],
                  m["mamba_d_state"])
    mup_vector = np.repeat(np.asarray(m["ssm_multipliers"], np.float32), zone_widths(m))
    u = product(m["ssm_in_multiplier"] * h, p["ssm_in"]) * mup_vector
    z, xbc, dt = jnp.split(u, (H * P, 2 * H * P + 2 * G * N), axis=-1)
    xbc = silu(causal_conv(xbc, p["ssm_conv"], p["ssm_conv_bias"]))
    x, b, c = jnp.split(xbc, (H * P, H * P + G * N), axis=-1)
    dt = jnp.logaddexp(0.0, dt + p["ssm_dt_bias"])  # softplus
    return x.reshape(T, H, P), dt, b.reshape(T, G, N), c.reshape(T, G, N), z


def state_space_mixer(m, p, h, norm_axis=None):
    """The held heads' part of the mixer's output."""
    import jax.numpy as jnp

    T = h.shape[0]
    G = m["ssm_groups_held"]
    x, dt, b, c, z = ssm_inputs(m, p, h)
    y = selective_scan(x, dt, -jnp.exp(p["ssm_a_log"]), b, c)
    y = y + p["ssm_d"][:, None] * x
    y = gated_group_norm(
        y.reshape(T, G, -1), z.reshape(T, G, -1), p["ssm_norm"].reshape(G, -1),
        m["rms_norm_eps"], norm_axis)
    return m["ssm_out_multiplier"] * (y.reshape(T, -1) @ p["ssm_out"])


def rope(x, theta):
    """Rotary positions 0, 1, .. over the WHOLE head, channel i paired with
    i + d/2 (transformers' rotate_half; the config gives theta alone).
    x: [T, heads, d]."""
    import jax.numpy as jnp

    T, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(m, p, h):
    """Causal softmax attention over the segment with the full T x T score
    matrix, one query head at a time (so that one matrix is held, not one a
    head), query head n on KV head ``n // (heads / kv heads)``; the held
    heads' part of the output."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    d = m["head_dim"]
    h = m["attention_in_multiplier"] * h
    q = rope((h @ p["q"]).reshape(T, -1, d), m["rope_theta"])
    k = rope((h @ p["k"]).reshape(T, -1, d) * m["key_multiplier"], m["rope_theta"])
    v = (h @ p["v"]).reshape(T, -1, d)
    A, KV = q.shape[1], k.shape[1]
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.where(causal, (qh @ kh.T) * d ** -0.5, -jnp.inf)
        w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        return (w / jnp.sum(w, axis=-1, keepdims=True)) @ vh

    of = jnp.arange(A) // (A // KV)
    o = jax.lax.map(head, (
        q.transpose(1, 0, 2), k.transpose(1, 0, 2)[of], v.transpose(1, 0, 2)[of]))
    return m["attention_out_multiplier"] * (
        o.transpose(1, 0, 2).reshape(T, A * d) @ p["o"])


def mlp(m, p, x):
    """The held columns' part: down_multiplier W_down(W_up x * SiLU(
    gate_multiplier W_gate x))."""
    gate_mult, down_mult = m["mlp_multipliers"]
    return down_mult * ((silu(gate_mult * (x @ p["gate"])) * (x @ p["up"])) @ p["down"])


def embed(m, table, tokens):
    import jax.numpy as jnp

    idx = tokens - m["vocab_start"]
    held = (idx >= 0) & (idx < table.shape[0])
    rows = jnp.where(held[:, None], table[jnp.where(held, idx, 0)], 0.0)
    return m["embedding_multiplier"] * rows


def layer_tensors(w: dict, i: int) -> dict:
    p = f"layer{i}."
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


def block(m, p, x, norm_axis=None):
    eps = m["rms_norm_eps"]
    h = rmsnorm(x, p["input_norm"], eps)
    x = x + state_space_mixer(m, p, h, norm_axis) + attention(m, p, h)
    return x + mlp(m, p, rmsnorm(x, p["pre_ff_norm"], eps))


def final_hidden(m, w, tokens):
    """[T, D] after the last norm."""
    import jax

    x = embed(m, w["embed"], tokens)
    for i in range(m["num_layers"]):
        # recomputation changes no number, only what is held between passes
        x = jax.checkpoint(functools.partial(block, m))(layer_tensors(w, i), x)
    return rmsnorm(x, w["final_norm"], m["rms_norm_eps"])


def segment_loss_sum(m, w, tokens, valid):
    """Sum over the segment's real, non-final positions t of the
    cross-entropy of token t + 1 given tokens <= t, over the held rows."""
    import jax.numpy as jnp

    h = final_hidden(m, w, tokens)
    logits = m["lm_head_multiplier"] * (h[:-1] @ w["head"].T)
    target = tokens[1:] - m["vocab_start"]
    top = jnp.max(logits, axis=-1)
    lse = top + jnp.log(jnp.sum(jnp.exp(logits - top[:, None]), axis=-1))
    picked = jnp.take_along_axis(logits, target[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(valid[1:], lse - picked, 0.0))


def no_decay(name: str) -> bool:
    return any(s in name for s in ("norm", "a_log", "dt_bias", "conv", "ssm_d"))


def adamw_update(opt, w, mom, var, grad, t):
    """One AdamW step, written out; ``t`` counts from 1.  One learning rate
    for every tensor (the config carries muP's forward multipliers only)."""
    import jax.numpy as jnp

    b1, b2 = opt["beta1"], opt["beta2"]
    nw, nm, nv = {}, {}, {}
    for name in w:
        g = grad[name]
        nm[name] = b1 * mom[name] + (1 - b1) * g
        nv[name] = b2 * var[name] + (1 - b2) * g * g
        mhat = nm[name] / (1 - b1 ** t)
        vhat = nv[name] / (1 - b2 ** t)
        step = mhat / (jnp.sqrt(vhat) + opt["eps"])
        if not no_decay(name):
            step = step + opt["weight_decay"] * w[name]
        nw[name] = w[name] - opt["lr"] * step
    return nw, nm, nv


# ---------------------------------------------------------------------------
# the replay (needs the device: the child process, or a chip script)


def ssd_probe(m, w, tokens):
    """The FIRST layer's state-space output ``S_t C_t`` (before the ``D``
    skip: ``D x_t`` is alike on both sides and 100 x larger at the seeded
    weights, so it would bury the state) for one segment, each head's P values
    along the seeded vector (standard normal from ``fold_in(PRNGKey(PROBE_SEED),
    2**20 + 1)``) -> [T, H].  The recurrence itself is the float32 one token by
    token; the projection is made in the stated precision of the products, so
    that both sides hand the recurrence the same numbers (the embedding rows
    are exact, their norm is float32 on both sides, one product of it rounds
    the same way) and the gap is the recurrence's own: its chunks, its kernel,
    and the precision of the state it carries."""
    import jax
    import jax.numpy as jnp

    p = layer_tensors(w, 0)
    h = rmsnorm(embed(m, w["embed"], tokens), p["input_norm"], m["rms_norm_eps"])
    x, dt, b, c, _ = ssm_inputs(m, p, h, bf16_product)
    y = selective_scan(x, dt, -jnp.exp(p["ssm_a_log"]), b, c)
    r = jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(PROBE_SEED), 2 ** 20 + 1),
        (y.shape[-1],), jnp.float32)
    return y @ r


def first_step_probe(m, seed, hist, rows, row_len):
    """``ssd_probe`` of every history of the first optimiser step, from the
    seeded initial weights, laid where the packing puts the history (row,
    offset) -> float32 [rows, row_len, H], NaN on padding.  One padded length
    (a segment's tail of padding cannot reach its tokens)."""
    import jax
    import jax.numpy as jnp

    out = np.full((len(rows), row_len, m["ssm_heads_held"]), np.nan, np.float32)
    length = max(len(hist[j]) for row in rows for j in row)
    with jax.default_matmul_precision("highest"):
        w = initial_weights(m, seed)
        w = {k: v for k, v in w.items() if k == "embed" or k.startswith("layer0.")}
        probe = jax.jit(lambda w, t: ssd_probe(m, w, t))
        for r, row in enumerate(rows):
            at = 0
            for j in row:
                tok = np.zeros(length, np.int32)
                tok[: len(hist[j])] = hist[j]
                out[r, at : at + len(hist[j])] = np.asarray(
                    probe(w, jnp.asarray(tok)))[: len(hist[j])]
                at += len(hist[j])
    return out


def replay(m, opt, seed, hist, steps, n_steps, say=print):
    """``n_steps`` optimiser steps from the seeded initial weights ->
    (final weights, per-step records)."""
    import jax
    import jax.numpy as jnp

    # the moments pass through untouched: the compiler fits a program's
    # temporaries into what ITS arguments leave of the device
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def accumulate(moments, gsum, w, tokens, valid):
        def total(w):
            return jnp.sum(jax.vmap(
                lambda t, v: segment_loss_sum(m, w, t, v))(tokens, valid))

        loss, g = jax.value_and_grad(total)(w)
        return moments, loss, jax.tree.map(jnp.add, gsum, g)

    @jax.jit
    def norms(g, scale):
        sq = {k: jnp.sum(v * v) for k, v in g.items()}
        probes = {k: grad_probe(n, v) * scale for n, (k, v) in enumerate(g.items())}
        return jnp.sqrt(sum(sq.values())) * scale, {
            k: jnp.sqrt(v) * scale for k, v in sq.items()}, probes

    update = jax.jit(
        lambda w, mom, var, g, scale, t: adamw_update(
            opt, w, mom, var, jax.tree.map(lambda x: x * scale, g), t),
        donate_argnums=(0, 1, 2, 3),
    )
    buckets = buckets_for(max(len(h) for h in hist))
    with jax.default_matmul_precision("highest"):
        w = initial_weights(m, seed)
        mom = jax.tree.map(jnp.zeros_like, w)
        var = jax.tree.map(jnp.zeros_like, w)
        records = []
        for s in range(n_steps):
            t0 = time.perf_counter()
            gsum = jax.tree.map(jnp.zeros_like, w)
            losses = []
            count = 0
            members = sorted(steps[s], key=lambda j: len(hist[j]))
            at = 0
            for length, batch in buckets:
                group = []
                while at < len(members) and len(hist[members[at]]) <= length:
                    group.append(members[at])
                    at += 1
                for c0 in range(0, len(group), batch):
                    tok = np.zeros((batch, length), np.int32)
                    val = np.zeros((batch, length), bool)
                    for r, j in enumerate(group[c0 : c0 + batch]):
                        tok[r, : len(hist[j])] = hist[j]
                        val[r, : len(hist[j])] = True
                        count += len(hist[j]) - 1
                    (mom, var), loss, gsum = accumulate(
                        (mom, var), gsum, w, jnp.asarray(tok), jnp.asarray(val))
                    losses.append(loss)
            scale = 1.0 / max(count, 1)
            loss = float(sum(float(x) for x in losses)) * scale
            gnorm, tnorms, probes = norms(gsum, scale)
            w, mom, var = update(w, mom, var, gsum, scale, float(s + 1))
            records.append({
                "loss": loss, "tokens": count, "grad_norm": float(gnorm),
                "tensor_grad_norm": {k: float(v) for k, v in tnorms.items()},
                "tensor_grad_probe": {k: float(v) for k, v in probes.items()},
            })
            say(f"replay step {s + 1}: loss {loss:.6f} over {count} positions, "
                f"gradient norm {float(gnorm):.6g}, {time.perf_counter() - t0:.1f} s")
    return w, records


def update_summary(m, seed, final: dict, n_rows: int) -> dict:
    """Per tensor of the replay: the L2 norm of its update (final - initial)
    and the largest update-row norm over the sampled rows."""
    import jax.numpy as jnp

    init = initial_weights(m, seed)
    out = {}
    for name, w in final.items():
        d = w - init[name]
        rows = d if d.ndim == 1 else jnp.linalg.norm(
            d[sampled_rows(name, d.shape[0], n_rows)], axis=-1)
        out[name] = [float(jnp.linalg.norm(d)), float(jnp.max(jnp.abs(rows)))]
    return out


def replay_job(job: dict, say=print) -> dict:
    """The whole replay of one job description -> records, the update's
    summary, and under ``final`` the final weights as float32 numpy arrays
    with the first step's ``ssd_probe`` beside them."""
    m, opt = job["model"], job["optimizer"]
    data = np.load(job["data"])
    hist = [
        h.astype(np.int32)
        for h in histories(data["user_idx"], data["item_ids"], job["max_len"])
    ]
    rows = rows_of([len(h) for h in hist], job["row_len"])
    t0 = time.perf_counter()
    probe = first_step_probe(
        m, job["seed"], hist, rows[: job["rows_per_step"]], job["row_len"])
    say(f"replay: the first step's state-space probe, {time.perf_counter() - t0:.1f} s")
    w, records = replay(
        m, opt, job["seed"], hist, steps_of(rows, job["rows_per_step"]),
        job["steps"], say)
    summary = update_summary(m, job["seed"], w, job["rows_checked"])
    final = {k: np.asarray(v) for k, v in w.items()}
    final["ssd_probe"] = probe
    return {"records": records, "update": summary, "final": final,
            "replay_s": time.perf_counter() - t0}


def child_main(argv) -> int:
    """``python falcon_h1.py JOB.json``: the replay of the job, its numbers
    as ``out.json`` and its final weights as ``<name>.npy`` beside it."""
    job = json.loads(Path(argv[1]).read_text())
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # the program's own default directory (utils/runtime.py)
        jax.config.update("jax_compilation_cache_dir", str(_REPO / ".jax_cache"))
    platform = jax.devices()[0].platform
    if platform != job["platform"]:
        raise SystemExit(f"the replay got {platform!r}, not {job['platform']!r}")
    res = replay_job(job)
    out = Path(job["out"])
    for name, w in res.pop("final").items():
        np.save(out / f"{name}.npy", w)
    (out / "out.json").write_text(json.dumps(res))
    return 0


# ---------------------------------------------------------------------------
# the check (in the harness's process: numpy only, the device work in a child)


def model_group(cfg: dict) -> dict:
    """The configuration's published widths and held counts under the names
    this file's functions read: its own copy of the share."""
    share = cfg["share"]
    return {
        "hidden_size": cfg["hidden_size"],
        "num_layers": cfg["num_hidden_layers"],
        "head_dim": cfg["head_dim"],
        "rope_theta": float(cfg["rope_theta"]),
        "rms_norm_eps": cfg["rms_norm_eps"],
        "attention_heads_held": cfg["num_attention_heads"],
        "kv_heads_held": cfg["num_key_value_heads"],
        "ssm_heads_held": cfg["mamba_n_heads"],
        "ssm_groups_held": cfg["mamba_n_groups"],
        "mamba_d_head": cfg["mamba_d_head"],
        "mamba_d_state": cfg["mamba_d_state"],
        "mamba_d_conv": cfg["mamba_d_conv"],
        "mlp_columns_held": share["mlp_columns_held"],
        "vocab_rows_held": cfg["vocab_size"],
        "vocab_start": share["vocab_start"],
        **{key: cfg[key] for key in MULTIPLIERS},
    }


def job_of(cfg: dict, platform: str, data_path, out_dir, steps=None) -> dict:
    algo = cfg["engine_json"]["algorithms"][0]["params"]
    prep = cfg["engine_json"]["preparator"]["params"]
    return {
        "platform": platform, "model": model_group(cfg),
        "optimizer": cfg["optimizer"], "seed": algo["seed"],
        "max_len": prep["maxLen"], "row_len": prep["rowLen"],
        "rows_per_step": algo["rowsPerStep"],
        "steps": algo["stepsPerRetrain"] if steps is None else steps,
        "rows_checked": cfg["reference"]["rows_checked"],
        "data": str(data_path), "out": str(out_dir),
    }


def compare_model(cfg: dict, model: dict, res: dict, final, say=print,
                  details: dict | None = None) -> list:
    """The persisted model and its training record against a replay's
    results.  ``final(name)`` gives the replay's final tensor; ``details``,
    where given, receives the per-tensor numbers behind the comparisons."""
    from benchmark.reference import Compared

    ref = cfg["reference"]
    n_steps = cfg["engine_json"]["algorithms"][0]["params"]["stepsPerRetrain"]
    rec = model["training_record"]
    done = len(rec["loss"])
    out = [
        Compared("optimizer_steps", float(done), float(n_steps), "min"),
        Compared("optimizer_steps_over", float(max(done - n_steps, 0)), 0.0),
        Compared(
            "positions_trained_gap",
            abs(float(np.sum(rec["tokens"]))
                - sum(r["tokens"] for r in res["records"][:n_steps])), 0.0),
    ]
    for s in range(n_steps):
        want = res["records"][s]["loss"]
        got = float(rec["loss"][s]) if s < done else float("nan")
        out.append(Compared(
            f"loss_step{s + 1}_rel_gap", abs(got - want) / abs(want),
            ref["loss_rel_gap_limit"]))
    want = res["records"][0]["tensor_grad_norm"]
    gaps = {
        k: abs(float(rec["tensor_grad_norm"][k][0]) - want[k]) / max(want[k], 1e-30)
        for k in want
    }
    worst = max(gaps, key=gaps.get)
    say(f"step-1 gradient norms against the replay: widest relative gap "
        f"{gaps[worst]:.4g} ({worst}), median {np.median(list(gaps.values())):.4g}")
    out.append(Compared(
        "grad_norm_step1_rel_gap_max", gaps[worst], ref["grad_norm_rel_gap_limit"]))
    # each tensor's probe against the replay's, in units of the gradient's
    # own norm (a probe of an error E has standard deviation |E|)
    probe = np.array([
        [
            abs(float(rec["tensor_grad_probe"][k][s]) - r["tensor_grad_probe"][k])
            / max(r["tensor_grad_norm"][k], 1e-30)
            if s < done else np.nan
            for k in want
        ]
        for s, r in enumerate(res["records"][:n_steps])
    ])
    leaf = [k.split(".")[-1] for k in want]
    in_mlp = np.array([n in MLP for n in leaf])
    in_attn = np.array([n in ATTENTION for n in leaf])
    in_ssm = np.array([n.startswith("ssm_") for n in leaf])
    rms = lambda x: float(np.sqrt(np.mean(np.square(x))))  # noqa: E731
    say(f"gradient probes against the replay, in units of each gradient's "
        f"norm: step 1 rms {rms(probe[0]):.4g} (MLP {rms(probe[0][in_mlp]):.4g}, "
        f"state space {rms(probe[0][in_ssm]):.4g}, attention "
        f"{rms(probe[0][in_attn]):.4g}), widest {probe[0].max():.4g} "
        f"({list(want)[int(probe[0].argmax())]}); later steps rms "
        f"{[round(rms(p), 5) for p in probe[1:]]}")
    out += [
        Compared("grad_probe_gap_rms", rms(probe[0]), ref["grad_probe_gap_rms_limit"]),
        Compared("grad_probe_gap_mlp_rms", rms(probe[0][in_mlp]),
                 ref["grad_probe_gap_mlp_rms_limit"]),
        Compared("grad_probe_gap_ssm_rms", rms(probe[0][in_ssm]),
                 ref["grad_probe_gap_ssm_rms_limit"]),
        Compared("grad_probe_gap_attention_rms", rms(probe[0][in_attn]),
                 ref["grad_probe_gap_attention_rms_limit"]),
        Compared("grad_probe_gap_later_steps_rms", rms(probe[1:]),
                 ref["grad_probe_gap_later_steps_rms_limit"]),
    ]
    # the first layer's state space on the first step's rows: the program's
    # (chunks, kernel, carried state) against the recurrence on the same inputs
    ssd_want = np.asarray(final("ssd_probe"))
    ssd_got = np.asarray(rec.get("ssd_probe", np.zeros(0)), np.float32)
    real = np.isfinite(ssd_want)
    if ssd_got.shape != ssd_want.shape or not real.any():
        ssd_gap = float("inf")
    else:
        err = np.where(
            real, ssd_got - np.where(real, ssd_want, 0.0), 0.0).astype(np.float64)
        ref_sq = np.where(real, ssd_want, 0.0).astype(np.float64) ** 2
        ssd_gap = float(np.sqrt(np.sum(err ** 2) / np.sum(ref_sq)))
        by_head = np.sqrt(np.sum(err ** 2, axis=(0, 1)) / np.sum(ref_sq, axis=(0, 1)))
        say(f"first layer's state space against the recurrence on the first "
            f"step's rows: relative L2 {ssd_gap:.4g} over {int(real.sum())} "
            f"values; by head {by_head.min():.3g} .. {by_head.max():.3g} (head "
            f"{int(by_head.argmax())}); largest gap over largest value "
            f"{np.abs(err).max() / np.sqrt(ref_sq.max()):.4g}")
        if details is not None:
            details["ssd_probe_by_head"] = by_head.tolist()
    out.append(Compared("ssd_probe_rel_gap", ssd_gap, ref["ssd_probe_rel_gap_limit"]))
    rel, row = {}, {}
    for name, (norm, row_norm) in res["update"].items():
        gap = np.asarray(model["params"][name], np.float32) - final(name)
        rel[name] = float(np.linalg.norm(gap)) / max(norm, 1e-30)
        rows = gap if gap.ndim == 1 else np.linalg.norm(
            gap[sampled_rows(name, gap.shape[0], ref["rows_checked"])], axis=-1)
        row[name] = float(np.max(np.abs(rows))) / max(row_norm, 1e-30)
    rel_worst, row_worst = max(rel, key=rel.get), max(row, key=row.get)
    say(f"weight updates against the replay: relative L2 widest "
        f"{rel[rel_worst]:.4g} ({rel_worst}), median "
        f"{np.median(list(rel.values())):.4g}; row gap widest "
        f"{row[row_worst]:.4g} ({row_worst})")
    if details is not None:
        details.update(
            grad_norm_gap=gaps,
            probe_gap={k: probe[:, n].tolist() for n, k in enumerate(want)},
            update_rel_l2=rel, update_row_gap=row)
    mlp_gaps = [v for k, v in rel.items() if k.split(".")[-1] in MLP]
    decay_gaps = [v for k, v in rel.items() if k.split(".")[-1] in DECAY]
    return out + [
        Compared("update_rel_l2_max", rel[rel_worst], ref["update_rel_l2_max_limit"]),
        Compared("update_rel_l2_median", float(np.median(list(rel.values()))),
                 ref["update_rel_l2_median_limit"]),
        Compared("update_rel_l2_mlp_max", max(mlp_gaps),
                 ref["update_rel_l2_mlp_max_limit"]),
        Compared("update_rel_l2_decay_max", max(decay_gaps),
                 ref["update_rel_l2_decay_max_limit"]),
        Compared("update_row_gap_max", row[row_worst], ref["update_row_gap_max_limit"]),
    ]


#: one replay a run: both apps of a window hold the same who-rated-what
_REPLAY: dict = {}


def check_retrain(ctx, model: dict, status: str, user_idx, item_idx, rating) -> list:
    """One retrain's persisted model against the replay, which is made once a
    run (the first time this is called) and serves every app's check."""
    from benchmark.reference import Compared

    cfg = ctx.config
    m = model_group(cfg)
    shapes = tensor_shapes(m)
    params = model.get("params", {})
    shape_ok = set(params) == set(shapes) and all(
        tuple(np.shape(params[k])) == tuple(s) for k, s in shapes.items())
    finite = shape_ok and all(bool(np.isfinite(v).all()) for v in params.values())
    ids = vocabulary_ids(model, item_idx, m["vocab_start"])
    compared = [
        Compared("instance_completed", float(status == "COMPLETED"), 1.0, "min"),
        Compared("tensor_shapes_as_configured", float(shape_ok), 1.0, "min"),
        Compared("weights_finite", float(finite), 1.0, "min"),
        Compared("vocabulary_first_seen_bijection", float(ids is not None), 1.0, "min"),
    ]
    if not (shape_ok and finite and ids is not None):
        return compared
    if _REPLAY.get("ctx") is not ctx:
        work = Path(ctx.run.work)
        out_dir = work / "replay"
        out_dir.mkdir(exist_ok=True)
        np.savez(work / "replay_data.npz", user_idx=user_idx, item_ids=ids)
        job = job_of(cfg, ctx.run.platform, work / "replay_data.npz", out_dir)
        (work / "replay_job.json").write_text(json.dumps(job))
        t0 = time.perf_counter()
        child = ctx.run.run_child(
            "replay",
            [sys.executable, str(Path(__file__).resolve()), str(work / "replay_job.json")],
            timeout=1500.0,
        )
        for line in child.stdout().splitlines():
            ctx.say(line)
        res = json.loads((out_dir / "out.json").read_text())
        ctx.say(f"replay: {res['replay_s']:.1f} s of it the steps, "
                f"{time.perf_counter() - t0:.1f} s with the child's start")
        _REPLAY.clear()
        _REPLAY.update(ctx=ctx, res=res, dir=out_dir)
    t0 = time.perf_counter()
    out_dir = _REPLAY["dir"]
    compared += compare_model(
        cfg, model, _REPLAY["res"],
        lambda name: np.load(out_dir / f"{name}.npy", mmap_mode="r"), ctx.say)
    ctx.say(f"comparison with the replay: {time.perf_counter() - t0:.1f} s")
    return compared


if __name__ == "__main__":
    raise SystemExit(child_main(sys.argv))
