"""The Nemotron-H stack's plain reference (Nemotron-3-Nano-30B-A3B): layers
that are ONE sublayer each, ``x + f(RMSNorm(x))``, of three kinds in one stack
-- Mamba-2 (``M``), grouped-query attention with no positions (``*``), routed
relu^2 experts chosen by a sigmoid router beside a shared expert (``E``);
next-item training with AdamW.

Straight ``jax.numpy`` in float32 with ``jax.default_matmul_precision
("highest")``: the state space token by token exactly as written below, a
full masked score matrix a head and block of queries, every held expert
applied DENSELY to every token under a mask of the chosen (no dispatch, no
grouping, no plan), ONE SEGMENT AT A TIME: no packing, no chunks, no kernels.
Gradients by ``jax.grad``, AdamW written out.  Nothing of the program is
imported; what no model's mathematics enters (how histories are grouped into
optimiser steps, the seeded gradient probe, the sampled rows, the vocabulary's
order) is shared with ``references/olmo_hybrid.py``, the token-by-token state
space, its convolution and gated norm with ``references/falcon_h1.py``, the
blocked score matrix with ``references/smallthinker.py``.

The published description is the model's config.json (huggingface.co/nvidia/
NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type: nemotron_h``); what it does
not settle is the configuration file's ``assumed``.  Per token t of a segment,
stream x in R^D, ``m`` the configuration's ``model`` group (``model_group``),
``h = RMSNorm(x; input_norm)``, a layer ``x + f(h)`` with ``f`` one of

    M   u = h W_in  ->  z [H P] | xBC [H P + 2 G N] | dt [H]
        xBC = silu(conv4(xBC) + b_conv);  x, B, C = split(xBC)
        Delta = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(Delta_t A) S_(t-1) + Delta_t x_t B_t^T,  S = 0 at the start
        y_t = S_t C_t + D x_t
        f   = W_out GroupRMSNorm(y * silu(z); w_norm)        the norm AFTER the gate
    *   q, k, v = h W_q, h W_k, h W_v;  NO positions;  causal softmax, scale
        1/sqrt(d), a KV head serving its query heads;  f = W_o o
    E   s = sigmoid(h W_r)                                   over ALL experts
        chosen = the k largest of s + b                      b: the selection bias
        w_j = scale * s[chosen_j] / (sum_j s[chosen_j] + 1e-20)
        f   = sum over chosen e HELD of w_e W_down,e relu(W_up,e h)^2
              + W_down,s relu(W_up,s h)^2                    the shared expert's held columns

The share (model-configs guide, section 4): the tensors are the slices one of
``chips`` chips holds (one B / C group's state-space heads, its query heads on
their KV head, its experts and its columns of the shared expert, its
vocabulary rows; the router and ``b`` whole), every function computes what
those slices give, an item id outside the held rows embeds to zero, logits and
loss run over the held rows, and a chosen expert that is not held adds nothing.

``check_retrain`` replays the configured optimiser steps from the seeded
initial weights in ONE child process on the chip and holds the persisted model
to the replay.  Two numbers are not the replay's.  ``ssd_probe`` runs the
FIRST layer's state space (an ``M`` layer) token by token on inputs projected
in the stated bf16 product, so that the program's record differs by the
recurrence alone.  ``moe_probe`` applies the first ``E`` layer's ``f`` to the
EMBEDDED rows under that layer's own norm (exact rows, normed in float32: the
same numbers on both sides, which the stream that layer really reads is not),
its products in the stated bf16, so that the program's record of the same
quantity differs by the expert path alone.  Routing is discrete, so the
choices themselves are compared: all of them (``route_flip_share_layer<n>``)
and, in the first routed layer (the one whose input no earlier flip has
touched), exactly where the replay's k-th and (k+1)-th biased scores lie
further apart than ``reference.clear_margin`` (``route_flip_clear_share``).
The largest row gap of a tensor's update is printed and not compared: four
sign-like AdamW steps saturate it (1.7-2.0 of a possible 2 in every column).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from pathlib import Path

import numpy as np

_REPO = Path(__file__).resolve().parents[2]
if str(_REPO) not in sys.path:  # run as a script: the replay's child
    sys.path.insert(0, str(_REPO))

from benchmark.references.falcon_h1 import (  # noqa: E402
    causal_conv, gated_group_norm, selective_scan)
from benchmark.references.olmo_hybrid import (  # noqa: E402
    PROBE_SEED, bf16_product, histories, rmsnorm, rows_of, sampled_rows, silu,
    steps_of, vocabulary_ids)
from benchmark.references.olmo_hybrid import grad_probe as _matrix_probe  # noqa: E402
from benchmark.references.smallthinker import GLOBAL  # noqa: E402
from benchmark.references.smallthinker import attention as _causal_attention  # noqa: E402

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"
ROUTED = ("experts_up", "experts_down")
SHARED = ("shared_up", "shared_down")
QKVO = ("q", "k", "v", "o")
DECAY = ("ssm_a_log", "ssm_dt_bias", "ssm_d")

#: positions a block of the loss holds
LOSS_BLOCK = 2048


# ---------------------------------------------------------------------------
# shapes and the seeded initial weights (the rule of the configuration file)


def tensor_shapes(m: dict) -> dict:
    """Flat name -> held shape, in the order the initialisation counts."""
    D, hd = m["hidden_size"], m["head_dim"]
    A, KV = m["attention_heads_held"], m["kv_heads_held"]
    H, P, G, N, K = (m["ssm_heads_held"], m["mamba_head_dim"], m["ssm_groups_held"],
                     m["ssm_state_size"], m["conv_kernel"])
    E, F, S, V = (m["experts_held"], m["expert_width"], m["shared_columns_held"],
                  m["vocab_rows_held"])
    ch, bc = H * P, G * N
    out = {"embed": (V, D)}
    for i, kind in enumerate(m["layer_kinds"]):
        p = f"layer{i}."
        out[p + "input_norm"] = (D,)
        if kind == MAMBA:
            out.update({
                p + "ssm_in": (D, 2 * ch + 2 * bc + H),
                p + "ssm_conv": (K, ch + 2 * bc), p + "ssm_conv_bias": (ch + 2 * bc,),
                p + "ssm_a_log": (H,), p + "ssm_d": (H,), p + "ssm_dt_bias": (H,),
                p + "ssm_norm": (ch,), p + "ssm_out": (ch, D),
            })
        elif kind == ATTENTION:
            out.update({
                p + "q": (D, A * hd), p + "k": (D, KV * hd), p + "v": (D, KV * hd),
                p + "o": (A * hd, D),
            })
        elif kind == EXPERTS:
            out.update({
                p + "router": (D, m["experts"]), p + "router_bias": (m["experts"],),
                p + "shared_up": (D, S), p + "shared_down": (S, D),
                p + "experts_up": (E, D, F), p + "experts_down": (E, F, D),
            })
        else:
            raise ValueError(kind)
    out["final_norm"] = (D,)
    out["head"] = (V, D)
    return out


def initial_weights(m: dict, seed: int) -> dict:
    """Tensor number n draws from ``fold_in(PRNGKey(seed), n)`` at its held
    shape (the configuration's ``initialisation``): norm weights and ``D`` 1,
    the selection bias 0, the convolution uniform(+-1/sqrt(width)), ``A_log =
    log(uniform(1, 16))``, ``dt_bias`` the inverse softplus of
    ``exp(uniform(log 0.001, log 0.1))`` floored at 1e-4, every matrix (the
    router and the stacked experts too) normal(0, 0.02)."""
    import math

    import jax
    import jax.numpy as jnp

    base = jax.random.PRNGKey(seed)
    out = {}
    for n, (name, shape) in enumerate(tensor_shapes(m).items()):
        key = jax.random.fold_in(base, n)
        leaf = name.split(".")[-1]
        if leaf.endswith("norm") or leaf == "ssm_d":
            w = jnp.ones(shape, jnp.float32)
        elif leaf == "router_bias":
            w = jnp.zeros(shape, jnp.float32)
        elif "conv" in leaf:
            bound = 1.0 / math.sqrt(m["conv_kernel"])
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
# the layers, for ONE segment: h [T, D] the normed stream; a segment is padded
# at its END to a length the replay compiles once (nothing after a token can
# reach it)


def ssm_inputs(m, p, h, product=None):
    """What the state space reads: x [T, H, P], Delta [T, H], B, C [T, G, N],
    and the gate z [T, H P].  ``product`` is how the projection is made
    (default: the plain float32 product)."""
    import jax.numpy as jnp

    product = product or jnp.matmul
    T = h.shape[0]
    H, P, G, N = (m["ssm_heads_held"], m["mamba_head_dim"], m["ssm_groups_held"],
                  m["ssm_state_size"])
    u = product(h, p["ssm_in"])
    z, xbc, dt = jnp.split(u, (H * P, 2 * H * P + 2 * G * N), axis=-1)
    xbc = silu(causal_conv(xbc, p["ssm_conv"], p["ssm_conv_bias"]))
    x, b, c = jnp.split(xbc, (H * P, H * P + G * N), axis=-1)
    dt = jnp.logaddexp(0.0, dt + p["ssm_dt_bias"])  # softplus; no clamp
    return x.reshape(T, H, P), dt, b.reshape(T, G, N), c.reshape(T, G, N), z


def mamba(m, p, h, norm_axis=None):
    """The held heads' part of an ``M`` layer's ``f``."""
    import jax.numpy as jnp

    T = h.shape[0]
    G = m["ssm_groups_held"]
    x, dt, b, c, z = ssm_inputs(m, p, h)
    y = selective_scan(x, dt, -jnp.exp(p["ssm_a_log"]), b, c)
    y = y + p["ssm_d"][:, None] * x
    y = gated_group_norm(
        y.reshape(T, G, -1), z.reshape(T, G, -1), p["ssm_norm"].reshape(G, -1),
        m["layer_norm_epsilon"], norm_axis)
    return y.reshape(T, -1) @ p["ssm_out"]


def attention(m, p, h):
    """The held query heads' part of the ``*`` layer's ``f``: causal softmax
    over the segment, no positional encoding."""
    return _causal_attention(m, p, h, GLOBAL)


def route(m, logits, bias):
    """-> (the chosen experts [T, k], their weights [T, k], the margin [T]
    between the k-th and the (k+1)-th biased score)."""
    import jax
    import jax.numpy as jnp

    k = m["experts_per_token"]
    s = 1.0 / (1.0 + jnp.exp(-logits))
    top, idx = jax.lax.top_k(s + bias, k + 1)
    idx = idx[:, :k]
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = m["routed_scaling_factor"] * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return idx, w, top[:, k - 1] - top[:, k]


def relu2(x):
    import jax.numpy as jnp

    r = jnp.maximum(x, 0.0)
    return r * r


def routed_experts(m, p, x, idx, w, product=None):
    """The HELD experts' part: each applied to every token, weighted by the
    token's weight for it (zero where the token did not choose it)."""
    import jax
    import jax.numpy as jnp

    product = product or jnp.matmul

    @jax.checkpoint
    def one(out, args):
        up, down, e = args
        chose = jnp.sum(jnp.where(idx == e + m["expert_start"], w, 0.0), axis=-1)
        return out + chose[:, None] * product(relu2(product(x, up)), down), None

    return jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_up"], p["experts_down"], jnp.arange(m["experts_held"])))[0]


def shared_expert(p, x, product=None):
    import jax.numpy as jnp

    product = product or jnp.matmul
    return product(relu2(product(x, p["shared_up"])), p["shared_down"])


def experts(m, p, h, product=None):
    """An ``E`` layer's ``f`` -> (routed + shared, the choices [T, k], the
    margin [T])."""
    idx, w, margin = route(m, h @ p["router"], p["router_bias"])
    y = routed_experts(m, p, h, idx, w, product)
    if m["shared_columns_held"]:
        y = y + shared_expert(p, h, product)
    return y, idx, margin


def embed(m, table, tokens):
    import jax.numpy as jnp

    idx = tokens - m["vocab_start"]
    held = (idx >= 0) & (idx < table.shape[0])
    return jnp.where(held[:, None], table[jnp.where(held, idx, 0)], 0.0)


def layer_tensors(w: dict, i: int) -> dict:
    p = f"layer{i}."
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


def block(m, kind, p, x, norm_axis=None):
    """-> (the stream after the layer, the router's choices and margin, or
    None where the layer routes nothing)."""
    h = rmsnorm(x, p["input_norm"], m["layer_norm_epsilon"])
    if kind == MAMBA:
        return x + mamba(m, p, h, norm_axis), None
    if kind == ATTENTION:
        return x + attention(m, p, h), None
    y, idx, margin = experts(m, p, h)
    return x + y, (idx, margin)


def final_hidden(m, w, tokens):
    """-> ([T, D] after the last norm, the ``E`` layers' choices [L_E, T, k]
    and margins [L_E, T])."""
    import jax
    import jax.numpy as jnp

    x = embed(m, w["embed"], tokens)
    routed = []
    for i, kind in enumerate(m["layer_kinds"]):
        # recomputation changes no number, only what is held between passes
        x, record = jax.checkpoint(functools.partial(block, m, kind))(
            layer_tensors(w, i), x)
        if record is not None:
            routed.append(record)
    k = m["experts_per_token"]
    choices = (jnp.stack([c for c, _ in routed]) if routed
               else jnp.zeros((0, x.shape[0], k), jnp.int32))
    margins = (jnp.stack([g for _, g in routed]) if routed
               else jnp.zeros((0, x.shape[0]), jnp.float32))
    return rmsnorm(x, w["final_norm"], m["layer_norm_epsilon"]), choices, margins


def segment_loss_sum(m, w, tokens, valid):
    """Sum over the segment's real, non-final positions t of the
    cross-entropy of token t + 1 given tokens <= t, over the held rows
    (``LOSS_BLOCK`` positions' logits at a time), the ``E`` layers' choices
    and margins."""
    import jax
    import jax.numpy as jnp

    h, choices, margins = final_hidden(m, w, tokens)
    T = h.shape[0] - 1
    lb = min(LOSS_BLOCK, T)
    blocks = -(-T // lb)
    pad = blocks * lb - T
    target = jnp.pad(tokens[1:] - m["vocab_start"], (0, pad))
    counted = jnp.pad(valid[1:], (0, pad))

    @jax.checkpoint
    def part(args):
        hb, tb, vb = args
        logits = hb @ w["head"].T
        top = jnp.max(logits, axis=-1)
        lse = top + jnp.log(jnp.sum(jnp.exp(logits - top[:, None]), axis=-1))
        picked = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(vb, lse - picked, 0.0))

    loss = jnp.sum(jax.lax.map(part, (
        jnp.pad(h[:-1], ((0, pad), (0, 0))).reshape(blocks, lb, -1),
        target.reshape(blocks, lb), counted.reshape(blocks, lb))))
    return loss, (choices, margins)


def no_decay(name: str) -> bool:
    return any(s in name for s in (
        "norm", "a_log", "dt_bias", "conv", "ssm_d", "router_bias"))


def adamw_update(opt, w, mom, var, grad, t):
    """One AdamW step, written out; ``t`` counts from 1.  The selection bias
    gets no gradient and no decay: it stays where it was."""
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


def _as_matrix(x):
    return x.reshape(-1, x.shape[-1]) if x.ndim > 2 else x


def grad_probe(n: int, g):
    """The seeded functional of tensor number n's gradient; stacked experts as
    one matrix, the experts' rows on end."""
    return _matrix_probe(n, _as_matrix(g))


# ---------------------------------------------------------------------------
# the replay (needs the device: the child process, or a chip script)


def ssd_probe(m, w, tokens):
    """The FIRST layer's state-space output ``S_t C_t`` (before the ``D``
    skip) for one segment, each head's P values along the seeded vector
    (standard normal from ``fold_in(PRNGKey(PROBE_SEED), 2**20 + 1)``) ->
    [T, H].  The recurrence is the float32 one token by token; the projection
    is made in the stated precision of the products, so that both sides hand
    the recurrence the same numbers and the gap is the recurrence's own: its
    chunks, its kernel, the precision of the state it carries."""
    import jax
    import jax.numpy as jnp

    p = layer_tensors(w, 0)
    h = rmsnorm(embed(m, w["embed"], tokens), p["input_norm"], m["layer_norm_epsilon"])
    x, dt, b, c, _ = ssm_inputs(m, p, h, bf16_product)
    y = selective_scan(x, dt, -jnp.exp(p["ssm_a_log"]), b, c)
    r = jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(PROBE_SEED), 2 ** 20 + 1),
        (y.shape[-1],), jnp.float32)
    return y @ r


def first_experts_layer(m) -> int:
    return m["layer_kinds"].index(EXPERTS)


def moe_probe(m, w, tokens):
    """The first ``E`` layer's ``f`` (its router's choices and weights in
    float32, the held experts densely, the shared expert) applied to the
    EMBEDDED rows under that layer's norm, for one segment, along the seeded
    vector (standard normal [D] from ``fold_in(PRNGKey(PROBE_SEED), 2**20 +
    2)``) -> [T].  The products are made in the stated precision (bfloat16
    inputs, float32 accumulation), so the gap to the program's record is the
    expert path's own: a pair dropped or misrouted, a tile's rows, the
    accumulation's precision, the combine, the shared expert's columns."""
    import jax
    import jax.numpy as jnp

    p = layer_tensors(w, first_experts_layer(m))
    h = rmsnorm(embed(m, w["embed"], tokens), p["input_norm"], m["layer_norm_epsilon"])
    y = experts(m, p, h, bf16_product)[0]
    r = jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(PROBE_SEED), 2 ** 20 + 2),
        (y.shape[-1],), jnp.float32)
    return y @ r


def moe_grad_probe(m, w, tokens, n):
    """The gradient of the ROUTED part of ``moe_probe``'s sum over a
    segment's first ``n`` tokens (the rest is padding): with respect to the
    two stacked matrices, each contracted over the hidden axis with a second
    seeded vector (``fold_in(PRNGKey(PROBE_SEED), 2**20 + 3)``) -> "up" and
    "down" [held, F], and with respect to the experts' input, summed over
    the tokens -> "input" [D].  The backward's four products are written out,
    an expert at a time over every token, in the stated precision: bfloat16
    inputs and float32 accumulation, a token's weight multiplied in float32
    (into the output's gradient before the product that sums over tokens,
    after the one that sums over the hidden axis).  ``jax.grad`` of the dense
    float32 form is what ``benchmark/tests/test_nemotron_cell.py`` holds these
    equations to."""
    import jax
    import jax.numpy as jnp

    p = layer_tensors(w, first_experts_layer(m))
    h = rmsnorm(embed(m, w["embed"], tokens), p["input_norm"], m["layer_norm_epsilon"])
    idx, wt, _ = route(m, h @ p["router"], p["router_bias"])
    wt = wt * (jnp.arange(tokens.shape[0]) < n)[:, None]
    D = h.shape[-1]
    key = jax.random.PRNGKey(PROBE_SEED)
    r = jax.random.normal(jax.random.fold_in(key, 2 ** 20 + 2), (D,), jnp.float32)
    q = jax.random.normal(jax.random.fold_in(key, 2 ** 20 + 3), (D,), jnp.float32)
    g = jnp.broadcast_to(r, h.shape)

    def one(dm, args):
        up, down, e = args
        chose = jnp.sum(jnp.where(idx == e + m["expert_start"], wt, 0.0), axis=-1)
        u = bf16_product(h, up)
        ddown = bf16_product(relu2(u).T, g * chose[:, None])
        du = bf16_product(g, down.T) * chose[:, None] * 2.0 * jnp.maximum(u, 0.0)
        dup = bf16_product(h.T, du)
        return dm + bf16_product(du, up.T), (q @ dup, ddown @ q)

    dm, (up, down) = jax.lax.scan(one, jnp.zeros_like(h), (
        p["experts_up"], p["experts_down"], jnp.arange(m["experts_held"])))
    return {"up": up, "down": down, "input": jnp.sum(dm, axis=0)}


def first_step_probes(m, seed, hist, rows, row_len):
    """``ssd_probe`` and ``moe_probe`` of every history of the first optimiser
    step, from the seeded initial weights, laid where the packing puts the
    history (row, offset) -> float32 [rows, row_len, H] and [rows, row_len,
    1], NaN on padding (and all NaN where the stack has no such layer, or its
    first layer is not the state space); and ``moe_grad_probe`` summed over
    the FIRST row's histories (None where no layer routes)."""
    import jax
    import jax.numpy as jnp

    kinds = m["layer_kinds"]
    ssd = np.full((len(rows), row_len, m["ssm_heads_held"]), np.nan, np.float32)
    routed = np.full((len(rows), row_len, 1), np.nan, np.float32)
    grads = None
    length = max(len(hist[j]) for row in rows for j in row)
    with jax.default_matmul_precision("highest"):
        w = initial_weights(m, seed)
        probes = []
        if kinds[0] == MAMBA:
            probes.append((ssd, jax.jit(lambda w, t: ssd_probe(m, w, t))))
        if EXPERTS in kinds:
            probes.append((routed, jax.jit(lambda w, t: moe_probe(m, w, t)[:, None])))
            backward = jax.jit(lambda w, t, n: moe_grad_probe(m, w, t, n))
        keep = {0, first_experts_layer(m) if EXPERTS in kinds else 0}
        w = {k: v for k, v in w.items() if k == "embed" or any(
            k.startswith(f"layer{i}.") for i in keep)}
        for r, row in enumerate(rows):
            at = 0
            for j in row:
                tok = np.zeros(length, np.int32)
                tok[: len(hist[j])] = hist[j]
                for out, probe in probes:
                    out[r, at : at + len(hist[j])] = np.asarray(
                        probe(w, jnp.asarray(tok)))[: len(hist[j])]
                if r == 0 and EXPERTS in kinds:
                    part = jax.tree.map(
                        np.asarray, backward(w, jnp.asarray(tok), len(hist[j])))
                    grads = part if grads is None else jax.tree.map(
                        np.add, grads, part)
                at += len(hist[j])
    return ssd, routed, grads


def buckets_for(max_len: int) -> tuple:
    """Padded segment lengths the replay compiles (a segment takes the
    smallest that holds it) and how many segments of each go through one
    call: ``max_len`` over 32, 16, 8, 4, 2 and 1, half of ``max_len``
    positions a call and the longest alone.  A step's time is its PADDED
    positions' (every held expert runs on every one), and the histories are
    many short ones beside a few long: three lengths padded 2.38 positions a
    real one over the cell's four steps, these six 1.58."""
    return tuple(
        (max(-(-max_len // d), 2), max(d // 2, 1)) for d in (32, 16, 8, 4, 2, 1))


def replay_programs(m, opt) -> tuple:
    """The replay's three jitted programs: ``accumulate`` (a call's segments'
    summed loss and gradients added to the step's), ``norms`` and ``update``
    at a step's end."""
    import jax
    import jax.numpy as jnp

    held = (m["expert_start"], m["expert_start"] + m["experts_held"])

    # the moments pass through untouched: the compiler fits a program's
    # temporaries into what ITS arguments leave of the device
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def accumulate(moments, gsum, w, tokens, valid):
        def total(w):
            loss, routed = jax.vmap(
                lambda t, v: segment_loss_sum(m, w, t, v))(tokens, valid)
            return jnp.sum(loss), routed

        (loss, (choices, margins)), g = jax.value_and_grad(total, has_aux=True)(w)
        mine = (choices >= held[0]) & (choices < held[1]) & valid[:, None, :, None]
        return (moments, loss, jax.tree.map(jnp.add, gsum, g),
                choices.astype(jnp.int16), margins, jnp.sum(mine, axis=(0, 2, 3)))

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
    return accumulate, norms, update


def calls_of(buckets, hist, members) -> list:
    """A step's histories cut into the replay's calls -> [((length, batch),
    the call's histories)], the longest first: a step's first calls then keep
    the device busy while the shorter shapes' programs are still being made
    ready."""
    members = sorted(members, key=lambda j: len(hist[j]))
    out, at = [], 0
    for length, batch in buckets:
        group = []
        while at < len(members) and len(hist[members[at]]) <= length:
            group.append(members[at])
            at += 1
        out += [((length, batch), group[c0 : c0 + batch])
                for c0 in range(0, len(group), batch)]
    return out[::-1]


def compiled_beside(m, opt, shapes) -> dict:
    """Every program of the replay compiled at its shapes -> name -> future
    of the compiled program.  One thread traces them in the order given (the
    order of first use: tracing holds Python's lock, so side by side they
    would all be ready last), each compile runs on a thread of its own (the
    compiler, and a read of the cache, work outside the lock): beside one
    another and beside the device's work on the probes and on the shapes
    already there, so a run waits for the slowest and not for their sum."""
    import jax
    import jax.numpy as jnp

    accumulate, norms, update = replay_programs(m, opt)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    w = {k: f32(s) for k, s in tensor_shapes(m).items()}
    todo = [((length, batch), accumulate, (
        (w, w), w, w, jax.ShapeDtypeStruct((batch, length), jnp.int32),
        jax.ShapeDtypeStruct((batch, length), jnp.bool_)))
        for length, batch in shapes]
    todo += [("norms", norms, (w, f32(()))),
             ("update", update, (w, w, w, w, f32(()), f32(())))]
    out = {name: Future() for name, _, _ in todo}
    compilers = ThreadPoolExecutor(max_workers=len(todo))

    def finish(name, lowered):
        try:
            out[name].set_result(lowered.compile())
        except BaseException as e:  # the waiting step raises it
            out[name].set_exception(e)

    def trace():
        for name, fn, specs in todo:
            try:
                with jax.default_matmul_precision("highest"):  # a thread's own setting
                    lowered = fn.lower(*specs)
            except BaseException as e:
                out[name].set_exception(e)
                continue
            compilers.submit(finish, name, lowered)
        compilers.shutdown(wait=False)

    threading.Thread(target=trace, daemon=True).start()
    return out


def replay(m, opt, seed, hist, steps, n_steps, say=print, programs=None):
    """``n_steps`` optimiser steps from the seeded initial weights -> (final
    weights, per-step records, the first step's choices and margins by
    history).  ``programs``: ``compiled_beside``'s, where the caller has
    started them already."""
    import jax
    import jax.numpy as jnp

    n_routed = m["layer_kinds"].count(EXPERTS)
    calls = replay_calls(hist, steps, n_steps)
    programs = programs or compiled_beside(m, opt, shapes_of(calls))
    first: dict = {}
    with jax.default_matmul_precision("highest"):
        w = initial_weights(m, seed)
        mom = jax.tree.map(jnp.zeros_like, w)
        var = jax.tree.map(jnp.zeros_like, w)
        records = []
        for s in range(n_steps):
            t0 = time.perf_counter()
            gsum = jax.tree.map(jnp.zeros_like, w)
            losses, pairs = [], []
            count = tokens_seen = 0
            for (length, batch), some in calls[s]:
                tok = np.zeros((batch, length), np.int32)
                val = np.zeros((batch, length), bool)
                for r, j in enumerate(some):
                    tok[r, : len(hist[j])] = hist[j]
                    val[r, : len(hist[j])] = True
                    count += len(hist[j]) - 1
                    tokens_seen += len(hist[j])
                (mom, var), loss, gsum, choices, margins, held_pairs = programs[
                    length, batch].result()(
                    (mom, var), gsum, w, jnp.asarray(tok), jnp.asarray(val))
                losses.append(loss)
                pairs.append(held_pairs)
                if s == 0:
                    choices, margins = np.asarray(choices), np.asarray(margins)
                    for r, j in enumerate(some):
                        first[j] = (choices[r, :, : len(hist[j])],
                                    margins[r, :, : len(hist[j])])
            scale = 1.0 / max(count, 1)
            loss = float(sum(float(x) for x in losses)) * scale
            gnorm, tnorms, probes = programs["norms"].result()(gsum, np.float32(scale))
            w, mom, var = programs["update"].result()(
                w, mom, var, gsum, np.float32(scale), np.float32(s + 1))
            records.append({
                "loss": loss, "tokens": count, "grad_norm": float(gnorm),
                "tensor_grad_norm": {k: float(v) for k, v in tnorms.items()},
                "tensor_grad_probe": {k: float(v) for k, v in probes.items()},
                "moe_pairs_held": (np.sum(
                    [np.asarray(x) for x in pairs], axis=0) if n_routed
                    else np.zeros(0, np.int64)).tolist(),
                "moe_pairs_total": tokens_seen * m["experts_per_token"],
            })
            say(f"replay step {s + 1}: loss {loss:.6f} over {count} positions in "
                f"{len(calls[s])} calls, gradient norm {float(gnorm):.6g}, held pairs "
                f"a routed layer {records[-1]['moe_pairs_held']} of "
                f"{records[-1]['moe_pairs_total']}, {time.perf_counter() - t0:.1f} s")
    return w, records, first


def replay_calls(hist, steps, n_steps) -> list:
    """Each replayed step's calls (``calls_of``)."""
    buckets = buckets_for(max(len(h) for h in hist))
    return [calls_of(buckets, hist, steps[s]) for s in range(n_steps)]


def shapes_of(calls) -> list:
    """The calls' shapes in the order of their first use."""
    return list(dict.fromkeys(shape for step in calls for shape, _ in step))


def update_summary(m, seed, final: dict, n_rows: int) -> dict:
    """Per tensor of the replay: the L2 norm of its update (final - initial)
    and the largest update-row norm over the sampled rows (stacked experts as
    one matrix).  ONE program over all tensors: sixty tensors' worth of
    single operations cost the host more than the arithmetic costs the
    device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def summary(final, init):
        out = {}
        for name, w in final.items():
            d = _as_matrix(w - init[name])
            rows = d if d.ndim == 1 else jnp.linalg.norm(
                d[sampled_rows(name, d.shape[0], n_rows)], axis=-1)
            out[name] = jnp.stack([jnp.linalg.norm(d), jnp.max(jnp.abs(rows))])
        return out

    return {k: np.asarray(v).tolist()
            for k, v in summary(final, initial_weights(m, seed)).items()}


def replay_job(job: dict, say=print) -> dict:
    """The whole replay of one job description -> records, the update's
    summary, and under ``final`` the final weights as float32 numpy arrays
    with the first step's ``ssd_probe``, ``moe_probe``, ``choices`` and
    ``route_margin`` (laid out as the packing lays the histories; -1 and NaN
    on padding) and the first row's ``moe_grad_probe_*`` beside them."""
    m, opt = job["model"], job["optimizer"]
    data = np.load(job["data"])
    hist = [
        h.astype(np.int32)
        for h in histories(data["user_idx"], data["item_ids"], job["max_len"])
    ]
    rows = rows_of([len(h) for h in hist], job["row_len"])
    first_rows = rows[: job["rows_per_step"]]
    t0 = time.perf_counter()
    steps = steps_of(rows, job["rows_per_step"])
    programs = compiled_beside(
        m, opt, shapes_of(replay_calls(hist, steps, job["steps"])))
    ssd, routed, grads = first_step_probes(
        m, job["seed"], hist, first_rows, job["row_len"])
    say(f"replay: the first step's probes, {time.perf_counter() - t0:.1f} s")
    w, records, first = replay(m, opt, job["seed"], hist, steps, job["steps"], say, programs)
    n_routed = m["layer_kinds"].count(EXPERTS)
    choices = np.full(
        (len(first_rows), n_routed, job["row_len"], m["experts_per_token"]),
        -1, np.int16)
    margins = np.full((len(first_rows), n_routed, job["row_len"]), np.nan, np.float32)
    for r, row in enumerate(first_rows):
        at = 0
        for j in row:
            if j in first:
                choices[r, :, at : at + len(hist[j])] = first[j][0]
                margins[r, :, at : at + len(hist[j])] = first[j][1]
            at += len(hist[j])
    t1 = time.perf_counter()
    summary = update_summary(m, job["seed"], w, job["rows_checked"])
    final = {k: np.asarray(v) for k, v in w.items()}
    say(f"replay: the update's summary and the fetch, {time.perf_counter() - t1:.1f} s")
    final.update(ssd_probe=ssd, moe_probe=routed, choices=choices, route_margin=margins)
    final.update({f"moe_grad_probe_{k}": v for k, v in (grads or {}).items()})
    return {"records": records, "update": summary, "final": final,
            "replay_s": time.perf_counter() - t0}


def child_main(argv) -> int:
    """``python nemotron_h.py JOB.json``: the replay of the job, its numbers
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
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=4) as writers:  # 3 GB: as the program persists
        list(writers.map(
            lambda item: np.save(out / f"{item[0]}.npy", item[1]),
            res.pop("final").items()))
    res["save_s"] = time.perf_counter() - t0
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
        "layer_norm_epsilon": cfg["layer_norm_epsilon"],
        # the pattern kept: its first ``num_hidden_layers`` characters
        "layer_kinds": list(cfg["hybrid_override_pattern"][: cfg["num_hidden_layers"]]),
        "head_dim": cfg["head_dim"],
        "attention_heads_held": cfg["num_attention_heads"],
        "kv_heads_held": cfg["num_key_value_heads"],
        "ssm_heads_held": cfg["mamba_num_heads"],
        "ssm_groups_held": cfg["n_groups"],
        "mamba_head_dim": cfg["mamba_head_dim"],
        "ssm_state_size": cfg["ssm_state_size"],
        "conv_kernel": cfg["conv_kernel"],
        # the router and the selection bias keep their published width
        "experts": share["published"]["n_routed_experts"],
        "experts_held": cfg["n_routed_experts"],
        "expert_start": share["expert_start"],
        "experts_per_token": cfg["num_experts_per_tok"],
        "expert_width": cfg["moe_intermediate_size"],
        "shared_columns_held": share["shared_expert_columns_held"],
        "routed_scaling_factor": cfg["routed_scaling_factor"],
        "vocab_rows_held": cfg["vocab_size"],
        "vocab_start": share["vocab_start"],
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


def _rel_l2(got, want):
    """Relative L2 gap over the finite entries of ``want`` (``inf`` where the
    shapes differ or nothing is finite), and the error array."""
    real = np.isfinite(want)
    if got.shape != want.shape or not real.any():
        return float("inf"), None, real
    err = np.where(real, got - np.where(real, want, 0.0), 0.0).astype(np.float64)
    ref_sq = np.where(real, want, 0.0).astype(np.float64) ** 2
    return float(np.sqrt(np.sum(err ** 2) / np.sum(ref_sq))), err, real


def _routing(cfg, rec, res, final, say, details) -> list:
    """The discrete part: choices, pairs computed, the expert path's probe."""
    from benchmark.reference import Compared

    ref = cfg["reference"]
    n_steps = cfg["engine_json"]["algorithms"][0]["params"]["stepsPerRetrain"]
    out = []
    want = np.asarray(final("choices"))
    margin = np.asarray(final("route_margin"))
    got = np.asarray(rec.get("choices", np.zeros(0)))
    k = want.shape[-1]
    real = want[..., 0] >= 0
    # only the FIRST routed layer reads a stream no flip has touched: a flip
    # there moves the token's stream, the state-space and attention layers
    # carry that to every later token of the segment, and the later routers
    # then flip at any margin
    clear = real & (np.nan_to_num(margin, nan=0.0) > ref["clear_margin"])
    clear[:, 1:] = False
    if got.shape != want.shape or not real.any():
        flips = [float("inf")] * want.shape[1]
        clear_share = float("inf")
    else:
        # a (token, choice) pair of the replay the program did not make
        same = (want[..., :, None] == got[..., None, :]).any(axis=-1)
        flips = [
            float(np.sum(~same[:, layer][real[:, layer]]) / (real[:, layer].sum() * k))
            for layer in range(want.shape[1])]
        clear_share = float(np.sum(~same[clear]) / max(clear.sum() * k, 1))
        say(f"the first step's choices against the replay's: share of (token, "
            f"choice) pairs that differ, by routed layer "
            f"{[round(f, 6) for f in flips]} over {int(real[:, 0].sum())} tokens; "
            f"{clear_share:.3g} over the {int(clear.sum())} tokens of the first "
            f"routed layer whose margin is over {ref['clear_margin']}")
    for layer, share in enumerate(flips):
        out.append(Compared(
            f"route_flip_share_layer{layer + 1}", share,
            ref["route_flip_share_first_layer_limit"] if layer == 0
            else ref["route_flip_share_limit"]))
    out.append(Compared(
        "route_flip_clear_share", clear_share, ref["route_flip_clear_share_limit"]))
    held = np.asarray(rec.get("moe_pairs_held", np.zeros((0, 0))), np.float64)
    total = np.asarray(rec.get("moe_pairs_total", np.zeros((0, 0))), np.float64)
    gaps, total_gap = [], 0.0
    m = model_group(cfg)
    for s, r in enumerate(res["records"][:n_steps]):
        w = np.asarray(r["moe_pairs_held"], np.float64)
        if s >= len(held) or held[s].shape != w.shape:
            gaps.append(float("inf"))
            total_gap = float("inf")
            continue
        # over the held experts' EVEN share of the step's pairs: four steps
        # drive a layer's held experts from that share to a few pairs, and a
        # gap over the count itself would then read the last few pairs
        even = r["moe_pairs_total"] * m["experts_held"] / m["experts"]
        gaps.append(float(np.max(np.abs(held[s] - w)) / max(even, 1.0)))
        total_gap = max(total_gap, float(np.max(np.abs(total[s] - r["moe_pairs_total"]))))
    say(f"pairs the held experts computed against the replay's, over their even "
        f"share: widest gap a step {[round(g, 6) for g in gaps]}; pairs of all "
        f"experts off by {total_gap:g}")
    out += [
        Compared("moe_pairs_total_gap", total_gap, 0.0),
        Compared("moe_pairs_held_step1_rel_gap", gaps[0],
                 ref["moe_pairs_held_step1_rel_gap_limit"]),
        Compared("moe_pairs_held_rel_gap", max(gaps), ref["moe_pairs_held_rel_gap_limit"]),
    ]
    probe_want = np.asarray(final("moe_probe"))
    gap, err, real = _rel_l2(
        np.asarray(rec.get("moe_probe", np.zeros(0)), np.float32), probe_want)
    if err is not None:
        say(f"first routed layer's experts (routed + shared) on the normed "
            f"embedding rows against the dense reference on the first step's "
            f"rows: relative L2 {gap:.4g} over {int(real.sum())} values")
    out.append(Compared("moe_probe_rel_gap", gap, ref["moe_probe_rel_gap_limit"]))
    # the experts' backward on the same exact inputs: the first row's
    got = rec.get("moe_grad_probe", {})
    grad_gaps = {
        part: _rel_l2(
            np.asarray(got.get(part, np.zeros((1, 0))), np.float32)[0],
            np.asarray(final(f"moe_grad_probe_{part}")))[0]
        for part in ("up", "down", "input")}
    say("the first routed layer's experts' gradients on the first row's normed "
        "embedding rows against the backward written out densely: relative L2 "
        + ", ".join(f"{part} {gap:.4g}" for part, gap in grad_gaps.items()))
    out.append(Compared(
        "moe_grad_probe_rel_gap", max(grad_gaps.values()),
        ref["moe_grad_probe_rel_gap_limit"]))
    if details is not None:
        details["moe_grad_probe_rel_gap"] = grad_gaps
        details["route_flip_share"] = flips
        details["moe_pairs_held_rel_gap"] = gaps
    return out


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
        # step 1 runs on the seeded weights; a later step on weights that
        # differ from the replay's by every route the roundings flipped
        out.append(Compared(
            f"loss_step{s + 1}_rel_gap", abs(got - want) / abs(want),
            ref["loss_step1_rel_gap_limit" if s == 0
                else "loss_later_steps_rel_gap_limit"]))
    out += _routing(cfg, rec, res, final, say, details)
    ssd_gap, err, real = _rel_l2(
        np.asarray(rec.get("ssd_probe", np.zeros(0)), np.float32),
        np.asarray(final("ssd_probe")))
    if err is not None:
        say(f"first layer's state space against the recurrence on the first "
            f"step's rows: relative L2 {ssd_gap:.4g} over {int(real.sum())} values")
    out.append(Compared("ssd_probe_rel_gap", ssd_gap, ref["ssd_probe_rel_gap_limit"]))
    want = res["records"][0]["tensor_grad_norm"]
    # the selection bias has no gradient on either side: 0 against 0
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
    groups = {
        "experts": np.array([n in ROUTED for n in leaf]),
        "shared": np.array([n in SHARED for n in leaf]),
        "router": np.array([n == "router" for n in leaf]),
        "ssm": np.array([n.startswith("ssm_") for n in leaf]),
        "attention": np.array([n in QKVO for n in leaf]),
    }
    rms = lambda x: float(np.sqrt(np.mean(np.square(x)))) if x.size else 0.0  # noqa: E731
    say(f"gradient probes against the replay, in units of each gradient's "
        f"norm: step 1 rms {rms(probe[0]):.4g} ("
        + ", ".join(f"{g} {rms(probe[0][at]):.4g}" for g, at in groups.items())
        + f"), widest {probe[0].max():.4g} ({list(want)[int(probe[0].argmax())]}); "
        f"later steps rms {[round(rms(p), 5) for p in probe[1:]]}")
    out.append(Compared(
        "grad_probe_gap_rms", rms(probe[0]), ref["grad_probe_gap_rms_limit"]))
    out += [
        Compared(f"grad_probe_gap_{g}_rms", rms(probe[0][at]),
                 ref[f"grad_probe_gap_{g}_rms_limit"])
        for g, at in groups.items()]
    out.append(Compared(
        "grad_probe_gap_later_steps_rms", rms(probe[1:]),
        ref["grad_probe_gap_later_steps_rms_limit"]))
    rel, row = {}, {}
    # one buffer for every tensor's gap: a fresh 320 MB array a tensor is
    # mostly page faults on a host without huge pages
    room = np.empty(max(np.size(v) for v in model["params"].values()), np.float32)
    for name, (norm, row_norm) in res["update"].items():
        got = np.asarray(model["params"][name], np.float32)
        if norm == 0.0:  # the selection bias: it may not have moved
            rel[name] = row[name] = float(np.abs(got - final(name)).max())
            continue
        gap = _as_matrix(np.subtract(
            got, final(name), out=room[: got.size].reshape(got.shape)))
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

    def widest(names):
        return max((v for k, v in rel.items() if k.split(".")[-1] in names),
                   default=0.0)

    return out + [
        Compared("update_rel_l2_max", rel[rel_worst], ref["update_rel_l2_max_limit"]),
        Compared("update_rel_l2_median", float(np.median(list(rel.values()))),
                 ref["update_rel_l2_median_limit"]),
        Compared("update_rel_l2_experts_max", widest(ROUTED + SHARED),
                 ref["update_rel_l2_experts_max_limit"]),
        Compared("update_rel_l2_decay_max", widest(DECAY),
                 ref["update_rel_l2_decay_max_limit"]),
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
                f"{res.get('save_s', 0.0):.1f} s the final weights' write, "
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
