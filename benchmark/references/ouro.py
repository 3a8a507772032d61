"""The Ouro block's plain reference: one stack of sandwich-norm layers run
``total_ut_steps`` times with the SAME tensors, an exit (the one head, the one
gate) after every pass; next-item training with AdamW on the expected
cross-entropy under the exit distribution, entropy-regularised.

Straight ``jax.numpy`` in float32 with ``jax.default_matmul_precision
("highest")``; attention with a full masked score matrix, ONE SEGMENT AT A TIME
(a segment is one entity's history, so positions start at 0 by themselves): no
packing, no kernels, no cache.  Gradients by ``jax.grad`` of the scalar loss,
AdamW written out.  The passes are one ``lax.scan`` over the same tensors
(the tie, as a loop).  Recomputation (``jax.checkpoint`` a layer) and the
loss's blocks of positions change no number, only what is held at once.  Nothing of the
program is imported; what no model's mathematics enters (how histories are
grouped into optimiser steps, the sampled rows, the vocabulary's order) is
shared with ``references/olmo_hybrid.py``.

The published description is the Ouro release's ``config.json``
(huggingface.co/ByteDance/Ouro-2.6B) for every size, and for the form its
``modeling_ouro.py`` and the paper "Scaling Latent Reasoning via Looped
Language Models" (arXiv:2510.25741) as the configuration file's ``assumed``
lists them (there is no network here).  For a segment of T tokens, ``m`` the
configuration's ``model`` group (``model_group``), R = ``total_ut_steps``:

    x^(0)   = E[tokens]
    for t = 1..R:                                  the SAME layers every pass
        y = x^(t-1)
        for l = 1..L:
            y = y + N2_l(Attn_l(N1_l(y)))          sandwich: norm in, norm out
            y = y + N4_l(MLP_l(N3_l(y)))
        x^(t) = Nf(y)                              one final norm, every pass;
                                                   its OUTPUT feeds pass t + 1
        z^(t) = x^(t) Wh^T                         the one head
        lam_t = sigmoid(x^(t) . w_g + b_g)         the one gate
    Attn:  q, k, v = h Wq, h Wk, h Wv a head; rotary positions 0, 1, .. over
           the whole head (theta); causal softmax, scale 1 / sqrt(head_dim);
           output Wo; no q / k norm
    MLP:   Wd(silu(h Wg) * (h Wu))
    exits: p_t = lam_t prod_{j<t} (1 - lam_j)  (t < R),  p_R = prod_{j<R} (1 - lam_j)
    loss, a position:  sum_t p_t CE(z^(t), next item) - beta H(p),
                       H(p) = - sum_t p_t log p_t
    step loss = the sum over real non-final positions / their count

``check_retrain`` replays the configured optimiser steps from the seeded
initial weights in ONE child process on the chip (the benchmark's worker has
released it by then) and holds the persisted model and its record to the
replay.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

_REPO = Path(__file__).resolve().parents[2]
if str(_REPO) not in sys.path:  # run as a script: the replay's child
    sys.path.insert(0, str(_REPO))

from benchmark.references.olmo_hybrid import (  # noqa: E402
    PROBE_SEED, histories, rmsnorm, rows_of, sampled_rows, silu, steps_of,
    vocabulary_ids)

MLP = ("gate", "up", "down")
ATTENTION = ("q", "k", "v", "o")
#: what only the exits' part of the loss reaches: the gate's two tensors
EXIT = ("exit_gate", "exit_gate_bias")
#: positions whose logits exist at once in ``token_losses``
LOSS_BLOCK = 512


# ---------------------------------------------------------------------------
# shapes and the seeded initial weights (the rule of the configuration file)


def tensor_shapes(m: dict) -> dict:
    """Flat name -> shape, in the order the initialisation counts."""
    D, hd = m["hidden_size"], m["head_dim"]
    A, KV, F, V = m["heads"], m["kv_heads"], m["mlp_columns"], m["vocab_rows"]
    out = {"embed": (V, D)}
    for i in range(m["num_layers"]):
        p = f"layer{i}."
        out.update({
            p + "input_norm": (D,),
            p + "q": (D, A * hd), p + "k": (D, KV * hd), p + "v": (D, KV * hd),
            p + "o": (A * hd, D), p + "attn_out_norm": (D,),
            p + "pre_ff_norm": (D,),
            p + "gate": (D, F), p + "up": (D, F), p + "down": (F, D),
            p + "mlp_out_norm": (D,),
        })
    out["final_norm"] = (D,)
    out["head"] = (V, D)
    if m["passes"] > 1:
        out["exit_gate"] = (D,)
        out["exit_gate_bias"] = ()
    return out


def initial_weights(m: dict, seed: int) -> dict:
    """Tensor number n draws from ``fold_in(PRNGKey(seed), n)`` at its shape
    (the configuration's ``initialisation``): norm weights 1, the gate's bias
    0, everything else normal(0, 0.02)."""
    import jax
    import jax.numpy as jnp

    base = jax.random.PRNGKey(seed)
    out = {}
    for n, (name, shape) in enumerate(tensor_shapes(m).items()):
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name == "exit_gate_bias":
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = 0.02 * jax.random.normal(
                jax.random.fold_in(base, n), shape, jnp.float32)
    return out


# ---------------------------------------------------------------------------
# the layers, for ONE segment: x [T, D]; ``valid`` [T] marks real tokens
# (a segment is padded at its END to a length the replay compiles once;
# nothing after a token can reach it)


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
    head), query head n on KV head ``n // (heads / kv heads)`` (the published
    model has one a query head)."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    d = m["head_dim"]
    q = rope((h @ p["q"]).reshape(T, -1, d), m["rope_theta"])
    k = rope((h @ p["k"]).reshape(T, -1, d), m["rope_theta"])
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
    return o.transpose(1, 0, 2).reshape(T, A * d) @ p["o"]


def mlp(p, x):
    return (silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def embed(m, table, tokens):
    import jax.numpy as jnp

    idx = tokens - m["vocab_start"]
    held = (idx >= 0) & (idx < table.shape[0])
    return jnp.where(held[:, None], table[jnp.where(held, idx, 0)], 0.0)


def layer_tensors(w: dict, i: int, t: int = 0) -> dict:
    """Layer i's tensors: the same for every pass t (the model's tie; the
    tier-1 tie test hands ``exit_states`` an untied rule)."""
    p = f"layer{i}."
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


def block(m, p, y):
    """One sandwich layer: a norm before and after each sublayer, inside the
    residual."""
    eps = m["rms_norm_eps"]
    a = attention(m, p, rmsnorm(y, p["input_norm"], eps))
    y = y + rmsnorm(a, p["attn_out_norm"], eps)
    f = mlp(p, rmsnorm(y, p["pre_ff_norm"], eps))
    return y + rmsnorm(f, p["mlp_out_norm"], eps)


def one_pass(m, w, x, t, tensors_of=layer_tensors):
    """The layer list once, then the final norm."""
    import jax

    for i in range(m["num_layers"]):
        x = jax.checkpoint(functools.partial(block, m))(tensors_of(w, i, t), x)
    return rmsnorm(x, w["final_norm"], m["rms_norm_eps"])


def exit_states(m, w, tokens, tensors_of=layer_tensors):
    """x^(1) .. x^(R) [R, T, D]: the state after each pass's final norm.  With
    the model's tie the passes are ONE loop over the same tensors
    (``lax.scan``: the replay's programs hold the layer list once, a fourth
    of the text to compile); an untied rule gets them written out.
    Recomputation (each layer) changes no number, only what is held between
    the forward and the backward pass."""
    import jax
    import jax.numpy as jnp

    x = embed(m, w["embed"], tokens)
    if tensors_of is layer_tensors:
        def step(x, _):
            x = one_pass(m, w, x, 0)
            return x, x

        return jax.lax.scan(step, x, None, length=m["passes"])[1]
    states = []
    for t in range(m["passes"]):
        x = one_pass(m, w, x, t, tensors_of)
        states.append(x)
    return jnp.stack(states)


def exit_log_probs(states, gate, bias):
    """``log p`` [R, T] of the exit distribution, from the gates' logarithms
    (``log sigmoid(z) = -log(1 + exp(-z))``), so that a gate that saturates
    leaves every term finite."""
    import jax.numpy as jnp

    z = states @ gate + bias
    log_lam, log_stay = -jnp.logaddexp(0.0, -z), -jnp.logaddexp(0.0, z)
    out, reach = [], jnp.zeros_like(z[0])  # log S_t, S_1 = 1
    for t in range(z.shape[0] - 1):
        out.append(log_lam[t] + reach)
        reach = reach + log_stay[t]
    return jnp.stack(out + [reach])


def token_losses(h, head, target):
    """The cross-entropy of every position of h [T, D] against its target,
    the logits of ``LOSS_BLOCK`` positions at a time -> [T]."""
    import jax
    import jax.numpy as jnp

    T, D = h.shape
    blk = min(LOSS_BLOCK, T)
    pad = -T % blk  # rows of zeros whose losses are cut off again
    h = jnp.pad(h, ((0, pad), (0, 0)))
    target = jnp.pad(target, (0, pad))

    @jax.checkpoint
    def some(x):
        hb, tb = x
        logits = hb @ head.T
        top = jnp.max(logits, axis=-1)
        lse = top + jnp.log(jnp.sum(jnp.exp(logits - top[:, None]), axis=-1))
        return lse - jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]

    return jax.lax.map(
        some, (h.reshape(-1, blk, D), target.reshape(-1, blk))).reshape(-1)[:T]


def segment_losses(m, w, tokens, valid, tensors_of=layer_tensors) -> dict:
    """Sums over the segment's real, non-final positions: ``loss`` (the
    objective), ``by_exit`` [R] (each exit's cross-entropy), ``mass`` [R]
    (each exit's probability), ``entropy``; and per position ``p`` [T, R] and
    ``carried`` [T, R - 1], the mean square of the state passes 2 .. R read."""
    import jax.numpy as jnp

    states = exit_states(m, w, tokens, tensors_of)
    R = states.shape[0]
    target = jnp.concatenate([tokens[1:], tokens[:1]]) - m["vocab_start"]
    weight = (valid & jnp.concatenate([valid[1:], valid[:1] & False])).astype(
        jnp.float32)
    nll = jnp.stack([token_losses(states[t], w["head"], target) for t in range(R)])
    if R > 1:
        logp = exit_log_probs(states, w["exit_gate"], w["exit_gate_bias"])
    else:
        logp = jnp.zeros_like(nll)
    p = jnp.exp(logp)
    entropy = -jnp.sum(p * logp, axis=0)
    objective = jnp.sum(p * nll, axis=0) - m["exit_beta"] * entropy
    return {
        "loss": jnp.sum(weight * objective),
        "by_exit": jnp.sum(weight * nll, axis=1),
        "mass": jnp.sum(weight * p, axis=1),
        "entropy": jnp.sum(weight * entropy),
        "p": p.T,
        "carried": jnp.mean(states[:-1] ** 2, axis=-1).T,
    }


def no_decay(name: str) -> bool:
    return "norm" in name or name == "exit_gate_bias"


def adamw_update(opt, w, mom, var, grad, t):
    """One AdamW step, written out; ``t`` counts from 1."""
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


def grad_probe(n: int, g):
    """``r^T g`` for a vector (or a bias of one element), ``r_rows^T G r_cols``
    for a matrix, the r's standard normal from ``fold_in(PRNGKey(PROBE_SEED),
    n)``, split in two for a matrix: one seeded linear functional of tensor
    number n's gradient."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.PRNGKey(PROBE_SEED), n)
    if g.ndim <= 1:
        return jnp.sum(g * jax.random.normal(key, g.shape, jnp.float32))
    kr, kc = jax.random.split(key)
    rows = jax.random.normal(kr, (g.shape[0],), jnp.float32)
    cols = jax.random.normal(kc, (g.shape[1],), jnp.float32)
    return jnp.sum(rows * (g @ cols))


# ---------------------------------------------------------------------------
# the replay (needs the device: the child process, or a chip script)


def buckets_for(max_len: int) -> tuple:
    """Padded segment lengths the replay compiles (a segment takes the
    smallest that holds it) and how many segments of each go through one
    call: five shapes, ``max_len`` over 32, 12, 6, 3 and 1.  Five and not
    the other references' three: this block does four passes' work a token,
    the padding is most of what the replay computes, and a run waits for it
    (with three shapes 2.4 padded positions a real one over the cell's
    histories, with these 1.6).  Every call holds a third of ``max_len``
    positions or more: the chip's compiler makes a program for fewer than
    ~2,000 positions five times as large (62-72 MB an entry of the compile
    cache against 11-13), and the machines cap that cache at 192 MiB: five
    such programs push one another and the row program out of it, run after
    run."""
    return tuple(
        (max(-(-max_len // d), 2), n)
        for d, n in ((32, 11), (12, 4), (6, 2), (3, 1), (1, 1)))


def replay_programs(m, opt) -> tuple:
    """The replay's three jitted functions: ``accumulate`` (a call's segments'
    sums, and their gradient added to the step's), ``norms`` (the step's
    gradient norms and probes) and ``update`` (AdamW)."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0,))
    def accumulate(gsum, w, tokens, valid):
        def total(w):
            parts = jax.vmap(lambda t, v: segment_losses(m, w, t, v))(tokens, valid)
            return jnp.sum(parts["loss"]), parts

        (_, parts), g = jax.value_and_grad(total, has_aux=True)(w)
        return parts, jax.tree.map(jnp.add, gsum, g)

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


def compiled_beside(m, opt, buckets) -> dict:
    """Every program of the replay compiled at its shapes, each on a thread
    of its own, the first needed first -> name -> future of the compiled
    program.  The compiler works outside Python's lock, so the shapes
    compile beside one another and beside the device's work on the shapes
    already there: a run waits for the slowest, not for their sum."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp

    accumulate, norms, update = replay_programs(m, opt)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    w = {k: f32(s) for k, s in tensor_shapes(m).items()}

    def compile_(fn, *specs):
        with jax.default_matmul_precision("highest"):  # a thread's own setting
            return fn.lower(*specs).compile()

    pool = ThreadPoolExecutor(max_workers=len(buckets) + 2)
    out = {
        (length, batch): pool.submit(
            compile_, accumulate, w, w,
            jax.ShapeDtypeStruct((batch, length), jnp.int32),
            jax.ShapeDtypeStruct((batch, length), jnp.bool_))
        for length, batch in buckets
    }
    out["norms"] = pool.submit(compile_, norms, w, f32(()))
    out["update"] = pool.submit(compile_, update, w, w, w, w, f32(()), f32(()))
    pool.shutdown(wait=False)
    return out


def replay(m, opt, seed, hist, steps, n_steps, say=print):
    """``n_steps`` optimiser steps from the seeded initial weights -> (final
    weights, per-step records, {history: (p [n, R], carried [n, R - 1])} for
    the FIRST step's histories: those are made from the seeded weights)."""
    import jax
    import jax.numpy as jnp

    buckets = buckets_for(max(len(h) for h in hist))
    programs = compiled_beside(m, opt, buckets)
    first: dict = {}
    with jax.default_matmul_precision("highest"):
        w = initial_weights(m, seed)
        # the moments wait on the HOST between updates: the longest history's
        # backward pass needs the device memory they would hold
        mom = var = {k: np.zeros(v.shape, np.float32) for k, v in w.items()}
        records = []
        for s in range(n_steps):
            t0 = time.perf_counter()
            gsum = jax.tree.map(jnp.zeros_like, w)
            sums = {k: 0.0 for k in ("loss", "by_exit", "mass", "entropy")}
            count = 0
            members = sorted(steps[s], key=lambda j: len(hist[j]))
            at = 0
            for length, batch in buckets:
                group = []
                while at < len(members) and len(hist[members[at]]) <= length:
                    group.append(members[at])
                    at += 1
                for c0 in range(0, len(group), batch):
                    some = group[c0 : c0 + batch]
                    tok = np.zeros((batch, length), np.int32)
                    val = np.zeros((batch, length), bool)
                    for r, j in enumerate(some):
                        tok[r, : len(hist[j])] = hist[j]
                        val[r, : len(hist[j])] = True
                        count += len(hist[j]) - 1
                    parts, gsum = programs[length, batch].result()(
                        gsum, w, jnp.asarray(tok), jnp.asarray(val))
                    for k in sums:  # float64 on the host, over the segments
                        sums[k] = sums[k] + np.asarray(parts[k], np.float64).sum(0)
                    if s == 0:
                        p, carried = np.asarray(parts["p"]), np.asarray(parts["carried"])
                        for r, j in enumerate(some):
                            first[j] = (p[r, : len(hist[j])], carried[r, : len(hist[j])])
            scale = 1.0 / max(count, 1)
            gnorm, tnorms, probes = programs["norms"].result()(gsum, np.float32(scale))
            w, mom, var = programs["update"].result()(
                w, jax.tree.map(jnp.asarray, mom), jax.tree.map(jnp.asarray, var),
                gsum, np.float32(scale), np.float32(s + 1))
            mom, var = jax.tree.map(np.asarray, (mom, var))
            records.append({
                "loss": float(sums["loss"]) * scale, "tokens": count,
                "grad_norm": float(gnorm),
                "tensor_grad_norm": {k: float(v) for k, v in tnorms.items()},
                "tensor_grad_probe": {k: float(v) for k, v in probes.items()},
                "loss_by_exit": (sums["by_exit"] * scale).tolist(),
                "exit_mass": (sums["mass"] * scale).tolist(),
                "exit_entropy": float(sums["entropy"]) * scale,
            })
            say(f"replay step {s + 1}: loss {records[-1]['loss']:.6f} over {count} "
                f"positions (by exit {np.round(records[-1]['loss_by_exit'], 5).tolist()}, "
                f"mass {np.round(records[-1]['exit_mass'], 5).tolist()}), gradient "
                f"norm {float(gnorm):.6g}, {time.perf_counter() - t0:.1f} s")
    return w, records, first


def first_step_probes(m, hist, rows, row_len, first: dict) -> tuple:
    """The first step's per-position records laid where the packing puts each
    history (row, offset) -> (exit_probe [rows, row_len, R], carry_probe
    [rows, row_len, R - 1], both NaN on padding; the next item of every
    position that has one [rows, row_len], -1 elsewhere)."""
    R = m["passes"]
    exits = np.full((len(rows), row_len, R), np.nan, np.float32)
    carry = np.full((len(rows), row_len, R - 1), np.nan, np.float32)
    targets = np.full((len(rows), row_len), -1, np.int32)
    for r, row in enumerate(rows):
        at = 0
        for j in row:
            n = len(hist[j])
            exits[r, at : at + n], carry[r, at : at + n] = first[j]
            targets[r, at : at + n - 1] = hist[j][1:] - m["vocab_start"]
            at += n
    return exits, carry, targets


def head_probe_positions(row_len: int, n: int) -> np.ndarray:
    """The n evenly spaced positions of a row whose exit states the program's
    record keeps (``head_probe_state``)."""
    return np.arange(n) * (row_len // n)


def head_again(states, head, targets) -> np.ndarray:
    """The cross-entropies of recorded exit states [.., R, D] against
    ``targets`` [..] (-1: none, NaN comes out) through ``head`` [V, D] in the
    precision the configuration STATES for that product: both inputs rounded
    to bfloat16, products and sums exact beyond float32 (float64 here).
    numpy alone: the harness's process holds no device."""
    import ml_dtypes

    def rounded(x):
        return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)

    states = np.asarray(states)
    flat = rounded(states).reshape(-1, states.shape[-1])
    logits = flat @ rounded(head).T
    top = logits.max(axis=-1)
    lse = top + np.log(np.exp(logits - top[:, None]).sum(axis=-1))
    of = np.repeat(np.asarray(targets).reshape(-1), states.shape[-2])
    nll = lse - logits[np.arange(len(of)), np.where(of >= 0, of, 0)]
    return np.where(of >= 0, nll, np.nan).reshape(states.shape[:-1])


def update_summary(m, seed, final: dict, n_rows: int) -> dict:
    """Per tensor of the replay: the L2 norm of its update (final - initial)
    and the largest update-row norm over the sampled rows."""
    import jax.numpy as jnp

    init = initial_weights(m, seed)
    out = {}
    for name, w in final.items():
        d = w - init[name]
        rows = d if d.ndim <= 1 else jnp.linalg.norm(
            d[sampled_rows(name, d.shape[0], n_rows)], axis=-1)
        out[name] = [float(jnp.linalg.norm(d)), float(jnp.max(jnp.abs(rows)))]
    return out


def replay_job(job: dict, say=print) -> dict:
    """The whole replay of one job description -> records, the update's
    summary, and under ``final`` the final weights as float32 numpy arrays
    with the first step's ``exit_probe``, ``carry_probe``, every position's
    next item (``head_probe_targets``) and the seeded head
    (``initial_head``) beside them."""
    m, opt = job["model"], job["optimizer"]
    data = np.load(job["data"])
    hist = [
        h.astype(np.int32)
        for h in histories(data["user_idx"], data["item_ids"], job["max_len"])
    ]
    rows = rows_of([len(h) for h in hist], job["row_len"])
    t0 = time.perf_counter()
    w, records, first = replay(
        m, opt, job["seed"], hist, steps_of(rows, job["rows_per_step"]),
        job["steps"], say)
    summary = update_summary(m, job["seed"], w, job["rows_checked"])
    final = {k: np.asarray(v) for k, v in w.items()}
    final["exit_probe"], final["carry_probe"], final["head_probe_targets"] = (
        first_step_probes(m, hist, rows[: job["rows_per_step"]], job["row_len"], first))
    # the head the first step's rows went through: the seeded one
    final["initial_head"] = np.asarray(initial_weights(m, job["seed"])["head"])
    return {"records": records, "update": summary, "final": final,
            "replay_s": time.perf_counter() - t0}


def child_main(argv) -> int:
    """``python ouro.py JOB.json``: the replay of the job, its numbers as
    ``out.json`` and its final weights as ``<name>.npy`` beside it."""
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
    """The configuration's published sizes under the names this file's
    functions read (nothing is a share: one chip holds every layer whole)."""
    algo = cfg["engine_json"]["algorithms"][0]["params"]
    return {
        "hidden_size": cfg["hidden_size"],
        "num_layers": cfg["num_hidden_layers"],
        "head_dim": cfg["head_dim"],
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "mlp_columns": cfg["intermediate_size"],
        "vocab_rows": cfg["vocab_size"],
        "vocab_start": 0,
        "rope_theta": float(cfg["rope_theta"]),
        "rms_norm_eps": cfg["rms_norm_eps"],
        "passes": cfg["total_ut_steps"],
        "exit_beta": algo["exitBeta"],
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


def _rel_l2(got, want) -> float:
    """Relative L2 gap over the values ``want`` holds (NaN marks padding);
    infinite where the shapes differ."""
    got, want = np.asarray(got, np.float32), np.asarray(want)
    real = np.isfinite(want)
    if got.shape != want.shape or not real.any():
        return float("inf")
    err = np.where(real, got - np.where(real, want, 0.0), 0.0).astype(np.float64)
    return float(np.sqrt(
        np.sum(err ** 2) / np.sum(np.where(real, want, 0.0).astype(np.float64) ** 2)))


def compare_model(cfg: dict, model: dict, res: dict, final, say=print,
                  details: dict | None = None) -> list:
    """The persisted model and its training record against a replay's
    results.  ``final(name)`` gives the replay's final tensor; ``details``,
    where given, receives the per-tensor numbers behind the comparisons."""
    from benchmark.reference import Compared

    ref = cfg["reference"]
    algo = cfg["engine_json"]["algorithms"][0]["params"]
    n_steps, R = algo["stepsPerRetrain"], cfg["total_ut_steps"]
    rec = model["training_record"]
    done = len(rec["loss"])
    applications = (
        n_steps * algo["rowsPerStep"] * R * cfg["num_hidden_layers"])
    out = [
        Compared("optimizer_steps", float(done), float(n_steps), "min"),
        Compared("optimizer_steps_over", float(max(done - n_steps, 0)), 0.0),
        Compared(
            "positions_trained_gap",
            abs(float(np.sum(rec["tokens"]))
                - sum(r["tokens"] for r in res["records"][:n_steps])), 0.0),
        Compared(
            "loop_layer_applications_gap",
            abs(float(np.sum(rec.get("loop_layer_applications", 0))) - applications),
            0.0),
    ]
    for s in range(n_steps):
        want = res["records"][s]["loss"]
        got = float(rec["loss"][s]) if s < done else float("nan")
        out.append(Compared(
            f"loss_step{s + 1}_rel_gap", abs(got - want) / abs(want),
            ref["loss_rel_gap_limit"]))
    # every exit's own loss and mass, every step: the widest gap, an exit's
    # loss in units of itself, its mass in units of an even share
    by_exit, mass, entropy = [], [], []
    got_l = np.asarray(rec.get("loss_by_exit", np.zeros((0, 0))))
    got_m = np.asarray(rec.get("exit_mass", np.zeros((0, 0))))
    for s, r in enumerate(res["records"][:n_steps]):
        if s >= done or got_l.shape[1:] != (R,) or got_m.shape[1:] != (R,):
            by_exit.append(float("inf"))
            mass.append(float("inf"))
            entropy.append(float("inf"))
            continue
        want_l, want_m = np.asarray(r["loss_by_exit"]), np.asarray(r["exit_mass"])
        by_exit.append(float(np.max(np.abs(got_l[s] - want_l) / np.abs(want_l))))
        mass.append(float(np.max(np.abs(got_m[s] - want_m)) * R))
        entropy.append(abs(float(rec["exit_entropy"][s]) - r["exit_entropy"])
                       / abs(r["exit_entropy"]))
    say(f"the exits against the replay, by step: loss_by_exit widest relative "
        f"gap {np.round(by_exit, 8).tolist()}, exit_mass widest gap x R "
        f"{np.round(mass, 8).tolist()}, entropy {np.round(entropy, 8).tolist()}")
    out += [
        Compared("loss_by_exit_rel_gap_max", max(by_exit),
                 ref["loss_by_exit_rel_gap_limit"]),
        Compared("exit_mass_gap_max", max(mass), ref["exit_mass_gap_limit"]),
        Compared("exit_entropy_rel_gap_max", max(entropy),
                 ref["exit_entropy_rel_gap_limit"]),
    ]
    want = res["records"][0]["tensor_grad_norm"]
    gaps = {
        k: abs(float(rec["tensor_grad_norm"][k][0]) - want[k]) / max(want[k], 1e-30)
        if k in rec["tensor_grad_norm"] else float("inf")
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
            if s < done and k in rec["tensor_grad_probe"] else np.nan
            for k in want
        ]
        for s, r in enumerate(res["records"][:n_steps])
    ])
    leaf = [k.split(".")[-1] for k in want]
    in_mlp = np.array([n in MLP for n in leaf])
    in_attn = np.array([n in ATTENTION for n in leaf])
    in_exit = np.array([n in EXIT for n in leaf])
    rms = lambda x: float(np.sqrt(np.mean(np.square(x))))  # noqa: E731
    say(f"gradient probes against the replay, in units of each gradient's "
        f"norm: step 1 rms {rms(probe[0]):.4g} (MLP {rms(probe[0][in_mlp]):.4g}, "
        f"attention {rms(probe[0][in_attn]):.4g}, the gate "
        f"{rms(probe[0][in_exit]):.4g}), widest {np.nanmax(probe[0]):.4g} "
        f"({list(want)[int(np.nanargmax(probe[0]))]}); later steps rms "
        f"{[round(rms(p), 5) for p in probe[1:]]}")
    out += [
        Compared("grad_probe_gap_rms", rms(probe[0]), ref["grad_probe_gap_rms_limit"]),
        Compared("grad_probe_gap_mlp_rms", rms(probe[0][in_mlp]),
                 ref["grad_probe_gap_mlp_rms_limit"]),
        Compared("grad_probe_gap_attention_rms", rms(probe[0][in_attn]),
                 ref["grad_probe_gap_attention_rms_limit"]),
        Compared("grad_probe_gap_exit_rms", rms(probe[:, in_exit]),
                 ref["grad_probe_gap_exit_rms_limit"]),
        Compared("grad_probe_gap_later_steps_rms", rms(probe[1:]),
                 ref["grad_probe_gap_later_steps_rms_limit"]),
    ]
    # the first step's rows, a position at a time: the exit distribution, and
    # the mean square of the state each later pass read
    exit_gap = _rel_l2(rec.get("exit_probe", np.zeros(0)), final("exit_probe"))
    carry_gap = _rel_l2(rec.get("carry_probe", np.zeros(0)), final("carry_probe"))
    # the head's product made again from the exit states the program kept,
    # in the precision the configuration states for it: exact on both sides
    # but for float32's own sums, whatever the trunk rounded on the way
    kept = np.asarray(rec.get("head_probe_state", np.zeros((0, 0, 0, 0))))
    head_gap = float("inf")
    if kept.ndim == 4 and kept.shape[1]:
        at = head_probe_positions(final("head_probe_targets").shape[1], kept.shape[1])
        head_gap = _rel_l2(rec.get("head_probe", np.zeros(0)), head_again(
            kept, final("initial_head"),
            np.asarray(final("head_probe_targets"))[: len(kept), at]))
    say(f"the first step's rows against the replay: exit distribution relative "
        f"L2 {exit_gap:.4g}, carried state's mean square {carry_gap:.4g}; the "
        f"head's product made again from the kept exit states {head_gap:.4g}")
    out += [
        Compared("exit_probe_rel_gap", exit_gap, ref["exit_probe_rel_gap_limit"]),
        Compared("carry_probe_rel_gap", carry_gap, ref["carry_probe_rel_gap_limit"]),
        Compared("head_probe_rel_gap", head_gap, ref["head_probe_rel_gap_limit"]),
    ]
    rel, row = {}, {}
    for name, (norm, row_norm) in res["update"].items():
        if name not in model["params"]:
            rel[name] = row[name] = float("inf")
            continue
        gap = np.asarray(model["params"][name], np.float32) - final(name)
        rel[name] = float(np.linalg.norm(gap)) / max(norm, 1e-30)
        rows = gap if gap.ndim <= 1 else np.linalg.norm(
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
    exit_gaps = [v for k, v in rel.items() if k.split(".")[-1] in EXIT]
    return out + [
        Compared("update_rel_l2_max", rel[rel_worst], ref["update_rel_l2_max_limit"]),
        Compared("update_rel_l2_median", float(np.median(list(rel.values()))),
                 ref["update_rel_l2_median_limit"]),
        Compared("update_rel_l2_mlp_max", max(mlp_gaps),
                 ref["update_rel_l2_mlp_max_limit"]),
        Compared("update_rel_l2_exit_max", max(exit_gaps),
                 ref["update_rel_l2_exit_max_limit"]),
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
