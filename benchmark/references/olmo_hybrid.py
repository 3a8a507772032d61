"""The Olmo-Hybrid block's plain reference: gated delta-rule linear attention
(arXiv:2412.06464) 3:1 with full attention, next-item training with AdamW.

Straight ``jax.numpy`` in float32 with ``jax.default_matmul_precision
("highest")``; the delta rule token by token exactly as the configuration
writes it, attention with a full masked score matrix, ONE SEGMENT AT A TIME (a
segment is one entity's history): no packing, no chunks, no cache, no kernels.
Gradients by ``jax.grad``, AdamW written out.  Nothing of the program is
imported (the persisted model arrives through the harness's ``load_models``).

Per token t of a segment, hidden x in R^D, H heads held, d_k / d_v key / value
sizes (``cfg`` is the configuration's ``model`` group):

    q, k, v = W_q x, W_k x, W_v x, each through a causal depthwise
              convolution over time (zero history at the start) and SiLU;
              q, k L2-normalised per head; q scaled by d_k^-1/2
    beta_t  = 2 sigmoid(W_b x_t)     g_t = -exp(A_log) softplus(W_a x_t + dt_bias)
    S_t     = exp(g_t) S_(t-1) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T,  S_0 = 0
    o_t     = S_t q_t
    y_t     = W_o [ RMSNorm_head(o_t) * SiLU(W_g x_t) ]

The share (model-configs guide, section 4): the tensors are the slices one of
``chips`` chips holds, every function computes what those slices give, an item
id outside the held vocabulary rows embeds to zero, logits and loss run over
the held rows.

``check_retrain`` replays the configured optimiser steps from the seeded
initial weights in ONE child process on the chip (the benchmark's worker has
released it by then) and holds the persisted model to the replay.  One number
is not the replay's: ``delta_rule_probe`` runs the FIRST layer's delta rule
token by token on inputs projected in the configuration's stated bf16
products, so that the program's record of the same quantity differs by the
rule alone (chunks, kernel, the precision of the carried state).
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

LINEAR = "linear_attention"
FULL = "full_attention"


# ---------------------------------------------------------------------------
# shapes and the seeded initial weights (the rule of the configuration file)


def tensor_shapes(m: dict) -> dict:
    """Flat name -> held shape, in the order the initialisation counts."""
    D, H, dk, dv = m["hidden_size"], m["linear_heads_held"], m["linear_key_head_dim"], m["linear_value_head_dim"]
    A, hd = m["attention_heads_held"], m["head_dim"]
    F, V, K = m["mlp_columns_held"], m["vocab_rows_held"], m["linear_conv_kernel_dim"]
    out = {"embed": (V, D)}
    for i, kind in enumerate(m["layer_types"]):
        p = f"layer{i}."
        if kind == LINEAR:
            out.update({
                p + "q": (D, H * dk), p + "k": (D, H * dk), p + "v": (D, H * dv),
                p + "g": (D, H * dv), p + "a": (D, H), p + "b": (D, H),
                p + "conv_q": (K, H * dk), p + "conv_k": (K, H * dk),
                p + "conv_v": (K, H * dv), p + "a_log": (H,), p + "dt_bias": (H,),
                p + "o_norm": (dv,), p + "o": (H * dv, D),
            })
        elif kind == FULL:
            out.update({
                p + "q": (D, A * hd), p + "k": (D, A * hd), p + "v": (D, A * hd),
                p + "q_norm": (hd,), p + "k_norm": (hd,), p + "o": (A * hd, D),
            })
        else:
            raise ValueError(kind)
        out.update({
            p + "mixer_norm": (D,), p + "gate": (D, F), p + "up": (D, F),
            p + "down": (F, D), p + "mlp_norm": (D,),
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
        if leaf.endswith("norm"):
            w = jnp.ones(shape, jnp.float32)
        elif leaf.startswith("conv"):
            bound = 1.0 / math.sqrt(m["linear_conv_kernel_dim"])
            w = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        elif leaf == "a_log":
            w = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-3, 16.0))
        elif leaf == "dt_bias":
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


def rmsnorm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def causal_conv(x, w):
    """y_t = sum_j w[j] x[t - (K - 1) + j], zero before the segment."""
    import jax.numpy as jnp

    K = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], axis=0)
    return sum(xp[j : j + x.shape[0]] * w[j] for j in range(K))


#: tokens between the states the backward pass of ``delta_rule`` keeps
STATE_EVERY = 64


def delta_rule(q, k, v, g, beta):
    """Token by token.  q, k: [T, H, dk]; v: [T, H, dv]; g, beta: [T, H].
    For the backward pass the state is kept every ``STATE_EVERY`` tokens and
    the tokens between are run again (8192 states of one segment would be
    9 GB); that changes no number."""
    import jax
    import jax.numpy as jnp

    T, H, dk = q.shape
    dv = v.shape[-1]

    def step(S, x):
        qt, kt, vt, gt, bt = x
        Sk = jnp.einsum("hvk,hk->hv", S, kt)
        S = jnp.exp(gt)[:, None, None] * (
            S - bt[:, None, None] * Sk[:, :, None] * kt[:, None, :]
        ) + bt[:, None, None] * vt[:, :, None] * kt[:, None, :]
        return S, jnp.einsum("hvk,hk->hv", S, qt)

    S0 = jnp.zeros((H, dv, dk), jnp.float32)
    xs = (q, k, v, g, beta)
    if T % STATE_EVERY or T <= STATE_EVERY:
        return jax.lax.scan(step, S0, xs)[1]
    blocks = tuple(x.reshape((T // STATE_EVERY, STATE_EVERY) + x.shape[1:]) for x in xs)
    _, o = jax.lax.scan(
        jax.checkpoint(lambda S, xb: jax.lax.scan(step, S, xb)), S0, blocks)
    return o.reshape(T, H, dv)


def delta_inputs(m, p, x, product=None):
    """What the delta rule reads: q, k [T, H, dk], v [T, H, dv], g, beta
    [T, H].  ``product`` is how q, k and v are projected (default: the plain
    float32 product)."""
    import jax.numpy as jnp

    product = product or jnp.matmul
    T = x.shape[0]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    H = p["a_log"].shape[0]
    q = silu(causal_conv(product(x, p["q"]), p["conv_q"])).reshape(T, H, dk)
    k = silu(causal_conv(product(x, p["k"]), p["conv_k"])).reshape(T, H, dk)
    v = silu(causal_conv(product(x, p["v"]), p["conv_v"])).reshape(T, H, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    beta_factor = 2.0 if m["linear_allow_neg_eigval"] else 1.0
    beta = beta_factor / (1.0 + jnp.exp(-(x @ p["b"])))
    g = -jnp.exp(p["a_log"]) * jnp.logaddexp(0.0, x @ p["a"] + p["dt_bias"])
    return q, k, v, g, beta


def linear_attention(m, p, x):
    """The held heads' part of the layer's output: sum_h o_h W_o[h]."""
    T = x.shape[0]
    o = delta_rule(*delta_inputs(m, p, x))
    o = rmsnorm(o, p["o_norm"], m["rms_norm_eps"]) * silu(x @ p["g"]).reshape(o.shape)
    return o.reshape(T, -1) @ p["o"]


def full_attention(m, p, x):
    """Causal softmax attention over the segment with the full T x T score
    matrix, one head at a time (so that one matrix is held, not one a head);
    the held heads' part of the output."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    d = m["head_dim"]
    A = p["q"].shape[1] // d
    q = rmsnorm((x @ p["q"]).reshape(T, A, d), p["q_norm"], m["rms_norm_eps"])
    k = rmsnorm((x @ p["k"]).reshape(T, A, d), p["k_norm"], m["rms_norm_eps"])
    v = (x @ p["v"]).reshape(T, A, d)
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.where(causal, (qh @ kh.T) * d ** -0.5, -jnp.inf)
        w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        return (w / jnp.sum(w, axis=-1, keepdims=True)) @ vh

    o = jax.lax.map(head, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    return o.transpose(1, 0, 2).reshape(T, A * d) @ p["o"]


def mlp(p, x):
    """The held columns' part: W_down(SiLU(W_gate x) * W_up x)."""
    return (silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def embed(m, table, tokens):
    import jax.numpy as jnp

    idx = tokens - m["vocab_start"]
    held = (idx >= 0) & (idx < table.shape[0])
    return jnp.where(held[:, None], table[jnp.where(held, idx, 0)], 0.0)


def layer_tensors(w: dict, i: int) -> dict:
    p = f"layer{i}."
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


def final_hidden(m, w, tokens):
    """[T, D] after the last norm."""
    import jax

    eps = m["rms_norm_eps"]
    x = embed(m, w["embed"], tokens)
    for i, kind in enumerate(m["layer_types"]):

        def block(p, x, kind=kind):
            y = linear_attention(m, p, x) if kind == LINEAR else full_attention(m, p, x)
            x = x + rmsnorm(y, p["mixer_norm"], eps)
            return x + rmsnorm(mlp(p, x), p["mlp_norm"], eps)

        # recomputation changes no number, only what is held between passes
        x = jax.checkpoint(block)(layer_tensors(w, i), x)
    return rmsnorm(x, w["final_norm"], eps)


def segment_loss_sum(m, w, tokens, valid):
    """Sum over the segment's real, non-final positions t of the
    cross-entropy of token t + 1 given tokens <= t, over the held rows."""
    import jax.numpy as jnp

    h = final_hidden(m, w, tokens)
    logits = h[:-1] @ w["head"].T
    target = tokens[1:] - m["vocab_start"]
    top = jnp.max(logits, axis=-1)
    lse = top + jnp.log(jnp.sum(jnp.exp(logits - top[:, None]), axis=-1))
    picked = jnp.take_along_axis(logits, target[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(valid[1:], lse - picked, 0.0))


def no_decay(name: str) -> bool:
    return any(s in name for s in ("norm", "a_log", "dt_bias", "conv"))


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




# ---------------------------------------------------------------------------
# which histories train in which step (the reference never packs: it only
# needs to know which segments share an optimiser step)


def histories(user_idx, item_ids, max_len: int) -> list:
    """Each entity's items in event order (the generator's event times rise
    with the row), cut to the most recent ``max_len``; entities in the order
    of their first event."""
    users, first = np.unique(user_idx, return_index=True)
    order = np.argsort(user_idx, kind="stable")
    bounds = np.searchsorted(user_idx[order], users).tolist() + [len(order)]
    return [
        item_ids[order[bounds[j] : bounds[j + 1]]][-max_len:]
        for j in np.argsort(first, kind="stable")
    ]


def rows_of(lengths: list, row_len: int) -> list:
    """First-fit decreasing of the histories into rows of ``row_len`` (equal
    lengths in first-event order), the rows in the order they were opened ->
    each row's history indices, in the order they lie in it."""
    free: list = []
    rows: list = []
    for j in sorted(range(len(lengths)), key=lambda j: -lengths[j]):
        r = next((r for r, room in enumerate(free) if lengths[j] <= room), None)
        if r is None:
            free.append(row_len)
            rows.append([])
            r = len(free) - 1
        free[r] -= lengths[j]
        rows[r].append(j)
    return rows


def steps_of(rows: list, rows_per_step: int) -> list:
    """``rows_per_step`` rows an optimiser step -> each step's history
    indices."""
    return [
        sum(rows[s : s + rows_per_step], [])
        for s in range(0, len(rows), rows_per_step)
    ]


# ---------------------------------------------------------------------------
# the replay (needs the device: the child process, or a chip script)


def buckets_for(max_len: int) -> tuple:
    """Padded segment lengths the replay compiles (a segment takes the
    smallest that holds it) and how many segments of each go through one
    call: three shapes, a sixteenth, a quarter and the whole of ``max_len``."""
    return tuple(
        (max(max_len // d, 2), n) for d, n in ((16, 8), (4, 2), (1, 1)))


#: seed of the probes the training record holds (the configuration's
#: ``training_record``)
PROBE_SEED = 1


def bf16_product(x, w):
    """``x @ w`` as the configuration's ``precision.matmuls`` states it:
    bfloat16 inputs, float32 accumulation."""
    import jax.numpy as jnp

    return jnp.matmul(
        x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32)


def delta_rule_probe(m, w, tokens):
    """The FIRST layer's delta-rule output for one segment, each head's d_v
    values along the seeded vector (standard normal from
    ``fold_in(PRNGKey(PROBE_SEED), 2**20)``) -> [T, H].  The rule itself is the
    float32 recurrence token by token; q, k and v are projected in the stated
    precision of the products, so that both sides hand the rule the same
    numbers (the embedding rows are exact, three products of them round the
    same way on both sides) and the gap is the rule's own: its chunks, its
    kernel, and the precision of the state it carries."""
    import jax
    import jax.numpy as jnp

    o = delta_rule(*delta_inputs(
        m, layer_tensors(w, 0), embed(m, w["embed"], tokens), bf16_product))
    r = jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(PROBE_SEED), 2 ** 20),
        (o.shape[-1],), jnp.float32)
    return o @ r


def first_step_probe(m, seed, hist, rows, row_len):
    """``delta_rule_probe`` of every history of the first optimiser step, from
    the seeded initial weights, laid where the packing puts the history (row,
    offset) -> float32 [rows, row_len, H], NaN on padding.  One padded length
    (a segment's tail of padding cannot reach its tokens)."""
    import jax
    import jax.numpy as jnp

    if m["layer_types"][0] != LINEAR:
        return np.full((len(rows), row_len, 0), np.nan, np.float32)
    out = np.full((len(rows), row_len, m["linear_heads_held"]), np.nan, np.float32)
    length = max(len(hist[j]) for row in rows for j in row)
    with jax.default_matmul_precision("highest"):
        w = initial_weights(m, seed)
        w = {k: v for k, v in w.items() if k == "embed" or k.startswith("layer0.")}
        probe = jax.jit(lambda w, t: delta_rule_probe(m, w, t))
        for r, row in enumerate(rows):
            at = 0
            for j in row:
                tok = np.zeros(length, np.int32)
                tok[: len(hist[j])] = hist[j]
                out[r, at : at + len(hist[j])] = np.asarray(
                    probe(w, jnp.asarray(tok)))[: len(hist[j])]
                at += len(hist[j])
    return out


def grad_probe(n: int, g):
    """``r^T g`` for a vector, ``r_rows^T G r_cols`` for a matrix, the r's
    standard normal from ``fold_in(PRNGKey(PROBE_SEED), n)``, split in two for
    a matrix: one seeded linear functional of tensor number n's gradient."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.PRNGKey(PROBE_SEED), n)
    if g.ndim == 1:
        return jnp.sum(g * jax.random.normal(key, g.shape, jnp.float32))
    kr, kc = jax.random.split(key)
    rows = jax.random.normal(kr, (g.shape[0],), jnp.float32)
    cols = jax.random.normal(kc, (g.shape[1],), jnp.float32)
    return jnp.sum(rows * (g @ cols))


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


def sampled_rows(name: str, n_rows: int, n: int) -> np.ndarray:
    """The seeded rows of a tensor the weights are compared on."""
    rng = np.random.default_rng([sum(name.encode()), n_rows])
    return np.sort(rng.choice(n_rows, min(n, n_rows), replace=False))


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
    with the first step's ``delta_rule_probe`` beside them."""
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
    say(f"replay: the first step's delta-rule probe, {time.perf_counter() - t0:.1f} s")
    w, records = replay(
        m, opt, job["seed"], hist, steps_of(rows, job["rows_per_step"]),
        job["steps"], say)
    summary = update_summary(m, job["seed"], w, job["rows_checked"])
    final = {k: np.asarray(v) for k, v in w.items()}
    final["delta_rule_probe"] = probe
    return {"records": records, "update": summary, "final": final,
            "replay_s": time.perf_counter() - t0}


def child_main(argv) -> int:
    """``python olmo_hybrid.py JOB.json``: the replay of the job, its numbers
    as ``out.json`` and its final weights as ``<name>.npy`` beside it."""
    job = json.loads(Path(argv[1]).read_text())
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # the program's own default directory (utils/runtime.py)
        jax.config.update(
            "jax_compilation_cache_dir",
            str(Path(__file__).resolve().parents[2] / ".jax_cache"))
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
    this file's functions read."""
    share = cfg["share"]
    return {
        "hidden_size": cfg["hidden_size"],
        "layer_types": cfg["layer_types"],
        "head_dim": cfg["hidden_size"] // share["published"]["num_attention_heads"],
        "linear_key_head_dim": cfg["linear_key_head_dim"],
        "linear_value_head_dim": cfg["linear_value_head_dim"],
        "linear_conv_kernel_dim": cfg["linear_conv_kernel_dim"],
        "linear_allow_neg_eigval": cfg["linear_allow_neg_eigval"],
        "rms_norm_eps": cfg["rms_norm_eps"],
        "attention_heads_held": cfg["num_attention_heads"],
        "linear_heads_held": cfg["linear_num_value_heads"],
        "mlp_columns_held": share["mlp_columns_held"],
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


def vocabulary_ids(model: dict, item_idx, vocab_start: int):
    """Each event's id under the PERSISTED vocabulary, or None where that
    vocabulary is not the events' items in first-seen order (a bijection
    onto ``vocab_start ..``)."""
    from benchmark import datagen

    first = item_idx[np.sort(np.unique(item_idx, return_index=True)[1])]
    if list(model["item_vocab"]) != [datagen.item_name(i) for i in first]:
        return None
    pos = np.full(int(item_idx.max()) + 1, -1, np.int64)
    pos[first] = vocab_start + np.arange(len(first))
    return pos[item_idx]


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
    linear = {
        f"layer{i}" for i, t in enumerate(cfg["layer_types"]) if t == LINEAR}
    in_mlp = np.array([k.split(".")[-1] in ("gate", "up", "down") for k in want])
    # a linear layer's tensors but for its MLP's
    in_gdn = np.array([
        k.split(".")[0] in linear
        and k.split(".")[-1] not in ("gate", "up", "down", "mlp_norm")
        for k in want])
    rms = lambda x: float(np.sqrt(np.mean(np.square(x))))  # noqa: E731
    say(f"gradient probes against the replay, in units of each gradient's "
        f"norm: step 1 rms {rms(probe[0]):.4g} (MLP {rms(probe[0][in_mlp]):.4g}, "
        f"delta-rule layers' mixers {rms(probe[0][in_gdn]):.4g}), widest "
        f"{probe[0].max():.4g} ({list(want)[int(probe[0].argmax())]}); later "
        f"steps rms {[round(rms(p), 5) for p in probe[1:]]}")
    out += [
        Compared("grad_probe_gap_rms", rms(probe[0]), ref["grad_probe_gap_rms_limit"]),
        Compared("grad_probe_gap_mlp_rms", rms(probe[0][in_mlp]),
                 ref["grad_probe_gap_mlp_rms_limit"]),
        Compared("grad_probe_gap_mixer_rms", rms(probe[0][in_gdn]),
                 ref["grad_probe_gap_mixer_rms_limit"]),
        Compared("grad_probe_gap_later_steps_rms", rms(probe[1:]),
                 ref["grad_probe_gap_later_steps_rms_limit"]),
    ]
    # the first layer's delta rule on the first step's rows: the program's
    # (chunks, kernel, carried state) against the recurrence on the same inputs
    rule_want = np.asarray(final("delta_rule_probe"))
    rule_got = np.asarray(rec.get("delta_rule_probe", np.zeros(0)), np.float32)
    real = np.isfinite(rule_want)
    if rule_got.shape != rule_want.shape or not real.any():
        rule_gap = (
            0.0 if rule_want.size == 0 and rule_got.shape == rule_want.shape
            else float("inf"))
    else:
        err = np.where(
            real, rule_got - np.where(real, rule_want, 0.0), 0.0).astype(np.float64)
        ref_sq = np.where(real, rule_want, 0.0).astype(np.float64) ** 2
        rule_gap = float(np.sqrt(np.sum(err ** 2) / np.sum(ref_sq)))
        by_head = np.sqrt(np.sum(err ** 2, axis=(0, 1)) / np.sum(ref_sq, axis=(0, 1)))
        say(f"first layer's delta rule against the recurrence on the first "
            f"step's rows: relative L2 {rule_gap:.4g} over {int(real.sum())} "
            f"values; by head {by_head.min():.3g} .. {by_head.max():.3g} (head "
            f"{int(by_head.argmax())}); largest gap over largest value "
            f"{np.abs(err).max() / np.sqrt(ref_sq.max()):.4g}")
        if details is not None:
            details["delta_rule_probe_by_head"] = by_head.tolist()
    out.append(Compared(
        "delta_rule_probe_rel_gap", rule_gap, ref["delta_rule_probe_rel_gap_limit"]))
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
            grad_norm_gap=gaps, probe_gap={k: probe[:, n].tolist() for n, k in enumerate(want)},
            update_rel_l2=rel, update_row_gap=row)
    mlp = [v for k, v in rel.items() if k.split(".")[-1] in ("gate", "up", "down")]
    gdn = [v for k, v in rel.items()
           if k.split(".")[-1] in ("a", "b", "a_log", "dt_bias")]
    return out + [
        Compared("update_rel_l2_max", rel[rel_worst], ref["update_rel_l2_max_limit"]),
        Compared("update_rel_l2_median", float(np.median(list(rel.values()))),
                 ref["update_rel_l2_median_limit"]),
        Compared("update_rel_l2_mlp_max", max(mlp), ref["update_rel_l2_mlp_max_limit"]),
        Compared("update_rel_l2_decay_max", max(gdn), ref["update_rel_l2_decay_max_limit"]),
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
