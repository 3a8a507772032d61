"""The SmallThinker block's plain reference: pre-norm layers of grouped-query
attention (global without rotary positions, or a sliding window with them)
followed by routed experts whose router reads the layer's normed INPUT;
next-item training with AdamW.

Straight ``jax.numpy`` in float32 with ``jax.default_matmul_precision
("highest")``: every held expert applied DENSELY to every token under a mask
of the chosen (no dispatch, no grouping, no capacity), attention with a full
score matrix a head and block of queries, the window and causality as a mask,
ONE SEGMENT AT A TIME (a segment is one entity's history, so positions start
at 0 by themselves): no packing, no kernels.  Blocks of queries, of loss
positions and one expert after another only bound what is live; they change
no number.  Gradients by ``jax.grad``, AdamW written out.  Nothing of the
program is imported; what no model's mathematics enters (how histories are
grouped into optimiser steps, the seeded gradient probe, the sampled rows, the
vocabulary's order) is shared with ``references/olmo_hybrid.py``, the rotary
rotation with ``references/falcon_h1.py``.

The published description is the model's config.json
(huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct) and its release's
summary; what they do not settle is the configuration file's ``assumed``.
Per token t of a segment, stream x in R^D, ``m`` the configuration's ``model``
group (``model_group``), ``h = RMSNorm(x; input_norm)``:

    r        = W_r h                          logits over ALL experts, before attention
    q, k, v  = W_q h, W_k h, W_v h            query head n on KV head n // (heads / kv heads)
    global   : softmax over s <= t of q_t k_s / sqrt(d), no rotary
    sliding  : the same over 0 <= t - s < window with RoPE(q), RoPE(k)
    x1       = x + W_o attn
    S        = the k largest of r;  w = softmax(r[S])
    y        = sum over e in S, e HELD, of w_e W_down,e(relu(W_gate,e m) * W_up,e m),
               m = RMSNorm(x1; post_norm)
    x2       = x1 + y

The share (model-configs guide, section 4): the tensors are the slices one of
``chips`` chips holds (its query and KV heads, its experts, its vocabulary
rows; the router whole), every function computes what those slices give, an
item id outside the held rows embeds to zero, logits and loss run over the
held rows, and a chosen expert that is not held adds nothing.

``check_retrain`` replays the configured optimiser steps from the seeded
initial weights in ONE child process on the chip and holds the persisted model
to the replay.  Routing is discrete, so beside the Falcon check's numbers it
compares the choices themselves (``route_flip_share_layer<n>``), the pairs the
held experts computed (``moe_pairs_held_rel_gap``) and ``moe_probe``: the first
layer's experts applied to ``h`` (exact embedding rows, normed in float32: the
same numbers on both sides) in the configuration's stated bf16 product, so
that the program's record of the same quantity differs by the expert path
alone (dispatch, grouping, the products' accumulation, the combine).
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

from benchmark.references.falcon_h1 import rope  # noqa: E402
from benchmark.references.olmo_hybrid import (  # noqa: E402
    PROBE_SEED, bf16_product, histories, rmsnorm, rows_of, sampled_rows,
    steps_of, vocabulary_ids)
from benchmark.references.olmo_hybrid import grad_probe as _matrix_probe  # noqa: E402

EXPERTS = ("experts_gate", "experts_up", "experts_down")
ATTENTION = ("q", "k", "v", "o")
GLOBAL, SLIDING = "global", "sliding"

#: queries a block of the score matrix holds, positions a block of the loss
QUERY_BLOCK = 1024
LOSS_BLOCK = 2048


# ---------------------------------------------------------------------------
# shapes and the seeded initial weights (the rule of the configuration file)


def tensor_shapes(m: dict) -> dict:
    """Flat name -> held shape, in the order the initialisation counts."""
    D, hd = m["hidden_size"], m["head_dim"]
    A, KV = m["attention_heads_held"], m["kv_heads_held"]
    E, F, V = m["experts_held"], m["expert_width"], m["vocab_rows_held"]
    out = {"embed": (V, D)}
    for i in range(len(m["layer_kinds"])):
        p = f"layer{i}."
        out.update({
            p + "input_norm": (D,), p + "router": (D, m["experts"]),
            p + "q": (D, A * hd), p + "k": (D, KV * hd), p + "v": (D, KV * hd),
            p + "o": (A * hd, D), p + "post_norm": (D,),
            p + "experts_gate": (E, D, F), p + "experts_up": (E, D, F),
            p + "experts_down": (E, F, D),
        })
    out["final_norm"] = (D,)
    out["head"] = (V, D)
    return out


def initial_weights(m: dict, seed: int) -> dict:
    """Tensor number n draws from ``fold_in(PRNGKey(seed), n)`` at its held
    shape (the configuration's ``initialisation``): norm weights 1, every
    matrix (the router and the stacked experts too) normal(0, 0.02)."""
    import jax
    import jax.numpy as jnp

    base = jax.random.PRNGKey(seed)
    out = {}
    for n, (name, shape) in enumerate(tensor_shapes(m).items()):
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            out[name] = 0.02 * jax.random.normal(
                jax.random.fold_in(base, n), shape, jnp.float32)
    return out


# ---------------------------------------------------------------------------
# the layers, for ONE segment: x [T, D]; ``valid`` [T] marks real tokens (a
# segment is padded at its END to a length the replay compiles once; nothing
# after a token can reach it)


def attention(m, p, h, kind: str):
    """Causal softmax attention over the segment (its last ``window`` keys
    where the layer slides, the query itself among them), the score matrix of
    one query head and ``QUERY_BLOCK`` queries at a time; the held heads' part
    of the output."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    d = m["head_dim"]
    q = (h @ p["q"]).reshape(T, -1, d)
    k = (h @ p["k"]).reshape(T, -1, d)
    v = (h @ p["v"]).reshape(T, -1, d)
    if kind == SLIDING:
        q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    A, KV = q.shape[1], k.shape[1]
    qb = min(QUERY_BLOCK, T)
    blocks = -(-T // qb)
    qp = jnp.pad(q, ((0, blocks * qb - T), (0, 0), (0, 0)))
    key_at = jnp.arange(T)

    @jax.checkpoint
    def block(args):
        qh, kh, vh, at = args
        seen = key_at[None, :] <= at[:, None]
        if kind == SLIDING:
            seen &= at[:, None] - key_at[None, :] < m["window"]
        s = jnp.where(seen, (qh @ kh.T) * d ** -0.5, -jnp.inf)
        w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        return (w / jnp.sum(w, axis=-1, keepdims=True)) @ vh

    of = jnp.arange(A) // (A // KV)

    def head(args):
        qh, kh, vh = args
        return jax.lax.map(
            lambda a: block((a[0], kh, vh, a[1])),
            (qh.reshape(blocks, qb, d), jnp.arange(blocks * qb).reshape(blocks, qb)),
        ).reshape(blocks * qb, d)[:T]

    o = jax.lax.map(head, (
        qp.transpose(1, 0, 2), k.transpose(1, 0, 2)[of], v.transpose(1, 0, 2)[of]))
    return o.transpose(1, 0, 2).reshape(T, A * d) @ p["o"]


def route(m, logits):
    """-> (the chosen experts [T, k], their weights [T, k]): the k largest
    logits, then softmax over those k."""
    import jax
    import jax.numpy as jnp

    top, idx = jax.lax.top_k(logits, m["experts_per_token"])
    w = jnp.exp(top - top[:, :1])
    return idx, w / jnp.sum(w, axis=-1, keepdims=True)


def experts(m, p, x, idx, w, product=None):
    """The HELD experts' part: each applied to every token, weighted by the
    token's weight for it (zero where the token did not choose it).
    ``product`` is how the three products are made (default: plain float32)."""
    import jax
    import jax.numpy as jnp

    product = product or jnp.matmul

    @jax.checkpoint
    def one(out, args):
        gate, up, down, e = args
        chose = jnp.sum(jnp.where(idx == e + m["expert_start"], w, 0.0), axis=-1)
        y = product(jnp.maximum(product(x, gate), 0.0) * product(x, up), down)
        return out + chose[:, None] * y, None

    return jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_gate"], p["experts_up"], p["experts_down"],
        jnp.arange(m["experts_held"])))[0]


def embed(m, table, tokens):
    import jax.numpy as jnp

    idx = tokens - m["vocab_start"]
    held = (idx >= 0) & (idx < table.shape[0])
    return jnp.where(held[:, None], table[jnp.where(held, idx, 0)], 0.0)


def layer_tensors(w: dict, i: int) -> dict:
    p = f"layer{i}."
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


def block(m, kind, p, x):
    """-> (the stream after the layer, the router's choices [T, k])."""
    eps = m["rms_norm_eps"]
    h = rmsnorm(x, p["input_norm"], eps)
    idx, w = route(m, h @ p["router"])  # read BEFORE attention
    x = x + attention(m, p, h, kind)
    return x + experts(m, p, rmsnorm(x, p["post_norm"], eps), idx, w), idx


def final_hidden(m, w, tokens):
    """-> ([T, D] after the last norm, every layer's choices [L, T, k])."""
    import jax
    import jax.numpy as jnp

    x = embed(m, w["embed"], tokens)
    choices = []
    for i, kind in enumerate(m["layer_kinds"]):
        # recomputation changes no number, only what is held between passes
        x, idx = jax.checkpoint(functools.partial(block, m, kind))(
            layer_tensors(w, i), x)
        choices.append(idx)
    return rmsnorm(x, w["final_norm"], m["rms_norm_eps"]), jnp.stack(choices)


def segment_loss_sum(m, w, tokens, valid):
    """Sum over the segment's real, non-final positions t of the
    cross-entropy of token t + 1 given tokens <= t, over the held rows
    (``LOSS_BLOCK`` positions' logits at a time), and the layers' choices."""
    import jax
    import jax.numpy as jnp

    h, choices = final_hidden(m, w, tokens)
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
    return loss, choices


def no_decay(name: str) -> bool:
    return "norm" in name


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
    """The seeded functional of tensor number n's gradient; stacked experts as
    one matrix, the experts' rows on end."""
    return _matrix_probe(n, _as_matrix(g))


# ---------------------------------------------------------------------------
# the replay (needs the device: the child process, or a chip script)


def buckets_for(max_len: int) -> tuple:
    """Padded segment lengths the replay compiles (a segment takes the
    smallest that holds it) and how many segments of each go through one
    call: a sixteenth, a quarter and the whole of ``max_len``."""
    return tuple(
        (max(-(-max_len // d), 2), n) for d, n in ((16, 8), (4, 2), (1, 1)))


def moe_probe(m, w, tokens):
    """The FIRST layer's experts applied to its own normed input ``h`` (its
    router's choices and weights, float32) for one segment, along the seeded
    vector (standard normal [D] from ``fold_in(PRNGKey(PROBE_SEED), 2**20 +
    2)``) -> [T].  ``h`` is exact embedding rows normed in float32, the same
    numbers on both sides; the three products are made in the stated precision
    (bfloat16 inputs, float32 accumulation), so the gap to the program's record
    is the expert path's own: a pair dropped or misrouted, a tile's rows, the
    accumulation's precision, the combine."""
    import jax
    import jax.numpy as jnp

    p = layer_tensors(w, 0)
    h = rmsnorm(embed(m, w["embed"], tokens), p["input_norm"], m["rms_norm_eps"])
    idx, wt = route(m, h @ p["router"])
    y = experts(m, p, h, idx, wt, bf16_product)
    r = jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(PROBE_SEED), 2 ** 20 + 2),
        (y.shape[-1],), jnp.float32)
    return y @ r


def first_step_probe(m, seed, hist, rows, row_len):
    """``moe_probe`` of every history of the first optimiser step, from the
    seeded initial weights, laid where the packing puts the history (row,
    offset) -> float32 [rows, row_len, 1], NaN on padding."""
    import jax
    import jax.numpy as jnp

    out = np.full((len(rows), row_len, 1), np.nan, np.float32)
    length = max(len(hist[j]) for row in rows for j in row)
    with jax.default_matmul_precision("highest"):
        w = initial_weights(m, seed)
        w = {k: v for k, v in w.items() if k == "embed" or k.startswith("layer0.")}
        probe = jax.jit(lambda w, t: moe_probe(m, w, t))
        for r, row in enumerate(rows):
            at = 0
            for j in row:
                tok = np.zeros(length, np.int32)
                tok[: len(hist[j])] = hist[j]
                out[r, at : at + len(hist[j]), 0] = np.asarray(
                    probe(w, jnp.asarray(tok)))[: len(hist[j])]
                at += len(hist[j])
    return out


def replay(m, opt, seed, hist, steps, n_steps, say=print):
    """``n_steps`` optimiser steps from the seeded initial weights -> (final
    weights, per-step records, the first step's choices by history)."""
    import jax
    import jax.numpy as jnp

    held = (m["expert_start"], m["expert_start"] + m["experts_held"])

    # the moments pass through untouched: the compiler fits a program's
    # temporaries into what ITS arguments leave of the device
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def accumulate(moments, gsum, w, tokens, valid):
        def total(w):
            loss, choices = jax.vmap(
                lambda t, v: segment_loss_sum(m, w, t, v))(tokens, valid)
            return jnp.sum(loss), choices

        (loss, choices), g = jax.value_and_grad(total, has_aux=True)(w)
        mine = (choices >= held[0]) & (choices < held[1]) & valid[:, None, :, None]
        return (moments, loss, jax.tree.map(jnp.add, gsum, g),
                choices.astype(jnp.int8), jnp.sum(mine, axis=(0, 2, 3)))

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
    first_choices: dict = {}
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
                        tokens_seen += len(hist[j])
                    (mom, var), loss, gsum, choices, held_pairs = accumulate(
                        (mom, var), gsum, w, jnp.asarray(tok), jnp.asarray(val))
                    losses.append(loss)
                    pairs.append(held_pairs)
                    if s == 0:
                        choices = np.asarray(choices)
                        for r, j in enumerate(group[c0 : c0 + batch]):
                            first_choices[j] = choices[r, :, : len(hist[j])]
            scale = 1.0 / max(count, 1)
            loss = float(sum(float(x) for x in losses)) * scale
            gnorm, tnorms, probes = norms(gsum, scale)
            w, mom, var = update(w, mom, var, gsum, scale, float(s + 1))
            records.append({
                "loss": loss, "tokens": count, "grad_norm": float(gnorm),
                "tensor_grad_norm": {k: float(v) for k, v in tnorms.items()},
                "tensor_grad_probe": {k: float(v) for k, v in probes.items()},
                "moe_pairs_held": np.sum(
                    [np.asarray(x) for x in pairs], axis=0).tolist(),
                "moe_pairs_total": tokens_seen * m["experts_per_token"],
            })
            say(f"replay step {s + 1}: loss {loss:.6f} over {count} positions, "
                f"gradient norm {float(gnorm):.6g}, held pairs a layer "
                f"{records[-1]['moe_pairs_held']} of {records[-1]['moe_pairs_total']}, "
                f"{time.perf_counter() - t0:.1f} s")
    return w, records, first_choices


def _as_matrix(x):
    return x.reshape(-1, x.shape[-1]) if x.ndim > 2 else x


def update_summary(m, seed, final: dict, n_rows: int) -> dict:
    """Per tensor of the replay: the L2 norm of its update (final - initial)
    and the largest update-row norm over the sampled rows (stacked experts as
    one matrix)."""
    import jax.numpy as jnp

    init = initial_weights(m, seed)
    out = {}
    for name, w in final.items():
        d = _as_matrix(w - init[name])
        rows = d if d.ndim == 1 else jnp.linalg.norm(
            d[sampled_rows(name, d.shape[0], n_rows)], axis=-1)
        out[name] = [float(jnp.linalg.norm(d)), float(jnp.max(jnp.abs(rows)))]
    return out


def replay_job(job: dict, say=print) -> dict:
    """The whole replay of one job description -> records, the update's
    summary, and under ``final`` the final weights as float32 numpy arrays
    with the first step's ``moe_probe`` and ``choices`` (laid out as the
    packing lays the histories; -1 on padding) beside them."""
    m, opt = job["model"], job["optimizer"]
    data = np.load(job["data"])
    hist = [
        h.astype(np.int32)
        for h in histories(data["user_idx"], data["item_ids"], job["max_len"])
    ]
    rows = rows_of([len(h) for h in hist], job["row_len"])
    first_rows = rows[: job["rows_per_step"]]
    t0 = time.perf_counter()
    probe = first_step_probe(m, job["seed"], hist, first_rows, job["row_len"])
    say(f"replay: the first step's expert probe, {time.perf_counter() - t0:.1f} s")
    w, records, first_choices = replay(
        m, opt, job["seed"], hist, steps_of(rows, job["rows_per_step"]),
        job["steps"], say)
    choices = np.full(
        (len(first_rows), len(m["layer_kinds"]), job["row_len"],
         m["experts_per_token"]), -1, np.int8)
    for r, row in enumerate(first_rows):
        at = 0
        for j in row:
            if j in first_choices:
                choices[r, :, at : at + len(hist[j])] = first_choices[j]
            at += len(hist[j])
    summary = update_summary(m, job["seed"], w, job["rows_checked"])
    final = {k: np.asarray(v) for k, v in w.items()}
    final["moe_probe"] = probe
    final["choices"] = choices
    return {"records": records, "update": summary, "final": final,
            "replay_s": time.perf_counter() - t0}


def child_main(argv) -> int:
    """``python smallthinker.py JOB.json``: the replay of the job, its numbers
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
    n = cfg["num_hidden_layers"]
    return {
        "hidden_size": cfg["hidden_size"],
        "head_dim": cfg["head_dim"],
        "rope_theta": float(cfg["rope_theta"]),
        "rms_norm_eps": cfg["rms_norm_eps"],
        "attention_heads_held": cfg["num_attention_heads"],
        "kv_heads_held": cfg["num_key_value_heads"],
        # the router keeps its published width; the experts are the held ones
        "experts": share["published"]["moe_num_primary_experts"],
        "experts_held": cfg["moe_num_primary_experts"],
        "expert_start": share["expert_start"],
        "experts_per_token": cfg["moe_num_active_primary_experts"],
        "expert_width": cfg["moe_ffn_hidden_size"],
        "window": cfg["sliding_window_size"],
        # the layouts stay whole in the file; the layers kept are their first
        "layer_kinds": [
            SLIDING if slides else GLOBAL
            for slides in cfg["sliding_window_layout"][:n]],
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


def _routing(cfg, rec, res, final, say, details) -> list:
    """The discrete part: choices, pairs computed, the expert path's probe."""
    from benchmark.reference import Compared

    ref = cfg["reference"]
    n_steps = cfg["engine_json"]["algorithms"][0]["params"]["stepsPerRetrain"]
    out = []
    want = np.asarray(final("choices"))
    got = np.asarray(rec.get("choices", np.zeros(0)))
    k = want.shape[-1]
    real = want[..., 0] >= 0
    if got.shape != want.shape or not real.any():
        flips = [float("inf")] * want.shape[1]
    else:
        # a (token, choice) pair of the replay the program did not make
        same = (want[..., :, None] == got[..., None, :]).any(axis=-1)
        flips = [
            float(np.sum(~same[:, layer][real[:, layer]]) / (real[:, layer].sum() * k))
            for layer in range(want.shape[1])]
        say(f"the first step's choices against the replay's: share of (token, "
            f"choice) pairs that differ, by layer {[round(f, 6) for f in flips]} "
            f"over {int(real[:, 0].sum())} tokens")
    for layer, share in enumerate(flips):
        out.append(Compared(
            f"route_flip_share_layer{layer + 1}", share,
            ref["route_flip_share_first_layer_limit"] if layer == 0
            else ref["route_flip_share_limit"]))
    held = np.asarray(rec.get("moe_pairs_held", np.zeros((0, 0))), np.float64)
    total = np.asarray(rec.get("moe_pairs_total", np.zeros((0, 0))), np.float64)
    gaps, total_gap = [], 0.0
    for s, r in enumerate(res["records"][:n_steps]):
        w = np.asarray(r["moe_pairs_held"], np.float64)
        if s >= len(held) or held[s].shape != w.shape:
            gaps.append(float("inf"))
            total_gap = float("inf")
            continue
        gaps.append(float(np.max(np.abs(held[s] - w) / np.maximum(w, 1.0))))
        total_gap = max(total_gap, float(np.max(np.abs(total[s] - r["moe_pairs_total"]))))
    say(f"pairs the held experts computed against the replay's: widest relative "
        f"gap a step {[round(g, 6) for g in gaps]}; pairs of all experts off by "
        f"{total_gap:g}")
    out += [
        Compared("moe_pairs_total_gap", total_gap, 0.0),
        # the first step's pairs differ by its flips alone; a later step's by
        # how far four optimiser steps carry them
        Compared("moe_pairs_held_step1_rel_gap", gaps[0],
                 ref["moe_pairs_held_step1_rel_gap_limit"]),
        Compared("moe_pairs_held_rel_gap", max(gaps), ref["moe_pairs_held_rel_gap_limit"]),
    ]
    probe_want = np.asarray(final("moe_probe"))
    probe_got = np.asarray(rec.get("moe_probe", np.zeros(0)), np.float32)
    real = np.isfinite(probe_want)
    if probe_got.shape != probe_want.shape or not real.any():
        gap = float("inf")
    else:
        err = np.where(real, probe_got - np.where(real, probe_want, 0.0), 0.0)
        ref_sq = np.where(real, probe_want, 0.0).astype(np.float64) ** 2
        gap = float(np.sqrt(np.sum(err.astype(np.float64) ** 2) / np.sum(ref_sq)))
        say(f"first layer's experts on its normed input against the dense "
            f"reference on the first step's rows: relative L2 {gap:.4g} over "
            f"{int(real.sum())} values; largest gap over largest value "
            f"{np.abs(err).max() / np.sqrt(ref_sq.max()):.4g}")
    out.append(Compared("moe_probe_rel_gap", gap, ref["moe_probe_rel_gap_limit"]))
    if details is not None:
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
    in_experts = np.array([n in EXPERTS for n in leaf])
    in_attn = np.array([n in ATTENTION for n in leaf])
    in_router = np.array([n == "router" for n in leaf])
    rms = lambda x: float(np.sqrt(np.mean(np.square(x))))  # noqa: E731
    say(f"gradient probes against the replay, in units of each gradient's "
        f"norm: step 1 rms {rms(probe[0]):.4g} (experts {rms(probe[0][in_experts]):.4g}, "
        f"router {rms(probe[0][in_router]):.4g}, attention "
        f"{rms(probe[0][in_attn]):.4g}), widest {probe[0].max():.4g} "
        f"({list(want)[int(probe[0].argmax())]}); later steps rms "
        f"{[round(rms(p), 5) for p in probe[1:]]}")
    out += [
        Compared("grad_probe_gap_rms", rms(probe[0]), ref["grad_probe_gap_rms_limit"]),
        Compared("grad_probe_gap_experts_rms", rms(probe[0][in_experts]),
                 ref["grad_probe_gap_experts_rms_limit"]),
        Compared("grad_probe_gap_router_rms", rms(probe[0][in_router]),
                 ref["grad_probe_gap_router_rms_limit"]),
        Compared("grad_probe_gap_attention_rms", rms(probe[0][in_attn]),
                 ref["grad_probe_gap_attention_rms_limit"]),
        Compared("grad_probe_gap_later_steps_rms", rms(probe[1:]),
                 ref["grad_probe_gap_later_steps_rms_limit"]),
    ]
    rel, row = {}, {}
    for name, (norm, row_norm) in res["update"].items():
        gap = _as_matrix(
            np.asarray(model["params"][name], np.float32) - final(name))
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
    expert_gaps = [v for k, v in rel.items() if k.split(".")[-1] in EXPERTS]
    return out + [
        Compared("update_rel_l2_max", rel[rel_worst], ref["update_rel_l2_max_limit"]),
        Compared("update_rel_l2_median", float(np.median(list(rel.values()))),
                 ref["update_rel_l2_median_limit"]),
        Compared("update_rel_l2_experts_max", max(expert_gaps),
                 ref["update_rel_l2_experts_max_limit"]),
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
