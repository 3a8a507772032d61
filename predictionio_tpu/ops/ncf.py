"""Neural Collaborative Filtering (two-tower GMF + MLP) with sharded tables.

The deep-rec configuration (BASELINE.json configs[4]: "NCF / two-tower in
JAX, sharded user x item embedding tables") — the one genuinely
model-parallel component of the framework (SURVEY.md §2.9):

  - embedding tables are ROW-SHARDED over the mesh ``model`` axis
    (NamedSharding P("model", None)); XLA GSPMD turns the per-batch gathers
    into collective lookups over ICI;
  - the interaction batch is sharded over ``data`` (pure data parallelism);
  - MLP weights are replicated; their gradients all-reduce automatically;
  - the whole optimization step (forward, loss, backward, Adam/AdamW
    update) is ONE jit program — no per-step host round trips.

Architecture follows the NCF paper shape: a GMF branch (elementwise product
of user/item vectors) and an MLP branch (concat -> relu stack), fused by a
final linear layer; ``mlp_layers=()`` selects a pure-GMF / matrix-
factorization head whose whole-catalog score is one matmul.  Losses: BPR
or sampled softmax over K sampled negatives, and — on the pure-GMF head —
exact whole-catalog ``full_softmax`` and ``wals`` (the implicit-ALS
objective trained by SGD).  ``train_ncf(initial_params=...)`` warm-starts
from pretrained tables (the paper's §3.4.1 recipe; implicit ALS is the
natural GMF pretrainer).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PSpec

from predictionio_tpu.ops.topk import SCORE_PRECISION

#: the serving scorers' matmul: full f32, so the device ranks a query as
#: the numpy host replica does (training keeps the default)
_score_mm = partial(jnp.matmul, precision=SCORE_PRECISION)


@dataclass(frozen=True)
class NCFParams:
    embed_dim: int = 32
    mlp_layers: tuple[int, ...] = (64, 32, 16)
    learning_rate: float = 1e-3
    num_epochs: int = 5
    batch_size: int = 8192
    #: negatives per positive per step.  BPR consumes them as independent
    #: pairwise terms; softmax ranks the positive against all of them
    #: jointly in one (1+K)-way classification.
    negatives_per_positive: int = 1
    #: negative-sampling distribution exponent over item train frequency:
    #: 0.0 = uniform over the catalog; 0.75 = popularity-smoothed (the
    #: word2vec/BPR standard) — harder negatives, much better top-k ranking
    #: on Zipf-shaped catalogs
    neg_power: float = 0.0
    #: ranking loss: "bpr" (pairwise log-sigmoid), "softmax" (sampled
    #: softmax cross-entropy over 1+K candidates), "full_softmax" (exact
    #: cross-entropy over the WHOLE catalog per positive), or "wals"
    #: (whole-catalog weighted least squares — the implicit-ALS objective
    #: trained by SGD; see :func:`wals_loss`).  The whole-catalog losses
    #: compute logits as one [b, d] @ [d, n_items] matmul and therefore
    #: require the pure-GMF architecture ``mlp_layers=()``.
    loss: str = "bpr"
    #: learned per-item score offset.  Catalogs with popularity-driven
    #: feedback are mostly explained by a bias term; giving the model one
    #: explicitly frees the embeddings for the interaction structure.
    item_bias: bool = True
    #: decoupled (AdamW) weight decay.  0 keeps plain Adam.  The
    #: full_softmax objective needs this: it is expressive enough to
    #: overfit a 20M-interaction catalog within a few epochs (MAP@10
    #: peaked at 2 epochs then fell by 30% unregularized), and decay is
    #: the SGD analog of the L2 term implicit-ALS bakes into its normal
    #: equations (reg=0.01 there).
    weight_decay: float = 0.0
    #: confidence weight on observed interactions for loss="wals" (the
    #: iALS alpha; the recommendation templates use 2.0)
    alpha: float = 2.0
    seed: int = 3

    def __post_init__(self):
        allowed = ("bpr", "softmax", "full_softmax", "wals")
        if self.loss not in allowed:
            raise ValueError(
                f"unknown loss {self.loss!r}; expected one of {allowed}"
            )


def init_ncf(rng: jax.Array, n_users: int, n_items: int, p: NCFParams) -> dict:
    """Parameter pytree.  Table rows are padded by the caller so the
    ``model`` axis divides them evenly.

    GMF and MLP embeddings live PACKED in one [n, 2d] table per entity
    (columns [0:d] = GMF half, [d:2d] = MLP half) instead of the paper's
    four separate [n, d] tables: one 2d-wide gather/grad-scatter per
    entity per step keeps the TPU on full vector lanes — the same flat-row
    layout lesson as ops/als._segment_stats (d=32 -> 64 lanes vs 32).
    """
    keys = jax.random.split(rng, 4 + 2 * len(p.mlp_layers))
    d = p.embed_dim
    scale = 1.0 / math.sqrt(d)
    if not p.mlp_layers:
        # pure GMF / matrix factorization: the whole embedding is the
        # interaction vector and the score is a plain dot product — the
        # factorized head the full_softmax loss needs (its whole-catalog
        # logits are one [b, d] @ [d, n_items] matmul).  Discriminated
        # downstream by the ABSENCE of "out_w".
        params = {
            "user_emb": jax.random.normal(keys[0], (n_users, d)) * scale,
            "item_emb": jax.random.normal(keys[1], (n_items, d)) * scale,
            "mlp": [],
            "out_b": jnp.zeros((1,)),
        }
        if p.item_bias:
            params["item_bias"] = jnp.zeros((n_items,))
        return params
    params = {
        "user_emb": jax.random.normal(keys[0], (n_users, 2 * d)) * scale,
        "item_emb": jax.random.normal(keys[1], (n_items, 2 * d)) * scale,
        "mlp": [],
        "out_w": jax.random.normal(keys[2], (d + p.mlp_layers[-1], 1)) * 0.1,
        "out_b": jnp.zeros((1,)),
    }
    if p.item_bias:
        params["item_bias"] = jnp.zeros((n_items,))
    in_dim = 2 * d
    for li, width in enumerate(p.mlp_layers):
        params["mlp"].append(
            {
                "w": jax.random.normal(keys[3 + 2 * li], (in_dim, width))
                * math.sqrt(2.0 / in_dim),
                "b": jnp.zeros((width,)),
            }
        )
        in_dim = width
    return params


def ncf_forward(params: dict, user_idx: jax.Array, item_idx: jax.Array) -> jax.Array:
    """Interaction scores for (user, item) pairs: [batch]."""
    ue = params["user_emb"][user_idx]
    ie = params["item_emb"][item_idx]
    if "out_w" not in params:  # pure GMF (mlp_layers=())
        score = jnp.sum(ue * ie, axis=-1) + params["out_b"][0]
        bias = params.get("item_bias")
        if bias is not None:
            score = score + bias[item_idx]
        return score
    d = params["user_emb"].shape[1] // 2
    gmf = ue[:, :d] * ie[:, :d]  # [b, d]
    h = jnp.concatenate([ue[:, d:], ie[:, d:]], axis=-1)
    for layer in params["mlp"]:
        h = jax.nn.relu(h @ layer["w"] + layer["b"])
    fused = jnp.concatenate([gmf, h], axis=-1)
    score = (fused @ params["out_w"] + params["out_b"])[..., 0]
    bias = params.get("item_bias")  # absent on pre-bias checkpoints
    if bias is not None:
        score = score + bias[item_idx]
    return score


def score_all_items(params: dict, user_idx: jax.Array) -> jax.Array:
    """One user against every item: [n_items] (the serving top-k path).

    The MLP tower broadcasts the user row against the full item table —
    a handful of [n_items, d] matmuls on the MXU.
    """
    if "out_w" not in params:  # pure GMF (mlp_layers=())
        score = _score_mm(params["item_emb"], params["user_emb"][user_idx])
        score = score + params["out_b"][0]
        bias = params.get("item_bias")
        if bias is not None:
            score = score + bias
        return score
    d = params["user_emb"].shape[1] // 2
    n_items = params["item_emb"].shape[0]
    ue = params["user_emb"][user_idx]  # [2d]
    gmf = ue[None, :d] * params["item_emb"][:, :d]  # [n_items, d]
    h = jnp.concatenate(
        [jnp.broadcast_to(ue[d:], (n_items, d)), params["item_emb"][:, d:]],
        axis=-1,
    )
    for layer in params["mlp"]:
        h = jax.nn.relu(_score_mm(h, layer["w"]) + layer["b"])
    fused = jnp.concatenate([gmf, h], axis=-1)
    score = (_score_mm(fused, params["out_w"]) + params["out_b"])[..., 0]
    bias = params.get("item_bias")
    if bias is not None:
        score = score + bias
    return score


def score_users_vs_items(
    head: dict, ue: jax.Array, item_emb: jax.Array, item_bias=None
) -> jax.Array:
    """``[B, 2d|d]`` user rows against an item-table BLOCK: ``[B, rows]``.

    The building block of factor-sharded serving: inside the sharded top-k
    kernel each device calls this with ONLY the item rows it owns (and the
    replicated MLP ``head``), so no device ever holds a full-catalog score
    row.  Same math as :func:`score_all_items` restricted to a row block —
    the per-row computation is identical, so sharded and unsharded serving
    score identically.  ``head`` carries ``mlp``/``out_w``/``out_b`` (and
    discriminates pure GMF by the absence of ``out_w``, as everywhere).
    """
    if "out_w" not in head:  # pure GMF (mlp_layers=())
        scores = _score_mm(ue, item_emb.T) + head["out_b"][0]
        if item_bias is not None:
            scores = scores + item_bias[None, :]
        return scores
    d = ue.shape[-1] // 2
    b, rows = ue.shape[0], item_emb.shape[0]
    gmf = ue[:, None, :d] * item_emb[None, :, :d]  # [B, rows, d]
    h = jnp.concatenate(
        [
            jnp.broadcast_to(ue[:, None, d:], (b, rows, d)),
            jnp.broadcast_to(item_emb[None, :, d:], (b, rows, d)),
        ],
        axis=-1,
    )
    for layer in head["mlp"]:
        h = jax.nn.relu(_score_mm(h, layer["w"]) + layer["b"])
    fused = jnp.concatenate([gmf, h], axis=-1)
    scores = (_score_mm(fused, head["out_w"]) + head["out_b"])[..., 0]
    if item_bias is not None:
        scores = scores + item_bias[None, :]
    return scores


def bpr_loss(params: dict, user_idx, pos_idx, neg_idx, valid) -> jax.Array:
    """Bayesian Personalized Ranking over K negatives: mean over pairs of
    -log sigmoid(s_pos - s_neg).  ``neg_idx`` is [b, K]."""
    b, k = neg_idx.shape
    pos = ncf_forward(params, user_idx, pos_idx)  # [b]
    neg = ncf_forward(
        params, jnp.repeat(user_idx, k), neg_idx.reshape(-1)
    ).reshape(b, k)
    losses = -jax.nn.log_sigmoid(pos[:, None] - neg).mean(axis=1) * valid
    return losses.sum() / jnp.maximum(valid.sum(), 1.0)


def sampled_softmax_loss(params: dict, user_idx, pos_idx, neg_idx, valid):
    """(1+K)-way sampled softmax: the positive must out-rank all K sampled
    negatives jointly — a tighter proxy for top-k ranking than independent
    pairwise terms.  ``neg_idx`` is [b, K]."""
    b, k = neg_idx.shape
    pos = ncf_forward(params, user_idx, pos_idx)  # [b]
    neg = ncf_forward(
        params, jnp.repeat(user_idx, k), neg_idx.reshape(-1)
    ).reshape(b, k)
    logits = jnp.concatenate([pos[:, None], neg], axis=1)  # [b, 1+K]
    losses = -jax.nn.log_softmax(logits, axis=1)[:, 0] * valid
    return losses.sum() / jnp.maximum(valid.sum(), 1.0)


def full_softmax_loss(params: dict, user_idx, pos_idx, valid,
                      n_items: int | None = None):
    """Exact softmax cross-entropy over the WHOLE catalog per positive.

    This is the objective sampled-negative SGD approximates (and the
    reason implicit ALS — whole-catalog weighted least squares — beat the
    sampled NCF configs by ~35% MAP in a run that predates the ledger).
    With the pure-GMF head the logits are ONE [b, d] @ [d, n_items] matmul,
    so "exact" is also the MXU-shaped choice.  Requires init with
    ``mlp_layers=()``."""
    if "out_w" in params:
        raise ValueError(
            "full_softmax needs the pure-GMF head: set mlp_layers=()"
        )
    logits = params["user_emb"][user_idx] @ params["item_emb"].T
    bias = params.get("item_bias")
    if bias is not None:
        logits = logits + bias[None, :]
    if n_items is not None and n_items < logits.shape[1]:
        # table rows past the real catalog are sharding padding: they must
        # not compete in the normalization (or receive gradient)
        logits = jnp.where(
            jnp.arange(logits.shape[1])[None, :] < n_items, logits, -jnp.inf
        )
    logp = jax.nn.log_softmax(logits, axis=1)
    picked = jnp.take_along_axis(logp, pos_idx[:, None].astype(jnp.int32), 1)
    losses = -picked[:, 0] * valid
    return losses.sum() / jnp.maximum(valid.sum(), 1.0)


def wals_loss(params: dict, user_idx, pos_idx, valid, inv_count,
              alpha: float, n_items: int):
    """The implicit-ALS objective, exactly, as a stream loss:

        L = sum_u [ sum_{i in P_u} ((1+a)(1 - s_ui)^2 - s_ui^2)
                    + sum_{j in catalog} s_uj^2 ]  (+ L2 via AdamW decay)

    which is Hu-Koren-Volinsky weighted least squares with confidence
    1 + a on observed cells and 1 on everything else.  Decomposed over the
    positive stream: each (u, i) row contributes its observed-cell term
    once, and carries the user's whole-catalog term scaled by
    ``inv_count = 1/|P_u|`` so a user appearing |P_u| times contributes it
    exactly once per epoch.  This is the objective that made implicit ALS
    beat every sampled NCF config by ~35% MAP before the ledger — here
    it trains the same factorization by AdamW instead of alternating
    exact solves, on logits that are one [b, d] @ [d, n_items] matmul.
    Requires the pure-GMF head (``mlp_layers=()``)."""
    if "out_w" in params:
        raise ValueError("wals needs the pure-GMF head: set mlp_layers=()")
    s = params["user_emb"][user_idx] @ params["item_emb"].T
    bias = params.get("item_bias")
    if bias is not None:
        s = s + bias[None, :]
    mask = (jnp.arange(s.shape[1])[None, :] < n_items).astype(s.dtype)
    s = s * mask
    s_pos = jnp.take_along_axis(s, pos_idx[:, None].astype(jnp.int32), 1)[
        :, 0
    ]
    per_row = (
        (1.0 + alpha) * (1.0 - s_pos) ** 2
        - s_pos**2
        + inv_count * jnp.sum(s * s, axis=1)
    )
    return (per_row * valid).sum() / jnp.maximum(valid.sum(), 1.0)


def param_shardings(mesh: Mesh, params: dict) -> dict:
    """Tables row-sharded over ``model``; everything else replicated.

    A mesh without a ``model`` axis (pure data parallelism, the engine
    default) replicates the tables too.
    """
    has_model = "model" in mesh.shape

    def one(path_leaf):
        path, _ = path_leaf
        name = path[0].key if hasattr(path[0], "key") else str(path[0])
        if has_model and name in ("user_emb", "item_emb"):
            return NamedSharding(mesh, PSpec("model", None))
        return NamedSharding(mesh, PSpec())

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(treedef, [one(f) for f in flat])


@dataclass
class NCFState:
    params: dict  # pytree (device arrays, possibly sharded)
    n_users: int
    n_items: int
    config: NCFParams


#: compiled-epoch cache, like ops.als._STEP_CACHE: a warmup call compiles,
#: subsequent same-shape trains only execute (num_epochs/seed excluded)
_EPOCH_CACHE: dict = {}
_EPOCH_CACHE_MAX = 8


def _get_epoch_fn(
    n_steps: int,
    batch_size: int,
    n_items: int,
    lr: float,
    mesh_key,
    loss: str = "bpr",
    k_neg: int = 1,
    weight_decay: float = 0.0,
    alpha: float = 2.0,
):
    key = (n_steps, batch_size, n_items, lr, mesh_key, loss, k_neg,
           weight_decay, alpha)
    hit = _EPOCH_CACHE.get(key)
    if hit is not None:
        return hit
    while len(_EPOCH_CACHE) >= _EPOCH_CACHE_MAX:
        del _EPOCH_CACHE[next(iter(_EPOCH_CACHE))]
    optimizer = (
        optax.adamw(lr, weight_decay=weight_decay)
        if weight_decay > 0.0
        else optax.adam(lr)
    )
    pair = (
        optimizer,
        make_epoch_fn(optimizer, n_steps, batch_size, n_items, loss, k_neg,
                      alpha),
    )
    _EPOCH_CACHE[key] = pair
    return pair


def make_epoch_fn(
    optimizer,
    n_steps: int,
    batch_size: int,
    n_items: int,
    loss: str = "bpr",
    k_neg: int = 1,
    alpha: float = 2.0,
):
    """One compiled program per EPOCH: device-side shuffle, in-step negative
    sampling, and a lax.scan over all batches.

    This is the TPU-native input pipeline: the positive interactions live on
    the device for the whole train, so there are no per-batch host
    ``device_put``s to prefetch around — the "double buffering" problem is
    dissolved rather than solved.  Per epoch the host does exactly one
    dispatch; gradients/updates stay fused into the scan body (grad +
    GSPMD-inserted all-reduce + Adam).
    """

    loss_fn = {
        "softmax": sampled_softmax_loss,
        "bpr": bpr_loss,
        "full_softmax": None,  # whole-catalog; handled in body
        "wals": None,          # whole-catalog; handled in body
    }[loss]

    # donate params+opt_state: the caller always rebinds them, so XLA can
    # update the tables and Adam moments in place instead of copying
    # ~3x the parameter bytes every epoch
    @partial(jax.jit, donate_argnums=(0, 1))
    def epoch(params, opt_state, u_all, i_all, valid_all, w_all, neg_cdf,
              key):
        kperm, kneg = jax.random.split(key)
        perm = jax.random.permutation(kperm, u_all.shape[0])
        us = u_all[perm].reshape(n_steps, batch_size)
        ps = i_all[perm].reshape(n_steps, batch_size)
        vs = valid_all[perm].reshape(n_steps, batch_size)
        ws = w_all[perm].reshape(n_steps, batch_size)
        # K sampled negatives per positive, drawn PER STEP inside the scan
        # body (a whole-epoch [n_steps, b, K] tensor would pad its minor
        # K dim to 128 lanes — 16x memory blowup at K=8, OOM at ML-20M
        # scale).  Inverse-CDF over ``neg_cdf`` (uniform or
        # popularity-smoothed per NCFParams.neg_power).
        step_keys = jax.random.split(kneg, n_steps)

        def body(carry, xs):
            params, opt_state = carry
            u, pos, valid, w, kstep = xs
            if loss == "wals":
                step_loss, grads = jax.value_and_grad(wals_loss)(
                    params, u, pos, valid, w, alpha, n_items
                )
            elif loss == "full_softmax":
                step_loss, grads = jax.value_and_grad(full_softmax_loss)(
                    params, u, pos, valid, n_items
                )
            else:
                neg = jnp.searchsorted(
                    neg_cdf, jax.random.uniform(kstep, (batch_size, k_neg))
                ).astype(jnp.int32)
                neg = jnp.minimum(neg, n_items - 1)
                step_loss, grads = jax.value_and_grad(loss_fn)(
                    params, u, pos, neg, valid
                )
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return (
                (optax.apply_updates(params, updates), opt_state),
                step_loss,
            )

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), (us, ps, vs, ws, step_keys)
        )
        return params, opt_state, losses.mean()

    return epoch


def train_ncf(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    n_users: int,
    n_items: int,
    params: NCFParams | None = None,
    mesh: Mesh | None = None,
    initial_params: dict | None = None,
) -> NCFState:
    """Train from positive (user, item) interactions with sampled negatives.

    With a mesh, tables are placed row-sharded over ``model`` and batches
    sharded over ``data``; single-device runs skip placement entirely.
    The interaction stream is staged to the device once; see make_epoch_fn.

    Multi-process contract: under ``jax.process_count() > 1`` EVERY process
    must pass the IDENTICAL full interaction arrays (all-gather your local
    shard rows first, e.g. ``multihost_utils.process_allgather``) — unlike
    ``ops.als.train_als_global``, which takes pre-sharded per-process
    chunks.  The global shuffle each epoch needs a consistent global view;
    device memory still only holds each process's shards.
    """
    p = params or NCFParams()

    # pad table rows for even model-axis sharding
    model_par = mesh.shape.get("model", 1) if mesh is not None else 1
    n_users_pad = ((n_users + model_par - 1) // model_par) * model_par
    n_items_pad = ((n_items + model_par - 1) // model_par) * model_par

    net = init_ncf(jax.random.PRNGKey(p.seed), n_users_pad, n_items_pad, p)
    if initial_params is not None:
        # warm start (the NCF paper's pretrain-GMF recipe, He et al. §3.4.1;
        # the natural pretrainer here is implicit ALS, which trains the
        # same factorization by exact alternating solves in seconds):
        # overlay any provided leaves onto the fresh init, zero-padding
        # table rows up to the sharding-padded shape
        unknown = set(initial_params) - set(net)
        if unknown:
            # a silently-dropped leaf would train from random init — the
            # exact hard-to-notice quality failure pretraining exists to
            # prevent
            raise ValueError(
                f"initial_params keys {sorted(unknown)} not in the model "
                f"(have {sorted(net)})"
            )

        def overlay(name, fresh):
            given = initial_params.get(name)
            if given is None:
                return fresh
            given = jnp.asarray(given, fresh.dtype)
            if given.shape == fresh.shape:
                return given
            if given.ndim == 2 and given.shape[1] == fresh.shape[1]:
                return fresh.at[: given.shape[0]].set(given)
            if given.ndim == 1:
                return fresh.at[: given.shape[0]].set(given)
            raise ValueError(
                f"initial_params[{name!r}] shape {given.shape} does not "
                f"fit table shape {fresh.shape}"
            )

        net = {k: overlay(k, v) if k != "mlp" else v for k, v in net.items()}

    data_sharding = None
    if mesh is not None:
        shardings = param_shardings(mesh, net)
        if jax.process_count() > 1:
            # multi-controller placement: every process computed the same
            # seed-deterministic init; each materializes only the shards its
            # local devices own
            net = jax.tree_util.tree_map(
                lambda x, s: jax.make_array_from_callback(
                    np.shape(x), s, lambda idx, x=x: np.asarray(x)[idx]
                ),
                net,
                shardings,
            )
        else:
            net = jax.device_put(net, shardings)
        if "data" in mesh.shape:
            data_sharding = NamedSharding(mesh, PSpec("data"))

    n_pos = len(user_idx)
    bs = min(p.batch_size, max(n_pos, 1))
    data_par = mesh.shape.get("data", 1) if mesh is not None else 1
    bs = ((bs + data_par - 1) // data_par) * data_par
    n_steps = max((n_pos + bs - 1) // bs, 1)
    optimizer, epoch_fn = _get_epoch_fn(
        n_steps,
        bs,
        n_items,
        p.learning_rate,
        mesh,
        loss=p.loss,
        k_neg=max(p.negatives_per_positive, 1),
        weight_decay=p.weight_decay,
        alpha=p.alpha,
    )
    opt_state = optimizer.init(net)

    # stage the full interaction stream on device once (valid masks the
    # padding up to n_steps * bs)
    total = n_steps * bs
    u_all = np.zeros(total, np.int32)
    i_all = np.zeros(total, np.int32)
    valid_all = np.zeros(total, np.float32)
    w_all = np.zeros(total, np.float32)
    u_all[:n_pos] = user_idx
    i_all[:n_pos] = item_idx
    valid_all[:n_pos] = 1.0
    if p.loss == "wals" and n_pos:
        # each stream row carries its user's whole-catalog term scaled by
        # 1/|P_u| so it enters the objective exactly once per epoch
        ucount = np.bincount(np.asarray(user_idx, np.int64))
        w_all[:n_pos] = 1.0 / ucount[np.asarray(user_idx, np.int64)]
    if data_sharding is not None:
        if jax.process_count() > 1:
            # every process passes the identical (all-gathered) interaction
            # stream; device memory still holds only the local shards
            u_all, i_all, valid_all, w_all = (
                jax.make_array_from_callback(
                    x.shape, data_sharding, lambda idx, x=x: x[idx]
                )
                for x in (u_all, i_all, valid_all, w_all)
            )
        else:
            u_all, i_all, valid_all, w_all = (
                jax.device_put(x, data_sharding)
                for x in (u_all, i_all, valid_all, w_all)
            )
    else:
        u_all, i_all, valid_all, w_all = map(
            jnp.asarray, (u_all, i_all, valid_all, w_all)
        )

    neg_cdf = jnp.asarray(
        negative_sampling_cdf(item_idx, n_items, p.neg_power)
    )
    key = jax.random.PRNGKey(p.seed)
    last_loss = None
    for _ in range(p.num_epochs):
        key, ek = jax.random.split(key)
        net, opt_state, last_loss = epoch_fn(
            net, opt_state, u_all, i_all, valid_all, w_all, neg_cdf, ek
        )
    if last_loss is not None:
        jax.block_until_ready(last_loss)
    return NCFState(params=net, n_users=n_users, n_items=n_items, config=p)


def negative_sampling_cdf(
    item_idx: np.ndarray, n_items: int, neg_power: float
) -> np.ndarray:
    """Inverse-CDF table for in-step negative sampling.

    ``neg_power == 0``: uniform over the real catalog [0, n_items).
    ``neg_power > 0``: P(i) ∝ count(i)^neg_power — popularity-smoothed
    negatives (0.75 is the word2vec convention); zero-count items are
    never drawn as negatives.
    """
    if neg_power > 0:
        counts = np.bincount(
            np.asarray(item_idx, np.int64), minlength=n_items
        ).astype(np.float64)[:n_items]
        w = counts**neg_power
        if w.sum() <= 0:
            w = np.ones(n_items)
    else:
        w = np.ones(n_items)
    return (np.cumsum(w) / w.sum()).astype(np.float32)
