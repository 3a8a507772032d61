"""Dense similarity scoring kernels for item-to-item recommendation.

The TPU-native replacement for the reference's per-item parallel-collection
cosine loop (examples/scala-parallel-similarproduct/.../ALSAlgorithm.scala:
predict — ``productFeatures.par.mapValues {cosine}``): all query-item feature
vectors score against the full item-factor matrix in one batched matmul on
the MXU, then a masked top-k.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from predictionio_tpu.ops.topk import SCORE_PRECISION


@partial(jax.jit, static_argnames=("k",))
def cosine_topk(
    query_features: jax.Array,  # [q, rank] feature vectors of query items
    item_factors: jax.Array,  # [n_items, rank]
    exclude_mask: jax.Array,  # [n_items] bool, True = filtered out
    k: int,
):
    """Sum of cosine similarities of each item to all query vectors, top-k.

    Mirrors the reference scoring exactly: per query vector cosine, summed
    over query vectors, items with score <= 0 dropped (realized by ranking
    with -inf on excluded entries; callers drop non-positive scores).
    """
    qn = query_features / jnp.maximum(
        jnp.linalg.norm(query_features, axis=1, keepdims=True), 1e-9
    )
    item_norm = jnp.maximum(jnp.linalg.norm(item_factors, axis=1), 1e-9)
    # [n_items, q] cosine matrix via one matmul, summed over query vectors
    scores = (
        jnp.matmul(item_factors, qn.T, precision=SCORE_PRECISION).sum(axis=1)
        / item_norm
    )
    scores = jnp.where(exclude_mask, -jnp.inf, scores)
    return jax.lax.top_k(scores, k)


@partial(jax.jit, static_argnames=("k",))
def dot_topk(
    user_vec: jax.Array,  # [rank]
    item_factors: jax.Array,  # [n_items, rank]
    exclude_mask: jax.Array,  # [n_items]
    k: int,
):
    """Dot-product scoring with masked top-k (the known-user serving path)."""
    scores = jnp.matmul(item_factors, user_vec, precision=SCORE_PRECISION)
    scores = jnp.where(exclude_mask, -jnp.inf, scores)
    return jax.lax.top_k(scores, k)
