"""Seeded inputs: MovieLens-shaped ratings and the event store they go into.

``make_movielens_like`` is the benchmark's own copy of the generator that
``bench.py`` held until PR 28 removed it (commit ``1ae2178`` is the last with
the file; the yardstick may not move with the program); every record the
repo keeps about ML-20M-sized runs was made with it.  ``thin_ratings`` is the
serve cells' short cut: few ratings, every user and every item named once, so
the served tables have the published shape.  ``write_events`` bulk-writes
ratings as ``rate`` events the way an import would (EventFrame ->
``p_events().write``).  Host only: numpy + the program's storage API, no JAX.
"""

from __future__ import annotations

import functools
import json

import numpy as np

#: rank of the planted taste structure
RANK_PLANTED = 8


#: of every BROWSE_K popularity-drawn candidates the user picks the preferred,
#: for BROWSE_FRAC of the interactions (the values of bench.py at 1ae2178)
BROWSE_K = 8
BROWSE_FRAC = 0.7


@functools.lru_cache(maxsize=1)
def _structure(nnz: int, num_users: int, num_items: int, structure_seed: int):
    """WHO rated WHAT, and each rating's noiseless part: (user_idx, item_idx,
    planted taste term, popularity z-score per item).  Kept for the last
    sizes asked, so a process that reads several seeds (the chip scripts under
    ``tests/``) draws it once; a benchmark run asks once anyway."""
    rng = np.random.default_rng(structure_seed)
    item_p = (np.arange(num_items) + 10.0) ** -0.8
    item_p /= item_p.sum()
    item_cdf = np.cumsum(item_p)
    user_w = rng.lognormal(0.0, 1.0, num_users)
    user_p = user_w / user_w.sum()
    user_cdf = np.cumsum(user_p)
    # inverse-CDF sampling: ~10x faster than rng.choice(p=...) at this scale
    user_idx = np.searchsorted(user_cdf, rng.random(nnz)).astype(np.int64)
    user_idx = np.minimum(user_idx, num_users - 1)
    uf = rng.standard_normal((num_users, RANK_PLANTED)).astype(np.float32)
    vf = rng.standard_normal((num_items, RANK_PLANTED)).astype(np.float32)

    item_idx = np.empty(nnz, np.int64)
    browse = rng.random(nnz) < BROWSE_FRAC
    n_plain = int((~browse).sum())
    plain = np.searchsorted(item_cdf, rng.random(n_plain)).astype(np.int64)
    item_idx[~browse] = np.minimum(plain, num_items - 1)
    b_users = user_idx[browse]
    browse_pos = np.flatnonzero(browse)
    # chunked best-of-K: candidates by popularity, winner by planted taste
    for c0 in range(0, len(b_users), 2_000_000):
        bu = b_users[c0 : c0 + 2_000_000]
        cand = np.searchsorted(
            item_cdf, rng.random((len(bu), BROWSE_K))
        ).astype(np.int64)
        cand = np.minimum(cand, num_items - 1)
        pref = np.einsum("nk,njk->nj", uf[bu], vf[cand])
        pick = cand[np.arange(len(bu)), pref.argmax(1)]
        item_idx[browse_pos[c0 : c0 + 2_000_000]] = pick

    zpop = -np.log(np.arange(num_items) + 10.0)
    zpop = (zpop - zpop.mean()) / zpop.std()
    taste = (
        1.8
        * np.einsum("nk,nk->n", uf[user_idx], vf[item_idx])
        / np.sqrt(RANK_PLANTED)
    )
    for a in (user_idx, item_idx, taste):
        a.setflags(write=False)
    return user_idx, item_idx, taste, zpop


def make_movielens_like(
    nnz: int, num_users: int, num_items: int, seed: int, structure_seed: int
):
    """Deterministic ML-shaped ratings (COO): Zipf item popularity, lognormal
    user activity, item quality correlated with popularity, planted rank-8
    personal preference structure + noise; for ``BROWSE_FRAC`` of
    interactions the user picks the preferred of ``BROWSE_K``
    popularity-drawn candidates (bench.py at commit 1ae2178 has the reasoning).

    WHO rated WHAT (and the planted tastes) come from ``structure_seed``, the
    rating VALUES' noise from ``seed``.  The program's compiled shapes follow
    the indices (segment plans, tile counts), so with the structure fixed in
    the configuration every seed finds every program in the persistent cache
    after a checkout's first run, does the same amount of work, and still
    trains on ratings of its own."""
    user_idx, item_idx, taste, zpop = _structure(
        nnz, num_users, num_items, structure_seed)
    noise = np.random.default_rng([seed, 0])
    item_bias = (
        0.3 * zpop + 0.2 * noise.standard_normal(num_items)
    ).astype(np.float32)
    raw = (
        1.55
        + item_bias[item_idx]
        + taste
        + 0.4 * noise.standard_normal(nnz).astype(np.float32)
    )
    rating = np.clip(np.round(raw * 2.0) / 2.0, 0.5, 5.0).astype(np.float32)
    return user_idx, item_idx, rating


def thin_ratings(nnz: int, num_users: int, num_items: int, seed: int,
                 structure_seed: int):
    """``nnz`` ratings whose first ``num_users + num_items`` events name every
    user and every item once (each with a drawn partner), the rest from
    ``make_movielens_like``; partners from ``structure_seed``, values from
    ``seed``."""
    cover = num_users + num_items
    if nnz < cover:
        raise ValueError(f"a thin store needs at least {cover} ratings")
    values = np.random.default_rng([seed, 1])
    rng = np.random.default_rng([structure_seed, 1])
    cu = np.concatenate(
        [np.arange(num_users), rng.integers(0, num_users, num_items)]
    ).astype(np.int64)
    ci = np.concatenate(
        [rng.integers(0, num_items, num_users), np.arange(num_items)]
    ).astype(np.int64)
    cr = (values.integers(1, 11, cover) * 0.5).astype(np.float32)
    u, i, r = make_movielens_like(
        nnz - cover, num_users, num_items, seed, structure_seed)
    return (
        np.concatenate([cu, u]),
        np.concatenate([ci, i]),
        np.concatenate([cr, r]),
    )


def user_name(idx) -> str:
    return f"u{idx}"


def item_name(idx) -> str:
    return f"i{idx}"


def write_events(storage, app_name, user_idx, item_idx, rating, num_users, num_items):
    """One ``rate`` event per rating under a new app ``app_name``."""
    from predictionio_tpu.data.storage.base import EventFrame
    from predictionio_tpu.tools import commands

    nnz = len(rating)
    app = commands.app_new(storage, app_name).app
    user_names = np.array([user_name(x) for x in range(num_users)], object)
    item_names = np.array([item_name(x) for x in range(num_items)], object)
    # ratings take ~10 distinct values: the property documents are a handful
    # of interned strings indexed per event
    rat_vals, rat_code = np.unique(rating, return_inverse=True)
    rat_docs = np.array(
        [json.dumps({"rating": float(v)}) for v in rat_vals], object
    )

    def const(value: str) -> np.ndarray:
        col = np.empty(nnz, object)
        col[:] = value
        return col

    frame = EventFrame(
        event=const("rate"),
        entity_type=const("user"),
        entity_id=user_names[user_idx],
        target_entity_type=const("item"),
        target_entity_id=item_names[item_idx],
        event_time_ms=np.full(nnz, 1_700_000_000_000, np.int64)
        + np.arange(nnz, dtype=np.int64) % 86_400_000,
        properties=rat_docs[rat_code],
    )
    storage.p_events().write(frame, app_id=app.id)
    return app
