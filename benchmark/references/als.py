"""Explicit ALS (ALS-WR regularisation): the plain reference.

Served: ``V @ u`` over the factors read back from the model store.

Retrain (``check_retrain``): the persisted factors against the raw ratings, in
float64, on seeded samples of rows.  A fit is held by three numbers that
together leave no half of an iteration unchecked:

* the ITEM side, updated last, solves its normal equations over the persisted
  user factors (``last_halfstep_gap_*``; the accumulator's precision shows
  here: one bf16 pass misses by 1e-3);
* the USER side is where one more half-step would put it, up to what twenty
  iterations leave of ALS's slowest mode (``user_fixedpoint_gap_median``: the
  gap between the persisted user rows and their float64 solve over the
  persisted ITEM factors, the checked rows' median; a user update that is
  dropped, solved with another regulariser, or iterated too few times leaves
  more).  The rows' MAXIMUM is printed and not compared (since PR 35): on some
  seeds' ratings a sound fit, in any accumulator precision, is still moving
  up to 2 % of its user rows by 0.027 or more at its twentieth iteration
  (the farthest of 3.6 M rows read: 0.32), where one bf16 pass leaves 0.15
  and a row never updated 0.54-1: no limit separates them (PERF.md section 2);
* the factors predict the ratings (``train_rmse``: all-zero factors are a
  fixed point of both solves, and predict nothing).
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import Compared, bf16_round


class Served:
    def __init__(self, model: dict, lower_precision: bool = False):
        self.U = np.asarray(model["user_factors"], np.float32)
        self.V = np.asarray(model["item_factors"], np.float32)
        self.user_index = {k: i for i, k in enumerate(model["user_vocab"])}
        self.items = list(model["item_vocab"])
        self.item_index = {k: i for i, k in enumerate(self.items)}
        self.finite = bool(np.isfinite(self.U).all() and np.isfinite(self.V).all())
        if lower_precision:
            self.U, self.V = bf16_round(self.U), bf16_round(self.V)

    def scores(self, user: str) -> np.ndarray:
        return self.V @ self.U[self.user_index[user]]


served = Served


def halfstep_gaps(
    solved: np.ndarray,
    other: np.ndarray,
    solved_idx: np.ndarray,
    other_idx: np.ndarray,
    rating: np.ndarray,
    rows: np.ndarray,
    reg: float,
) -> np.ndarray:
    """For each row j of the table ``solved`` in ``rows``: the relative gap
    between the row and the float64 solve of ALS-WR's normal equations
    ``(sum f f^T + reg * n_j * I) x = sum r f`` over the row's ratings, with f
    the rows of the table ``other`` they pair it with.  ``*_idx`` are each
    rating's positions in the two tables."""
    S = np.asarray(solved, np.float64)
    O = np.asarray(other, np.float64)
    k = O.shape[1]
    sel = np.flatnonzero(np.isin(solved_idx, rows))
    order = sel[np.argsort(solved_idx[sel], kind="stable")]
    its = solved_idx[order]
    lo = np.searchsorted(its, rows, side="left")
    hi = np.searchsorted(its, rows, side="right")
    gaps = np.empty(len(rows))
    for n, j in enumerate(rows):
        e = order[lo[n] : hi[n]]
        f = O[other_idx[e]]
        lhs = f.T @ f + reg * max(len(e), 1) * np.eye(k)
        x = np.linalg.solve(lhs, f.T @ rating[e].astype(np.float64))
        gaps[n] = np.linalg.norm(S[j] - x) / max(np.linalg.norm(x), 1e-30)
    return gaps


def halfstep_control(f: np.ndarray, r: np.ndarray, reg: float, precision: str) -> np.ndarray:
    """The control for the item-side check, in numpy: one row's normal
    equations with the accumulator's update rows (``f f^T`` and ``r f``, made
    in float32) rounded the way the Pallas accumulator's precision modes round
    them before the float32 sum — ``bf16``: one pass, rows rounded to bfloat16
    (~2^-8); ``hilo``: the two-pass split hi + lo (~2^-16) — then solved in
    float64."""
    f = np.asarray(f, np.float32)
    upd = np.concatenate(
        [(f[:, :, None] * f[:, None, :]).reshape(len(f), -1),
         f * np.asarray(r, np.float32)[:, None]], axis=1)
    hi = bf16_round(upd)
    if precision == "bf16":
        acc = hi.sum(0, dtype=np.float32)
    elif precision == "hilo":
        acc = hi.sum(0, dtype=np.float32) + bf16_round(upd - hi).sum(0, dtype=np.float32)
    else:
        raise ValueError(precision)
    k = f.shape[1]
    lhs = acc[: k * k].reshape(k, k).astype(np.float64)
    lhs += reg * max(len(f), 1) * np.eye(k)
    return np.linalg.solve(lhs, acc[k * k :].astype(np.float64))


def train_rmse(user_factors, item_factors, user_idx, item_idx, rating, sample):
    """RMSE of the factors on a sample of the ratings."""
    e = sample
    pred = np.einsum(
        "nk,nk->n",
        np.asarray(user_factors, np.float64)[user_idx[e]],
        np.asarray(item_factors, np.float64)[item_idx[e]],
    )
    return float(np.sqrt(np.mean((pred - rating[e]) ** 2)))


def check_retrain(ctx, model: dict, status: str, user_idx, item_idx, rating) -> list:
    """One retrain's persisted model against the configuration and the raw
    ratings it was trained on (generator ids, not table positions)."""
    cfg = ctx.config
    data, ref = cfg["data"], cfg["reference"]
    algo = cfg["engine_json"]["algorithms"][0]["params"]
    U = np.asarray(model["user_factors"], np.float32)
    V = np.asarray(model["item_factors"], np.float32)
    # a table row for every user / item that has a rating (the lognormal
    # activity leaves a few of the configuration's users without one)
    rated_users, rated_items = np.unique(user_idx), np.unique(item_idx)
    shape_ok = (
        U.shape == (len(rated_users), algo["rank"])
        and V.shape == (len(rated_items), algo["rank"])
    )
    finite = bool(np.isfinite(U).all() and np.isfinite(V).all())
    compared = [
        Compared("instance_completed", float(status == "COMPLETED"), 1.0, "min"),
        Compared("table_shape_as_configured", float(shape_ok), 1.0, "min"),
        Compared("factors_finite", float(finite), 1.0, "min"),
    ]
    if not (shape_ok and finite):
        return compared
    # each rating's positions in the persisted tables
    upos = np.full(data["num_users"], -1, np.int64)
    upos[[int(k[1:]) for k in model["user_vocab"]]] = np.arange(len(U))
    ipos = np.full(data["num_items"], -1, np.int64)
    ipos[[int(k[1:]) for k in model["item_vocab"]]] = np.arange(len(V))
    u_at, i_at = upos[user_idx], ipos[item_idx]
    rng = np.random.default_rng([ctx.seed, 5])
    n_rows = int(ref["rows_checked"])
    reg = float(algo["lambda"])

    def rows_of(rated: np.ndarray, pos: np.ndarray) -> np.ndarray:
        return pos[np.sort(rng.choice(rated, min(n_rows, len(rated)), replace=False))]

    item_gaps = halfstep_gaps(V, U, i_at, u_at, rating, rows_of(rated_items, ipos), reg)
    user_gaps = halfstep_gaps(U, V, u_at, i_at, rating, rows_of(rated_users, upos), reg)
    sample = rng.choice(len(rating), min(len(rating), 1_000_000), replace=False)
    rmse = train_rmse(U, V, u_at, i_at, rating, sample)
    ctx.say(
        f"item rows against their normal equations ({len(item_gaps)} rows): "
        f"median {np.median(item_gaps):.3g}, p95 {np.quantile(item_gaps, 0.95):.3g}, "
        f"max {item_gaps.max():.3g}; user rows against one more half-step "
        f"({len(user_gaps)} rows): median {np.median(user_gaps):.4g}, "
        f"p95 {np.quantile(user_gaps, 0.95):.4g}, max {user_gaps.max():.4g}; "
        f"train RMSE over {len(sample)} ratings {rmse:.5f}"
    )
    return compared + [
        Compared("last_halfstep_gap_median", float(np.median(item_gaps)),
                 float(ref["halfstep_gap_median_limit"])),
        Compared("last_halfstep_gap_max", float(item_gaps.max()),
                 float(ref["halfstep_gap_max_limit"])),
        Compared("user_fixedpoint_gap_median", float(np.median(user_gaps)),
                 float(ref["user_fixedpoint_gap_median_limit"])),
        Compared("train_rmse", rmse, float(ref["train_rmse_limit"])),
    ]
