"""The controls, at sizes a test run can hold: the reference put in the
program's place and computed one precision below what the configuration
states has to come out NOT correct, by the limits the configuration files
carry; the same reference at the stated precision passes.  (The chip runs of
the same controls at the cells' own sizes: ``control_als_chip.py`` and
``control_serve_chip.py``; their readings are in the configuration files.)"""

import numpy as np
import pytest

from benchmark import reference
from benchmark import run as harness
from benchmark.references import als


def config(name):
    return harness.load_json(harness.BENCH / "configs" / f"{name}.json")


def seeded_model(kind, cfg, seed):
    """Tables of the configuration's own shape with served-size scores."""
    rng = np.random.default_rng(seed)
    nu, ni = cfg["data"]["num_users"], cfg["data"]["num_items"]
    k = cfg["engine_json"]["algorithms"][0]["params"].get("rank", 10)
    users = [f"u{i}" for i in range(nu)]
    items = [f"i{i}" for i in range(ni)]
    U = rng.standard_normal((nu, k)).astype(np.float32) * 0.7
    V = rng.standard_normal((ni, k)).astype(np.float32) * 0.7
    if kind == "als":
        return {"user_factors": U, "item_factors": V,
                "user_vocab": users, "item_vocab": items}
    return {"params": {"user_emb": U, "item_emb": V,
                       "out_b": np.zeros(1, np.float32),
                       "item_bias": rng.standard_normal(ni).astype(np.float32)},
            "user_vocab": users, "item_vocab": items}


@pytest.mark.parametrize("name", ["als-ml20m", "ncf-ml20m"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serving_in_one_bf16_pass_is_not_correct(name, seed):
    cfg = config(name)
    kind, tol = cfg["reference"]["kind"], cfg["reference"]["score_tolerance"]
    model = seeded_model(kind, cfg, seed)
    ref = reference.load(kind).served(model)
    control = reference.load(kind).served(model, lower_precision=True)
    users = [f"u{i}" for i in np.random.default_rng(seed).integers(0, 1000, 64)]

    sound = [(u, reference.top_items(ref, u, 10)) for u in users]
    assert all(c.ok for c in reference.compare_topk(sound, ref, 10, tol))

    served = [(u, reference.top_items(control, u, 10)) for u in users]
    by = {c.name: c for c in reference.compare_topk(served, ref, 10, tol)}
    assert not by["served_score_gap_max"].ok
    # one pass misses by some 1e-3..1e-2 on O(5) scores: far over 3x the limit
    assert by["served_score_gap_max"].value > 10 * tol


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_retrain_accumulated_in_one_bf16_pass_is_not_correct(seed):
    cfg = config("als-ml20m")
    ref = cfg["reference"]
    reg = cfg["engine_json"]["algorithms"][0]["params"]["lambda"]
    rng = np.random.default_rng(seed)
    nu, ni, nnz, k = 3000, 64, 40_000, 10
    U = (rng.standard_normal((nu, k)) * 0.6).astype(np.float32)
    user_idx = rng.integers(0, nu, nnz)
    item_idx = rng.integers(0, ni, nnz)
    rating = (rng.integers(1, 11, nnz) * 0.5).astype(np.float32)

    def table(precision):
        V = np.zeros((ni, k), np.float32)
        for j in range(ni):
            e = np.flatnonzero(item_idx == j)
            V[j] = als.halfstep_control(
                U[user_idx[e]], rating[e], reg, precision)
        return V

    rows = np.arange(ni)
    gaps = {
        p: als.halfstep_gaps(
            table(p), U, item_idx, user_idx, rating, rows, reg)
        for p in ("hilo", "bf16")
    }
    assert np.median(gaps["hilo"]) <= ref["halfstep_gap_median_limit"]
    assert gaps["hilo"].max() <= ref["halfstep_gap_max_limit"]
    assert np.median(gaps["bf16"]) > 3 * ref["halfstep_gap_median_limit"]
