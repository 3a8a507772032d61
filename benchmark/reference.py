"""The comparisons that decide ``correct``, and what every plain reference
shares.

A configuration names its reference (``reference.kind``); a cell's file under
``cells/`` may name another (``reference``).  Either is a file
``references/<name>.py``, found by ``load`` the way kinds and readers are, so a
new model family or a new check comes as a new file.  A reference module has

    served(model, lower_precision=False)   for serve kinds: an object with
                                           scores(user), items, item_index,
                                           user_index, finite
    check_retrain(ctx, model, status, user_idx, item_idx, rating)
                                           for the retrain kind: [Compared]

and only what it can check.  numpy only, and nothing of the program: the
references read the persisted tables (the serve path's input) or the raw
ratings (the retrain's input) and compute what the configuration's
mathematics says, in float32/float64.  Every comparison yields a ``Compared``
(name, value, limit), which the harness prints in every run; ``correct`` is
"all within their limits".  ``lower_precision`` is the control: the same
reference computed one precision below (``bf16_round`` + one pass).
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass

import numpy as np


def load(name: str):
    """The reference module ``references/<name>.py``."""
    return importlib.import_module(f"benchmark.references.{name}")


@dataclass(frozen=True)
class Compared:
    name: str
    value: float
    limit: float
    #: "max": value <= limit holds; "min": value >= limit holds
    sense: str = "max"

    @property
    def ok(self) -> bool:
        if not np.isfinite(self.value):
            return False
        if self.sense == "min":
            return self.value >= self.limit
        return self.value <= self.limit

    def line(self) -> str:
        op = ">=" if self.sense == "min" else "<="
        return (
            f"compared {self.name}: {self.value:.6g} {op} limit "
            f"{self.limit:.6g} -> {'ok' if self.ok else 'NOT OK'}"
        )


def bf16_round(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to the nearest bfloat16 (returned as float32):
    what one MXU pass at the TPU's DEFAULT precision does to its operands."""
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


# ---------------------------------------------------------------------------
# served top-k


def top_items(ref, user: str, num: int) -> list[dict]:
    """The reference's own answer for ``user`` in the served JSON shape (the
    control: a lower-precision reference put in the program's place)."""
    s = ref.scores(user)
    order = np.argsort(-s, kind="stable")[:num]
    return [{"item": ref.items[j], "score": float(s[j])} for j in order]


def compare_topk(answers, ref, num: int, score_tol: float) -> list[Compared]:
    """``answers``: (user, itemScores) pairs as served.  Against the reference
    scores: each answer is ``num`` distinct known items in descending order;
    each score is the reference score of ITS item within ``score_tol``; the
    ids EQUAL ``argsort(-ref)[:num]`` unless the reference's own top
    ``num + 1`` scores hold a gap under twice the tolerance (a near-tie no
    f32 program orders reliably), where every served item must still score
    within the tolerance of the num-th.  Yields the widest score gap and the
    number of malformed / wrongly ranked answers."""
    worst = 0.0
    malformed = wrong_ids = 0
    for user, item_scores in answers:
        try:
            idx = np.array([ref.item_index[e["item"]] for e in item_scores])
            got = np.array([e["score"] for e in item_scores], np.float64)
        except (KeyError, TypeError):
            malformed += 1
            continue
        if (
            len(idx) != num
            or not np.isfinite(got).all()
            or len(set(idx.tolist())) != num
            or (np.diff(got) > 0).any()
        ):
            malformed += 1
            continue
        r = ref.scores(user)
        worst = max(worst, float(np.abs(got - r[idx]).max()))
        order = np.argsort(-r, kind="stable")[: num + 1]
        if np.array_equal(idx, order[:num]):
            continue
        near_tie = bool((-np.diff(r[order]) <= 2 * score_tol).any())
        if not (near_tie and (r[idx] >= r[order[num - 1]] - score_tol).all()):
            wrong_ids += 1
    return [
        Compared("served_score_gap_max", worst, score_tol),
        Compared("served_answers_malformed", float(malformed), 0.0),
        Compared("served_answers_not_reference_topk", float(wrong_ids), 0.0),
    ]
