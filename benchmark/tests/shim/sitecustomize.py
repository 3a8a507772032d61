"""Test-only: breaks the timed path underneath the harness, in the children
of ``test_broken_path.py`` (which put this directory on their PYTHONPATH and
say what to break in ``BENCH_TEST_BREAK``).  Without that variable it does
nothing.

    train   the retrain returns item factors 1 % off what the last half-step
            solved (a step whose state is not the one it computed)
    iterations  the retrain runs ONE iteration whatever the configuration says
            (part of the mathematics left out)
    serve   the ALS host replica's scores come out 1e-3 too high (an answer
            altered where it is produced)
"""

import os

_what = os.environ.get("BENCH_TEST_BREAK")

if _what == "train":
    import dataclasses

    from predictionio_tpu.models.recommendation import engine

    _train = engine.train_als

    def _stale(*args, **kwargs):
        state = _train(*args, **kwargs)
        return dataclasses.replace(state, item_factors=state.item_factors * 1.01)

    engine.train_als = _stale

elif _what == "iterations":
    import dataclasses

    from predictionio_tpu.models.recommendation import engine

    _train = engine.train_als

    def _cut_short(*args, params=None, **kwargs):
        return _train(
            *args, params=dataclasses.replace(params, num_iterations=1), **kwargs)

    engine.train_als = _cut_short

elif _what == "serve":
    from predictionio_tpu.models.recommendation import engine

    _topk = engine.ALSAlgorithm._host_topk_rows

    def _altered(self, model, rows, k):
        scores, items = _topk(self, model, rows, k)
        return scores + 1e-3, items

    engine.ALSAlgorithm._host_topk_rows = _altered
