"""A served ALS model without a storage daemon: the model builder
``tests/test_hostprofile.py`` deploys in-process, and the script
``tests/test_fleet.py::TestRouterTraceLane`` starts as a real serving
subprocess (run it from the repo root)."""

import numpy as np


def build_als_model(state, num_users, num_items):
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.recommendation.engine import ALSModel

    user_vocab = BiMap.from_keys(np.asarray([str(u) for u in range(num_users)]))
    item_vocab = BiMap.from_keys(np.asarray([str(i) for i in range(num_items)]))
    return ALSModel(
        user_factors=np.asarray(state.user_factors),
        item_factors=np.asarray(state.item_factors),
        user_vocab=user_vocab,
        item_vocab=item_vocab,
    )


_SERVER_SCRIPT = r"""
# Serving process: a FRESH interpreter pinned to cpu (ALS serves these
# waves from its host replica anyway).  Prints its port, serves until its
# stdin closes.
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import threading, types
import numpy as np
from tests.serving_fixture import build_als_model
from predictionio_tpu.core.base import FirstServing
from predictionio_tpu.models.recommendation.engine import ALSAlgorithm
from predictionio_tpu.server.aio import AsyncAppServer
from predictionio_tpu.server.prediction_server import (
    DeployedEngine, create_prediction_server_app,
)

blob = np.load(sys.argv[1])

class _State:
    user_factors = blob["U"]
    item_factors = blob["V"]

model = build_als_model(_State(), len(blob["U"]), len(blob["V"]))
deployed = DeployedEngine.__new__(DeployedEngine)
deployed._lock = threading.RLock()
deployed.instance = types.SimpleNamespace(id="bench")
deployed.storage = None
deployed.algorithms = [ALSAlgorithm()]
deployed.models = [model]
deployed.serving = FirstServing()
app = create_prediction_server_app(deployed, use_microbatch=True)
server = AsyncAppServer(app, "127.0.0.1", 0).start_background()
print(server.port, flush=True)
sys.stdin.readline()  # parent closes stdin to stop us
server.shutdown()
"""
