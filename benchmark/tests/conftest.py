"""The benchmark's own tests: run by a builder (``python -m pytest
benchmark/tests -q``), not collected by the repository's tier-1 run
(``tests/``).  Children and in-process JAX are held to the CPU here."""

import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("BENCH_RUN", None)
