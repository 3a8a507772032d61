"""Test harness configuration.

Distributed logic is tested the way the reference tests Spark code with
``local[*]`` (SURVEY.md §4): a virtual 8-device CPU mesh via
``--xla_force_host_platform_device_count=8``.  Both are set by environment
before anything imports jax (pytest.ini disables the one plugin that would).
"""

import atexit
import os
import shutil
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# the CLI verbs (in-process and as children) keep a persistent compile cache:
# a test session's goes to a throwaway directory, not the checkout's
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="pio_test_jax_cache_"
    )
    atexit.register(
        shutil.rmtree, os.environ["JAX_COMPILATION_CACHE_DIR"], True
    )

import pytest


@pytest.fixture()
def storage(tmp_path):
    """A fresh isolated storage runtime rooted in a temp dir."""
    from predictionio_tpu.data.storage.config import (
        StorageConfig,
        reset_storage,
    )

    cfg = StorageConfig.from_env(
        {"PIO_HOME": str(tmp_path / "pio_home")}
    )
    rt = reset_storage(cfg)
    yield rt
    rt.close()


@pytest.fixture(autouse=True)
def _no_console_handler_on_a_closed_stream():
    """A ``pio`` verb run in-process leaves its console handler on the
    captured stderr of the test that ran it; once that capture is closed every
    later log line of the worker prints a traceback (milliseconds each, inside
    whatever span is open).  Drop such handlers before a test starts."""
    import logging

    root = logging.getLogger()
    for h in list(root.handlers):
        if getattr(getattr(h, "stream", None), "closed", False):
            root.removeHandler(h)
    yield


@pytest.fixture()
def pallas_on_cpu(monkeypatch):
    """The chip's train path under the Pallas interpreter: the steering a
    CPU test needs lives here, not in an option of the program."""
    import functools

    from predictionio_tpu.ops import als, als_pallas

    monkeypatch.setattr(als, "_use_pallas", lambda p: True)
    for kernel in ("segment_stats_fused", "segment_stats_pallas"):
        monkeypatch.setattr(
            als_pallas, kernel,
            functools.partial(getattr(als_pallas, kernel), interpret=True),
        )
    als._STEP_CACHE.clear()
    als._STAGE_CACHE.clear()
    yield
    als._STEP_CACHE.clear()
    als._STAGE_CACHE.clear()
