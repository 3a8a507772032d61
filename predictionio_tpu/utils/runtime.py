"""Process-level JAX runtime set-up shared by the `pio` verbs that compile
(train / deploy / batchpredict / eval) and the benchmark's chip checks
(``benchmark/tests/*_chip.py``): where compiled
programs are cached, which device the process actually got, and whether it
has touched one at all."""

from __future__ import annotations

import os
import sys
from pathlib import Path
from typing import Any


def default_compile_cache_dir() -> Path:
    """``<checkout>/.jax_cache`` (listed in .gitignore) when the package runs
    from its source tree; for an installed package the user's cache
    directory, not a folder inside ``site-packages``.  A fixed path either
    way: the directory is part of the cache key's world, so one that moves
    never hits."""
    root = Path(__file__).resolve().parents[2]
    if (root / "pyproject.toml").is_file():
        return root / ".jax_cache"
    base = os.environ.get("XDG_CACHE_HOME") or Path.home() / ".cache"
    return Path(base) / "predictionio_tpu" / "jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ONE directory and return
    it.  ``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads it itself; nothing is
    set in code); otherwise :func:`default_compile_cache_dir`.  Must run
    before the process's first compile — JAX decides whether it caches
    exactly once."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", str(default_compile_cache_dir())
        )
    # cache every program that took noticeable time to build, whatever its
    # size (the serving kernels are small and slow to compile)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return str(jax.config.jax_compilation_cache_dir)


def backend_initialized() -> bool:
    """Whether this process has initialized a JAX backend — on a chip host,
    whether it may hold the chip.  Observability asks this before it reads
    anything off ``jax.devices()``: a scrape of a process that merely
    imported jax (the event server, the dashboard) must not claim the chip
    the serving process needs, nor fail because there is none to claim."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge  # no public spelling of this question

    return xla_bridge.backends_are_initialized()


def describe_devices() -> dict[str, Any]:
    """What this process runs on, as JAX reports it.  Initializes the
    backend, so a process that cannot get its platform fails HERE (with
    ``JAX_PLATFORMS=tpu`` set, losing the chip is an error, not a silent
    CPU run)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
