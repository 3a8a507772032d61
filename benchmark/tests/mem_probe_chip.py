#!/usr/bin/env python3
"""What the device's allocator statistics count, on the chip: (1) a jitted
program with a known 4 GiB temporary, (2) ``ops.als.train_als`` at given sizes;
for each, ``memory_stats()`` beside the compiled program's ``memory_analysis()``.

    chiprun --timeout 900 -- python3 benchmark/tests/mem_probe_chip.py \
        nnz:users:items:rank:iterations [...]
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
os.environ.setdefault("JAX_PLATFORMS", "tpu")

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def stats():
    return {k: v for k, v in (jax.devices()[0].memory_stats() or {}).items()}


def gib(x):
    return round(x / 2**30, 3)


def known_temp():
    x = jnp.ones((32768, 8192), jnp.float32)
    w = jnp.ones((8192, 32768), jnp.float32)
    w2 = jnp.ones((32768, 128), jnp.float32)

    @jax.jit
    def f(x, w, w2):
        return (x @ w) @ w2

    before = stats()
    c = f.lower(x, w, w2).compile()
    m = c.memory_analysis()
    jax.block_until_ready(f(x, w, w2))
    after = stats()
    print(json.dumps({"probe": "known_temp", "before": before, "after": after,
                      "analysis": {"args": m.argument_size_in_bytes,
                                   "temp": m.temp_size_in_bytes,
                                   "out": m.output_size_in_bytes}}), flush=True)
    print("known_temp: peak_bytes_in_use GiB", gib(after.get("peak_bytes_in_use", 0)),
          "args+temp+out GiB", gib(m.argument_size_in_bytes + m.temp_size_in_bytes
                                   + m.output_size_in_bytes), flush=True)
    del x, w, w2


def als_case(spec):
    from predictionio_tpu.ops import als, als_pallas

    nnz, nu, ni, rank, iters = (int(v) for v in spec.split(":"))
    rng = np.random.default_rng(7)
    uw = rng.lognormal(0.0, 1.0, nu)
    ucdf = np.cumsum(uw / uw.sum())
    ip = (np.arange(ni) + 10.0) ** -0.8
    icdf = np.cumsum(ip / ip.sum())
    u = np.minimum(np.searchsorted(ucdf, rng.random(nnz)), nu - 1).astype(np.int32)
    i = np.minimum(np.searchsorted(icdf, rng.random(nnz)), ni - 1).astype(np.int32)
    r = rng.integers(1, 11, nnz).astype(np.float32) / 2
    als._STAGE_CACHE.clear()
    als._STEP_CACHE.clear()
    before = stats()
    t0 = time.perf_counter()
    p = als.ALSParams(rank=rank, num_iterations=iters)
    try:
        state = als.train_als(u, i, r, num_users=nu, num_items=ni, params=p)
        jax.block_until_ready((state.user_factors, state.item_factors))
        err = None
    except Exception as e:  # noqa: BLE001
        err = f"{type(e).__name__}: {str(e)[:600]}"
    wall = time.perf_counter() - t0
    after = stats()
    t1 = time.perf_counter()
    if err is None:  # staged and compiled: the device loop alone
        state = als.train_als(u, i, r, num_users=nu, num_items=ni, params=p)
        jax.block_until_ready((state.user_factors, state.item_factors))
    again = time.perf_counter() - t1
    info = dict(als.LAST_PLAN_INFO)
    row = {"probe": "als", "spec": spec, "wall_s": wall, "again_s": again, "error": err,
           "plan": info, "before": before, "after": after}
    # the compiled step's own account of its memory
    try:
        staged = next(iter(als._STAGE_CACHE.values()))
        (up, u_plan, u_oth, u_rat, u_val), (ipl, i_plan, i_oth, i_rat, i_val) = staged
        fused = info.get("mode") == "fused"
        key = next(k for k in als._STEP_CACHE if k[0] == "pallas")
        steps = als._STEP_CACHE[key]
        nup = max((nu + 127) // 128 * 128, 128)
        nip = max((ni + 127) // 128 * 128, 128)
        U = jnp.zeros((nup, rank), jnp.float32)
        V = jnp.zeros((nip, rank), jnp.float32)
        c = steps.lower(u_plan, u_oth, u_rat, u_val, i_plan, i_oth, i_rat, i_val,
                        U, V, jnp.int32(iters)).compile()
        m = c.memory_analysis()
        row["analysis"] = {"args": m.argument_size_in_bytes,
                           "temp": m.temp_size_in_bytes,
                           "out": m.output_size_in_bytes, "fused": fused}
    except Exception as e:  # noqa: BLE001
        row["analysis_error"] = f"{type(e).__name__}: {str(e)[:300]}"
    print(json.dumps(row, default=str), flush=True)
    a = row.get("analysis") or {}
    print("als", spec, "wall_s", round(wall, 2), "again_s", round(again, 2), "mode", info.get("mode"),
          "stage_s", info.get("stage_s"), "peak_bytes_in_use GiB",
          gib(after.get("peak_bytes_in_use", 0)), "analysis args/temp GiB",
          gib(a.get("args", 0)), gib(a.get("temp", 0)), "error", err, flush=True)
    als._STAGE_CACHE.clear()


def main():
    print(json.dumps({"devices": [str(d) for d in jax.devices()],
                      "kind": jax.devices()[0].device_kind}), flush=True)
    known_temp()
    for spec in sys.argv[1:]:
        als_case(spec)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
