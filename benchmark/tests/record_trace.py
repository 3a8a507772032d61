#!/usr/bin/env python3
"""Record a small profiler trace on whatever device JAX finds and print its
structure (planes, lines, the first events of each line).

This is how the fixture ``benchmark/tests/data/tiny_tpu.xplane.pb`` was made
(one call on the v5e), and how to look at a trace by hand before changing
``benchmark/trace_reduce.py``:

    chiprun -- python3 benchmark/tests/record_trace.py chiprun_out/trace_probe
"""

from __future__ import annotations

import glob
import json
import os
import sys
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                      "memory_stats": dev.memory_stats()}, default=str))

    @jax.jit
    def step(x):
        return jnp.tanh(x @ x) * 0.5

    x = jnp.ones((1024, 1024), jnp.float32)
    step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    os.makedirs(out_dir, exist_ok=True)
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    for _ in range(5):
        x = step(x)
        x.block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"), recursive=True)
    print("trace files", [(p, os.path.getsize(p)) for p in paths])
    data = jax.profiler.ProfileData.from_file(paths[0])
    for plane in data.planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for ev in events[:4]:
                print("     ", repr(ev.name), ev.start_ns, ev.duration_ns)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/trace_probe"))
