#!/usr/bin/env python3
"""What a row of the routed layer's pair buffer costs on the chip, whether a
pair lies on it or not, in one process (the builder's script; nothing here is
part of a run):

    chiprun --timeout 900 -- python3 benchmark/tests/micro_moe_rows_chip.py [parts]

at the two routed cells' shapes (``SHAPES``: tokens a row, hidden width,
expert width, the router's width, experts held, experts a token; tiles of 256
pairs), the plan made from seeded logits (the even load: 1.5 and 0.75 held
pairs a token).

    take     ``jnp.take`` of the buffer's rows from [N, D], bfloat16 and
             float32: the whole buffer under the plan's ``row_token`` (what
             the parent runs), the live rows alone, a buffer whose every row
             holds a pair and one on which none does: what a live row and a
             fill row cost
    layer    the routed layer forward and forward + backward as
             ``ops/moe.py`` runs it, at the even load and with every token
             choosing the same held experts (every window runs)
    window   the same with ``moe.WINDOW_TILES`` set to each of 4, 8, 16, 32
             and to the whole buffer (one window: every row, as the parent)
    maps     the same with the maps between the products (``moe._tile_maps``)
             in each of three forms: ``grid`` the tree's (a kernel on the
             products' grid of tiles), ``loop`` over the gathers' windows
             (``moe._live_windows``), ``whole`` the whole buffer's map (the
             parent's); and whether ``out`` and every gradient of ``loop``
             and ``whole`` are the grid form's bit for bit ON THE CHIP
    zeros    the same at the tree's window with the loops' buffers started
             as zeros (a broadcast over all ``plan_rows`` rows) in place of
             what the allocator hands out: what ``moe._buffer`` saves
    tiny     beside the others: small shapes, for a rehearsal off the chip

Results: stdout and ``chiprun_out/micro/moe_rows.jsonl``.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

TILE = 256
SHAPES = {
    "smallthinker-21b-ep4": dict(N=16384, D=2560, F=768, E=64, held=16, k=6, gated=True),
    "nemotron3-nano-30b-ep8": dict(N=8192, D=2688, F=1856, E=128, held=16, k=6, gated=False),
}


def main(argv):
    parts = argv or ["take", "layer"]
    shapes = SHAPES
    if "tiny" in parts:  # the rehearsal off the chip
        shapes = {c: {**s, "N": 512, "D": 128, "F": 128} for c, s in SHAPES.items()}
    import jax
    import jax.numpy as jnp

    from benchmark.tests.micro_sequence_chip import timed as best_of

    from predictionio_tpu.ops import moe
    from predictionio_tpu.utils.runtime import configure_compile_cache

    def timed(fn, *args):
        return best_of(fn, *args, repeat=5)[0]

    configure_compile_cache()
    out_dir = REPO / "chiprun_out" / "micro"
    out_dir.mkdir(parents=True, exist_ok=True)
    rows = open(out_dir / "moe_rows.jsonl", "a")
    device = jax.devices()[0]

    def emit(**row):
        row["device"] = device.device_kind
        print(json.dumps(row), flush=True)
        rows.write(json.dumps(row) + "\n")
        rows.flush()

    for cell, s in shapes.items():
        N, D, F, E, held, k = (s[x] for x in ("N", "D", "F", "E", "held", "k"))
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        m = jax.random.normal(ks[0], (N, D))
        logits = jax.random.normal(ks[1], (N, E))
        valid = jnp.ones((N,), bool)
        idx, w = moe.route(logits, k)
        plan = jax.jit(moe.make_plan, static_argnums=(2, 3, 4))(idx, valid, 0, held, TILE)
        R = int(plan.row_token.shape[0])
        live = int(plan.n_active[0]) * TILE
        pairs = int(plan.counts.sum())
        if "take" in parts:
            take = jax.jit(lambda x, i: jnp.take(x, i, axis=0, mode="fill", fill_value=0))
            every = jax.random.randint(ks[2], (R,), 0, N, jnp.int32)
            every = jnp.sort(every.reshape(held, -1), axis=1).reshape(-1)
            none = jnp.full((R,), N, jnp.int32)
            for dtype in (jnp.bfloat16, jnp.float32):
                x = m.astype(dtype)
                t = {
                    "whole_buffer_s": timed(take, x, plan.row_token),
                    "live_rows_s": timed(take, x, plan.row_token[:live]),
                    "every_row_a_pair_s": timed(take, x, every),
                    "no_row_a_pair_s": timed(take, x, none),
                }
                live_ns = 1e9 * t["live_rows_s"] / live
                fill_ns = 1e9 * (t["whole_buffer_s"] - t["live_rows_s"]) / (R - live)
                emit(part="take", cell=cell, dtype=jnp.dtype(dtype).name, plan_rows=R,
                     live_rows=live, pairs=pairs, **t, live_row_ns=live_ns,
                     fill_row_ns=fill_ns, fill_over_live=fill_ns / live_ns,
                     every_row_ns=1e9 * t["every_row_a_pair_s"] / R,
                     no_row_ns=1e9 * t["no_row_a_pair_s"] / R)
        if not {"layer", "window", "maps", "zeros"} & set(parts):
            continue
        gate = 0.02 * jax.random.normal(ks[3], (held, D, F)) if s["gated"] else None
        up = 0.02 * jax.random.normal(ks[4], (held, D, F))
        down = 0.02 * jax.random.normal(ks[5], (held, F, D))
        same = jnp.zeros((N, E)).at[:, :k].set(1.0) + 1e-3 * logits
        loads = {"even": logits, "every_token_the_same_experts": same}

        def measure(**tags):
            # a function of its own each time: ``moe.WINDOW_TILES`` is read as
            # the layer is traced, and a jitted function is traced once
            def layer(m, gate, up, down, logits):
                out, _, counts = moe.experts_layer(
                    m, logits, valid, gate, up, down, k=k, start=0, tile=TILE,
                    dtype=jnp.bfloat16)
                return out.sum(), counts

            fwd = jax.jit(layer)
            both = jax.jit(jax.value_and_grad(layer, argnums=(0, 2, 3, 4), has_aux=True))
            got = {}
            for load, lg in loads.items():
                counts = fwd(m, gate, up, down, lg)[1]
                tiles = int(jnp.maximum(-(-counts // TILE), 1).sum())
                emit(cell=cell, load=load, plan_rows=R, live_rows=tiles * TILE,
                     pairs=int(counts.sum()),
                     forward_s=timed(fwd, m, gate, up, down, lg),
                     forward_backward_s=timed(both, m, gate, up, down, lg), **tags)
                (out, _), grads = both(m, gate, up, down, lg)
                got[load] = jax.tree.leaves((out, grads))
            return got

        if "layer" in parts:
            measure(part="layer", window_tiles=getattr(moe, "WINDOW_TILES", None))
        if "maps" in parts:
            kept = moe._tile_maps
            forms = {
                "grid": kept,
                "loop": lambda plan, impl, fn, *ins, name: moe._live_windows(
                    plan, impl, fn, *ins),
                "whole": lambda plan, impl, fn, *ins, name: fn(*ins),
            }
            got = {}
            for form, maps in forms.items():
                moe._tile_maps = maps
                got[form] = measure(part="maps", maps=form, window_tiles=moe.WINDOW_TILES)
            moe._tile_maps = kept
            for form in ("loop", "whole"):
                for load in loads:
                    gaps = [float(jnp.max(jnp.abs(a - b)))
                            for a, b in zip(got["grid"][load], got[form][load])]
                    emit(part="maps_equal", cell=cell, load=load, against=form,
                         bit_for_bit=[g == 0.0 for g in gaps], max_abs_gap=gaps,
                         scale=[float(jnp.max(jnp.abs(a))) for a in got["grid"][load]])
        if "zeros" in parts:
            kept = moe._buffer
            moe._buffer = lambda shape, dtype, impl: jnp.zeros(shape, dtype)
            measure(part="zeros", window_tiles=moe.WINDOW_TILES)
            moe._buffer = kept
        if "window" in parts:
            kept = moe.WINDOW_TILES
            for tiles in (4, 8, 16, 32, R // TILE):
                moe.WINDOW_TILES = tiles
                measure(part="window", window_tiles=tiles)
            moe.WINDOW_TILES = kept
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
