"""Share of their roofline the experts' grouped products reached, in %: for
the ``moe_gmm_*`` / ``moe_tgmm_*`` operations among the reducer's longest (one
instruction a call site of a kernel), the least time a correct kernel needs
for that site's calls over the site's device self time.

A call site is one product of one layer in one pass and runs once a packed
row.  With P the (token, expert) pairs the held experts computed in a layer
over the retrain (``stages["counters"]["moe_pairs_held"]`` over the layers: a
site's layer is not in its name, so the layers' mean), D the hidden size, F an
expert's width, E the experts held, the least a correct kernel must do:

    gate_up, gate_up_dlhs, tgmm_gate_up   2 P D 2F FLOPs
    down, down_dlhs, tgmm_down            2 P F D  FLOPs
    bytes   the pairs' rows read once in bfloat16, the result written once in
            float32, and the held experts' weights read (gmm, bfloat16) or
            written (tgmm, float32) once

Least time = max(FLOPs / the device's bf16 peak, bytes / its HBM peak).  A
program without these kernels (the parent's) has no such operation, one that
counts nothing no ``counters``: nothing to read."""

from benchmark.readers.device_op_prefix import matching


def site_least(name: str, pairs: float, d: int, f: int, held: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one call site's calls over a retrain."""
    wide = 2 * f if "gate_up" in name else f
    flops = 2.0 * pairs * d * wide
    if "tgmm" in name:  # pairs^T x pairs -> the weights' gradient, float32
        return flops, float(pairs * (d + wide) * 2 + held * d * wide * 4)
    # forward: gate_up reads rows of D, down rows of F; a dlhs the other way
    reads_d = ("gate_up" in name) != ("dlhs" in name)
    rows_in, rows_out = (d, wide) if reads_d else (wide, d)
    return flops, float(pairs * (rows_in * 2 + rows_out * 4) + held * d * wide * 2)


def read(evidence: dict, args: dict):
    ops = matching(evidence, args["prefix"])
    stages = (evidence.get("retrain") or {}).get("stages") or {}
    counters = stages.get("counters")
    if not ops or not counters:
        return None
    kind = evidence["device"]["kind"]
    peaks = evidence["peaks"]["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    cfg = evidence["config"]
    pairs = counters["moe_pairs_held"] / cfg["num_hidden_layers"]
    least_s = busy_s = 0.0
    for name, seconds in ops:
        flops, nbytes = site_least(
            name, pairs, cfg["hidden_size"], cfg["moe_ffn_hidden_size"],
            cfg["moe_num_primary_experts"])
        least_s += max(
            flops / peaks[kind]["bf16_flops_per_s"],
            nbytes / peaks[kind]["hbm_bytes_per_s"])
        busy_s += seconds
    return 100.0 * least_s / busy_s if busy_s > 0 else None
