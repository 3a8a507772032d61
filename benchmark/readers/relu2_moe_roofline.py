"""Share of their roofline the held two-matrix (relu^2) experts reached, in %
(``readers/roofline.py``): the least time of the six grouped products of every
(token, expert) pair the held experts computed, in every routed layer, over
ALL device self time under ``args["scopes"]`` (the program's ``moe.experts``:
the products, the activation between them and whatever the compiler copies
there).

With P the pairs the held experts computed in a routed layer over the retrain
(``stages["counters"]["moe_pairs_held"]`` over the routed layers: the layers'
mean, which sums to the count), D the hidden size, F an expert's width, E the
experts held, the least a correct product must do:

    up, up_dlhs, tgmm_up          2 P D F FLOPs
    down, down_dlhs, tgmm_down    2 P F D FLOPs
    bytes   the pairs' rows read once in bfloat16, the result written once in
            float32, and the held experts' weights read (gmm, bfloat16) or
            written (tgmm, float32) once

Two forward, four backward: one forward and one backward of every pair,
whatever F is a multiple of.  A program that counts nothing has no
``counters``, a configuration of another block no pattern: nothing to read.
``site_least`` takes a product by the name the program's kernels carry
(``tests/test_nemotron_cell.py`` holds it to their call shapes)."""

from benchmark.readers import roofline

#: forward, then the backward's two input gradients and two weight gradients
PRODUCTS = (
    "moe_gmm_up", "moe_gmm_down", "moe_gmm_down_dlhs", "moe_gmm_up_dlhs",
    "moe_tgmm_down", "moe_tgmm_up",
)


def site_least(name: str, pairs: float, d: int, f: int, held: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one product of one routed layer over a retrain."""
    flops = 2.0 * pairs * d * f
    if "tgmm" in name:  # pairs^T x pairs -> the weights' gradient, float32
        return flops, float(pairs * (d + f) * 2 + held * d * f * 4)
    # forward: up reads rows of D, down rows of F; a dlhs the other way
    reads_d = ("_up" in name) != ("dlhs" in name)
    rows_in, rows_out = (d, f) if reads_d else (f, d)
    return flops, float(pairs * (rows_in * 2 + rows_out * 4) + held * d * f * 2)


def required(evidence: dict) -> list:
    stages = (evidence.get("retrain") or {}).get("stages") or {}
    counters = stages.get("counters")
    cfg = evidence["config"]
    if not counters or "hybrid_override_pattern" not in cfg:
        return []
    layers = cfg["hybrid_override_pattern"][: cfg["num_hidden_layers"]].count("E")
    return [
        site_least(name, counters["moe_pairs_held"] / layers, cfg["hidden_size"],
                   cfg["moe_intermediate_size"], cfg["n_routed_experts"])
        for name in PRODUCTS
    ] * layers


def read(evidence: dict, args: dict):
    return roofline.share_pct(evidence, args, required)
