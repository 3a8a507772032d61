"""Share of its roofline the delta rule's sequential pass reached, in %
(``readers/roofline.py``): the least time of one forward and one backward of
every held head of every linear layer over every trained row, over ALL device
self time under ``args["scopes"]`` (the program's ``gdn.chunk``).

The least a correct pass must do, per (row, linear layer, head, chunk of C
tokens), with d_k / d_v the key / value sizes, all float32:

    forward   V' = U - W S;  O = Qg S + P V';  S <- a S + Kd^T V'
              FLOPs 2 C d_v (3 d_k + C); bytes: W, Qg, Kd [C, d_k], U [C, d_v],
              P [C, C] read, O [C, d_v] written (the carried state never
              leaves the chip)
    backward  nine products: FLOPs 2 C d_v (7 d_k + 2 C); bytes: the forward's
              inputs and dO read, dW, dQg, dKd, dU, dP written, and the
              chunk's state read (the one thing the forward must have left)

``site_least`` counts one call site of the program's kernels (one GROUP of a
layer's heads, the configuration's ``delta_rule_heads_per_call``, once a
packed row: ``tests/test_sequence_cell.py`` holds it to the compiled shape);
the layer's heads in any grouping do the same work, so the reader counts all
held heads of all linear layers at once."""

from benchmark.readers import roofline


def site_least(kind: str, rows: int, heads: int, tokens: int, chunk: int,
               dk: int, dv: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one pass of ``heads`` heads over ``rows`` rows."""
    calls = rows * heads * (tokens // chunk)
    c = chunk
    inputs = 3 * c * dk + c * dv + c * c
    if kind == "fwd":
        flops = 2 * c * dv * (3 * dk + c)
        words = inputs + c * dv
    else:
        flops = 2 * c * dv * (7 * dk + 2 * c)
        words = 2 * inputs + c * dv + dk * dv
    return float(calls * flops), float(calls * words * 4)


def required(evidence: dict) -> list:
    cfg = evidence["config"]
    heads = cfg["linear_num_value_heads"] * cfg["layer_types"].count("linear_attention")
    tokens = cfg["engine_json"]["preparator"]["params"]["rowLen"]
    return [
        site_least(kind, roofline.trained_rows(cfg), heads, tokens,
                   cfg["delta_rule_chunk"], cfg["linear_key_head_dim"],
                   cfg["linear_value_head_dim"])
        for kind in ("fwd", "bwd")
    ]


def read(evidence: dict, args: dict):
    return roofline.share_pct(evidence, args, required)
