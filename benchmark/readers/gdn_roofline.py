"""Share of its roofline the delta rule's sequential pass reached, in %: for
the ``gdn_chunk_fwd*`` / ``gdn_chunk_bwd*`` operations among the reducer's
longest (one instruction a call site of a kernel), the least time a correct
kernel needs for that site's calls over the site's device self time.

The least a correct kernel must do, per (row, linear layer, head, chunk of C
tokens), with d_k / d_v the key / value sizes, all float32:

    forward   V' = U - W S;  O = Qg S + P V';  S <- a S + Kd^T V'
              FLOPs 2 C d_v (3 d_k + C); bytes: W, Qg, Kd [C, d_k], U [C, d_v],
              P [C, C] read, O [C, d_v] written (the carried state never
              leaves the chip)
    backward  nine products: FLOPs 2 C d_v (7 d_k + 2 C); bytes: the forward's
              inputs and dO read, dW, dQg, dKd, dU, dP written, and the
              chunk's state read (the one thing the forward must have left)

Least time = max(FLOPs / the device's bf16 peak, bytes / its HBM peak): the
bf16 peak, though the kernel's products are float32, so the share errs low.
A call site (one instruction) is one GROUP of a linear layer's heads, the
configuration's ``delta_rule_heads_per_call`` (5 of the 15 held: the layer
runs its heads in groups, ``ops/seqmodel.linear_attention``), and runs once a
packed row: ``rows`` = the configured steps x rows a step."""

from benchmark.readers.device_op_prefix import matching


def site_least(kind: str, rows: int, heads: int, tokens: int, chunk: int,
               dk: int, dv: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one call site's calls over a retrain."""
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


def read(evidence: dict, args: dict):
    ops = matching(evidence, args["prefix"])
    if not ops:
        return None
    kind = evidence["device"]["kind"]
    peaks = evidence["peaks"]["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    cfg = evidence["config"]
    algo = cfg["engine_json"]["algorithms"][0]["params"]
    prep = cfg["engine_json"]["preparator"]["params"]
    rows = algo["stepsPerRetrain"] * algo["rowsPerStep"]
    least_s = busy_s = 0.0
    for name, seconds in ops:
        flops, nbytes = site_least(
            "bwd" if "bwd" in name else "fwd", rows, cfg["delta_rule_heads_per_call"],
            prep["rowLen"], cfg["delta_rule_chunk"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
        )
        least_s += max(
            flops / peaks[kind]["bf16_flops_per_s"],
            nbytes / peaks[kind]["hbm_bytes_per_s"],
        )
        busy_s += seconds
    return 100.0 * least_s / busy_s if busy_s > 0 else None
