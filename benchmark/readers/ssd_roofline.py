"""Share of its roofline the state space's sequential pass reached, in %: for
the ``ssd_chunk_fwd*`` / ``ssd_chunk_bwd*`` operations among the reducer's
longest (one instruction a call site of a kernel), the least time a correct
kernel needs for that site's calls over the site's device self time.

The least a correct kernel must do, per (row, layer, chunk of C tokens), with
H heads in G groups, P channels a head and a state of N, all float32:

    forward   O = C S;  S <- a S + B^T Xe
              FLOPs 4 C N P a head; bytes: Xe [C, P] read and O [C, P] written
              once a head, B and C [C, N] read once a group (the carried
              state never leaves the chip)
    backward  dXe = B dS;  dB = Xe dS^T;  dC = dO S^T;  dS <- a dS + C^T dO
              FLOPs 8 C N P a head; bytes: Xe and dO read, dXe written and the
              chunk's state [N, P] read once a head (the one thing the
              forward must have left), B and C read and dB and dC written
              once a group

Least time = max(FLOPs / the device's bf16 peak, bytes / its HBM peak): the
bf16 peak, though the kernel's products are float32, so the share errs low.
A call site (one instruction) is one layer's held heads, and runs once a
packed row (twice where the layer is recomputed: two call sites): ``rows`` =
the configured steps x rows a step."""

from benchmark.readers.device_op_prefix import matching


def site_least(kind: str, rows: int, heads: int, groups: int, tokens: int,
               chunk: int, p: int, n: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one call site's calls over a retrain."""
    chunks = rows * (tokens // chunk)
    c = chunk
    if kind == "fwd":
        flops = heads * 4 * c * n * p
        words = heads * 2 * c * p + groups * 2 * c * n
    else:
        flops = heads * 8 * c * n * p
        words = heads * (3 * c * p + n * p) + groups * 4 * c * n
    return float(chunks * flops), float(chunks * words * 4)


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
            "bwd" if "bwd" in name else "fwd", rows, cfg["mamba_n_heads"],
            cfg["mamba_n_groups"], prep["rowLen"], cfg["mamba_chunk_size"],
            cfg["mamba_d_head"], cfg["mamba_d_state"],
        )
        least_s += max(
            flops / peaks[kind]["bf16_flops_per_s"],
            nbytes / peaks[kind]["hbm_bytes_per_s"],
        )
        busy_s += seconds
    return 100.0 * least_s / busy_s if busy_s > 0 else None
