"""Share of its roofline the state space's sequential pass reached, in %
(``readers/roofline.py``): the least time of one forward and one backward of
every held head of every layer over every trained row, over ALL device self
time under ``args["scopes"]`` (the program's ``ssm.chunk``).

The least a correct pass must do, per (row, layer, chunk of C tokens), with
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

``site_least`` counts one call site of the program's kernels (one layer's
held heads, once a packed row: ``tests/test_h1_cell.py`` holds it to the
compiled shape); the reader counts every layer."""

from benchmark.readers import roofline


def site_least(kind: str, rows: int, heads: int, groups: int, tokens: int,
               chunk: int, p: int, n: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one pass of one layer over ``rows`` rows."""
    chunks = rows * (tokens // chunk)
    c = chunk
    if kind == "fwd":
        flops = heads * 4 * c * n * p
        words = heads * 2 * c * p + groups * 2 * c * n
    else:
        flops = heads * 8 * c * n * p
        words = heads * (3 * c * p + n * p) + groups * 4 * c * n
    return float(chunks * flops), float(chunks * words * 4)


def required(evidence: dict) -> list:
    cfg = evidence["config"]
    tokens = cfg["engine_json"]["preparator"]["params"]["rowLen"]
    return [
        site_least(kind, roofline.trained_rows(cfg), cfg["mamba_n_heads"],
                   cfg["mamba_n_groups"], tokens, cfg["mamba_chunk_size"],
                   cfg["mamba_d_head"], cfg["mamba_d_state"])
        for kind in ("fwd", "bwd")
    ] * cfg["num_hidden_layers"]


def read(evidence: dict, args: dict):
    return roofline.share_pct(evidence, args, required)
