"""Model FLOPs utilisation of a traced retrain of the SmallThinker block, in
%: the operations the configured optimiser steps REQUIRE (forward + backward =
3 x forward; recomputation and the record's probe not counted) over the
device's bf16 peak, over the device's busy seconds in the traced retrain.

Forward FLOPs, from the configuration's held sizes and what the retrain
COUNTED (``stages["counters"]``, summed on the device beside the gradients):

    experts      2 x 3 x hidden x expert width a (token, expert) pair the held
                 experts computed: ``moe_pairs_held``, all steps and layers
    router       2 x hidden x the router's width a token and layer
    projections  2 x (parameters of q, k, v, o) a token and layer
    attention    2 x 2 x query heads x head_dim a (query, key) pair: a segment
                 of n tokens makes n (n + 1) / 2 pairs in a global layer and,
                 past the window W, W (W + 1) / 2 + (n - W) W in a sliding one
                 (the KV head serves its query heads: one head's work a QUERY
                 head); the segments are the configuration's own histories
                 (who rated what comes from ``data.structure_seed``, not from
                 the run's seed), those of the rows the configured steps train
    head         2 x vocabulary rows held x hidden a token

Tokens are real tokens (``moe_pairs_total`` / experts a token / layers).  Busy
time holds everything the device ran in the retrain (initialisation, AdamW and
the fetch's copies too), so the share is of the whole retrain's device time.
A configuration of another block has no experts, a program that counts nothing
no ``counters``: there is nothing to read."""

import numpy as np


def pairs_of_segment(n: int, window: int | None) -> int:
    """(query, key) pairs of a causal segment of n tokens."""
    if window is None or n <= window:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def trained_segments(cfg: dict) -> list:
    """Lengths of the histories in the rows the configured steps train: the
    configuration's who-rated-what, cut to ``maxLen`` and packed first-fit
    decreasing into rows of ``rowLen`` as the Preparator packs them."""
    from benchmark import datagen
    from benchmark.references.olmo_hybrid import rows_of

    data = cfg["data"]
    algo = cfg["engine_json"]["algorithms"][0]["params"]
    prep = cfg["engine_json"]["preparator"]["params"]
    users, _, _ = datagen.make_movielens_like(
        data["nnz"], data["num_users"], data["num_items"], 0, data["structure_seed"])
    _, first, counts = np.unique(users, return_index=True, return_counts=True)
    lengths = np.minimum(counts[np.argsort(first, kind="stable")], prep["maxLen"])
    rows = rows_of(lengths.tolist(), prep["rowLen"])
    rows = rows[: algo["stepsPerRetrain"] * algo["rowsPerStep"]]
    return [int(lengths[j]) for row in rows for j in row]


def forward_flops(cfg: dict, counters: dict, segments: list) -> float:
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    A, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n_layers = cfg["num_hidden_layers"]
    k = cfg["moe_num_active_primary_experts"]
    tokens = counters["moe_pairs_total"] / k / n_layers
    flops = counters["moe_pairs_held"] * 2.0 * 3 * D * cfg["moe_ffn_hidden_size"]
    width = cfg["share"]["published"]["moe_num_primary_experts"]
    flops += n_layers * tokens * 2.0 * D * (width + hd * (2 * A + 2 * KV))
    for slides in cfg["sliding_window_layout"][:n_layers]:
        window = cfg["sliding_window_size"] if slides else None
        flops += 2.0 * 2 * A * hd * sum(pairs_of_segment(n, window) for n in segments)
    return flops + tokens * 2.0 * cfg["vocab_size"] * D


def read(evidence: dict, args: dict):
    trace = evidence.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    cfg = evidence["config"]
    stages = (evidence.get("retrain") or {}).get("stages") or {}
    counters = stages.get("counters")
    if "moe_ffn_hidden_size" not in cfg or not counters:
        return None
    kind = evidence["device"]["kind"]
    peaks = evidence["peaks"]["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    flops = 3.0 * forward_flops(cfg, counters, trained_segments(cfg))
    return 100.0 * flops / peaks[kind]["bf16_flops_per_s"] / trace["busy_s"]
