"""Model FLOPs utilisation of a traced retrain of the Nemotron-H stack, in %:
the operations the configured optimiser steps REQUIRE (forward + backward =
3 x forward; recomputation and the record's probes not counted) over the
device's bf16 peak, over the device's busy seconds in the traced retrain.

Forward FLOPs by KIND of layer, the kinds from the configuration's pattern
(``hybrid_override_pattern`` cut to ``num_hidden_layers``), the held sizes
from the configuration and what the retrain COUNTED (``stages["counters"]``,
summed on the device beside the gradients):

    M   projections   2 x (parameters of in_proj and out_proj) a token
        state space   the chunked form's products a token: 2 C N a group
                      (C B^T), 2 C P a head ((C B^T * L) X) and 4 N P a head
                      (the sequential pass's O = C S and S += B^T Xe), C the
                      chunk, P a head's channels, N the state
    *   projections   2 x (parameters of q, k, v, o) a token
        attention     2 x 2 x query heads x head_dim a (query, key) pair: a
                      segment of n tokens makes n (n + 1) / 2 pairs (the KV
                      head serves its query heads: one head's work a QUERY
                      head); the segments are the configuration's own
                      histories, those of the rows the configured steps train
    E   router        2 x hidden x the router's width a token
        shared        2 x 2 x hidden x the shared columns held a token
        experts       2 x 2 x hidden x expert width a (token, expert) pair the
                      held experts computed: ``moe_pairs_held``, all steps and
                      routed layers
    head              2 x vocabulary rows held x hidden a token

Tokens are real tokens (``moe_pairs_total`` / experts a token / routed
layers).  Busy time holds everything the device ran in the retrain
(initialisation, AdamW and the fetch's copies too), so the share is of the
whole retrain's device time.  A configuration of another block has no pattern,
a program that counts nothing no ``counters``: there is nothing to read."""

from benchmark.readers.st_mfu import pairs_of_segment, trained_segments


def layer_kinds(cfg: dict) -> str:
    return cfg["hybrid_override_pattern"][: cfg["num_hidden_layers"]]


def forward_flops_by_kind(cfg: dict, counters: dict, segments: list) -> dict:
    """-> {"M", "*", "E", "head"}: forward FLOPs of the whole retrain."""
    kinds = layer_kinds(cfg)
    D = cfg["hidden_size"]
    routed = kinds.count("E")
    tokens = counters["moe_pairs_total"] / cfg["num_experts_per_tok"] / routed
    H, P, G, N, C = (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
                     cfg["ssm_state_size"], cfg["chunk_size"])
    in_cols = 2 * H * P + 2 * G * N + H
    mamba = 2.0 * D * (in_cols + H * P) + G * 2 * C * N + H * (2 * C * P + 4 * N * P)
    A, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    attention = tokens * 2.0 * D * hd * (2 * A + 2 * KV) + 2.0 * 2 * A * hd * sum(
        pairs_of_segment(n, None) for n in segments)
    shared = cfg["share"]["shared_expert_columns_held"]
    width = cfg["share"]["published"]["n_routed_experts"]
    experts = routed * tokens * 2.0 * D * (width + 2 * shared) + counters[
        "moe_pairs_held"] * 2.0 * 2 * D * cfg["moe_intermediate_size"]
    return {
        "M": kinds.count("M") * tokens * mamba,
        "*": kinds.count("*") * attention,
        "E": experts,
        "head": tokens * 2.0 * cfg["vocab_size"] * D,
    }


def read(evidence: dict, args: dict):
    trace = evidence.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    cfg = evidence["config"]
    stages = (evidence.get("retrain") or {}).get("stages") or {}
    counters = stages.get("counters")
    if "hybrid_override_pattern" not in cfg or not counters or not counters.get(
            "moe_pairs_total"):
        return None
    kind = evidence["device"]["kind"]
    peaks = evidence["peaks"]["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    flops = 3.0 * sum(
        forward_flops_by_kind(cfg, counters, trained_segments(cfg)).values())
    return 100.0 * flops / peaks[kind]["bf16_flops_per_s"] / trace["busy_s"]
