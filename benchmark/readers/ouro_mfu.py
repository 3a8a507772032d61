"""Model FLOPs utilisation of a traced retrain of a LOOPED block, in %: the
operations the configured optimiser steps REQUIRE (forward + backward =
3 x forward; recomputation not counted) over the device's bf16 peak, over the
device's busy seconds in the traced retrain.

A looped model uses a layer's parameters once a PASS, so the count goes by
layer applications, not by parameters held.  Forward FLOPs, from the
configuration's widths and the retrain's own counters (``stages.counters``:
``loop_tokens`` real tokens went through ``loop_layer_applications / rows``
layer applications each, their attention over ``loop_attention_pairs``
(query, key) pairs within the histories):

    a token and layer application   2 x (q, k, v, o and the MLP's parameters)
    a pair and layer application    2 x 2 x query heads x head_dim
                                    (scores and values)
    a token and pass                2 x vocabulary x hidden (the one head,
                                    after every pass) + 2 x hidden (the gate)

Busy time holds everything the device ran in the retrain (initialisation,
AdamW and the fetch's copies too), so the share is of the whole retrain's
device time.  A configuration that is not looped, or a program that counts
none of this (the parent's), gives nothing to read."""


def forward_flops(cfg: dict, tokens: float, pairs: float, applications: float) -> float:
    """``applications``: layer applications a row (passes x layers)."""
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    A, KV, F = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["intermediate_size"])
    layer = 2.0 * (D * hd * (2 * A + 2 * KV) + 3 * D * F)
    attention = 2.0 * 2 * A * hd
    exits = cfg["total_ut_steps"] * (2.0 * cfg["vocab_size"] * D + 2.0 * D)
    return tokens * (applications * layer + exits) + pairs * applications * attention


def read(evidence: dict, args: dict):
    trace = evidence.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    cfg = evidence["config"]
    stages = (evidence.get("retrain") or {}).get("stages") or {}
    counters = stages.get("counters") or {}
    if "total_ut_steps" not in cfg or "loop_layer_applications" not in counters:
        return None
    kind = evidence["device"]["kind"]
    peaks = evidence["peaks"]["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    algo = cfg["engine_json"]["algorithms"][0]["params"]
    rows = algo["stepsPerRetrain"] * algo["rowsPerStep"]
    flops = 3.0 * forward_flops(
        cfg, counters["loop_tokens"], counters["loop_attention_pairs"],
        counters["loop_layer_applications"] / rows)
    return 100.0 * flops / peaks[kind]["bf16_flops_per_s"] / trace["busy_s"]
