"""Model FLOPs utilisation of the traced retrain, in %: the operations the
configured optimiser steps REQUIRE (forward + backward = 3 x forward;
recomputation not counted) over the device's bf16 peak, over the device's busy
seconds in the traced retrain.

Forward FLOPs a token, from the configuration's held sizes:

    matmuls      2 x (parameters of every projection, of the MLP and of the
                 head; the embedding is a lookup)
    attention    per full layer 2 x 2 x heads x head_dim x (T + 1) / 2
                 (scores and values over the causal half of a row of T)
    delta rule   per linear layer and head: 2 d_v (3 d_k + C) in the
                 sequential pass and 2 (C d_k + C d_k + C (d_k + d_v)) / 1 in
                 the chunk's own products (K K^T, Q K^T, T [V | K]), a token

Busy time holds everything the device ran in the retrain (initialisation,
AdamW and the fetch's copies too), so the share is of the whole retrain's
device time."""


def forward_flops_per_token(cfg: dict, row_len: int) -> float:
    D = cfg["hidden_size"]
    H, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    A = cfg["num_attention_heads"]
    hd = D // cfg["share"]["published"]["num_attention_heads"]
    F = cfg["share"]["mlp_columns_held"]
    chunk = cfg["delta_rule_chunk"]
    total = 2.0 * cfg["vocab_size"] * D  # the head
    for kind in cfg["layer_types"]:
        total += 2.0 * 3 * D * F  # the MLP
        if kind == "linear_attention":
            total += 2.0 * (D * (2 * H * dk + 2 * H * dv + 2 * H) + H * dv * D)
            total += H * (
                2.0 * dv * (3 * dk + chunk)
                + 2.0 * chunk * (2 * dk + dk + dv)
            )
        else:
            total += 2.0 * 4 * D * A * hd
            total += 2.0 * 2 * A * hd * (row_len + 1) / 2
    return total


def read(evidence: dict, args: dict):
    trace = evidence.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    kind = evidence["device"]["kind"]
    peaks = evidence["peaks"]["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    cfg = evidence["config"]
    if "layer_types" not in cfg:
        return None
    algo = cfg["engine_json"]["algorithms"][0]["params"]
    prep = cfg["engine_json"]["preparator"]["params"]
    tokens = algo["stepsPerRetrain"] * algo["rowsPerStep"] * prep["rowLen"]
    flops = 3.0 * forward_flops_per_token(cfg, prep["rowLen"]) * tokens
    return 100.0 * flops / peaks[kind]["bf16_flops_per_s"] / trace["busy_s"]
