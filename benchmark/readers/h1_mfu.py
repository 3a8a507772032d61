"""Model FLOPs utilisation of a traced retrain of the Falcon-H1 block, in %:
the operations the configured optimiser steps REQUIRE (forward + backward =
3 x forward; recomputation not counted) over the device's bf16 peak, over the
device's busy seconds in the traced retrain.

Forward FLOPs a token, from the configuration's held sizes:

    matmuls      2 x (parameters of in_proj, out_proj, q k v o, the MLP and
                 the head; the embedding is a lookup)
    attention    per layer 2 x 2 x query heads x head_dim x (T + 1) / 2
                 (scores and values over the causal half of a row of T; the
                 KV head is repeated for its query heads into the attention
                 kernel, which therefore does one head's work a QUERY head)
    state space  per layer, with chunk C, state N, head channels P: 4 N P a
                 head in the sequential pass (C S and B^T Xe), 2 C N a group
                 (C B^T) and 2 C P a head ((C B^T * L) X) in the chunk

Busy time holds everything the device ran in the retrain (initialisation,
AdamW and the fetch's copies too), so the share is of the whole retrain's
device time.  A configuration of another block has none of these keys: there
is nothing to read."""


def forward_flops_per_token(cfg: dict, row_len: int) -> float:
    D = cfg["hidden_size"]
    A, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    H, G = cfg["mamba_n_heads"], cfg["mamba_n_groups"]
    P, N, C = cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_chunk_size"]
    F = cfg["share"]["mlp_columns_held"]
    layer = 2.0 * D * (2 * H * P + 2 * G * N + H) + 2.0 * H * P * D  # in, out
    layer += 2.0 * D * hd * (2 * A + 2 * KV)  # q, o, k, v
    layer += 2.0 * 3 * D * F  # the MLP
    layer += 2.0 * 2 * A * hd * (row_len + 1) / 2  # attention
    layer += H * 4.0 * N * P + G * 2.0 * C * N + H * 2.0 * C * P  # state space
    return cfg["num_hidden_layers"] * layer + 2.0 * cfg["vocab_size"] * D


def read(evidence: dict, args: dict):
    trace = evidence.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    cfg = evidence["config"]
    if "mamba_n_heads" not in cfg:
        return None
    kind = evidence["device"]["kind"]
    peaks = evidence["peaks"]["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    algo = cfg["engine_json"]["algorithms"][0]["params"]
    prep = cfg["engine_json"]["preparator"]["params"]
    tokens = algo["stepsPerRetrain"] * algo["rowsPerStep"] * prep["rowLen"]
    flops = 3.0 * forward_flops_per_token(cfg, prep["rowLen"]) * tokens
    return 100.0 * flops / peaks[kind]["bf16_flops_per_s"] / trace["busy_s"]
