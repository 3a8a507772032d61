"""The SmallThinker cell at a size a CPU test can hold, added to a throwaway
copy of the benchmark's data files the way ``tiny.tiny_root`` makes it: hidden
64, 2 query heads on 1 KV head of 16, 4 of 16 experts of width 32 held with 4
a token, a window of 16, 128 vocabulary rows, one global and one sliding
layer, rows of 128 tokens (histories of up to 128: some pass the window)."""

from __future__ import annotations

import json
from pathlib import Path

from benchmark import run as harness
from benchmark.tests.tiny import tiny_root

CONFIG = "smallthinker-21b-ep4"
CELL = f"{CONFIG}.retrain"


def tiny_smallthinker_root(tmp: Path) -> tuple[dict, Path]:
    manifest, root = tiny_root(tmp)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    cfg = harness.load_json(harness.REPO / entry["file"])
    cfg.update({
        "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 16, "moe_num_primary_experts": 4,
        "moe_num_active_primary_experts": 4, "moe_ffn_hidden_size": 32,
        "sliding_window_size": 16, "vocab_size": 128,
    })
    cfg["share"]["published"]["moe_num_primary_experts"] = 16
    # a few long histories: segments longer and shorter than the window
    cfg["data"].update({"nnz": 900, "num_users": 24, "num_items": 100})
    prep = cfg["engine_json"]["preparator"]["params"]
    prep.update({"rowLen": 128, "maxLen": 128, "rowsPerStep": 2, "vocabSize": 128})
    algo = cfg["engine_json"]["algorithms"][0]["params"]
    algo.update({
        "hiddenSize": 64,
        "layerTypes": ["global_attention_moe", "sliding_attention_moe"],
        "numAttentionHeads": 2, "numKeyValueHeads": 1, "headDim": 16,
        "slidingWindowSize": 16, "moeNumPrimaryExperts": 16, "moeExpertsHeld": 4,
        "moeNumActivePrimaryExperts": 4, "moeFfnHiddenSize": 32, "vocabSize": 128,
        "rowsPerStep": 2, "stepsPerRetrain": 2,
    })
    cfg["reference"].update(TINY_LIMITS)
    (tmp / entry["file"]).write_text(json.dumps(cfg))
    return manifest, root


# the limits belong to a size.  Readings at this one (CPU): sound / the sliding
# layer without its window / pairs dropped at a capacity of 1.25:
# moe_probe_rel_gap 7.1e-8 / 7.1e-8 (the first layer is global) / 0.142,
# attention's probes 0.0046 / 0.552 / 0.0053, the experts' 0.039 / 0.144 /
# 0.188, the later step's 0.010 / 0.129 / 0.141, update_rel_l2_max 0.182 /
# 0.881 / 0.395, update_row_gap_max 1.11 / 1.79 / 1.27; the choices equal the
# replay's in both layers in all three
TINY_LIMITS = {
    "rows_checked": 16, "loss_step1_rel_gap_limit": 1e-3,
    "loss_later_steps_rel_gap_limit": 1e-3,
    "route_flip_share_first_layer_limit": 0.0, "route_flip_share_limit": 0.02,
    "moe_pairs_held_step1_rel_gap_limit": 0.03,
    "moe_pairs_held_rel_gap_limit": 0.03, "moe_probe_rel_gap_limit": 1e-3,
    "grad_norm_rel_gap_limit": 0.2, "grad_probe_gap_rms_limit": 0.1,
    "grad_probe_gap_experts_rms_limit": 0.1, "grad_probe_gap_router_rms_limit": 0.1,
    "grad_probe_gap_attention_rms_limit": 0.1,
    "grad_probe_gap_later_steps_rms_limit": 0.1,
    "update_rel_l2_max_limit": 0.3, "update_rel_l2_median_limit": 0.2,
    "update_rel_l2_experts_max_limit": 0.3, "update_row_gap_max_limit": 1.5,
}
