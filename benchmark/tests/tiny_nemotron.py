"""The Nemotron-H cell at a size a CPU test can hold, added to a throwaway
copy of the benchmark's data files the way ``tiny.tiny_root`` makes it: hidden
64, the pattern ``MEM*E``, 4 state-space heads of 8 in 2 groups with a state
of 16 in chunks of 16, 4 query heads on 2 KV heads of 16, 4 of 16 experts of
width 24 held with 3 a token beside 40 shared columns, 128 vocabulary rows,
rows of 128 tokens packed from several histories."""

from __future__ import annotations

import json
from pathlib import Path

from benchmark import run as harness
from benchmark.tests.tiny import tiny_root

CONFIG = "nemotron3-nano-30b-ep8"
CELL = f"{CONFIG}.retrain"
KINDS = {"M": "state_space", "*": "grouped_attention", "E": "shared_routed_experts"}


def tiny_nemotron_root(tmp: Path) -> tuple[dict, Path]:
    manifest, root = tiny_root(tmp)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    cfg = harness.load_json(harness.REPO / entry["file"])
    cfg.update({
        "hidden_size": 64, "num_hidden_layers": 5, "hybrid_override_pattern": "MEM*E",
        "mamba_num_heads": 4, "n_groups": 2, "mamba_head_dim": 8, "ssm_state_size": 16,
        "chunk_size": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "n_routed_experts": 4, "num_experts_per_tok": 3,
        "moe_intermediate_size": 24, "vocab_size": 128,
    })
    cfg["share"]["published"]["n_routed_experts"] = 16
    cfg["share"]["shared_expert_columns_held"] = 40
    cfg["data"].update({"nnz": 900, "num_users": 24, "num_items": 100})
    prep = cfg["engine_json"]["preparator"]["params"]
    prep.update({"rowLen": 128, "maxLen": 128, "rowsPerStep": 2, "vocabSize": 128})
    algo = cfg["engine_json"]["algorithms"][0]["params"]
    algo.update({
        "hiddenSize": 64, "layerTypes": [KINDS[c] for c in "MEM*E"],
        "numAttentionHeads": 4, "numKeyValueHeads": 2, "headDim": 16,
        "mambaNHeads": 4, "mambaNGroups": 2, "mambaDHead": 8, "mambaDState": 16,
        "mambaChunkSize": 16, "moeNumPrimaryExperts": 16, "moeExpertsHeld": 4,
        "moeNumActivePrimaryExperts": 3, "moeFfnHiddenSize": 24,
        "moeSharedExpertColumns": 40, "vocabSize": 128, "rowsPerStep": 2,
        "stepsPerRetrain": 2,
    })
    cfg["reference"].update(TINY_LIMITS)
    (tmp / entry["file"]).write_text(json.dumps(cfg))
    return manifest, root


# the limits belong to a size: at hidden 64 a route flips on a rounding and
# moves a whole row of a tiny expert's gradient, so these are loose where the
# configuration's own are tight; the probes (exact inputs on both sides) and
# the exact items are what this rehearsal holds tightly
TINY_LIMITS = {
    "rows_checked": 16, "clear_margin": 0.02, "loss_step1_rel_gap_limit": 1e-3,
    "loss_later_steps_rel_gap_limit": 2e-3,
    "route_flip_share_first_layer_limit": 0.05, "route_flip_share_limit": 0.05,
    "route_flip_clear_share_limit": 0.0,
    "moe_pairs_held_step1_rel_gap_limit": 0.1, "moe_pairs_held_rel_gap_limit": 0.2,
    "moe_probe_rel_gap_limit": 2e-3, "moe_grad_probe_rel_gap_limit": 1e-3,
    "ssd_probe_rel_gap_limit": 5e-4,
    "grad_norm_rel_gap_limit": 0.3, "grad_probe_gap_rms_limit": 0.3,
    "grad_probe_gap_experts_rms_limit": 0.3, "grad_probe_gap_shared_rms_limit": 0.3,
    "grad_probe_gap_router_rms_limit": 0.4, "grad_probe_gap_ssm_rms_limit": 0.3,
    "grad_probe_gap_attention_rms_limit": 0.3,
    "grad_probe_gap_later_steps_rms_limit": 0.4,
    "update_rel_l2_max_limit": 0.8, "update_rel_l2_median_limit": 0.4,
    "update_rel_l2_experts_max_limit": 0.8, "update_rel_l2_decay_max_limit": 0.5,
}
