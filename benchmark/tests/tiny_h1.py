"""The Falcon-H1 cell at a size a CPU test can hold, added to a throwaway
copy of the benchmark's data files the way ``tiny.tiny_root`` makes it:
hidden 64, 4 query heads on 2 KV heads of 16, 4 state-space heads of 8 in 2
groups with a state of 16, 16 MLP columns, 128 vocabulary rows, 2 layers, rows
of 64 tokens in chunks of 16 (so the state is carried three times a row)."""

from __future__ import annotations

import json
from pathlib import Path

from benchmark import run as harness
from benchmark.tests.tiny import tiny_root

CONFIG = "falcon-h1-34b-tp4"
CELL = f"{CONFIG}.retrain"

#: muP's multipliers nearer 1 than the published ones: at hidden 64 and the
#: 0.02 initialisation those leave the mixers' outputs under float32's noise
MULTIPLIERS = {
    "embedding_multiplier": 5.65, "lm_head_multiplier": 0.25,
    "ssm_in_multiplier": 0.5, "ssm_multipliers": [0.7, 0.5, 0.35, 1.4, 0.8],
    "ssm_out_multiplier": 0.3, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.4, "key_multiplier": 0.6,
    "mlp_multipliers": [0.7, 0.2],
}


def _camel(key: str) -> str:
    head, *rest = key.split("_")
    return head + "".join(w.capitalize() for w in rest)


def tiny_h1_root(tmp: Path) -> tuple[dict, Path]:
    manifest, root = tiny_root(tmp)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    cfg = harness.load_json(harness.REPO / entry["file"])
    cfg.update({
        "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "mamba_n_heads": 4,
        "mamba_n_groups": 2, "mamba_d_head": 8, "mamba_d_state": 16,
        "mamba_chunk_size": 16, "vocab_size": 128, **MULTIPLIERS,
    })
    cfg["share"]["mlp_columns_held"] = 16
    # short histories: several segments share a packed row
    cfg["data"].update({"nnz": 700, "num_users": 32, "num_items": 100})
    prep = cfg["engine_json"]["preparator"]["params"]
    prep.update({"rowLen": 64, "maxLen": 64, "rowsPerStep": 2, "vocabSize": 128})
    algo = cfg["engine_json"]["algorithms"][0]["params"]
    algo.update({
        "hiddenSize": 64, "layerTypes": ["parallel_ssm_attention"] * 2,
        "numAttentionHeads": 4, "numKeyValueHeads": 2, "headDim": 16,
        "mambaNHeads": 4, "mambaNGroups": 2, "mambaDHead": 8, "mambaDState": 16,
        "mambaChunkSize": 16, "intermediateSize": 16, "vocabSize": 128,
        "rowsPerStep": 2, "stepsPerRetrain": 2,
        **{_camel(k): v for k, v in MULTIPLIERS.items()},
    })
    cfg["reference"].update(TINY_LIMITS)
    (tmp / entry["file"]).write_text(json.dumps(cfg))
    return manifest, root


# the limits belong to a size.  Readings at this one (CPU): sound / no reset at
# boundaries / bfloat16 state: later steps' probe rms 0.0055 / 0.203 / 0.0057,
# update_rel_l2_max 0.064 / 0.375 / 0.058, its decay tensors 0.011 / 0.375 /
# 0.008, ssd_probe_rel_gap 1.8e-7 / 1.8e-7 (the first step's rows are whole
# histories) / 8.0e-4 against the configuration's own 5e-4
TINY_LIMITS = {
    "rows_checked": 16, "loss_rel_gap_limit": 1e-3,
    "grad_norm_rel_gap_limit": 0.1, "grad_probe_gap_rms_limit": 0.05,
    "grad_probe_gap_mlp_rms_limit": 0.05, "grad_probe_gap_ssm_rms_limit": 0.05,
    "grad_probe_gap_attention_rms_limit": 0.05,
    "grad_probe_gap_later_steps_rms_limit": 0.05,
    "update_rel_l2_max_limit": 0.2, "update_rel_l2_median_limit": 0.15,
    "update_rel_l2_mlp_max_limit": 0.2, "update_rel_l2_decay_max_limit": 0.15,
    "update_row_gap_max_limit": 1.0,
}
