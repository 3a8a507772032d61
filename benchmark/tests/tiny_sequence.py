"""The sequence cell at a size a CPU test can hold, added to a throwaway copy
of the benchmark's data files the way ``tiny.tiny_root`` makes it (that file
may not be edited by a PR that only adds): hidden 64, 2 + 2 heads of 16 / 8 /
16, 16 MLP columns, 128 vocabulary rows, rows of 64 tokens."""

from __future__ import annotations

import json
from pathlib import Path

from benchmark import run as harness
from benchmark.tests.tiny import tiny_root

CONFIG = "olmo-hybrid-7b-tp2"
CELL = f"{CONFIG}.retrain"


def tiny_sequence_root(tmp: Path) -> tuple[dict, Path]:
    manifest, root = tiny_root(tmp)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    cfg = harness.load_json(harness.REPO / entry["file"])
    cfg.update({
        "hidden_size": 64, "num_attention_heads": 2, "num_key_value_heads": 2,
        "linear_num_key_heads": 2, "linear_num_value_heads": 2,
        "linear_key_head_dim": 8, "linear_value_head_dim": 16, "vocab_size": 128,
    })
    cfg["share"]["published"]["num_attention_heads"] = 4
    cfg["share"]["mlp_columns_held"] = 16
    # short histories: several segments share a packed row
    cfg["data"].update({"nnz": 700, "num_users": 32, "num_items": 100})
    prep = cfg["engine_json"]["preparator"]["params"]
    prep.update({"rowLen": 64, "maxLen": 64, "rowsPerStep": 2, "vocabSize": 128})
    algo = cfg["engine_json"]["algorithms"][0]["params"]
    algo.update({
        "hiddenSize": 64, "numAttentionHeads": 2, "headDim": 16,
        "linearNumHeads": 2, "linearKeyHeadDim": 8, "linearValueHeadDim": 16,
        "intermediateSize": 16, "vocabSize": 128, "rowsPerStep": 2,
        "stepsPerRetrain": 2,
    })
    # the limits belong to a size: at this one the sound program reads probe
    # gaps of 0.13 / 0.22 (step 1 / later) and update gaps of 0.31-0.47, and
    # without the reset at boundaries 0.51 and 0.85 (CPU)
    cfg["reference"].update({
        "rows_checked": 16, "loss_rel_gap_limit": 0.02,
        "grad_norm_rel_gap_limit": 1.0, "grad_probe_gap_rms_limit": 0.25,
        "grad_probe_gap_mlp_rms_limit": 0.2, "grad_probe_gap_mixer_rms_limit": 0.3,
        "grad_probe_gap_later_steps_rms_limit": 0.35,
        "update_rel_l2_max_limit": 0.65, "update_rel_l2_median_limit": 0.6,
        "update_rel_l2_mlp_max_limit": 0.8, "update_rel_l2_decay_max_limit": 0.65,
        "update_row_gap_max_limit": 2.5,
    })
    (tmp / entry["file"]).write_text(json.dumps(cfg))
    return manifest, root
