"""The Ouro cell at a size a CPU test can hold, added to a throwaway copy of
the benchmark's data files the way ``tiny.tiny_root`` makes it: hidden 64, 4
heads of 16, 48 MLP columns, 128 vocabulary rows, 2 layers run 4 times, rows
of 64 tokens."""

from __future__ import annotations

import json
from pathlib import Path

from benchmark import run as harness
from benchmark.tests.tiny import tiny_root

CONFIG = "ouro-2.6b-d8"
CELL = f"{CONFIG}.retrain"


def tiny_ouro_root(tmp: Path) -> tuple[dict, Path]:
    manifest, root = tiny_root(tmp)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    cfg = harness.load_json(harness.REPO / entry["file"])
    cfg.update({
        "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 48,
        "vocab_size": 128,
    })
    # short histories: several segments share a packed row
    cfg["data"].update({"nnz": 700, "num_users": 32, "num_items": 100})
    prep = cfg["engine_json"]["preparator"]["params"]
    prep.update({"rowLen": 64, "maxLen": 64, "rowsPerStep": 2, "vocabSize": 128})
    algo = cfg["engine_json"]["algorithms"][0]["params"]
    algo.update({
        "hiddenSize": 64, "layerTypes": ["sandwich_attention"] * 2,
        "numAttentionHeads": 4, "numKeyValueHeads": 4, "headDim": 16,
        "intermediateSize": 48, "vocabSize": 128, "rowsPerStep": 2,
        "stepsPerRetrain": 2,
    })
    cfg["reference"].update(TINY_LIMITS)
    (tmp / entry["file"]).write_text(json.dumps(cfg))
    return manifest, root


# the limits belong to a size.  Readings at this one (CPU) are beside
# ``test_ouro_cell.py``'s cases; the carried state's probe keeps the
# configuration's own limit
TINY_LIMITS = {
    "rows_checked": 16, "loss_rel_gap_limit": 1e-3,
    "loss_by_exit_rel_gap_limit": 1e-3, "exit_mass_gap_limit": 0.02,
    "exit_entropy_rel_gap_limit": 0.02,
    "grad_norm_rel_gap_limit": 0.15, "grad_probe_gap_rms_limit": 0.1,
    "grad_probe_gap_mlp_rms_limit": 0.1, "grad_probe_gap_attention_rms_limit": 0.1,
    "grad_probe_gap_exit_rms_limit": 0.1,
    "grad_probe_gap_later_steps_rms_limit": 0.1, "exit_probe_rel_gap_limit": 0.02,
    "update_rel_l2_max_limit": 0.3, "update_rel_l2_median_limit": 0.2,
    "update_rel_l2_mlp_max_limit": 0.3, "update_rel_l2_exit_max_limit": 0.3,
    "update_row_gap_max_limit": 1.6,
}
