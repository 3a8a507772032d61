"""Tier-1's twin of the benchmark's plain reference for the sequence engine:
the SAME functions (``benchmark/references/olmo_hybrid.py``, loaded by path so
that tier-1 needs nothing else of ``benchmark/``), and the tiny sizes the CPU
tests run at."""

from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

_PATH = Path(__file__).resolve().parents[1] / "benchmark" / "references" / "olmo_hybrid.py"
_spec = importlib.util.spec_from_file_location("olmo_hybrid_reference", _PATH)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

LAYERS = ("linear_attention", "linear_attention", "linear_attention", "full_attention")

#: the whole tiny model (4 heads of each kind, 32 MLP columns, 128 items) ...
FULL = {
    "hidden_size": 64, "layer_types": list(LAYERS), "head_dim": 16,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rms_norm_eps": 1e-6, "attention_heads_held": 4, "linear_heads_held": 4,
    "mlp_columns_held": 32, "vocab_rows_held": 128, "vocab_start": 0,
}
#: ... and one of two chips' share of it (2 + 2 heads, half the columns and rows)
HALF = {**FULL, "attention_heads_held": 2, "linear_heads_held": 2,
        "mlp_columns_held": 16, "vocab_rows_held": 64}


def seq_config(m: dict, **kw):
    """The program's ``SeqConfig`` for a reference model group."""
    from predictionio_tpu.ops.seqmodel import SeqConfig

    return SeqConfig(
        hidden=m["hidden_size"], layer_types=tuple(m["layer_types"]),
        heads=m["attention_heads_held"], head_dim=m["head_dim"],
        lin_heads=m["linear_heads_held"], lin_key_dim=m["linear_key_head_dim"],
        lin_value_dim=m["linear_value_head_dim"],
        conv_width=m["linear_conv_kernel_dim"], mlp_cols=m["mlp_columns_held"],
        vocab_rows=m["vocab_rows_held"], vocab_start=m["vocab_start"],
        eps=m["rms_norm_eps"], neg_eigval=m["linear_allow_neg_eigval"],
        **{"chunk": 8, "loss_block": 32, **kw},
    )


def random_weights(m: dict, seed: int, gain: float = 1.2) -> dict:
    """Weights large enough that every path matters (the 0.02 of the seeded
    initialisation leaves the first layer's projections near zero): matrices
    normal with standard deviation ``gain / sqrt(rows)``, norm weights 1 +-
    0.2."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    w = {k: np.asarray(v) for k, v in reference.initial_weights(m, seed).items()}
    for name, v in w.items():
        leaf = name.split(".")[-1]
        if v.ndim == 2 and not leaf.startswith("conv"):
            w[name] = (
                gain * rng.standard_normal(v.shape) / np.sqrt(v.shape[0])
            ).astype(np.float32)
        elif leaf.endswith("norm"):
            w[name] = (1.0 + 0.2 * rng.standard_normal(v.shape)).astype(np.float32)
    return {k: jnp.asarray(v) for k, v in w.items()}


def pack(segments: list, row_len: int):
    """Segments laid end to end in one row (tokens, segment ids), padded."""
    tok = np.zeros(row_len, np.int32)
    seg = np.full(row_len, -1, np.int32)
    at = 0
    for n, s in enumerate(segments):
        tok[at : at + len(s)] = s
        seg[at : at + len(s)] = n
        at += len(s)
    return tok, seg
