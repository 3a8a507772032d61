"""Tier-1's twin of the benchmark's plain reference for the SmallThinker block:
the SAME functions (``benchmark/references/smallthinker.py``, loaded by path as
``h1_reference`` loads the Falcon one), the tiny sizes the CPU tests run at,
and how the whole tiny model is cut into four chips' shares."""

from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

_PATH = Path(__file__).resolve().parents[1] / "benchmark" / "references" / "smallthinker.py"
_spec = importlib.util.spec_from_file_location("smallthinker_reference", _PATH)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

#: the whole tiny model (8 query heads on 4 KV heads of 16, 16 experts of width
#: 32 with 4 a token, a window of 16, 128 items; one global and one sliding
#: layer) ...
WHOLE = {
    "hidden_size": 64, "head_dim": 16, "rope_theta": 1.5e6, "rms_norm_eps": 1e-6,
    "attention_heads_held": 8, "kv_heads_held": 4, "experts": 16,
    "experts_held": 16, "expert_start": 0, "experts_per_token": 4,
    "expert_width": 32, "window": 16, "layer_kinds": ["global", "sliding"],
    "vocab_rows_held": 128, "vocab_start": 0,
}
#: ... and one of four chips' share of it: 2 query heads on 1 KV head, 4 of the
#: 16 experts (the router whole), a quarter of the rows
SHARE = {**WHOLE, "attention_heads_held": 2, "kv_heads_held": 1,
         "experts_held": 4, "vocab_rows_held": 32}
CHIPS = 4


def seq_config(m: dict, **kw):
    """The program's ``SeqConfig`` for a reference model group."""
    from predictionio_tpu.ops.seqmodel import GLOBAL_MOE, SLIDING_MOE, SeqConfig

    return SeqConfig(
        hidden=m["hidden_size"],
        layer_types=tuple(
            SLIDING_MOE if kind == "sliding" else GLOBAL_MOE
            for kind in m["layer_kinds"]),
        heads=m["attention_heads_held"], head_dim=m["head_dim"],
        lin_heads=0, lin_key_dim=0, lin_value_dim=0, conv_width=4, mlp_cols=0,
        vocab_rows=m["vocab_rows_held"], vocab_start=m["vocab_start"],
        eps=m["rms_norm_eps"], kv_heads=m["kv_heads_held"],
        rope_theta=m["rope_theta"], experts=m["experts"],
        experts_held=m["experts_held"], expert_start=m["expert_start"],
        experts_per_token=m["experts_per_token"], expert_width=m["expert_width"],
        window=m["window"], **{"moe_tile": 8, "loss_block": 32, **kw},
    )


def random_weights(m: dict, seed: int, gain: float = 1.2) -> dict:
    """Weights large enough that every path matters: matrices (the stacked
    experts' too) normal with standard deviation ``gain / sqrt(rows)``, norm
    weights 1 +- 0.2."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    w = {}
    for name, shape in reference.tensor_shapes(m).items():
        if name.endswith("norm"):
            w[name] = 1.0 + 0.2 * rng.standard_normal(shape)
        else:
            w[name] = gain * rng.standard_normal(shape) / np.sqrt(shape[-2])
    return {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}


def share_of(w_whole: dict, chip: int, share: dict = SHARE) -> dict:
    """Chip ``chip``'s slices of the whole tiny model's tensors: every axis
    the share holds less of, its ``chip``-th part; the router and the norms
    whole."""
    out = {}
    for name, shape in reference.tensor_shapes(share).items():
        t = w_whole[name]
        out[name] = t[tuple(
            slice(chip * h, (chip + 1) * h) if h != f else slice(None)
            for h, f in zip(shape, t.shape))]
    return out


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
