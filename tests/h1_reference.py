"""Tier-1's twin of the benchmark's plain reference for the Falcon-H1 block:
the SAME functions (``benchmark/references/falcon_h1.py``, loaded by path as
``seq_reference`` loads the Olmo one), the tiny sizes the CPU tests run at,
and how the whole tiny model is cut into four chips' shares."""

from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

_PATH = Path(__file__).resolve().parents[1] / "benchmark" / "references" / "falcon_h1.py"
_spec = importlib.util.spec_from_file_location("falcon_h1_reference", _PATH)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

#: muP's multipliers, as uneven as the published ones
MULTIPLIERS = {
    "embedding_multiplier": 5.65, "lm_head_multiplier": 0.25,
    "ssm_in_multiplier": 0.5, "ssm_multipliers": [0.7, 0.5, 0.35, 1.4, 0.8],
    "ssm_out_multiplier": 0.3, "attention_in_multiplier": 0.9,
    "attention_out_multiplier": 0.4, "key_multiplier": 0.6,
    "mlp_multipliers": [0.7, 0.2],
}

#: the whole tiny model (8 query heads on 4 KV heads, 8 state-space heads in 2
#: groups, 64 MLP columns, 128 items) ...
WHOLE = {
    "hidden_size": 64, "num_layers": 2, "head_dim": 16, "rope_theta": 1e4,
    "rms_norm_eps": 1e-5, "attention_heads_held": 8, "kv_heads_held": 4,
    "ssm_heads_held": 8, "ssm_groups_held": 2, "mamba_d_head": 8,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mlp_columns_held": 64,
    "vocab_rows_held": 128, "vocab_start": 0, **MULTIPLIERS,
}
#: ... and one of four chips' share of it: 2 query heads on 1 KV head, 2 of a
#: group's 4 state-space heads with the group's B and C whole, a quarter of
#: the columns and rows
SHARE = {**WHOLE, "attention_heads_held": 2, "kv_heads_held": 1,
         "ssm_heads_held": 2, "ssm_groups_held": 1, "mlp_columns_held": 16,
         "vocab_rows_held": 32}
CHIPS = 4


def seq_config(m: dict, **kw):
    """The program's ``SeqConfig`` for a reference model group."""
    from predictionio_tpu.ops.seqmodel import PARALLEL, MuP, SeqConfig

    gate, down = m["mlp_multipliers"]
    return SeqConfig(
        hidden=m["hidden_size"], layer_types=(PARALLEL,) * m["num_layers"],
        heads=m["attention_heads_held"], head_dim=m["head_dim"],
        lin_heads=0, lin_key_dim=0, lin_value_dim=0, conv_width=4,
        mlp_cols=m["mlp_columns_held"], vocab_rows=m["vocab_rows_held"],
        vocab_start=m["vocab_start"], eps=m["rms_norm_eps"],
        kv_heads=m["kv_heads_held"], rope_theta=m["rope_theta"],
        ssm_heads=m["ssm_heads_held"], ssm_head_dim=m["mamba_d_head"],
        ssm_state=m["mamba_d_state"], ssm_groups=m["ssm_groups_held"],
        ssm_conv_width=m["mamba_d_conv"],
        mup=MuP(
            embedding=m["embedding_multiplier"], lm_head=m["lm_head_multiplier"],
            ssm_in=m["ssm_in_multiplier"], ssm_zones=tuple(m["ssm_multipliers"]),
            ssm_out=m["ssm_out_multiplier"],
            attention_in=m["attention_in_multiplier"],
            attention_out=m["attention_out_multiplier"], key=m["key_multiplier"],
            mlp_gate=gate, mlp_down=down),
        **{"ssm_chunk": 8, "loss_block": 32, **kw},
    )


def random_weights(m: dict, seed: int, gain: float = 1.2) -> dict:
    """Weights large enough that every path matters: matrices normal with
    standard deviation ``gain / sqrt(rows)``, norm weights and D 1 +- 0.2."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    w = {k: np.asarray(v) for k, v in reference.initial_weights(m, seed).items()}
    for name, v in w.items():
        leaf = name.split(".")[-1]
        if v.ndim == 2 and "conv" not in leaf:
            w[name] = (
                gain * rng.standard_normal(v.shape) / np.sqrt(v.shape[0])
            ).astype(np.float32)
        elif leaf.endswith("norm") or leaf == "ssm_d":
            w[name] = (1.0 + 0.2 * rng.standard_normal(v.shape)).astype(np.float32)
    return {k: jnp.asarray(v) for k, v in w.items()}


def _zone_columns(whole: dict, share: dict, chip: int, zones: tuple) -> np.ndarray:
    """The columns chip ``chip`` holds of a tensor whose last axis is laid out
    in ``zones`` (of ``"heads"``: split by state-space head; ``"groups"``:
    the chip's group whole; ``"dt"``: one column a head)."""
    H, P, G, N = (whole["ssm_heads_held"], whole["mamba_d_head"],
                  whole["ssm_groups_held"], whole["mamba_d_state"])
    h = share["ssm_heads_held"]
    group = chip * h // (H // G)
    cols, at = [], 0
    for zone in zones:
        if zone == "heads":
            cols.append(at + np.arange(chip * h * P, (chip + 1) * h * P))
            at += H * P
        elif zone == "groups":
            cols.append(at + np.arange(group * N, (group + 1) * N))
            at += G * N
        else:
            cols.append(at + np.arange(chip * h, (chip + 1) * h))
            at += H
    return np.concatenate(cols)


def share_of(w_whole: dict, chip: int, whole: dict = WHOLE, share: dict = SHARE) -> dict:
    """Chip ``chip``'s slices of the whole tiny model's tensors."""
    from predictionio_tpu.ops import seqmodel

    proj = _zone_columns(whole, share, chip, ("heads", "heads", "groups", "groups", "dt"))
    conv = _zone_columns(whole, share, chip, ("heads", "groups", "groups"))
    out = {}
    for name, shape in seqmodel.param_shapes(seq_config(share)).items():
        t, leaf = w_whole[name], name.split(".")[-1]
        if leaf == "ssm_in":
            out[name] = t[:, proj]
        elif leaf in ("ssm_conv", "ssm_conv_bias"):
            out[name] = t[..., conv]
        else:
            index = tuple(
                slice(chip * h, (chip + 1) * h) if h != f else slice(None)
                for h, f in zip(shape, t.shape))
            out[name] = t[index]
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
