"""Tier-1's twin of the benchmark's plain reference for the Nemotron-H stack:
the SAME functions (``benchmark/references/nemotron_h.py``, loaded by path as
``st_reference`` loads the SmallThinker one), the tiny sizes the CPU tests run
at, and how a whole tiny model is cut into eight chips' shares."""

from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

_PATH = Path(__file__).resolve().parents[1] / "benchmark" / "references" / "nemotron_h.py"
_spec = importlib.util.spec_from_file_location("nemotron_h_reference", _PATH)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

#: the tiny stack the program is held to the reference at: 4 state-space heads
#: of 8 in 2 groups with a state of 16, 4 query heads on 2 KV heads of 16, 8
#: of 16 experts held (from expert 4) of width 24, 3 a token, scale 2.5, a
#: shared expert of 40 columns, 512 items; the pattern ``MEM*E``
TINY = {
    "hidden_size": 64, "layer_norm_epsilon": 1e-5, "layer_kinds": list("MEM*E"),
    "head_dim": 16, "attention_heads_held": 4, "kv_heads_held": 2,
    "ssm_heads_held": 4, "ssm_groups_held": 2, "mamba_head_dim": 8,
    "ssm_state_size": 16, "conv_kernel": 4, "experts": 16, "experts_held": 8,
    "expert_start": 4, "experts_per_token": 3, "expert_width": 24,
    "shared_columns_held": 40, "routed_scaling_factor": 2.5,
    "vocab_rows_held": 512, "vocab_start": 0,
}
#: the published pattern's first nine layers, at the tiny widths
NINE = {**TINY, "layer_kinds": list("MEMEM*EME")}

#: the whole tiny model the share test cuts in eight (16 state-space heads in
#: 8 groups, 16 query heads on 8 KV heads, all 16 experts, 40 shared columns,
#: 512 rows) ...
WHOLE = {**TINY, "layer_kinds": list("ME*"), "attention_heads_held": 16,
         "kv_heads_held": 8, "ssm_heads_held": 16, "ssm_groups_held": 8,
         "experts_held": 16, "expert_start": 0}
#: ... and one of eight chips' share of it: one group's 2 heads, 2 query heads
#: on their KV head, 2 experts (the router and the bias whole), 5 of the
#: shared expert's columns, 64 rows
SHARE = {**WHOLE, "attention_heads_held": 2, "kv_heads_held": 1,
         "ssm_heads_held": 2, "ssm_groups_held": 1, "experts_held": 2,
         "shared_columns_held": 5, "vocab_rows_held": 64}
CHIPS = 8

KIND = {"M": "state_space", "*": "grouped_attention", "E": "shared_routed_experts"}


def seq_config(m: dict, **kw):
    """The program's ``SeqConfig`` for a reference model group."""
    from predictionio_tpu.ops.seqmodel import SeqConfig

    return SeqConfig(
        hidden=m["hidden_size"],
        layer_types=tuple(KIND[k] for k in m["layer_kinds"]),
        heads=m["attention_heads_held"], head_dim=m["head_dim"],
        lin_heads=0, lin_key_dim=0, lin_value_dim=0, conv_width=4, mlp_cols=0,
        vocab_rows=m["vocab_rows_held"], vocab_start=m["vocab_start"],
        eps=m["layer_norm_epsilon"], kv_heads=m["kv_heads_held"],
        ssm_heads=m["ssm_heads_held"], ssm_head_dim=m["mamba_head_dim"],
        ssm_state=m["ssm_state_size"], ssm_groups=m["ssm_groups_held"],
        ssm_conv_width=m["conv_kernel"], experts=m["experts"],
        experts_held=m["experts_held"], expert_start=m["expert_start"],
        experts_per_token=m["experts_per_token"], expert_width=m["expert_width"],
        shared_cols=m["shared_columns_held"],
        routed_scale=m["routed_scaling_factor"],
        **{"ssm_chunk": 8, "moe_tile": 8, "loss_block": 32, **kw},
    )


def random_weights(m: dict, seed: int, gain: float = 1.2) -> dict:
    """Weights large enough that every path matters: matrices (the stacked
    experts' too) normal with standard deviation ``gain / sqrt(rows)``, norm
    weights and ``D`` 1 +- 0.2, a selection bias of +- 0.1 (enough to move a
    choice); the decays' parameters and the convolution as initialised."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    w = {k: np.asarray(v) for k, v in reference.initial_weights(m, seed).items()}
    for name, v in w.items():
        leaf = name.split(".")[-1]
        if leaf == "router_bias":
            w[name] = (0.1 * rng.standard_normal(v.shape)).astype(np.float32)
        elif v.ndim >= 2 and "conv" not in leaf:
            w[name] = (
                gain * rng.standard_normal(v.shape) / np.sqrt(v.shape[-2])
            ).astype(np.float32)
        elif leaf.endswith("norm") or leaf == "ssm_d":
            w[name] = (1.0 + 0.2 * rng.standard_normal(v.shape)).astype(np.float32)
    return {k: jnp.asarray(v) for k, v in w.items()}


def _zone_columns(whole: dict, share: dict, chip: int, zones: tuple) -> np.ndarray:
    """The columns chip ``chip`` holds of a tensor whose last axis is laid out
    in ``zones`` (``"heads"``: split by state-space head; ``"groups"``: the
    chip's group whole; ``"dt"``: one column a head)."""
    H, P, G, N = (whole["ssm_heads_held"], whole["mamba_head_dim"],
                  whole["ssm_groups_held"], whole["ssm_state_size"])
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
    """Chip ``chip``'s slices of the whole tiny model's tensors: every axis
    the share holds less of, its ``chip``-th part (the state-space
    projection and convolution by their zones); the router, the selection
    bias and the norms whole."""
    proj = _zone_columns(whole, share, chip, ("heads", "heads", "groups", "groups", "dt"))
    conv = _zone_columns(whole, share, chip, ("heads", "groups", "groups"))
    out = {}
    for name, shape in reference.tensor_shapes(share).items():
        t, leaf = w_whole[name], name.split(".")[-1]
        if leaf == "ssm_in":
            out[name] = t[:, proj]
        elif leaf in ("ssm_conv", "ssm_conv_bias"):
            out[name] = t[..., conv]
        else:
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
