"""Tier-1's twin of the benchmark's plain reference for the Ouro block: the
SAME functions (``benchmark/references/ouro.py``, loaded by path as
``h1_reference`` loads the Falcon one: plain ``jax.numpy`` float32, nothing of
``ops/seqmodel.py``), the tiny sizes the CPU tests run at, and the untied
model the tie test compares the shared layers' gradients with."""

from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

_PATH = Path(__file__).resolve().parents[1] / "benchmark" / "references" / "ouro.py"
_spec = importlib.util.spec_from_file_location("ouro_reference_functions", _PATH)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

#: the tiny model: hidden 64, 4 heads of 16 (one KV head a query head), 3
#: layers run 4 times, 96 MLP columns, 512 items
TINY = {
    "hidden_size": 64, "num_layers": 3, "head_dim": 16, "heads": 4,
    "kv_heads": 4, "mlp_columns": 96, "vocab_rows": 512, "vocab_start": 0,
    "rope_theta": 1e6, "rms_norm_eps": 1e-6, "passes": 4, "exit_beta": 0.1,
}


def seq_config(m: dict, **kw):
    """The program's ``SeqConfig`` for a reference model group."""
    from predictionio_tpu.ops.seqmodel import SANDWICH, SeqConfig

    return SeqConfig(
        hidden=m["hidden_size"], layer_types=(SANDWICH,) * m["num_layers"],
        heads=m["heads"], head_dim=m["head_dim"], lin_heads=0, lin_key_dim=0,
        lin_value_dim=0, conv_width=4, mlp_cols=m["mlp_columns"],
        vocab_rows=m["vocab_rows"], vocab_start=m["vocab_start"],
        eps=m["rms_norm_eps"], kv_heads=m["kv_heads"], rope_theta=m["rope_theta"],
        loop_steps=m["passes"], exit_beta=m["exit_beta"],
        **{"loss_block": 64, **kw},
    )


def random_weights(m: dict, seed: int, gain: float = 1.2) -> dict:
    """Weights large enough that every path matters: matrices normal with
    standard deviation ``gain / sqrt(rows)``, norm weights 1 +- 0.2, a gate
    that spreads the exits (its vector normal / sqrt(D), its bias 0.3)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    w = {k: np.asarray(v) for k, v in reference.initial_weights(m, seed).items()}
    for name, v in w.items():
        if v.ndim == 2:
            w[name] = (
                gain * rng.standard_normal(v.shape) / np.sqrt(v.shape[0])
            ).astype(np.float32)
        elif name.endswith("norm"):
            w[name] = (1.0 + 0.2 * rng.standard_normal(v.shape)).astype(np.float32)
        elif name == "exit_gate":
            w[name] = (rng.standard_normal(v.shape) / np.sqrt(v.shape[0])).astype(
                np.float32)
        elif name == "exit_gate_bias":
            w[name] = np.float32(0.3)
    return {k: jnp.asarray(v) for k, v in w.items()}


def untied(w: dict, m: dict) -> dict:
    """Four copies of the stack, one a pass: ``pass<t>.layer<i>.<leaf>``."""
    out = {k: v for k, v in w.items() if not k.startswith("layer")}
    for t in range(m["passes"]):
        out.update({f"pass{t}.{k}": v for k, v in w.items() if k.startswith("layer")})
    return out


def untied_tensors(w: dict, i: int, t: int) -> dict:
    """``reference.exit_states``' rule for the untied model: pass t reads ITS
    copy of layer i."""
    p = f"pass{t}.layer{i}."
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


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
