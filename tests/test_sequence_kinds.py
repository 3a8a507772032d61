"""What the table of layer kinds must keep (ISSUE 44): every block's tensors
by name, shape and ORDER (tensor number n seeds its own draw, the model
store's parts and the references go by name), the groupings other code reads,
the module-level names a control replaces after import, and the training
record's keys the references read."""

from __future__ import annotations

import dataclasses
import functools

import jax.numpy as jnp
import numpy as np
import pytest

import h1_reference
import nemotron_reference
import ouro_reference
import seq_reference
import st_reference
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.sequence import engine as seq
from predictionio_tpu.ops import seqmodel
from predictionio_tpu.utils.params import extract_params
from test_h1_engine import VARIANT as H1_VARIANT
from test_nemotron_engine import VARIANT as NEMOTRON_VARIANT
from test_ouro_engine import VARIANT as OURO_VARIANT
from test_sequence_engine import VARIANT as OLMO_VARIANT
from test_smallthinker_engine import VARIANT as ST_VARIANT

#: the five blocks' tier-1 configurations: what their own tests build
BLOCKS = {
    "olmo": (seq_reference, seq_reference.HALF),
    "falcon_h1": (h1_reference, h1_reference.SHARE),
    "smallthinker": (st_reference, st_reference.SHARE),
    "ouro": (ouro_reference, ouro_reference.TINY),
    "nemotron": (nemotron_reference, nemotron_reference.TINY),
}


def block_config(block: str):
    module, group = BLOCKS[block]
    return module.seq_config(group)


def _olmo_layer(i: int, linear: bool) -> str:
    mixer = (
        "q:64x16 k:64x16 v:64x32 g:64x32 a:64x2 b:64x2 conv_q:4x16 conv_k:4x16 "
        "conv_v:4x32 a_log:2 dt_bias:2 o_norm:16 o:32x64" if linear else
        "q:64x32 k:64x32 v:64x32 q_norm:16 k_norm:16 o:32x64")
    rest = "mixer_norm:64 gate:64x16 up:64x16 down:16x64 mlp_norm:64"
    return " ".join(f"layer{i}.{t}" for t in f"{mixer} {rest}".split())


#: ``param_shapes`` at commit af3b99e, ``name:shape`` in order
RECORDED_SHAPES = {
    "olmo": " ".join([
        "embed:64x64", _olmo_layer(0, True), _olmo_layer(1, True),
        _olmo_layer(2, True), _olmo_layer(3, False), "final_norm:64",
        "head:64x64"]),
    "falcon_h1": " ".join(["embed:32x64"] + [
        f"layer{i}.{t}" for i in range(2) for t in (
            "input_norm:64 ssm_in:64x66 ssm_conv:4x48 ssm_conv_bias:48 "
            "ssm_a_log:2 ssm_d:2 ssm_dt_bias:2 ssm_norm:16 ssm_out:16x64 q:64x32 "
            "k:64x16 v:64x16 o:32x64 pre_ff_norm:64 gate:64x16 up:64x16 "
            "down:16x64").split()] + ["final_norm:64", "head:32x64"]),
    "smallthinker": " ".join(["embed:32x64"] + [
        f"layer{i}.{t}" for i in range(2) for t in (
            "input_norm:64 router:64x16 q:64x32 k:64x16 v:64x16 o:32x64 "
            "post_norm:64 experts_gate:4x64x32 experts_up:4x64x32 "
            "experts_down:4x32x64").split()] + ["final_norm:64", "head:32x64"]),
    "ouro": " ".join(["embed:512x64"] + [
        f"layer{i}.{t}" for i in range(3) for t in (
            "input_norm:64 q:64x64 k:64x64 v:64x64 o:64x64 attn_out_norm:64 "
            "pre_ff_norm:64 gate:64x96 up:64x96 down:96x64 "
            "mlp_out_norm:64").split()] + [
        "final_norm:64", "head:512x64", "exit_gate:64", "exit_gate_bias:"]),
    "nemotron": (
        "embed:512x64 "
        "layer0.input_norm:64 layer0.ssm_in:64x132 layer0.ssm_conv:4x96 "
        "layer0.ssm_conv_bias:96 layer0.ssm_a_log:4 layer0.ssm_d:4 "
        "layer0.ssm_dt_bias:4 layer0.ssm_norm:32 layer0.ssm_out:32x64 "
        "layer1.input_norm:64 layer1.router:64x16 layer1.router_bias:16 "
        "layer1.shared_up:64x40 layer1.shared_down:40x64 "
        "layer1.experts_up:8x64x24 layer1.experts_down:8x24x64 "
        "layer2.input_norm:64 layer2.ssm_in:64x132 layer2.ssm_conv:4x96 "
        "layer2.ssm_conv_bias:96 layer2.ssm_a_log:4 layer2.ssm_d:4 "
        "layer2.ssm_dt_bias:4 layer2.ssm_norm:32 layer2.ssm_out:32x64 "
        "layer3.input_norm:64 layer3.q:64x64 layer3.k:64x32 layer3.v:64x32 "
        "layer3.o:64x64 "
        "layer4.input_norm:64 layer4.router:64x16 layer4.router_bias:16 "
        "layer4.shared_up:64x40 layer4.shared_down:40x64 "
        "layer4.experts_up:8x64x24 layer4.experts_down:8x24x64 "
        "final_norm:64 head:512x64"),
}


@pytest.mark.parametrize("block", BLOCKS)
def test_a_blocks_tensors_keep_their_names_shapes_and_order(block):
    got = " ".join(
        f"{name}:{'x'.join(map(str, shape))}"
        for name, shape in seqmodel.param_shapes(block_config(block)).items())
    assert got.split() == RECORDED_SHAPES[block].split()


# ---------------------------------------------------------------------------
# the table, and what is derived from it

KINDS = (
    "linear_attention", "full_attention", "parallel_ssm_attention",
    "global_attention_moe", "sliding_attention_moe", "sandwich_attention",
    "state_space", "grouped_attention", "shared_routed_experts")
#: a block whose stack holds the kind, and a layer of that kind in it
BLOCK_OF = {
    "linear_attention": ("olmo", 0), "full_attention": ("olmo", 3),
    "parallel_ssm_attention": ("falcon_h1", 0),
    "global_attention_moe": ("smallthinker", 0),
    "sliding_attention_moe": ("smallthinker", 1),
    "sandwich_attention": ("ouro", 0), "state_space": ("nemotron", 0),
    "grouped_attention": ("nemotron", 3), "shared_routed_experts": ("nemotron", 1),
}


def test_the_groupings_derived_from_the_table_are_the_published_ones():
    assert seqmodel.KINDS == KINDS == tuple(seqmodel.LAYER_KINDS)
    assert seqmodel.MOE_KINDS == ("global_attention_moe", "sliding_attention_moe")
    assert seqmodel.SUBLAYER_KINDS == (
        "state_space", "grouped_attention", "shared_routed_experts")
    assert seqmodel.ROUTED_KINDS == (
        "global_attention_moe", "sliding_attention_moe", "shared_routed_experts")
    assert seqmodel.PROBE_NAME == {
        "linear_attention": "delta_rule_probe", "full_attention": "delta_rule_probe",
        "parallel_ssm_attention": "ssd_probe", "global_attention_moe": "moe_probe",
        "sliding_attention_moe": "moe_probe", "sandwich_attention": "exit_probe",
        "state_space": "ssd_probe", "shared_routed_experts": "moe_probe"}
    assert (seqmodel.GLOBAL_MOE, seqmodel.SLIDING_MOE) == seqmodel.MOE_KINDS


@functools.lru_cache(maxsize=None)
def _layer_inputs(block: str, i: int):
    cfg = block_config(block)
    w = seqmodel.init_params(cfg, 3)
    T = cfg.token_multiple
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((1, T, cfg.hidden)), jnp.float32)
    return cfg, w, seqmodel.layer_params(w, i), x, jnp.zeros((1, T), jnp.int32)


@pytest.mark.parametrize("kind", KINDS)
def test_a_kinds_entry_records_a_dict_and_layer_is_its_lookup(kind):
    block, i = BLOCK_OF[kind]
    cfg, _, p, x, seg = _layer_inputs(block, i)
    assert cfg.layer_types[i] == kind
    entry = seqmodel.LAYER_KINDS[kind]
    assert list(entry.tensors(cfg)) == list(p)
    y, record = entry.apply(cfg, p, x, seg)
    assert y.shape == x.shape and isinstance(record, dict)
    routed = kind in seqmodel.ROUTED_KINDS
    assert routed == entry.routed == ("choices" in record) == ("expert_pairs" in record)
    probes = set(record) - {"choices", "expert_pairs"}
    assert probes <= {seqmodel.PROBE_NAME.get(kind)}
    out = seqmodel.layer(cfg, kind, p, x, seg)
    assert isinstance(out, tuple) and len(out) == 2
    np.testing.assert_array_equal(out[0], y)
    assert set(out[1]) == set(record)


class _Reached(Exception):
    pass


def _marker(*args, **kwargs):
    raise _Reached


#: a module-level name the controls under ``benchmark/tests/`` replace after
#: import, and a block whose forward must then run the replacement
REPLACED = [
    ("layer", "olmo"), ("layer", "smallthinker"), ("layer", "ouro"),
    ("layer", "nemotron"), ("routed_layer", "smallthinker"),
    ("sublayer", "nemotron"), ("shared_expert", "nemotron"),
    ("routed_attention", "smallthinker"), ("routed_attention", "nemotron"),
    ("mlp", "olmo"), ("mlp", "falcon_h1"), ("mlp", "ouro"), ("rmsnorm", "olmo"),
    ("rmsnorm", "falcon_h1"), ("rmsnorm", "smallthinker"), ("rmsnorm", "ouro"),
    ("rmsnorm", "nemotron"),
]


@pytest.mark.parametrize("name,block", REPLACED)
def test_a_name_replaced_after_import_is_what_the_trunk_runs(monkeypatch, name, block):
    cfg, w, _, x, seg = _layer_inputs(block, 0)
    monkeypatch.setattr(seqmodel, name, _marker)
    with pytest.raises(_Reached):
        seqmodel.trunk(cfg, w, x, seg)


# ---------------------------------------------------------------------------
# the training record

VARIANTS = {
    "olmo": OLMO_VARIANT, "falcon_h1": H1_VARIANT, "smallthinker": ST_VARIANT,
    "ouro": OURO_VARIANT, "nemotron": NEMOTRON_VARIANT}

STEP_KEYS = {"loss", "tokens", "grad_norm", "tensor_grad_norm", "tensor_grad_probe"}
ROUTING_KEYS = {
    "choices", "moe_expert_pairs", "moe_pairs_held", "moe_pairs_total",
    "moe_rows_live", "moe_rows_planned"}
#: the training record's keys at commit af3b99e
RECORDED_KEYS = {
    "olmo": STEP_KEYS | {"delta_rule_probe"},
    "falcon_h1": STEP_KEYS | {"ssd_probe"},
    "smallthinker": STEP_KEYS | ROUTING_KEYS | {"moe_probe"},
    "ouro": STEP_KEYS | {
        "exit_probe", "carry_probe", "head_probe", "head_probe_state",
        "loss_by_exit", "exit_mass", "exit_entropy", "loop_layer_applications",
        "loop_tokens", "loop_attention_pairs"},
    "nemotron": STEP_KEYS | ROUTING_KEYS | {
        "ssd_probe", "moe_probe", "moe_grad_probe"},
}


def _packed(prep, steps: int) -> seq.PackedSequences:
    """Rows of two histories and a tail of padding each."""
    rng = np.random.default_rng(44)
    rows, T = steps * prep.rows_per_step, prep.row_len
    tokens = rng.integers(0, 100, (rows, T)).astype(np.int32)
    segments = np.full((rows, T), seq.PAD_SEGMENT, np.int32)
    for r in range(rows):
        segments[r, : T // 2] = 2 * r
        segments[r, T // 2 : T - 5] = 2 * r + 1
    return seq.PackedSequences(
        item_vocab=BiMap.from_keys([f"i{i}" for i in range(100)]),
        entities=np.array([f"u{e}" for e in range(2 * rows)], object),
        history_offsets=np.zeros(2 * rows + 1, np.int64),
        history_tokens=np.zeros(0, np.int32), tokens=tokens, segments=segments,
        vocab_start=prep.vocab_start, vocab_size=prep.vocab_size,
        rows_per_step=prep.rows_per_step)


@pytest.mark.parametrize("block", VARIANTS)
def test_a_tiny_retrains_record_keeps_its_keys(monkeypatch, block):
    variant = VARIANTS[block]
    # tiles of 8 pairs, as the routed blocks' own engine tests take them
    configured = seq.SequenceAlgorithm.seq_config
    monkeypatch.setattr(
        seq.SequenceAlgorithm, "seq_config",
        lambda self: dataclasses.replace(configured(self), moe_tile=8))
    params = extract_params(
        seq.SequenceAlgorithmParams, variant["algorithms"][0]["params"])
    prep = extract_params(
        seq.SequencePreparatorParams, variant["preparator"]["params"])
    model = seq.SequenceAlgorithm(params).train(
        None, _packed(prep, params.steps_per_retrain))
    record = model.training_record
    assert set(record) == RECORDED_KEYS[block]
    assert np.isfinite(record["loss"]).all()
    rows = (params.rows_per_step, prep.row_len)
    for name in set(seqmodel.PROBE_NAME.values()) & set(record):
        assert record[name].shape[:2] == rows, name
        assert record[name].shape[2] > 0, name
