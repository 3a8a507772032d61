"""Every device operation of a sequence retrain under ONE name the program
wrote (ISSUE 36): in the jaxprs of the three programs (the row program
``accumulate_row``, the step ``apply_step``, the initialisation
``init_state``) of the five blocks, backward and recomputed equations
included, every equation that makes an array of more than a handful of
elements carries exactly one top-level scope of ``seqmodel.SCOPES`` in its
name stack.  A trace's ``(no scope)`` then holds only what the compiler made
without an op name (``benchmark/trace_reduce.py``; ``device_unscoped_s``).

Names and structure only: nothing here runs on a device."""

from __future__ import annotations

import math
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from predictionio_tpu.ops import seqmodel

#: an equation whose results are all this small is a scalar's bookkeeping
HANDFUL = 16
TOP_LEVEL = re.compile(r"seq\.\w+")

#: the mixers' and the feed-forward's scopes a block's row program must hold
BLOCKS = {
    "olmo_hybrid": {"seq.gdn", "seq.attn", "seq.mlp"},
    "falcon_h1": {"seq.ssm", "seq.attn", "seq.mlp"},
    "smallthinker": {"seq.attn", "seq.moe"},
    # the looped block: its exits' scope beside the mixer's and the MLP's
    "ouro": {"seq.attn", "seq.mlp", "seq.exit"},
    # layers of ONE sublayer each: no MLP, and no top-level name of its own
    "nemotron_h": {"seq.ssm", "seq.attn", "seq.moe"},
}
#: the component a looped model writes around pass t, OUTSIDE the top-level scope
LOOP_PASS = re.compile(r"loop\.pass(\d+)")


def _config(block: str):
    if block == "olmo_hybrid":
        import seq_reference as ref
        return ref.seq_config(ref.HALF)
    if block == "ouro":
        import ouro_reference as ref
        return ref.seq_config(ref.TINY)
    if block == "nemotron_h":
        import nemotron_reference as ref
        return ref.seq_config(ref.TINY)
    if block == "falcon_h1":
        import h1_reference as ref
    else:
        import st_reference as ref
    return ref.seq_config(ref.SHARE)


def _subjaxprs(eqn):
    for value in eqn.params.values():
        for v in value if isinstance(value, (list, tuple)) else [value]:
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield inner


def _operations(jaxpr, outer=""):
    """(primitive, whole name stack, elements of the largest result) of the
    equations that become device operations: an equation that only holds
    other equations (a ``jit``, a ``checkpoint``, a loop) hands its name
    stack down to them, as the lowering does; a kernel is one operation."""
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        inner = list(_subjaxprs(eqn))
        if inner and eqn.primitive.name != "pallas_call":
            for sub in inner:
                yield from _operations(sub, stack)
        else:
            yield eqn.primitive.name, stack, max(
                (math.prod(v.aval.shape) for v in eqn.outvars), default=0)


def _loops(jaxpr, outer=""):
    """(whole name stack, the operations inside) of every loop with a traced
    bound (``while``) under ``seq.moe``, wherever it is nested."""
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "while" and "seq.moe" in stack:
            yield stack, [op for sub in _subjaxprs(eqn) for op in _operations(sub, stack)]
            continue
        for sub in _subjaxprs(eqn):
            yield from _loops(sub, stack)


def _program(cfg, program: str):
    if program == "init_state":
        return jax.make_jaxpr(lambda: seqmodel.init_state(cfg, 3))()
    state, acc = jax.eval_shape(lambda: seqmodel.init_state(cfg, 3))
    if program == "apply_step":
        return jax.make_jaxpr(
            lambda s, a: seqmodel.apply_step(seqmodel.AdamW(), s, a))(state, acc)
    row = jax.ShapeDtypeStruct((64,), jnp.int32)
    return jax.make_jaxpr(
        lambda s, a, t, g: seqmodel.accumulate_row(cfg, s, a, t, g))(
            state, acc, row, row)


@pytest.mark.parametrize("program", ["accumulate_row", "apply_step", "init_state"])
@pytest.mark.parametrize("block", list(BLOCKS))
def test_every_operation_carries_one_top_level_scope(block, program):
    ops = list(_operations(_program(_config(block), program).jaxpr))
    assert not [s for _, s, _ in ops if "seq.adamw" in s]
    found: set[str] = set()
    for primitive, stack, size in ops:
        if size <= HANDFUL:
            continue
        tops = set(TOP_LEVEL.findall(stack))
        assert len(tops) == 1 and tops <= set(seqmodel.SCOPES), (
            primitive, stack, size)
        found |= tops
    if program == "apply_step":
        assert found == {"seq.step"}
    elif program == "init_state":
        assert found == {"seq.init"}
    else:
        assert found == BLOCKS[block] | {
            "seq.embed", "seq.loss", "seq.stream", "seq.accumulate"}
        # the adds of the stream's gradient: a transposition adds the
        # cotangents of a value read twice under the name stack of the
        # equation that read it FIRST, so the scopes around the norms and the
        # residual adds name them too, and no fork of the stream is needed
        adds = [s for p, s, n in ops if p == "add_any" and n > HANDFUL]
        assert any("seq.stream" in s and "transpose" in s for s in adds)
        assert all(TOP_LEVEL.search(s) for s in adds)
        # only a looped model writes the pass component, and the three
        # programs this engine had hold the scopes they held
        passes = {t for _, s, _ in ops for t in LOOP_PASS.findall(s)}
        assert passes == ({"0", "1", "2", "3"} if block == "ouro" else set())


def test_a_pass_component_lies_outside_the_scope_of_its_operations():
    """``loop.pass<t>`` comes BEFORE the one top-level scope in a name stack
    (a path then reads ``loop.pass2/seq.attn/attn.causal``), every pass holds
    the trunk's scopes, forward, recomputed and backward, and the exits and
    the loss lie under no pass."""
    ops = [(s, n) for _, s, n in _operations(
        _program(_config("ouro"), "accumulate_row").jaxpr) if n > HANDFUL]
    for t in range(4):
        mine = [s for s, _ in ops if f"loop.pass{t}" in s]
        assert {top for s in mine for top in TOP_LEVEL.findall(s)} == {
            "seq.attn", "seq.mlp", "seq.stream"}
        assert all(s.index(f"loop.pass{t}") < TOP_LEVEL.search(s).start() for s in mine)
        assert any("rematted_computation" in s for s in mine)
        assert any("transpose" in s for s in mine)
    outside = [s for s, _ in ops if not LOOP_PASS.search(s)]
    assert {"seq.exit", "seq.loss", "seq.embed", "seq.accumulate"} <= {
        top for s in outside for top in TOP_LEVEL.findall(s)}
    assert not [s for s, _ in ops if LOOP_PASS.search(s) and (
        "seq.exit" in s or "seq.loss" in s)]


def test_the_experts_probe_program_is_scoped_like_the_row_program():
    """``experts_probe`` is a program of its own (the first step's rows): its
    forward and the experts' backward carry exactly one top-level scope each,
    ``seq.embed`` for the rows and ``seq.moe`` with the row program's
    components for the rest."""
    cfg = _config("nemotron_h")
    params = jax.eval_shape(lambda: seqmodel.init_state(cfg, 3))[0]["params"]
    first = cfg.layer_types.index(seqmodel.SHARED_EXPERTS)
    row = jax.ShapeDtypeStruct((64,), jnp.int32)
    ops = [(s, n) for _, s, n in _operations(jax.make_jaxpr(
        lambda table, p, t, g: seqmodel.experts_probe(cfg, True, table, p, t, g))(
            params["embed"], seqmodel.layer_params(params, first), row, row).jaxpr)
        if n > HANDFUL]
    assert all(len(set(TOP_LEVEL.findall(s))) == 1 for s, _ in ops), [
        s for s, _ in ops if len(set(TOP_LEVEL.findall(s))) != 1]
    assert {t for s, _ in ops for t in TOP_LEVEL.findall(s)} == {"seq.embed", "seq.moe"}
    assert {c for s, _ in ops for c in re.findall(r"moe\.\w+", s)} == {
        "moe.route", "moe.dispatch", "moe.experts", "moe.combine", "moe.shared"}
    assert any("transpose" in s and "moe.experts" in s for s, _ in ops)  # the backward


def test_the_shared_expert_is_a_component_of_the_routed_layers_scope():
    """An ``E`` layer's work is ``seq.moe`` with the components that exist
    (``moe.route``, ``moe.dispatch``, ``moe.experts``, ``moe.combine``) and one
    new, ``moe.shared``, forward, recomputed and backward; the four older
    blocks' programs hold no such component."""
    ops = [s for _, s, n in _operations(
        _program(_config("nemotron_h"), "accumulate_row").jaxpr) if n > HANDFUL]
    shared = [s for s in ops if "moe.shared" in s]
    assert shared and all("seq.moe" in s for s in shared)
    assert all(s.index("seq.moe") < s.index("moe.shared") for s in shared)
    assert any("rematted_computation" in s for s in shared)
    assert any("transpose" in s for s in shared)
    components = {c for s in ops if "seq.moe" in s for c in re.findall(r"moe\.\w+", s)}
    assert components == {
        "moe.route", "moe.dispatch", "moe.experts", "moe.combine", "moe.shared"}
    # a state-space layer's and the attention layer's components are the
    # Falcon and SmallThinker blocks' own
    assert {c for s in ops for c in re.findall(r"ssm\.\w+", s)} == {
        "ssm.proj", "ssm.conv", "ssm.intra", "ssm.chunk", "ssm.norm"}
    assert any("seq.attn/attn.causal" in s for s in ops)
    assert not [s for s in ops if "attn.rope" in s or "attn.window" in s]
    for block in ("olmo_hybrid", "falcon_h1", "smallthinker", "ouro"):
        older = _operations(_program(_config(block), "accumulate_row").jaxpr)
        assert not [s for _, s, _ in older if "moe.shared" in s], block


@pytest.mark.parametrize("block", ["smallthinker", "nemotron_h"])
def test_the_live_window_loops_lie_inside_the_routed_layers_components(block):
    """The loops over the pair buffer's live windows (``moe._live_windows``)
    are work of the components that were there: every window's write
    (``dynamic_update_slice``) and every gather of rows into the buffer lies
    under ``seq.moe`` and ONE of ``moe.dispatch`` (the forward's gather, twice:
    the recomputed one too) and ``moe.combine`` (the backward's gather);
    nothing of a loop carries another name or none.  The maps between the
    products are no loop (``moe._tile_maps``: kernels under ``moe.experts``
    on the chip, the whole buffer's map here)."""
    loops = list(_loops(_program(_config(block), "accumulate_row").jaxpr))
    assert loops and all("seq.moe" in stack for stack, _ in loops)
    writes = []
    for stack, ops in loops:
        # one top-level scope, one component: the loop's and all it holds
        assert len(set(TOP_LEVEL.findall(stack))) == 1, stack
        assert len(set(re.findall(r"moe\.\w+", stack))) == 1, stack
        assert all(s.startswith(stack) and set(re.findall(r"(?:seq|moe)\.\w+", s)) == set(
            re.findall(r"(?:seq|moe)\.\w+", stack)) for _, s, _ in ops), stack
        assert [p for p, _, _ in ops].count("dynamic_update_slice") >= 1
        writes.append(stack)
    by_component = {
        c: [s for s in writes if c in s]
        for c in ("moe.dispatch", "moe.combine")}
    assert sum(map(len, by_component.values())) == len(writes)
    for component, mine in by_component.items():
        assert mine, component
    assert any("rematted_computation" in s for s in by_component["moe.dispatch"])
    # the backward's own gather (not a recomputed forward's) is moe.combine's
    assert all("rematted_computation" in s for s in by_component["moe.dispatch"]
               if "transpose" in s)
    assert all("transpose" in s and "rematted_computation" not in s
               for s in by_component["moe.combine"])
    # and no block without routed layers holds such a loop
    assert not [s for _, s, _ in _operations(
        _program(_config("falcon_h1"), "accumulate_row").jaxpr) if "moe." in s]


@pytest.mark.parametrize("call", [
    lambda: seqmodel._init_tensor.lower(
        "q", (8, 32), 4, jax.random.PRNGKey(0), 1),
    lambda: seqmodel._zeros.lower((8, 32), jnp.float32),
], ids=["draw", "zeros"])
def test_the_scope_reaches_the_programs_initialisation_dispatches(call):
    """``init_state`` is no program of its own: it dispatches a small one a
    tensor, and a ``named_scope`` around a dispatch does not reach the
    program dispatched.  The scope is written inside each of them."""
    assert 'op_name="jit(' in (text := call().compile().as_text())
    assert "seq.init/" in text


def test_the_docs_draw_the_closed_list_of_scopes():
    text = (Path(__file__).resolve().parents[1] / "docs"
            / "observability.md").read_text()
    for scope in seqmodel.SCOPES:
        assert scope in text, scope
    assert "seq.adamw" not in text
