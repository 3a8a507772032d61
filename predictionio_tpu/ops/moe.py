"""A layer of routed experts, as ONE share of an expert-parallel deployment
holds it.

The router keeps its published width: every token's logits over ALL experts,
the ``k`` largest, and the weights ``softmax`` over those ``k`` (float32,
``Precision.HIGHEST``).  The share holds experts ``start .. start + held`` and
computes the (token, expert) pairs of THOSE experts alone; what the other
shares' experts would add is left out and the partial result goes on
(``ops/seqmodel.py``, "The share").  On one chip the layer runs without its
exchange: tensor-parallel attention over the same chips replicates the tokens,
so every pair of a held expert is already here.

**No pair is dropped.**  The pairs are laid out by expert in a buffer of
``plan_rows`` rows, each expert's pairs from a row that is a multiple of
``tile`` (an expert with no pair still gets one tile of zeros, so that its
gradient is written).  The buffer is sized for the worst case, every token
choosing ``min(k, held)`` held experts, so capacity is never a reason to drop
a pair; the grouped products work through the tiles that hold pairs and skip
the rest, and so does everything else whose leading dimension is the
buffer's: the gathers into it run over windows of ``WINDOW_TILES`` tiles, as
many as ``plan.n_active`` says at run time (``_live_windows``), and the maps
between the products over the products' own grid of tiles (``_tile_maps``),
into buffers whose other rows nothing writes or reads.  Padding tokens
(``valid`` false) make no pair.

**The products.**  One tile of pairs times its expert's matrix, bfloat16
inputs and float32 accumulation, in the forward and in both products of the
backward: ``moe_gmm`` (pairs x weights, and pairs x weights^T for the
gradient of the pairs) and ``moe_tgmm`` (pairs^T x pairs, the weights'
gradient, summed over an expert's tiles).  On a TPU they are the Pallas
kernels below (the weight block stays in VMEM across an expert's tiles);
elsewhere ``jax.numpy`` over the same tiles.

    gate | up = xs @ [W_gate | W_up]      one product, K = hidden
    y         = (act(gate) * up) @ W_down
    out[t]    = sum over t's held pairs of  w * y

``expert_ffn`` carries its own backward pass: gathers in both directions (a
pair's row is written once), never a scatter-add of rows.

**A second routing rule and a second expert form** (Nemotron-3-Nano's layer,
``ops/seqmodel.py`` ``"shared_routed_experts"``).  ``route_sigmoid``: scores
``s = sigmoid(logits)``, the ``k`` largest of ``s + b`` (``b`` a selection
bias no gradient reaches), the weights the chosen UNBIASED scores normalised
to sum to ``scale``.  ``relu2_ffn``: two matrices an expert and no gate,

    u   = xs @ W_up                       K = hidden
    y   = relu(u)^2 @ W_down
    out[t] = sum over t's held pairs of  w * y

over the SAME plan, dispatch, combine and grouped kernels (six products under
the kernels' names with ``up`` in place of ``gate_up``), with its own
backward.  An expert width that is no multiple of the 128 lanes (1856) goes
through ``gmm`` as a block of the full width; ``tgmm`` covers it with blocks
of ``block_n`` columns, the last one partly outside the array (``_tgmm_block``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops.gdn import use_pallas

HIGHEST = jax.lax.Precision.HIGHEST

#: scoped VMEM the kernels may use: a weight block [2560, 1536] bf16 twice
#: over, a tile of pairs and its result (the default of 16 MiB is less)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024

#: tiles of the pair buffer a window of ``_live_windows`` takes (16 x 256 rows
#: divide both routed cells' buffers, 400 and 208 tiles; chosen on the chip:
#: ``benchmark/tests/micro_moe_rows_chip.py window``)
WINDOW_TILES = 16


def act(g):
    """The experts' gate activation: ReLU (the published ReGLU)."""
    return jnp.maximum(g, 0.0)


def act_grad(g):
    return (g > 0).astype(g.dtype)


def act2(u):
    """The two-matrix experts' activation: squared ReLU (``relu2``)."""
    r = jnp.maximum(u, 0.0)
    return r * r


def act2_grad(u):
    return 2.0 * jnp.maximum(u, 0.0)


def route(logits, k: int):
    """[N, E] float32 logits -> (chosen experts [N, k] int32, their weights
    [N, k]): the ``k`` largest logits, then ``softmax`` over those ``k`` (they
    sum to one)."""
    top, idx = jax.lax.top_k(logits, k)
    return idx.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def route_sigmoid(logits, bias, k: int, scale: float):
    """[N, E] float32 logits, [E] selection bias -> (chosen experts [N, k]
    int32, their weights [N, k]): scores ``sigmoid(logits)``, the ``k``
    largest of ``score + bias``, the weights the chosen scores WITHOUT the
    bias over their sum (+ 1e-20), times ``scale`` (they sum to ``scale``).
    The bias only moves the choice: no gradient reaches it, the weights'
    gradient goes through the scores, the normaliser and the scale."""
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w


class Plan(NamedTuple):
    """Where every held pair lies in the buffer of ``plan_rows`` rows."""

    #: [N, k]: the row of pair (t, j); ``plan_rows`` where the pair's expert
    #: is not held here (or the token is padding)
    dest: jax.Array
    #: [R]: the pair a row holds, ``t * k + j``; N * k on a row no pair lies on
    row_pair: jax.Array
    #: [R / tile]: the held expert (0 ..) a tile belongs to
    tile_group: jax.Array
    #: [1]: tiles that hold pairs (or an empty expert's zeros), from the front
    n_active: jax.Array
    #: [held]: pairs of each held expert
    counts: jax.Array

    @property
    def row_token(self):
        """[R]: the token a row reads; N on a row no pair lies on."""
        return self.row_pair // self.dest.shape[1]


def plan_rows(tokens: int, k: int, held: int, tile: int) -> int:
    """Rows of the pair buffer: the worst case, every token choosing
    ``min(k, held)`` held experts, and under ``tile`` rows of padding an
    expert (a whole tile for an expert with no pair)."""
    worst = tokens * min(k, held)
    return -(-worst // tile) * tile + held * tile


def expert_tiles(counts, tile: int):
    """Tiles of the buffer each held expert takes: its pairs' (``counts``
    [..., held]), or one of zeros for an expert with no pair.  Their sum is
    ``plan.n_active``, the live tiles."""
    return jnp.maximum(-(-counts // tile), 1)


def make_plan(idx, valid, start: int, held: int, tile: int) -> Plan:
    """idx: [N, k] chosen experts of each token; valid: [N] bool.  The held
    pairs in token order within each expert, experts one after another from
    tile-aligned rows."""
    N, k = idx.shape
    R = plan_rows(N, k, held, tile)
    local = idx - start
    mine = (local >= 0) & (local < held) & valid[:, None]
    # chosen[t, e]: token t chose held expert e (a token's k experts differ)
    chosen = jnp.sum(
        (local[:, :, None] == jnp.arange(held)) & mine[:, :, None], axis=1,
        dtype=jnp.int32)
    before = jnp.cumsum(chosen, axis=0) - chosen
    counts = jnp.sum(chosen, axis=0)
    tiles = expert_tiles(counts, tile)
    ends = jnp.cumsum(tiles)
    first_row = (ends - tiles) * tile
    at = jnp.clip(local, 0, held - 1)
    rank = jnp.take_along_axis(before, at, axis=1)
    dest = jnp.where(mine, first_row[at] + rank, R).astype(jnp.int32)
    row_pair = jnp.full((R,), N * k, jnp.int32).at[dest.reshape(-1)].set(
        jnp.arange(N * k, dtype=jnp.int32), mode="drop", unique_indices=True)
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(R // tile), side="right"), held - 1
    ).astype(jnp.int32)
    return Plan(dest, row_pair, tile_group, ends[-1:].astype(jnp.int32), counts)


# ---------------------------------------------------------------------------
# the grouped products


def _impl(impl: str | None) -> str:
    return impl or ("pallas" if use_pallas() else "xla")


def _gmm_kernel(group_ref, active_ref, lhs_ref, rhs_ref, out_ref, *, transpose_rhs):
    del group_ref

    @pl.when(pl.program_id(0) < active_ref[0])
    def _():
        dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], dims,
            preferred_element_type=jnp.float32)


def gmm(lhs, rhs, plan: Plan, *, transpose_rhs: bool = False, name: str,
        impl: str | None = None):
    """``lhs`` [R, K] times, tile by tile, its expert's ``rhs`` [held, K, N]
    (or [held, N, K] with ``transpose_rhs``) -> [R, N] float32.  Rows of the
    tiles past ``plan.n_active`` are not written (nothing reads them)."""
    R, K = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tiles = plan.tile_group.shape[0]
    tm = R // tiles
    impl = _impl(impl)
    if impl == "xla":
        w = rhs[plan.tile_group]
        out = jnp.einsum(
            "itk,ink->itn" if transpose_rhs else "itk,ikn->itn",
            lhs.reshape(tiles, tm, K), w, preferred_element_type=jnp.float32)
        live = jnp.arange(tiles) < plan.n_active[0]
        return jnp.where(live[:, None, None], out, 0.0).reshape(R, n)
    def last(i, active):
        return jnp.minimum(i, active[0] - 1)

    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((R, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles,),
            in_specs=[
                pl.BlockSpec((tm, K), lambda i, g, a: (last(i, a), 0)),
                pl.BlockSpec((None,) + rhs.shape[1:],
                             lambda i, g, a: (g[last(i, a)], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tm, n), lambda i, g, a: (last(i, a), 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * R * K * n, transcendentals=0,
            bytes_accessed=lhs.size * lhs.dtype.itemsize + 4 * R * n
            + rhs.size * rhs.dtype.itemsize),
        interpret=impl == "interpret",
        name=name,
    )(plan.tile_group, plan.n_active, lhs, rhs)


def _tgmm_kernel(group_ref, active_ref, lhs_ref, rhs_ref, out_ref):
    i = pl.program_id(1)

    @pl.when(i < active_ref[0])
    def _():
        part = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        first = (i == 0) | (group_ref[i] != group_ref[jnp.maximum(i - 1, 0)])

        @pl.when(first)
        def _():
            out_ref[...] = part

        @pl.when(jnp.logical_not(first))
        def _():
            out_ref[...] += part


def tgmm(lhs, rhs, plan: Plan, held: int, *, name: str, impl: str | None = None,
         block_n: int | None = None):
    """Per held expert, ``lhs[rows]^T @ rhs[rows]`` over the expert's tiles:
    lhs [R, K], rhs [R, N] -> [held, K, N] float32 (every expert has a tile,
    so every block is written).  The columns go in ``ceil(N / block_n)``
    blocks, the last one partly outside the array where ``block_n`` does not
    divide N (what it reads there reaches only columns that are not written);
    with no ``block_n`` in blocks of 768 where that divides N, else in one
    block of the full width."""
    R, K = lhs.shape
    n = rhs.shape[1]
    tiles = plan.tile_group.shape[0]
    tm = R // tiles
    impl = _impl(impl)
    if impl == "xla":
        live = (jnp.arange(tiles) < plan.n_active[0])[:, None, None]
        part = jnp.einsum(
            "itk,itn->ikn", jnp.where(live, lhs.reshape(tiles, tm, K), 0),
            jnp.where(live, rhs.reshape(tiles, tm, n), 0),
            preferred_element_type=jnp.float32)
        return jnp.zeros((held, K, n), jnp.float32).at[plan.tile_group].add(part)
    tn = block_n or (768 if n % 768 == 0 else n)

    def last(i, active):
        return jnp.minimum(i, active[0] - 1)

    return pl.pallas_call(
        _tgmm_kernel,
        out_shape=jax.ShapeDtypeStruct((held, K, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(-(-n // tn), tiles),
            in_specs=[
                pl.BlockSpec((tm, K), lambda j, i, g, a: (last(i, a), 0)),
                pl.BlockSpec((tm, tn), lambda j, i, g, a: (last(i, a), j)),
            ],
            out_specs=pl.BlockSpec(
                (None, K, tn), lambda j, i, g, a: (g[last(i, a)], 0, j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * R * K * n, transcendentals=0,
            bytes_accessed=(lhs.size * -(-n // tn) + rhs.size) * lhs.dtype.itemsize
            + 4 * held * K * n),
        interpret=impl == "interpret",
        name=name,
    )(plan.tile_group, plan.n_active, lhs, rhs)


# ---------------------------------------------------------------------------
# the experts over a plan


def _gather_rows(x, index):
    """``x[index]`` with zeros where the index is past the last row."""
    return jnp.take(x, index, axis=0, mode="fill", fill_value=0)


def _buffer(shape, dtype, impl: str):
    """What the loop over the live windows writes into.  No row past the live
    windows is ever read (the products and the maps skip their tiles, no
    token's ``dest`` points there), so beside the kernels the buffer is left
    as the allocator hands it, by a kernel that writes nothing: a broadcast of
    zeros over all ``plan_rows`` rows, every gathered buffer of every layer
    and pass, is time no pair needs and carries no operation's name.  The
    kernel takes no operand: the compiler is then free to make every layer's
    buffers at the program's start, side by side (6.7 GB of temporaries in the
    SmallThinker cell's row program for 4.6, which fit), where an operand that
    ties a buffer to what its gathers read, the whole array or one row of it,
    is copied on the chip (0.18 s a retrain).  On the ``"xla"`` path, whose
    products and maps read every row, zeros."""
    if impl == "xla":
        return jnp.zeros(shape, dtype)
    return pl.pallas_call(
        lambda out: None, out_shape=jax.ShapeDtypeStruct(shape, dtype),
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        interpret=impl == "interpret", name="moe_unwritten")()


def _live_windows(plan: Plan, impl: str | None, fn, *ins):
    """The gathers into the buffer, over its live rows: ``fn`` takes
    ``WINDOW_TILES`` tiles of every ``ins`` [R, ...] (the plan's indices) and
    gives a tuple of as many rows, written in place into buffers of R rows ->
    those buffers.  The windows run from the front, as many as hold
    ``plan.n_active`` tiles (counted on the device: a loop with a traced
    bound); a last window that would pass the buffer's end starts earlier and
    writes the rows it shares a second time.  Rows past the live windows are
    never written (``_buffer``): no pair lies on them."""
    R, tiles = plan.row_pair.shape[0], plan.tile_group.shape[0]
    span = min(WINDOW_TILES, tiles)
    rows = span * (R // tiles)

    def window(x):
        return jax.ShapeDtypeStruct((rows,) + x.shape[1:], x.dtype)

    def body(i, bufs):
        at = jnp.minimum(i * rows, R - rows)
        parts = fn(*(jax.lax.dynamic_slice_in_dim(x, at, rows) for x in ins))
        return tuple(jax.lax.dynamic_update_slice_in_dim(buf, part, at, 0)
                     for buf, part in zip(bufs, parts))

    bufs = tuple(_buffer((R,) + part.shape[1:], part.dtype, _impl(impl))
                 for part in jax.eval_shape(fn, *map(window, ins)))
    return jax.lax.fori_loop(0, -(-plan.n_active[0] // span), body, bufs)


def _tile_maps(plan: Plan, impl: str | None, fn, *ins, name: str):
    """The maps between the products, over the buffer's live tiles: ``fn``
    takes one tile of every ``ins`` [R, ...] and gives a tuple of as many
    rows -> arrays of R rows.  One grid step a tile as in ``gmm``: the tiles
    past ``plan.n_active`` are neither read nor written (their rows are what
    the allocator hands out; nothing reads them).  On the ``"xla"`` path
    ``fn`` of the whole buffer."""
    impl = _impl(impl)
    if impl == "xla":
        return fn(*ins)
    R, tiles = plan.row_pair.shape[0], plan.tile_group.shape[0]
    tm = R // tiles

    def tile(x):
        return jax.ShapeDtypeStruct((tm,) + x.shape[1:], x.dtype)

    def block(x):
        return pl.BlockSpec(
            (tm,) + x.shape[1:],
            lambda i, a: (jnp.minimum(i, a[0] - 1),) + (0,) * (len(x.shape) - 1))

    outs = jax.eval_shape(fn, *map(tile, ins))

    def kernel(active_ref, *refs):
        @pl.when(pl.program_id(0) < active_ref[0])
        def _():
            parts = fn(*(ref[...] for ref in refs[:len(ins)]))
            for ref, part in zip(refs[len(ins):], parts):
                ref[...] = part

    moved = sum(x.size * x.dtype.itemsize for x in ins) + sum(
        tiles * o.size * o.dtype.itemsize for o in outs)
    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((R,) + o.shape[1:], o.dtype) for o in outs],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles,),
            in_specs=[block(x) for x in ins],
            out_specs=[block(o) for o in outs],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=8 * max(x.size for x in ins), transcendentals=0,
            bytes_accessed=moved),
        interpret=impl == "interpret",
        name=name,
    )(plan.n_active, *ins)


def _dispatch(plan: Plan, impl: str | None, m):
    """The forward's way into the buffer: every live row's token's ``m``."""
    k = plan.dest.shape[1]
    with jax.named_scope("moe.dispatch"):
        return _live_windows(
            plan, impl, lambda pair: (_gather_rows(m, pair // k),), plan.row_pair)[0]


def _combine(rows, dest, weights):
    """out[t] = sum_j weights[t, j] * rows[dest[t, j]] (one gather a choice:
    the k gathered copies are never held side by side)."""
    out = None
    for j in range(dest.shape[1]):
        part = _gather_rows(rows, dest[:, j])
        if weights is not None:
            part = part * weights[:, j, None]
        out = part if out is None else out + part
    return out


def _cotangent_rows(plan: Plan, static, w, dout):
    """The backward's way into the buffer: a row's weight ([R, 1], read from
    the row's side, zero where no pair lies), and the output's gradient at
    the row's token as the products read it, plain and times the row's
    weight; the float32 rows exist a window at a time."""
    dtype, impl = static

    def rows(pair):
        row_w = _gather_rows(w.reshape(-1, 1), pair)
        g = _gather_rows(dout, pair // w.shape[1])
        return row_w, g.astype(dtype), (g * row_w).astype(dtype)

    with jax.named_scope("moe.combine"):
        return _live_windows(plan, impl, rows, plan.row_pair)


def _cotangent_tokens(plan: Plan, w, dxs, dw_row):
    """And out of it: the tokens' gradient summed over their held pairs, and
    each choice's weight's (``dw_row`` [R, 1])."""
    with jax.named_scope("moe.dispatch"):
        dm = _combine(dxs, plan.dest, None)
        return dm, _gather_rows(dw_row, plan.dest.reshape(-1)).reshape(w.shape)


def _ffn_forward(static, m, w, gate, up, down, plan):
    dtype, impl = static
    xs = _dispatch(plan, impl, m.astype(dtype))
    with jax.named_scope("moe.experts"):
        both = jnp.concatenate([gate.astype(dtype), up.astype(dtype)], axis=2)
        gu = gmm(xs, both, plan, name="moe_gmm_gate_up", impl=impl)
        f = gate.shape[2]
        a, = _tile_maps(
            plan, impl, lambda gu: ((act(gu[:, :f]) * gu[:, f:]).astype(dtype),), gu,
            name="moe_map_act")
        ys = gmm(a, down.astype(dtype), plan, name="moe_gmm_down", impl=impl)
    with jax.named_scope("moe.combine"):
        out = _combine(ys, plan.dest, w)
    return out, (xs, gu)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def expert_ffn(static, m, w, gate, up, down, plan: Plan):
    """The held experts' part of the layer's output.  m: [N, D] float32 (the
    normed stream); w: [N, k] float32 weights of each token's choices; gate,
    up: [held, D, F]; down: [held, F, D]; ``static`` = (the products' input
    dtype, the implementation) -> [N, D] float32."""
    return _ffn_forward(static, m, w, gate, up, down, plan)[0]


def _ffn_fwd(static, m, w, gate, up, down, plan):
    out, (xs, gu) = _ffn_forward(static, m, w, gate, up, down, plan)
    return out, (xs, gu, w, gate, up, down, plan)


def _ffn_bwd(static, res, dout):
    dtype, impl = static
    xs, gu, w, gate, up, down, plan = res
    held, _, f = gate.shape
    row_w, g_rows, gw_rows = _cotangent_rows(plan, static, w, dout)

    def maps(gu, da, row_w):
        g_act, u = act(gu[:, :f]), gu[:, f:]
        a = g_act * u
        # d(out) / d(weight of a row) = <dout[token], y[row]> = <dout W_down^T, a>
        dw_row = jnp.sum(da * a, axis=-1, keepdims=True)
        da = da * row_w
        # each half is cast BEFORE the concatenation: the chip's compiler
        # splits a cast of the whole into casts it makes itself, which carry
        # no op name, and the fusion they root reads as ``(no scope)``
        dgu = jnp.concatenate(
            [(da * u * act_grad(gu[:, :f])).astype(dtype),
             (da * g_act).astype(dtype)], axis=1)
        return a.astype(dtype), dw_row, dgu

    with jax.named_scope("moe.experts"):
        da = gmm(g_rows, down.astype(dtype), plan,
                 transpose_rhs=True, name="moe_gmm_down_dlhs", impl=impl)
        a, dw_row, dgu = _tile_maps(
            plan, impl, maps, gu, da, row_w, name="moe_map_act_grad")
        ddown = tgmm(a, gw_rows, plan, held, name="moe_tgmm_down", impl=impl)
        both = jnp.concatenate([gate.astype(dtype), up.astype(dtype)], axis=2)
        dxs = gmm(dgu, both, plan, transpose_rhs=True,
                  name="moe_gmm_gate_up_dlhs", impl=impl)
        dboth = tgmm(xs, dgu, plan, held, name="moe_tgmm_gate_up", impl=impl)
    dm, dw = _cotangent_tokens(plan, w, dxs, dw_row)
    return dm, dw, dboth[:, :, :f], dboth[:, :, f:], ddown, None


expert_ffn.defvjp(_ffn_fwd, _ffn_bwd)


#: columns of a ``tgmm`` block where the width is no multiple of the lanes
#: (``relu2_ffn``: 1856 columns in three blocks of 640, the last 576 wide)
COVER_BLOCK = 640


def _tgmm_block(n: int) -> int:
    """The block in which ``relu2_ffn``'s weight gradients take N columns:
    whole lanes in the largest block that divides them (2688 = 3 x 896), else
    ``COVER_BLOCK``, the last block partly outside the array."""
    if n % 128:
        return COVER_BLOCK
    return next(b for b in (1024, 896, 768, 640, 512, 384, 256, 128) if n % b == 0)


def _relu2_forward(static, m, w, up, down, plan):
    dtype, impl = static
    xs = _dispatch(plan, impl, m.astype(dtype))
    with jax.named_scope("moe.experts"):
        u = gmm(xs, up.astype(dtype), plan, name="moe_gmm_up", impl=impl)
        a, = _tile_maps(
            plan, impl, lambda u: (act2(u).astype(dtype),), u, name="moe_map_act")
        ys = gmm(a, down.astype(dtype), plan, name="moe_gmm_down", impl=impl)
    with jax.named_scope("moe.combine"):
        out = _combine(ys, plan.dest, w)
    return out, (xs, u)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def relu2_ffn(static, m, w, up, down, plan: Plan):
    """``expert_ffn`` for experts of two matrices, ``W_down relu(W_up m)^2``.
    up: [held, D, F]; down: [held, F, D]; everything else as there."""
    return _relu2_forward(static, m, w, up, down, plan)[0]


def _relu2_fwd(static, m, w, up, down, plan):
    out, (xs, u) = _relu2_forward(static, m, w, up, down, plan)
    return out, (xs, u, w, up, down, plan)


def _relu2_bwd(static, res, dout):
    dtype, impl = static
    xs, u, w, up, down, plan = res
    held = up.shape[0]
    row_w, g_rows, gw_rows = _cotangent_rows(plan, static, w, dout)

    def maps(u, da, row_w):
        a = act2(u)
        dw_row = jnp.sum(da * a, axis=-1, keepdims=True)
        da = da * row_w
        return a.astype(dtype), dw_row, (da * act2_grad(u)).astype(dtype)

    with jax.named_scope("moe.experts"):
        da = gmm(g_rows, down.astype(dtype), plan,
                 transpose_rhs=True, name="moe_gmm_down_dlhs", impl=impl)
        a, dw_row, du = _tile_maps(
            plan, impl, maps, u, da, row_w, name="moe_map_act_grad")
        ddown = tgmm(a, gw_rows, plan, held, name="moe_tgmm_down", impl=impl,
                     block_n=_tgmm_block(down.shape[2]))
        dxs = gmm(du, up.astype(dtype), plan, transpose_rhs=True,
                  name="moe_gmm_up_dlhs", impl=impl)
        dup = tgmm(xs, du, plan, held, name="moe_tgmm_up", impl=impl,
                   block_n=_tgmm_block(up.shape[2]))
    dm, dw = _cotangent_tokens(plan, w, dxs, dw_row)
    return dm, dw, dup, ddown, None


relu2_ffn.defvjp(_relu2_fwd, _relu2_bwd)


def experts_layer(m, logits, valid, gate, up, down, *, k: int, start: int,
                  tile: int, dtype, impl: str | None = None, bias=None,
                  scale: float = 1.0):
    """The held experts' part of a routed layer.  m: [N, D] what the experts
    read; logits: [N, E] the router's (made by the caller, from what the
    router reads); valid: [N] bool, false on padding -> (out [N, D], the
    choices [N, k], the pairs of each held expert [held]).  With a selection
    ``bias`` [E] the choice and weights are ``route_sigmoid``'s (the weights
    sum to ``scale``); with no ``gate`` the experts are ``relu2_ffn``'s."""
    held = up.shape[0]
    with jax.named_scope("moe.route"):
        idx, w = (route(logits, k) if bias is None
                  else route_sigmoid(logits, bias, k, scale))
        plan = make_plan(idx, valid, start, held, tile)
    if gate is None:
        out = relu2_ffn((dtype, impl), m, w, up, down, plan)
    else:
        out = expert_ffn((dtype, impl), m, w, gate, up, down, plan)
    return out, idx, plan.counts
