"""Mamba-2's selective state space (arXiv:2405.21060) in its chunked (SSD) form.

Per head n (of group ``g(n)``: the heads of a group read the same ``B`` and
``C``), with state ``S`` in R^(P x N), ``S = 0`` at a segment's start:

    S_t = exp(Delta_t A_n) S_(t-1) + Delta_t x_t B_t^T        A_n < 0
    y_t = S_t C_t                       (the ``D`` skip is the caller's)

run chunk by chunk.  Inside a chunk of ``C`` tokens, with ``l_t`` the running
sum of ``Delta A`` from the chunk's start,

    L[t, s]  = exp(l_t - l_s)   for s <= t in the same segment, else 0
    Y_intra  = ((C B^T) * L) (Delta * X)                  the chunk's own tokens
    Xe[s]    = exp(l_end - l_s) Delta_s x_s               0 unless s is of the
                                                          chunk's LAST segment
    from[t]  = exp(l_t)         0 once a boundary has passed since the start

and across chunks a sequential pass that carries the state (stored transposed,
``S`` in R^(N x P)):

    O_c = C_c S_(c-1)           S_c = a_c S_(c-1) + B_c^T Xe_c,  a_c = from[end]
    Y   = Y_intra + from * O

``intra`` is batched matmuls (``jax.named_scope("ssm.intra")``), differentiated
by JAX.  The sequential pass is either ``chunk_scan`` (a ``lax.scan`` over
chunks, differentiated by JAX: the CPU path) or ``chunk_pallas``: two Pallas
kernels, ``ssd_chunk_fwd`` and ``ssd_chunk_bwd``, under one ``custom_vjp``,
with the state in VMEM, the chunks of a head in grid order and ``B`` / ``C``
fetched once for the heads of a group that a grid step works on.  Everything
here is float32; the matrix products ask for ``Precision.HIGHEST`` (the state,
the decay sums and ``Delta`` never pass through bf16).

A segment boundary is a decay of exactly zero: ``L``, ``Xe`` and ``from`` are
built from the running sum and masked by the segment ids, so tokens of
different segments packed into one row never see each other's state.  The
decays are exponentials of DIFFERENCES of the running sum (never a quotient of
two exponentials), so a chunk whose own decay underflows stays exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops.gdn import use_pallas

HIGHEST = jax.lax.Precision.HIGHEST

#: segment id no token carries: the "previous chunk" of a row's first chunk
NO_SEGMENT = -2


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def intra(x, dt, a, b, c, seg, chunk: int):
    """Per-chunk quantities from per-token inputs.

    x: [B, T, H, P]; dt (Delta, positive): [B, T, H]; a: [H] (negative);
    b, c: [B, T, G, N] with H a multiple of G; seg: [B, T] int32.  Returns
    ``(Y_intra [B,H,NC,C,P], from [B,H,NC,C])`` and the inputs of the
    sequential pass ``(Cc, Bc [B,G,NC,C,N], Xe [B,H,NC,C,P], a_c [B,H,NC])``."""
    B, T, H, _ = x.shape
    G = b.shape[2]
    C = chunk
    NC = T // C
    if NC * C != T:
        raise ValueError(f"row length {T} is not a multiple of chunk {C}")
    if H % G:
        raise ValueError(f"{H} heads are not a multiple of {G} groups")

    def chunks(t):  # [B, T, n, d] -> [B, n, NC, C, d]
        return t.reshape(B, NC, C, t.shape[2], -1).transpose(0, 3, 1, 2, 4)

    with jax.named_scope("ssm.intra"):
        xd = chunks(dt[..., None] * x)
        bc, cc = chunks(b), chunks(c)
        lc = jnp.cumsum(chunks((dt * a)[..., None])[..., 0], axis=-1)  # [B,H,NC,C]
        sc = seg.reshape(B, 1, NC, C)
        # the segment of the token before each chunk
        prev = jnp.concatenate(
            [jnp.full((B, 1, 1), NO_SEGMENT, seg.dtype), sc[:, :, :-1, -1]], axis=2)
        carry = (sc == prev[..., None]).astype(jnp.float32)  # [B,1,NC,C]
        keep = jnp.tril(jnp.ones((C, C), bool)) & (
            sc[..., :, None] == sc[..., None, :])  # [B,1,NC,C,C]
        L = jnp.exp(jnp.where(
            keep, lc[..., :, None] - lc[..., None, :], -jnp.inf))  # [B,H,NC,C,C]
        cb = _mm(cc, jnp.swapaxes(bc, -1, -2))  # [B,G,NC,C,C], once a group
        y = _mm(jnp.repeat(cb, H // G, axis=1) * L, xd)
        xe = L[..., -1, :, None] * xd
        start = jnp.exp(lc) * carry  # decay from the chunk's start
    return (y, start), (cc, bc, xe, start[..., -1])


def _per_head(t, heads: int):
    """[B, G, ...] -> [B, H, ...]: each group's entry for each of its heads."""
    return jnp.repeat(t, heads // t.shape[1], axis=1)


def chunk_scan(cc, bc, xe, ac):
    """The sequential pass as a ``lax.scan`` over chunks -> O [B,H,NC,C,P]."""
    B, H, NC, C, P = xe.shape
    N = cc.shape[-1]

    def step(S, inp):
        c, b, x, a = inp
        o = _mm(c, S)
        return a[..., None, None] * S + _mm(jnp.swapaxes(b, -1, -2), x), o

    xs = tuple(
        jnp.moveaxis(t, 2, 0)
        for t in (_per_head(cc, H), _per_head(bc, H), xe, ac))
    with jax.named_scope("ssm.chunk"):
        _, o = jax.lax.scan(step, jnp.zeros((B, H, N, P), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 2)


# ---------------------------------------------------------------------------
# the sequential pass as Pallas kernels


def _dot(a, b):
    return jnp.dot(a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a [m, n] . b [p, n]^T -> [m, p]"""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )


def _fwd_kernel(hb, c_ref, bt_ref, xe_ref, a_ref, o_ref, s_ref, s_scr):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    c, bt = c_ref[0, 0], bt_ref[0, 0]
    for h in range(hb):
        S = s_scr[h]
        s_ref[h, 0] = S  # the state at the chunk's start, for the backward
        o_ref[h, 0] = _dot(c, S)
        s_scr[h] = a_ref[h, 0] * S + _dot(bt, xe_ref[h, 0])


def _bwd_kernel(hb, b_ref, ct_ref, xe_ref, a_ref, s_ref, do_ref,
                dxe_ref, db_ref, dc_ref, da_ref, ds_scr):
    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    b, ct = b_ref[0, 0], ct_ref[0, 0]
    db = dc = None
    for h in range(hb):
        S = s_ref[h, 0]
        dS = ds_scr[h]
        do = do_ref[h, 0]
        dxe_ref[h, 0] = _dot(b, dS)
        da_ref[h, 0] = jnp.sum(dS * S, axis=0, keepdims=True)
        # B and C are the block's heads' alike: their gradients add up here
        part_b, part_c = _dot_nt(xe_ref[h, 0], dS), _dot_nt(do, S)
        db = part_b if db is None else db + part_b
        dc = part_c if dc is None else dc + part_c
        ds_scr[h] = a_ref[h, 0] * dS + _dot(ct, do)
    db_ref[0, 0] = db
    dc_ref[0, 0] = dc


def heads_per_block(heads_a_group: int) -> int:
    """Heads a grid step works on side by side (independent chains for the
    scheduler to interleave, one fetch of the group's B and C for all of
    them): the largest of 4, 2 that divides the heads of a group."""
    return next((hb for hb in (4, 2) if heads_a_group % hb == 0), 1)


def _spec(n, rows, cols, nc=None, of_block=None):
    """A block of ``n`` entries of the leading axis and one chunk; ``nc``:
    the chunks in reverse; ``of_block``: leading index from the grid's."""
    of_block = of_block or (lambda i: i)
    if nc is None:
        return pl.BlockSpec((n, 1, rows, cols), lambda i, c: (of_block(i), c, 0, 0))
    return pl.BlockSpec(
        (n, 1, rows, cols), lambda i, c: (of_block(i), nc - 1 - c, 0, 0))


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _fwd_call(hpg, cc, bt, xe, av, interpret):
    F, NC, C, P = xe.shape
    N = cc.shape[-1]
    hb = heads_per_block(hpg)
    group = lambda i: i * hb // hpg  # noqa: E731
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb),
        grid=(F // hb, NC),
        in_specs=[
            _spec(1, C, N, of_block=group), _spec(1, N, C, of_block=group),
            _spec(hb, C, P), _spec(hb, 1, P),
        ],
        out_specs=[_spec(hb, C, P), _spec(hb, N, P)],
        out_shape=[
            jax.ShapeDtypeStruct((F, NC, C, P), f32),
            jax.ShapeDtypeStruct((F, NC, N, P), f32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, N, P), f32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ssd_chunk_fwd",
    )(cc, bt, xe, av)


def _bwd_call(hpg, bc, ct, xe, av, S, dO, interpret):
    F, NC, C, P = xe.shape
    N = bc.shape[-1]
    hb = heads_per_block(hpg)
    group = lambda i: i * hb // hpg  # noqa: E731
    f32 = jnp.float32
    r = NC
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hb),
        grid=(F // hb, NC),
        in_specs=[
            _spec(1, C, N, r, group), _spec(1, N, C, r, group),
            _spec(hb, C, P, r), _spec(hb, 1, P, r), _spec(hb, N, P, r),
            _spec(hb, C, P, r),
        ],
        out_specs=[
            _spec(hb, C, P, r), _spec(1, C, N, r), _spec(1, C, N, r),
            _spec(hb, 1, P, r),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((F, NC, C, P), f32),
            jax.ShapeDtypeStruct((F // hb, NC, C, N), f32),
            jax.ShapeDtypeStruct((F // hb, NC, C, N), f32),
            jax.ShapeDtypeStruct((F, NC, 1, P), f32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, N, P), f32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ssd_chunk_bwd",
    )(bc, ct, xe, av, S, dO)


def _flat(x):  # [B, n, NC, ...] -> [B*n, NC, ...]
    return x.reshape((-1,) + x.shape[2:])


def _decay_rows(a, P):  # [B, H, NC] -> [B*H, NC, 1, P]
    return jnp.broadcast_to(_flat(a)[..., None, None], _flat(a).shape + (1, P))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def chunk_pallas(cc, bc, xe, ac, interpret=False):
    """The sequential pass in the Pallas kernels -> O [B,H,NC,C,P]."""
    return _chunk_pallas_fwd(cc, bc, xe, ac, interpret)[0]


def _chunk_pallas_fwd(cc, bc, xe, ac, interpret):
    hpg = xe.shape[1] // cc.shape[1]
    with jax.named_scope("ssm.chunk"):
        o, S = _fwd_call(
            hpg, _flat(cc), jnp.swapaxes(_flat(bc), -1, -2), _flat(xe),
            _decay_rows(ac, xe.shape[-1]), interpret)
    return o.reshape(xe.shape), (cc, bc, xe, ac, S)


def _chunk_pallas_bwd(interpret, res, dO):
    cc, bc, xe, ac, S = res
    H, G = xe.shape[1], cc.shape[1]
    with jax.named_scope("ssm.chunk"):
        dxe, db, dc, da = _bwd_call(
            H // G, _flat(bc), jnp.swapaxes(_flat(cc), -1, -2), _flat(xe),
            _decay_rows(ac, xe.shape[-1]), S, _flat(dO), interpret)

    def group_sum(t):  # [B*H/hb, NC, C, N]: the blocks of a group add up
        return t.reshape(cc.shape[:2] + (-1,) + cc.shape[2:]).sum(axis=2)

    return (
        group_sum(dc), group_sum(db), dxe.reshape(xe.shape),
        da.sum(axis=(-1, -2)).reshape(ac.shape),
    )


chunk_pallas.defvjp(_chunk_pallas_fwd, _chunk_pallas_bwd)


def ssd(x, dt, a, b, c, seg, chunk: int = 128, impl: str | None = None):
    """``y_t = S_t C_t`` [B, T, H, P] of the selective state space over packed
    rows (without the ``D`` skip).

    ``impl``: ``"pallas"`` (the kernels), ``"interpret"`` (the kernels in
    Pallas' interpreter: tests), ``"scan"`` (``lax.scan`` over chunks);
    ``None`` chooses by backend."""
    if impl is None:
        impl = "pallas" if use_pallas() else "scan"
    B, T, H, P = x.shape
    (y, start), parts = intra(x, dt, a, b, c, seg, chunk)
    if impl in ("pallas", "interpret"):
        o = chunk_pallas(*parts, impl == "interpret")
    elif impl == "scan":
        o = chunk_scan(*parts)
    else:
        raise ValueError(f"unknown state-space implementation {impl!r}")
    y = y + start[..., None] * o
    # [B, H, NC, C, P] -> [B, T, H, P]
    return y.transpose(0, 2, 3, 1, 4).reshape(B, T, H, P)
