"""Gated delta rule (Gated DeltaNet, arXiv:2412.06464) in chunkwise form.

Per head, with state ``S`` in R^(d_v x d_k), ``S = 0`` at a segment's start:

    S_t = alpha_t S_(t-1) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t                       alpha_t = exp(g_t), g_t <= 0

run chunk by chunk (arXiv:2406.06484 sec. 3, the WY form): inside a chunk of
``C`` tokens, with ``R[i, j]`` the decay from token j to token i (0 across a
segment boundary) and ``gamma_i`` the decay from the chunk's start to token i
(0 once a boundary has passed),

    A = tril(diag(beta) (K K^T * R), -1)        T = (I + A)^-1 diag(beta)
    U = T V          W = T (K * gamma)          pseudo-values and their keys
    P = tril(Q K^T * R)                         the chunk's own attention

and across chunks a sequential pass that carries the state (here stored
transposed, ``S`` in R^(d_k x d_v)):

    V' = U - W S        O = (Q * gamma) S + P V'
    S <- gamma_C S + (K * R[C, :])^T V'

``intra`` is batched matmuls (``jax.named_scope("gdn.intra")``), differentiated
by JAX.  The sequential pass is either ``chunk_scan`` (a ``lax.scan`` over
chunks, differentiated by JAX: the CPU path) or ``chunk_pallas``: two Pallas
kernels, ``gdn_chunk_fwd`` and ``gdn_chunk_bwd``, under one ``custom_vjp``,
with the state in VMEM and the chunks of a head in grid order.  Everything
here is float32; the matrix products ask for ``Precision.HIGHEST`` (the
state, the decay sums and the triangular inverse never pass through bf16).

A segment boundary is a decay of exactly zero: ``R`` and ``gamma`` are built
from the running sum of ``g`` and masked by the segment ids, so tokens of
different segments packed into one row never see each other's state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST

#: segment id no token carries: the "previous chunk" of a row's first chunk
NO_SEGMENT = -2


def use_pallas() -> bool:
    """The sequence path's ONE backend choice: on a TPU the kernels (the
    sequential pass of the delta rule here and of ``ops/ssd``, the grouped
    products of ``ops/moe``, ``seqmodel._attend``'s splash attention),
    everywhere else their plain forms (the way ``ops/als._use_pallas``
    chooses, which also asks the rank)."""
    return jax.default_backend() == "tpu"


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def _unit_lower_inverse(a):
    """``(I + A)^-1`` for strictly lower triangular ``A`` [..., C, C] by block
    doubling: with ``M`` the inverse of the diagonal blocks of size b (exact
    for b = 1: the identity) and ``A_off`` the part of ``A`` that joins the
    two halves of each diagonal block of size 2b,

        [[L11, 0], [L21, L22]]^-1 = [[M11, 0], [-M22 L21 M11, M22]]
        M <- M - M A_off M

    log2(C) rounds of two matmuls over whole [C, C] matrices (masks, no
    slices).  Not the finite Neumann product ``(I - A)(I + A^2)(I + A^4)...``,
    which costs the same: its powers of ``A`` grow combinatorially when a
    chunk's keys are alike (the same item again and again: ``A`` near ``beta``
    everywhere below the diagonal) and float32 cannot cancel them."""
    c = a.shape[-1]
    at = jnp.arange(c)
    inv = jnp.eye(c, dtype=a.dtype)
    b = 1
    while b < c:
        joins = (at[:, None] // (2 * b) == at[None, :] // (2 * b)) & (
            at[:, None] // b != at[None, :] // b)
        inv = inv - _mm(_mm(inv, jnp.where(joins, a, 0.0)), inv)
        b *= 2
    return inv


def intra(q, k, v, g, beta, seg, chunk: int):
    """Per-chunk WY quantities from per-token inputs.

    q, k: [B, T, H, dk]; v: [B, T, H, dv]; g, beta: [B, T, H]; seg: [B, T]
    int32.  Returns ``(W, U, Qg, P, Kd, a)`` shaped [B, H, NC, C, *] (``a``:
    [B, H, NC]), the inputs of the sequential pass."""
    B, T, H, dk = q.shape
    C = chunk
    NC = T // C
    if NC * C != T:
        raise ValueError(f"row length {T} is not a multiple of chunk {C}")

    def chunks(x):  # [B, T, H, d] -> [B, H, NC, C, d]
        return x.reshape(B, NC, C, H, -1).transpose(0, 3, 1, 2, 4)

    with jax.named_scope("gdn.intra"):
        qc, kc, vc = chunks(q), chunks(k), chunks(v)
        gc = jnp.cumsum(chunks(g[..., None])[..., 0], axis=-1)  # [B,H,NC,C]
        bc = chunks(beta[..., None])[..., 0]
        sc = seg.reshape(B, 1, NC, C)
        # the segment of the token before each chunk
        prev = jnp.concatenate(
            [jnp.full((B, 1, 1), NO_SEGMENT, seg.dtype), sc[:, :, :-1, -1]], axis=2
        )
        carry = (sc == prev[..., None]).astype(jnp.float32)  # [B,1,NC,C]
        tri = jnp.tril(jnp.ones((C, C), bool))
        same = sc[..., :, None] == sc[..., None, :]
        keep = tri & same  # [B,1,NC,C,C]
        diff = gc[..., :, None] - gc[..., None, :]
        R = jnp.exp(jnp.where(keep, diff, -jnp.inf))  # [B,H,NC,C,C]
        gam = jnp.exp(gc) * carry  # decay from the chunk's start, 0 past a boundary
        kk = _mm(kc, jnp.swapaxes(kc, -1, -2))
        strict = jnp.tril(jnp.ones((C, C), jnp.float32), -1)
        A = bc[..., None] * kk * R * strict
        Tm = _unit_lower_inverse(A)
        U = _mm(Tm, bc[..., None] * vc)
        W = _mm(Tm, (bc * gam)[..., None] * kc)
        Qg = qc * gam[..., None]
        P = _mm(qc, jnp.swapaxes(kc, -1, -2)) * R
        Kd = kc * R[..., -1, :, None]
        a = gam[..., -1]
    return W, U, Qg, P, Kd, a


def chunk_scan(W, U, Qg, P, Kd, a):
    """The sequential pass as a ``lax.scan`` over chunks -> O [B,H,NC,C,dv]."""
    B, H, NC, C, dk = W.shape
    dv = U.shape[-1]

    def step(S, x):
        w, u, qg, p, kd, ac = x
        v_new = u - _mm(w, S)
        o = _mm(qg, S) + _mm(p, v_new)
        S = ac[..., None, None] * S + _mm(jnp.swapaxes(kd, -1, -2), v_new)
        return S, o

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (W, U, Qg, P, Kd, a))
    with jax.named_scope("gdn.chunk"):
        _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, dv), jnp.float32), xs)
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


def _fwd_kernel(hb, w_ref, u_ref, qg_ref, p_ref, kdt_ref, a_ref,
                o_ref, s_ref, s_scr):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    for h in range(hb):
        S = s_scr[h]
        s_ref[h, 0] = S  # the state at the chunk's start, for the backward
        v_new = u_ref[h, 0] - _dot(w_ref[h, 0], S)
        o_ref[h, 0] = _dot(qg_ref[h, 0], S) + _dot(p_ref[h, 0], v_new)
        s_scr[h] = a_ref[h, 0] * S + _dot(kdt_ref[h, 0], v_new)


def _bwd_kernel(hb, w_ref, u_ref, wt_ref, qgt_ref, pt_ref, kd_ref, a_ref,
                s_ref, do_ref,
                dw_ref, du_ref, dqg_ref, dp_ref, dkd_ref, da_ref, ds_scr):
    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    for h in range(hb):
        S = s_ref[h, 0]
        dS = ds_scr[h]
        do = do_ref[h, 0]
        v_new = u_ref[h, 0] - _dot(w_ref[h, 0], S)
        dv = _dot(pt_ref[h, 0], do) + _dot(kd_ref[h, 0], dS)
        dp_ref[h, 0] = _dot_nt(do, v_new)
        dqg_ref[h, 0] = _dot_nt(do, S)
        dkd_ref[h, 0] = _dot_nt(v_new, dS)
        da_ref[h, 0] = jnp.sum(dS * S, axis=0, keepdims=True)
        du_ref[h, 0] = dv
        dw_ref[h, 0] = -_dot_nt(dv, S)
        ds_scr[h] = (
            a_ref[h, 0] * dS + _dot(qgt_ref[h, 0], do) - _dot(wt_ref[h, 0], dv)
        )


def heads_per_block(n: int) -> int:
    """Heads a grid step works on side by side (independent chains for the
    scheduler to interleave): the largest of 5, 4, 3, 2 that divides n."""
    return next((hb for hb in (5, 4, 3, 2) if n % hb == 0), 1)


def _spec(hb, rows, cols, reverse_nc=None):
    if reverse_nc is None:
        return pl.BlockSpec((hb, 1, rows, cols), lambda i, c: (i, c, 0, 0))
    last = reverse_nc - 1
    return pl.BlockSpec((hb, 1, rows, cols), lambda i, c: (i, last - c, 0, 0))


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _fwd_call(W, U, Qg, P, KdT, av, interpret):
    G, NC, C, dk = W.shape
    dv = U.shape[-1]
    hb = heads_per_block(G)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb),
        grid=(G // hb, NC),
        in_specs=[
            _spec(hb, C, dk), _spec(hb, C, dv), _spec(hb, C, dk),
            _spec(hb, C, C), _spec(hb, dk, C), _spec(hb, 1, dv),
        ],
        out_specs=[_spec(hb, C, dv), _spec(hb, dk, dv)],
        out_shape=[
            jax.ShapeDtypeStruct((G, NC, C, dv), f32),
            jax.ShapeDtypeStruct((G, NC, dk, dv), f32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), f32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="gdn_chunk_fwd",
    )(W, U, Qg, P, KdT, av)


def _bwd_call(W, U, WT, QgT, PT, Kd, av, S, dO, interpret):
    G, NC, C, dk = W.shape
    dv = U.shape[-1]
    hb = heads_per_block(G)
    f32 = jnp.float32
    r = NC
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hb),
        grid=(G // hb, NC),
        in_specs=[
            _spec(hb, C, dk, r), _spec(hb, C, dv, r), _spec(hb, dk, C, r),
            _spec(hb, dk, C, r), _spec(hb, C, C, r), _spec(hb, C, dk, r),
            _spec(hb, 1, dv, r), _spec(hb, dk, dv, r), _spec(hb, C, dv, r),
        ],
        out_specs=[
            _spec(hb, C, dk, r), _spec(hb, C, dv, r), _spec(hb, C, dk, r),
            _spec(hb, C, C, r), _spec(hb, C, dk, r), _spec(hb, 1, dv, r),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, NC, C, dk), f32),
            jax.ShapeDtypeStruct((G, NC, C, dv), f32),
            jax.ShapeDtypeStruct((G, NC, C, dk), f32),
            jax.ShapeDtypeStruct((G, NC, C, C), f32),
            jax.ShapeDtypeStruct((G, NC, C, dk), f32),
            jax.ShapeDtypeStruct((G, NC, 1, dv), f32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), f32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="gdn_chunk_bwd",
    )(W, U, WT, QgT, PT, Kd, av, S, dO)


def _flat(x):  # [B, H, NC, ...] -> [B*H, NC, ...]
    return x.reshape((-1,) + x.shape[2:])


def _decay_rows(a, dv):  # [B, H, NC] -> [B*H, NC, 1, dv]
    return jnp.broadcast_to(_flat(a)[..., None, None], _flat(a).shape + (1, dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def chunk_pallas(W, U, Qg, P, Kd, a, interpret=False):
    """The sequential pass in the Pallas kernels -> O [B,H,NC,C,dv]."""
    return _chunk_pallas_fwd(W, U, Qg, P, Kd, a, interpret)[0]


def _chunk_pallas_fwd(W, U, Qg, P, Kd, a, interpret):
    with jax.named_scope("gdn.chunk"):
        o, S = _fwd_call(
            _flat(W), _flat(U), _flat(Qg), _flat(P),
            jnp.swapaxes(_flat(Kd), -1, -2), _decay_rows(a, U.shape[-1]),
            interpret,
        )
    return o.reshape(U.shape), (W, U, Qg, P, Kd, a, S)


def _chunk_pallas_bwd(interpret, res, dO):
    W, U, Qg, P, Kd, a, S = res
    t = lambda x: jnp.swapaxes(_flat(x), -1, -2)  # noqa: E731
    with jax.named_scope("gdn.chunk"):
        dW, dU, dQg, dP, dKd, da = _bwd_call(
            _flat(W), _flat(U), t(W), t(Qg), t(P), _flat(Kd),
            _decay_rows(a, U.shape[-1]), S, _flat(dO), interpret,
        )
    return (
        dW.reshape(W.shape), dU.reshape(U.shape), dQg.reshape(Qg.shape),
        dP.reshape(P.shape), dKd.reshape(Kd.shape),
        da.sum(axis=(-1, -2)).reshape(a.shape),
    )


chunk_pallas.defvjp(_chunk_pallas_fwd, _chunk_pallas_bwd)


def gated_delta_rule(q, k, v, g, beta, seg, chunk: int = 64, impl: str | None = None):
    """o [B, T, H, dv] of the gated delta rule over packed rows.

    ``impl``: ``"pallas"`` (the kernels), ``"interpret"`` (the kernels in
    Pallas' interpreter: tests), ``"scan"`` (``lax.scan`` over chunks);
    ``None`` chooses by backend."""
    if impl is None:
        impl = "pallas" if use_pallas() else "scan"
    B, T, H, _ = q.shape
    parts = intra(q, k, v, g, beta, seg, chunk)
    if impl in ("pallas", "interpret"):
        o = chunk_pallas(*parts, impl == "interpret")
    elif impl == "scan":
        o = chunk_scan(*parts)
    else:
        raise ValueError(f"unknown gated delta rule implementation {impl!r}")
    # [B, H, NC, C, dv] -> [B, T, H, dv]
    return o.transpose(0, 2, 3, 1, 4).reshape(B, T, H, -1)
