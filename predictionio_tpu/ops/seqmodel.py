"""Hybrid sequence models, trainable on packed rows of tokens: five blocks.

Layers follow ``layer_types``; the block's FORM is a property of the kind, and
a kind is ONE entry of ``LAYER_KINDS``: its tensors, the sizes it needs, its
row-length multiple, its function and what that records.  Each kind's form is
described where it is written: ``linear_attention`` and ``full_attention``
(the Olmo-Hybrid block), ``parallel_layer`` (Falcon-H1), ``routed_layer``
(SmallThinker), ``sandwich_layer`` with ``looped_row_grads`` (Ouro, a looped
model), ``sublayer`` (the Nemotron-H stack's three kinds).

**The share.**  A deployment divides every layer over ``chips`` chips; this
process holds one share of it: ``heads`` of the attention heads, ``mlp_cols``
of the MLP's columns (or ``experts_held`` of the experts, from
``expert_start``; the router and its selection bias whole; ``shared_cols`` of
a shared expert's columns), ``ssm_heads`` of the state-space heads in
``ssm_groups`` of the groups, ``vocab_rows`` rows of the embedding and
the head, starting at ``vocab_start``.  Every function computes the part of the result
its share gives (the sum over its heads of ``o_h W_o[h]``, the sum over its
MLP columns, logits and loss over its vocabulary rows; an id outside its rows
embeds to zero).  Nothing stands in for the other shares or their exchange.
One statistic of the parallel block crosses chips, the gated norm's mean
square (a group's channels may lie on several chips): ``gated_group_norm``
takes the mapped axis to reduce it over, ``None`` on one chip, where the mean
is over the channels held.

**Precision.**  Master weights, gradients and Adam moments float32.  The large
matrix products take bfloat16 inputs and accumulate in float32, in the forward
and both backward products (``mm``).  Residual stream, norms, the
convolution, the decay projections and everything of the delta rule float32;
the router's logits, its scores, its top-k and the chosen experts' weights
float32 at ``Precision.HIGHEST``; so are the state a looped model carries from pass to
pass, its gates' product, the exit distribution, its entropy and the
combination of the exits' losses.

**Training.**  ``train_steps`` dispatches, for each optimiser step, the rows of
the step one at a time (forward, per-layer recomputation, backward; gradients
accumulated in place) and then AdamW; buffers are donated from program to
program and nothing returns to the host between steps.  Each row also hands
back the first layer's recurrence (the delta rule's output, the state
space's ``S_t C_t``) along a seeded vector (``trunk``): what the training
record holds the carried state's precision by.  A routed block hands back
instead the first layer's experts applied to ``h`` (exact on both sides of a
comparison) along a seeded vector, every layer's choices, and the pairs each
held expert computed, which the step's accumulator sums beside the gradients.
A stack of one-sublayer kinds hands back the first state-space layer's
``S_t C_t`` with the routed layers' choices and pairs, the counters summed
over the layers that route and not over all of them; the first experts
layer's ``f`` applied to the embedded rows under its own norm (exact inputs,
which the stream that layer reads is not) comes from a small program of its
own run on the first step's rows, with the experts' backward on the first of
them (``experts_probe``).
A looped block hands back each position's exit distribution, the mean square
of the state each later pass read, and at a few positions the exit states
with their cross-entropies; the step's accumulator sums every exit's loss, its
mass, the entropy and the layer applications made.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.ops import gdn, moe, ssd

HIGHEST = jax.lax.Precision.HIGHEST

LINEAR = "linear_attention"
FULL = "full_attention"
PARALLEL = "parallel_ssm_attention"
GLOBAL_MOE = "global_attention_moe"
SLIDING_MOE = "sliding_attention_moe"
SANDWICH = "sandwich_attention"
STATE_SPACE = "state_space"
GROUPED_ATTENTION = "grouped_attention"
SHARED_EXPERTS = "shared_routed_experts"

#: segment id of a row's padding (real segments count from 0)
PAD_SEGMENT = -1

#: the top-level ``jax.named_scope`` names of a retrain's three programs (the
#: row program ``accumulate_row``, the step ``apply_step``, the
#: initialisation ``init_state``): every device operation of them carries
#: exactly ONE of these in its op name, so they partition the device's busy
#: time and ``(no scope)`` in a trace holds only what the program did not
#: write (docs/observability.md; ``tests/test_sequence_scopes.py`` holds both)
SCOPES = (
    "seq.embed", "seq.gdn", "seq.ssm", "seq.moe", "seq.attn", "seq.mlp",
    "seq.loss",
    # a looped model's gates, exit distribution, entropy and the combination
    # of its exits' losses, with their backward (the losses stay ``seq.loss``)
    "seq.exit",
    # what ``layer`` / ``routed_layer`` / ``trunk`` do to the residual stream
    # between the mixers: the norms on it and the residual adds, and through
    # those the adds of the stream's gradient
    "seq.stream",
    # a row's gradients, loss, count and routing counters into the step's sums
    "seq.accumulate",
    # the whole of ``apply_step``: norms, probes, the routing record, AdamW
    "seq.step",
    # the seeded draws of ``init_params``, the zeros of moments and sums
    "seq.init",
)

#: the component around pass t of a looped model's trunk, OUTSIDE the
#: top-level scope of its operations: ``loop.pass2/seq.attn/attn.causal``
LOOP_PASS = "loop.pass{}"
#: positions of a row, evenly spaced, whose exit states a looped model's
#: training record keeps beside their cross-entropies (``head_probe``)
HEAD_PROBE_POSITIONS = 32

#: what the large matrix products round their inputs to (the configuration's
#: stated precision; tests set float32 to compare with the plain reference
#: to rounding error)
MATMUL_DTYPE = jnp.bfloat16


@dataclasses.dataclass(frozen=True)
class MuP:
    """muP's forward multipliers as a published config carries them (Falcon-H1:
    twelve in a layer, five of them a vector over the zones of the state-space
    projection's output, and two at the embedding and the head).  1 changes
    nothing, and then nothing is multiplied."""

    embedding: float = 1.0
    lm_head: float = 1.0
    ssm_in: float = 1.0
    #: the zones z, x, B, C, dt of the state-space projection's output
    ssm_zones: tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    ssm_out: float = 1.0
    attention_in: float = 1.0
    attention_out: float = 1.0
    key: float = 1.0
    mlp_gate: float = 1.0
    mlp_down: float = 1.0

    def __post_init__(self):
        object.__setattr__(self, "ssm_zones", tuple(float(m) for m in self.ssm_zones))
        if len(self.ssm_zones) != 5:
            raise ValueError("ssm_zones multiplies the five zones z, x, B, C, dt")


def _scaled(x, m: float):
    return x if m == 1.0 else x * m


@dataclasses.dataclass(frozen=True)
class SeqConfig:
    """Widths as published, counts as HELD by this share.  A layer's kind says
    which of the sizes it reads: its entry of ``LAYER_KINDS`` has the tensors
    it makes of them and the check that they are there."""

    hidden: int
    layer_types: tuple[str, ...]
    #: full-attention heads held, and their size
    heads: int
    head_dim: int
    #: linear-attention heads held (keys and values alike), and their sizes
    lin_heads: int
    lin_key_dim: int
    lin_value_dim: int
    conv_width: int
    #: MLP columns held
    mlp_cols: int
    #: vocabulary rows held: ids ``vocab_start .. vocab_start + vocab_rows``
    vocab_rows: int
    vocab_start: int = 0
    eps: float = 1e-6
    neg_eigval: bool = True
    chunk: int = 64
    #: token block of the loss: logits of this many tokens at a time
    loss_block: int = 2048
    #: sequential pass of the delta rule: None = by backend (ops/gdn.py)
    gdn_impl: str | None = None
    #: attention: None = by backend; "flash" = the TPU kernel (splash
    #: attention), "interpret" = it under the interpreter, "dense" = jax.numpy
    attn_impl: str | None = None
    #: KV heads held by the parallel block's attention (None: one a query head)
    kv_heads: int | None = None
    #: base of its rotary positions
    rope_theta: float = 10000.0
    #: state-space heads held, a head's channels, the state's size, the B / C
    #: groups held (the heads divide evenly over them)
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv_width: int = 4
    ssm_chunk: int = 128
    #: sequential pass of the state space: None = by backend (ops/ssd.py)
    ssm_impl: str | None = None
    mup: MuP = MuP()
    #: the routed blocks: the router's width (ALL experts), the experts held
    #: here and the first of them, experts a token, an expert's width
    experts: int = 0
    experts_held: int = 0
    expert_start: int = 0
    experts_per_token: int = 0
    expert_width: int = 0
    #: keys a query of a sliding layer sees, itself included
    window: int = 0
    #: rows of a tile of (token, expert) pairs (ops/moe.py)
    moe_tile: int = 256
    #: the grouped products: None = by backend (ops/moe.py)
    moe_impl: str | None = None
    #: times the layer list is applied, with the same tensors (a looped
    #: model's ``total_ut_steps``); more than 1 brings an exit after every pass
    loop_steps: int = 1
    #: weight of the exit distribution's entropy in a looped model's loss
    exit_beta: float = 0.1
    #: ``"shared_routed_experts"`` layers: the shared expert's columns held,
    #: and what a token's chosen weights sum to
    shared_cols: int = 0
    routed_scale: float = 1.0

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - set(LAYER_KINDS)
        if bad:
            raise ValueError(
                f"unknown layer types {sorted(bad)}: the kinds are {list(LAYER_KINDS)}")
        if self.loop_steps < 1:
            raise ValueError("loop_steps counts the passes: at least 1")
        if self.loop_steps > 1 and set(self.layer_types) != {SANDWICH}:
            raise ValueError(f"only a stack of {SANDWICH} layers is looped")
        for kind in dict.fromkeys(self.layer_types):
            for check in LAYER_KINDS[kind].checks:
                check(self)

    @property
    def token_multiple(self) -> int:
        """Row lengths are multiples of this (the recurrences' chunks, the
        windowed attention's smallest block)."""
        return math.lcm(*(
            LAYER_KINDS[kind].token_multiple(self) for kind in self.layer_types))


@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1


#: tensors AdamW does not decay: norms, the decays' parameters, the
#: convolutions with their bias, the state space's skip, the exit gate's
#: bias, the router's selection bias (no gradient reaches it either)
NO_DECAY = ("norm", "a_log", "dt_bias", "conv", "ssm_d", "exit_gate_bias",
            "router_bias")


def decays(name: str) -> bool:
    return not any(part in name for part in NO_DECAY)


# ---------------------------------------------------------------------------
# parameters


def param_shapes(cfg: SeqConfig) -> dict[str, tuple[int, ...]]:
    """Flat name -> shape of every held tensor, in a fixed order: the
    embedding, each layer's tensors as its kind lists them, the final norm,
    the head, a looped model's exit gate."""
    D = cfg.hidden
    shapes: dict[str, tuple[int, ...]] = {"embed": (cfg.vocab_rows, D)}
    for i, kind in enumerate(cfg.layer_types):
        shapes.update({
            f"layer{i}.{leaf}": shape
            for leaf, shape in LAYER_KINDS[kind].tensors(cfg).items()})
    shapes["final_norm"] = (D,)
    shapes["head"] = (cfg.vocab_rows, D)
    if cfg.loop_steps > 1:
        # the one exit gate, a ``Linear(D, 1)`` with bias, read after every pass
        shapes["exit_gate"] = (D,)
        shapes["exit_gate_bias"] = ()
    return shapes


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
@jax.named_scope("seq.init")
def _init_tensor(leaf: str, shape: tuple, conv_width: int, base, n):
    # the scope is written INSIDE the jitted function: one around the call
    # does not reach a program that is dispatched on its own
    key = jax.random.fold_in(base, n)
    if leaf.endswith("norm") or leaf == "ssm_d":
        return jnp.ones(shape, jnp.float32)
    if leaf in ("exit_gate_bias", "router_bias"):
        return jnp.zeros(shape, jnp.float32)
    if "conv" in leaf:
        bound = 1.0 / math.sqrt(conv_width)
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if leaf == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-3, 16.0))
    if leaf == "ssm_a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if leaf.endswith("dt_bias"):
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        dt = jnp.maximum(dt, 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    return 0.02 * jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1))
@jax.named_scope("seq.init")
def _zeros(shape: tuple, dtype):
    return jnp.zeros(shape, dtype)


def init_params(cfg: SeqConfig, seed: int) -> dict[str, jax.Array]:
    """Seeded weights of the held share.  Tensor number n (the order of
    ``param_shapes``) draws from ``fold_in(PRNGKey(seed), n)`` at its held
    shape: matrices normal(0, 0.02); norm weights 1; convolution taps and
    bias uniform(-1/sqrt(width), 1/sqrt(width)) (a depthwise Conv1d's
    default); ``a_log = log(uniform(0.001, 16))`` and ``dt_bias`` the inverse
    softplus of ``exp(uniform(log 0.001, log 0.1))`` floored at 1e-4 (the
    Gated DeltaNet release's initialisation; its ``uniform(0, 16)`` floored so
    that the logarithm is finite); the state space's ``ssm_a_log =
    log(uniform(1, 16))``, ``ssm_dt_bias`` as ``dt_bias`` and ``ssm_d = 1``
    (the Mamba-2 release's); a looped model's exit gate normal(0, 0.02) like
    any matrix, its bias 0; a router's selection bias 0.  One small program a
    tensor: the random bits of one tensor are the only temporary."""
    base = jax.random.PRNGKey(seed)
    out = {}
    for n, (name, shape) in enumerate(param_shapes(cfg).items()):
        leaf = name.rsplit(".", 1)[-1]
        width = cfg.ssm_conv_width if leaf.startswith("ssm_") else cfg.conv_width
        out[name] = _init_tensor(leaf, shape, width, base, n)
    return out


def layer_params(params: dict, i: int) -> dict:
    p = f"layer{i}."
    return {k[len(p):]: v for k, v in params.items() if k.startswith(p)}


# ---------------------------------------------------------------------------
# pieces


@jax.custom_vjp
def mm(x, w):
    """``x @ w`` with bfloat16 inputs and float32 accumulation, in the forward
    and in both products of the backward."""
    return jnp.matmul(
        x.astype(MATMUL_DTYPE), w.astype(MATMUL_DTYPE),
        preferred_element_type=jnp.float32,
    )


def _mm_fwd(x, w):
    return mm(x, w), (x, w)


def _mm_bwd(res, g):
    x, w = res
    gb = g.astype(MATMUL_DTYPE)
    dx = jnp.matmul(
        gb, w.astype(MATMUL_DTYPE).T, preferred_element_type=jnp.float32)
    x2 = x.reshape(-1, x.shape[-1]).astype(MATMUL_DTYPE)
    dw = jnp.matmul(
        x2.T, gb.reshape(-1, gb.shape[-1]), preferred_element_type=jnp.float32)
    return dx.astype(x.dtype), dw.astype(w.dtype)


mm.defvjp(_mm_fwd, _mm_bwd)


def mm_f32(x, w):
    return jnp.matmul(x, w, precision=HIGHEST, preferred_element_type=jnp.float32)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def causal_conv(x, w, seg):
    """Depthwise causal convolution over time, zero history at a segment's
    start.  x: [B, T, ch]; w: [width, ch] (tap ``width - 1`` is the current
    token); seg: [B, T]."""
    width = w.shape[0]
    y = x * w[width - 1]
    for s in range(1, width):
        xs = jnp.pad(x, ((0, 0), (s, 0), (0, 0)))[:, : x.shape[1]]
        ss = jnp.pad(seg, ((0, 0), (s, 0)), constant_values=gdn.NO_SEGMENT)[
            :, : seg.shape[1]]
        y = y + jnp.where((ss == seg)[..., None], xs, 0.0) * w[width - 1 - s]
    return y


def embed(cfg: SeqConfig, table, tokens):
    """Rows of the held slice; an id another share holds embeds to zero."""
    with jax.named_scope("seq.embed"):
        idx = tokens - cfg.vocab_start
        held = (idx >= 0) & (idx < cfg.vocab_rows)
        rows = jnp.take(table, jnp.where(held, idx, 0), axis=0)
        return _scaled(jnp.where(held[..., None], rows, 0.0), cfg.mup.embedding)


#: a linear layer's tensors that are split by head along their LAST axis, the
#: one split along its first, and the one every head shares
_BY_HEAD_COLS = ("q", "k", "v", "g", "a", "b", "conv_q", "conv_k", "conv_v",
                 "a_log", "dt_bias")


def _head_group(p: dict, lo: int, hi: int, heads: int) -> dict:
    """The tensors of heads ``lo .. hi`` of a linear layer."""
    def cols(w):
        per = w.shape[-1] // heads
        return w[..., lo * per : hi * per]

    out = {n: cols(p[n]) for n in _BY_HEAD_COLS}
    per = p["o"].shape[0] // heads
    out["o"] = p["o"][lo * per : hi * per]
    out["o_norm"] = p["o_norm"]
    return out


def delta_inputs(cfg: SeqConfig, p: dict, x, seg):
    """What the delta rule of the heads in ``p`` reads -> (q, k [B, T, H, dk],
    v [B, T, H, dv], g, beta [B, T, H]) and the output gate's projection: q,
    k, v projections, each through a causal depthwise convolution and SiLU; q,
    k L2-normalised per head; ``beta = 2 sigmoid(.)`` (negative eigenvalues)
    or ``sigmoid(.)``; ``g = -exp(A_log) softplus(. + dt_bias)``."""
    B, T, _ = x.shape
    H, dk, dv = p["a_log"].shape[0], cfg.lin_key_dim, cfg.lin_value_dim
    with jax.named_scope("gdn.proj"):
        q, k, v, gate = (mm(x, p[n]) for n in ("q", "k", "v", "g"))
        a, b = mm_f32(x, p["a"]), mm_f32(x, p["b"])
    with jax.named_scope("gdn.conv"):
        q, k, v = (
            jax.nn.silu(causal_conv(t, p[n], seg))
            for t, n in ((q, "conv_q"), (k, "conv_k"), (v, "conv_v"))
        )
        q, k = (t.reshape(B, T, H, dk) for t in (q, k))
        q, k = (
            t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
            for t in (q, k)
        )
        q = q * dk ** -0.5
        beta = jax.nn.sigmoid(b) * (2.0 if cfg.neg_eigval else 1.0)
        g = -jnp.exp(p["a_log"]) * jax.nn.softplus(a + p["dt_bias"])
    return q, k, v.reshape(B, T, H, dv), g, beta, gate.reshape(B, T, H, dv)


#: seed of the probes the training record holds
PROBE_SEED = 1


def delta_probe_vector(dv: int):
    """The seeded direction each head's delta-rule output is recorded along:
    standard normal [dv] from ``fold_in(PRNGKey(PROBE_SEED), 2**20)`` (the
    gradient probes fold in a tensor's number, far below)."""
    return jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(PROBE_SEED), 2 ** 20), (dv,),
        jnp.float32)


def _linear_heads(cfg: SeqConfig, p: dict, x, seg):
    """``sum_h o_h W_o[h]`` over the heads whose tensors ``p`` holds, and the
    delta rule's own output along the probe vector [B, T, H]: what the rule
    made of ITS inputs, before any later rounding."""
    B, T, _ = x.shape
    q, k, v, g, beta, gate = delta_inputs(cfg, p, x, seg)
    o = gdn.gated_delta_rule(q, k, v, g, beta, seg, cfg.chunk, cfg.gdn_impl)
    probe = jax.lax.stop_gradient(
        jnp.einsum("bthv,v->bth", o, delta_probe_vector(o.shape[-1]),
                   precision=HIGHEST))
    with jax.named_scope("gdn.proj"):
        o = rmsnorm(o, p["o_norm"], cfg.eps) * jax.nn.silu(gate)
        return mm(o.reshape(B, T, -1), p["o"]), probe


def linear_attention(cfg: SeqConfig, p: dict, x, seg):
    """The share's part of a gated delta-rule layer's output (before the
    residual norm), the sum over its heads of ``o_h W_o[h]``, and the rule's
    output along the probe vector [B, T, H]: ``delta_inputs``, the gated delta
    rule (``ops/gdn.py``), per-head RMSNorm of the output gated by
    ``SiLU(W_g x)``, output projection.  The heads go through in groups
    of ``gdn.heads_per_block`` (the heads one step of the kernel's grid works
    on side by side: 5 of 15), one group after another, each recomputed in its
    own backward pass: what a group keeps live is that share of the layer's."""
    H = p["a_log"].shape[0]
    per = gdn.heads_per_block(H)
    f = jax.checkpoint(functools.partial(_linear_heads, cfg))
    with jax.named_scope("seq.gdn"):
        y, probes = None, []
        for lo in range(0, H, per):
            part, probe = f(_head_group(p, lo, lo + per, H), x, seg)
            y = part if y is None else y + part
            probes.append(probe)
        return y, jnp.concatenate(probes, axis=-1)


def _dense_attention(q, k, v, seg, scale, window=None):
    """[B, H, T, d] -> causal softmax attention within the segment (and,
    with ``window``, over a query's last ``window`` keys, itself included)."""
    T = q.shape[2]
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    causal = jnp.tril(jnp.ones((T, T), bool))
    if window is not None:
        causal &= ~jnp.tril(jnp.ones((T, T), bool), -window)
    mask = causal & (seg[:, None, :, None] == seg[:, None, None, :])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)


#: splash attention's blocks, the forward and both backward kernels alike,
#: under either mask: queries and keys a grid step, and the keys of one
#: product inside it.  Chosen on the chip over the three cells' rows
#: (``tests/micro_attention_chip.py``; PERF.md section 6, PR 34).
ATTN_BLOCK = 1024
ATTN_BLOCK_COMPUTE = 512
#: the kernels' blocks are multiples of the TPU's lanes
_LANES = 128


def _attn_block(T: int) -> int:
    """The block a row of T goes through in: ``ATTN_BLOCK``, for a shorter
    row the power of two that holds it (whole lanes at least)."""
    return min(ATTN_BLOCK, max(_LANES, 1 << (T - 1).bit_length()))


@functools.lru_cache(maxsize=64)
def _splash_kernel(T: int, rep: int, window: int | None, interpret: bool):
    """The library's block-sparse kernel for ``rep`` query heads on one KV
    head over rows of T (a multiple of ``_attn_block(T)``): a causal mask,
    with ``window`` a local one too (a query's last ``window`` keys); blocks
    outside the mask are skipped; segment ids come with the call.  The cache
    holds the masks' metadata a row length and mask: serving's powers of two
    up to a row of 16,384 under both masks, and the training row's."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    b = _attn_block(T)
    c = min(ATTN_BLOCK_COMPUTE, b)
    of_head = (sm.CausalMask((T, T)) if window is None
               else sm.LocalMask((T, T), (window - 1, 0), 0))
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mqa_single_device(
            sm.MultiHeadMask([of_head] * rep), interpret=interpret,
            block_sizes=sk.BlockSizes(
                block_q=b, block_kv=b, block_kv_compute=c, block_q_dkv=b,
                block_kv_dkv=b, block_kv_dkv_compute=c, block_q_dq=b,
                block_kv_dq=b))


def _repeat_kv(k, v, rep: int):
    """[B, T, KV, d] -> each KV head repeated for the ``rep`` query heads it
    serves (the ``jax.numpy`` path; the kernel takes them as held).  The
    barrier keeps the repeat out of the attention's products: folded into
    them, XLA's CPU backend meets a bf16 dot it cannot run."""
    if rep == 1:
        return k, v
    return jax.lax.optimization_barrier(
        tuple(jnp.repeat(t, rep, axis=2) for t in (k, v)))


def _splash_attention(q, k, v, seg, window: int | None, interpret: bool):
    """``_attend``'s TPU path -> [B, H, T, d].  A KV head's ``rep`` query
    heads go through one multi-query call.  The one place that says what a
    row length the block does not divide meets (``_attn_block``: the full
    block, or for a shorter row the power of two that holds it): the row is
    padded at its end up to a multiple of the block with tokens of
    ``PAD_SEGMENT``, which no real token sees and whose outputs are cut off
    again (rows of the cells, 8192 and 16,384, take the full block and no
    padding; so does every power of two from 128 that serving pads to)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)

    B, T, H, d = q.shape
    KV = k.shape[2]
    rep = H // KV
    pad = -T % _attn_block(T)
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0))) for t in (q, k, v))
        seg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=PAD_SEGMENT)
    kernel = _splash_kernel(T + pad, rep, window, interpret)
    # the kernel takes its scores unscaled: the scale goes into q
    q = (q * d ** -0.5).astype(MATMUL_DTYPE).transpose(0, 2, 1, 3)
    k, v = (t.astype(MATMUL_DTYPE).transpose(0, 2, 1, 3) for t in (k, v))
    of_group = jax.vmap(kernel, in_axes=(0, 0, 0, None))
    o = jax.vmap(of_group)(
        q.reshape(B, KV, rep, T + pad, d), k, v, sk.SegmentIds(q=seg, kv=seg))
    return o.reshape(B, H, T + pad, d)[:, :, :T]


def _attend(cfg: SeqConfig, q, k, v, seg, window: int | None = None):
    """q: [B, T, H, d]; k, v: [B, T, KV, d] as held, or already repeated for
    their query heads (KV = H) -> causal softmax attention within the segment
    (with ``window``, over a query's last ``window`` keys), [B, T, H * d]
    float32.  On a TPU the library's block-sparse splash kernel under a
    causal or a local mask (``_splash_attention``), elsewhere ``jax.numpy``
    under the same mask."""
    B, T, H, d = q.shape
    impl = cfg.attn_impl or ("flash" if gdn.use_pallas() else "dense")
    with jax.named_scope("attn.window" if window is not None else "attn.causal"):
        if impl == "dense":
            k, v = _repeat_kv(k, v, H // k.shape[2])
            q, k, v = (
                t.transpose(0, 2, 1, 3).astype(MATMUL_DTYPE) for t in (q, k, v))
            o = _dense_attention(q, k, v, seg, d ** -0.5, window)
        else:
            o = _splash_attention(q, k, v, seg, window, impl == "interpret")
    return o.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(B, T, H * d)


def full_attention(cfg: SeqConfig, p: dict, x, seg):
    """The share's part of a full-attention layer's output: per-head RMSNorm
    on q and k, no positional encoding, causal softmax within the segment
    (``_attend``: on a TPU no T x T score matrix, blocks above the diagonal
    skipped)."""
    B, T, _ = x.shape
    d = cfg.head_dim
    H = p["q"].shape[1] // d
    with jax.named_scope("seq.attn"):
        q, k, v = (mm(x, p[n]).reshape(B, T, H, d) for n in ("q", "k", "v"))
        q = rmsnorm(q, p["q_norm"], cfg.eps)
        k = rmsnorm(k, p["k_norm"], cfg.eps)
        return mm(_attend(cfg, q, k, v, seg), p["o"])


def segment_positions(seg):
    """[B, T] segment ids -> each token's position within its segment (a
    segment is one run of equal ids): 0 at every segment's first token."""
    at = jnp.arange(seg.shape[1])
    prev = jnp.pad(seg[:, :-1], ((0, 0), (1, 0)), constant_values=gdn.NO_SEGMENT)
    return at - jax.lax.cummax(jnp.where(seg != prev, at, 0), axis=1)


def rope(x, pos, theta: float):
    """Rotary positions over the whole head, channel i paired with i + d/2.
    x: [B, T, H, d] float32; pos: [B, T]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = pos[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(angle)[:, :, None], jnp.sin(angle)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def grouped_query_attention(cfg: SeqConfig, p: dict, h, seg):
    """The share's part of the parallel block's attention mixer: its query
    heads on its KV heads (k and v as held: a KV head's query heads in one
    multi-query call of the kernel ``full_attention`` calls), rotary positions
    over the whole head that RESTART at every segment of a packed row, no
    q / k norm."""
    B, T, _ = h.shape
    d, mup = cfg.head_dim, cfg.mup
    with jax.named_scope("seq.attn"):
        h = _scaled(h, mup.attention_in)
        q, k, v = (mm(h, p[n]).reshape(B, T, -1, d) for n in ("q", "k", "v"))
        with jax.named_scope("attn.rope"):
            pos = segment_positions(seg)
            q = rope(q, pos, cfg.rope_theta)
            k = rope(_scaled(k, mup.key), pos, cfg.rope_theta)
        return _scaled(mm(_attend(cfg, q, k, v, seg), p["o"]), mup.attention_out)


def moe_probe_vector(D: int, n: int = 2):
    """The seeded direction the first routed layer's output is recorded
    along: standard normal [D] from ``fold_in(PRNGKey(PROBE_SEED), 2**20 + 2)``
    (``n`` 3: the one ``experts_probe`` contracts the experts' gradients with)."""
    return jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(PROBE_SEED), 2 ** 20 + n), (D,),
        jnp.float32)


def routed_attention(cfg: SeqConfig, kind: str, p: dict, h, seg):
    """The share's part of a routed block's attention: its query heads on its
    KV heads, no q / k norm, through the attention kernel the other blocks
    call; the global kind causal over the whole segment with NO rotary
    positions; the sliding kind with rotary positions that restart at a
    segment and its window: query t sees key s iff ``0 <= t - s < window`` in
    its segment (a local mask: blocks outside the window are skipped; segment
    ids mask inside a block, no block is skipped for them)."""
    B, T, _ = h.shape
    d = cfg.head_dim
    with jax.named_scope("seq.attn"):
        q, k, v = (mm(h, p[n]).reshape(B, T, -1, d) for n in ("q", "k", "v"))
        window = None
        if kind == SLIDING_MOE:
            window = cfg.window
            with jax.named_scope("attn.rope"):
                pos = segment_positions(seg)
                q, k = rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta)
        return mm(_attend(cfg, q, k, v, seg, window), p["o"])


def routed_layer(cfg: SeqConfig, kind: str, p: dict, x, seg):
    """One ``*_moe`` layer (the SmallThinker block): pre-norm, grouped-query
    attention with no q / k norm, then a layer of routed experts
    (``ops/moe.py``) in place of the MLP, ``x1 = x + attn(h)``,
    ``x2 = x1 + experts(RMSNorm(x1))`` with ``h = RMSNorm(x)``.  The router
    reads ``h``, BEFORE attention (a deployment fetches the chosen experts
    while attention runs): logits over all experts, the ``k`` largest, weights
    ``softmax`` over those ``k``; experts ReGLU, ``W_down(relu(W_gate m) *
    W_up m)`` -> (x after it, (the experts' output on ``h`` along the probe
    vector [B, T, 1], the router's choices [B, T, k], the pairs of each held
    expert [held])).  The probe feeds the experts ``h``, which a
    first layer makes from exact embedding rows in float32: the same on both
    sides of a comparison, as the stream after attention is not."""
    B, T, D = x.shape
    with jax.named_scope("seq.stream"):
        h = rmsnorm(x, p["input_norm"], cfg.eps)
    with jax.named_scope("seq.moe"), jax.named_scope("moe.route"):
        logits = mm_f32(h, p["router"]).reshape(B * T, -1)
    a = routed_attention(cfg, kind, p, h, seg)
    with jax.named_scope("seq.stream"):
        x = x + a
    # ``post_norm`` is what the experts read and stays under their scope, as
    # the q / k norms and ``ssm.norm`` stay under their mixers'
    with jax.named_scope("seq.moe"):
        experts = functools.partial(
            moe.experts_layer, logits=logits,
            valid=(seg != PAD_SEGMENT).reshape(-1), gate=p["experts_gate"],
            up=p["experts_up"], down=p["experts_down"], k=cfg.experts_per_token,
            start=cfg.expert_start, tile=cfg.moe_tile, dtype=MATMUL_DTYPE,
            impl=cfg.moe_impl)
        m = rmsnorm(x, p["post_norm"], cfg.eps)
        y, choices, pairs = experts(m.reshape(B * T, D))
        probe = jax.lax.stop_gradient(jnp.matmul(
            experts(jax.lax.stop_gradient(h).reshape(B * T, D))[0],
            moe_probe_vector(D), precision=HIGHEST))
        y, probe = y.reshape(B, T, D), probe.reshape(B, T, 1)
        choices = choices.reshape(B, T, -1)
    with jax.named_scope("seq.stream"):
        return x + y, (probe, choices, pairs)


def shared_expert(p: dict, h):
    """The share's columns of the shared expert, ``relu(h W_up)^2 W_down``,
    on every token."""
    with jax.named_scope("moe.shared"):
        return mm(moe.act2(mm(h, p["shared_up"])), p["shared_down"])


def shared_routed_experts(cfg: SeqConfig, p: dict, h, seg):
    """What a ``"shared_routed_experts"`` layer adds to the stream: the held
    routed experts' part plus the shared expert's held columns
    (``shared_expert``), both from the layer's one normed input ``h``
    [B, T, D], which the router reads too: scores ``sigmoid(h W_r)``, the
    ``k`` largest of ``score + b`` (``router_bias``, a selection bias no
    gradient reaches and no step moves), weights the chosen UNBIASED scores
    over their sum, times ``routed_scale``; experts of two matrices,
    ``W_down relu(W_up h)^2`` (``ops/moe.py`` ``relu2_ffn``) -> (the sum
    [B, T, D], the choices [B, T, k], the pairs of each held expert [held])."""
    B, T, D = h.shape
    with jax.named_scope("seq.moe"):
        with jax.named_scope("moe.route"):
            logits = mm_f32(h, p["router"]).reshape(B * T, -1)
        y, choices, pairs = moe.experts_layer(
            h.reshape(B * T, D), logits, (seg != PAD_SEGMENT).reshape(-1), None,
            p["experts_up"], p["experts_down"], k=cfg.experts_per_token,
            start=cfg.expert_start, tile=cfg.moe_tile, dtype=MATMUL_DTYPE,
            impl=cfg.moe_impl, bias=p["router_bias"], scale=cfg.routed_scale)
        y = y.reshape(B, T, D) + shared_expert(p, h)
        return y, choices.reshape(B, T, -1), pairs


def sublayer(cfg: SeqConfig, kind: str, p: dict, x, seg):
    """One layer of the Nemotron-H stack, which is ONE sublayer,
    ``x + f(RMSNorm(x))``, and mixes three kinds in any order; nothing is
    multiplied (``MuP()``).  ``f`` is the parallel block's state-space mixer
    alone (the gated norm's group the B / C group's channels), or the global
    routed kind's attention alone (no rotary positions and no window: the
    state-space layers carry position), or ``shared_routed_experts`` -> (x
    after it, what ``f`` records: the state space's probe [B, T, H], nothing
    for attention, the choices and the pairs for the experts)."""
    with jax.named_scope("seq.stream"):
        h = rmsnorm(x, p["input_norm"], cfg.eps)
    if kind == STATE_SPACE:
        y, probe = state_space_mixer(cfg, p, h, seg)
        record = {PROBE_NAME[kind]: probe}
    elif kind == GROUPED_ATTENTION:
        # the global routed kind's attention: no rotary, no window
        y, record = routed_attention(cfg, kind, p, h, seg), {}
    else:
        y, choices, pairs = shared_routed_experts(cfg, p, h, seg)
        record = {"choices": choices, "expert_pairs": pairs}
    with jax.named_scope("seq.stream"):
        return x + y, record


@functools.partial(jax.jit, static_argnums=(0, 1))
def experts_probe(cfg: SeqConfig, backward: bool, table, p: dict, tokens, seg):
    """What the training record holds the experts' path by (route, dispatch,
    the grouped products, combine, shared expert): a ``"shared_routed_experts"``
    layer's ``f`` applied to the EMBEDDED row ``tokens`` [T] under its own
    norm.  Exact embedding rows normed in float32 are the same numbers on both
    sides of a comparison, which the stream a deeper layer reads is not.  A
    program of its own, run on the first step's rows and not in the row
    program: -> {"moe_probe": ``f`` along the probe vector [T, 1],
    "moe_grad_probe": with ``backward`` the gradient of the ROUTED part's sum
    along that vector, {"up", "down": the two stacked matrices' [held, F],
    each contracted over the hidden axis with a second seeded vector,
    "input": what reaches the experts' input, summed over the tokens [D]}
    (the four backward products); zeros without}."""
    T, D, F = tokens.shape[0], cfg.hidden, cfg.expert_width
    with jax.named_scope("seq.embed"):
        rows = tokens[None]
    x0 = embed(cfg, table, rows)
    with jax.named_scope("seq.moe"):
        h = rmsnorm(x0, p["input_norm"], cfg.eps)
        r, q = moe_probe_vector(D), moe_probe_vector(D, 3)
        with jax.named_scope("moe.route"):
            logits = mm_f32(h, p["router"]).reshape(T, -1)

        def routed(m, up, down):
            return moe.experts_layer(
                m, logits, seg != PAD_SEGMENT, None, up, down,
                k=cfg.experts_per_token, start=cfg.expert_start,
                tile=cfg.moe_tile, dtype=MATMUL_DTYPE, impl=cfg.moe_impl,
                bias=p["router_bias"], scale=cfg.routed_scale)[0]

        y, vjp = jax.vjp(routed, h[0], p["experts_up"], p["experts_down"])
        probe = jnp.matmul(y + shared_expert(p, h)[0], r, precision=HIGHEST)
        if backward:
            dm, dup, ddown = vjp(jnp.broadcast_to(r, y.shape))
            grads = {
                "up": jnp.einsum("edf,d->ef", dup, q, precision=HIGHEST),
                "down": jnp.einsum("efd,d->ef", ddown, q, precision=HIGHEST),
                "input": jnp.sum(dm, axis=0)}
        else:
            held = cfg.experts_held
            grads = {"up": jnp.zeros((held, F)), "down": jnp.zeros((held, F)),
                     "input": jnp.zeros((D,))}
        return {"moe_probe": probe[:, None], "moe_grad_probe": grads}


def gated_group_norm(y, z, w, eps, axis_name=None):
    """``RMSNorm(y * SiLU(z))`` over the last axis, a group's channels HELD
    here.  A group's channels may lie on several chips: ``axis_name`` is the
    mapped axis over those chips, and the mean square is then taken over all
    of them (one float a token and group crosses); ``None`` on one chip,
    where the mean is over the channels held."""
    g = y * jax.nn.silu(z)
    ss, n = jnp.sum(g * g, axis=-1, keepdims=True), g.shape[-1]
    if axis_name is not None:
        ss, n = jax.lax.psum(ss, axis_name), n * jax.lax.psum(1, axis_name)
    return g * jax.lax.rsqrt(ss / n + eps) * w


def ssm_probe_vector(P: int):
    """The seeded direction each head's state-space output is recorded
    along: standard normal [P] from ``fold_in(PRNGKey(PROBE_SEED), 2**20 + 1)``."""
    return jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(PROBE_SEED), 2 ** 20 + 1), (P,),
        jnp.float32)


def ssm_inputs(cfg: SeqConfig, p: dict, h, seg):
    """What the state space reads -> (x [B, T, H, P], Delta [B, T, H],
    B, C [B, T, G, N]) and the gate z [B, T, H * P]."""
    Bsz, T, _ = h.shape
    H, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    mup = cfg.mup
    with jax.named_scope("ssm.proj"):
        u = mm(_scaled(h, mup.ssm_in), p["ssm_in"])
        if any(m != 1.0 for m in mup.ssm_zones):
            u = u * np.repeat(
                np.asarray(mup.ssm_zones, np.float32),
                (H * P, H * P, G * N, G * N, H))
        z, xbc, dt = jnp.split(u, (H * P, 2 * H * P + 2 * G * N), axis=-1)
    with jax.named_scope("ssm.conv"):
        xbc = jax.nn.silu(causal_conv(xbc, p["ssm_conv"], seg) + p["ssm_conv_bias"])
        x, b, c = jnp.split(xbc, (H * P, H * P + G * N), axis=-1)
        dt = jax.nn.softplus(dt + p["ssm_dt_bias"])
    return (x.reshape(Bsz, T, H, P), dt, b.reshape(Bsz, T, G, N),
            c.reshape(Bsz, T, G, N), z)


def state_space_mixer(cfg: SeqConfig, p: dict, h, seg, norm_axis=None):
    """The share's part of the parallel block's state-space mixer (the sum
    over its heads' rows of the output projection): one projection to
    ``z | x | B | C | dt`` (each zone times its multiplier), a causal depthwise
    convolution with a bias and SiLU over ``x | B | C``, ``Delta = softplus(dt
    + dt_bias)`` (``ssm_inputs``), Mamba-2's selective state space in its
    chunked form (``ops/ssd.py``) with the ``D`` skip, ``RMSNorm(y * SiLU(z))``
    over each group's channels, output projection; and the recurrence's own
    output ``S_t C_t`` along the probe vector [B, T, H], before the ``D`` skip
    (which is alike on both sides of any comparison and 100 x larger at the
    seeded weights) and any later rounding."""
    Bsz, T, _ = h.shape
    G = cfg.ssm_groups
    with jax.named_scope("seq.ssm"):
        x, dt, b, c, z = ssm_inputs(cfg, p, h, seg)
        y = ssd.ssd(x, dt, -jnp.exp(p["ssm_a_log"]), b, c, seg, cfg.ssm_chunk,
                    cfg.ssm_impl)
        probe = jax.lax.stop_gradient(jnp.einsum(
            "bthp,p->bth", y, ssm_probe_vector(y.shape[-1]), precision=HIGHEST))
        with jax.named_scope("ssm.norm"):
            y = y + p["ssm_d"][:, None] * x
            y = gated_group_norm(
                y.reshape(Bsz, T, G, -1), z.reshape(Bsz, T, G, -1),
                p["ssm_norm"].reshape(G, -1), cfg.eps, norm_axis)
        with jax.named_scope("ssm.proj"):
            out = mm(y.reshape(Bsz, T, -1), p["ssm_out"])
        return _scaled(out, cfg.mup.ssm_out), probe


def mlp(cfg: SeqConfig, p: dict, x):
    """The share's part of the MLP's output: the sum over its columns."""
    mup = cfg.mup
    with jax.named_scope("seq.mlp"):
        gate = jax.nn.silu(_scaled(mm(x, p["gate"]), mup.mlp_gate))
        return _scaled(mm(gate * mm(x, p["up"]), p["down"]), mup.mlp_down)


def _post_norm_layer(cfg: SeqConfig, p: dict, x, y):
    """The OLMo 2/3 residual form around a mixer's output ``y`` and the
    SwiGLU MLP, ``x + RMSNorm(f(x))`` -> x after the layer."""
    with jax.named_scope("seq.stream"):
        x = x + rmsnorm(y, p["mixer_norm"], cfg.eps)
    y = mlp(cfg, p, x)
    with jax.named_scope("seq.stream"):
        return x + rmsnorm(y, p["mlp_norm"], cfg.eps)


def linear_layer(cfg: SeqConfig, p: dict, x, seg):
    """The Olmo-Hybrid block's ``"linear_attention"`` layer (post-norm, one
    mixer and the MLP) -> (x after it, {the delta rule's probe [B, T, H]})."""
    y, probe = linear_attention(cfg, p, x, seg)
    return _post_norm_layer(cfg, p, x, y), {PROBE_NAME[LINEAR]: probe}


def parallel_layer(cfg: SeqConfig, p: dict, x, seg):
    """The Falcon-H1 block, ``"parallel_ssm_attention"``: pre-norm, and TWO
    mixers that read one normed input ``h = RMSNorm(x)``, their outputs scaled
    and summed, ``x + ssm(h) + attn(h)``, then ``x + mlp(RMSNorm(x))``; muP's
    forward multipliers (``MuP``) where the published model has them -> (x
    after it, {the state space's probe [B, T, H]})."""
    with jax.named_scope("seq.stream"):
        h = rmsnorm(x, p["input_norm"], cfg.eps)
    m, probe = state_space_mixer(cfg, p, h, seg)
    a = grouped_query_attention(cfg, p, h, seg)
    with jax.named_scope("seq.stream"):
        x = x + m + a
        h = rmsnorm(x, p["pre_ff_norm"], cfg.eps)
    y = mlp(cfg, p, h)
    with jax.named_scope("seq.stream"):
        return x + y, {PROBE_NAME[PARALLEL]: probe}


def sandwich_layer(cfg: SeqConfig, p: dict, x, seg):
    """The Ouro block, ``"sandwich_attention"``: a norm before AND after each
    sublayer, inside the residual, ``x1 = x + N2(attn(N1(x)))``,
    ``x2 = x1 + N4(mlp(N3(x1)))``; attention is plain multi-head (the grouped
    path with one KV head a query head), rotary positions over the whole head
    that restart at every segment, no q / k norm; the MLP SwiGLU.  Nothing is
    recorded here: the exits' record is the looped trunk's."""
    with jax.named_scope("seq.stream"):
        h = rmsnorm(x, p["input_norm"], cfg.eps)
    a = grouped_query_attention(cfg, p, h, seg)
    with jax.named_scope("seq.stream"):
        x = x + rmsnorm(a, p["attn_out_norm"], cfg.eps)
        h = rmsnorm(x, p["pre_ff_norm"], cfg.eps)
    y = mlp(cfg, p, h)
    with jax.named_scope("seq.stream"):
        return x + rmsnorm(y, p["mlp_out_norm"], cfg.eps), {}


# ---------------------------------------------------------------------------
# the layer kinds


def _norms(cfg: SeqConfig, *names: str) -> dict:
    return {name: (cfg.hidden,) for name in names}


def _delta_rule_tensors(cfg: SeqConfig) -> dict:
    D, H = cfg.hidden, cfg.lin_heads
    qk, vv = H * cfg.lin_key_dim, H * cfg.lin_value_dim
    return {
        "q": (D, qk), "k": (D, qk), "v": (D, vv), "g": (D, vv), "a": (D, H),
        "b": (D, H), "conv_q": (cfg.conv_width, qk), "conv_k": (cfg.conv_width, qk),
        "conv_v": (cfg.conv_width, vv), "a_log": (H,), "dt_bias": (H,),
        "o_norm": (cfg.lin_value_dim,), "o": (vv, D)}


def _attention_tensors(cfg: SeqConfig, grouped: bool = True) -> dict:
    """q / k / v / o of an attention mixer: k and v on the KV heads held, or
    (not ``grouped``: the full-attention kind) a KV head a query head and a
    per-head norm on q and k."""
    D, hd = cfg.hidden, cfg.heads * cfg.head_dim
    kv = (cfg.kv_heads or cfg.heads) * cfg.head_dim if grouped else hd
    norms = {} if grouped else {"q_norm": (cfg.head_dim,), "k_norm": (cfg.head_dim,)}
    return {"q": (D, hd), "k": (D, kv), "v": (D, kv), **norms, "o": (hd, D)}


def _state_space_tensors(cfg: SeqConfig) -> dict:
    D, H = cfg.hidden, cfg.ssm_heads
    ch, bc = H * cfg.ssm_head_dim, cfg.ssm_groups * cfg.ssm_state
    return {
        "ssm_in": (D, 2 * ch + 2 * bc + H),
        "ssm_conv": (cfg.ssm_conv_width, ch + 2 * bc),
        "ssm_conv_bias": (ch + 2 * bc,), "ssm_a_log": (H,), "ssm_d": (H,),
        "ssm_dt_bias": (H,), "ssm_norm": (ch,), "ssm_out": (ch, D)}


def _mlp_tensors(cfg: SeqConfig) -> dict:
    D, F = cfg.hidden, cfg.mlp_cols
    return {"gate": (D, F), "up": (D, F), "down": (F, D)}


def _experts_tensors(cfg: SeqConfig, gated: bool = True) -> dict:
    """The held experts, stacked: three matrices an expert, or (not
    ``gated``: the relu^2 experts) two."""
    D, E, F = cfg.hidden, cfg.experts_held, cfg.expert_width
    gate = {"experts_gate": (E, D, F)} if gated else {}
    return {**gate, "experts_up": (E, D, F), "experts_down": (E, F, D)}


def _need(ok, message: str):
    if not ok:
        raise ValueError(message)


def _check_groups(heads: int, groups: int):
    _need(heads % groups == 0, "heads do not divide over their groups")


def _check_attention(cfg: SeqConfig):
    _check_groups(cfg.heads, cfg.kv_heads or cfg.heads)


def _check_state_space(cfg: SeqConfig):
    _need(cfg.ssm_heads and cfg.ssm_head_dim and cfg.ssm_state,
          "state-space layers need the ssm_* sizes")
    _check_groups(cfg.ssm_heads, cfg.ssm_groups)


def _check_experts(cfg: SeqConfig):
    _need(cfg.experts and cfg.experts_held and cfg.expert_width
          and 0 < cfg.experts_per_token <= cfg.experts,
          "*_moe layers need the experts' sizes")
    _need(cfg.expert_start + cfg.experts_held <= cfg.experts,
          "the experts held lie outside the router's width")


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What a layer's kind decides, written once: ``param_shapes``,
    ``SeqConfig``'s checks and row length, ``layer``, ``trunk``,
    ``init_state`` and ``train_steps`` read it here (docs/engines.md,
    "Adding a block")."""

    #: leaf name -> shape of the layer's tensors, in order
    tensors: Callable[[SeqConfig], dict]
    #: (cfg, p, x, seg) -> (x after the layer, what it records: a dict of named
    #: arrays, its probe under ``probe`` and a routed layer's ``choices``
    #: [B, T, k] and ``expert_pairs`` [held]).  A function of THIS module is
    #: named in the body and looked up when the layer runs, so that one
    #: replaced after import (``benchmark/tests/control_*``) is what runs
    apply: Callable
    #: what a row's length is a multiple of (the recurrences' chunks, the
    #: windowed attention's smallest block)
    token_multiple: Callable[[SeqConfig], int]
    #: each raises ``ValueError`` where a size the kind reads is missing
    checks: tuple[Callable[[SeqConfig], None], ...] = ()
    #: what the training record calls the probe of a stack of this kind: the
    #: first layer's recurrence or experts, a looped stack's exit distribution
    probe: str | None = None
    #: its layers route tokens to experts: the step sums their counters
    routed: bool = False
    #: a layer that is ONE sublayer, ``x + f(RMSNorm(x))``: the span tag that
    #: counts such layers of a stack
    sublayer_tag: str | None = None
    #: a program of its own, run on the first step's rows for the FIRST layer
    #: of the kind: (cfg, backward, embedding, p, tokens, seg) -> more probes
    first_step_probe: Callable | None = None


def _moe_kind(kind: str, *checks) -> LayerKind:
    """A SmallThinker layer's entry: ``routed_layer`` with its record named."""
    def apply(cfg: SeqConfig, p: dict, x, seg):
        x, (probe, choices, pairs) = routed_layer(cfg, kind, p, x, seg)
        return x, {
            PROBE_NAME[kind]: probe, "choices": choices, "expert_pairs": pairs}

    return LayerKind(
        tensors=lambda cfg: (
            _norms(cfg, "input_norm") | {"router": (cfg.hidden, cfg.experts)}
            | _attention_tensors(cfg) | _norms(cfg, "post_norm")
            | _experts_tensors(cfg)),
        apply=apply, token_multiple=lambda cfg: _LANES,
        checks=(_check_experts, _check_attention) + checks, probe="moe_probe",
        routed=True)


LAYER_KINDS = {
    LINEAR: LayerKind(
        tensors=lambda cfg: (
            _delta_rule_tensors(cfg) | _norms(cfg, "mixer_norm")
            | _mlp_tensors(cfg) | _norms(cfg, "mlp_norm")),
        apply=linear_layer, token_multiple=lambda cfg: cfg.chunk,
        probe="delta_rule_probe"),
    # the Olmo-Hybrid block's other layer, in the same residual form: it
    # records nothing, the stack's probe is a linear layer's
    FULL: LayerKind(
        tensors=lambda cfg: (
            _attention_tensors(cfg, grouped=False) | _norms(cfg, "mixer_norm")
            | _mlp_tensors(cfg) | _norms(cfg, "mlp_norm")),
        apply=lambda cfg, p, x, seg: (
            _post_norm_layer(cfg, p, x, full_attention(cfg, p, x, seg)), {}),
        token_multiple=lambda cfg: cfg.chunk, probe="delta_rule_probe"),
    PARALLEL: LayerKind(
        tensors=lambda cfg: (
            _norms(cfg, "input_norm") | _state_space_tensors(cfg)
            | _attention_tensors(cfg) | _norms(cfg, "pre_ff_norm")
            | _mlp_tensors(cfg)),
        apply=parallel_layer, token_multiple=lambda cfg: cfg.ssm_chunk,
        checks=(_check_state_space, _check_attention), probe="ssd_probe"),
    GLOBAL_MOE: _moe_kind(GLOBAL_MOE),
    SLIDING_MOE: _moe_kind(SLIDING_MOE, lambda cfg: _need(
        cfg.window > 0, f"{SLIDING_MOE} layers need a window")),
    SANDWICH: LayerKind(
        tensors=lambda cfg: (
            _norms(cfg, "input_norm") | _attention_tensors(cfg)
            | _norms(cfg, "attn_out_norm", "pre_ff_norm") | _mlp_tensors(cfg)
            | _norms(cfg, "mlp_out_norm")),
        apply=sandwich_layer, token_multiple=lambda cfg: cfg.chunk,
        checks=(_check_attention,), probe="exit_probe"),
    STATE_SPACE: LayerKind(
        tensors=lambda cfg: _norms(cfg, "input_norm") | _state_space_tensors(cfg),
        apply=lambda cfg, p, x, seg: sublayer(cfg, STATE_SPACE, p, x, seg),
        token_multiple=lambda cfg: cfg.ssm_chunk, checks=(_check_state_space,),
        probe="ssd_probe", sublayer_tag="layers_state_space"),
    GROUPED_ATTENTION: LayerKind(
        tensors=lambda cfg: _norms(cfg, "input_norm") | _attention_tensors(cfg),
        apply=lambda cfg, p, x, seg: sublayer(cfg, GROUPED_ATTENTION, p, x, seg),
        token_multiple=lambda cfg: _LANES, checks=(_check_attention,),
        sublayer_tag="layers_attention"),
    SHARED_EXPERTS: LayerKind(
        tensors=lambda cfg: (
            _norms(cfg, "input_norm") | {
                "router": (cfg.hidden, cfg.experts), "router_bias": (cfg.experts,),
                "shared_up": (cfg.hidden, cfg.shared_cols),
                "shared_down": (cfg.shared_cols, cfg.hidden)}
            | _experts_tensors(cfg, gated=False)),
        apply=lambda cfg, p, x, seg: sublayer(cfg, SHARED_EXPERTS, p, x, seg),
        token_multiple=lambda cfg: _LANES,
        checks=(_check_experts, lambda cfg: _need(
            cfg.shared_cols > 0,
            f"{SHARED_EXPERTS} layers need the shared expert's columns")),
        # its probe is not the row program's: ``experts_probe`` makes it
        probe="moe_probe", routed=True, sublayer_tag="layers_experts",
        first_step_probe=lambda *args: experts_probe(*args)),
}

KINDS = tuple(LAYER_KINDS)
#: every kind that routes: its layers have the routing counters
ROUTED_KINDS = tuple(k for k, e in LAYER_KINDS.items() if e.routed)
#: the kinds that are ONE sublayer, ``x + f(RMSNorm(x))``
SUBLAYER_KINDS = tuple(k for k, e in LAYER_KINDS.items() if e.sublayer_tag)
#: the kinds whose feed-forward is a layer of routed experts after attention
MOE_KINDS = tuple(k for k in ROUTED_KINDS if k not in SUBLAYER_KINDS)
#: what the training record calls a stack's probe, by its layers' kind
PROBE_NAME = {k: e.probe for k, e in LAYER_KINDS.items() if e.probe}


def layer(cfg: SeqConfig, kind: str, p: dict, x, seg):
    """One layer of ``kind`` -> (x after it, what it records: ``LayerKind.apply``)."""
    return LAYER_KINDS[kind].apply(cfg, p, x, seg)


def trunk(cfg: SeqConfig, params: dict, x, seg, remat: bool = False):
    """The layers and the final norm over embedded rows x [B, T, D] -> (the
    normalised hidden states, the stack's record: under each probe's name the
    FIRST layer's that records one (a first layer's inputs are products of
    exact embedding rows, normed first in a pre-norm block, so the training
    record can hold the recurrence to its token-by-token form there; deeper
    layers read a residual stream that already carries every earlier
    rounding), and where layers route ``choices`` [B, routed layers, T, k] and
    ``expert_pairs`` [routed layers, held]).  With ``remat`` each layer is
    recomputed in the backward pass, so that only the residual stream between
    layers is kept.  A looped model (``loop_steps`` R > 1) gives the R exit
    states [R, B, T, D] first, and the carried state's mean squares second
    (``looped_trunk``)."""
    if cfg.loop_steps > 1:
        return looped_trunk(cfg, params, x, seg, remat)
    kept, routed = {}, []
    for i, kind in enumerate(cfg.layer_types):
        f = functools.partial(layer, cfg, kind)
        if remat:
            f = jax.checkpoint(f)
        x, record = f(layer_params(params, i), x, seg)
        record = dict(record)
        if "choices" in record:
            routed.append((record.pop("choices"), record.pop("expert_pairs")))
        for name, probe in record.items():
            kept.setdefault(name, probe)
    if routed:
        with jax.named_scope("seq.moe"):
            kept["choices"] = jnp.stack([c for c, _ in routed], axis=1)
            kept["expert_pairs"] = jnp.stack([n for _, n in routed])
    with jax.named_scope("seq.stream"):
        return rmsnorm(x, params["final_norm"], cfg.eps), kept


def loop_pass(cfg: SeqConfig, params: dict, x, seg, remat: bool = False):
    """One pass of a looped model: the layer list, then the one final norm ->
    the pass's exit state, which is also what the next pass reads.  With
    ``remat`` every layer application is recomputed in the backward pass."""
    for i, kind in enumerate(cfg.layer_types):
        f = functools.partial(layer, cfg, kind)
        if remat:
            f = jax.checkpoint(f)
        x, _ = f(layer_params(params, i), x, seg)
    with jax.named_scope("seq.stream"):
        return rmsnorm(x, params["final_norm"], cfg.eps)


def carried_mean_square(x):
    """The mean square of the state a later pass reads [B, T]: what the
    training record holds the carried state's precision by.  The final norm
    leaves it ``ms / (ms + eps)`` times its weights' whatever the layers
    rounded; a state rounded to bfloat16 on its way to the next pass has lost
    that in the fourth digit."""
    with jax.named_scope("seq.stream"):
        return jax.lax.stop_gradient(jnp.mean(x * x, axis=-1))


def _passes(cfg: SeqConfig, x, one_pass):
    """x through ``loop_steps`` calls of ``one_pass`` -> (the exit states
    [R, B, T, D], the mean square of the state as each LATER pass read it
    [B, T, R - 1]).  Pass t's operations carry the component ``loop.pass<t>``
    outside their scope."""
    states, carried = [], []
    for t in range(cfg.loop_steps):
        if t:
            carried.append(carried_mean_square(x))
        with jax.named_scope(LOOP_PASS.format(t)):
            x = one_pass(x)
        states.append(x)
    with jax.named_scope("seq.stream"):
        return jnp.stack(states), jnp.stack(carried, axis=-1)


def looped_trunk(cfg: SeqConfig, params: dict, x, seg, remat: bool = False):
    """The layer list ``loop_steps`` times over the SAME tensors -> (the exit
    states, the carried state's mean squares: ``_passes``).  Training takes
    the passes one at a time (``loop_forward``); this is the whole forward,
    for serving."""
    return _passes(cfg, x, lambda x: loop_pass(cfg, params, x, seg, remat))


def hidden_states(cfg: SeqConfig, params: dict, tokens, seg):
    """Final normalised hidden states [B, T, D] of packed rows (a looped
    model's LAST pass: no exit is taken early)."""
    h = trunk(cfg, params, embed(cfg, params["embed"], tokens), seg)[0]
    return h[-1] if cfg.loop_steps > 1 else h


# ---------------------------------------------------------------------------
# loss


def next_item_targets(tokens, seg):
    """Targets and weights of next-item prediction: position t predicts token
    t + 1 where that is the same (real) segment."""
    nxt = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    nseg = jnp.pad(seg[:, 1:], ((0, 0), (0, 1)), constant_values=gdn.NO_SEGMENT)
    weight = ((nseg == seg) & (seg != PAD_SEGMENT)).astype(jnp.float32)
    return nxt, weight


def cross_entropy(cfg: SeqConfig, h, head, targets, weight, dhead,
                  per_token: bool = False):
    """Sum over tokens of ``weight * cross-entropy(h @ head^T, target)`` over
    the held vocabulary rows (the logits times ``mup.lm_head``) AND its
    gradients, a block of tokens at a time:
    the logits of a block exist once, their gradient is made beside them, and
    the head's gradient is added into ``dhead`` -> (loss, dh, dhead); with
    ``per_token`` also every token's own cross-entropy, unweighted (what a
    weight that is a function of the parameters needs for ITS gradient)."""
    shape = h.shape
    T = math.prod(shape[:-1])
    blk = min(cfg.loss_block, T)
    if T % blk:
        raise ValueError(f"{T} tokens are not a multiple of the loss block {blk}")
    n = T // blk
    scale = cfg.mup.lm_head

    def block(carry, x):
        loss, dw = carry
        hx, tx, wx = x
        h16 = hx.astype(MATMUL_DTYPE)
        logits = _scaled(
            jnp.matmul(h16, w16.T, preferred_element_type=jnp.float32), scale)
        lse = jax.nn.logsumexp(logits, axis=-1)
        hit = jnp.arange(logits.shape[-1])[None, :] == tx[:, None]
        picked = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        nll = lse - picked
        loss = loss + jnp.sum(wx * nll)
        dlog = _scaled(
            (jnp.exp(logits - lse[:, None]) - hit) * wx[:, None], scale
        ).astype(MATMUL_DTYPE)
        dh = jnp.matmul(dlog, w16, preferred_element_type=jnp.float32)
        dw = dw + jnp.matmul(dlog.T, h16, preferred_element_type=jnp.float32)
        return (loss, dw), ((dh, nll) if per_token else dh)

    with jax.named_scope("seq.loss"):
        w16 = head.astype(MATMUL_DTYPE)
        local = targets.reshape(n, blk) - cfg.vocab_start
        (loss, dhead), dh = jax.lax.scan(
            block, (jnp.float32(0.0), dhead),
            (h.reshape(n, blk, shape[-1]), local, weight.reshape(n, blk)),
        )
        if per_token:
            dh, nll = dh
            return loss, dh.reshape(shape), dhead, nll.reshape(shape[:-1])
        return loss, dh.reshape(shape), dhead


def counted_targets(tokens, seg):
    """``next_item_targets`` and how many positions they weigh."""
    with jax.named_scope("seq.loss"):
        targets, weight = next_item_targets(tokens, seg)
        return targets, weight, jnp.sum(weight)


def table_grads(cfg: SeqConfig, gsum: dict, tokens, dx0, dhead) -> dict:
    """A row's sums of the two vocabulary tables' gradients: the embedded
    rows' cotangent ``dx0`` scattered into ``gsum["embed"]`` in place, never
    held beside it, and the head's as the loss accumulated it."""
    with jax.named_scope("seq.embed"):
        idx = tokens - cfg.vocab_start
        held = (idx >= 0) & (idx < cfg.vocab_rows)
        dembed = gsum["embed"].at[jnp.where(held, idx, 0)].add(
            _scaled(jnp.where(held[..., None], dx0, 0.0), cfg.mup.embedding))
    return {"embed": dembed, "head": dhead}


def row_grads(cfg: SeqConfig, params: dict, tokens, seg, gsum: dict):
    """Forward, per-layer recomputation and backward of packed rows
    [B, T] -> (sum of the next-item cross-entropy over their real positions,
    how many those are, ``gsum`` + the sum's gradient, the stack's record
    (``trunk``)).  A looped model goes through ``looped_row_grads``."""
    if cfg.loop_steps > 1:
        return looped_row_grads(cfg, params, tokens, seg, gsum)
    inner = {k: v for k, v in params.items() if k not in ("embed", "head")}
    x0 = embed(cfg, params["embed"], tokens)
    h, vjp, record = jax.vjp(
        lambda p, x: trunk(cfg, p, x, seg, remat=True), inner, x0, has_aux=True)
    targets, weight, count = counted_targets(tokens, seg)
    loss, dh, dhead = cross_entropy(
        cfg, h, params["head"], targets, weight, gsum["head"])
    dinner, dx0 = vjp(dh)
    tables = table_grads(cfg, gsum, tokens, dx0, dhead)
    with jax.named_scope("seq.accumulate"):
        out = {k: gsum[k] + g for k, g in dinner.items()}
    return loss, count, out | tables, record


def exit_log_probs(states, gate, bias):
    """The exit states [R, B, T, D] through the one gate -> ``log p`` [R, B, T],
    ``p_t = lam_t prod_{j<t} (1 - lam_j)`` for t < R and the last pass taking
    the rest, ``lam_t = sigmoid(x_t . gate + bias)``; in logarithms, so that
    a gate that saturates leaves every ``log p`` finite."""
    z = jnp.einsum("rbtd,d->rbt", states, gate, precision=HIGHEST) + bias
    log_lam, log_stay = jax.nn.log_sigmoid(z), jax.nn.log_sigmoid(-z)
    # log S_t, S_1 = 1: what has not left before pass t
    reach = jnp.cumsum(log_stay, axis=0) - log_stay
    return jnp.concatenate([(log_lam + reach)[:-1], reach[-1:]])


def loop_forward(cfg: SeqConfig, inner: dict, x, seg):
    """Embedded rows x [B, T, D] through the R passes, each differentiated on
    its own -> (the exit states, the carried state's mean squares
    (``_passes``), the passes' ``vjp``s).  Every layer application is
    recomputed in its backward pass: ``loop_steps x layers`` residual streams
    are kept."""
    pass_vjps = []

    def one_pass(x):
        x, pass_vjp = jax.vjp(
            lambda p, x: loop_pass(cfg, p, x, seg, remat=True), inner, x)
        pass_vjps.append(pass_vjp)
        return x

    return (*_passes(cfg, x, one_pass), pass_vjps)


def loop_backward(pass_vjps: list, dstates, gsum: dict):
    """Back through the passes, last first -> (``gsum`` + the shared tensors'
    gradients, the embedded rows' cotangent).  A pass's state takes its
    exit's cotangent and the next pass's; a shared tensor's gradient is the
    sum over its R uses, each added into the step's sum as its pass yields
    it: both additions are written here, under their scopes."""
    dx = None
    for t in reversed(range(len(pass_vjps))):
        with jax.named_scope("seq.stream"):
            dstate = dstates[t] if dx is None else dstates[t] + dx
        dinner, dx = pass_vjps[t](dstate)
        with jax.named_scope("seq.accumulate"):
            gsum = {k: gsum[k] + g for k, g in dinner.items()}
    return gsum, dx


def looped_row_grads(cfg: SeqConfig, params: dict, tokens, seg, gsum: dict):
    """``row_grads`` of a looped model: an exit after every pass.  A
    position's loss is ``sum_t p_t l_t - exit_beta H(p)``, ``l_t`` the
    next-item cross-entropy of pass t's state through the one head and ``p``
    the exit distribution its gates give (``exit_log_probs``); nothing is
    detached.  The gates and ``p`` come first (R products [T, D] x [D]); the
    blocked loss then runs ONCE over the R states end on end with the weight
    of a token ``weight x p_t`` and hands back every ``l_t``; the gate's own
    path, ``dL/dlog p_t = p_t (l_t + exit_beta (log p_t + 1))``, is closed
    through ``p(lam)`` into the gate's two tensors and into the states'
    cotangents, beside the loss's own.  The trunk is differentiated a PASS at
    a time (``loop_forward`` / ``loop_backward``), so that the sum of a shared
    tensor's gradient over its R uses, and of a state's two cotangents, are
    the program's own additions under their scopes.
    -> (the sum of the positions' losses, their count, ``gsum`` + the
    gradient, {"exit_probe": p [B, T, R], "carry_probe" [B, T, R - 1]
    (``looped_trunk``), at ``HEAD_PROBE_POSITIONS`` evenly spaced positions
    "head_probe" [B, n, R] (each ``l_t``) and "head_probe_state" [B, n, R, D]
    (the exit states those came from: the head's product can be made again
    from them, which holds ITS precision whatever the trunk rounded), and
    summed over the positions "exit_loss" [R] (each ``l_t``), "exit_mass" [R]
    (each ``p_t``), "exit_entropy"})."""
    R, beta = cfg.loop_steps, cfg.exit_beta
    outer = ("embed", "head", "exit_gate", "exit_gate_bias")
    inner = {k: v for k, v in params.items() if k not in outer}
    x0 = embed(cfg, params["embed"], tokens)
    states, carried, pass_vjps = loop_forward(cfg, inner, x0, seg)
    targets, weight, count = counted_targets(tokens, seg)
    with jax.named_scope("seq.exit"):
        logp, gate_vjp = jax.vjp(
            exit_log_probs, states, params["exit_gate"], params["exit_gate_bias"])
        p = jnp.exp(logp)
        weighted = weight * p
        exits = jnp.broadcast_to(targets, (R,) + targets.shape)
    loss, dstates, dhead, nll = cross_entropy(
        cfg, states, params["head"], exits, weighted, gsum["head"], per_token=True)
    with jax.named_scope("seq.loss"):
        T = tokens.shape[-1]
        n = min(HEAD_PROBE_POSITIONS, T)
        at = jnp.arange(n) * (T // n)
        head_probe = {
            "head_probe": jnp.moveaxis(nll[:, :, at], 0, -1),
            "head_probe_state": jnp.moveaxis(states[:, :, at], 0, 2),
        }
    with jax.named_scope("seq.exit"):
        entropy = -jnp.sum(weighted * logp)
        dgate_states, dgate, dbias = gate_vjp(weighted * (nll + beta * (logp + 1.0)))
        loss = loss - beta * entropy
        dstates = dstates + dgate_states
        probe = {
            PROBE_NAME[SANDWICH]: jax.lax.stop_gradient(jnp.moveaxis(p, 0, -1)),
            "carry_probe": carried, **head_probe,
            "exit_loss": jnp.sum(weight * nll, axis=(1, 2)),
            "exit_mass": jnp.sum(weighted, axis=(1, 2)),
            "exit_entropy": entropy,
        }
    out, dx = loop_backward(pass_vjps, dstates, {k: gsum[k] for k in inner})
    tables = table_grads(cfg, gsum, tokens, dx, dhead)
    with jax.named_scope("seq.accumulate"):
        out["exit_gate"] = gsum["exit_gate"] + dgate
        out["exit_gate_bias"] = gsum["exit_gate_bias"] + dbias
    return loss, count, out | tables, probe


# ---------------------------------------------------------------------------
# training


def init_state(cfg: SeqConfig, seed: int) -> tuple[dict, dict]:
    """(state, acc): the weights with Adam's two moments and the step count,
    and the accumulator of one optimiser step (the gradients' sum, the loss's
    sum, the positions counted; zero between steps).  16 bytes a parameter."""
    params = init_params(cfg, seed)
    zeros = lambda: {  # noqa: E731
        k: _zeros(v.shape, v.dtype) for k, v in params.items()}
    state = {"params": params, "m": zeros(), "v": zeros(),
             "t": jnp.zeros((), jnp.int32)}
    acc = {"g": zeros(), "loss": jnp.float32(0.0), "count": jnp.float32(0.0)}
    routed = sum(LAYER_KINDS[kind].routed for kind in cfg.layer_types)
    if routed:
        # the routing counters of the step, summed beside the gradients
        acc["expert_pairs"] = jnp.zeros((routed, cfg.experts_held), jnp.int32)
        acc["pairs_total"] = jnp.zeros((), jnp.int32)
        # rows of the pair buffer the layers' work ran over (the live tiles'),
        # and the rows the buffer has
        acc["rows_live"] = jnp.zeros((routed,), jnp.int32)
        acc["rows_planned"] = jnp.zeros((), jnp.int32)
    if cfg.loop_steps > 1:
        # the exits' sums over the step's positions
        acc["exit_loss"] = jnp.zeros((cfg.loop_steps,), jnp.float32)
        acc["exit_mass"] = jnp.zeros((cfg.loop_steps,), jnp.float32)
        acc["exit_entropy"] = jnp.float32(0.0)
        # counters: layer applications (passes x layers a row), the real
        # tokens that went through them, and their (query, key) pairs
        for key in ("layer_applications", "loop_tokens", "attention_pairs"):
            acc[key] = jnp.zeros((), jnp.int32)
    return state, acc


def accumulate_row(cfg: SeqConfig, state: dict, acc: dict, tokens, seg):
    """One packed row [T] through forward, recomputation and backward, added
    into the step's accumulator -> (state, acc, the row's record: ``trunk``'s
    probes, each [T, ..]).  The state goes in and comes out untouched: the
    moments are this program's arguments only so that the compiler plans its
    temporaries beside ALL that is resident (it fits a program into the memory
    its own arguments leave)."""
    with jax.named_scope("seq.accumulate"):
        rows = tokens[None], seg[None]
    loss, count, g, probe = row_grads(cfg, state["params"], *rows, acc["g"])
    with jax.named_scope("seq.accumulate"):
        out = {"g": g, "loss": acc["loss"] + loss, "count": acc["count"] + count}
        if "expert_pairs" in acc:
            pairs = probe.pop("expert_pairs")
            out["expert_pairs"] = acc["expert_pairs"] + pairs
            out["pairs_total"] = acc["pairs_total"] + (
                cfg.experts_per_token * jnp.sum(seg != PAD_SEGMENT, dtype=jnp.int32))
            out["rows_live"] = acc["rows_live"] + cfg.moe_tile * jnp.sum(
                moe.expert_tiles(pairs, cfg.moe_tile), axis=-1)
            out["rows_planned"] = acc["rows_planned"] + moe.plan_rows(
                tokens.shape[0], cfg.experts_per_token, cfg.experts_held,
                cfg.moe_tile)
        if "exit_loss" in acc:
            for key in ("exit_loss", "exit_mass", "exit_entropy"):
                out[key] = acc[key] + probe.pop(key)
            real = seg != PAD_SEGMENT
            out["layer_applications"] = acc["layer_applications"] + (
                cfg.loop_steps * len(cfg.layer_types))
            out["loop_tokens"] = acc["loop_tokens"] + jnp.sum(real, dtype=jnp.int32)
            # a token sees the keys of its segment up to itself
            out["attention_pairs"] = acc["attention_pairs"] + jnp.sum(
                jnp.where(real, segment_positions(seg[None])[0] + 1, 0),
                dtype=jnp.int32)
        return state, out, jax.tree.map(lambda a: a[0], probe)


def grad_probe(n: int, g):
    """One seeded linear functional of tensor number n's gradient: ``r^T g``
    for a vector, ``r_rows^T G r_cols`` for a matrix, the r's standard normal
    from ``fold_in(PRNGKey(PROBE_SEED), n)`` (split in two for a matrix).  Its
    error is the gradient's own error, undamped and unamplified: what a norm
    cannot show and an Adam step blows up."""
    key = jax.random.fold_in(jax.random.PRNGKey(PROBE_SEED), n)
    if g.ndim > 2:  # stacked experts: one matrix, the experts' rows on end
        g = g.reshape(-1, g.shape[-1])
    if g.ndim <= 1:  # a vector, or a bias of one element
        return jnp.sum(g * jax.random.normal(key, g.shape, jnp.float32))
    kr, kc = jax.random.split(key)
    rows = jax.random.normal(kr, (g.shape[0],), jnp.float32)
    cols = jax.random.normal(kc, (g.shape[1],), jnp.float32)
    return jnp.sum(rows * mm_f32(g, cols))


@jax.named_scope("seq.step")
def apply_step(opt: AdamW, state: dict, acc: dict):
    """AdamW from the accumulated step -> (state, the accumulator zeroed, the
    step's record: loss, positions, the global gradient norm, and per tensor
    the gradient's norm and its seeded probe).  ONE scope holds all of it: the
    chip's compiler fuses a tensor's norm and probe with its update, and a
    fusion carries the op name of whichever of them is its root."""
    scale = 1.0 / jnp.maximum(acc["count"], 1.0)
    gsum = acc["g"]
    sq = {k: jnp.sum(g * g) for k, g in gsum.items()}
    record = {
        "loss": acc["loss"] * scale,
        "tokens": acc["count"],
        "grad_norm": jnp.sqrt(sum(sq.values())) * scale,
        "tensor_grad_norm": {k: jnp.sqrt(v) * scale for k, v in sq.items()},
        "tensor_grad_probe": {
            k: grad_probe(n, g) * scale for n, (k, g) in enumerate(gsum.items())},
    }
    if "expert_pairs" in acc:
        # a layer's pairs: all the step's tokens made, those of the experts
        # held here (the pairs computed), and each held expert's
        pairs = acc["expert_pairs"]
        record["moe_expert_pairs"] = pairs
        record["moe_pairs_held"] = jnp.sum(pairs, axis=-1)
        record["moe_pairs_total"] = jnp.full(
            pairs.shape[:1], acc["pairs_total"], jnp.int32)
        # the pair buffer's rows a layer's gathers and maps ran over (its
        # live tiles', summed over the step's rows) and the rows it has
        record["moe_rows_live"] = acc["rows_live"]
        record["moe_rows_planned"] = jnp.full(
            pairs.shape[:1], acc["rows_planned"], jnp.int32)
    if "exit_loss" in acc:
        # an exit's mean cross-entropy and mean mass over the step's
        # positions, the exit distribution's mean entropy, and the step's
        # counters: layer applications (passes x layers a row), real tokens
        # through them, and the (query, key) pairs of their attention
        record["loss_by_exit"] = acc["exit_loss"] * scale
        record["exit_mass"] = acc["exit_mass"] * scale
        record["exit_entropy"] = acc["exit_entropy"] * scale
        record["loop_layer_applications"] = acc["layer_applications"]
        record["loop_tokens"] = acc["loop_tokens"]
        record["loop_attention_pairs"] = acc["attention_pairs"]
    t = state["t"] + 1
    tf = t.astype(jnp.float32)
    c1 = 1.0 - opt.b1 ** tf
    c2 = 1.0 - opt.b2 ** tf
    new_p, new_m, new_v = {}, {}, {}
    for name, p in state["params"].items():
        g = gsum[name] * scale
        m = opt.b1 * state["m"][name] + (1.0 - opt.b1) * g
        v = opt.b2 * state["v"][name] + (1.0 - opt.b2) * g * g
        step = (m / c1) / (jnp.sqrt(v / c2) + opt.eps)
        if decays(name):
            step = step + opt.weight_decay * p
        new_p[name], new_m[name], new_v[name] = p - opt.lr * step, m, v
    zeroed = jax.tree.map(jnp.zeros_like, acc)
    return {"params": new_p, "m": new_m, "v": new_v, "t": t}, zeroed, record


@functools.lru_cache(maxsize=8)
def train_programs(cfg: SeqConfig, opt: AdamW):
    """The two jitted programs of a training run: a row into the accumulator,
    and the optimiser step; state and accumulator are donated to both.  Each
    is straight-line: state carried through a device loop is copied by the
    compiler (3 GB a copy at the published widths), buffers handed from one
    program to the next are not."""
    return (
        jax.jit(functools.partial(accumulate_row, cfg), donate_argnums=(0, 1)),
        jax.jit(functools.partial(apply_step, opt), donate_argnums=(0, 1)),
    )


def train_steps(cfg: SeqConfig, opt: AdamW, state: dict, acc: dict, tokens, seg):
    """``tokens``, ``seg``: [steps, rows, T] int32 on the device.  Every row
    and every optimiser step is dispatched at once (nothing is fetched in
    between, so the host never waits for a step) -> (state, acc, the records
    of the steps, the records of the FIRST step's rows (``accumulate_row``'s,
    with a kind's ``first_step_probe`` beside): those are made from the
    seeded initial weights; all still on the device)."""
    accumulate, apply = train_programs(cfg, opt)
    records, probes = [], []
    for s in range(tokens.shape[0]):
        for r in range(tokens.shape[1]):
            state, acc, probe = accumulate(state, acc, tokens[s, r], seg[s, r])
            if s == 0:
                for kind in dict.fromkeys(cfg.layer_types):
                    if LAYER_KINDS[kind].first_step_probe:
                        # the kind's first layer on exact inputs; its backward
                        # on the first row alone
                        probe.update(LAYER_KINDS[kind].first_step_probe(
                            cfg, r == 0, state["params"]["embed"], layer_params(
                                state["params"], cfg.layer_types.index(kind)),
                            tokens[s, r], seg[s, r]))
                probes.append(probe)
        state, acc, record = apply(state, acc)
        records.append(record)
    return state, acc, records, probes


# ---------------------------------------------------------------------------
# serving (a plain full forward: no cache)


@functools.partial(jax.jit, static_argnums=(0,))
def last_hidden(cfg: SeqConfig, params: dict, tokens, seg, last):
    """Final hidden state [B, D] at position ``last[b]`` of each row."""
    h = hidden_states(cfg, params, tokens, seg)
    return jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]


def num_params(cfg: SeqConfig) -> int:
    return int(sum(np.prod(s) for s in param_shapes(cfg).values()))
