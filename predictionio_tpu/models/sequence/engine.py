"""Sequence engine: next-item prediction over each entity's ORDERED history.

The DataSource returns every entity's events in ``(event_time, store order)``
order (the other templates drop order: a rating is a rating), the Preparator
turns them into rows of tokens — item vocabulary in first-seen order,
histories cut to their most recent ``maxLen`` events and packed first-fit
decreasing into rows of ``rowLen`` with segment ids — and the algorithm
trains one of the hybrid blocks of ``ops/seqmodel.py`` — gated delta-rule
linear attention among full attention (Olmo-Hybrid's layer, config.json at
huggingface.co/allenai/Olmo-Hybrid-7B), Mamba-2 state-space heads beside
grouped-query attention on one normed input (Falcon-H1's,
huggingface.co/tiiuae/Falcon-H1-34B-Instruct), or routed experts after
global (no rotary) and sliding-window (rotary) attention layers, the router
read before attention (SmallThinker's,
huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct), or a looped stack of
sandwich-norm attention layers run ``totalUtSteps`` times with the same
weights, an exit head and a gate after every pass (Ouro's,
huggingface.co/ByteDance/Ouro-2.6B), or a stack whose layers are ONE sublayer
each — Mamba-2, or grouped attention with no positions, or relu² experts
chosen by a sigmoid router beside a shared expert (Nemotron-3-Nano's,
huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16) — by next-item
cross-entropy (the looped
model: the expected cross-entropy under its exit distribution, less
``exitBeta`` times that distribution's entropy)
with AdamW: ``stepsPerRetrain`` optimiser steps of ``rowsPerStep`` rows, one
pass in packed order, from a seeded initialisation.  ``layerTypes`` says
which block; each kind reads its own sizes.

The persisted model is the float32 weights, the vocabulary, the histories
(for serving) and a small training record.  ``predict`` answers ``{user,
num}`` with the top-k of the head over the user's history by a plain full
forward (no cache): the serving path a later issue rebuilds around per-entity
recurrent state.

Sizes are the HELD share of a deployment that divides each layer over
several chips (``ops/seqmodel.py``, "The share").
"""

from __future__ import annotations

import dataclasses
import logging
from dataclasses import dataclass
from typing import Any

import numpy as np

from predictionio_tpu.core import (
    Algorithm,
    DataSource,
    Engine,
    EngineContext,
    FirstServing,
    Preparator,
    SanityCheckError,
)
from predictionio_tpu.core.engine import engine_factory
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.recommendation.engine import (
    ItemScore,
    PredictedResult,
    Query,
)
from predictionio_tpu.obs import device as device_obs
from predictionio_tpu.obs import provenance
from predictionio_tpu.obs.tracing import trace

log = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# DataSource


@dataclass(frozen=True)
class SequenceDataSourceParams:
    app_name: str = "default"
    channel_name: str | None = None
    event_names: tuple[str, ...] = ("rate",)
    entity_type: str = "user"
    target_entity_type: str = "item"


@dataclass
class SequenceData:
    """Every entity's events in time order, entities in first-event order."""

    #: object[E]: the entities
    entities: np.ndarray
    #: int64[E + 1]: entity e's events are ``order[offsets[e]:offsets[e + 1]]``
    offsets: np.ndarray
    #: int64[N]: positions in ``items``, grouped by entity, each group in
    #: (event time, store order) order
    order: np.ndarray
    #: object[N]: each event's item, in the store's read order
    items: np.ndarray

    def sanity_check(self):
        if len(self.items) == 0:
            raise SanityCheckError(
                "SequenceData has no events — check appName/eventNames")


class SequenceDataSource(DataSource):
    params_class = SequenceDataSourceParams

    def __init__(self, params: SequenceDataSourceParams | None = None):
        self.params = params or SequenceDataSourceParams()

    def read_training(self, ctx: EngineContext) -> SequenceData:
        p = self.params
        frame = ctx.p_event_store.find(
            p.app_name,
            channel_name=p.channel_name,
            entity_type=p.entity_type,
            target_entity_type=p.target_entity_type,
            event_names=list(p.event_names),
        )
        with trace("datasource.sequences") as span:
            who = BiMap.factorize(frame.entity_id)
            # stable: events of one entity at one instant keep the store's
            # order (the parquet read sorts by (event_time_ms, seq))
            order = np.lexsort((frame.event_time_ms, who.codes))
            counts = np.bincount(who.codes, minlength=len(who.vocab))
            offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
            td = SequenceData(
                entities=np.array(list(who.vocab), object),
                offsets=offsets,
                order=order,
                items=frame.target_entity_id,
            )
            span.tags = {"events": len(order), "entities": len(who.vocab)}
            del frame
        return td

    def read_eval(self, ctx: EngineContext):
        raise NotImplementedError(
            "the sequence engine has no evaluation split yet")


# ---------------------------------------------------------------------------
# Preparator


@dataclass(frozen=True)
class SequencePreparatorParams:
    #: tokens a packed row holds
    row_len: int = 8192
    #: a history keeps its most recent ``max_len`` events
    max_len: int = 8192
    #: the rows are padded (with empty rows) to a multiple of this
    rows_per_step: int = 4
    #: ids the model's share of the vocabulary holds: more items are an error
    vocab_size: int = 50176
    #: the first id of that share
    vocab_start: int = 0

    def __post_init__(self):
        if self.max_len > self.row_len:
            raise ValueError("maxLen may not exceed rowLen")


#: segment id of padding (ops/seqmodel.PAD_SEGMENT)
PAD_SEGMENT = -1


@dataclass
class PackedSequences:
    """Rows of tokens ready for the device."""

    item_vocab: BiMap
    entities: np.ndarray  # object[E]
    #: int32[E + 1] / int32[M]: each entity's kept history (ids), for serving
    history_offsets: np.ndarray
    history_tokens: np.ndarray
    #: int32[R, row_len]: item ids; int32[R, row_len]: segment ids (the
    #: entity's index; PAD_SEGMENT on padding)
    tokens: np.ndarray
    segments: np.ndarray
    #: what the rows were made for (the Preparator's parameters): the
    #: algorithm refuses rows made for another share or step size
    vocab_start: int
    vocab_size: int
    rows_per_step: int

    def sanity_check(self):
        if not (self.segments != PAD_SEGMENT).any():
            raise SanityCheckError("no tokens were packed")


def pack_first_fit_decreasing(lengths: np.ndarray, row_len: int) -> list[list[int]]:
    """Histories into rows of ``row_len``: longest first (equal lengths in
    index order), each into the first row with room; rows in the order they
    were opened."""
    free: list[int] = []
    rows: list[list[int]] = []
    for j in np.argsort(-lengths, kind="stable"):
        n = int(lengths[j])
        r = next((r for r, room in enumerate(free) if n <= room), None)
        if r is None:
            free.append(row_len)
            rows.append([])
            r = len(rows) - 1
        free[r] -= n
        rows[r].append(int(j))
    return rows


class SequencePreparator(Preparator):
    params_class = SequencePreparatorParams

    def __init__(self, params: SequencePreparatorParams | None = None):
        self.params = params or SequencePreparatorParams()

    def prepare(self, ctx: EngineContext, td: SequenceData) -> PackedSequences:
        p = self.params
        with trace("prepare.vocab") as span:
            items = BiMap.factorize(td.items)
            span.tags = {
                "path": items.path, "rows": len(items.codes),
                "items": len(items.vocab), "item_keys_hashed": items.hashed,
            }
            if len(items.vocab) > p.vocab_size:
                raise ValueError(
                    f"{len(items.vocab)} distinct items do not fit the "
                    f"vocabulary of {p.vocab_size} ids this model holds")
        with trace("prepare.pack") as span:
            ids = (items.codes[td.order] + p.vocab_start).astype(np.int32)
            full = np.diff(td.offsets)
            kept = np.minimum(full, p.max_len)
            # each history's most recent ``kept`` events
            first_kept = np.repeat(td.offsets[1:] - kept, full)
            history_tokens = ids[np.arange(len(ids)) >= first_kept]
            history_offsets = np.concatenate([[0], np.cumsum(kept)]).astype(np.int64)
            rows = pack_first_fit_decreasing(kept, p.row_len)
            while len(rows) % p.rows_per_step:
                rows.append([])
            tokens = np.zeros((len(rows), p.row_len), np.int32)
            segments = np.full((len(rows), p.row_len), PAD_SEGMENT, np.int32)
            for r, members in enumerate(rows):
                at = 0
                for j in members:
                    n = int(kept[j])
                    lo = history_offsets[j]
                    tokens[r, at : at + n] = history_tokens[lo : lo + n]
                    segments[r, at : at + n] = j
                    at += n
            span.tags = tags = {
                "rows": len(rows), "tokens": int(kept.sum()),
                "pad_tokens": int(tokens.size - kept.sum()),
                "cut_tokens": int((full - kept).sum()),
            }
        log.info(
            "packed %(tokens)d tokens into %(rows)d rows (%(pad_tokens)d "
            "padding, %(cut_tokens)d cut)", tags, extra={"pack": tags})
        return PackedSequences(
            item_vocab=items.vocab,
            entities=td.entities,
            history_offsets=history_offsets,
            history_tokens=history_tokens,
            tokens=tokens,
            segments=segments,
            vocab_start=p.vocab_start,
            vocab_size=p.vocab_size,
            rows_per_step=p.rows_per_step,
        )


# ---------------------------------------------------------------------------
# Algorithm


@dataclass(frozen=True)
class SequenceAlgorithmParams:
    """Widths as published; head, group, column and row counts as HELD
    (defaults: one of two chips' share of the Olmo-Hybrid-7B layer, one period
    deep).  ``layer_types`` names each layer's kind, and the kind fixes the
    block's form: ``linear_attention`` / ``full_attention`` (post-norm, one
    mixer a layer; the ``linear_*`` sizes, ``num_attention_heads``) or
    ``parallel_ssm_attention`` (pre-norm, a state-space and an attention mixer
    side by side: the ``mamba_*`` sizes, ``num_key_value_heads``,
    ``rope_theta`` and muP's forward multipliers, 1 where a model has none) or
    ``global_attention_moe`` / ``sliding_attention_moe`` (pre-norm, attention
    then routed experts: the ``moe_*`` sizes with the experts HELD and the first
    of them, ``sliding_window_size``, ``num_key_value_heads``, ``rope_theta``)
    or ``sandwich_attention`` (a norm before and after each sublayer inside
    the residual, rotary multi-head attention and the MLP;
    ``num_key_value_heads``, ``rope_theta``; with ``total_ut_steps`` > 1 the
    layer list is run that many times with the same weights and every pass
    ends in an exit: one head, one gate, ``exit_beta`` the entropy's weight in
    the loss) or the one-sublayer kinds ``state_space`` (the ``mamba_*``
    sizes), ``grouped_attention`` (``num_attention_heads`` on
    ``num_key_value_heads``, no positions) and ``shared_routed_experts`` (the
    ``moe_*`` sizes with ``moe_shared_expert_columns`` HELD of the shared
    expert and ``routed_scaling_factor``; the kind fixes the router's rule and
    the experts' form), mixed in one stack in any order."""

    hidden_size: int = 3840
    layer_types: tuple[str, ...] = (
        "linear_attention", "linear_attention", "linear_attention",
        "full_attention",
    )
    num_attention_heads: int = 15
    head_dim: int = 128
    linear_num_heads: int = 15
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    intermediate_size: int = 5504
    vocab_size: int = 50176
    vocab_start: int = 0
    rms_norm_eps: float = 1e-6
    rows_per_step: int = 4
    steps_per_retrain: int = 4
    learning_rate: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    adam_eps: float = 1e-8
    weight_decay: float = 0.1
    seed: int = 3
    #: the parallel block: KV heads held (None: one a query head), rotary base
    num_key_value_heads: int | None = None
    rope_theta: float = 10000.0
    #: its state space: heads and B / C groups held, a head's channels, the
    #: state's size, the convolution's width, the chunk of the scan
    mamba_n_heads: int = 0
    mamba_n_groups: int = 1
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    #: muP's forward multipliers, by their published names
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    ssm_out_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    #: gate, down
    mlp_multipliers: tuple[float, ...] = (1.0, 1.0)
    #: the routed blocks: the router's width (the published expert count), the
    #: experts held here and the first of them, experts a token, an expert's
    #: width, and the sliding layers' window (keys a query sees, itself included)
    moe_num_primary_experts: int = 0
    moe_experts_held: int = 0
    moe_expert_start: int = 0
    moe_num_active_primary_experts: int = 0
    moe_ffn_hidden_size: int = 0
    sliding_window_size: int = 0
    #: a looped model: passes through the layer list, and the weight of the
    #: exit distribution's entropy in its loss
    total_ut_steps: int = 1
    exit_beta: float = 0.1
    #: ``shared_routed_experts`` layers: the shared expert's columns held, and
    #: what a token's chosen weights sum to
    moe_shared_expert_columns: int = 0
    routed_scaling_factor: float = 1.0


@dataclass
class SequenceModel:
    #: flat name -> float32 array (``ops/seqmodel.param_shapes``): on the
    #: device out of ``train``, on the host out of ``load_persistent_model``
    params: dict
    item_vocab: BiMap
    entity_vocab: BiMap
    history_offsets: np.ndarray
    history_tokens: np.ndarray
    #: per step: loss, tokens, grad_norm; per tensor and step:
    #: tensor_grad_norm, tensor_grad_probe; and the first layer's recurrence
    #: along a seeded vector for the first step's rows [rows a step, row_len,
    #: heads] (``ops/seqmodel.trunk``), under the name of its kind
    #: (``seqmodel.PROBE_NAME``): delta_rule_probe (the delta rule's output)
    #: or ssd_probe (the state space's ``S_t C_t``).  A routed block: moe_probe
    #: (the first layer's experts on its normed input, [.., 1]), ``choices``
    #: [rows a step, routed layers, row_len, experts a token] for the same
    #: rows, and per step and routed layer moe_pairs_total, moe_pairs_held,
    #: moe_expert_pairs [.., experts held], moe_rows_live and moe_rows_planned
    #: (the pair buffers' live tiles' rows, and all they have;
    #: ``ops/seqmodel.apply_step``).  A
    #: looped model: exit_probe (each position's exit distribution, [.., passes]),
    #: carry_probe (the mean square of the state each later pass read) and, at
    #: a few positions, head_probe with head_probe_state (every exit's
    #: cross-entropy and the exit state it came from) for the same rows, and
    #: per step loss_by_exit and exit_mass [.., passes], exit_entropy and the
    #: counters loop_layer_applications, loop_tokens, loop_attention_pairs.
    #: A stack of one-sublayer kinds: ssd_probe (the first state-space
    #: layer's) AND moe_probe (the first experts layer's ``f`` on the normed
    #: embedded rows) with moe_grad_probe (that layer's experts' gradients on
    #: the first row: ``seqmodel.experts_probe``), ``choices`` [rows a step,
    #: ROUTED layers, row_len, experts a token] and the routing counters over
    #: the routed layers
    training_record: dict
    config: Any = None

    def sanity_check(self):
        if not np.isfinite(np.asarray(self.training_record["loss"])).all():
            raise SanityCheckError("the training loss is not finite")


class SequenceAlgorithm(Algorithm):
    flavor = "P2L"
    params_class = SequenceAlgorithmParams
    query_class = Query

    def __init__(self, params: SequenceAlgorithmParams | None = None):
        self.params = params or SequenceAlgorithmParams()

    def seq_config(self):
        from predictionio_tpu.ops.seqmodel import MuP, SeqConfig

        p = self.params
        gate, down = p.mlp_multipliers
        return SeqConfig(
            hidden=p.hidden_size, layer_types=tuple(p.layer_types),
            heads=p.num_attention_heads, head_dim=p.head_dim,
            lin_heads=p.linear_num_heads, lin_key_dim=p.linear_key_head_dim,
            lin_value_dim=p.linear_value_head_dim,
            conv_width=p.linear_conv_kernel_dim, mlp_cols=p.intermediate_size,
            vocab_rows=p.vocab_size, vocab_start=p.vocab_start,
            eps=p.rms_norm_eps, neg_eigval=p.linear_allow_neg_eigval,
            kv_heads=p.num_key_value_heads, rope_theta=p.rope_theta,
            ssm_heads=p.mamba_n_heads, ssm_head_dim=p.mamba_d_head,
            ssm_state=p.mamba_d_state, ssm_groups=p.mamba_n_groups,
            ssm_conv_width=p.mamba_d_conv, ssm_chunk=p.mamba_chunk_size,
            mup=MuP(
                embedding=p.embedding_multiplier, lm_head=p.lm_head_multiplier,
                ssm_in=p.ssm_in_multiplier, ssm_zones=tuple(p.ssm_multipliers),
                ssm_out=p.ssm_out_multiplier,
                attention_in=p.attention_in_multiplier,
                attention_out=p.attention_out_multiplier, key=p.key_multiplier,
                mlp_gate=gate, mlp_down=down,
            ),
            experts=p.moe_num_primary_experts, experts_held=p.moe_experts_held,
            expert_start=p.moe_expert_start,
            experts_per_token=p.moe_num_active_primary_experts,
            expert_width=p.moe_ffn_hidden_size, window=p.sliding_window_size,
            loop_steps=p.total_ut_steps, exit_beta=p.exit_beta,
            shared_cols=p.moe_shared_expert_columns,
            routed_scale=p.routed_scaling_factor,
        )

    def train(self, ctx: EngineContext, pd: PackedSequences) -> SequenceModel:
        import jax
        import jax.numpy as jnp

        from predictionio_tpu.ops import seqmodel

        p = self.params
        cfg = self.seq_config()
        opt = seqmodel.AdamW(
            lr=p.learning_rate, b1=p.beta1, b2=p.beta2, eps=p.adam_eps,
            weight_decay=p.weight_decay,
        )
        for name, packed, mine in (
            ("vocabStart", pd.vocab_start, p.vocab_start),
            ("vocabSize", pd.vocab_size, p.vocab_size),
            ("rowsPerStep", pd.rows_per_step, p.rows_per_step),
        ):
            if packed != mine:
                raise ValueError(
                    f"the Preparator packed rows for {name} {packed}, the "
                    f"algorithm is configured with {mine}")
        need = p.steps_per_retrain * p.rows_per_step
        if len(pd.tokens) < need:
            raise SanityCheckError(
                f"{len(pd.tokens)} packed rows are fewer than the "
                f"{p.steps_per_retrain} steps of {p.rows_per_step} rows asked for")
        shape = (p.steps_per_retrain, p.rows_per_step, pd.tokens.shape[1])
        with trace("seq.init") as span:
            state, acc = seqmodel.init_state(cfg, p.seed)
            tokens = jnp.asarray(pd.tokens[:need].reshape(shape))
            segments = jnp.asarray(pd.segments[:need].reshape(shape))
            jax.block_until_ready((state, acc))
            n = seqmodel.num_params(cfg)
            span.tags = {"params": n, "bytes": 16 * n}
        with trace("seq.device_loop") as span:
            state, acc, records, probes = seqmodel.train_steps(
                cfg, opt, state, acc, tokens, segments)
            jax.block_until_ready(state["params"])
            del acc
            span.tags = {
                "steps": p.steps_per_retrain, "rows": need,
                "tokens": int((pd.segments[:need] != PAD_SEGMENT).sum()),
                "block": "+".join(dict.fromkeys(cfg.layer_types)),
                **_loop_tags(cfg),
            }
        with trace("seq.fetch") as span:
            # the weights stay on the device: the model store's writers fetch
            # them part by part, under the write (``persist.fetch``)
            params = state["params"]
            # every copy started before the first is waited for: a step's
            # record is a few hundred small arrays, 0.4 ms each one by one
            records, probes = jax.device_get((records, probes))
            record = jax.tree.map(lambda *xs: np.stack(xs), *records)
            record.update(jax.tree.map(lambda *xs: np.stack(xs), *probes))
            span.tags = {
                "bytes": int(sum(
                    v.nbytes for v in jax.tree.leaves(record))),
                **_loop_tags(cfg)}
            if "moe_expert_pairs" in record:
                span.tags["counters"] = _routing_counters(record)
            if "loop_layer_applications" in record:
                span.tags["counters"] = {
                    key: int(record[key].sum()) for key in (
                        "loop_layer_applications", "loop_tokens",
                        "loop_attention_pairs")}
            del state
        log.info(
            "trained %d steps: loss %s", p.steps_per_retrain,
            np.round(record["loss"], 4).tolist(),
            extra={"seq_loss": [float(x) for x in record["loss"]]},
        )
        return SequenceModel(
            params=params,
            item_vocab=pd.item_vocab,
            entity_vocab=BiMap.from_keys(pd.entities),
            history_offsets=pd.history_offsets,
            history_tokens=pd.history_tokens,
            training_record=record,
            config=cfg,
        )

    # -- serving ---------------------------------------------------------------

    def predict(self, model: SequenceModel, query: Query) -> PredictedResult:
        return self.batch_predict(model, [(0, query)])[0][1]

    def batch_predict(self, model: SequenceModel, indexed_queries):
        """Top-k of the head over each known user's history: one full forward
        a query (histories padded to a power of two of tokens), then the
        serving top-k kernel over the head."""
        import jax.numpy as jnp

        from predictionio_tpu.ops import seqmodel
        from predictionio_tpu.ops.topk import fused_topk_batch

        provenance.note(engine_path="sequence.full_forward")
        cfg = model.config or self.seq_config()
        params = _device_params(model)
        n_items = len(model.item_vocab)
        pending, out = [], []
        for i, q in indexed_queries:
            e = model.entity_vocab.get(q.user)
            k = min(q.num, n_items)
            if e is None or k <= 0:
                out.append((i, PredictedResult()))
                continue
            hist = model.history_tokens[
                model.history_offsets[e] : model.history_offsets[e + 1]]
            length = max(1 << (len(hist) - 1).bit_length(), cfg.token_multiple)
            tokens = np.zeros((1, length), np.int32)
            seg = np.full((1, length), PAD_SEGMENT, np.int32)
            tokens[0, : len(hist)] = hist
            seg[0, : len(hist)] = 0
            h = seqmodel.last_hidden(
                cfg, params, jnp.asarray(tokens), jnp.asarray(seg),
                jnp.asarray([len(hist) - 1], jnp.int32))
            if cfg.mup.lm_head != 1.0:
                h = h * cfg.mup.lm_head
            pending.append(
                (i, fused_topk_batch(h, params["head"], k, limit=n_items)))
        for i, packed in _fetch_winners(pending):
            out.append((i, PredictedResult(item_scores=tuple(
                ItemScore(item=model.item_vocab.inverse(int(j)), score=float(s))
                for s, j in zip(packed[0, 0], packed[1, 0])
                if np.isfinite(s)
            ))))
        return sorted(out, key=lambda pair: pair[0])

    # -- persistence -------------------------------------------------------------

    def make_persistent_model(self, ctx: EngineContext, model: SequenceModel):
        return {
            "params": model.params,
            "item_vocab": model.item_vocab.to_state(),
            "entity_vocab": model.entity_vocab.to_state(),
            "history_offsets": model.history_offsets,
            "history_tokens": model.history_tokens,
            "training_record": model.training_record,
            "algorithm_params": dataclasses.asdict(self.params),
        }

    def load_persistent_model(self, ctx: EngineContext, data) -> SequenceModel:
        return SequenceModel(
            params=data["params"],
            item_vocab=BiMap.from_state(data["item_vocab"]),
            entity_vocab=BiMap.from_state(data["entity_vocab"]),
            history_offsets=data["history_offsets"],
            history_tokens=data["history_tokens"],
            training_record=data["training_record"],
            config=SequenceAlgorithm(
                SequenceAlgorithmParams(**data["algorithm_params"])
            ).seq_config(),
        )


def _loop_tags(cfg) -> dict:
    """What a looped model's spans say of it: its passes, and its exits; a
    stack of one-sublayer kinds: how many layers of each kind it holds."""
    from predictionio_tpu.ops import seqmodel

    if set(cfg.layer_types) & set(seqmodel.SUBLAYER_KINDS):
        return {
            seqmodel.LAYER_KINDS[kind].sublayer_tag: cfg.layer_types.count(kind)
            for kind in seqmodel.SUBLAYER_KINDS}
    if cfg.loop_steps == 1:
        return {}
    return {"loop_steps": cfg.loop_steps, "exits": cfg.loop_steps}


def _routing_counters(record: dict) -> dict:
    """The routed layers' counters of a training record as flat numbers (a
    span's ``counters`` tag, the ``stages`` extra's ``counters``): the
    retrain's sums, and a step and layer the pairs all tokens made, those of
    the experts held (the pairs computed) and the busiest held expert's."""
    pairs = record["moe_expert_pairs"]
    live = int(record["moe_rows_live"].sum())
    planned = int(record["moe_rows_planned"].sum())
    out = {
        "moe_routed_layers": int(pairs.shape[1]),
        "moe_experts_held": int(pairs.shape[-1]),
        "moe_pairs_total": int(record["moe_pairs_total"].sum()),
        "moe_pairs_held": int(record["moe_pairs_held"].sum()),
        # the pair buffers' rows the layers' gathers and maps ran over (the
        # live tiles'), of the rows the buffers have
        "moe_rows_live": live,
        "moe_rows_planned": planned,
        "moe_rows_live_pct": 100.0 * live / planned,
    }
    for s, l in np.ndindex(pairs.shape[:2]):
        at = f".step{s}.layer{l}"
        out["moe_pairs_total" + at] = int(record["moe_pairs_total"][s, l])
        out["moe_pairs_held" + at] = int(record["moe_pairs_held"][s, l])
        out["moe_expert_pairs_max" + at] = int(pairs[s, l].max())
    return out


def _fetch_winners(pending: list) -> list:
    """The wave's packed winners, to the host: once a wave, after every
    query's forward and top-k have been dispatched."""
    with device_obs.wave_stage("d2h"):
        return [(i, np.asarray(packed)) for i, packed in pending]


def _device_params(model: SequenceModel) -> dict:
    """The weights on the device, placed once a served model."""
    cached = getattr(model, "_device_params", None)
    if cached is None:
        import jax.numpy as jnp

        cached = {k: jnp.asarray(v) for k, v in model.params.items()}
        model._device_params = cached
    return cached


@engine_factory("sequence")
def sequence_engine() -> Engine:
    return Engine(
        SequenceDataSource,
        SequencePreparator,
        # one algorithm under the name of each block's recurrence
        {"gdn": SequenceAlgorithm, "ssd": SequenceAlgorithm,
         "moe": SequenceAlgorithm, "loop": SequenceAlgorithm,
         "hybrid": SequenceAlgorithm},
        FirstServing,
    )
