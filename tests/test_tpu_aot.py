"""The chip's programs, compiled for a v5e without a chip.

libtpu can AOT-compile for a named topology from the CPU sandbox
(``jax.experimental.topologies``), so whether today's Mosaic and XLA accept
every Pallas kernel and the whole fused ALS train program — and how much HBM
XLA plans for it — is a tier-1 question, answered here instead of on chip
budget.  Nothing runs: these tests see compile errors and memory plans, not
numerics (``python chip_smoke.py`` on the chip checks those against numpy).
One installation, one topology: no skip.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from predictionio_tpu.ops import als, als_pallas
from predictionio_tpu.ops.topk import fused_topk_batch

#: one v5e chip's HBM (Google Cloud "TPU v5e": 16 GB)
V5E_HBM_BYTES = 16 * 10**9

# the MovieLens-20M shape chip_smoke.py trains at
NNZ, NUM_USERS, NUM_ITEMS = 20_000_000, 138_493, 26_744


@pytest.fixture(scope="module")
def v5e():
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    assert len(topo.devices) == 4
    return topo


def _spec_on(sharding):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return sds


def _compile(jitted, *specs):
    return jitted.trace(*specs).lower(lowering_platforms=("tpu",)).compile()


@pytest.mark.parametrize(
    "batch,k",
    [(8, 10), (32, 10), (512, 10), (1024, 10), (32, 16), (8, 128)],
)
def test_fused_topk_batch_compiles(v5e, batch, k):
    """The serving top-k kernel at the wave sizes serving pads to and the
    bulk sizes batchpredict sends, over the ML-20M item table."""
    sds = _spec_on(SingleDeviceSharding(v5e.devices[0]))
    fn = jax.jit(lambda q, t: fused_topk_batch(q, t, k, interpret=False))
    _compile(fn, sds((batch, 10)), sds((NUM_ITEMS, 10)))


@pytest.mark.parametrize("rank", [10, 32])
def test_als_accumulators_compile(v5e, rank):
    """Both Pallas normal-equation accumulators: the single-grid fused
    kernel and the chunk-scan one the OOM ladder falls back to."""
    sds = _spec_on(SingleDeviceSharding(v5e.devices[0]))
    T = als_pallas.T
    nt, nb, n_other = 64, 8, 4096
    i32 = jnp.int32

    fused = jax.jit(
        lambda plan, oth, wrv, f: als_pallas.segment_stats_fused(
            plan, oth, wrv, f, nt, nb
        )
    )
    _compile(
        fused,
        (sds((nt,), i32), sds((nt,), i32), sds((nt, T // 128, 128), i32)),
        sds((nt, T), i32), sds((nt, 3, T)), sds((n_other, rank)),
    )

    chunks, tpc = 2, 32
    chunked = jax.jit(
        lambda plan, oth, rat, val, f: als_pallas.segment_stats_pallas(
            plan, oth, rat, val, f, False, 1.0, tpc, nb
        )
    )
    _compile(
        chunked,
        (
            sds((chunks, tpc), i32), sds((chunks, tpc), i32),
            sds((chunks, tpc, T // 128, 128), i32), sds((chunks, nb)),
        ),
        sds((chunks, tpc * T), i32), sds((chunks, tpc * T)),
        sds((chunks, tpc * T)), sds((n_other, rank)),
    )


@pytest.mark.parametrize("rank", [10, 32])
def test_fused_als_train_program_fits_the_chip(v5e, rank):
    """The WHOLE fused train program (all iterations in one fori_loop) at
    ML-20M shapes: it must compile, and XLA's own memory plan — temps plus
    the staged streams it takes as arguments — must fit one v5e.  The plan
    shape is the worst case of ``build_plan``'s block padding (every block
    wastes a whole tile), so real data plans slightly under this."""
    sds = _spec_on(SingleDeviceSharding(v5e.devices[0]))
    T = als_pallas.T
    users_pad = (NUM_USERS + 127) // 128 * 128
    items_pad = (NUM_ITEMS + 127) // 128 * 128
    nb_u, nb_i = users_pad // als_pallas.S, items_pad // als_pallas.S
    nt_u, nt_i = NNZ // T + nb_u, NNZ // T + nb_i
    steps = als._make_pallas_step(
        (nt_u, nb_u, nt_i, nb_i), als.ALSParams(rank=rank),
        users_pad, items_pad, fused=True,
    )
    i32 = jnp.int32

    def side(nt):
        return (
            (sds((nt,), i32), sds((nt,), i32), sds((nt, T // 128, 128), i32)),
            sds((nt, T), i32), sds((nt, T)), sds((nt, T)),
        )

    compiled = _compile(
        steps, *side(nt_u), *side(nt_i),
        sds((users_pad, rank)), sds((items_pad, rank)), sds((), i32),
    )
    mem = compiled.memory_analysis()
    planned = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert planned < V5E_HBM_BYTES, (
        f"rank {rank}: XLA plans {planned / 1e9:.1f} GB "
        f"({mem.temp_size_in_bytes / 1e9:.1f} GB of temps) on a 16 GB chip"
    )


def test_ncf_wave_program_compiles(v5e):
    """``_score_topk_batch`` — the ``ncf.device_wave`` program — at the one
    padded shape serving uses (32 x k 16) over the flagship's tables."""
    from predictionio_tpu.models.ncf.engine import _score_topk_batch

    sds = _spec_on(SingleDeviceSharding(v5e.devices[0]))
    params = {
        "user_emb": sds((NUM_USERS, 10)),
        "item_emb": sds((NUM_ITEMS, 10)),
        "item_bias": sds((NUM_ITEMS,)),
        "out_b": sds((1,)),
    }
    _compile(_score_topk_batch, params, sds((32,), jnp.int32), NUM_ITEMS, 16)


def test_serving_score_contractions_are_full_f32():
    """Every device serving scorer beside the fused kernel — the NCF wave
    (pure GMF and MLP tower), ALS's materialized-row top-k, the similarity
    templates — contracts at HIGHEST.  The TPU runs a DEFAULT f32 dot as
    one bf16 pass (scores ~1e-3 off, ranks differ from the host replica);
    the CPU computes full f32 either way, so CPU numerics cannot see a
    regression here: the program lowered for the TPU is read instead."""
    from predictionio_tpu.models.ncf.engine import _score_topk_batch
    from predictionio_tpu.models.recommendation.engine import (
        _device_score_topk,
    )
    from predictionio_tpu.ops.ncf import (
        NCFParams,
        init_ncf,
        score_users_vs_items,
    )
    from predictionio_tpu.ops.similarity import cosine_topk, dot_topk

    def dots(jitted, *specs):
        text = jitted.trace(*specs).lower(lowering_platforms=("tpu",)).as_text()
        found = [ln for ln in text.splitlines() if "stablehlo.dot_general" in ln]
        assert found, "no contraction in the lowered program"
        return found

    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    users = jax.ShapeDtypeStruct((32,), jnp.int32)
    mask = jax.ShapeDtypeStruct((NUM_ITEMS,), jnp.bool_)
    towers = [
        jax.eval_shape(
            lambda p=p: init_ncf(jax.random.PRNGKey(0), 64, NUM_ITEMS, p)
        )
        for p in (
            NCFParams(embed_dim=10, mlp_layers=()),
            NCFParams(embed_dim=8, mlp_layers=(16, 8)),
        )
    ]
    programs = [
        dots(_score_topk_batch, tower, users, NUM_ITEMS, 16)
        for tower in towers
    ] + [
        # the per-shard scorer of factor-sharded NCF serving
        dots(
            jax.jit(score_users_vs_items),
            {k: v for k, v in tower.items() if k in ("mlp", "out_w", "out_b")},
            f32(32, tower["user_emb"].shape[1]),
            tower["item_emb"],
        )
        for tower in towers
    ] + [
        dots(_device_score_topk, f32(64, 10), f32(NUM_ITEMS, 10), users, 16),
        dots(dot_topk, f32(10), f32(NUM_ITEMS, 10), mask, 16),
        dots(cosine_topk, f32(3, 10), f32(NUM_ITEMS, 10), mask, 16),
    ]
    for lines in programs:
        for ln in lines:
            assert "precision = [HIGHEST, HIGHEST]" in ln, ln


def test_per_shard_fused_topk_compiles_on_four_chips(v5e):
    """The sharded serving kernel as ``ALSAlgorithm._sharded_topk`` builds
    it: each of 4 devices runs the fused top-k over ITS quarter of the item
    table inside shard_map, then the k winners all_gather and merge."""
    from predictionio_tpu.parallel.placement import (
        ShardPlan,
        build_sharded_topk,
    )

    mesh = Mesh(np.array(v5e.devices), ("model",))
    plan = ShardPlan.model_parallel(
        ["item_factors"], rows={"item_factors": NUM_ITEMS}
    )
    rows_pad = (NUM_ITEMS + 3) // 4 * 4
    batch, k = 32, 16

    def fused_local(item_local, q, kc, limit):
        packed = fused_topk_batch(
            q, item_local, kc, limit=limit, interpret=False
        )
        return packed[0], packed[1].astype(jnp.int32)

    kernel = build_sharded_topk(
        mesh, plan, lambda item_local, q: q @ item_local.T,
        ["item_factors"], n_items=NUM_ITEMS, k=k,
        name="aot.sharded_topk", local_topk_fn=fused_local,
    )
    table = jax.ShapeDtypeStruct(
        (rows_pad, 10), jnp.float32,
        sharding=NamedSharding(mesh, PartitionSpec("model", None)),
    )
    queries = jax.ShapeDtypeStruct(
        (batch, 10), jnp.float32,
        sharding=NamedSharding(mesh, PartitionSpec()),
    )
    _compile(kernel, table, queries)


# the sequence engine's kernels at Olmo-Hybrid's published widths (one of two
# chips' share: 15 heads of d_k 96 / d_v 192, rows of 8192 tokens in chunks
# of 64; five heads a group as the layer runs them)
GDN = dict(heads=5, chunks=128, chunk=64, dk=96, dv=192)


def _gdn_parts(sds):
    h, nc, c, dk, dv = (GDN[k] for k in ("heads", "chunks", "chunk", "dk", "dv"))
    return (
        sds((1, h, nc, c, dk)), sds((1, h, nc, c, dv)), sds((1, h, nc, c, dk)),
        sds((1, h, nc, c, c)), sds((1, h, nc, c, dk)), sds((1, h, nc)),
    )


@pytest.mark.parametrize("passes", ["forward", "forward_backward"])
def test_gdn_chunk_kernels_compile(v5e, passes):
    """The delta rule's sequential pass: ``gdn_chunk_fwd`` alone, and with
    ``gdn_chunk_bwd`` under its ``custom_vjp`` (key / value sizes that are no
    multiple of the 128 lanes: Mosaic pads them, and must accept that)."""
    from predictionio_tpu.ops import gdn

    sds = _spec_on(SingleDeviceSharding(v5e.devices[0]))
    if passes == "forward":
        fn = jax.jit(lambda *p: gdn.chunk_pallas(*p, False))
    else:
        fn = jax.jit(jax.grad(
            lambda *p: gdn.chunk_pallas(*p, False).sum(), argnums=tuple(range(6))))
    # the shape one call site of the layer has: a group of the 15 held heads
    assert gdn.heads_per_block(15) == GDN["heads"]
    text = _compile(fn, *_gdn_parts(sds)).as_text()
    assert "gdn_chunk_fwd" in text
    assert ("gdn_chunk_bwd" in text) == (passes == "forward_backward")


def _assert_splash_alone(text: str):
    """The library's splash kernels as a row program names them (the forward
    keeping the logsumexp for the backward, the two backward kernels), and no
    call of the flash kernel that stood there before PR 34."""
    for name in ("splash_mqa_fwd_segmented_residuals", "splash_mqa_dkv_segmented",
                 "splash_mqa_dq_segmented"):
        assert name in text, name
    for name in ("flash_attention", "flash_mha_bwd"):
        assert name not in text, name


def _assert_buffer_work_follows_the_live_tiles(text: str, rows: int):
    """A routed row program as the chip compiles it gathers no array of the
    pair buffer's ``rows`` rows in one operation: the gathers into the buffer
    run window by window inside loops under ``moe.dispatch`` (the forward's,
    the recomputed forward's) and ``moe.combine`` (the backward's;
    ``moe._live_windows``: as many windows as hold the live tiles), and the
    maps between the products are kernels on the products' grid of tiles
    under ``moe.experts`` (``moe._tile_maps``), where no loop is and no
    operation of the compiler's own maps an array of ``rows`` rows."""
    import re

    from predictionio_tpu.ops import moe

    whole = re.findall(rf"= \w+\[{rows},\d+\]\S* gather\(", text)
    assert not whole, whole[:3]
    for component in ("moe.dispatch", "moe.combine"):
        assert re.search(rf'{component}/while"', text), component
    assert not re.search(r'moe\.experts/while"', text)
    for name in ("moe_map_act", "moe_map_act_grad"):
        assert re.search(rf'moe\.experts/{name}/pallas_call', text), name
    # between the products, nothing but the kernels writes ``rows`` rows
    # (the compiler's relayouts of the [rows, 1] weights apart)
    mapped = re.findall(
        rf"= \w+\[{rows},(\d+)\]\S* (?:fusion|convert|multiply|maximum|select)\(", text)
    assert not [n for n in mapped if int(n) > 1], mapped[:3]
    # the loops' buffers start as the allocator hands them: a kernel that
    # writes nothing each, and no broadcast over the buffer's rows
    assert "moe_unwritten" in text
    assert not re.findall(rf"= \w+\[{rows},\d+\]\S* broadcast\(", text)
    windows = re.findall(
        rf"= \w+\[(\d+),\d+\]\S* fusion\([^\n]*moe\.(?:dispatch|combine)/while/body/jit\(_take\)/gather", text)
    assert windows and {int(n) for n in windows} == {moe.WINDOW_TILES * 256}, set(windows)


def _assert_every_named_operation_is_scoped(text: str):
    """In a row program as the chip compiles it, every op name the PROGRAM
    wrote (they start with its ``jit(``; the compiler's own ``gather`` or an
    argument's name do not) holds one of ``seqmodel.SCOPES``; a checkpoint's
    own copies (``.../remat2``) alone hold none.  What a trace then reads as
    ``(no scope)`` is what the compiler made without an op name."""
    import re

    from predictionio_tpu.ops import seqmodel

    bare = {
        name for name in re.findall(r'op_name="(jit\([^"]*)"', text)
        if not any(f"{s}/" in name or f"{s})" in name or name.endswith(f"/{s}")
                   for s in seqmodel.SCOPES)}
    assert {n.rsplit("/", 1)[-1] for n in bare} <= {"remat2"}, sorted(bare)[:5]


def test_segment_masked_splash_attention_compiles(v5e):
    """The full-attention layers' library kernel as ``ops/seqmodel`` calls
    it: 15 heads of 128 over a row of 8192 with segment ids under a causal
    mask, forward and backward (no 8192 x 8192 score matrix in the program's
    temporaries), and no call of the flash kernel left."""
    from predictionio_tpu.ops import seqmodel

    sds = _spec_on(SingleDeviceSharding(v5e.devices[0]))
    cfg = seqmodel.SeqConfig(
        hidden=3840, layer_types=(seqmodel.FULL,), heads=15, head_dim=128,
        lin_heads=15, lin_key_dim=96, lin_value_dim=192, conv_width=4,
        mlp_cols=5504, vocab_rows=50176, attn_impl="flash")
    shapes = {
        k[len("layer0."):]: sds(s) for k, s in seqmodel.param_shapes(cfg).items()
        if k.startswith("layer0.")}
    fn = jax.jit(jax.grad(lambda p, x, seg: seqmodel.full_attention(
        cfg, p, x, seg).sum(), argnums=(0, 1)))
    compiled = _compile(fn, shapes, sds((1, 8192, 3840)), sds((1, 8192), jnp.int32))
    _assert_splash_alone(compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 10**9


def test_serving_lengths_build_each_splash_kernel_once(v5e):
    """``batch_predict`` pads a history to a power of two of tokens: the
    routed block's two masks (causal, window 4096) at every length from 128 to
    a row of 16,384 compile forward for the chip (7 query heads on one KV
    head), and the masks' metadata is built once a (length, mask): a second
    query of each length finds all sixteen in ``_splash_kernel``'s cache."""
    from predictionio_tpu.ops import seqmodel

    sds = _spec_on(SingleDeviceSharding(v5e.devices[0]))
    cfg = seqmodel.SeqConfig(
        hidden=2560, layer_types=(seqmodel.GLOBAL_MOE, seqmodel.SLIDING_MOE),
        heads=7, kv_heads=1, head_dim=128, lin_heads=0, lin_key_dim=0,
        lin_value_dim=0, conv_width=4, mlp_cols=0, vocab_rows=18992, experts=64,
        experts_held=16, experts_per_token=6, expert_width=768, window=4096,
        attn_impl="flash")
    lengths = [1 << n for n in range(7, 15)]
    assert max(lengths) == 16384 and cfg.token_multiple == min(lengths) == 128
    seqmodel._splash_kernel.cache_clear()
    for again in (False, True):
        for T in lengths:
            for window in (None, cfg.window):
                fn = jax.jit(lambda q, k, v, seg, window=window: seqmodel._attend(
                    cfg, q, k, v, seg, window))
                specs = (sds((1, T, 7, 128)), sds((1, T, 1, 128)), sds((1, T, 1, 128)),
                         sds((1, T), jnp.int32))
                if again:
                    fn.trace(*specs)
                else:
                    assert "splash_mqa_fwd_segmented" in _compile(fn, *specs).as_text()
        info = seqmodel._splash_kernel.cache_info()
        assert info.misses == info.currsize == 2 * len(lengths), info
        assert info.hits == (2 * len(lengths) if again else 0), info


# the Falcon-H1 block at its published widths (one of four chips' share: 8
# state-space heads of 128 channels in one group, state 256, rows of 8192
# tokens in chunks of 128; four heads a grid step)
SSD = dict(heads=8, groups=1, chunks=64, chunk=128, p=128, n=256)


def _ssd_parts(sds):
    h, g, nc, c, p, n = (SSD[k] for k in ("heads", "groups", "chunks", "chunk", "p", "n"))
    return (sds((1, g, nc, c, n)), sds((1, g, nc, c, n)), sds((1, h, nc, c, p)),
            sds((1, h, nc)))


@pytest.mark.parametrize("passes", ["forward", "forward_backward"])
def test_ssd_chunk_kernels_compile(v5e, passes):
    """The state space's sequential pass: ``ssd_chunk_fwd`` alone, and with
    ``ssd_chunk_bwd`` under its ``custom_vjp`` (B and C one block a group,
    their gradients summed over a grid step's heads inside the kernel)."""
    from predictionio_tpu.ops import ssd

    sds = _spec_on(SingleDeviceSharding(v5e.devices[0]))
    if passes == "forward":
        fn = jax.jit(lambda *p: ssd.chunk_pallas(*p, False))
    else:
        fn = jax.jit(jax.grad(
            lambda *p: ssd.chunk_pallas(*p, False).sum(), argnums=tuple(range(4))))
    assert ssd.heads_per_block(SSD["heads"] // SSD["groups"]) == 4
    text = _compile(fn, *_ssd_parts(sds)).as_text()
    assert "ssd_chunk_fwd" in text
    assert ("ssd_chunk_bwd" in text) == (passes == "forward_backward")


def test_falcon_h1_row_program_fits_beside_its_arguments(v5e):
    """The training row of ``falcon-h1-34b-tp4.retrain`` as the chip compiles
    it (splash attention, the SSD kernels; 8192 tokens, 769.6 M parameters at
    16 bytes): the compiler plans its temporaries beside 12.31 GB of weights,
    moments and gradient sums, under the 16,909,336,064 B the v5e's allocator
    reports as its limit (PERF.md).  A plan, not a reading."""
    import dataclasses
    import json
    from pathlib import Path

    from predictionio_tpu.models.sequence import engine as seq
    from predictionio_tpu.ops import seqmodel
    from predictionio_tpu.utils.params import extract_params

    body = json.loads((Path(__file__).resolve().parents[1] / "benchmark" / "configs"
                       / "falcon-h1-34b-tp4.json").read_text())
    algo = seq.SequenceAlgorithm(extract_params(
        seq.SequenceAlgorithmParams, body["engine_json"]["algorithms"][0]["params"]))
    cfg = dataclasses.replace(algo.seq_config(), attn_impl="flash", ssm_impl="pallas")
    assert seqmodel.num_params(cfg) == body["share"]["parameters_held"] == 769_637_472
    row_len = body["engine_json"]["preparator"]["params"]["rowLen"]
    sds = _spec_on(SingleDeviceSharding(v5e.devices[0]))
    params = {k: sds(s) for k, s in seqmodel.param_shapes(cfg).items()}
    state = {"params": params, "m": params, "v": params, "t": sds((), jnp.int32)}
    acc = {"g": params, "loss": sds(()), "count": sds(())}
    accumulate, _ = seqmodel.train_programs(cfg, seqmodel.AdamW())
    compiled = _compile(
        accumulate, state, acc, sds((row_len,), jnp.int32), sds((row_len,), jnp.int32))
    text = compiled.as_text()
    assert "ssd_chunk_fwd" in text and "ssd_chunk_bwd" in text
    _assert_splash_alone(text)
    _assert_every_named_operation_is_scoped(text)
    plan = compiled.memory_analysis()
    assert plan.argument_size_in_bytes == pytest.approx(16 * 769_637_472, rel=1e-3)
    assert plan.alias_size_in_bytes >= 0.999 * plan.argument_size_in_bytes  # donated
    assert plan.argument_size_in_bytes + plan.temp_size_in_bytes < 16_909_336_064


# the SmallThinker block at its published widths (one of four chips' share: 16
# of 64 experts of width 768 on a stream of 2560, 6 experts a token, rows of
# 16384 tokens, tiles of 256 pairs)
MOE = dict(tokens=16384, hidden=2560, width=768, experts=64, held=16, k=6, tile=256)


@pytest.mark.parametrize("passes", ["forward", "forward_backward"])
def test_moe_grouped_kernels_compile(v5e, passes):
    """The experts' grouped products as ``ops/moe`` calls them at the cell's
    size: ``moe_gmm_*`` forward (a weight block [2560, 1536] bf16 resident
    across an expert's tiles), and under ``expert_ffn``'s own backward the
    transposed products and ``moe_tgmm_*`` (pairs^T x pairs, contracted over a
    tile's rows)."""
    from predictionio_tpu.ops import moe

    sds = _spec_on(SingleDeviceSharding(v5e.devices[0]))
    N, D, F, E, held, k, tile = (MOE[n] for n in (
        "tokens", "hidden", "width", "experts", "held", "k", "tile"))
    assert moe.plan_rows(N, k, held, tile) == N * k + held * tile == 102_400

    def layer(m, logits, valid, gate, up, down):
        return moe.experts_layer(
            m, logits, valid, gate, up, down, k=k, start=0, tile=tile,
            dtype=jnp.bfloat16, impl="pallas")[0].sum()

    fn = jax.jit(layer if passes == "forward" else jax.grad(
        layer, argnums=(0, 1, 3, 4, 5)))
    text = _compile(
        fn, sds((N, D)), sds((N, E)), sds((N,), jnp.bool_), sds((held, D, F)),
        sds((held, D, F)), sds((held, F, D))).as_text()
    assert "moe_gmm_gate_up" in text and "moe_gmm_down" in text
    for name in ("moe_gmm_down_dlhs", "moe_gmm_gate_up_dlhs", "moe_tgmm_down",
                 "moe_tgmm_gate_up"):
        assert (name in text) == (passes == "forward_backward"), name


def test_smallthinker_row_program_fits_beside_its_arguments(v5e):
    """The training row of ``smallthinker-21b-ep4.retrain`` as the chip
    compiles it (the library's splash kernel under the global layer's causal
    mask and the sliding layers' window, the grouped expert kernels; 16384 tokens,
    496.4 M parameters at 16 bytes): the compiler plans its temporaries beside
    7.94 GB of weights, moments and gradient sums, under the 16,909,336,064 B
    the v5e's allocator reports as its limit (PERF.md).  A plan, not a
    reading."""
    import dataclasses
    import json
    from pathlib import Path

    from predictionio_tpu.models.sequence import engine as seq
    from predictionio_tpu.ops import seqmodel
    from predictionio_tpu.utils.params import extract_params

    body = json.loads((Path(__file__).resolve().parents[1] / "benchmark" / "configs"
                       / "smallthinker-21b-ep4.json").read_text())
    algo = seq.SequenceAlgorithm(extract_params(
        seq.SequenceAlgorithmParams, body["engine_json"]["algorithms"][0]["params"]))
    cfg = dataclasses.replace(algo.seq_config(), attn_impl="flash", moe_impl="pallas")
    assert seqmodel.num_params(cfg) == body["share"]["parameters_held"] == 496_376_320
    by_part = body["share"]["parameters_by_part"]
    assert 4 * (by_part["attention_a_layer"] + by_part["router_a_layer"]
                + by_part["experts_a_layer"] + by_part["norms_a_layer"]) + by_part[
        "embedding_and_head"] + by_part["final_norm"] == 496_376_320
    row_len = body["engine_json"]["preparator"]["params"]["rowLen"]
    assert row_len == body["max_position_embeddings"] == 16384
    sds = _spec_on(SingleDeviceSharding(v5e.devices[0]))
    state, acc = jax.eval_shape(lambda: seqmodel.init_state(cfg, 3))
    state, acc = jax.tree.map(lambda a: sds(a.shape, a.dtype), (state, acc))
    assert acc["expert_pairs"].shape == (4, 16)
    accumulate, _ = seqmodel.train_programs(cfg, seqmodel.AdamW())
    compiled = _compile(
        accumulate, state, acc, sds((row_len,), jnp.int32), sds((row_len,), jnp.int32))
    text = compiled.as_text()
    for name in ("moe_gmm_gate_up", "moe_tgmm_down"):
        assert name in text, name
    _assert_splash_alone(text)
    _assert_every_named_operation_is_scoped(text)
    _assert_buffer_work_follows_the_live_tiles(text, 102_400)
    plan = compiled.memory_analysis()
    assert plan.argument_size_in_bytes == pytest.approx(16 * 496_376_320, rel=1e-3)
    assert plan.alias_size_in_bytes >= 0.999 * plan.argument_size_in_bytes  # donated
    assert plan.argument_size_in_bytes + plan.temp_size_in_bytes < 16_909_336_064
    # ABOVE PR 41's plan (4.54 GB of temporaries; this program's 6.68): the
    # kernel that leaves the loops' buffers unwritten takes no operand, so the
    # compiler makes every layer's buffers at the program's start, side by
    # side, in the room the arguments leave; an operand that ties a buffer to
    # what its gathers read holds the plan at 4.56 GB and is copied on the
    # chip (0.18 s a retrain: PERF.md section 6, PR 42 and PR 43)
    assert plan.temp_size_in_bytes < 6.9 * 10**9


def test_ouro_row_program_fits_beside_its_arguments(v5e):
    """The training row of ``ouro-2.6b-d8.retrain`` as the chip compiles it at
    the PUBLISHED widths (8 sandwich layers run 4 times, 16 heads of 128, 5632
    MLP columns, all 49,152 vocabulary rows; 8192 tokens, 612.4 M parameters
    at 16 bytes): 9.80 GB of weights, moments and gradient sums, donated, with
    the looped trunk's 32 kept streams and the four exits' loss beside them.
    The chip's compiler refuses a program it cannot fit; its plan counts
    7.7 GB of temporaries where the chip's allocator then reserved 6.87 GB
    beside 9.88 GB in use, of its 16,909,336,064 B (PERF.md).  A plan, not a
    reading."""
    import dataclasses
    import json
    from pathlib import Path

    from predictionio_tpu.models.sequence import engine as seq
    from predictionio_tpu.ops import seqmodel
    from predictionio_tpu.utils.params import extract_params

    body = json.loads((Path(__file__).resolve().parents[1] / "benchmark" / "configs"
                       / "ouro-2.6b-d8.json").read_text())
    algo = seq.SequenceAlgorithm(extract_params(
        seq.SequenceAlgorithmParams, body["engine_json"]["algorithms"][0]["params"]))
    cfg = dataclasses.replace(algo.seq_config(), attn_impl="flash")
    assert (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.mlp_cols,
            cfg.vocab_rows, cfg.loop_steps, len(cfg.layer_types)) == (
        body["hidden_size"], body["num_attention_heads"], body["num_key_value_heads"],
        body["head_dim"], body["intermediate_size"], body["vocab_size"],
        body["total_ut_steps"], body["num_hidden_layers"]) == (
        2048, 16, 16, 128, 5632, 49152, 4, 8)
    assert seqmodel.num_params(cfg) == body["share"]["parameters_held"] == 612_438_017
    row_len = body["engine_json"]["preparator"]["params"]["rowLen"]
    sds = _spec_on(SingleDeviceSharding(v5e.devices[0]))
    state, acc = jax.eval_shape(lambda: seqmodel.init_state(cfg, 3))
    state, acc = jax.tree.map(lambda a: sds(a.shape, a.dtype), (state, acc))
    assert acc["exit_loss"].shape == (4,)
    accumulate, _ = seqmodel.train_programs(cfg, seqmodel.AdamW())
    compiled = _compile(
        accumulate, state, acc, sds((row_len,), jnp.int32), sds((row_len,), jnp.int32))
    text = compiled.as_text()
    _assert_splash_alone(text)
    _assert_every_named_operation_is_scoped(text)
    for t in range(4):  # every pass's component reached the chip's op names
        assert f"loop.pass{t}/" in text or f"loop.pass{t})" in text, t
    plan = compiled.memory_analysis()
    assert plan.argument_size_in_bytes == pytest.approx(16 * 612_438_017, rel=1e-3)
    assert plan.alias_size_in_bytes >= 0.999 * plan.argument_size_in_bytes  # donated
    assert plan.temp_size_in_bytes < 8 * 10**9


# the Nemotron-H stack at its published widths (one of eight chips' share: 16
# of 128 two-matrix experts of width 1856 = 14.5 lane tiles on a stream of
# 2688, 6 a token, rows of 8192 tokens, tiles of 256 pairs; 8 state-space
# heads of 64 channels = half a lane tile, state 128, one group)
RELU2 = dict(tokens=8192, hidden=2688, width=1856, experts=128, held=16, k=6, tile=256)


@pytest.mark.parametrize("passes", ["forward", "forward_backward"])
def test_relu2_grouped_kernels_compile_at_a_width_of_no_whole_lane_tiles(v5e, passes):
    """The two-matrix experts' grouped products at F = 1856: ``gmm`` takes the
    weight block [2688, 1856] at its full width, ``relu2_ffn``'s own backward
    the transposed products and ``moe_tgmm_up`` over three blocks of 640
    columns, the last one 576 wide (``moe._tgmm_block``)."""
    from predictionio_tpu.ops import moe

    sds = _spec_on(SingleDeviceSharding(v5e.devices[0]))
    N, D, F, E, held, k, tile = (RELU2[n] for n in (
        "tokens", "hidden", "width", "experts", "held", "k", "tile"))
    assert F % 128 == 64 and moe.plan_rows(N, k, held, tile) == 53_248
    assert (moe._tgmm_block(F), moe._tgmm_block(D)) == (640, 896)

    def layer(m, logits, valid, up, down, bias):
        return moe.experts_layer(
            m, logits, valid, None, up, down, k=k, start=0, tile=tile,
            dtype=jnp.bfloat16, impl="pallas", bias=bias, scale=2.5)[0].sum()

    fn = jax.jit(layer if passes == "forward" else jax.grad(
        layer, argnums=(0, 1, 3, 4)))
    text = _compile(
        fn, sds((N, D)), sds((N, E)), sds((N,), jnp.bool_), sds((held, D, F)),
        sds((held, F, D)), sds((E,))).as_text()
    assert "moe_gmm_up" in text and "moe_gmm_down" in text
    assert "moe_gmm_gate_up" not in text
    for name in ("moe_gmm_down_dlhs", "moe_gmm_up_dlhs", "moe_tgmm_down",
                 "moe_tgmm_up"):
        assert (name in text) == (passes == "forward_backward"), name


def test_ssd_chunk_kernels_compile_at_half_a_lane_tile_a_head(v5e):
    """``ssd_chunk_fwd`` / ``ssd_chunk_bwd`` at 8 heads of 64 channels and a
    state of 128 (Falcon-H1's are 128 and 256), four heads a grid step."""
    from predictionio_tpu.ops import ssd

    sds = _spec_on(SingleDeviceSharding(v5e.devices[0]))
    h, g, nc, c, p, n = 8, 1, 64, 128, 64, 128
    fn = jax.jit(jax.grad(
        lambda *a: ssd.chunk_pallas(*a, False).sum(), argnums=tuple(range(4))))
    assert ssd.heads_per_block(h // g) == 4
    text = _compile(
        fn, sds((1, g, nc, c, n)), sds((1, g, nc, c, n)), sds((1, h, nc, c, p)),
        sds((1, h, nc))).as_text()
    assert "ssd_chunk_fwd" in text and "ssd_chunk_bwd" in text


def test_nemotron_row_program_fits_beside_its_arguments(v5e):
    """The training row of ``nemotron3-nano-30b-ep8.retrain`` as the chip
    compiles it at the PUBLISHED widths (nine layers of one sublayer each: the
    SSD kernels at 64 channels a head, splash attention without positions,
    the grouped expert kernels at a width of 1856 beside the shared expert;
    8192 tokens, 760.9 M parameters at 16 bytes): the compiler plans its
    temporaries beside 12.17 GB of weights, moments and gradient sums, under
    the 16,909,336,064 B the v5e's allocator reports as its limit (PERF.md).
    A plan, not a reading."""
    import dataclasses
    import json
    from pathlib import Path

    from predictionio_tpu.models.sequence import engine as seq
    from predictionio_tpu.ops import seqmodel
    from predictionio_tpu.utils.params import extract_params

    body = json.loads((Path(__file__).resolve().parents[1] / "benchmark" / "configs"
                       / "nemotron3-nano-30b-ep8.json").read_text())
    algo = seq.SequenceAlgorithm(extract_params(
        seq.SequenceAlgorithmParams, body["engine_json"]["algorithms"][0]["params"]))
    cfg = dataclasses.replace(
        algo.seq_config(), attn_impl="flash", ssm_impl="pallas", moe_impl="pallas")
    assert (cfg.hidden, cfg.ssm_head_dim, cfg.ssm_state, cfg.head_dim,
            cfg.expert_width, 8 * cfg.shared_cols) == (
        body["hidden_size"], body["mamba_head_dim"], body["ssm_state_size"],
        body["head_dim"], body["moe_intermediate_size"],
        body["moe_shared_expert_intermediate_size"]) == (2688, 64, 128, 128, 1856, 3712)
    assert (cfg.experts, cfg.experts_per_token, cfg.routed_scale) == (128, 6, 2.5)
    kinds = {"M": seqmodel.STATE_SPACE, "*": seqmodel.GROUPED_ATTENTION,
             "E": seqmodel.SHARED_EXPERTS}
    assert cfg.layer_types == tuple(kinds[c] for c in body["hybrid_override_pattern"])
    assert seqmodel.num_params(cfg) == body["share"]["parameters_held"] == 760_856_416
    by_part = body["share"]["parameters_by_part"]
    assert 4 * by_part["state_space_a_layer"] + by_part["attention_a_layer"] + 4 * by_part[
        "experts_a_layer"] + by_part["embedding_and_head"] + by_part[
        "final_norm"] == 760_856_416
    row_len = body["engine_json"]["preparator"]["params"]["rowLen"]
    sds = _spec_on(SingleDeviceSharding(v5e.devices[0]))
    state, acc = jax.eval_shape(lambda: seqmodel.init_state(cfg, 3))
    state, acc = jax.tree.map(lambda a: sds(a.shape, a.dtype), (state, acc))
    assert acc["expert_pairs"].shape == (4, 16)  # the FOUR routed layers of nine
    accumulate, _ = seqmodel.train_programs(cfg, seqmodel.AdamW())
    compiled = _compile(
        accumulate, state, acc, sds((row_len,), jnp.int32), sds((row_len,), jnp.int32))
    text = compiled.as_text()
    for name in ("moe_gmm_up", "moe_tgmm_up", "moe_tgmm_down", "ssd_chunk_fwd",
                 "ssd_chunk_bwd", "moe.shared"):
        assert name in text, name
    _assert_splash_alone(text)
    _assert_every_named_operation_is_scoped(text)
    _assert_buffer_work_follows_the_live_tiles(text, 53_248)
    plan = compiled.memory_analysis()
    assert plan.argument_size_in_bytes == pytest.approx(16 * 760_856_416, rel=1e-3)
    assert plan.alias_size_in_bytes >= 0.999 * plan.argument_size_in_bytes  # donated
    assert plan.argument_size_in_bytes + plan.temp_size_in_bytes < 16_909_336_064
    # the compiler plans into the room the arguments leave (4.11 GB of
    # temporaries in 4.47 GB; PR 41's program planned 3.62): held to that
    # room by 0.8 GiB of ballast beside the arguments, this one plans under it
    squeezed = _compile(
        jax.jit(lambda state, acc, tokens, seg, ballast: (
            seqmodel.accumulate_row(cfg, state, acc, tokens, seg), ballast + 1.0),
            donate_argnums=(0, 1)),
        state, acc, sds((row_len,), jnp.int32), sds((row_len,), jnp.int32),
        sds((int(0.8 * 2**28),), jnp.float32))
    assert squeezed.memory_analysis().temp_size_in_bytes < 3.62 * 10**9
    # the first step's probe of the experts (forward, and their backward on
    # the first row) runs beside the same resident state
    resident = plan.argument_size_in_bytes
    first = cfg.layer_types.index(seqmodel.SHARED_EXPERTS)
    probe = seqmodel.experts_probe.lower(
        cfg, True, state["params"]["embed"],
        seqmodel.layer_params(state["params"], first),
        sds((row_len,), jnp.int32), sds((row_len,), jnp.int32)).compile()
    text = probe.as_text()
    for name in ("moe_gmm_up", "moe_gmm_down", "moe_gmm_down_dlhs", "moe_gmm_up_dlhs",
                 "moe_tgmm_down", "moe_tgmm_up", "moe.shared"):
        assert name in text, name
    _assert_every_named_operation_is_scoped(text)
    plan = probe.memory_analysis()
    assert resident + plan.temp_size_in_bytes + plan.output_size_in_bytes < 16_909_336_064
    print("experts_probe plan:", plan.temp_size_in_bytes, plan.output_size_in_bytes)
