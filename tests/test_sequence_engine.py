"""The sequence engine behind the DASE contract (ISSUE 26): time order from
the store, the Preparator's vocabulary and packing, ``pio train`` -> persisted
model -> ``load_models`` -> ``predict``, and the spans a retrain opens."""

from __future__ import annotations

import dataclasses
import json
import logging
import subprocess
import sys

import numpy as np
import pytest

from predictionio_tpu.core import EngineContext
from predictionio_tpu.core.engine import resolve_engine_factory
from predictionio_tpu.core.persistence import load_models
from predictionio_tpu.core.workflow import run_train
from predictionio_tpu.data.storage.base import EventFrame
from predictionio_tpu.data.storage.config import StorageConfig, StorageRuntime
from predictionio_tpu.models.recommendation.engine import Query
from predictionio_tpu.models.sequence import engine as seq
from predictionio_tpu.tools import commands

N_USERS, N_ITEMS, NNZ = 24, 100, 700

VARIANT = {
    "datasource": {"params": {"appName": "seq"}},
    "preparator": {"params": {
        "rowLen": 64, "maxLen": 64, "rowsPerStep": 2, "vocabSize": 128}},
    "algorithms": [{"name": "gdn", "params": {
        "hiddenSize": 64, "numAttentionHeads": 2, "headDim": 16,
        "linearNumHeads": 2, "linearKeyHeadDim": 8, "linearValueHeadDim": 16,
        "intermediateSize": 16, "vocabSize": 128, "rowsPerStep": 2,
        "stepsPerRetrain": 2}}],
}

#: ISSUE 26, table 7: the spans the engine adds to a retrain's tree
SPANS = ("datasource.sequences", "prepare.vocab", "prepare.pack", "seq.init",
         "seq.device_loop", "seq.fetch")


def _events(rng):
    users = rng.integers(0, N_USERS, NNZ)
    items = rng.integers(0, N_ITEMS, NNZ)
    # distinct instants, and a few shared ones: ties keep the write order
    times = 1_700_000_000_000 + np.sort(rng.integers(0, NNZ // 2, NNZ))
    return users, items, times


@pytest.fixture()
def store(tmp_path):
    """A parquet event store whose events were WRITTEN in shuffled order."""
    home = tmp_path / "pio_home"
    rt = StorageRuntime(StorageConfig.from_env({
        "PIO_HOME": str(home),
        "PIO_STORAGE_SOURCES_PARQUET_TYPE": "parquet",
        "PIO_STORAGE_SOURCES_PARQUET_PATH": str(home / "events_parquet"),
        "PIO_STORAGE_SOURCES_PARQUET_NSHARDS": "4",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "PARQUET",
        # the models on the local filesystem, as the benchmark's are
        "PIO_STORAGE_SOURCES_LOCALFS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_LOCALFS_PATH": str(home / "models"),
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "LOCALFS",
    }))
    app = commands.app_new(rt, "seq").app
    rng = np.random.default_rng(26)
    users, items, times = _events(rng)
    # shuffle whole instants, so that the write order within an instant (the
    # store's tie-break) stays what the test knows
    instants = np.unique(times)
    rank = rng.permutation(len(instants))[np.searchsorted(instants, times)]
    order = np.argsort(rank, kind="stable")

    def const(value: str) -> np.ndarray:
        col = np.empty(NNZ, object)
        col[:] = value
        return col

    rt.p_events().write(
        EventFrame(
            event=const("rate"), entity_type=const("user"),
            entity_id=np.array([f"u{u}" for u in users[order]], object),
            target_entity_type=const("item"),
            target_entity_id=np.array([f"i{i}" for i in items[order]], object),
            event_time_ms=times[order],
            properties=const('{"rating": 4.0}'),
        ),
        app_id=app.id,
    )
    yield rt, (users, items, times)
    rt.close()


def test_datasource_returns_each_entitys_events_in_time_order(store):
    rt, (users, items, times) = store
    td = seq.SequenceDataSource(
        seq.SequenceDataSourceParams(app_name="seq")
    ).read_training(EngineContext(storage=rt))
    assert len(td.items) == NNZ and td.offsets[-1] == NNZ
    assert sorted(td.entities) == sorted({f"u{u}" for u in users})
    for e, name in enumerate(td.entities):
        got = td.items[td.order[td.offsets[e] : td.offsets[e + 1]]].tolist()
        mine = np.flatnonzero(users == int(name[1:]))
        assert got == [f"i{i}" for i in items[mine]], name  # (time, write order)


def test_equal_timestamps_keep_the_stores_order_to_the_element(tmp_path):
    """Every event at ONE instant, written as two frames: the read's order
    is then all the store's tie-break (write, then shard, then row), and
    the DataSource's output is held to it element by element.  This engine
    calls ``find`` with the defaults; the order-free read of ISSUE 27 must
    never reach it."""
    from predictionio_tpu.data.storage.base import frame_shard_of

    home = tmp_path / "pio_home"
    rt = StorageRuntime(StorageConfig.from_env({
        "PIO_HOME": str(home),
        "PIO_STORAGE_SOURCES_PARQUET_TYPE": "parquet",
        "PIO_STORAGE_SOURCES_PARQUET_PATH": str(home / "events_parquet"),
        "PIO_STORAGE_SOURCES_PARQUET_NSHARDS": "4",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "PARQUET",
    }))
    app = commands.app_new(rt, "seq").app
    rng = np.random.default_rng(27)
    n = 240
    users = np.array([f"u{u}" for u in rng.integers(0, 9, n)], object)
    items = np.array([f"i{i}" for i in rng.integers(0, 50, n)], object)

    def const(value: str, rows: int) -> np.ndarray:
        col = np.empty(rows, object)
        col[:] = value
        return col

    for part in (slice(0, 150), slice(150, n)):
        rows = len(users[part])
        rt.p_events().write(
            EventFrame(
                event=const("rate", rows), entity_type=const("user", rows),
                entity_id=users[part], target_entity_type=const("item", rows),
                target_entity_id=items[part],
                event_time_ms=np.full(rows, 1_700_000_000_000, np.int64),
                properties=const("", rows),
            ),
            app_id=app.id,
        )
    shard = frame_shard_of(const("user", n), users, 4)
    written = np.arange(n) >= 150
    # stable by (write, shard): rows keep their place within a shard's write
    read_order = np.lexsort((shard, written))
    try:
        td = seq.SequenceDataSource(
            seq.SequenceDataSourceParams(app_name="seq")
        ).read_training(EngineContext(storage=rt))
    finally:
        rt.close()
    assert td.items.tolist() == items[read_order].tolist()
    read_users = users[read_order]
    assert td.entities.tolist() == list(dict.fromkeys(read_users))
    for e, name in enumerate(td.entities):
        mine = np.flatnonzero(read_users == name)
        assert td.order[td.offsets[e] : td.offsets[e + 1]].tolist() == (
            mine.tolist()), name
    assert td.offsets[-1] == n


def test_preparator_packs_the_most_recent_events(store):
    rt, (users, items, _) = store
    ctx = EngineContext(storage=rt)
    td = seq.SequenceDataSource(
        seq.SequenceDataSourceParams(app_name="seq")).read_training(ctx)
    pd = seq.SequencePreparator(seq.SequencePreparatorParams(
        row_len=32, max_len=16, rows_per_step=4, vocab_size=128, vocab_start=10)
    ).prepare(ctx, td)
    assert pd.tokens.shape == pd.segments.shape and len(pd.tokens) % 4 == 0
    assert pd.tokens.shape[1] == 32
    for e, name in enumerate(td.entities):
        mine = [f"i{i}" for i in items[users == int(name[1:])]][-16:]
        row, col = np.nonzero(pd.segments == e)
        assert len(set(row)) == 1 and (np.diff(col) == 1).all()  # one run
        ids = pd.tokens[row, col] - 10
        assert [pd.item_vocab.inverse(int(j)) for j in ids] == mine
    real = pd.segments != seq.PAD_SEGMENT
    assert real.sum() == sum(
        min(16, int((users == u).sum())) for u in np.unique(users))
    assert (pd.tokens[~real] == 0).all()


def test_more_items_than_vocabulary_rows_is_an_error(store):
    rt, _ = store
    ctx = EngineContext(storage=rt)
    td = seq.SequenceDataSource(
        seq.SequenceDataSourceParams(app_name="seq")).read_training(ctx)
    with pytest.raises(ValueError, match="do not fit the vocabulary of 64"):
        seq.SequencePreparator(seq.SequencePreparatorParams(
            row_len=64, max_len=64, vocab_size=64)).prepare(ctx, td)


@pytest.mark.parametrize("name,value", [
    ("vocab_start", 64), ("vocab_size", 256), ("rows_per_step", 1)])
def test_rows_packed_for_another_share_are_refused(store, name, value):
    """The share and the step size are written twice in engine.json (the
    Preparator's and the algorithm's): rows made for one are not trained by
    an algorithm configured with another (ids from another ``vocabStart``
    would embed to zero without a word)."""
    rt, _ = store
    ctx = EngineContext(storage=rt)
    engine = resolve_engine_factory("sequence")()
    params = engine.params_from_json(VARIANT)
    _, prep, (algo,), _ = engine.instantiate(params)
    td = seq.SequenceDataSource(
        seq.SequenceDataSourceParams(app_name="seq")).read_training(ctx)
    pd = prep.prepare(ctx, td)
    assert (pd.vocab_start, pd.vocab_size, pd.rows_per_step) == (0, 128, 2)
    other = seq.SequenceAlgorithm(dataclasses.replace(algo.params, **{name: value}))
    with pytest.raises(ValueError, match="the Preparator packed rows for"):
        other.train(ctx, pd)


class _Stages(logging.Handler):
    def __init__(self):
        super().__init__(logging.INFO)
        self.stages = None

    def emit(self, record):
        if hasattr(record, "stages"):
            self.stages = record.stages


#: a weight of this many bytes is a part of its own at the tiny widths (the
#: 64 x 64 projections; the norms and the convolutions stay in the manifest)
TINY_PART_THRESHOLD = 4096


@pytest.fixture()
def trained(store, monkeypatch):
    from predictionio_tpu.core import persistence

    monkeypatch.setattr(persistence, "PART_THRESHOLD", TINY_PART_THRESHOLD)
    rt, data = store
    seen = _Stages()
    log = logging.getLogger("predictionio_tpu.workflow")
    log.addHandler(seen)
    level = log.level
    log.setLevel(logging.INFO)
    try:
        engine = resolve_engine_factory("sequence")()
        params = engine.params_from_json(VARIANT)
        instance = run_train(
            engine, params, engine_factory="sequence", storage=rt,
            ctx=EngineContext(storage=rt))
    finally:
        log.removeHandler(seen)
        log.setLevel(level)
    assert instance.status == "COMPLETED"
    return rt, data, engine, params, instance, seen.stages


def _retrain_root(instance):
    from predictionio_tpu.obs.tracing import recent_traces

    return next(
        t for t in recent_traces(5) if t.get("request_id") == instance.id)


def test_train_persist_load_predict_round_trip(trained):
    rt, (users, items, _), engine, params, instance, _ = trained
    (data,) = load_models(rt.models(), instance.id)
    record = data["training_record"]
    assert len(record["loss"]) == 2 and np.isfinite(record["loss"]).all()
    assert record["loss"][0] == pytest.approx(np.log(128), rel=0.02)
    assert set(record["tensor_grad_norm"]) == set(data["params"])
    assert all(v.dtype == np.float32 for v in data["params"].values())
    algo = engine.instantiate(params)[2][0]
    model = algo.load_persistent_model(EngineContext(storage=rt), data)
    seen = {f"i{i}" for i in items}
    answer = algo.predict(model, Query(user=f"u{users[0]}", num=5))
    assert len(answer.item_scores) == 5
    scores = [s.score for s in answer.item_scores]
    assert scores == sorted(scores, reverse=True)
    assert {s.item for s in answer.item_scores} <= seen  # never a padding row
    assert algo.predict(model, Query(user="nobody", num=5)).item_scores == ()
    # the answer is the head over the history's last hidden state
    from predictionio_tpu.ops import seqmodel

    e = model.entity_vocab[f"u{users[0]}"]
    hist = model.history_tokens[model.history_offsets[e] : model.history_offsets[e + 1]]
    tokens = np.zeros((1, 64), np.int32)
    segments = np.full((1, 64), seq.PAD_SEGMENT, np.int32)
    tokens[0, : len(hist)], segments[0, : len(hist)] = hist, 0
    h = seqmodel.hidden_states(
        model.config, data["params"], tokens, segments)[0, len(hist) - 1]
    want = np.asarray(data["params"]["head"] @ h)[: len(model.item_vocab)]
    top = np.argsort(-want, kind="stable")[:5]
    assert [s.item for s in answer.item_scores] == [
        model.item_vocab.inverse(int(j)) for j in top]
    np.testing.assert_allclose(scores, want[top], rtol=2e-2, atol=2e-3)


def test_every_span_of_the_engine_appears_once_in_stages(trained):
    stages = trained[-1]
    for name in SPANS + ("train.algorithm.gdn", "train.persist.save_models",
                         "train.datasource.read", "train.preparator.prepare"):
        assert name in stages and stages[name] >= 0, name
    # nothing ran side by side but the model store's writers
    assert stages["parallel"] == ["persist.fetch", "persist.part"]
    for part, whole in (("datasource.sequences", "train.datasource.read"),
                        ("prepare.pack", "train.preparator.prepare"),
                        ("seq.device_loop", "train.algorithm.gdn")):
        assert stages[part] <= stages[whole] + 1e-3


def test_the_sequence_read_asks_for_time_order(trained):
    """The engine that NEEDS the store's order runs the ordered path: its
    retrain's ``eventstore.scan`` says ``ordered: true``, every column."""
    instance, stages = trained[-2:]
    root = _retrain_root(instance)
    read = next(
        c for c in root["children"] if c["name"] == "train.datasource.read")
    by_name = {c["name"]: c for c in read["children"]}
    assert list(by_name) == ["eventstore.scan", "eventstore.sort",
                             "eventstore.decode", "datasource.sequences"]
    assert by_name["eventstore.scan"]["ordered"] is True
    assert by_name["eventstore.scan"]["columns"] == 12
    assert by_name["eventstore.sort"]["sorted"] is True
    assert by_name["eventstore.sort"]["rows"] == NNZ
    for name in by_name:
        assert stages[name] >= 0, name
    # the store offers its six dictionary columns as codes (ISSUE 37); this
    # engine asks for none, reads object columns, and prepares as ever
    assert by_name["eventstore.decode"]["coded_columns"] == 6
    prepare = next(
        c for c in root["children"] if c["name"] == "train.preparator.prepare")
    vocab = next(c for c in prepare["children"] if c["name"] == "prepare.vocab")
    assert vocab["path"] == "factorize"


def test_template_scaffolds_the_engine(tmp_path):
    from predictionio_tpu.tools.cli import build_parser

    args = build_parser().parse_args(
        ["template", "get", "sequence", str(tmp_path / "engine")])
    assert args.fn(args) == 0
    variant = json.loads((tmp_path / "engine" / "engine.json").read_text())
    assert variant["engineFactory"] == "sequence"
    engine = resolve_engine_factory("sequence")()
    params = engine.params_from_json(variant)
    assert params.algorithms[0][1].linear_key_head_dim == 96  # published widths


def test_importing_the_engines_loads_no_kernel_code():
    """``pio train`` of another engine pays nothing for this one: its Pallas
    and attention code load when it trains."""
    code = (
        "import sys, predictionio_tpu.models\n"
        "bad = [m for m in sys.modules if m.startswith(('jax.experimental.pallas',"
        " 'predictionio_tpu.ops.gdn', 'predictionio_tpu.ops.seqmodel'))]\n"
        "print(bad)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "[]"



def test_the_weights_leave_the_device_inside_the_part_writers(trained):
    """``seq.fetch`` brings the training record alone; every weight of part
    size is fetched by the writer of its part (``persist.fetch`` under
    ``persist.part``), and ``train.persist.save_models`` says how many were
    (ISSUE 41)."""
    from predictionio_tpu.ops import seqmodel

    rt, _, engine, params, instance, stages = trained
    root = _retrain_root(instance)
    algo = next(c for c in root["children"] if c["name"] == "train.algorithm.gdn")
    assert [c["name"] for c in algo["children"]] == [
        "seq.init", "seq.device_loop", "seq.fetch"]
    shapes = seqmodel.param_shapes(engine.instantiate(params)[2][0].seq_config())
    sizes = {k: 4 * int(np.prod(shape)) for k, shape in shapes.items()}
    big = sorted(
        (n for n in sizes.values() if n >= TINY_PART_THRESHOLD), reverse=True)
    assert len(big) > 4 and len(big) < len(sizes)
    # the record's few numbers, not the weights' bytes
    assert 0 < algo["children"][2]["bytes"] < min(big)
    persist = next(
        c for c in root["children"] if c["name"] == "train.persist.save_models")
    assert persist["parts"] == persist["streamed_parts"] == len(big)
    assert persist["fetched_parts"] == len(big)
    assert persist["fetched_bytes"] == sum(big)
    writers = persist["children"]
    assert [w["name"] for w in writers] == ["persist.part"] * len(big)
    for w in writers:
        (fetch,) = w["children"]
        assert (fetch["name"], fetch["part"]) == ("persist.fetch", w["part"])
    assert sorted((w["children"][0]["bytes"] for w in writers), reverse=True) == big
    # the longest writer's seconds in fetches, inside the span that waited
    assert 0 <= stages["persist.fetch"] <= stages["persist.part"]
    assert stages["persist.part"] <= stages["train.persist.save_models"] + 1e-4
    (data,) = load_models(rt.models(), instance.id)
    assert {k: v.nbytes for k, v in data["params"].items()} == sizes
    assert all(type(v) is np.ndarray for v in data["params"].values())


def test_no_weight_outlives_the_retrain(trained):
    """After ``run_train`` has returned nothing holds a weight on the device
    (not the parts' mapping, not a span's tags, not a cached copy): the next
    retrain's ``seq.init`` finds the memory this one trained in."""
    import jax

    assert [
        (a.shape, a.dtype) for a in jax.live_arrays()
        if a.nbytes >= TINY_PART_THRESHOLD
    ] == []
