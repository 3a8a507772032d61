"""The retrain's span tree (ISSUE 24): one tree from the store scan to the
persisted model, the same spans on the profiler's clock, children opened from
other threads, and what reaches whoever reads ``stages`` / ``LAST_PLAN_INFO``.

Everything here is counts, names and structure; seconds are only compared
with each other (children against their parent)."""

from __future__ import annotations

import logging
import subprocess
import sys
import threading

import numpy as np
import pytest

from predictionio_tpu.core import EngineContext, EngineParams
from predictionio_tpu.core.workflow import _stage_breakdown, run_train
from predictionio_tpu.data.storage.base import EventFrame
from predictionio_tpu.data.storage.config import StorageConfig, StorageRuntime
from predictionio_tpu.models.recommendation import (
    ALSAlgorithmParams,
    DataSourceParams,
    recommendation_engine,
)
from predictionio_tpu.obs import tracing
from predictionio_tpu.obs.tracing import Span, recent_traces, trace
from predictionio_tpu.ops import als, als_pallas
from predictionio_tpu.parallel.mesh import MeshConfig
from predictionio_tpu.tools import commands

#: ISSUE 24, table (2): the spans of a retrain, whichever train path ran ...
SPANS = {
    "eventstore.scan", "eventstore.sort", "eventstore.decode",
    "datasource.columns", "prepare.vocab", "prepare.index",
    "als.stage", "als.stage.plan", "als.stage.permute", "als.stage.upload",
    "als.init", "als.device_loop", "als.fetch",
    "train.persist.save_models",
}
#: ... and the one only the Pallas path has (its staging cache's key)
PALLAS_ONLY = {"als.fingerprint"}
#: the root's children: what ``stages`` held before the tree went deeper
DASE = {
    "train.datasource.read", "train.preparator.prepare",
    "train.algorithm.als", "train.persist.save_models",
}
ITERATIONS = 3
N_USERS, N_ITEMS, NNZ = 120, 40, 2000


@pytest.fixture()
def parquet_storage(tmp_path, request):
    """A throwaway parquet event store holding one app's ratings; asked for
    with ``"localfs"`` (indirect), its models go to the local filesystem as
    the benchmark's do."""
    home = tmp_path / "pio_home"
    env = {
        "PIO_HOME": str(home),
        "PIO_STORAGE_SOURCES_PARQUET_TYPE": "parquet",
        "PIO_STORAGE_SOURCES_PARQUET_PATH": str(home / "events_parquet"),
        "PIO_STORAGE_SOURCES_PARQUET_NSHARDS": "4",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "PARQUET",
    }
    if getattr(request, "param", None) == "localfs":
        env.update({
            "PIO_STORAGE_SOURCES_LOCALFS_TYPE": "localfs",
            "PIO_STORAGE_SOURCES_LOCALFS_PATH": str(home / "models"),
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "LOCALFS",
        })
    rt = StorageRuntime(StorageConfig.from_env(env))
    app = commands.app_new(rt, "spans").app
    rng = np.random.default_rng(24)
    users = rng.integers(0, N_USERS, NNZ)
    items = rng.integers(0, N_ITEMS, NNZ)

    def const(value: str) -> np.ndarray:
        col = np.empty(NNZ, object)
        col[:] = value
        return col

    rt.p_events().write(
        EventFrame(
            event=const("rate"),
            entity_type=const("user"),
            entity_id=np.array([f"u{u}" for u in users], object),
            target_entity_type=const("item"),
            target_entity_id=np.array([f"i{i}" for i in items], object),
            event_time_ms=1_700_000_000_000 + np.arange(NNZ, dtype=np.int64),
            properties=np.array(
                [f'{{"rating": {1 + (u + i) % 5}.0}}'
                 for u, i in zip(users, items)],
                object,
            ),
        ),
        app_id=app.id,
    )
    yield rt
    rt.close()


class _Stages(logging.Handler):
    def __init__(self):
        super().__init__(logging.INFO)
        self.stages = None

    def emit(self, record):
        if hasattr(record, "stages"):
            self.stages = record.stages


def _retrain(storage, devices=1):
    """(stages extra of the workflow's log record, the root span's dict) of
    one ``run_train`` over a mesh of ``devices`` of the virtual CPU devices."""
    seen = _Stages()
    log = logging.getLogger("predictionio_tpu.workflow")
    log.addHandler(seen)
    level = log.level
    log.setLevel(logging.INFO)
    try:
        inst = run_train(
            recommendation_engine(),
            EngineParams(
                datasource=("ratings", DataSourceParams(app_name="spans")),
                preparator=("ratings", None),
                algorithms=(
                    ("als", ALSAlgorithmParams(
                        rank=4, num_iterations=ITERATIONS)),
                ),
                serving=("first", None),
            ),
            ctx=EngineContext(
                storage=storage,
                mesh_config=MeshConfig(axes={"data": devices}),
            ),
            storage=storage,
        )
    finally:
        log.removeHandler(seen)
        log.setLevel(level)
    assert inst.status == "COMPLETED"
    root = next(
        t for t in recent_traces(5) if t.get("request_id") == inst.id
    )
    return seen.stages, root


def _walk(node, depth=0):
    yield node, depth
    for child in node.get("children", ()):
        yield from _walk(child, depth + 1)


def _check_tree(stages, root, expected, mode):
    # every span of the table, by its own name, beside the DASE stages
    assert expected | DASE <= set(stages)
    assert stages["total"] > 0 and "jax_compile" in stages
    # the root's children: the same keys with the same meaning as before
    children = {c["name"]: c["duration_s"] for c in root["children"]}
    assert set(children) == DASE
    for name, secs in children.items():
        assert stages[name] == pytest.approx(secs, abs=1e-4)
    # deeper spans accumulate by name: none sits directly under the root
    deeper = {n["name"] for n, depth in _walk(root) if depth >= 2}
    assert expected - DASE <= deeper
    # on one thread children sum to at most their parent, and to all of it
    # (5 % + 5 ms) where the block is nothing but spans
    by_name = {n["name"]: n for n, _ in _walk(root)}
    for parent in ("train.datasource.read", "train.preparator.prepare"):
        node = by_name[parent]
        inner = sum(c["duration_s"] for c in node["children"])
        assert inner <= node["duration_s"]
        assert node["duration_s"] - inner <= 0.05 * node["duration_s"] + 0.005
    # counts at the boundaries
    assert by_name["eventstore.scan"]["rows"] == NNZ
    assert by_name["eventstore.scan"]["shards"] == 4
    assert by_name["eventstore.scan"]["bytes_read"] > 0
    assert by_name["eventstore.decode"]["rows"] == NNZ
    assert by_name["datasource.columns"]["rows_kept"] == NNZ
    # the training read states what it uses (ISSUE 27): `event` and three
    # columns of twelve, in no order
    assert by_name["eventstore.scan"]["columns"] == 4
    assert by_name["eventstore.scan"]["ordered"] is False
    assert by_name["eventstore.sort"]["sorted"] is False
    assert by_name["eventstore.decode"]["columns"] == 4
    # the store's dictionary codes reach the Preparator's index arrays
    # (ISSUE 37): all four columns left the store coded, the DataSource read
    # them so, and the Preparator hashed each id of the dictionary once
    assert by_name["eventstore.decode"]["coded_columns"] == 4
    assert by_name["datasource.columns"]["path"] == "codes"
    vocab = by_name["prepare.vocab"]
    assert vocab["users"] <= N_USERS and vocab["items"] <= N_ITEMS
    assert vocab["path"] == "codes" and vocab["rows"] == NNZ
    assert vocab["user_keys_hashed"] == vocab["users"]
    assert vocab["item_keys_hashed"] == vocab["items"]
    assert by_name["prepare.index"]["rows"] == NNZ
    assert by_name["prepare.index"]["path"] == "codes"
    loop = by_name["als.device_loop"]
    assert loop["iterations"] == ITERATIONS and loop["mode"] == mode
    assert by_name["als.fetch"]["bytes"] > 0
    assert by_name["als.stage"]["upload_bytes"] > 0
    # the counters the benchmark reads beside the spans
    info = als.LAST_PLAN_INFO
    assert info["iterations"] == ITERATIONS
    assert info["loop_s"] == pytest.approx(loop["duration_s"], abs=1e-3)
    assert info["upload_bytes"] == by_name["als.stage"]["upload_bytes"]
    assert info["stage_s"] == round(by_name["als.stage"]["duration_s"], 2)


@pytest.mark.parametrize("devices", [1, 8])
def test_scatter_retrain_holds_the_span_tree(parquet_storage, devices):
    stages, root = _retrain(parquet_storage, devices)
    _check_tree(stages, root, SPANS, "scatter")
    # one thread staged: nothing ran side by side
    assert "parallel" not in stages
    stage = next(n for n, _ in _walk(root) if n["name"] == "als.stage")
    assert sum(c["duration_s"] for c in stage["children"]) <= stage["duration_s"]


def test_pallas_retrain_holds_the_span_tree(parquet_storage, pallas_on_cpu):
    stages, root = _retrain(parquet_storage)
    _check_tree(stages, root, SPANS | PALLAS_ONLY, "fused")
    # the two sides staged on two threads: each name's value is the longer
    # side, the breakdown says which names those are, and no side outlasts
    # the parent
    staged = ["als.stage.permute", "als.stage.plan", "als.stage.upload"]
    assert stages["parallel"] == staged
    stage = next(n for n, _ in _walk(root) if n["name"] == "als.stage")
    sides = {}
    for child in stage["children"]:
        sides.setdefault(child["name"], {})[child["side"]] = child["duration_s"]
    for name in staged:
        assert set(sides[name]) == {"user", "item"}
        assert stages[name] == pytest.approx(
            max(sides[name].values()), abs=1e-4)
        assert stages[name] <= stage["duration_s"] + 1e-4
    assert stage["upload_bytes"] == sum(
        c["upload_bytes"] for c in stage["children"]
        if c["name"] == "als.stage.upload"
    )


# -- the model store's write: parts in flight (ISSUE 31) --------------------


@pytest.mark.parametrize("parquet_storage", ["localfs"], indirect=True)
def test_persist_span_says_what_the_store_wrote(parquet_storage, monkeypatch):
    """``train.persist.save_models`` keeps its name and its place (the
    benchmark's ``persist_s`` reads it, in all four cells, by the prefix
    ``train.persist``, which no other stage may share), is tagged with what
    the local store wrote, and holds a ``persist.part`` child a part, opened
    from the writers' threads."""
    from predictionio_tpu.core import persistence
    from predictionio_tpu.data.storage import localfs_models

    # the factor tables of this tiny fit are under the part threshold
    monkeypatch.setattr(persistence, "PART_THRESHOLD", 256)
    stages, root = _retrain(parquet_storage)
    assert [n for n in stages if n.startswith("train.persist")] == [
        "train.persist.save_models"]
    span = next(
        c for c in root["children"] if c["name"] == "train.persist.save_models")
    files = {
        p.name: p.stat().st_size
        for p in parquet_storage.models().root.iterdir() if ":part:" in p.name
    }
    assert len(files) >= 2
    assert span["parts"] == span["streamed_parts"] == len(files)
    assert span["bytes"] == sum(files.values())
    assert span["writers"] == min(localfs_models.PART_WRITERS, len(files))
    children = span["children"]
    assert {c["name"] for c in children} == {"persist.part"}
    assert {
        f"pio_model_{root['request_id']}:part:{c['part']}.bin": c["bytes"]
        for c in children
    } == files
    # the parts' seconds reach ``stages`` under their own name (the longest
    # thread's, where several wrote): never more than the span that waited
    assert stages["persist.part"] <= stages["train.persist.save_models"] + 1e-4


# -- the training read: three columns and no order (ISSUE 27) ---------------


def test_read_spans_keep_their_names_and_say_what_the_read_did(
    parquet_storage, caplog
):
    """The four spans the benchmark's ``scan_s`` / ``sort_s`` / ``decode_s``
    / ``columns_s`` read are all in ``stages`` on the order-free path, the
    tags say the mechanism engaged, and the DataSource's log says what it
    asked for."""
    with caplog.at_level(logging.INFO, "predictionio_tpu"):
        stages, root = _retrain(parquet_storage)
    for name in ("eventstore.scan", "eventstore.sort", "eventstore.decode",
                 "datasource.columns"):
        assert stages[name] >= 0, name
    read = next(
        c for c in root["children"] if c["name"] == "train.datasource.read")
    assert [c["name"] for c in read["children"]] == [
        "eventstore.scan", "eventstore.sort", "eventstore.decode",
        "datasource.columns"]
    scan, sort, decode, columns = read["children"]
    assert (scan["ordered"], scan["columns"]) == (False, 4)
    assert (sort["sorted"], sort["rows"]) == (False, NNZ)
    assert (decode["columns"], decode["coded_columns"]) == (4, 4)
    assert columns["path"] == "codes"
    (store,) = [r.bulk_read for r in caplog.records
                if hasattr(r, "bulk_read")]
    assert (store["ordered"], store["columns"], store["rows"]) == (
        False, 4, NNZ)
    (record,) = [r for r in caplog.records if hasattr(r, "read")]
    assert record.read == {
        "columns": ("entity_id", "target_entity_id", "properties"),
        "ordered": False, "rows_in": NNZ, "rows_kept": NNZ, "path": "codes",
    }


def _old_read(monkeypatch):
    """The DataSource's read as it was: the full frame in time order."""
    from predictionio_tpu.data.store import PEventStore

    find = PEventStore.find
    monkeypatch.setattr(
        PEventStore, "find",
        lambda self, *a, columns=None, ordered=True, **kw: find(
            self, *a, **kw),
    )


def test_new_read_hands_the_preparator_the_same_ratings(
    parquet_storage, monkeypatch
):
    """Same events in, the same ratings per (user, item) out, through a
    compacted segment and a hot head with ``buy`` events and events that
    are not ratings; only the order rows are first seen in differs, and
    that order is the store's (shard, then row), read after read."""
    from predictionio_tpu.data.storage.base import frame_shard_of
    from predictionio_tpu.models.recommendation.engine import (
        RatingsDataSource,
        RatingsPreparator,
    )

    rt = parquet_storage
    app_id = rt.apps().get_by_name("spans").id
    pe = rt.p_events()
    pe.compact(app_id)
    n = 300

    def const(value):
        col = np.empty(n, object)
        col[:] = value
        return col

    rng = np.random.default_rng(27)
    event = const("buy")
    event[::3] = "view"  # not a rating: the filter leaves it out
    props = const("")
    props[1::3] = '{"rating": "n/a"}'  # a buy's rating is the fixed one
    pe.write(
        EventFrame(
            event=event, entity_type=const("user"),
            entity_id=np.array(
                [f"u{u}" for u in rng.integers(0, N_USERS + 9, n)], object),
            target_entity_type=const("item"),
            target_entity_id=np.array(
                [f"i{i}" for i in rng.integers(0, N_ITEMS + 3, n)], object),
            event_time_ms=1_600_000_000_000 + np.arange(n, dtype=np.int64),
            properties=props,
        ),
        app_id=app_id,
    )
    ctx = EngineContext(storage=rt)
    ds = RatingsDataSource(DataSourceParams(app_name="spans", buy_rating=3.5))
    new = ds.read_training(ctx)
    again = ds.read_training(ctx)
    with monkeypatch.context() as m:
        _old_read(m)
        old = ds.read_training(ctx)

    def triples(td):
        return sorted(zip(td.users, td.items, td.ratings.tolist()))

    assert len(new.ratings) == NNZ + 200 and 3.5 in new.ratings
    assert triples(new) == triples(old)
    assert not (new.users == old.users).all()  # another order ...
    for a, b in ((new.users, again.users), (new.items, again.items),
                 (new.ratings, again.ratings)):
        assert (a == b).all()  # ... and the same one on every read
    shard = frame_shard_of(
        np.full(len(new.users), "user", object), np.asarray(new.users), 4)
    assert (np.diff(shard) >= 0).all()

    prep = RatingsPreparator()
    pd_new, pd_old = prep.prepare(ctx, new), prep.prepare(ctx, old)
    assert sorted(pd_new.user_vocab) == sorted(pd_old.user_vocab)
    assert sorted(pd_new.item_vocab) == sorted(pd_old.item_vocab)
    # ids are handed out as first seen in the read's own order
    first_seen = list(dict.fromkeys(new.users))
    assert list(pd_new.user_vocab) == first_seen
    assert list(pd_new.user_vocab) != list(pd_old.user_vocab)

    def decoded(pd):
        return sorted(zip(
            (pd.user_vocab.inverse(int(u)) for u in pd.user_idx),
            (pd.item_vocab.inverse(int(i)) for i in pd.item_idx),
            pd.ratings.tolist(),
        ))

    assert decoded(pd_new) == decoded(pd_old) == triples(old)


# -- the Preparator: one factorize pass a column, or the loop a row ----------

PREPARE_ROWS, PREPARE_USERS, PREPARE_ITEMS = 200_000, 5_000, 800


def _ids(prefix, k, seed):
    """PREPARE_ROWS ids over ``k`` distinct ones, one str object each: what
    the event store's dictionary decode hands the Preparator."""
    vocab = np.empty(k, object)
    vocab[:] = [f"{prefix}{j}" for j in range(k)]
    return vocab[np.random.default_rng(seed).integers(0, k, PREPARE_ROWS)]


def _with_none(col):
    col = col.copy()
    col[[7, 70_000, 199_999]] = None
    return col


def _boxed(col):
    # a row-by-row decoder: every row a str object of its own
    out = np.empty(len(col), object)
    out[:] = [str(k) + "" for k in col.astype("U")]
    return out


def _coded(col):
    # what the parquet scan hands over: the store's dictionary (with entries
    # no row uses, in an order nothing like first-seen) and an int32 code a
    # row; a null id is one more entry
    from test_bimap import _coded as coded_column

    return coded_column(col)


#: users column -> the way the Preparator must say it went (the items
#: column comes interned, or coded where the case's name says both)
PREPARE_CASES = {
    "interned": (lambda u: u, "factorize", PREPARE_USERS),
    # a pointer is a pointer: None is one more distinct object
    "none-user-id": (_with_none, "factorize", PREPARE_USERS + 1),
    "U-dtype": (lambda u: u.astype("U"), "loop", PREPARE_ROWS),
    "object-a-row": (_boxed, "loop", PREPARE_ROWS),
    # the store's codes (ISSUE 37): Python hashes the dictionary's entries
    # that have a row, and no pointer a row is ever made
    "both-coded": (_coded, "codes", PREPARE_USERS),
    "both-coded-none-user-id": (
        lambda u: _coded(_with_none(u)), "codes", PREPARE_USERS + 1),
    # one column of each kind: each goes its own way, the tag names the
    # slower of the two
    "coded-users-interned-items": (_coded, "factorize", PREPARE_USERS),
}


@pytest.mark.parametrize("case", PREPARE_CASES)
def test_preparator_equals_the_loop_and_says_which_way_it_went(case, caplog):
    from predictionio_tpu.models.recommendation.engine import (
        RatingsPreparator,
        TrainingData,
    )

    make, path, hashed = PREPARE_CASES[case]
    items = _ids("i", PREPARE_ITEMS, 26)
    td = TrainingData(
        users=make(_ids("u", PREPARE_USERS, 25)),
        items=_coded(items) if case.startswith("both-coded") else items,
        ratings=np.ones(PREPARE_ROWS, np.float32),
    )
    with caplog.at_level(logging.INFO, "predictionio_tpu"):
        with trace("root", ring=False) as root:
            pd = RatingsPreparator().prepare(EngineContext(), td)

    # the loop a row, written out, is the oracle
    for col, vocab, idx in (
        (td.users, pd.user_vocab, pd.user_idx),
        (td.items, pd.item_vocab, pd.item_idx),
    ):
        forward = {}
        for k in col:
            if k not in forward:
                forward[k] = len(forward)
        assert list(vocab.items()) == list(forward.items())
        assert idx.dtype == np.int32
        np.testing.assert_array_equal(
            idx, np.fromiter((forward[k] for k in col), np.int32, len(col))
        )
    assert pd.ratings is td.ratings

    vocab, index = root.children
    assert (vocab.name, index.name) == ("prepare.vocab", "prepare.index")
    assert vocab.tags == {
        "path": path,
        "rows": PREPARE_ROWS,
        "users": len(pd.user_vocab),
        "items": PREPARE_ITEMS,
        "user_keys_hashed": hashed,
        "item_keys_hashed": PREPARE_ITEMS,
    }
    assert index.tags == {"rows": PREPARE_ROWS, "path": path}
    # ... and in the retrain's log, for whoever has no trace
    (record,) = [r for r in caplog.records if hasattr(r, "prepare")]
    assert record.prepare == vocab.tags


def test_staged_streams_reused_upload_nothing(pallas_on_cpu):
    rng = np.random.default_rng(3)
    u = rng.integers(0, 50, 600)
    i = rng.integers(0, 20, 600)
    r = rng.random(600).astype(np.float32)
    p = als.ALSParams(rank=4, num_iterations=2)
    als.train_als(u, i, r, 50, 20, p)
    assert als.LAST_PLAN_INFO["upload_bytes"] > 0
    with trace("again") as root:
        als.train_als(u, i, r, 50, 20, p)
    assert als.LAST_PLAN_INFO["upload_bytes"] == 0
    assert als.LAST_PLAN_INFO["iterations"] == 2
    names = {c.name for c in root.children}
    assert "als.fingerprint" in names and "als.stage" not in names


def test_pallas_train_puts_no_host_clock_on_the_roofline(
    parquet_storage, pallas_on_cpu, caplog
):
    """The Pallas kernel's share of the roofline is the benchmark's, read
    off the device trace: the running process publishes none from the host
    clock of ``als.device_loop``.  What names the path and what the
    benchmark's plan reader takes stay."""
    from predictionio_tpu.obs.device import default_efficiency

    with caplog.at_level(logging.INFO, "predictionio_tpu.ops.als"):
        _retrain(parquet_storage)
    assert "als.pallas_step" not in default_efficiency().snapshot()["functions"]
    (record,) = [r for r in caplog.records if hasattr(r, "als_path")]
    assert record.als_path == "als.pallas_step"
    assert {"loop_s", "stage_s", "upload_bytes", "mode"} <= set(
        als.LAST_PLAN_INFO
    )


def test_breakdown_keeps_stage_values_and_takes_the_longest_thread():
    root = Span("workflow.run_train")
    root.duration_s = 10.0
    stage = Span("train.algorithm.als")
    stage.duration_s = 6.0
    root.children.append(stage)
    for name, thread, secs in [
        ("als.stage.plan", 1, 2.0), ("als.stage.plan", 2, 3.0),
        ("als.tick", 1, 0.5), ("als.tick", 1, 0.25),
        # a deeper span under a stage's own name never replaces the stage
        ("train.algorithm.als", 1, 1.0),
    ]:
        s = Span(name)
        s.duration_s, s.thread_id = secs, thread
        stage.children.append(s)
    out = _stage_breakdown(root, 0.0)
    assert out["train.algorithm.als"] == 6.0
    assert out["als.stage.plan"] == 3.0  # the longer side, not the sum
    assert out["als.tick"] == 0.75  # same thread: accumulated
    assert out["parallel"] == ["als.stage.plan"]
    assert out["total"] == 10.0


# -- (b) children from other threads ----------------------------------------


def test_spans_from_worker_threads_attach_to_the_given_parent():
    from predictionio_tpu.obs.logging import (
        reset_request_context,
        set_request_context,
    )

    tokens = set_request_context("req-24", "trace-24")
    started = threading.Barrier(2, timeout=10)
    seen = {}

    def work(side):
        # a pool worker: no span stack, no request context of its own
        assert tracing.current_span() is None
        with trace("side", parent=parent) as span:
            span.tags = {"side": side}
            started.wait()  # both sides are open at the same time
            with trace("inner"):
                seen[side] = tracing.current_span().name
        assert tracing.current_span() is None

    try:
        with trace("outer") as outer:
            with trace("parent") as parent:
                threads = [
                    threading.Thread(target=work, args=(s,)) for s in "ab"
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=10)
                assert not any(t.is_alive() for t in threads)
                # the parent's own stack is untouched by the workers
                assert tracing.current_span() is parent
            assert tracing.current_span() is outer
    finally:
        reset_request_context(tokens)
    assert seen == {"a": "inner", "b": "inner"}
    assert sorted(c.tags["side"] for c in parent.children) == ["a", "b"]
    assert outer.children == [parent]
    for child in parent.children:
        assert child.parent_id == parent.span_id
        assert child.trace_id == "trace-24" and child.request_id == "req-24"
        assert child.span_id and child.span_id != parent.span_id
        assert child.thread_id != parent.thread_id
        assert [g.name for g in child.children] == ["inner"]
    assert len({c.span_id for c in parent.children}) == 2


def test_a_span_with_a_given_parent_is_not_a_root():
    before = len(recent_traces(256))
    root = Span("finished-elsewhere")
    done = []

    def work():
        with trace("child", parent=root):
            pass
        done.append(tracing.current_span())

    t = threading.Thread(target=work)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive() and done == [None]
    # it attaches to the parent it was given and never lands in the ring of
    # root spans, though the thread's own stack held nothing above it
    assert [c.name for c in root.children] == ["child"]
    assert len(recent_traces(256)) == before


# -- (c) the profiler's clock ------------------------------------------------


def test_a_span_imports_nothing_where_jax_is_not_loaded():
    code = (
        "import sys\n"
        "from predictionio_tpu.obs import tracing\n"
        # the package's own imports may have loaded jax: forget it, as in a
        # process that never imported it
        "for m in [m for m in sys.modules\n"
        "          if m.split('.')[0] in ('jax', 'jaxlib')]:\n"
        "    del sys.modules[m]\n"
        "before = set(sys.modules)\n"
        "with tracing.trace('outer'):\n"
        "    with tracing.trace('inner', record=False):\n"
        "        pass\n"
        "new = set(sys.modules) - before\n"
        "assert not new, sorted(new)\n"
        "assert tracing._trace_annotation is None\n"
        "print('ok')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "ok"


def test_untraced_spans_open_no_annotation():
    import jax  # noqa: F401  (loaded, but no profiler session is open)

    with trace("quiet") as span:
        assert tracing._open_annotation("probe") is None
    assert span.duration_s >= 0


def test_spans_land_on_the_profilers_host_plane(parquet_storage, tmp_path):
    import jax

    from benchmark import trace_reduce

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        _retrain(parquet_storage)
    finally:
        jax.profiler.stop_trace()
    data = jax.profiler.ProfileData.from_file(
        trace_reduce.find_xplane(str(tmp_path / "trace"))
    )
    # the reducer's own host-plane loop: every event with a duration
    host = {}
    for plane in data.planes:
        if plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host[ev.name] = host.get(ev.name, 0) + ev.duration_ns
    assert {"train.datasource.read", "eventstore.decode"} <= set(host)
    assert SPANS | DASE | {"workflow.run_train"} <= set(host)
    # one clock: a child's annotation lies inside its parent's
    assert host["eventstore.decode"] <= host["train.datasource.read"]
    assert host["train.datasource.read"] <= host["workflow.run_train"]


# -- /debug/profile ----------------------------------------------------------


@pytest.mark.parametrize(
    "query,python_level", [({}, 0), ({"python": "0"}, 0), ({"python": "1"}, 1)]
)
def test_debug_profile_leaves_the_python_tracer_to_the_operator(
    monkeypatch, query, python_level
):
    import jax

    from predictionio_tpu.obs import profiler as profiler_mod
    from predictionio_tpu.obs.http import add_observability_routes
    from predictionio_tpu.obs.metrics import MetricsRegistry
    from predictionio_tpu.server.httpd import HTTPApp, Request

    started = []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda out_dir, profiler_options=None: started.append(
            (out_dir, profiler_options)),
    )
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    controller = profiler_mod.ProfilerController()
    monkeypatch.setattr(profiler_mod, "PROFILER", controller)
    monkeypatch.setattr("predictionio_tpu.obs.http.PROFILER", controller)
    app = HTTPApp("profile-options")
    add_observability_routes(app, MetricsRegistry(), access_key="pk")
    r = app.handle(Request(
        "POST", "/debug/profile",
        {"seconds": "0.05", "accessKey": "pk", **query}, {},
    ))
    assert r.status == 202 and r.body["python_tracer"] is bool(python_level)
    (_, opts), = started
    # the program's annotations are in the capture; Python frames only when
    # the operator asked for them
    assert opts.host_tracer_level == 1
    assert opts.python_tracer_level == python_level
    controller._wakeup.set()
    for _ in range(200):
        if not controller.status()["running"]:
            break
        threading.Event().wait(0.01)
    assert controller.status()["last"]["error"] is None


# -- (d) stable names on the device ------------------------------------------


def _scopes(jaxpr, found):
    for eqn in jaxpr.eqns:
        found.add(str(eqn.source_info.name_stack))
        for sub in _subjaxprs(eqn):
            _scopes(sub, found)


def _subjaxprs(eqn):
    for value in eqn.params.values():
        values = value if isinstance(value, (list, tuple)) else [value]
        for v in values:
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield inner


def test_scatter_step_carries_the_scope_names():
    import jax
    import jax.numpy as jnp

    p = als.ALSParams(rank=4, chunk_size=256)
    step = als._make_train_step(None, 64, 32, p)
    n = 512
    jaxpr = jax.make_jaxpr(step)(
        jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.int32),
        jnp.zeros(n, jnp.float32), jnp.ones(n, jnp.float32),
        jnp.ones((64, 4), jnp.float32), jnp.ones((32, 4), jnp.float32),
    )
    found = set()
    _scopes(jaxpr.jaxpr, found)
    for side in ("als.user_half", "als.item_half"):
        for part in ("als.weights", "als.accumulate", "als.solve"):
            assert any(f"{side}/{part}" in s for s in found), (side, part)


def test_fused_step_carries_the_scope_names_and_the_kernels_name():
    import jax
    import jax.numpy as jnp

    T = als_pallas.T
    nt, nb, users_pad, items_pad, k = 4, 1, 128, 128, 4
    als._STEP_CACHE.clear()
    steps = als._make_pallas_step(
        (nt, nb, nt, nb), als.ALSParams(rank=k), users_pad, items_pad,
        fused=True,
    )
    als._STEP_CACHE.clear()
    i32, f32 = jnp.int32, jnp.float32

    def side():
        return (
            (jnp.zeros(nt, i32), jnp.ones(nt, i32),
             jnp.zeros((nt, T // 128, 128), i32)),
            jnp.zeros((nt, T), i32), jnp.ones((nt, T), f32),
            jnp.ones((nt, T), f32),
        )

    jaxpr = jax.make_jaxpr(steps)(
        *side(), *side(), jnp.ones((users_pad, k), f32),
        jnp.ones((items_pad, k), f32), jnp.int32(2),
    )
    found = set()
    _scopes(jaxpr.jaxpr, found)
    assert any("als.weights" in s for s in found)
    for side_name in ("als.user_half", "als.item_half"):
        for part in ("als.accumulate", "als.solve"):
            assert any(f"{side_name}/{part}" in s for s in found), (
                side_name, part)
    text = str(jaxpr)
    assert "als_fused_accum" in text
