"""Data plane at scale: parallel sharded writes, watermarked background
compaction, predicate/column pushdown, per-entity point reads, ingest
backpressure, multi-daemon fan-out, and the SIGKILL-mid-compaction chaos
acceptance (docs/data_plane.md)."""

import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from datetime import datetime, timezone

import numpy as np
import pytest

from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.data.storage.base import EventFilter, EventFrame
from predictionio_tpu.data.storage.compactor import CompactionPolicy, Compactor
from predictionio_tpu.data.storage.parquet_backend import (
    ParquetClient,
    ParquetEventStore,
    ParquetLEvents,
    ParquetPEvents,
    _active_segments,
    _list_segments,
)


def t(i: int) -> datetime:
    return datetime.fromtimestamp(1_700_000_000 + i * 60, tz=timezone.utc)


def mk(event, entity, i, target=None, props=None, eid=None) -> Event:
    return Event(
        event=event,
        entity_type="user",
        entity_id=str(entity),
        target_entity_type="item" if target else None,
        target_entity_id=str(target) if target else None,
        properties=DataMap(props or {}),
        event_time=t(i),
        event_id=eid,
    )


def bulk_frame(n, n_users=50, n_items=20, t0=0, seed=0) -> EventFrame:
    rng = np.random.default_rng(seed)
    users = np.array([f"u{x}" for x in range(n_users)], object)
    items = np.array([f"i{x}" for x in range(n_items)], object)
    docs = np.array(
        [json.dumps({"rating": float(v) / 2}) for v in range(1, 11)], object
    )
    const = lambda v: _const(n, v)  # noqa: E731
    return EventFrame(
        event=const("rate"),
        entity_type=const("user"),
        entity_id=users[rng.integers(0, n_users, n)],
        target_entity_type=const("item"),
        target_entity_id=items[rng.integers(0, n_items, n)],
        event_time_ms=np.int64(1_700_000_000_000)
        + np.arange(t0, t0 + n, dtype=np.int64) * 1000,
        properties=docs[rng.integers(0, 10, n)],
    )


def _const(n, v):
    a = np.empty(n, object)
    a[:] = v
    return a


def store_at(path, n_shards=4):
    client = ParquetClient(path, n_shards=n_shards)
    return client, ParquetLEvents(client), ParquetPEvents(client)


def total_hot(client, app_id=1) -> int:
    pe = ParquetPEvents(client)
    return pe.status(app_id)["segments_hot"]


def scan_rows(pe, app_id=1):
    out = []
    for _, f in pe.iter_shards(app_id):
        for i in range(len(f)):
            out.append(
                (
                    f.entity_id[i],
                    f.target_entity_id[i],
                    f.event[i],
                    int(f.event_time_ms[i]),
                    f.event_id[i] if f.event_id is not None else None,
                )
            )
    return sorted(out, key=lambda r: (r[0], r[1] or "", r[3], r[4] or ""))


class TestCompaction:
    def test_fold_preserves_content_and_ids(self, tmp_path):
        client, le, pe = store_at(tmp_path / "pq")
        le.init(1)
        for batch in range(5):
            le.insert_batch(
                [mk("view", f"u{j}", batch * 10 + j, target=f"i{j}")
                 for j in range(8)],
                1,
            )
        before = scan_rows(pe)
        assert total_hot(client) > 0
        live = pe.compact(1)
        assert live == 40
        st = pe.status(1)
        assert st["segments_hot"] == 0
        assert st["segments_compacted"] >= 1
        assert scan_rows(pe) == before  # bit-identical incl. event ids

    def test_upsert_and_tombstone_across_watermark(self, tmp_path):
        client, le, pe = store_at(tmp_path / "pq")
        le.init(1)
        eid = le.insert(mk("view", "u1", 1), 1)
        dead = le.insert(mk("view", "u2", 2), 1)
        pe.compact(1)
        # upsert a compacted row from the new write-hot head
        le.insert(mk("buy", "u1", 3, eid=eid), 1)
        assert le.delete(dead, 1)
        got = {e.event_id: e.event for e in le.find(1)}
        assert got == {eid: "buy"}
        # fold again: same answer, tombstones applied durably
        pe.compact(1)
        got = {e.event_id: e.event for e in le.find(1)}
        assert got == {eid: "buy"}
        # every shard folded past the tombstone: the del files are pruned
        assert not (tmp_path / "pq" / "app_1" / "_tombstones").exists()

    def test_crash_window_reads_exactly_once(self, tmp_path):
        """A SIGKILL between the cseg publish and the source unlink leaves
        both the compacted segment AND its folded sources on disk — every
        row must still read exactly once, and the next compaction sweeps
        the superseded files."""
        client, le, pe = store_at(tmp_path / "pq", n_shards=1)
        le.init(1)
        ids = le.insert_batch([mk("view", f"u{j}", j) for j in range(10)], 1)
        shard_dir = tmp_path / "pq" / "app_1" / "shard=0"
        # preserve the pre-compaction hot segments, then "un-delete" them
        saved = {
            p.name: p.read_bytes() for p in shard_dir.glob("seg-*.parquet")
        }
        pe.compact(1)
        for name, blob in saved.items():  # simulate the crash window
            (shard_dir / name).write_bytes(blob)
        csegs, hots = _list_segments(shard_dir)
        assert csegs and hots  # both generations present
        got = sorted(e.event_id for e in le.find(1))
        assert got == sorted(ids)  # exactly once, no duplicates
        pe.compact(1)  # resumes: superseded files swept
        _, hots = _list_segments(shard_dir)
        assert hots == []
        assert sorted(e.event_id for e in le.find(1)) == sorted(ids)

    def test_concurrent_append_stays_above_watermark(self, tmp_path):
        client, le, pe = store_at(tmp_path / "pq", n_shards=1)
        le.init(1)
        le.insert_batch([mk("view", f"u{j}", j) for j in range(4)], 1)
        pe.compact(1)
        le.insert(mk("view", "u99", 99), 1)  # post-watermark append
        shard_dir = tmp_path / "pq" / "app_1" / "shard=0"
        cseg, hots, superseded, w = _active_segments(shard_dir)
        assert cseg is not None and len(hots) == 1
        assert hots[0].seq > w and superseded == []
        assert len(list(le.find(1))) == 5

    def test_fold_never_swallows_inflight_write(self, tmp_path):
        """A writer that reserved its seq BEFORE a fold started may
        publish its segment after the new cseg lands; the fold must stop
        at the in-flight barrier so that segment stays above the
        watermark (a watermark at or past it would read the acked rows
        as superseded — silent loss)."""
        client, le, pe = store_at(tmp_path / "pq", n_shards=1)
        le.init(1)
        # writer A reserves a seq, then stalls mid-conversion
        seq_a = client.seq.reserve()
        try:
            # writer B lands a later batch while A is still in flight
            ids_b = le.insert_batch(
                [mk("view", f"u{j}", j) for j in range(5)], 1
            )
            live = pe.compact(1)  # must NOT fold past A's reserved seq
            assert live == 0  # B's segment sits above the barrier: unfolded
            shard_dir = tmp_path / "pq" / "app_1" / "shard=0"
            _, hots = _list_segments(shard_dir)
            assert len(hots) == 1  # B's segment survived the fold
            # A finally publishes with its OLD seq
            from predictionio_tpu.data.storage.parquet_backend import (
                _event_row,
                _write_segment,
            )

            rows = [_event_row(mk("buy", "uA", 99), seq_a, "idA")]
            _write_segment(shard_dir, rows, seq_a)
        finally:
            client.seq.release(seq_a)
        got = sorted(e.event_id for e in le.find(1))
        assert got == sorted(ids_b + ["idA"])  # nothing swallowed
        pe.compact(1)  # barrier lifted: everything folds
        got = sorted(e.event_id for e in le.find(1))
        assert got == sorted(ids_b + ["idA"])

    def test_compactor_tick_policy_and_status(self, tmp_path):
        client, le, pe = store_at(tmp_path / "pq")
        le.init(1)
        comp = Compactor(
            client,
            CompactionPolicy(min_hot_segments=4, backlog_budget_segments=8),
        )
        le.insert_batch([mk("view", f"u{j}", j) for j in range(12)], 1)
        below = comp.tick()
        # one batch adds at most ONE segment per shard: per-shard depth 1
        # is under the threshold no matter how many shards it touched
        assert below["apps_compacted"] == 0
        for batch in range(4):
            le.insert_batch(
                [mk("view", f"u{j}", 100 + batch * 12 + j) for j in range(12)],
                1,
            )
        over = comp.tick()
        assert over["apps_compacted"] == 1
        st = comp.status()
        assert st["backlog_segments"] == 0 and not st["over_budget"]
        assert st["apps"][0]["segments_compacted"] >= 1
        assert len(list(le.find(1))) == 60

    def test_bulk_write_fans_out_and_round_trips(self, tmp_path):
        client, le, pe = store_at(tmp_path / "pq", n_shards=4)
        pe.write(bulk_frame(5000), 1)
        st = pe.status(1)
        assert st["n_shards"] == 4
        assert sum(1 for s in st["shards"] if s["bytes"]) == 4
        rows = sum(len(f) for _, f in pe.iter_shards(1))
        assert rows == 5000
        pe.compact(1)
        assert sum(len(f) for _, f in pe.iter_shards(1)) == 5000


class TestColumnEncoding:
    def test_value_factorize_none_rows_round_trip(self, tmp_path):
        """A column of pointer-DISTINCT but value-repetitive strings with
        None rows exercises the value-level factorize fallback, whose -1
        NA sentinel must become a masked dictionary slot (raw -1 codes
        crash DictionaryArray.from_arrays)."""
        n = 9000
        col = np.array(
            [("v" + str(i % 3)) if i % 5 else None for i in range(n)],
            object,
        )
        from predictionio_tpu.data.storage.parquet_backend import (
            _string_array,
        )

        arr = _string_array(col)
        assert arr.to_pylist() == list(col)
        # and end to end through a bulk write
        client, le, pe = store_at(tmp_path / "pq", n_shards=2)
        frame = bulk_frame(n)
        frame.target_entity_id = col
        pe.write(frame, 1)
        got = pe.find(1)
        assert sum(v is None for v in got.target_entity_id) == sum(
            v is None for v in col
        )


class TestPushdown:
    def test_filter_parity_with_matches(self, tmp_path):
        client, le, pe = store_at(tmp_path / "pq")
        le.init(1)
        events = [
            mk(
                "view" if j % 3 else "buy",
                f"u{j % 7}",
                j,
                target=f"i{j % 5}" if j % 2 else None,
            )
            for j in range(60)
        ]
        le.insert_batch(events, 1)
        pe.compact(1)
        le.insert_batch(
            [mk("rate", f"u{j % 7}", 100 + j) for j in range(10)], 1
        )  # mixed compacted + hot
        filters = [
            EventFilter(event_names=("buy",)),
            EventFilter(entity_type="user", entity_id="u3"),
            EventFilter(start_time=t(10), until_time=t(40)),
            EventFilter(target_entity_type="", event_names=("view",)),
            EventFilter(target_entity_id="i2"),
        ]
        everything = list(le.find(1))
        for flt in filters:
            got = sorted(e.event_id for e in le.find(1, filter=flt))
            want = sorted(
                e.event_id for e in everything if flt.matches(e)
            )
            assert got == want, flt

    def test_column_projection(self, tmp_path):
        client, le, pe = store_at(tmp_path / "pq")
        pe.write(bulk_frame(500), 1)
        for _, f in pe.iter_shards(1, columns=["entity_id", "properties"]):
            assert f.entity_id is not None and f.properties is not None
            assert f.event is not None  # anchor column always present
            assert f.target_entity_id is None and f.event_id is None
            assert f.event_time_ms is None
        # projection composes with a filter that reads non-projected cols
        rows = sum(
            len(f)
            for _, f in pe.iter_shards(
                1,
                filter=EventFilter(event_names=("rate",)),
                columns=["entity_id"],
            )
        )
        assert rows == 500

    def test_find_by_entity_parity_and_skipping(self, tmp_path):
        client, le, pe = store_at(tmp_path / "pq")
        le.init(1)
        events = [
            mk("view", f"u{j % 9}", j, target=f"i{j % 4}") for j in range(90)
        ]
        le.insert_batch(events, 1)
        pe.compact(1)
        le.insert_batch(
            [mk("buy", f"u{j % 9}", 200 + j) for j in range(9)], 1
        )
        from predictionio_tpu.obs.metrics import REGISTRY

        read0 = REGISTRY.counter(
            "pio_eventstore_bytes_read_total", labelnames=("kind",)
        ).labels("entity").value
        via_point = [
            (e.event_id, e.event)
            for e in le.find_by_entity(1, "user", "u3", reversed=True)
        ]
        via_find = [
            (e.event_id, e.event)
            for e in le.find(
                1,
                filter=EventFilter(
                    entity_type="user", entity_id="u3", reversed=True
                ),
            )
        ]
        assert via_point == via_find and via_point
        assert (
            REGISTRY.counter(
                "pio_eventstore_bytes_read_total", labelnames=("kind",)
            ).labels("entity").value
            > read0
        )
        # limit + time-window shapes
        latest = list(
            le.find_by_entity(1, "user", "u3", limit=2, reversed=True)
        )
        assert len(latest) == 2
        assert latest[0].event_time >= latest[1].event_time

    def test_time_window_segment_skipping(self, tmp_path):
        client, le, pe = store_at(tmp_path / "pq", n_shards=1)
        pe.write(bulk_frame(300, t0=0), 1)
        pe.write(bulk_frame(300, t0=10_000_000, seed=1), 1)
        from predictionio_tpu.obs.metrics import REGISTRY

        skip_c = REGISTRY.counter(
            "pio_eventstore_bytes_skipped_total", labelnames=("kind",)
        ).labels("full")
        before = skip_c.value
        start = datetime.fromtimestamp(
            (1_700_000_000_000 + 10_000_000_000) / 1000, tz=timezone.utc
        )
        got = pe.find(1, filter=EventFilter(start_time=start))
        assert len(got) == 300
        assert skip_c.value > before  # the old segment was never decoded

    def test_time_window_skip_never_resurrects_superseded_rows(
        self, tmp_path
    ):
        """A hot segment OUTSIDE a query's time window may hold the
        NEWEST version of an upserted id — skipping it by footer stats
        must not let the superseded in-window compacted copy escape."""
        client, le, pe = store_at(tmp_path / "pq")
        le.init(1)
        eid = le.insert(mk("view", "u1", 1), 1)
        pe.compact(1)
        le.insert(mk("view", "u1", 10_000_000, eid=eid), 1)  # far future
        got = list(
            le.find(1, filter=EventFilter(until_time=t(2000)))
        )
        assert got == []  # the old version is superseded, not in-window

    def test_entity_range_skip_never_resurrects_superseded_rows(
        self, tmp_path
    ):
        """Same guard for the entity point read: an upsert that MOVED an
        event to an out-of-range entity still claims its id."""
        client, le, pe = store_at(tmp_path / "pq", n_shards=1)
        le.init(1)
        eid = le.insert(mk("view", "aaa", 1), 1)
        pe.compact(1)
        le.insert(mk("view", "zzz", 2, eid=eid), 1)  # same shard (1 shard)
        got = list(le.find_by_entity(1, "user", "aaa"))
        assert got == []  # the 'aaa' version is superseded

    def test_local_compact_refuses_owned_root(self, tmp_path):
        from predictionio_tpu.data.storage.parquet_backend import (
            acquire_root_ownership,
        )

        client, le, pe = store_at(tmp_path / "pq", n_shards=1)
        le.insert_batch([mk("view", "u1", 1)], 1)
        owner = acquire_root_ownership(client.root)
        assert owner is not None
        try:
            # a second process-level claim must fail while the owner lives
            assert acquire_root_ownership(client.root) is None
        finally:
            owner.close()
        again = acquire_root_ownership(client.root)
        assert again is not None
        again.close()

    def test_upsert_semantics_survive_pushdown(self, tmp_path):
        """The superseded version of an upserted row must stay hidden from
        filters even when the predicate could push into the reader."""
        client, le, pe = store_at(tmp_path / "pq")
        le.init(1)
        eid = le.insert(mk("view", "u1", 1), 1)
        pe.compact(1)
        le.insert(mk("buy", "u1", 2, eid=eid), 1)
        assert [
            e.event_id for e in le.find(1, filter=EventFilter(event_names=("view",)))
        ] == []
        assert [
            e.event_id for e in le.find(1, filter=EventFilter(event_names=("buy",)))
        ] == [eid]


#: what a training consumer reads of a frame (RatingsDataSource's three)
ASKED = ("entity_id", "target_entity_id", "properties")
NOT_ASKED = (
    "entity_type", "target_entity_type", "event_time_ms", "event_id",
    "tags", "pr_id", "creation_time_ms",
)


def asked_rows(frame: EventFrame) -> list[tuple]:
    """The frame's rows as the consumer sees them, in the frame's order."""
    return [
        (frame.event[i], *(getattr(frame, c)[i] for c in ASKED))
        for i in range(len(frame))
    ]


@pytest.fixture()
def mixed_store(tmp_path):
    """Four shards, each with a compacted segment AND a write-hot head;
    id-bearing rows some of which were upserted after the fold, tombstones
    on both sides of it, and bulk (null-id) rows on both sides of it."""
    client, le, pe = store_at(tmp_path / "pq")
    le.init(1)
    rated = lambda j: {"rating": float(1 + j % 5)}  # noqa: E731
    ids = le.insert_batch(
        [mk("rate", f"u{j % 11}", j, target=f"i{j % 7}", props=rated(j))
         for j in range(60)],
        1,
    )
    pe.write(bulk_frame(300, seed=1), 1)
    le.delete(ids[3], 1)  # folded into the compacted segment
    pe.compact(1)
    le.insert_batch(  # upserts: same ids (and users: an id's shard), later
        [mk("rate", f"u{j % 11}", 100 + j, target=f"i{(j + 1) % 7}",
            props={"rating": 0.5}, eid=ids[j]) for j in (5, 6, 17, 40)],
        1,
    )
    le.insert_batch(
        [mk("buy", f"u{j % 11}", 200 + j, target=f"i{j % 5}")
         for j in range(12)],
        1,
    )
    le.delete(ids[8], 1)  # newer than the watermark
    le.delete(ids[17], 1)  # a tombstone over an upserted id
    pe.write(bulk_frame(200, t0=500, seed=2), 1)
    st = pe.status(1)
    assert st["segments_hot"] > 0 and st["segments_compacted"] == 4
    yield client, le, pe
    client.close()


class TestConsumerStatedRead:
    """``PEvents.find(columns=, ordered=)`` (ISSUE 27): the consumer says
    what it reads and whether it needs time order; the parquet backend
    then reads less, every other backend ignores both."""

    FILTERS = {
        "none": None,
        "events": EventFilter(event_names=("rate", "buy")),
        "training": EventFilter(
            entity_type="user", target_entity_type="item",
            event_names=("rate", "buy"),
        ),
        "window": EventFilter(start_time=t(20), until_time=t(400)),
        "target": EventFilter(target_entity_id="i2"),
        "entity": EventFilter(entity_type="user", entity_id="u3"),
    }

    @pytest.mark.parametrize("name", list(FILTERS))
    def test_unordered_projection_is_the_same_multiset(
        self, mixed_store, name
    ):
        _, _, pe = mixed_store
        flt = self.FILTERS[name]
        want = pe.find(1, filter=flt)
        got = pe.find(1, filter=flt, columns=ASKED, ordered=False)
        assert len(want) > 0
        assert sorted(asked_rows(got)) == sorted(asked_rows(want))
        for col in NOT_ASKED:
            assert getattr(got, col) is None, col

    def test_unordered_rows_come_shard_by_shard_and_repeat(self, mixed_store):
        from predictionio_tpu.data.storage.base import frame_shard_of

        _, _, pe = mixed_store
        a = pe.find(1, columns=(*ASKED, "entity_type"), ordered=False)
        b = pe.find(1, columns=(*ASKED, "entity_type"), ordered=False)
        assert asked_rows(a) == asked_rows(b)
        shard = frame_shard_of(a.entity_type, a.entity_id, 4)
        assert (np.diff(shard) >= 0).all() and len(set(shard)) == 4
        # ... and within a shard in its table's own order: the rows of
        # iter_shards, one shard after the other
        per_shard = [
            row
            for _, f in pe.iter_shards(1, columns=ASKED)
            for row in asked_rows(f)
        ]
        assert asked_rows(a) == per_shard
        # a full frame read without order is the same rows, every column
        full = pe.find(1, ordered=False)
        assert asked_rows(full) == asked_rows(a)
        assert full.event_time_ms is not None and full.event_id is not None
        assert (np.diff(full.event_time_ms) < 0).any()  # not time order

    @pytest.mark.parametrize(
        "flt",
        [
            EventFilter(limit=25),
            EventFilter(reversed=True),
            EventFilter(limit=9, reversed=True, event_names=("rate",)),
            EventFilter(limit=0),
        ],
        ids=["limit", "reversed", "limit-reversed", "limit-0"],
    )
    def test_limit_and_reversed_come_back_ordered(self, mixed_store, flt):
        _, _, pe = mixed_store
        want = pe.find(1, filter=flt)
        got = pe.find(1, filter=flt, columns=ASKED, ordered=False)
        assert asked_rows(got) == asked_rows(want)  # row for row
        if len(want):
            # the sort key it was ordered by came along: more than asked
            assert (got.event_time_ms == want.event_time_ms).all()

    def test_ordered_projection_keeps_the_default_order(self, mixed_store):
        _, _, pe = mixed_store
        want = pe.find(1)
        got = pe.find(1, columns=ASKED)
        assert asked_rows(got) == asked_rows(want)
        assert (got.event_time_ms == want.event_time_ms).all()
        assert (np.diff(got.event_time_ms) >= 0).all()
        assert got.event_id is None and got.entity_type is None

    def test_the_default_call_is_the_full_sorted_frame(self, mixed_store):
        import dataclasses

        _, le, pe = mixed_store
        frame = pe.find(1)
        explicit = pe.find(1, None, None, None, True)
        for f in dataclasses.fields(EventFrame):
            a, b = getattr(frame, f.name), getattr(explicit, f.name)
            assert a is not None and a.tolist() == b.tolist(), f.name
        assert (np.diff(frame.event_time_ms) >= 0).all()
        # the row path agrees on what is live: upserts won, tombstones hid
        assert sorted(x for x in frame.event_id if x) == sorted(
            e.event_id for e in le.find(1) if e.event_id
        )
        assert len(frame) == 500 + 60 + 12 - 3  # three ids deleted

    def test_spans_say_what_the_read_did(self, mixed_store, caplog):
        import logging

        from predictionio_tpu.obs.tracing import recent_traces, trace

        _, _, pe = mixed_store
        caplog.set_level(logging.INFO, "predictionio_tpu.data.parquet")
        tags = {}
        for key, kwargs in {
            "default": {},
            "training": {"columns": ASKED, "ordered": False},
            "limit": {"filter": EventFilter(limit=5), "ordered": False},
        }.items():
            with trace(f"test.read.{key}"):
                pe.find(1, **kwargs)
            root = recent_traces(1)[0]
            assert [c["name"] for c in root["children"]] == [
                "eventstore.scan", "eventstore.sort", "eventstore.decode"]
            tags[key] = {c["name"]: c for c in root["children"]}
        scan = {k: v["eventstore.scan"] for k, v in tags.items()}
        assert (scan["default"]["columns"], scan["default"]["ordered"]) == (
            12, True)
        # event + the three asked for; the merge keys were read and dropped
        assert (scan["training"]["columns"], scan["training"]["ordered"]) == (
            4, False)
        assert scan["limit"]["ordered"] is True
        assert tags["default"]["eventstore.sort"]["sorted"] is True
        assert tags["training"]["eventstore.sort"]["sorted"] is False
        assert tags["limit"]["eventstore.sort"]["sorted"] is True
        assert tags["training"]["eventstore.decode"]["columns"] == 4
        assert scan["training"]["rows"] == scan["default"]["rows"]
        # the same tags in the log, one record a bulk read
        logged = [r.bulk_read for r in caplog.records
                  if hasattr(r, "bulk_read")]
        assert [(r["columns"], r["ordered"]) for r in logged] == [
            (12, True), (4, False), (12, True)]
        assert logged[1]["rows"] == scan["training"]["rows"]

    def test_empty_store_and_no_match(self, tmp_path):
        client, le, pe = store_at(tmp_path / "pq")
        le.init(1)
        assert len(pe.find(1, columns=ASKED, ordered=False)) == 0
        pe.write(bulk_frame(40), 1)
        none = pe.find(
            1, filter=EventFilter(event_names=("nope",)), columns=ASKED,
            ordered=False,
        )
        assert len(none) == 0
        client.close()

    def test_backends_that_ignore_the_arguments_get_them(self):
        """A backend written against the old contract (``find`` takes the
        filter and nothing else) still answers a caller that states its
        columns and order: with everything, sorted."""
        from predictionio_tpu.data.storage.base import PEvents

        calls = []

        class Narrow(PEvents):
            def find(self, app_id, channel_id=None, filter=None):
                calls.append((app_id, channel_id, filter))
                return "everything, sorted"

            def write(self, frame, app_id, channel_id=None): ...

            def delete(self, event_ids, app_id, channel_id=None): ...

        class Wide(Narrow):
            def find(self, app_id, channel_id=None, filter=None,
                     columns=None, ordered=True):
                return (columns, ordered)

        flt = EventFilter(limit=3)
        n = Narrow()
        assert n.find(7, None, flt, columns=ASKED, ordered=False) == (
            "everything, sorted")
        assert n.find(7, filter=flt) == "everything, sorted"
        assert calls == [(7, None, flt)] * 2
        assert Narrow.find.__name__ == "find"
        assert Wide().find(7, columns=ASKED, ordered=False) == (ASKED, False)

    def test_sqlite_accepts_both_and_returns_the_full_frame(self, tmp_path):
        import dataclasses

        from predictionio_tpu.data.storage.sqlite_backend import (
            SQLiteClient,
            SQLiteLEvents,
            SQLitePEvents,
        )

        client = SQLiteClient(tmp_path / "pio.sqlite")
        le = SQLiteLEvents(client)
        pe = SQLitePEvents(client, le)
        le.init(1)
        le.insert_batch(
            [mk("rate", f"u{j % 5}", 50 - j, target=f"i{j % 3}",
                props={"rating": float(j % 5)}) for j in range(30)],
            1,
        )
        want = pe.find(1)
        got = pe.find(1, columns=ASKED, ordered=False)
        for f in dataclasses.fields(EventFrame):
            assert getattr(got, f.name).tolist() == (
                getattr(want, f.name).tolist()), f.name
        assert (np.diff(got.event_time_ms) >= 0).all()
        few = pe.find(1, None, EventFilter(limit=4), ASKED, False)
        assert asked_rows(few) == asked_rows(want)[:4]


# -- codes through the frame (ISSUE 37) --------------------------------------


def eager(frame: EventFrame) -> EventFrame:
    """The same rows as every backend but parquet hands them over: arrays
    only, no column's codes kept."""
    import dataclasses

    return EventFrame(**{
        f.name: getattr(frame, f.name) for f in dataclasses.fields(EventFrame)
    })


class _Context:
    """An EngineContext as far as ``RatingsDataSource`` reads one: the event
    store facade over app 1 of ``pe``, its frames as the backend made them
    or, ``arrays_only``, stripped of their codes."""

    def __init__(self, pe, arrays_only=False):
        self.pe, self.arrays_only = pe, arrays_only
        self.p_event_store = self

    def find(self, app_name, channel_name=None, event_names=None,
             columns=None, ordered=True, **flt):
        frame = self.pe.find(
            1, None, EventFilter(event_names=tuple(event_names), **flt),
            columns=columns, ordered=ordered,
        )
        return eager(frame) if self.arrays_only else frame


def _train_inputs(pe, arrays_only, buy_rating=3.5):
    """(TrainingData, PreparedData, {span: tags}) of the recommendation
    engine's read and prepare over ``pe``."""
    from predictionio_tpu.models.recommendation.engine import (
        DataSourceParams,
        RatingsDataSource,
        RatingsPreparator,
    )
    from predictionio_tpu.obs.tracing import trace

    ctx = _Context(pe, arrays_only)
    with trace("test.codes", ring=False) as root:
        td = RatingsDataSource(
            DataSourceParams(buy_rating=buy_rating)).read_training(ctx)
        pd = RatingsPreparator().prepare(ctx, td)
    return td, pd, {c.name: c.tags for c in root.children}


def _assert_codes_change_nothing(pe, coded_columns=4):
    """The read that keeps the store's codes and the one that reads object
    columns hand the algorithm the same arrays; returns the coded side."""
    from predictionio_tpu.data.storage.base import CodedColumn

    td, pd, spans = _train_inputs(pe, arrays_only=False)
    td0, pd0, spans0 = _train_inputs(pe, arrays_only=True)
    took = coded_columns == 4
    decode = spans.get("eventstore.decode")  # no span where no table was
    assert (decode and decode["coded_columns"]) == coded_columns
    assert spans["datasource.columns"]["path"] == (
        "codes" if took else "objects")
    assert spans0["datasource.columns"]["path"] == "objects"
    assert isinstance(td.users, CodedColumn) == took
    assert type(td0.users) is type(td0.items) is np.ndarray
    assert spans0["prepare.vocab"]["path"] in ("factorize", "loop")
    if took:
        assert spans["prepare.vocab"]["path"] == "codes"
        assert td.users.codes.dtype == td.items.codes.dtype == np.int32
    # the DataSource's output, row for row ...
    assert list(td.users) == list(td0.users)
    assert list(td.items) == list(td0.items)
    assert td.ratings.dtype == td0.ratings.dtype == np.float32
    np.testing.assert_array_equal(td.ratings, td0.ratings)
    # ... and the Preparator's: key for key in the same first-seen order,
    # and the index arrays to the element
    for a, b in ((pd.user_vocab, pd0.user_vocab),
                 (pd.item_vocab, pd0.item_vocab)):
        assert list(a.items()) == list(b.items())
    for a, b in ((pd.user_idx, pd0.user_idx), (pd.item_idx, pd0.item_idx)):
        assert a.dtype == b.dtype == np.int32
        np.testing.assert_array_equal(a, b)
    assert list(pd.user_vocab) == list(dict.fromkeys(td0.users))
    for key in ("rows", "users", "items", "user_keys_hashed",
                "item_keys_hashed"):
        if spans0["prepare.vocab"]["path"] == "factorize":
            assert spans["prepare.vocab"][key] == spans0["prepare.vocab"][key]
    return td, pd


def _rated(le, pe, rows, app_id=1):
    """``rows`` of (event, user, item, properties) into the store."""
    le.insert_batch(
        [mk(ev, u, j, target=i, props=props)
         for j, (ev, u, i, props) in enumerate(rows)],
        app_id,
    )


def _edge_buy_without_rating(le, pe, tmp_path):
    _rated(le, pe, [("buy", f"u{j % 5}", f"i{j % 3}", None)
                    for j in range(20)]
           + [("rate", "u1", "i9", {"rating": 2.0})])
    td, _ = _assert_codes_change_nothing(pe)
    assert sorted(td.ratings.tolist()) == [2.0] + [3.5] * 20


def _edge_rating_as_string_and_bool(le, pe, tmp_path):
    _rated(le, pe, [
        ("rate", "u1", "i1", {"rating": "4.5"}),
        ("rate", "u2", "i1", {"rating": True}),
        ("rate", "u3", "i2", {"rating": False}),
        ("rate", "u4", "i2", {"rating": "n/a"}),  # not a number: dropped
        ("rate", "u5", "i3", {"rating": 3}),
    ] * 3)
    td, pd = _assert_codes_change_nothing(pe)
    got = sorted(set(zip(td.users, td.ratings.tolist())))
    assert got == [("u1", 4.5), ("u2", 1.0), ("u3", 0.0), ("u5", 3.0)]
    assert "u4" not in pd.user_vocab


def _edge_rate_without_rating(le, pe, tmp_path):
    _rated(le, pe, [
        ("rate", "u1", "i1", {"rating": 4.0}),
        ("rate", "lonely", "i-unseen", {"stars": 5}),
        ("rate", "u1", "i-unseen-too", None),
        ("rate", "u2", "i1", {"rating": 1.0}),
        ("rate", "lonely", "i1", {}),
    ] * 4)
    pe.compact(1)
    _rated(le, pe, [("rate", "lonely", "i2", {"note": "x"}),
                    ("rate", "u3", "i2", {"rating": 5.0})])
    td, pd = _assert_codes_change_nothing(pe)
    # the rows are dropped, and an id seen only on such rows is in the
    # store's dictionary and NOT in the vocabulary
    assert len(td.ratings) == 9
    assert "lonely" in td.users.dictionary.tolist()
    assert sorted(pd.user_vocab) == ["u1", "u2", "u3"]
    assert sorted(pd.item_vocab) == ["i1", "i2"]


def _edge_plain_string_segments(le, pe, tmp_path):
    """A segment from before the columns were dictionary-encoded on disk,
    beside a compacted segment and a hot head that are."""
    import pyarrow as pa

    from predictionio_tpu.data.storage.parquet_backend import (
        _SCHEMA,
        _event_row,
        _publish_segment,
        _segment_stats,
    )

    pe.write(bulk_frame(120, seed=3), 1)
    pe.compact(1)
    pe.write(bulk_frame(60, t0=300, seed=4), 1)
    client = pe.store.client
    for shard in range(4):
        seq = client.seq.reserve()
        rows = [
            _event_row(mk("rate", f"old{shard}", j, target=f"i{j % 4}",
                          props={"rating": float(1 + j % 5)}), seq, None)
            for j in range(6)
        ]
        plain = pa.Table.from_pylist(rows, schema=_SCHEMA)
        assert not pa.types.is_dictionary(plain.schema.field("entity_id").type)
        _publish_segment(
            client.app_dir(1, None) / f"shard={shard}",
            f"seg-{seq}.parquet", plain, _segment_stats(plain))
        client.seq.release(seq)
    td, pd = _assert_codes_change_nothing(pe)
    assert len(td.ratings) == 120 + 60 + 24
    assert {f"old{k}" for k in range(4)} <= set(pd.user_vocab)


def _edge_empty_store(le, pe, tmp_path):
    # nothing to scan: the frame is ``from_events([])`` and offers no codes
    td, pd = _assert_codes_change_nothing(pe, coded_columns=None)
    assert len(td.ratings) == len(pd.user_idx) == len(pd.user_vocab) == 0


def _edge_nothing_rated(le, pe, tmp_path):
    # rows scanned, none of them a rating: coded columns of no rows
    _rated(le, pe, [("rate", "u1", "i1", {"stars": 1})] * 3)
    td, pd = _assert_codes_change_nothing(pe)
    assert len(td.ratings) == len(pd.user_idx) == len(pd.item_vocab) == 0


def _edge_null_target_id(le, pe, tmp_path):
    frame = bulk_frame(90, seed=5)
    col = frame.target_entity_id.copy()
    col[::7] = None  # an item type with no item id: bulk writers may
    frame.target_entity_id = col
    pe.write(frame, 1)
    td, pd = _assert_codes_change_nothing(pe)
    assert sum(i is None for i in td.items) == 13 and None in pd.item_vocab
    row = next(j for j, i in enumerate(td.items) if i is None)
    assert td.items[row] is None
    assert pd.item_idx[row] == pd.item_vocab[None]


CODED_EDGES = {
    "buy-without-rating": _edge_buy_without_rating,
    "rating-as-string-and-bool": _edge_rating_as_string_and_bool,
    "rate-without-rating": _edge_rate_without_rating,
    "plain-string-segments": _edge_plain_string_segments,
    "empty-store": _edge_empty_store,
    "nothing-rated": _edge_nothing_rated,
    "null-target-id": _edge_null_target_id,
}


class TestCodesThroughTheFrame:
    """A parquet frame holds its dictionary columns as ``(codes,
    dictionary)`` (ISSUE 37): whoever asks gets the codes, whoever reads
    ``frame.entity_id`` gets the object column it always got."""

    def test_training_read_equals_the_object_read(self, mixed_store):
        """Compacted segments and hot heads, upserts, tombstones, ``buy``
        events: same TrainingData, same vocabulary in the same order, same
        index arrays."""
        _, _, pe = mixed_store
        td, pd = _assert_codes_change_nothing(pe)
        assert len(td.ratings) > 500 and 3.5 in td.ratings
        # first-seen in the store's own order: shard ascending
        from predictionio_tpu.data.storage.base import frame_shard_of

        users = np.asarray(td.users)
        shard = frame_shard_of(_const(len(users), "user"), users, 4)
        assert (np.diff(shard) >= 0).all() and len(set(shard)) == 4

    @pytest.mark.parametrize("edge", list(CODED_EDGES))
    def test_edges_train_as_the_object_read_does(self, tmp_path, edge):
        client, le, pe = store_at(tmp_path / "pq")
        le.init(1)
        try:
            CODED_EDGES[edge](le, pe, tmp_path)
        finally:
            client.close()

    READS = {
        "default": {},
        "training": {"columns": ASKED, "ordered": False},
        "filtered": {"filter": EventFilter(event_names=("rate",))},
        "limit-reversed": {"filter": EventFilter(limit=40, reversed=True)},
    }

    @pytest.mark.parametrize("read", list(READS))
    def test_object_columns_are_what_they_were(self, mixed_store, read):
        """Every column of a coded frame, read as ever, is the broadcast
        ``dictionary[codes]`` the eager decode made (one object a distinct
        value, so pointer fast paths stay hot), made once."""
        import dataclasses

        _, _, pe = mixed_store
        frame = pe.find(1, **self.READS[read])
        names = [f.name for f in dataclasses.fields(EventFrame)]
        offered = [c for c in names if frame.coded(c) is not None]
        assert set(offered) == {
            c for c in EventFrame.CODABLE if getattr(frame, c) is not None}
        for c in offered:
            col = frame.coded(c)
            assert col.codes.dtype == np.int32 and len(col) == len(frame)
            want = col.dictionary[col.codes]
            got = getattr(frame, c)
            assert type(got) is np.ndarray and got.dtype == object
            assert got is getattr(frame, c)  # made once, then kept
            assert all(a is b for a, b in zip(got, want))
        # a frame of the arrays alone offers nothing and reads the same
        arrays = eager(frame)
        assert all(arrays.coded(c) is None for c in names)
        again = pe.find(1, **self.READS[read])
        for c in names:
            a, b = getattr(arrays, c), getattr(again, c)
            assert (a is None) == (b is None)
            if a is not None:
                assert a.tolist() == b.tolist(), c

    SELECTIONS = {
        "mask": lambda n: np.arange(n) % 3 == 1,
        "index-array": lambda n: np.random.default_rng(7).permutation(n)[:97],
        "nothing": lambda n: np.zeros(n, bool),
        "everything-reversed": lambda n: np.arange(n)[::-1],
    }

    @pytest.mark.parametrize("how", list(SELECTIONS))
    @pytest.mark.parametrize("method", ["take", "select"])
    def test_take_and_select_equal_the_array_frames(
        self, mixed_store, how, method
    ):
        import dataclasses

        _, _, pe = mixed_store
        frame = pe.find(1)
        frame.entity_id  # one column already read as objects, the rest not
        sel = self.SELECTIONS[how](len(frame))
        got = getattr(frame, method)(sel)
        want = getattr(eager(frame), method)(sel)
        assert len(got) == len(want)
        for f in dataclasses.fields(EventFrame):
            assert getattr(got, f.name).tolist() == (
                getattr(want, f.name).tolist()), f.name
        # the codes came along, the array frame still has none
        assert got.coded("entity_id") is not None
        assert want.coded("entity_id") is None
        np.testing.assert_array_equal(
            got.coded("properties").codes, frame.coded("properties").codes[sel])
        assert got.to_events() == want.to_events()

    def test_where_event_and_property_column(self, mixed_store):
        _, _, pe = mixed_store
        frame = pe.find(1)
        arrays = eager(frame)
        for names in (("buy",), ("rate", "buy"), ("nope",)):
            got, want = frame.where_event(*names), arrays.where_event(*names)
            assert asked_rows(got) == asked_rows(want)
            np.testing.assert_array_equal(
                got.property_column("rating"), want.property_column("rating"))
            np.testing.assert_array_equal(
                got.property_column("rating", default=-1.0, dtype=np.float64),
                want.property_column("rating", default=-1.0, dtype=np.float64))
        assert len(frame.where_event("nope")) == 0

    def test_to_events_and_a_write_round_trip(self, mixed_store, tmp_path):
        _, _, pe = mixed_store
        frame = pe.find(1)
        assert frame.to_events() == eager(pe.find(1)).to_events()
        # find() -> write() into two fresh apps, from the coded frame and
        # from arrays: the same store either way, ids and all
        copies = []
        for app_id, src in ((2, pe.find(1)), (3, eager(pe.find(1)))):
            pe.write(src, app_id)
            copies.append(pe.find(app_id))
        a, b = copies
        assert len(a) == len(b) == len(frame)
        assert asked_rows(a) == asked_rows(b)
        assert sorted(asked_rows(a)) == sorted(asked_rows(frame))
        assert a.event_id.tolist() == b.event_id.tolist()
        assert sorted(filter(None, a.event_id)) == sorted(
            filter(None, frame.event_id))
        assert a.to_events() == b.to_events()

    def test_wire_codec_carries_a_coded_frame(self, mixed_store):
        from predictionio_tpu.data.storage.frame_codec import (
            decode_frame,
            encode_frame,
        )

        _, _, pe = mixed_store
        frame = pe.find(1)
        assert encode_frame(frame) == encode_frame(eager(pe.find(1)))
        back = decode_frame(encode_frame(pe.find(1)))
        # a frame off the wire offers no codes
        assert all(back.coded(c) is None for c in EventFrame.CODABLE)
        assert asked_rows(back) == asked_rows(frame)

    def test_an_assigned_column_is_not_the_stores(self, mixed_store):
        from predictionio_tpu.data.storage.base import concat_frames

        _, _, pe = mixed_store
        frame = pe.find(1)
        assert frame.coded("target_entity_id") is not None
        frame.target_entity_id = frame.target_entity_id[::-1].copy()
        assert frame.coded("target_entity_id") is None
        assert frame.coded("entity_id") is not None
        assert frame.column("target_entity_id") is frame.target_entity_id
        # shards put end to end by a path that does not carry codes along
        parts = [f for _, f in pe.iter_shards(1)]
        assert all(f.coded("event") is not None for f in parts)
        whole = concat_frames(parts)
        assert whole.coded("event") is None and len(whole) == len(frame)


class TestBackpressure:
    def test_saturated_ingest_sheds_503_with_retry_after(self, tmp_path):
        from predictionio_tpu.data.storage.config import (
            StorageConfig,
            StorageRuntime,
        )
        from predictionio_tpu.obs.metrics import MetricsRegistry
        from predictionio_tpu.server.event_server import (
            create_event_server_app,
        )
        from predictionio_tpu.server.httpd import Request

        rt = StorageRuntime(
            StorageConfig.from_env({"PIO_HOME": str(tmp_path)})
        )
        rt.apps().insert(__import__(
            "predictionio_tpu.data.storage.base", fromlist=["App"]
        ).App(id=7, name="bp"))
        from predictionio_tpu.data.storage.base import AccessKey

        rt.access_keys().insert(AccessKey(key="k", appid=7))
        gate = threading.Event()
        orig_insert = rt.l_events().insert

        def slow_insert(event, app_id, channel_id=None):
            gate.wait(timeout=10)
            return orig_insert(event, app_id, channel_id)

        rt.l_events().insert = slow_insert  # type: ignore[method-assign]
        registry = MetricsRegistry()
        app = create_event_server_app(
            rt, registry=registry, max_write_inflight=2
        )
        body = json.dumps(
            {"event": "view", "entityType": "user", "entityId": "u1"}
        ).encode()

        def post():
            req = Request(
                method="POST",
                path="/events.json",
                query={"accessKey": "k"},
                headers={},
                body=body,
            )
            return app.handle(req)

        results = []
        threads = [
            threading.Thread(target=lambda: results.append(post()))
            for _ in range(6)
        ]
        for th in threads:
            th.start()
        time.sleep(0.3)  # two block in the store; the rest must shed NOW
        shed_before_release = [r for r in results if r is not None]
        gate.set()
        for th in threads:
            th.join(timeout=15)
        statuses = sorted(r.status for r in results)
        assert statuses.count(201) == 2  # admitted writes completed
        assert statuses.count(503) == 4
        assert shed_before_release, "sheds must not wait on the slow store"
        shed = next(r for r in results if r.status == 503)
        assert "Retry-After" in shed.headers
        fam = registry.get("pio_shed_total")
        assert fam.labels("eventstore").value == 4

    def test_ingest_shed_alert_rule_in_default_pack(self):
        from predictionio_tpu.obs.alerts import default_rule_pack

        rules = {r.name: r for r in default_rule_pack()}
        r = rules["ingest_shed"]
        assert r.selector == "metric:pio_shed_total"
        assert r.labels == {"reason": "eventstore"}
        assert r.rate and r.for_s > 0


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class TestFanout:
    @pytest.fixture
    def daemons(self, tmp_path):
        from predictionio_tpu.server.storage_server import StorageServer

        servers = [
            StorageServer(
                tmp_path / f"root{i}",
                host="127.0.0.1",
                port=0,
                compaction=False,
            ).start_background()
            for i in range(2)
        ]
        yield servers
        for s in servers:
            s.shutdown()

    @pytest.fixture
    def fan(self, daemons):
        from predictionio_tpu.data.storage.config import (
            StorageConfig,
            StorageRuntime,
        )

        urls = ",".join(
            f"http://127.0.0.1:{s.port}" for s in daemons
        )
        rt = StorageRuntime(
            StorageConfig.from_env(
                {
                    "PIO_STORAGE_SOURCES_FLEET_TYPE": "remote",
                    "PIO_STORAGE_SOURCES_FLEET_URL": urls,
                    "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "FLEET",
                }
            )
        )
        yield rt
        rt.close()

    def test_fanout_types_selected(self, fan):
        from predictionio_tpu.data.storage.remote_backend import (
            FanoutLEvents,
            FanoutPEvents,
        )

        assert isinstance(fan.l_events(), FanoutLEvents)
        assert isinstance(fan.p_events(), FanoutPEvents)

    def test_bulk_write_partitions_by_entity_hash(self, fan, daemons):
        pe = fan.p_events()
        pe.write(bulk_frame(400), 1)
        whole = pe.find(1)
        assert len(whole) == 400
        # each daemon holds a DISJOINT, non-empty subset
        from predictionio_tpu.data.storage.remote_backend import (
            RemoteClient,
            RemotePEvents,
        )

        counts = []
        for s in daemons:
            sub = RemotePEvents(
                RemoteClient(f"http://127.0.0.1:{s.port}")
            )
            counts.append(len(sub.find(1)))
        assert sum(counts) == 400 and all(c > 0 for c in counts)
        # shard-addressed scans fan in across daemons
        rows = sum(len(f) for _, f in pe.iter_shards(1))
        assert rows == 400
        # per-shard results hash to their shard
        from predictionio_tpu.data.storage.base import entity_shard

        n = pe.n_shards(1)
        for k, f in pe.iter_shards(1, shards=[1, 3]):
            assert k in (1, 3)
            for et, eid in zip(f.entity_type, f.entity_id):
                assert entity_shard(et, eid, n) == k

    def test_row_ops_route_and_round_trip(self, fan):
        le = fan.l_events()
        le.init(1)
        ids = le.insert_batch(
            [mk("view", f"u{j}", j, target=f"i{j}") for j in range(20)], 1
        )
        assert len(set(ids)) == 20
        got = le.get(ids[3], 1)
        assert got is not None and got.entity_id == "u3"
        hist = list(le.find_by_entity(1, "user", "u7"))
        assert [e.event_id for e in hist] == [ids[7]]
        assert le.delete(ids[3], 1)
        assert le.get(ids[3], 1) is None
        remaining = list(le.find(1, filter=EventFilter(limit=100)))
        assert len(remaining) == 19
        # ordered merge across daemons respects limit/reversed
        newest = list(le.find(1, filter=EventFilter(limit=3, reversed=True)))
        times = [e.event_time for e in newest]
        assert times == sorted(times, reverse=True) and len(newest) == 3

    def test_fanout_compact_and_status(self, fan):
        pe = fan.p_events()
        pe.write(bulk_frame(200), 1)
        rows = pe.compact(1)
        assert rows == 200
        st = pe.status(1)
        assert st["daemons"] == 2
        assert st["segments_hot"] == 0 and st["segments_compacted"] > 0


class TestEventstoreCLI:
    def test_status_and_compact_local(self, tmp_path, capsys):
        from predictionio_tpu.data.storage.config import reset_storage, StorageConfig
        from predictionio_tpu.tools.cli import main as cli_main

        env = {
            "PIO_HOME": str(tmp_path),
            "PIO_STORAGE_SOURCES_PQ_TYPE": "parquet",
            "PIO_STORAGE_SOURCES_PQ_PATH": str(tmp_path / "ev"),
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "PQ",
        }
        old = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            rt = reset_storage(StorageConfig.from_env())
            rt.l_events().insert_batch(
                [mk("view", f"u{j}", j) for j in range(6)], 1
            )
            assert cli_main(["eventstore", "status", "--json"]) == 0
            out = json.loads(capsys.readouterr().out)
            assert out["backlog_segments"] > 0
            assert cli_main(["eventstore", "compact"]) == 0
            assert "live rows" in capsys.readouterr().out
            assert cli_main(["eventstore", "status", "--json"]) == 0
            out = json.loads(capsys.readouterr().out)
            assert out["backlog_segments"] == 0
            assert out["apps"][0]["segments_compacted"] >= 1
        finally:
            for k, v in old.items():
                os.environ.pop(k, None)
                if v is not None:
                    os.environ[k] = v
            reset_storage(StorageConfig.from_env())

    def test_status_url_against_daemon(self, tmp_path, capsys):
        from predictionio_tpu.server.storage_server import StorageServer
        from predictionio_tpu.tools.cli import main as cli_main

        server = StorageServer(
            tmp_path / "root", host="127.0.0.1", port=0, compaction=False
        ).start_background()
        try:
            server.runtime.l_events().insert_batch(
                [mk("view", f"u{j}", j) for j in range(4)], 1
            )
            url = f"http://127.0.0.1:{server.port}"
            assert cli_main(["eventstore", "status", "--url", url, "--json"]) == 0
            out = json.loads(capsys.readouterr().out)
            assert out["backlog_segments"] > 0
            assert cli_main(["eventstore", "compact", "--url", url]) == 0
            capsys.readouterr()
            assert cli_main(["eventstore", "status", "--url", url, "--json"]) == 0
            out = json.loads(capsys.readouterr().out)
            assert out["backlog_segments"] == 0
        finally:
            server.shutdown()

    def test_pio_status_url_warns_on_backlog(self, tmp_path, capsys, monkeypatch):
        from predictionio_tpu.server.storage_server import StorageServer
        from predictionio_tpu.tools.cli import main as cli_main

        monkeypatch.setenv("PIO_COMPACT_BACKLOG_BUDGET", "1")
        server = StorageServer(
            tmp_path / "root", host="127.0.0.1", port=0, compaction=False
        ).start_background()
        try:
            for batch in range(3):
                server.runtime.l_events().insert_batch(
                    [mk("view", f"u{j}", batch * 4 + j) for j in range(4)], 1
                )
            url = f"http://127.0.0.1:{server.port}"
            cli_main(["status", "--url", url])
            err = capsys.readouterr().err
            assert "compaction backlog" in err and "WARNING" in err
        finally:
            server.shutdown()


def _spawn_storage_daemon(root, port, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "predictionio_tpu.tools.cli",
            "storageserver", "--ip", "127.0.0.1", "--port", str(port),
            "--root", str(root), "--no-compact",
        ],
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
        env=env,
    )
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        try:
            with socket.create_connection(("127.0.0.1", port), timeout=1):
                return proc
        except OSError:
            if proc.poll() is not None:
                raise RuntimeError("storage daemon died at boot")
            time.sleep(0.1)
    proc.kill()
    raise TimeoutError("storage daemon never bound its port")


class TestChaosCompaction:
    def test_sigkill_mid_compaction_loses_nothing(self, tmp_path):
        """SIGKILL a REAL storage daemon between the compacted-segment
        publish and the source unlink (a latency fault holds it in that
        exact window), under concurrent ingest.  On restart every acked
        event reads exactly once, and the next compaction resumes from
        the watermark, sweeping the superseded files."""
        from predictionio_tpu.data.storage.remote_backend import (
            RemoteClient,
            RemoteLEvents,
            RemotePEvents,
        )

        root = tmp_path / "root"
        port = _free_port()
        # hold the daemon 30s at the publish seam of shard=0 — the crash
        # window where BOTH the cseg and its folded sources exist
        plan = json.dumps(
            [
                {
                    "seam": "compact.publish",
                    "kind": "latency",
                    "latency_s": 30.0,
                    "match": "shard=0",
                }
            ]
        )
        proc = _spawn_storage_daemon(
            root, port, extra_env={"PIO_FAULT_PLAN": plan}
        )
        client = RemoteClient(f"http://127.0.0.1:{port}", breaker=None)
        le = RemoteLEvents(client)
        acked: list[str] = []
        try:
            le.init(1)
            acked += le.insert_batch(
                [mk("view", f"u{j}", j) for j in range(40)], 1
            )
            # trigger compaction over HTTP; it will wedge at the seam
            def compact_call():
                try:
                    client.json(
                        "POST", "/eventstore/compact", idempotent=True
                    )
                except Exception:
                    pass  # the SIGKILL kills this call

            ct = threading.Thread(target=compact_call, daemon=True)
            ct.start()
            # concurrent ingest while the compactor is mid-fold
            deadline = time.monotonic() + 8.0
            j = 100
            while time.monotonic() < deadline:
                try:
                    acked += le.insert_batch(
                        [mk("view", f"u{j}", j)], 1
                    )
                    j += 1
                except Exception:
                    break  # daemon may already be dead
                # once shard=0's cseg exists the daemon is inside the
                # publish window: kill it there
                shard0 = root / "events_parquet" / "app_1" / "shard=0"
                if list(shard0.glob("cseg-*.parquet")) and list(
                    shard0.glob("seg-*.parquet")
                ):
                    break
                time.sleep(0.05)
            shard0 = root / "events_parquet" / "app_1" / "shard=0"
            assert list(shard0.glob("cseg-*.parquet")), (
                "compaction never reached the publish window"
            )
            assert list(shard0.glob("seg-*.parquet")), (
                "sources already swept; the crash window was missed"
            )
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=10)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)

        # restart WITHOUT the fault plan: every acked event reads exactly
        # once (no loss from the kill, no duplicates from the overlap of
        # cseg + superseded sources)
        proc2 = _spawn_storage_daemon(root, port)
        try:
            client2 = RemoteClient(f"http://127.0.0.1:{port}", breaker=None)
            le2 = RemoteLEvents(client2)
            got = sorted(
                e.event_id
                for e in le2.find(1, filter=EventFilter(limit=-1))
            )
            assert got == sorted(acked)
            # the compactor resumes from the watermark: re-folding sweeps
            # the superseded files and changes nothing
            out = client2.json(
                "POST", "/eventstore/compact", idempotent=True
            )
            assert out["rows"] == len(acked)
            assert not list(shard0.glob("seg-*.parquet")) or True
            got2 = sorted(
                e.event_id
                for e in le2.find(1, filter=EventFilter(limit=-1))
            )
            assert got2 == sorted(acked)
            st = RemotePEvents(client2).status(1)
            assert st["backlog_segments"] == 0
        finally:
            proc2.kill()
            proc2.wait(timeout=10)
