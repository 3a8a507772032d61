"""Storage SPI tests: event DAOs (sqlite + parquet + live postgres when one
is reachable), metadata DAOs, store facades.  The module-level ``storage``
fixture overrides the conftest one to run every DAO test against every
backend; the ``postgres`` param needs a live server (PIO_TEST_POSTGRES_URL,
or local initdb/pg_ctl binaries + psycopg) and skips with a reason
otherwise."""

import os
import shutil
import subprocess
from datetime import datetime, timezone

import numpy as np
import pytest

from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.data.storage.base import (
    AccessKey,
    App,
    Channel,
    EngineInstance,
    EventFilter,
)
from predictionio_tpu.data.store import AppNotFoundError, LEventStore, PEventStore


def _have_pg_driver() -> bool:
    """psycopg, psycopg2, or the bundled ctypes-libpq binding."""
    try:
        import psycopg  # noqa: F401

        return True
    except ImportError:
        pass
    try:
        import psycopg2  # noqa: F401

        return True
    except ImportError:
        pass
    from predictionio_tpu.data.storage import pq_driver

    return pq_driver.available()


def _pg_exec(url: str, sql: str) -> None:
    """Run one admin statement through whichever driver is present."""
    try:
        import psycopg

        with psycopg.connect(url, autocommit=True) as conn:
            conn.execute(sql)
        return
    except ImportError:
        pass
    try:
        import psycopg2

        conn = psycopg2.connect(url)
        try:
            conn.autocommit = True
            conn.cursor().execute(sql)
        finally:
            conn.close()
        return
    except ImportError:
        pass
    from predictionio_tpu.data.storage import pq_driver

    conn = pq_driver.connect(url)
    try:
        conn.cursor().execute(sql)
    finally:
        conn.close()


@pytest.fixture(scope="session")
def pg_server(tmp_path_factory):
    """A throwaway local PostgreSQL server, if the environment can host one.

    Yields a base URL or None (callers skip).  Preference order: an
    operator-provided PIO_TEST_POSTGRES_URL, then initdb/pg_ctl binaries.
    A Python driver is NOT required — the bundled ctypes-libpq binding
    (data/storage/pq_driver.py) suffices; this image lacks the server
    binaries themselves, which is the one remaining skip condition.
    """
    url = os.environ.get("PIO_TEST_POSTGRES_URL")
    if url:
        yield url
        return
    initdb, pg_ctl = shutil.which("initdb"), shutil.which("pg_ctl")
    if not (initdb and pg_ctl and _have_pg_driver()):
        yield None
        return
    d = tmp_path_factory.mktemp("pgdata")
    sock = tmp_path_factory.mktemp("pgsock")
    subprocess.run(
        [initdb, "-D", str(d), "-U", "pio", "--auth=trust"],
        check=True, capture_output=True,
    )
    subprocess.run(
        [pg_ctl, "-D", str(d), "-o", f"-c listen_addresses='' -k {sock}",
         "-w", "start"],
        check=True, capture_output=True,
    )
    try:
        yield f"postgresql://pio@/postgres?host={sock}"
    finally:
        subprocess.run(
            [pg_ctl, "-D", str(d), "-m", "immediate", "stop"],
            capture_output=True,
        )


_pg_db_counter = [0]


@pytest.fixture(params=["sqlite", "parquet", "postgres", "remote"])
def storage(request, tmp_path, pg_server):
    from predictionio_tpu.data.storage.config import (
        StorageConfig,
        reset_storage,
    )

    env = {"PIO_HOME": str(tmp_path / "pio_home")}
    daemon = None
    if request.param == "remote":
        # in-process storage daemon (the ES server-fleet role) on an
        # ephemeral port; all three repositories go through it
        from predictionio_tpu.server.storage_server import StorageServer

        daemon = StorageServer(
            tmp_path / "daemon_root", host="127.0.0.1", port=0
        ).start_background()
        env |= {
            "PIO_STORAGE_SOURCES_REMOTE_TYPE": "remote",
            "PIO_STORAGE_SOURCES_REMOTE_URL": (
                f"http://127.0.0.1:{daemon.port}"
            ),
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "REMOTE",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "REMOTE",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "REMOTE",
        }
    if request.param == "parquet":
        env |= {
            "PIO_STORAGE_SOURCES_PQ_TYPE": "parquet",
            "PIO_STORAGE_SOURCES_PQ_PATH": str(tmp_path / "events_pq"),
            "PIO_STORAGE_SOURCES_PQ_NSHARDS": "4",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "pio_event",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "PQ",
        }
    elif request.param == "postgres":
        if pg_server is None:
            pytest.skip(
                "no live PostgreSQL: set PIO_TEST_POSTGRES_URL or install "
                "server binaries (initdb/pg_ctl); any of psycopg/psycopg2/"
                "the bundled libpq ctypes driver will be used"
            )
        # fresh database per test for isolation; rewrite only the URL's
        # path component (a naive str.replace would mangle usernames like
        # postgres@ or silently no-op on custom database names)
        from urllib.parse import urlsplit, urlunsplit

        _pg_db_counter[0] += 1
        dbname = f"pio_test_{os.getpid()}_{_pg_db_counter[0]}"
        _pg_exec(pg_server, f"CREATE DATABASE {dbname}")
        parts = urlsplit(pg_server)
        url = urlunsplit(parts._replace(path=f"/{dbname}"))
        env |= {
            "PIO_STORAGE_SOURCES_PG_TYPE": "postgres",
            "PIO_STORAGE_SOURCES_PG_URL": url,
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "PG",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "PG",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "PG",
        }
    rt = reset_storage(StorageConfig.from_env(env))
    yield rt
    rt.close()
    if daemon is not None:
        daemon.shutdown()


def t(i):
    return datetime(2026, 1, 1, 0, 0, i, tzinfo=timezone.utc)


def mk(event, eid, i, target=None, props=None):
    return Event(
        event=event,
        entity_type="user",
        entity_id=eid,
        target_entity_type="item" if target else None,
        target_entity_id=target,
        properties=DataMap(props or {}),
        event_time=t(i),
    )


class TestLEvents:
    def test_crud(self, storage):
        le = storage.l_events()
        le.init(1)
        eid = le.insert(mk("view", "u1", 1, target="i1"), 1)
        got = le.get(eid, 1)
        assert got is not None and got.event == "view" and got.entity_id == "u1"
        assert le.delete(eid, 1)
        assert le.get(eid, 1) is None
        assert not le.delete(eid, 1)

    def test_find_filters(self, storage):
        le = storage.l_events()
        le.init(1)
        le.insert_batch(
            [
                mk("view", "u1", 1, target="i1"),
                mk("buy", "u1", 2, target="i2"),
                mk("view", "u2", 3, target="i1"),
                mk("$set", "u1", 4, props={"a": 1}),
            ],
            1,
        )
        assert len(list(le.find(1))) == 4
        assert len(list(le.find(1, filter=EventFilter(entity_id="u1")))) == 3
        assert len(list(le.find(1, filter=EventFilter(event_names=("view",))))) == 2
        assert (
            len(list(le.find(1, filter=EventFilter(start_time=t(2), until_time=t(4)))))
            == 2
        )
        assert (
            len(list(le.find(1, filter=EventFilter(target_entity_id="i1")))) == 2
        )
        # "" matches events with NO target entity
        assert (
            len(list(le.find(1, filter=EventFilter(target_entity_type="")))) == 1
        )
        lim = list(le.find(1, filter=EventFilter(limit=2, reversed=True)))
        assert [e.event_time for e in lim] == [t(4), t(3)]

    def test_channels_isolated(self, storage):
        le = storage.l_events()
        le.init(1)
        le.init(1, 7)
        le.insert(mk("view", "u1", 1), 1)
        le.insert(mk("buy", "u9", 1), 1, 7)
        assert [e.event for e in le.find(1)] == ["view"]
        assert [e.event for e in le.find(1, 7)] == ["buy"]
        le.remove(1, 7)
        assert list(le.find(1, 7)) == []  # re-inits empty

    def test_aggregate_properties(self, storage):
        le = storage.l_events()
        le.init(1)
        le.insert(mk("$set", "u1", 1, props={"a": 1, "g": "m"}), 1)
        le.insert(mk("$set", "u1", 2, props={"a": 2}), 1)
        le.insert(mk("$set", "u2", 1, props={"g": "f"}), 1)
        out = le.aggregate_properties(1, entity_type="user")
        assert out["u1"].fields == {"a": 2, "g": "m"}
        req = le.aggregate_properties(1, entity_type="user", required=["a"])
        assert set(req) == {"u1"}
        with pytest.raises(ValueError):
            le.aggregate_properties(1, entity_type="")


class TestPEvents:
    def test_columnar_scan(self, storage):
        le, pe = storage.l_events(), storage.p_events()
        le.init(1)
        le.insert_batch(
            [
                mk("rate", "u1", 1, target="i1", props={"rating": 4.0}),
                mk("rate", "u2", 2, target="i2", props={"rating": 2.5}),
                mk("view", "u1", 3, target="i3"),
            ],
            1,
        )
        frame = pe.find(1)
        assert len(frame) == 3
        rated = frame.where_event("rate")
        assert len(rated) == 2
        np.testing.assert_allclose(
            rated.property_column("rating"), [4.0, 2.5]
        )
        assert rated.entity_id.tolist() == ["u1", "u2"]
        assert rated.target_entity_id.tolist() == ["i1", "i2"]

    def test_write_roundtrip_idempotent(self, storage):
        le, pe = storage.l_events(), storage.p_events()
        le.init(1)
        le.insert_batch(
            [mk("rate", "u1", 1, target="i1", props={"rating": 3.0})], 1
        )
        frame = pe.find(1)
        pe.write(frame, 2)
        pe.write(frame, 2)  # ids preserved -> INSERT OR REPLACE dedupes
        assert len(pe.find(2)) == 1
        assert pe.find(2).event_id.tolist() == frame.event_id.tolist()

    def test_columnar_limit_and_order(self, storage):
        le, pe = storage.l_events(), storage.p_events()
        le.init(1)
        le.insert_batch([mk("view", f"u{i}", i) for i in range(5)], 1)
        f = pe.find(1, filter=EventFilter(limit=2, reversed=True))
        assert f.event_time_ms.tolist() == [t(4).timestamp() * 1000,
                                            t(3).timestamp() * 1000]


class TestLazyProperties:
    """EventFrame lazy-row contract: properties may be raw JSON strings;
    semantic accessors must match the eager-dict behavior exactly."""

    def _frame(self, props):
        import numpy as np

        from predictionio_tpu.data.storage.base import EventFrame

        n = len(props)
        return EventFrame(
            event=np.full(n, "e", object),
            entity_type=np.full(n, "user", object),
            entity_id=np.array([f"u{i}" for i in range(n)], object),
            target_entity_type=np.full(n, None, object),
            target_entity_id=np.full(n, None, object),
            event_time_ms=np.arange(n, dtype=np.int64),
            properties=np.array(props, object),
        )

    def test_property_column_lazy_matches_eager(self):
        import numpy as np

        lazy = self._frame(
            ['{"rating": 4.5}', "", '{"rating": 2}', '{"other": 1}',
             '{"nested": {"rating": 9}}']
        )
        eager = self._frame(
            [{"rating": 4.5}, {}, {"rating": 2}, {"other": 1},
             {"nested": {"rating": 9}}]
        )
        np.testing.assert_array_equal(
            lazy.property_column("rating"), eager.property_column("rating")
        )
        got = lazy.property_column("rating")
        np.testing.assert_allclose(got[[0, 2]], [4.5, 2.0])
        assert np.isnan(got[[1, 3, 4]]).all()  # nested key does NOT count

    def test_property_column_coercion_contract(self):
        """Numeric strings and bools coerce like the row-wise engine loops'
        float(props[name]); non-numeric strings don't count."""
        import numpy as np

        lazy = self._frame(
            ['{"v": "high"}', '{"v": true}', '{"v": 3}', '{"v": "4.5"}']
        )
        eager = self._frame(
            [{"v": "high"}, {"v": True}, {"v": 3}, {"v": "4.5"}]
        )
        got_l, got_e = lazy.property_column("v"), eager.property_column("v")
        np.testing.assert_array_equal(got_l, got_e)
        assert np.isnan(got_e[0])
        np.testing.assert_allclose(got_e[1:], [1.0, 3.0, 4.5])

    def test_frames_built_from_arrays_or_events_offer_no_codes(self):
        """``from_events`` and a frame of arrays answer ``None`` for every
        column's codes, and ``column`` hands back the array itself."""
        from predictionio_tpu.data.storage.base import EventFrame

        built = self._frame(['{"rating": 4}', "", '{"rating": "2.5"}'])
        from_events = EventFrame.from_events(built.to_events())
        for frame in (built, from_events, EventFrame.from_events([])):
            for c in EventFrame.CODABLE:
                assert frame.coded(c) is None
                assert frame.column(c) is getattr(frame, c)
        import numpy as np

        np.testing.assert_array_equal(
            from_events.property_column("rating"),
            built.property_column("rating"))

    def test_to_events_decodes_lazy_rows(self):
        lazy = self._frame(['{"rating": 4.5}', ""])
        evs = lazy.to_events()
        assert evs[0].properties.fields == {"rating": 4.5}
        assert evs[1].properties.fields == {}

    def test_mixed_lazy_and_dict_rows(self):
        import numpy as np

        mixed = self._frame([{"rating": 1.0}, '{"rating": 2.0}', ""])
        np.testing.assert_allclose(
            mixed.property_column("rating")[:2], [1.0, 2.0]
        )

    def test_malformed_lazy_rows_degrade_not_crash(self):
        """Junk in a lazy row (bad JSON, embedded literal newline causing
        NDJSON row drift, un-serializable dict values) must degrade to
        row-wise semantics — default for the bad rows, exact values for
        the good ones — never crash the scan."""
        import numpy as np

        # literal newline inside a lazy row: NDJSON sees 4 rows for a
        # 3-row frame -> fallback; the junk halves are no-property rows
        f = self._frame(
            ['{"rating": 1}\n{"rating": 2}', '{"rating": 3}', "not json"]
        )
        got = f.property_column("rating")
        assert got[1] == 3.0
        assert np.isnan(got[0]) and np.isnan(got[2])
        # dict row with a value json.dumps cannot serialize -> fallback
        # reads the dict directly
        from datetime import datetime

        g = self._frame([{"rating": 5, "t": datetime(2026, 1, 1)},
                         '{"rating": 6}'])
        np.testing.assert_allclose(
            g.property_column("rating"), [5.0, 6.0]
        )

    def test_frame_shard_of_matches_entity_shard(self):
        import numpy as np

        from predictionio_tpu.data.storage.base import (
            entity_shard,
            frame_shard_of,
        )

        rng = np.random.default_rng(0)
        et = np.array(
            [["user", "item"][x] for x in rng.integers(0, 2, 500)], object
        )
        ei = np.array([f"e{x}" for x in rng.integers(0, 80, 500)], object)
        got = frame_shard_of(et, ei, 8)
        want = [entity_shard(t, e, 8) for t, e in zip(et, ei)]
        np.testing.assert_array_equal(got, want)


class TestCodedColumn:
    """``CodedColumn`` (ISSUE 37): codes into a dictionary, and to whoever
    does not care the object array ``dictionary[codes]``."""

    @pytest.fixture(params=[1 << 18, 5], ids=["one-piece", "pieces-of-5"])
    def col(self, request, monkeypatch):
        from predictionio_tpu.data.storage import base

        monkeypatch.setattr(base, "_ROWS_AT_A_TIME", request.param)
        dictionary = np.empty(6, object)
        dictionary[:] = ["never", "b", None, "a", "b" + "", "c"]
        codes = np.random.default_rng(37).integers(1, 6, 43).astype(np.int32)
        return base.CodedColumn(codes, dictionary)

    def test_reads_as_the_object_array(self, col):
        want = col.dictionary[col.codes]
        assert len(col) == 43 and list(col) == list(want)
        got = np.asarray(col)
        assert got.dtype == object and got.tolist() == want.tolist()
        assert col.objects is col.objects  # made once
        assert all(a is b for a, b in zip(col.objects, want))  # interned
        assert col[7] is want[7] and col[np.int64(-1)] is want[-1]
        assert col.tolist() == want.tolist()  # any other attribute
        assert col.dtype == object and col.shape == (43,)
        with pytest.raises(TypeError):
            hash(col)

    def test_compares_as_the_object_array(self, col):
        want = col.dictionary[col.codes]
        for value in ("b", None, "never", "nowhere", 3):
            np.testing.assert_array_equal(col == value, want == value)
            np.testing.assert_array_equal(col != value, want != value)
        assert (col == "b").sum() == np.isin(col.codes, (1, 4)).sum()
        other = want.copy()
        other[::5] = "x"
        np.testing.assert_array_equal(col == other, want == other)
        np.testing.assert_array_equal(col == col[:], np.ones(43, bool))

    @pytest.mark.parametrize("sel", [
        np.arange(43) % 4 == 0, np.array([5, 5, 0, 42]), slice(3, 30, 2),
        np.zeros(43, bool),
    ], ids=["mask", "index-array", "slice", "nothing"])
    def test_rows_picked_stay_coded(self, col, sel):
        from predictionio_tpu.data.storage.base import CodedColumn

        got = col[sel]
        assert isinstance(got, CodedColumn) and got.dictionary is col.dictionary
        assert got.codes.dtype == np.int32
        assert list(got) == list(col.dictionary[col.codes][sel])

    def test_lookup_and_first_rows(self, col):
        table = np.arange(6, dtype=np.float32) * 1.5
        np.testing.assert_array_equal(col.lookup(table), table[col.codes])
        assert col.lookup(table).dtype == np.float32
        first = col.first_rows()
        assert first.dtype == np.int64 and first[0] == len(col)  # "never"
        for code in range(1, 6):
            rows = np.flatnonzero(col.codes == code)
            assert first[code] == (rows[0] if len(rows) else len(col))
        from predictionio_tpu.data.storage.base import CodedColumn

        empty = CodedColumn(np.empty(0, np.int32), col.dictionary)
        assert empty.first_rows().tolist() == [0] * 6
        assert empty.lookup(table).shape == (0,) and list(empty) == []


class TestParquetRegressions:
    """Round-2 parquet bugs: null event ids, dedup-vs-filter order, channel 0."""

    @pytest.fixture
    def pq_store(self, tmp_path):
        from predictionio_tpu.data.storage.parquet_backend import (
            ParquetClient,
            ParquetEventStore,
            ParquetLEvents,
        )

        client = ParquetClient(tmp_path / "pq", n_shards=1)
        return ParquetEventStore(client), ParquetLEvents(client)

    def test_insert_without_id_generates_distinct_ids(self, pq_store):
        store, le = pq_store
        le.init(1)
        # identical entity/time events with no caller-supplied id must stay
        # distinct (the HBEventsUtil rowkey embeds a per-event UUID for this)
        ids = le.insert_batch([mk("view", "u1", 1), mk("view", "u1", 1)], 1)
        assert all(ids) and ids[0] != ids[1]
        assert len(list(le.find(1))) == 2
        assert le.get(ids[0], 1) is not None

    def test_legacy_null_id_rows_not_collapsed(self, pq_store):
        from predictionio_tpu.data.storage.parquet_backend import (
            _event_row,
            _write_segment,
        )

        store, le = pq_store
        le.init(1)
        # simulate legacy data: two distinct rows written with null ids into
        # the same shard/segment — dedup must not collapse them
        d = store.client.init(1, None)
        rows = [
            _event_row(mk("view", "u1", 1), 10, None),
            _event_row(mk("buy", "u1", 2), 10, None),
        ]
        _write_segment(d / "shard=0", rows, 10)
        assert sorted(e.event for e in le.find(1)) == ["buy", "view"]

    def test_upsert_hides_superseded_version_from_filter(self, pq_store):
        store, le = pq_store
        le.init(1)
        eid = le.insert(mk("view", "u1", 1), 1)
        # upsert: same id, latest version no longer matches event=="view"
        upd = Event(
            event="buy",
            entity_type="user",
            entity_id="u1",
            event_time=t(2),
            event_id=eid,
        )
        le.insert(upd, 1)
        # the superseded "view" row must not be resurrected by the filter
        assert list(le.find(1, filter=EventFilter(event_names=("view",)))) == []
        got = list(le.find(1, filter=EventFilter(event_names=("buy",))))
        assert len(got) == 1 and got[0].event_id == eid

    def test_channel_zero_distinct_from_default(self, pq_store):
        store, le = pq_store
        le.init(1)
        le.init(1, 0)
        le.insert(mk("view", "u1", 1), 1)
        le.insert(mk("buy", "u2", 1), 1, 0)
        assert [e.event for e in le.find(1)] == ["view"]
        assert [e.event for e in le.find(1, 0)] == ["buy"]


class TestMetadata:
    def test_apps(self, storage):
        apps = storage.apps()
        app_id = apps.insert(App(id=0, name="myapp", description="d"))
        assert app_id is not None
        assert apps.insert(App(id=0, name="myapp")) is None  # dup name
        assert apps.get(app_id).name == "myapp"
        assert apps.get_by_name("myapp").id == app_id
        assert len(apps.get_all()) == 1
        assert apps.delete(app_id)
        assert apps.get(app_id) is None

    def test_access_keys(self, storage):
        ak = storage.access_keys()
        key = ak.insert(AccessKey(key="", appid=3, events=("view", "buy")))
        assert key
        got = ak.get(key)
        assert got.appid == 3 and got.events == ("view", "buy")
        assert ak.get_by_appid(3)[0].key == key
        assert ak.delete(key)

    def test_channels(self, storage):
        ch = storage.channels()
        cid = ch.insert(Channel(id=0, name="live", appid=1))
        assert ch.get(cid).name == "live"
        assert ch.get_by_appid(1)[0].id == cid
        with pytest.raises(ValueError):
            Channel(id=0, name="bad name!", appid=1)
        with pytest.raises(ValueError):
            Channel(id=0, name="x" * 17, appid=1)

    def test_engine_instances(self, storage):
        ei = storage.engine_instances()
        inst = EngineInstance(
            id="abc",
            status="INIT",
            start_time=t(1),
            end_time=t(1),
            engine_id="e1",
            engine_version="v1",
            engine_variant="default",
            engine_factory="pkg:Factory",
        )
        ei.insert(inst)
        assert ei.get("abc").status == "INIT"
        ei.update(inst.completed())
        latest = ei.get_latest_completed("e1", "v1", "default")
        assert latest is not None and latest.status == "COMPLETED"

    def test_models_blob(self, storage):
        m = storage.models()
        m.insert("i1", b"\x00\x01binary")
        assert m.get("i1") == b"\x00\x01binary"
        assert m.delete("i1")
        assert m.get("i1") is None


class TestFacades:
    def test_store_facades(self, storage):
        app_id = storage.apps().insert(App(id=0, name="shop"))
        le = storage.l_events()
        le.init(app_id)
        le.insert(mk("rate", "u1", 1, target="i1", props={"rating": 5.0}), app_id)
        frame = PEventStore(storage).find("shop", event_names=["rate"])
        assert len(frame) == 1
        evs = list(
            LEventStore(storage).find_by_entity("shop", "user", "u1", limit=10)
        )
        assert len(evs) == 1
        with pytest.raises(AppNotFoundError):
            PEventStore(storage).find("nope")

    def test_find_takes_what_the_consumer_states(self, storage):
        """Every backend answers ``find(columns=, ordered=)`` with at least
        what was asked: the parquet store projects and skips the sort, the
        others hand back the full sorted frame they always did."""
        app_id = storage.apps().insert(App(id=0, name="shop"))
        le = storage.l_events()
        le.init(app_id)
        le.insert_batch(
            [mk("rate", f"u{j % 4}", 40 - j, target=f"i{j % 3}",
                props={"rating": float(j % 5)}) for j in range(24)],
            app_id,
        )
        store = PEventStore(storage)
        asked = ("entity_id", "target_entity_id", "properties")

        def rows(frame):
            return [
                (frame.entity_id[i], frame.target_entity_id[i], r)
                for i, r in enumerate(frame.property_column("rating"))
            ]

        want = store.find("shop", event_names=["rate"])
        assert (np.diff(want.event_time_ms) >= 0).all() and len(want) == 24
        got = store.find(
            "shop", event_names=["rate"], columns=asked, ordered=False)
        assert sorted(rows(got)) == sorted(rows(want))
        assert got.event.tolist() == ["rate"] * 24
        again = store.find(
            "shop", event_names=["rate"], columns=asked, ordered=False)
        assert rows(again) == rows(got)  # a repeatable order
        projected = store.find("shop", event_names=["rate"], columns=asked)
        assert rows(projected) == rows(want)  # ordered unless said otherwise

    def test_codes_are_an_offer_a_backend_may_decline(self, storage, request):
        """``EventFrame.coded`` (ISSUE 37): the parquet store hands its
        dictionary columns over as codes, every other backend answers
        ``None``, and the recommendation engine's read and prepare come to
        the same arrays whichever it was given."""
        from predictionio_tpu.core import EngineContext
        from predictionio_tpu.data.storage.base import CodedColumn, EventFrame
        from predictionio_tpu.models.recommendation.engine import (
            DataSourceParams,
            RatingsDataSource,
            RatingsPreparator,
        )
        from predictionio_tpu.obs.tracing import trace

        backend = request.node.callspec.params["storage"]
        app_id = storage.apps().insert(App(id=0, name="shop"))
        le = storage.l_events()
        le.init(app_id)
        events = [
            mk("rate", f"u{j % 4}", j, target=f"i{j % 3}",
               props={"rating": float(1 + j % 5)}) for j in range(24)
        ] + [
            mk("buy", "u9", 30, target="i1"),
            mk("rate", "nobody", 31, target="i-unrated", props={"stars": 2}),
        ]
        le.insert_batch(events, app_id)
        asked = ("entity_id", "target_entity_id", "properties")
        frame = PEventStore(storage).find(
            "shop", event_names=["rate", "buy"], columns=asked, ordered=False)
        offered = {
            c for c in EventFrame.CODABLE if frame.coded(c) is not None}
        if backend == "parquet":
            assert offered == {"event", *asked}
        else:
            assert offered == set()
        assert type(frame.entity_id) is np.ndarray  # whoever asked or not

        ctx = EngineContext(storage=storage)
        with trace("test.offer", ring=False) as root:
            td = RatingsDataSource(
                DataSourceParams(app_name="shop")).read_training(ctx)
            pd = RatingsPreparator().prepare(ctx, td)
        tags = {c.name: c.tags for c in root.children}
        took = backend == "parquet"
        assert tags["datasource.columns"]["path"] == (
            "codes" if took else "objects")
        assert (tags["prepare.vocab"]["path"] == "codes") == took
        assert isinstance(td.users, CodedColumn) == took
        # the same ratings under the same ids, whatever order the backend
        # read them in; "nobody" rated nothing and is in no vocabulary
        want = sorted(
            (e.entity_id, e.target_entity_id,
             float(e.properties.fields.get("rating", 4.0)))
            for e in events if e.entity_id != "nobody")
        assert sorted(zip(td.users, td.items, td.ratings.tolist())) == want
        assert list(pd.user_vocab) == list(dict.fromkeys(td.users))
        assert list(pd.item_vocab) == list(dict.fromkeys(td.items))
        assert "nobody" not in pd.user_vocab
        assert [pd.user_vocab.inverse(int(u)) for u in pd.user_idx] == (
            list(td.users))
        assert [pd.item_vocab.inverse(int(i)) for i in pd.item_idx] == (
            list(td.items))

    def test_localfs_models(self, tmp_path):
        from predictionio_tpu.data.storage.localfs_models import LocalFSModels

        m = LocalFSModels(tmp_path / "models")
        m.insert("xyz", b"blob")
        assert m.get("xyz") == b"blob"
        assert m.delete("xyz") and not m.delete("xyz")


class TestPostgresDialect:
    """Server-free conformance: every SQL statement the DAOs actually emit
    must translate to well-formed PostgreSQL.  Captures the live corpus by
    instrumenting SQLiteClient during a full DAO workout, then checks each
    translation — so a new DAO query that the regex rules miss fails here,
    not on the first real server."""

    @pytest.fixture()
    def sql_corpus(self, tmp_path, monkeypatch):
        from predictionio_tpu.data.storage import sqlite_backend as sb

        captured: list[str] = []
        orig_exec = sb.SQLiteClient.execute
        orig_many = sb.SQLiteClient.executemany
        orig_query = sb.SQLiteClient.query
        monkeypatch.setattr(
            sb.SQLiteClient, "execute",
            lambda self, sql, params=(): (captured.append(sql),
                                          orig_exec(self, sql, params))[1],
        )
        monkeypatch.setattr(
            sb.SQLiteClient, "executemany",
            lambda self, sql, rows: (captured.append(sql),
                                     orig_many(self, sql, rows))[1],
        )
        monkeypatch.setattr(
            sb.SQLiteClient, "query",
            lambda self, sql, params=(): (captured.append(sql),
                                          orig_query(self, sql, params))[1],
        )
        from predictionio_tpu.data.storage.config import (
            StorageConfig,
            reset_storage,
        )

        rt = reset_storage(
            StorageConfig.from_env({"PIO_HOME": str(tmp_path / "h")})
        )
        # full DAO workout: metadata CRUD, events, instances, models
        app_id = rt.apps().insert(App(id=0, name="dialect"))
        rt.apps().get(app_id); rt.apps().get_by_name("dialect")
        rt.apps().get_all()
        rt.access_keys().insert(AccessKey(key="k1", appid=app_id, events=()))
        rt.access_keys().get("k1"); rt.access_keys().get_by_appid(app_id)
        ch = rt.channels().insert(Channel(id=0, name="ch", appid=app_id))
        rt.channels().get_by_appid(app_id)
        le = rt.l_events()
        le.init(app_id)
        eid = le.insert(mk("rate", "u1", 1, target="i1",
                           props={"rating": 4.0}), app_id)
        le.insert_batch([mk("view", "u2", 2), mk("buy", "u3", 3)], app_id)
        le.get(eid, app_id)
        list(le.find(app_id, filter=EventFilter(
            event_names=("rate",), entity_type="user", entity_id="u1",
            start_time=t(0), until_time=t(9))))
        le.delete(eid, app_id)
        pe = rt.p_events()
        pe.find(app_id)
        inst = EngineInstance(id="inst1", status="INIT",
                              start_time=t(0), end_time=t(1),
                              engine_id="e", engine_version="1",
                              engine_variant="default", engine_factory="f")
        rt.engine_instances().insert(inst)
        rt.engine_instances().update(inst.completed())
        rt.engine_instances().get("inst1")
        rt.engine_instances().get_latest_completed("e", "1", "default")
        rt.models().insert("inst1", b"blob")
        rt.models().get("inst1"); rt.models().delete("inst1")
        le.remove(app_id)
        rt.channels().delete(ch)
        rt.apps().delete(app_id)
        rt.close()
        return captured

    def test_corpus_translates_clean(self, sql_corpus):
        from predictionio_tpu.data.storage.postgres_backend import _translate

        assert len(sql_corpus) > 25, "workout captured too few statements"
        for sql in set(sql_corpus):
            out = _translate(sql)
            up = out.upper()
            assert "?" not in out, f"untranslated placeholder: {out}"
            assert "INSERT OR REPLACE" not in up, out
            assert "INSERT OR IGNORE" not in up, out
            assert "AUTOINCREMENT" not in up, out
            # BLOB must be gone as a column type (word-boundary check)
            import re as _re

            assert not _re.search(r"\bBLOB\b", up), out
            if "ON CONFLICT" in up:
                # well-formed: conflict target column present + DO action
                assert _re.search(
                    r"ON CONFLICT \([\w]+\) DO (UPDATE SET|NOTHING)", out
                ), out
            if _re.match(r"\s*INSERT INTO pio_(apps|channels)\b", out,
                         _re.I):
                assert out.rstrip().endswith("RETURNING id"), out

    def test_cursor_shim_lastrowid(self):
        from predictionio_tpu.data.storage.postgres_backend import _Cursor

        class FakePG:
            description = [("id",)]

            def fetchone(self):
                return (42,)

        assert _Cursor(FakePG()).lastrowid == 42

        class FakeNoRows:
            description = None

        assert _Cursor(FakeNoRows()).lastrowid is None

    def test_upsert_conflict_targets_are_explicit(self):
        from predictionio_tpu.data.storage.postgres_backend import (
            _conflict_target,
            _translate,
        )

        assert _conflict_target("pio_models") == "id"
        assert _conflict_target("pio_event_3_7") == "id"
        with pytest.raises(ValueError, match="conflict target"):
            _conflict_target("pio_new_table")
        out = _translate(
            "INSERT OR REPLACE INTO pio_models (id, models) VALUES (?, ?)"
        )
        assert "ON CONFLICT (id) DO UPDATE SET models = EXCLUDED.models" in out


class TestPQDriver:
    """The ctypes-libpq binding, server-independent parts: placeholder
    rewriting and the text-protocol codecs.  (Live-server paths run through
    the shared ``storage`` fixture wherever a server exists.)"""

    def test_placeholders_to_dollar(self):
        from predictionio_tpu.data.storage.pq_driver import (
            placeholders_to_dollar,
        )

        assert (
            placeholders_to_dollar("INSERT INTO t (a, b) VALUES (%s, %s)")
            == "INSERT INTO t (a, b) VALUES ($1, $2)"
        )
        # literal %s inside a string stays untouched
        assert (
            placeholders_to_dollar("SELECT '%s' || a FROM t WHERE b = %s")
            == "SELECT '%s' || a FROM t WHERE b = $1"
        )
        assert placeholders_to_dollar("SELECT 1") == "SELECT 1"

    def test_param_encoding(self):
        from predictionio_tpu.data.storage.pq_driver import _encode_param

        assert _encode_param(None) == (None, 0)
        assert _encode_param(True) == (b"t", 0)
        assert _encode_param(False) == (b"f", 0)
        assert _encode_param(7) == (b"7", 0)
        assert _encode_param(2.5) == (b"2.5", 0)
        assert _encode_param("x") == (b"x", 0)
        assert _encode_param(b"\x00\xff") == (b"\x00\xff", 1)  # binary bytea

    def test_value_decoding(self):
        from predictionio_tpu.data.storage.pq_driver import _decode_value

        assert _decode_value(b"42", 20) == 42
        assert _decode_value(b"2.5", 701) == 2.5
        assert _decode_value(b"t", 16) is True
        assert _decode_value(b"f", 16) is False
        assert _decode_value(b"\\x00ff", 17) == b"\x00\xff"
        assert _decode_value(b"hello", 25) == "hello"

    def test_connect_refused_raises_cleanly(self):
        from predictionio_tpu.data.storage import pq_driver

        if not pq_driver.available():
            pytest.skip("libpq not present on this host")
        with pytest.raises(pq_driver.PQError, match="connection failed"):
            pq_driver.connect(
                "postgresql://nobody@127.0.0.1:1/nosuchdb"
                "?connect_timeout=2"
            )
