"""Storage DAO contracts and metadata entities.

The reference defines DAO traits LEvents (data/.../storage/LEvents.scala:40),
PEvents (PEvents.scala:38) and metadata DAOs Apps/AccessKeys/Channels/
EngineInstances/EvaluationInstances/Models.  This module is their TPU-native
contract: the "P" side does not return RDDs but **EventFrame** — a columnar
numpy batch that stages directly into ``jax.device_put`` — which is the
framework's Spark-replacement seam.
"""

from __future__ import annotations

import abc
import functools
import hashlib
import inspect
import json
from dataclasses import dataclass, field, replace
from datetime import datetime, timezone
from typing import Any, Iterable, Iterator, Mapping, Sequence

import numpy as np

from predictionio_tpu.data.aggregator import aggregate_properties
from predictionio_tpu.data.datamap import DataMap, PropertyMap
from predictionio_tpu.data.event import Event

# ---------------------------------------------------------------------------
# Metadata entities (data/.../storage/{Apps,AccessKeys,Channels,...}.scala)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class App:
    id: int
    name: str
    description: str | None = None


@dataclass(frozen=True)
class AccessKey:
    key: str
    appid: int
    events: tuple[str, ...] = ()  # empty = all events allowed


@dataclass(frozen=True)
class Channel:
    id: int
    name: str
    appid: int

    def __post_init__(self):
        if not channel_name_is_valid(self.name):
            raise ValueError(
                f"invalid channel name {self.name!r}: must be 1-16 chars of "
                "[a-zA-Z0-9-]"
            )


def channel_name_is_valid(name: str) -> bool:
    """Channel naming rule from the reference (Channels.scala: 1-16 word chars/hyphen)."""
    if not 1 <= len(name) <= 16:
        return False
    return all(c.isalnum() or c == "-" for c in name)


@dataclass(frozen=True)
class EngineInstance:
    """Record of one training run — the deploy/resume handle.

    Mirrors EngineInstances.scala:46: every parameter that produced the model
    is frozen into this row as JSON.
    """

    id: str
    status: str  # INIT | TRAINING | COMPLETED | FAILED
    start_time: datetime
    end_time: datetime
    engine_id: str
    engine_version: str
    engine_variant: str
    engine_factory: str
    batch: str = ""
    env: dict[str, str] = field(default_factory=dict)
    mesh_conf: dict[str, Any] = field(default_factory=dict)  # sparkConf analog
    datasource_params: str = "{}"
    preparator_params: str = "{}"
    algorithms_params: str = "[]"
    serving_params: str = "{}"

    def completed(self) -> "EngineInstance":
        return replace(
            self, status="COMPLETED", end_time=datetime.now(tz=timezone.utc)
        )


@dataclass(frozen=True)
class EvaluationInstance:
    """Record of one evaluation run (EvaluationInstances.scala:42)."""

    id: str
    status: str  # INIT | EVALUATING | EVALCOMPLETED | FAILED
    start_time: datetime
    end_time: datetime
    evaluation_class: str = ""
    engine_params_generator_class: str = ""
    batch: str = ""
    env: dict[str, str] = field(default_factory=dict)
    evaluator_results: str = ""  # one-liner
    evaluator_results_html: str = ""
    evaluator_results_json: str = ""


class Apps(abc.ABC):
    @abc.abstractmethod
    def insert(self, app: App) -> int | None: ...

    @abc.abstractmethod
    def get(self, app_id: int) -> App | None: ...

    @abc.abstractmethod
    def get_by_name(self, name: str) -> App | None: ...

    @abc.abstractmethod
    def get_all(self) -> list[App]: ...

    @abc.abstractmethod
    def update(self, app: App) -> bool: ...

    @abc.abstractmethod
    def delete(self, app_id: int) -> bool: ...


class AccessKeys(abc.ABC):
    @abc.abstractmethod
    def insert(self, k: AccessKey) -> str | None: ...

    @abc.abstractmethod
    def get(self, key: str) -> AccessKey | None: ...

    @abc.abstractmethod
    def get_by_appid(self, appid: int) -> list[AccessKey]: ...

    @abc.abstractmethod
    def get_all(self) -> list[AccessKey]: ...

    @abc.abstractmethod
    def update(self, k: AccessKey) -> bool: ...

    @abc.abstractmethod
    def delete(self, key: str) -> bool: ...


class Channels(abc.ABC):
    @abc.abstractmethod
    def insert(self, channel: Channel) -> int | None: ...

    @abc.abstractmethod
    def get(self, channel_id: int) -> Channel | None: ...

    @abc.abstractmethod
    def get_by_appid(self, appid: int) -> list[Channel]: ...

    @abc.abstractmethod
    def delete(self, channel_id: int) -> bool: ...


class EngineInstances(abc.ABC):
    @abc.abstractmethod
    def insert(self, i: EngineInstance) -> str: ...

    @abc.abstractmethod
    def get(self, instance_id: str) -> EngineInstance | None: ...

    @abc.abstractmethod
    def get_all(self) -> list[EngineInstance]: ...

    @abc.abstractmethod
    def get_latest_completed(
        self, engine_id: str, engine_version: str, engine_variant: str
    ) -> EngineInstance | None: ...

    @abc.abstractmethod
    def get_completed(
        self, engine_id: str, engine_version: str, engine_variant: str
    ) -> list[EngineInstance]: ...

    @abc.abstractmethod
    def update(self, i: EngineInstance) -> bool: ...

    @abc.abstractmethod
    def delete(self, instance_id: str) -> bool: ...


class EvaluationInstances(abc.ABC):
    @abc.abstractmethod
    def insert(self, i: EvaluationInstance) -> str: ...

    @abc.abstractmethod
    def get(self, instance_id: str) -> EvaluationInstance | None: ...

    @abc.abstractmethod
    def get_all(self) -> list[EvaluationInstance]: ...

    @abc.abstractmethod
    def get_completed(self) -> list[EvaluationInstance]: ...

    @abc.abstractmethod
    def update(self, i: EvaluationInstance) -> bool: ...

    @abc.abstractmethod
    def delete(self, instance_id: str) -> bool: ...


class Models(abc.ABC):
    """Model blob store keyed by engine-instance id (Models.scala:33).

    Besides the single-blob contract, every backend supports a *multipart*
    checkpoint layout (manifest + named parts, used for sharded model saves
    — the HDFS/S3 role of storing big models outside one row,
    storage/s3/.../S3Models.scala:36).  The default implementation maps each
    part onto an ordinary keyed blob (``<id>:part:<name>``) with the
    manifest written last as the commit point, so any insert/get/delete
    backend gets multipart for free; backends with a cheaper native layout
    (e.g. one object per part on S3) may override.
    """

    @abc.abstractmethod
    def insert(self, instance_id: str, blob: bytes) -> None: ...

    @abc.abstractmethod
    def get(self, instance_id: str) -> bytes | None: ...

    @abc.abstractmethod
    def delete(self, instance_id: str) -> bool: ...

    # -- multipart (sharded checkpoints) -------------------------------------
    def insert_parts(
        self, instance_id: str, manifest: bytes, parts: Mapping[str, bytes]
    ) -> None:
        self._drop_checkpoint_for_resave(instance_id)
        for name, blob in parts.items():
            self.insert(f"{instance_id}:part:{name}", blob)
        # manifest last: readers treat its presence as "all parts written"
        self.insert(f"{instance_id}:manifest", _manifest_blob(manifest, parts))

    def _drop_checkpoint_for_resave(self, instance_id: str) -> None:
        # Instance ids are write-once in normal operation (run_train mints a
        # fresh id per training run).  Re-saving an existing id is still made
        # safe: drop the old manifest FIRST so concurrent readers see
        # "absent" rather than pairing the old part list with new bytes,
        # then remove the old parts so a re-save with fewer parts cannot
        # leak orphaned blobs.
        old = self.get(f"{instance_id}:manifest")
        if old is not None:
            self.delete(f"{instance_id}:manifest")
            for name in _manifest_part_names(old):
                self.delete(f"{instance_id}:part:{name}")

    def get_manifest(self, instance_id: str) -> bytes | None:
        raw = self.get(f"{instance_id}:manifest")
        return None if raw is None else _manifest_payload(raw)

    def get_part(self, instance_id: str, name: str) -> bytes | None:
        return self.get(f"{instance_id}:part:{name}")

    def delete_parts(self, instance_id: str) -> bool:
        raw = self.get(f"{instance_id}:manifest")
        if raw is None:
            return False
        for name in _manifest_part_names(raw):
            self.delete(f"{instance_id}:part:{name}")
        return self.delete(f"{instance_id}:manifest")

    def delete_models(self, instance_id: str) -> bool:
        """Remove a checkpoint in either layout (sharded parts and/or the
        legacy single blob) — the deletion entry point for cleanup paths."""
        had_parts = self.delete_parts(instance_id)
        had_blob = self.delete(instance_id)
        return had_parts or had_blob


def _manifest_blob(manifest: bytes, parts: Mapping[str, bytes]) -> bytes:
    """Frame the part-name list in front of the manifest payload so
    delete_parts can enumerate parts without deserializing models."""
    names = ",".join(sorted(parts)).encode()
    return len(names).to_bytes(4, "big") + names + manifest


def _manifest_payload(raw: bytes) -> bytes:
    n = int.from_bytes(raw[:4], "big")
    return raw[4 + n:]


def _manifest_part_names(raw: bytes) -> list[str]:
    n = int.from_bytes(raw[:4], "big")
    names = raw[4 : 4 + n].decode()
    return names.split(",") if names else []


def run_concurrent(executor, thunks: Sequence) -> list:
    """Run thunks on the executor and join them ALL, then surface the
    first error — the fan-out idiom shared by the parquet backend's
    per-shard segment writes and the remote fleet's per-daemon calls
    (joining everything first keeps partial failures from orphaning
    in-flight writes)."""
    if len(thunks) == 1:
        return [thunks[0]()]
    futs = [executor.submit(t) for t in thunks]
    out, errs = [], []
    for f in futs:
        try:
            out.append(f.result())
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errs.append(e)
    if errs:
        raise errs[0]
    return out


def obj_ptrs(col: np.ndarray) -> np.ndarray | None:
    """int64 view of an object array's PyObject pointers (read-only; the
    caller must keep ``col`` alive while using the view).

    Pointer equality implies value equality, so a pointer-level
    factorization is a *conservative* dictionary encode: bulk columns are
    built as ``vocabulary[codes]`` (one Python object per unique value,
    broadcast), and hashing 8-byte pointers is ~10x cheaper than hashing
    the strings/dicts they point to.  Distinct-but-equal objects merely
    split a dictionary entry — never wrong, just less compact."""
    if col.dtype != object or col.itemsize != 8 or len(col) == 0:
        return None
    import ctypes

    buf = (ctypes.c_char * (len(col) * col.itemsize)).from_address(
        col.ctypes.data
    )
    return np.frombuffer(buf, dtype=np.int64)


def ptr_factorize(
    col: np.ndarray, max_card_frac: float = 0.25
) -> tuple[np.ndarray, np.ndarray] | None:
    """(codes int64, unique objects) by pointer identity, or None when the
    column is mostly-distinct at the pointer level (note that
    ``np.full(n, "x", object)`` boxes n DISTINCT objects — constant
    columns built that way need a value-level pass)."""
    import pandas as pd

    col = np.ascontiguousarray(col)
    ptrs = obj_ptrs(col)
    if ptrs is None:
        return None
    codes, uniq_ptrs = pd.factorize(ptrs)
    n, k = len(col), len(uniq_ptrs)
    if k > max(int(n * max_card_frac), 64):
        return None
    # first-occurrence index per code: reversed scatter, last write wins
    first = np.empty(k, np.int64)
    first[codes[::-1]] = np.arange(n - 1, -1, -1)
    return codes, col[first]


#: rows a gather or scatter over a coded column handles at a time.  numpy
#: copies an int32 index array to intp before it uses it: a quarter of a
#: million rows of that stay in cache, 20 M rows are 160 MB of fresh pages,
#: which on a host without transparent huge pages cost more than the gather
_ROWS_AT_A_TIME = 1 << 18


class CodedColumn:
    """A column held as ``dictionary[codes]``: the store's own encoding of a
    repetitive column (20 M rows over 165 k ids, or over ten ``properties``
    documents), kept so that a consumer who wants the codes never pays for
    20 M object pointers.

    ``codes`` is int32, one a row, every one an index into ``dictionary``
    (an object array of the distinct values; a null row's code points at an
    entry that holds the null value, ``None``).  Entries need not have a row,
    and equal values may sit at two codes: a consumer that needs neither
    says so itself (``BiMap.factorize``).  Read-only by convention.

    To whoever does not care, it reads as the object array it stands for:
    ``len``, iteration, ``np.asarray``, ``==`` and an element by its row
    give what ``dictionary[codes]`` gives (made on first touch, then kept),
    and any other attribute is that array's.  Rows picked by a mask, a
    slice or an index array stay coded."""

    __slots__ = ("codes", "dictionary", "_objects")

    def __init__(self, codes: np.ndarray, dictionary: np.ndarray):
        self.codes = codes
        self.dictionary = dictionary
        self._objects = None

    def lookup(self, table: np.ndarray) -> np.ndarray:
        """``table[codes]`` for a table with a value an entry of the
        dictionary: what is true of an entry, said of every row."""
        out = np.empty(len(self.codes), table.dtype)
        for at in range(0, len(out), _ROWS_AT_A_TIME):
            rows = slice(at, at + _ROWS_AT_A_TIME)
            # "clip" only spares numpy a buffer: every code is an index
            np.take(table, self.codes[rows], out=out[rows], mode="clip")
        return out

    def first_rows(self) -> np.ndarray:
        """The row each entry is first seen at, ``len(self)`` for an entry
        no row uses.  Piece by piece from the top: only the rows whose entry
        no earlier piece had are scattered (in reverse, so that the last
        write is the first row), and nothing is read once every entry has
        a row."""
        n = len(self.codes)
        first = np.full(len(self.dictionary), n, np.int64)
        seen = np.zeros(len(first), bool)
        for at in range(0, n, _ROWS_AT_A_TIME):
            codes = self.codes[at:at + _ROWS_AT_A_TIME]
            new = np.flatnonzero(~seen.take(codes, mode="clip"))
            if len(new):
                codes = codes[new]
                first[codes[::-1]] = (at + new)[::-1]
                seen[codes] = True
                if seen.all():
                    break
        return first

    @property
    def objects(self) -> np.ndarray:
        """The object column, one pointer a row: ``dictionary[codes]``."""
        if self._objects is None:
            self._objects = self.lookup(self.dictionary)
        return self._objects

    def __len__(self) -> int:
        return len(self.codes)

    def __iter__(self):
        return iter(self.objects)

    def __array__(self, dtype=None, copy=None):
        return self.objects

    def __getitem__(self, sel):
        if isinstance(sel, (int, np.integer)):
            return self.dictionary[self.codes[sel]]
        return CodedColumn(self.codes[sel], self.dictionary)

    def __eq__(self, other):
        if not np.ndim(other):
            # K compares; then their answers row by row, if any was yes
            hit = self.dictionary == other
            return self.lookup(hit) if hit.any() else np.zeros(len(self), bool)
        return self.objects == np.asarray(other)

    def __ne__(self, other):
        return ~(self == other)

    __hash__ = None

    def __getattr__(self, name):
        if name.startswith("__"):
            raise AttributeError(name)
        return getattr(self.objects, name)


def entity_shard(entity_type: str, entity_id: str, n_shards: int) -> int:
    """The HBEventsUtil.scala:83 row-key hash, reduced to a shard index.
    Every backend's scan sharding (parquet layout, SQL entity-hash scans,
    the remote daemon's shard protocol) keys on this one function.  Lives
    here (not in the parquet module) so hash users never drag the pyarrow
    import in."""
    digest = hashlib.md5(f"{entity_type}-{entity_id}".encode()).digest()
    return int.from_bytes(digest[:4], "big") % n_shards


def frame_shard_of(
    entity_type_col: np.ndarray,
    entity_id_col: np.ndarray,
    n_shards: int,
    factorized: tuple[tuple, tuple] | None = None,
) -> np.ndarray:
    """Vectorized entity_shard over frame columns: md5 each UNIQUE
    (type, id) pair once (entities are ~100x fewer than events) and
    broadcast through hash-based pandas factorize codes — the one home of
    the pair-coding arithmetic every backend's scan splitting shares.

    ``factorized`` lets a caller that already factorized the columns
    (the parquet write path shares its arrow-conversion factorization)
    skip the two hash passes: ``((tcode, utypes), (icode, uids))``."""
    import pandas as pd

    if factorized is not None:
        (tcode, utypes), (icode, uids) = factorized
    else:
        tcode, utypes = pd.factorize(entity_type_col)
        icode, uids = pd.factorize(entity_id_col)
    inv, upairs = pd.factorize(
        tcode.astype(np.int64) * len(uids) + icode
    )
    utypes = np.asarray(utypes, object)
    uids = np.asarray(uids, object)
    shard_of_uniq = np.fromiter(
        (
            entity_shard(utypes[c // len(uids)], uids[c % len(uids)], n_shards)
            for c in upairs
        ),
        np.int64,
        len(upairs),
    )
    return shard_of_uniq[inv]


def _coerce_numeric(v) -> float | None:
    """The ``float(props[name])`` coercion contract of the row-wise engine
    loops: ints/floats pass, bools become 0/1, numeric strings parse;
    everything else is not-a-number (None)."""
    if isinstance(v, bool):
        return float(v)
    if isinstance(v, (int, float)):
        return float(v)
    if isinstance(v, str):
        try:
            return float(v)
        except ValueError:
            return None
    return None


# ---------------------------------------------------------------------------
# Event DAOs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class EventFilter:
    """The find() filter algebra shared by both DAO shapes.

    Mirrors LEvents.futureFind (LEvents.scala:188): time window
    [start_time, until_time), entity, event-name list, target entity, limit
    (None = all, reference used Some(-1) for all), reversed ordering.
    """

    start_time: datetime | None = None
    until_time: datetime | None = None
    entity_type: str | None = None
    entity_id: str | None = None
    event_names: tuple[str, ...] | None = None
    target_entity_type: str | None = None  # "" matches None-valued target
    target_entity_id: str | None = None
    limit: int | None = None
    reversed: bool = False

    def matches(self, e: Event) -> bool:
        if self.start_time is not None and e.event_time < self.start_time:
            return False
        if self.until_time is not None and e.event_time >= self.until_time:
            return False
        if self.entity_type is not None and e.entity_type != self.entity_type:
            return False
        if self.entity_id is not None and e.entity_id != self.entity_id:
            return False
        if self.event_names is not None and e.event not in self.event_names:
            return False
        if self.target_entity_type is not None:
            want = self.target_entity_type or None
            if e.target_entity_type != want:
                return False
        if self.target_entity_id is not None:
            want = self.target_entity_id or None
            if e.target_entity_id != want:
                return False
        return True


class LEvents(abc.ABC):
    """Row-at-a-time event CRUD + query, per (app_id, channel_id) namespace.

    The reference exposes scala-future methods with blocking wrappers
    (LEvents.scala:90-280); servers here wrap these sync methods in executors.
    """

    @abc.abstractmethod
    def init(self, app_id: int, channel_id: int | None = None) -> bool:
        """Create the namespace (table/keyspace) for an app/channel."""

    @abc.abstractmethod
    def remove(self, app_id: int, channel_id: int | None = None) -> bool:
        """Drop all events of an app/channel."""

    @abc.abstractmethod
    def close(self) -> None: ...

    @abc.abstractmethod
    def insert(self, event: Event, app_id: int, channel_id: int | None = None) -> str:
        """Insert one event, returning its id.

        An event carrying an existing ``event_id`` upserts that row
        (implementations must replace, not duplicate) — the self-cleaning
        compaction path relies on this.
        """

    def insert_batch(
        self, events: Sequence[Event], app_id: int, channel_id: int | None = None
    ) -> list[str]:
        return [self.insert(e, app_id, channel_id) for e in events]

    @abc.abstractmethod
    def get(
        self, event_id: str, app_id: int, channel_id: int | None = None
    ) -> Event | None: ...

    @abc.abstractmethod
    def delete(
        self, event_id: str, app_id: int, channel_id: int | None = None
    ) -> bool: ...

    @abc.abstractmethod
    def find(
        self,
        app_id: int,
        channel_id: int | None = None,
        filter: EventFilter | None = None,
    ) -> Iterator[Event]: ...

    def find_by_entity(
        self,
        app_id: int,
        entity_type: str,
        entity_id: str,
        channel_id: int | None = None,
        event_names: Sequence[str] | None = None,
        target_entity_type: str | None = None,
        target_entity_id: str | None = None,
        start_time: datetime | None = None,
        until_time: datetime | None = None,
        limit: int | None = None,
        reversed: bool = False,
    ) -> Iterator[Event]:
        """Per-entity history — the serving-path access pattern (sequence
        models, business rules).  The default delegates to ``find`` with
        an entity-pinned filter; backends with a cheaper point-read path
        (parquet segment/row-group skipping) override."""
        return self.find(
            app_id,
            channel_id,
            EventFilter(
                start_time=start_time,
                until_time=until_time,
                entity_type=entity_type,
                entity_id=entity_id,
                event_names=tuple(event_names) if event_names else None,
                target_entity_type=target_entity_type,
                target_entity_id=target_entity_id,
                limit=limit,
                reversed=reversed,
            ),
        )

    def aggregate_properties(
        self,
        app_id: int,
        entity_type: str,
        channel_id: int | None = None,
        start_time: datetime | None = None,
        until_time: datetime | None = None,
        required: Sequence[str] | None = None,
    ) -> dict[str, PropertyMap]:
        """Fold $set/$unset/$delete into per-entity property maps
        (LEvents.futureAggregateProperties, LEvents.scala:215)."""
        if not entity_type:
            raise ValueError("aggregate_properties requires a non-empty entity_type")
        events = self.find(
            app_id,
            channel_id,
            EventFilter(
                start_time=start_time,
                until_time=until_time,
                entity_type=entity_type,
                event_names=("$set", "$unset", "$delete"),
            ),
        )
        result = aggregate_properties(events)
        if required:
            req = set(required)
            result = {
                k: v for k, v in result.items() if req.issubset(v.keyset())
            }
        return result


# ---------------------------------------------------------------------------
# EventFrame: the columnar bulk-scan result (the PEvents role)
# ---------------------------------------------------------------------------

_EPOCH = datetime.fromtimestamp(0, tz=timezone.utc)


def _to_ms(dt: datetime) -> int:
    return int(dt.timestamp() * 1000)


@dataclass
class EventFrame:
    """A columnar batch of events: numpy arrays ready for host staging.

    This replaces the reference's ``RDD[Event]`` (PEvents.find, PEvents.scala:80).
    String columns are object arrays (vocab-mapped to index arrays via BiMap
    before device_put); ``event_time_ms`` is int64 epoch millis; a row of
    ``properties`` is a dict or, from a bulk scan, the LAZY serialized JSON
    document (``""`` = empty; see the field).  Use ``property_column`` to
    pull one numeric property into a float array without materializing
    Events.

    **Coded columns.**  A builder that holds a string column or
    ``properties`` as dictionary codes (the parquet scan) may hand it over
    as a ``CodedColumn`` in place of the array.  Every reader of
    ``frame.entity_id`` still gets the object array, the one
    ``dictionary[codes]`` gives, made on first touch and kept; ``take`` and
    ``select`` carry the codes along.  ``coded(name)`` is the offer to a
    consumer that wants the codes themselves, and ``None`` is the answer of
    every frame built from arrays (sqlite / Postgres, remote, fan-out,
    ``from_events``, ``concat_frames``) and of a column assigned after the
    frame was built.  No argument chooses it: a consumer asks, and takes
    the object column where the answer is ``None``.
    """

    #: the columns a builder may hand over coded (not the optional ones: an
    #: unset attribute of theirs would read as the class-level ``None``)
    CODABLE = (
        "event", "entity_type", "entity_id", "target_entity_type",
        "target_entity_id", "properties",
    )

    event: np.ndarray  # object[str]
    entity_type: np.ndarray  # object[str]
    entity_id: np.ndarray  # object[str]
    target_entity_type: np.ndarray  # object[str|None]
    target_entity_id: np.ndarray  # object[str|None]
    event_time_ms: np.ndarray  # int64
    #: object[dict | str] — a str entry is a LAZY row: the serialized JSON
    #: document ("" = empty), left undecoded by bulk scans so 20M-row reads
    #: don't pay 20M json.loads for properties they may never touch.
    #: ``property_column`` parses columnar at C speed; ``to_events``
    #: decodes row-wise; storage writers pass str rows through verbatim.
    properties: np.ndarray  # object[dict | str]
    # Identity/bookkeeping columns: kept so find() -> write() round-trips are
    # lossless and idempotent (ids preserved). None when synthesized.
    event_id: np.ndarray | None = None  # object[str|None]
    tags: np.ndarray | None = None  # object[tuple[str,...]]
    pr_id: np.ndarray | None = None  # object[str|None]
    creation_time_ms: np.ndarray | None = None  # int64

    def __post_init__(self):
        # coded columns go aside and leave their attribute unset, so that the
        # first read of it lands in __getattr__ and makes the object column
        coded = {
            name: self.__dict__.pop(name)
            for name in self.CODABLE
            if isinstance(self.__dict__[name], CodedColumn)
        }
        self.__dict__["_coded"] = coded

    def __getattr__(self, name):
        col = self.__dict__.get("_coded", {}).get(name)
        if col is None:
            raise AttributeError(name)
        self.__dict__[name] = out = col.objects
        return out

    def __setattr__(self, name, value):
        # a column assigned from outside is not the store's any more
        self.__dict__.get("_coded", {}).pop(name, None)
        object.__setattr__(self, name, value)

    def coded(self, name: str) -> CodedColumn | None:
        """Column ``name`` as the builder's ``(codes, dictionary)``, or None
        where this frame holds it as an array only.  An offer: whoever does
        not ask reads ``frame.<name>`` as ever."""
        return self._coded.get(name)

    def column(self, name: str) -> "np.ndarray | CodedColumn | None":
        """Column ``name`` as the frame holds it: coded where the builder
        left it so, else the array.  For a consumer that masks, compares or
        hands it to ``BiMap.factorize``, which take either."""
        col = self._coded.get(name)
        return getattr(self, name) if col is None else col

    def __len__(self) -> int:
        return len(self.column("event"))

    def take(self, sel) -> "EventFrame":
        """Row subset by boolean mask or index array (all columns; a coded
        column stays coded)."""
        import dataclasses

        def rows(name):
            v = self.column(name)
            return v[sel] if v is not None else None

        return EventFrame(
            **{f.name: rows(f.name) for f in dataclasses.fields(self)}
        )

    @classmethod
    def from_events(cls, events: Iterable[Event]) -> "EventFrame":
        evs = list(events)
        n = len(evs)

        def col(f, dtype=object):
            a = np.empty(n, dtype=dtype)
            for i, e in enumerate(evs):
                a[i] = f(e)
            return a

        return cls(
            event=col(lambda e: e.event),
            entity_type=col(lambda e: e.entity_type),
            entity_id=col(lambda e: e.entity_id),
            target_entity_type=col(lambda e: e.target_entity_type),
            target_entity_id=col(lambda e: e.target_entity_id),
            event_time_ms=np.fromiter(
                (_to_ms(e.event_time) for e in evs), dtype=np.int64, count=n
            ),
            properties=col(lambda e: e.properties.fields),
            event_id=col(lambda e: e.event_id),
            tags=col(lambda e: e.tags),
            pr_id=col(lambda e: e.pr_id),
            creation_time_ms=np.fromiter(
                (_to_ms(e.creation_time) for e in evs), dtype=np.int64, count=n
            ),
        )

    def select(self, mask: np.ndarray) -> "EventFrame":
        return self.take(mask)

    def where_event(self, *names: str) -> "EventFrame":
        return self.take(np.isin(self.event, list(names)))

    def property_column(
        self, name: str, default: float = np.nan, dtype=np.float32
    ) -> np.ndarray:
        """One numeric property as a float column.  Numeric JSON strings
        ("4.5") and bools coerce the way the row-wise engine loops always
        did via ``float(props[name])`` — stored event data keeps training
        identically whichever path reads it."""
        # repetitive frames (dictionary-decoded scans, vocabulary-broadcast
        # ingest) collapse under pointer identity: parse/coerce each UNIQUE
        # document once and broadcast — a 20M-row rating column is ~20
        # distinct JSON documents
        # ... and a frame that kept the store's codes needs no pass over
        # pointers to find them
        col = self.coded("properties")
        if col is None:
            f = ptr_factorize(self.properties)
            col = None if f is None else CodedColumn(*f)
        if col is not None:
            vals = np.empty(len(col.dictionary), dtype)
            for j, p in enumerate(col.dictionary):
                v = self._row_value(p, name)
                vals[j] = default if v is None else v
            return col.lookup(vals)
        # branch on row kind (a cheap isinstance sweep) so a lazy row late
        # in a mostly-dict frame doesn't waste a full eager fill
        if any(isinstance(p, str) for p in self.properties):
            return self._lazy_property_column(name, default, dtype)
        out = np.full(len(self), default, dtype=dtype)
        for i, p in enumerate(self.properties):
            v = _coerce_numeric(p.get(name) if p else None)
            if v is not None:
                out[i] = v
        return out

    def _lazy_property_column(self, name: str, default, dtype) -> np.ndarray:
        """Columnar numeric extraction over lazy (raw-JSON) rows: join all
        rows into one NDJSON buffer and let pyarrow's C JSON reader parse
        it — ~20x the throughput of per-row json.loads at 20M rows.  Any
        malformed input (junk lazy rows, un-serializable dict values,
        row-count drift from embedded newlines) degrades to the exact
        row-wise semantics instead of crashing the scan."""
        import io

        import pyarrow as pa
        import pyarrow.json as pj

        out = np.full(len(self), default, dtype=dtype)
        try:
            rows = [
                p if isinstance(p, str) and p
                else (json.dumps(p) if p else "{}")
                for p in self.properties
            ]
            table = pj.read_json(
                io.BytesIO(("\n".join(rows) + "\n").encode("utf-8")),
                parse_options=pj.ParseOptions(newlines_in_values=False),
            )
            if table.num_rows != len(self):
                raise ValueError(
                    "NDJSON row drift (embedded newline in a lazy row?)"
                )
            if name not in table.column_names:
                return out
            col = table.column(name)
            if pa.types.is_integer(col.type) or pa.types.is_floating(col.type):
                vals = col.to_numpy(zero_copy_only=False).astype(np.float64)
            elif pa.types.is_boolean(col.type) or pa.types.is_string(
                col.type
            ) or pa.types.is_large_string(col.type):
                # mixed/typed-as-string columns: per-value coercion keeps
                # "4.5"/true rows training like the old float(props[name])
                raw = col.to_pylist()
                vals = np.fromiter(
                    (
                        v if (v := _coerce_numeric(r)) is not None else np.nan
                        for r in raw
                    ),
                    np.float64,
                    len(raw),
                )
            else:  # objects/lists don't count as numeric properties
                return out
        except (pa.ArrowException, ValueError, TypeError):
            return self._rowwise_property_column(name, out)
        mask = ~np.isnan(vals)
        out[mask] = vals[mask].astype(dtype)
        return out

    @staticmethod
    def _row_value(p, name: str) -> float | None:
        """One row's coerced property value (None = absent/malformed) —
        the exact semantics of the row-wise loop, applied per UNIQUE
        document by the pointer fast path."""
        if isinstance(p, str):
            if not p:
                return None
            try:
                d = json.loads(p)
            except json.JSONDecodeError:
                return None  # junk row -> no properties
        else:
            d = p
        return _coerce_numeric(d.get(name) if isinstance(d, dict) else None)

    def _rowwise_property_column(self, name: str, out: np.ndarray) -> np.ndarray:
        """Exact per-row semantics; malformed lazy rows count as empty."""
        for i, p in enumerate(self.properties):
            if isinstance(p, str):
                if not p:
                    continue
                try:
                    d = json.loads(p)
                except json.JSONDecodeError:
                    continue  # junk row -> no properties
            else:
                d = p
            v = _coerce_numeric(d.get(name) if isinstance(d, dict) else None)
            if v is not None:
                out[i] = v
        return out

    def to_events(self) -> list[Event]:
        out = []
        for i in range(len(self)):
            kwargs = {}
            if self.event_id is not None:
                kwargs["event_id"] = self.event_id[i]
            if self.tags is not None and self.tags[i]:
                kwargs["tags"] = tuple(self.tags[i])
            if self.pr_id is not None:
                kwargs["pr_id"] = self.pr_id[i]
            if self.creation_time_ms is not None:
                kwargs["creation_time"] = datetime.fromtimestamp(
                    self.creation_time_ms[i] / 1000.0, tz=timezone.utc
                )
            props = self.properties[i]
            if isinstance(props, str):  # lazy raw-JSON row
                props = json.loads(props) if props else {}
            out.append(
                Event(
                    event=self.event[i],
                    entity_type=self.entity_type[i],
                    entity_id=self.entity_id[i],
                    target_entity_type=self.target_entity_type[i],
                    target_entity_id=self.target_entity_id[i],
                    properties=DataMap(props or {}),
                    event_time=datetime.fromtimestamp(
                        self.event_time_ms[i] / 1000.0, tz=timezone.utc
                    ),
                    **kwargs,
                )
            )
        return out


def concat_frames(frames: Sequence["EventFrame"]) -> "EventFrame":
    """Row-wise concatenation of EventFrames (all columns).  An optional
    column is kept only when every input carries it — mixing frames from
    different backends would otherwise fabricate ids/tags for some rows."""
    frames = [f for f in frames if len(f)]
    if not frames:
        return EventFrame.from_events([])
    if len(frames) == 1:
        return frames[0]
    import dataclasses

    cols = {}
    for fld in dataclasses.fields(EventFrame):
        vals = [getattr(f, fld.name) for f in frames]
        cols[fld.name] = (
            np.concatenate(vals) if all(v is not None for v in vals) else None
        )
    return EventFrame(**cols)


class PEvents(abc.ABC):
    """Bulk columnar event access — the Spark-side DAO role, TPU-native.

    ``find`` yields one EventFrame per shard so multi-host workers can each
    scan an entity-hash range (the HBase row-key idea, HBEventsUtil.scala:83).
    """

    def n_shards(self, app_id: int, channel_id: int | None = None) -> int:
        """Entity-hash scan-shard count of this app's layout (1 =
        unsharded).  Part of the contract so shard-addressed consumers
        (the storage daemon's /shards route, multi-process trainers) never
        reach into backend internals for it."""
        return 1

    def __init_subclass__(cls, **kwargs):
        """A backend whose ``find`` takes only the filter gets the two
        consumer arguments here, ignored: the full, time-sorted frame it
        returns satisfies any projection and any order."""
        super().__init_subclass__(**kwargs)
        find = cls.__dict__.get("find")
        if find is None or "ordered" in inspect.signature(find).parameters:
            return

        @functools.wraps(find)
        def full_sorted(
            self, app_id, channel_id=None, filter=None, columns=None,
            ordered=True,
        ):
            return find(self, app_id, channel_id, filter)

        cls.find = full_sorted

    @abc.abstractmethod
    def find(
        self,
        app_id: int,
        channel_id: int | None = None,
        filter: EventFilter | None = None,
        columns: Sequence[str] | None = None,
        ordered: bool = True,
    ) -> EventFrame:
        """Every event the filter admits, as one frame.

        The last two arguments describe the CONSUMER, and a backend may
        ignore either: ``columns`` names the EventFrame columns it will
        read (None = all; ``event`` always comes), ``ordered=False`` says
        it does not need the rows by ``event_time_ms``.  A backend may
        return more than was asked (extra columns, sorted rows), never
        less: a column not asked for may be None, and unordered rows still
        come in an order that two reads of one unchanged store repeat.  A
        filter with ``limit`` or ``reversed`` is answered in time order
        whatever ``ordered`` says.  The defaults return what ``find``
        always returned.

        A backend that holds a string column or ``properties`` as
        dictionary codes may hand it to the frame as a ``CodedColumn``
        in place of the object array (``EventFrame``, "Coded columns").
        That too is the backend's to decide and an offer the consumer may
        ignore: ``frame.<column>`` reads as the array either way,
        ``frame.coded(<column>)`` is ``None`` where there are no codes,
        and nothing the caller passes here asks for them."""

    @abc.abstractmethod
    def write(
        self, frame: EventFrame, app_id: int, channel_id: int | None = None
    ) -> None: ...

    @abc.abstractmethod
    def delete(
        self, event_ids: Sequence[str], app_id: int, channel_id: int | None = None
    ) -> None: ...

    def aggregate_properties(
        self,
        app_id: int,
        entity_type: str,
        channel_id: int | None = None,
        start_time: datetime | None = None,
        until_time: datetime | None = None,
        required: Sequence[str] | None = None,
    ) -> dict[str, PropertyMap]:
        if not entity_type:
            raise ValueError("aggregate_properties requires a non-empty entity_type")
        frame = self.find(
            app_id,
            channel_id,
            EventFilter(
                start_time=start_time,
                until_time=until_time,
                entity_type=entity_type,
                event_names=("$set", "$unset", "$delete"),
            ),
        )
        result = aggregate_properties(frame.to_events())
        if required:
            req = set(required)
            result = {k: v for k, v in result.items() if req.issubset(v.keyset())}
        return result
