"""Model serialization: pytree-aware blobs for the Models store.

The reference Kryo-serializes the whole Seq[model] into the MODELDATA
repository (workflow/CoreWorkflow.scala:76-81).  Here models are arbitrary
Python objects whose array leaves may be jax device arrays: every jax array
is pulled to host numpy (device_get) before its bytes are made, so checkpoint
contents never depend on device topology.

Large array leaves (NCF embedding tables, ALS factor matrices) do not
round-trip through one monolithic pickle: ``serialize_models_sharded`` spills
every numpy leaf over ``PART_THRESHOLD`` bytes into its own named part
(raw ``.npy`` bytes) via the pickle ``persistent_id`` hook, leaving a small
manifest blob that references them.  Parts are stored as individual keyed
blobs in any Models backend (localfs/sqlite/s3) — see
``data/storage/base.Models.insert_parts`` — so a multi-gigabyte table is
written and read leaf-by-leaf, and a deploy host streams parts instead of
materializing blob + pickle + arrays three times over.  The writer streams
too: ``serialize_models_sharded`` hands back the parts as a mapping
(``LazyParts``) that makes a part's ``.npy`` bytes when it is asked for them
and writes a part straight into an open file when it is handed one.  A store
that asks for bytes (the base ``insert_parts``: sqlite, S3, HDFS, remote)
holds the arrays and ONE part's buffer and bytes at a time; the local
filesystem store hands over files and holds the arrays alone (measured in the
sandbox at 3.0 GB of float32 leaves, the largest 770 MB: host peak over the
arrays +1.52 GB through the bytes, +0.001 GB through the files; PERF.md,
PR 31.  Before ``LazyParts`` every part's bytes were held until the last was
written: +3.24 GB at 3.2 GB; PERF.md, PR 26).

A ``jax.Array`` leaf of part size leaves the device inside the write, not
before it: it becomes a part as it is, and ``LazyParts.write_part`` fetches it
when a store's writer asks for that part, in pieces of ``FETCH_PIECE_BYTES``
(span ``persist.fetch`` around each wait), so the device's copies run under
the writers' disk writes and the host holds a few pieces of the model, never
all of it (PERF.md, PR 41).
"""

from __future__ import annotations

import functools
import io
import math
import pickle
import threading
from collections.abc import Mapping
from typing import Any, BinaryIO, Callable, Iterable, Iterator

import jax
import numpy as np

from predictionio_tpu.obs.tracing import current_span, trace

#: leaves at or above this many bytes become standalone parts
PART_THRESHOLD = 1 << 20

#: a device leaf larger than this leaves the device in pieces of about this
#: size, each fetched, written and let go.  What sets the size is the host's
#: memory, not the device: a copy into a NEW buffer pays a page fault for every
#: 4 KiB of it, beside writers that are taking new page-cache pages for the
#: same bytes, and the allocator hands a freed buffer back to the next request
#: (instead of unmapping it) only up to 32 MiB.  Read on the chip's host, the
#: Olmo block's 3.2 GB in 33 parts: fetched whole 1.9-2.0 GB/s alone and
#: 3.5-3.7 s fetched under the write, in pieces of 128 MiB the same, in pieces
#: of 32 MiB 4.3 GB/s alone and 2.8 s (PERF.md section 6, PR 41)
FETCH_PIECE_BYTES = 16 << 20

#: pieces of the part a writer is on whose copies run while it writes one
FETCH_PIECES_AHEAD = 3


def _to_host(obj: Any, part_threshold: int | None = None) -> Any:
    """Map jax arrays to numpy throughout an arbitrary pytree-ish object;
    those of ``part_threshold`` bytes or more stay where they are (they
    become parts, fetched when they are written)."""
    def pull(x: Any) -> Any:
        if not isinstance(x, jax.Array):
            return x
        if part_threshold is not None and x.nbytes >= part_threshold:
            return x
        return np.asarray(jax.device_get(x))

    return jax.tree_util.tree_map(
        pull, obj, is_leaf=lambda x: isinstance(x, jax.Array)
    )


class _ShardingPickler(pickle.Pickler):
    """Pickler that spills big array leaves (host or device) into a side
    table of parts.

    ``persistent_id`` sees every object in the graph, registered pytree or
    not — dataclasses, dicts, BiMaps — so any reachable large array is
    sharded without cooperation from the containing type.
    """

    def __init__(self, buf: io.BytesIO, threshold: int):
        super().__init__(buf, protocol=pickle.HIGHEST_PROTOCOL)
        #: part name -> the array it holds (its bytes are made on demand)
        self.leaves: dict[str, np.ndarray | jax.Array] = {}
        self.threshold = threshold
        # persistent_id runs before pickle's own memoization, so aliased
        # arrays (one table referenced from two fields) must be deduped here
        # or they double both checkpoint size and deploy-host RAM; the
        # arrays in ``leaves`` pin their id() for the dump's life
        self._seen: dict[int, str] = {}

    def persistent_id(self, obj: Any):
        if (
            isinstance(obj, (np.ndarray, jax.Array))
            and obj.nbytes >= self.threshold
        ):
            name = self._seen.get(id(obj))
            if name is None:
                name = f"leaf{len(self.leaves):05d}"
                self.leaves[name] = obj
                self._seen[id(obj)] = name
            return ("pio-part", name)
        return None


@functools.partial(jax.jit, static_argnums=2)
def _rows(leaf: jax.Array, start, rows: int) -> jax.Array:
    """``rows`` rows of the leaf seen as a matrix of its last axis' rows, as a
    device array of its own: one program a leaf's shape and ``rows``,
    whatever ``start`` is."""
    # the span's name on the device too: a traced retrain reads the slices'
    # time under it and not as operations nobody named
    with jax.named_scope("persist.fetch"):
        view = leaf.reshape(-1, leaf.shape[-1]) if leaf.ndim > 1 else leaf.reshape(-1)
        return jax.lax.dynamic_slice_in_dim(view, start, rows)


class _DevicePart:
    """A leaf that is still on the device, on its way to the host: whole
    where it is small, else in pieces of whole rows of its last axis (about
    ``FETCH_PIECE_BYTES`` each, in C order, every piece a device array of its
    own), so that a piece's bytes are written while the next leave the
    device, and the host holds a few pieces of a large part and never all of
    it.  A piece's copy is started (``start``) before it is waited for
    (``take``); the pieces are taken in order, by one thread."""

    def __init__(self, leaf: jax.Array):
        self.leaf = leaf
        self.length = math.prod(leaf.shape[:-1]) if leaf.ndim > 1 else leaf.size
        pieces = min(self.length, max(1, -(-leaf.nbytes // FETCH_PIECE_BYTES)))
        self.rows = -(-self.length // pieces)
        self.pieces = -(-self.length // self.rows)
        self._started: dict[int, jax.Array] = {}

    def _first_row(self, k: int) -> int:
        # the last piece reaches back over rows the one before it held, so
        # that every piece of a leaf is the one program's
        return min(k * self.rows, self.length - self.rows)

    def start(self, k: int) -> None:
        if k >= self.pieces or k in self._started:
            return
        piece = self.leaf
        if self.pieces > 1:
            piece = _rows(self.leaf, self._first_row(k), self.rows)
        piece.copy_to_host_async()
        self._started[k] = piece

    def take(self, k: int) -> np.ndarray:
        """Piece ``k``'s rows on the host.  Of a leaf in several pieces
        nothing here keeps the copy: it goes with the array returned."""
        host = np.asarray(self._started.pop(k))
        held = k * self.rows - self._first_row(k)
        return host[held:] if held else host


class LazyParts(Mapping):
    """Part name -> raw ``.npy`` bytes, serialized when asked for and not
    kept: a store that writes part after part holds one part's bytes, and a
    store that hands ``write_part`` an open file holds none.

    A part whose leaf is a ``jax.Array`` leaves the device when it is asked
    for, piece by piece (``_DevicePart``), each wait inside a span
    ``persist.fetch`` (a child of whatever span the asking thread has open:
    the local store's ``persist.part``); ``fetched`` says which parts came
    that way, and how many bytes."""

    def __init__(self, leaves: dict[str, np.ndarray | jax.Array]):
        self._leaves = leaves
        self._on_device = {
            name: _DevicePart(leaf) for name, leaf in leaves.items()
            if isinstance(leaf, jax.Array)
        }
        #: the device's copies are started from several writers' threads
        self._lock = threading.Lock()
        self._written: set[str] = set()
        #: part name -> bytes, of the parts fetched from the device so far
        self.fetched: dict[str, int] = {}

    def __getitem__(self, name: str) -> bytes:
        part = io.BytesIO()
        self.write_part(name, part)
        return part.getvalue()

    def write_part(self, name: str, file: BinaryIO) -> None:
        """Write the bytes ``self[name]`` would return into ``file``.  Onto a
        real file ``np.save`` writes the header and then the array's buffer
        from its own memory (``ndarray.tofile``, which releases the GIL);
        into anything else it copies the array chunk by chunk.  A device
        leaf's bytes are the same ``.npy``: its header, then piece after
        piece as each arrives, the next one's copy running under the write."""
        part = self._on_device.get(name)
        if part is None:
            np.save(file, self._leaves[name], allow_pickle=False)
            return
        leaf = part.leaf
        np.lib.format.write_array_header_1_0(file, {
            "descr": np.lib.format.dtype_to_descr(leaf.dtype),
            "fortran_order": False, "shape": leaf.shape,
        })
        fetched = 0
        for k in range(part.pieces):
            with trace("persist.fetch", ring=False) as span:
                with self._lock:
                    for ahead in range(k, k + 1 + FETCH_PIECES_AHEAD):
                        part.start(ahead)
                host = part.take(k)
                span.tags = {"part": name, "piece": k, "bytes": host.nbytes}
            file.write(host.reshape(-1).view(np.uint8).data)
            fetched += host.nbytes
        self.fetched[name] = fetched
        self._written.add(name)

    def fetch_ahead(self, names: Iterable[str]) -> None:
        """Start the copy off the device of these parts' first pieces and do
        not wait for it: the writer that asks for one of them later finds it
        on its way or there.  Asking twice starts nothing twice."""
        with self._lock:
            for name in names:
                if name in self._on_device and name not in self._written:
                    self._on_device[name].start(0)

    def part_nbytes(self, name: str) -> int:
        """The part's size without its ``.npy`` header (what a store orders
        its writes by, without making the bytes or fetching the leaf)."""
        return self._leaves[name].nbytes

    def __iter__(self) -> Iterator[str]:
        return iter(self._leaves)

    def __len__(self) -> int:
        return len(self._leaves)


class _ShardingUnpickler(pickle.Unpickler):
    def __init__(self, buf: io.BytesIO, get_part: Callable[[str], bytes | None]):
        super().__init__(buf)
        self.get_part = get_part
        self._loaded: dict[str, np.ndarray] = {}

    def persistent_load(self, pid: Any) -> Any:
        kind, name = pid
        if kind != "pio-part":
            raise pickle.UnpicklingError(f"unknown persistent id {pid!r}")
        # memoized so aliased references restore as one shared array
        if name not in self._loaded:
            blob = self.get_part(name)
            if blob is None:
                raise pickle.UnpicklingError(f"missing model part {name!r}")
            self._loaded[name] = np.load(io.BytesIO(blob), allow_pickle=False)
        return self._loaded[name]


def serialize_models(models: list[Any]) -> bytes:
    """Single-blob format (legacy/small models)."""
    buf = io.BytesIO()
    pickle.Pickler(buf, protocol=pickle.HIGHEST_PROTOCOL).dump(
        [_to_host(m) for m in models]
    )
    return buf.getvalue()


def deserialize_models(blob: bytes) -> list[Any]:
    return pickle.loads(blob)


def serialize_models_sharded(
    models: list[Any], threshold: int = PART_THRESHOLD
) -> tuple[bytes, Mapping[str, bytes]]:
    """Return (manifest blob, {part name: raw .npy bytes}); the mapping makes
    a part's bytes each time it is read (``LazyParts``), and fetches the part
    then where its leaf is a device array."""
    buf = io.BytesIO()
    p = _ShardingPickler(buf, threshold)
    p.dump([_to_host(m, threshold) for m in models])
    return buf.getvalue(), LazyParts(p.leaves)


def deserialize_models_sharded(
    manifest: bytes, get_part: Callable[[str], bytes | None]
) -> list[Any]:
    """Inverse of ``serialize_models_sharded``; parts are fetched lazily
    through ``get_part`` as the manifest references them."""
    return _ShardingUnpickler(io.BytesIO(manifest), get_part).load()


def save_models(
    models_store, instance_id: str, models: list[Any],
    threshold: int | None = None,
) -> None:
    """Persist a model list under an engine-instance id (sharded format).

    ``threshold`` overrides ``PART_THRESHOLD`` (read at call time, so tests
    and deployments can lower it to force factor tables into named parts —
    the layout the lifecycle per-part checksums verify shard-by-shard)."""
    manifest, parts = serialize_models_sharded(
        models, threshold if threshold is not None else PART_THRESHOLD
    )
    models_store.insert_parts(instance_id, manifest, parts)
    span = current_span()
    if span is not None:
        span.tags = {
            **(span.tags or {}),
            "fetched_parts": len(parts.fetched),
            "fetched_bytes": sum(parts.fetched.values()),
        }


def load_models(models_store, instance_id: str) -> list[Any] | None:
    """Load a model list saved by ``save_models`` or the legacy single-blob
    ``insert`` format (checked in that order)."""
    manifest = models_store.get_manifest(instance_id)
    if manifest is not None:
        return deserialize_models_sharded(
            manifest, lambda name: models_store.get_part(instance_id, name)
        )
    blob = models_store.get(instance_id)
    if blob is None:
        return None
    return deserialize_models(blob)
