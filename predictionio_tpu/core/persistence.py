"""Model serialization: pytree-aware blobs for the Models store.

The reference Kryo-serializes the whole Seq[model] into the MODELDATA
repository (workflow/CoreWorkflow.scala:76-81).  Here models are arbitrary
Python objects whose array leaves may be jax device arrays: every jax array
is pulled to host numpy (device_get) before pickling, so checkpoint contents
never depend on device topology.

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
"""

from __future__ import annotations

import io
import pickle
from collections.abc import Mapping
from typing import Any, BinaryIO, Callable, Iterator

import jax
import numpy as np

#: leaves at or above this many bytes become standalone parts
PART_THRESHOLD = 1 << 20


def _to_host(obj: Any) -> Any:
    """Map jax arrays to numpy throughout an arbitrary pytree-ish object."""
    return jax.tree_util.tree_map(
        lambda x: np.asarray(jax.device_get(x)) if isinstance(x, jax.Array) else x,
        obj,
        is_leaf=lambda x: isinstance(x, jax.Array),
    )


class _ShardingPickler(pickle.Pickler):
    """Pickler that spills big ndarray leaves into a side table of parts.

    ``persistent_id`` sees every object in the graph, registered pytree or
    not — dataclasses, dicts, BiMaps — so any reachable large array is
    sharded without cooperation from the containing type.
    """

    def __init__(self, buf: io.BytesIO, threshold: int):
        super().__init__(buf, protocol=pickle.HIGHEST_PROTOCOL)
        #: part name -> the array it holds (its bytes are made on demand)
        self.leaves: dict[str, np.ndarray] = {}
        self.threshold = threshold
        # persistent_id runs before pickle's own memoization, so aliased
        # arrays (one table referenced from two fields) must be deduped here
        # or they double both checkpoint size and deploy-host RAM; the
        # arrays in ``leaves`` pin their id() for the dump's life
        self._seen: dict[int, str] = {}

    def persistent_id(self, obj: Any):
        if isinstance(obj, np.ndarray) and obj.nbytes >= self.threshold:
            name = self._seen.get(id(obj))
            if name is None:
                name = f"leaf{len(self.leaves):05d}"
                self.leaves[name] = obj
                self._seen[id(obj)] = name
            return ("pio-part", name)
        return None


class LazyParts(Mapping):
    """Part name -> raw ``.npy`` bytes, serialized when asked for and not
    kept: a store that writes part after part holds one part's bytes, and a
    store that hands ``write_part`` an open file holds none."""

    def __init__(self, leaves: dict[str, np.ndarray]):
        self._leaves = leaves

    def __getitem__(self, name: str) -> bytes:
        part = io.BytesIO()
        self.write_part(name, part)
        return part.getvalue()

    def write_part(self, name: str, file: BinaryIO) -> None:
        """Write the bytes ``self[name]`` would return into ``file``.  Onto a
        real file ``np.save`` writes the header and then the array's buffer
        from its own memory (``ndarray.tofile``, which releases the GIL);
        into anything else it copies the array chunk by chunk."""
        np.save(file, self._leaves[name], allow_pickle=False)

    def part_nbytes(self, name: str) -> int:
        """The part's size without its ``.npy`` header (what a store orders
        its writes by, without making the bytes)."""
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
    a part's bytes each time it is read (``LazyParts``)."""
    buf = io.BytesIO()
    p = _ShardingPickler(buf, threshold)
    p.dump([_to_host(m) for m in models])
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
