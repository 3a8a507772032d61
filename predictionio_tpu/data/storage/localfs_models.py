"""Local-filesystem model blob store (reference storage/localfs/LocalFSModels.scala:32)."""

from __future__ import annotations

import os
import secrets
from collections.abc import Callable, Mapping
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from pathlib import Path
from typing import BinaryIO

from predictionio_tpu.data.storage import base

#: parts of one checkpoint in flight at a time.  Read on the chip's host
#: (PERF.md section 6, PR 31, table (b): 3.2 GB in 33 parts of 22-770 MB
#: written from memory, each fsynced, two readings a row): 1 writer 3.48 /
#: 3.72 s, 2 writers 1.41 / 1.17 s, 4 writers 1.24 / 1.10 s, 8 writers
#: 1.49 / 1.26 s.  From 2 on the two largest parts' own 0.5-0.9 s bound the
#: wall; 4 keeps the small parts off their threads.
PART_WRITERS = 4

#: parts, from the one a writer takes on in the write's order, whose first
#: piece's copy off the device has been started (``LazyParts.fetch_ahead``;
#: host arrays have none): the ``PART_WRITERS`` being written and two behind
#: them.  The device's copies are served in the order they were asked for, so
#: more ahead delays the pieces the writers are waiting for now (8 ahead with
#: one piece a writer: 2.1 s of a writer's 3.7 in waits; PERF.md, PR 41)
PARTS_FETCHING = PART_WRITERS + 2


class LocalFSModels(base.Models):
    def __init__(self, path: str | Path):
        self.root = Path(path)
        self.root.mkdir(parents=True, exist_ok=True)

    def _file(self, instance_id: str) -> Path:
        # instance ids are hex/uuid strings; guard against path traversal anyway
        safe = instance_id.replace("/", "_").replace("..", "_")
        return self.root / f"pio_model_{safe}.bin"

    def insert(self, instance_id: str, blob: bytes) -> None:
        """Durable atomic publish: write a per-writer unique tmp file,
        fsync it, rename over the final name, fsync the directory.

        The unique tmp name means two concurrent trainers staging the same
        key race only at the (atomic) rename — neither can truncate or
        interleave the other's half-written bytes, and the final file is
        always exactly one writer's blob.  The fsyncs make the
        write-then-rename ordering hold across a power cut / SIGKILL: a
        crash at ANY point leaves either the old complete blob or the new
        complete blob, never a torn file.  This is the localfs half of the
        lifecycle manifest's crash-safety contract
        (predictionio_tpu/lifecycle/generations.py).
        """
        self._publish(instance_id, lambda file: file.write(blob))
        self._fsync_dir()

    def _publish(
        self, instance_id: str, write: Callable[[BinaryIO], object]
    ) -> int:
        """``insert`` up to the rename: ``write(file)`` fills the unique tmp
        file, which is fsynced and renamed over the final name.  Returns the
        file's size; the caller owes the directory's fsync."""
        final = self._file(instance_id)
        tmp = final.with_name(
            f"{final.name}.{os.getpid()}.{secrets.token_hex(6)}.tmp"
        )
        fd = os.open(str(tmp), os.O_WRONLY | os.O_CREAT | os.O_EXCL, 0o644)
        try:
            try:
                # buffered: its write() loops where one write(2) stops short
                # (2 GiB - 4 KiB a call), as fwrite does under ndarray.tofile
                with open(fd, "wb", closefd=False) as file:
                    write(file)
                    size = file.tell()
                os.fsync(fd)
            finally:
                os.close(fd)
            os.replace(str(tmp), str(final))
        except BaseException:
            # a failed publish must not leak its tmp (the unique name would
            # otherwise accumulate per retry); the final file is untouched
            try:
                os.unlink(str(tmp))
            except OSError:
                pass
            raise
        return size

    def insert_parts(
        self, instance_id: str, manifest: bytes, parts: Mapping[str, bytes]
    ) -> None:
        """The base class's commit order (old checkpoint dropped, parts,
        manifest last) with ``PART_WRITERS`` parts in flight, largest first.

        A mapping that offers ``write_part(name, file)`` (``LazyParts``) has
        each part written from its array's memory, and a part that is still
        on the device fetched by its writer as it writes it (``persist.fetch``
        under ``persist.part``), the first copies of the next
        ``PARTS_FETCHING`` parts started ahead; any other mapping's values
        are the parts' bytes.  Every part's file is fsynced before its
        rename, and the directory before the manifest is written, so a
        manifest that can be seen names parts that are all on the disk; the
        manifest goes through ``insert``, and this returns after its
        directory fsync.  On an error every writer is joined, each has
        removed its own tmp file, and no manifest is written.

        Inside a span (``train.persist.save_models``) the writers' spans
        ``persist.part`` become its children and it is tagged with what was
        written."""
        # imported here: obs's package imports the storage registry
        from predictionio_tpu.obs.tracing import current_span, trace

        self._drop_checkpoint_for_resave(instance_id)
        streamed = hasattr(parts, "write_part")
        if streamed:
            size_of, write = parts.part_nbytes, parts.write_part
            fetch_ahead = parts.fetch_ahead
        else:
            def fetch_ahead(names: list[str]) -> None:
                """Bytes are on the host already."""

            def size_of(name: str) -> int:
                return len(parts[name])

            def write(name: str, file: BinaryIO) -> None:
                file.write(parts[name])

        parent = current_span()
        order = sorted(parts, key=size_of, reverse=True)

        def publish(at: int) -> int:
            name = order[at]
            with trace("persist.part", ring=False, parent=parent) as span:
                fetch_ahead(order[at : at + PARTS_FETCHING])
                size = self._publish(
                    f"{instance_id}:part:{name}", partial(write, name)
                )
                span.tags = {"part": name, "bytes": size}
            return size

        writers = min(PART_WRITERS, len(order))
        sizes = []
        if order:
            with ThreadPoolExecutor(writers) as pool:
                sizes = base.run_concurrent(
                    pool, [partial(publish, at) for at in range(len(order))]
                )
            # the parts' names reach the disk before the manifest's can.
            # One flush for all of them: a directory fsync after each rename
            # is 0.4 ms alone and stalls every writer's file fsync when it
            # runs beside them (7.79 s against 1.10-1.24; PERF.md, PR 31)
            self._fsync_dir()
        self.insert(
            f"{instance_id}:manifest", base._manifest_blob(manifest, parts)
        )
        if parent is not None:
            parent.tags = {
                **(parent.tags or {}),
                "parts": len(order),
                "bytes": sum(sizes),
                "writers": writers,
                "streamed_parts": len(order) if streamed else 0,
            }

    def _fsync_dir(self) -> None:
        """Persist the rename itself (directory entry) — without this a
        crash can resurrect the OLD name even though the data blocks of
        the new blob reached disk."""
        try:
            dfd = os.open(str(self.root), os.O_RDONLY)
        except OSError:
            return  # platforms without directory fds: rename still atomic
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)

    def get(self, instance_id: str) -> bytes | None:
        f = self._file(instance_id)
        return f.read_bytes() if f.exists() else None

    def delete(self, instance_id: str) -> bool:
        f = self._file(instance_id)
        if f.exists():
            f.unlink()
            return True
        return False
