"""Binary wire codec for EventFrame — the bulk-scan payload of the remote
storage daemon.

The reference's Elasticsearch backend ships bulk event scans through the
elasticsearch-spark connector's own columnar wire format
(storage/elasticsearch/.../ESPEvents.scala:42); the remote backend here
needs the same thing: a compact, self-describing encoding of one columnar
EventFrame that round-trips losslessly (ids, tags, prId, creation time)
without per-event JSON objects on the hot path.

Layout (version 1)::

    b"PIOF1\\n"                       magic
    u32 big-endian header length
    header JSON  {"n": N, "cols": [{"name": ..., "kind": ...}, ...]}
    per-column payloads, in header order

Column kinds:

* ``i64``  — raw little-endian int64 array (N*8 bytes)
* ``str``  — i32 length array (N*4 bytes; -1 encodes None) followed by the
  concatenated UTF-8 bytes
* ``json`` — same layout as ``str``; each row is a JSON document, with the
  empty string standing for the column's "empty" value ({} or ())

Absent optional columns (event_id/tags/pr_id/creation_time_ms may be None
on synthesized frames) are simply omitted from the header.

Both directions are vectorized through pyarrow's string buffers (lengths
and bytes move as two C arrays, never one Python object per row), with
the per-row loop kept only as the fallback for exotic row types — the
codec is on the multi-daemon fan-out write path, where 20M-row frames
must encode in seconds, not minutes.
"""

from __future__ import annotations

import json

import numpy as np

from predictionio_tpu.data.storage.base import (
    CodedColumn,
    EventFrame,
    ptr_factorize,
)

MAGIC = b"PIOF1\n"

_I64_COLS = ("event_time_ms", "creation_time_ms")
_STR_COLS = (
    "event",
    "entity_type",
    "entity_id",
    "target_entity_type",
    "target_entity_id",
    "event_id",
    "pr_id",
)
_JSON_COLS = ("properties", "tags")
_COLUMN_ORDER = (
    "event",
    "entity_type",
    "entity_id",
    "target_entity_type",
    "target_entity_id",
    "event_time_ms",
    "properties",
    "event_id",
    "tags",
    "pr_id",
    "creation_time_ms",
)


def _lengths_and_bytes(col: np.ndarray) -> bytes | None:
    """Vectorized (i32 lengths + concatenated UTF-8) for an all-str/None
    column via arrow's offset buffers; None when any row needs coercion."""
    import pyarrow as pa

    try:
        # ArrowCapacityError: >2 GiB of string data overflows the int32
        # offsets the wire format shares with arrow — the row loop
        # handles it (per-column payloads are framed by explicit lengths)
        arr = pa.array(col, pa.string())
    except (pa.ArrowInvalid, pa.ArrowTypeError, pa.ArrowCapacityError):
        return None
    bufs = arr.buffers()  # [validity, offsets(int32 n+1), data]
    offsets = np.frombuffer(bufs[1], dtype="<i4", count=len(col) + 1)
    lengths = np.diff(offsets).astype("<i4")
    if arr.null_count:
        nulls = arr.is_null().to_numpy(zero_copy_only=False)
        lengths[nulls] = -1
    data = bufs[2].to_pybytes() if bufs[2] is not None else b""
    return lengths.tobytes() + data[offsets[0]: offsets[-1]]


def _encode_str_col(col: np.ndarray) -> bytes:
    fast = _lengths_and_bytes(col)
    if fast is not None:
        return fast
    parts = []
    lengths = np.empty(len(col), dtype="<i4")
    for i, v in enumerate(col):
        if v is None:
            lengths[i] = -1
        else:
            b = v.encode("utf-8") if isinstance(v, str) else str(v).encode("utf-8")
            lengths[i] = len(b)
            parts.append(b)
    return lengths.tobytes() + b"".join(parts)


def _ser_json(v) -> str:
    """One row's serialized document ('' = empty value)."""
    if not v:
        return ""
    if isinstance(v, str):  # lazy row: already-serialized JSON
        return v
    return json.dumps(
        list(v) if isinstance(v, tuple) else v, separators=(",", ":")
    )


def _encode_json_col(col: np.ndarray) -> bytes:
    # repetitive columns (rating documents, empty tag tuples) serialize
    # each UNIQUE value once through the pointer factorization
    f = ptr_factorize(col)
    if f is not None:
        codes, uniq = f
        docs = np.array([_ser_json(v) for v in uniq], object)
        fast = _lengths_and_bytes(docs[codes])
        if fast is not None:
            return fast
    # all-lazy (already-str) columns vectorize directly
    fast = _lengths_and_bytes(col) if all(
        isinstance(v, str) for v in col
    ) else None
    if fast is not None:
        return fast
    parts = []
    lengths = np.empty(len(col), dtype="<i4")
    for i, v in enumerate(col):
        s = _ser_json(v)
        if not s:
            lengths[i] = 0
        else:
            b = s.encode("utf-8")
            lengths[i] = len(b)
            parts.append(b)
    return lengths.tobytes() + b"".join(parts)


def _decode_str_buffer(buf: memoryview, n: int) -> tuple:
    """(arrow StringArray, consumed bytes) from the wire layout, or
    (None, consumed) when the column exceeds int32 offset range — the
    row-wise fallback decodes those (the wire format itself has no such
    bound: each row is framed by its own length)."""
    import pyarrow as pa

    lengths = np.frombuffer(buf[: n * 4], dtype="<i4")
    sizes = np.where(lengths > 0, lengths, 0).astype(np.int64)
    offsets64 = np.concatenate(([0], np.cumsum(sizes)))
    total = int(offsets64[-1])
    if total >= 2**31:
        return None, n * 4 + total
    offsets = offsets64.astype("<i4")
    data = bytes(buf[n * 4: n * 4 + total])
    validity = None
    if (lengths < 0).any():
        validity = pa.array(lengths >= 0).buffers()[1]
    arr = pa.Array.from_buffers(
        pa.utf8(),
        n,
        [validity, pa.py_buffer(offsets.tobytes()), pa.py_buffer(data)],
    )
    return arr, n * 4 + total


def dictionary_to_coded(arr, null_value=None, transform=None) -> CodedColumn:
    """Arrow DictionaryArray -> ``CodedColumn``: each UNIQUE dictionary
    value decoded (and optionally ``transform``-ed) once, the int32 indices
    as they are; null rows share one more entry, which holds
    ``null_value``.  The one home of this null-handling sequence: the
    parquet scan decoders and the wire codec all share it.  No pointer a
    row is made here: ``.objects`` broadcasts them, interned, which keeps
    downstream pointer fast paths hot."""
    import pyarrow as pa

    if transform is None:
        uniq = np.asarray(
            arr.dictionary.to_numpy(zero_copy_only=False), object
        )
    else:
        vals = arr.dictionary.to_pylist()
        uniq = np.empty(len(vals), object)
        for j, v in enumerate(vals):
            uniq[j] = transform(v)
    indices = arr.indices
    if indices.type != pa.int32():
        indices = indices.cast(pa.int32())
    if arr.null_count:
        k = len(uniq)
        uniq = np.concatenate([uniq, np.empty(1, object)])
        uniq[k] = null_value
        indices = indices.fill_null(k)
    return CodedColumn(indices.to_numpy(zero_copy_only=False), uniq)


def dictionary_to_objects(arr, null_value=None, transform=None) -> np.ndarray:
    """Arrow DictionaryArray -> numpy object column: ``dictionary_to_coded``
    broadcast through its codes."""
    return dictionary_to_coded(arr, null_value, transform).objects


def _arr_to_objects(arr) -> np.ndarray:
    """Arrow strings -> numpy object column, decoding each UNIQUE value
    once when the column is repetitive."""
    import pyarrow as pa

    n = len(arr)
    if n >= 1024:
        try:
            d = arr.dictionary_encode()
        except pa.ArrowException:
            return arr.to_numpy(zero_copy_only=False)
        if len(d.dictionary) * 4 <= n:
            return dictionary_to_objects(d)
    return arr.to_numpy(zero_copy_only=False)


def _decode_var_col_rowwise(
    buf: memoryview, n: int, is_json: bool, empty, lazy: bool
) -> tuple[np.ndarray, int]:
    """Per-row decode — the fallback for columns past int32 offsets."""
    lengths = np.frombuffer(buf[: n * 4], dtype="<i4")
    out = np.empty(n, dtype=object)
    pos = n * 4
    for i in range(n):
        ln = lengths[i]
        if ln < 0:
            out[i] = None
        elif ln == 0:
            out[i] = "" if not is_json else ("" if lazy else empty)
        else:
            raw = bytes(buf[pos: pos + ln])
            pos += ln
            if not is_json or lazy:
                out[i] = raw.decode("utf-8")
            else:
                out[i] = _parse_json(raw.decode("utf-8"), empty)
    return out, pos


def _decode_var_col(
    buf: memoryview, n: int, is_json: bool, empty, lazy: bool = False
) -> tuple[np.ndarray, int]:
    arr, consumed = _decode_str_buffer(buf, n)
    if arr is None:  # >2 GiB column: int32 offsets can't carry it
        return _decode_var_col_rowwise(buf, n, is_json, empty, lazy)
    out = _arr_to_objects(arr)
    if not is_json:
        return out, consumed
    if lazy:
        # keep serialized documents (EventFrame lazy-row contract) — bulk
        # receivers skip N json.loads calls; '' stands for the empty doc
        return out, consumed
    # eager json (tags): parse each unique document once
    f = ptr_factorize(out)
    if f is not None:
        codes, uniq = f
        parsed = np.empty(len(uniq), object)
        for j, s in enumerate(uniq):
            parsed[j] = _parse_json(s, empty)
        return parsed[codes], consumed
    for i, s in enumerate(out):
        out[i] = _parse_json(s, empty)
    return out, consumed


def _parse_json(s, empty):
    if not s:
        return empty
    v = json.loads(s)
    return tuple(v) if isinstance(v, list) else v


def encode_frame(frame: EventFrame) -> bytes:
    n = len(frame)
    cols = []
    payloads = []
    for name in _COLUMN_ORDER:
        col = getattr(frame, name)
        if col is None:
            continue
        if name in _I64_COLS:
            kind = "i64"
            payload = np.ascontiguousarray(col, dtype="<i8").tobytes()
        elif name in _JSON_COLS:
            kind = "json"
            payload = _encode_json_col(col)
        else:
            kind = "str"
            payload = _encode_str_col(col)
        cols.append({"name": name, "kind": kind, "len": len(payload)})
        payloads.append(payload)
    header = json.dumps({"n": n, "cols": cols}).encode("utf-8")
    return b"".join(
        [MAGIC, len(header).to_bytes(4, "big"), header] + payloads
    )


def decode_frame(data: bytes) -> EventFrame:
    if data[: len(MAGIC)] != MAGIC:
        raise ValueError("not a PIOF1 frame")
    view = memoryview(data)
    off = len(MAGIC)
    hlen = int.from_bytes(view[off : off + 4], "big")
    off += 4
    header = json.loads(bytes(view[off : off + hlen]))
    off += hlen
    n = header["n"]
    kwargs: dict[str, np.ndarray] = {}
    for spec in header["cols"]:
        name, kind, plen = spec["name"], spec["kind"], spec["len"]
        buf = view[off : off + plen]
        off += plen
        if kind == "i64":
            kwargs[name] = np.frombuffer(buf, dtype="<i8").astype(np.int64)
        elif kind == "json":
            if name == "properties":  # lazy rows ("" = empty document)
                kwargs[name], _ = _decode_var_col(buf, n, True, "", lazy=True)
            else:
                kwargs[name], _ = _decode_var_col(buf, n, True, ())
        else:
            kwargs[name], _ = _decode_var_col(buf, n, False, "")
    return EventFrame(**kwargs)
