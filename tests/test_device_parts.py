"""A model whose big leaves are still on the device (ISSUE 41): they become
parts without being fetched, a store's writer fetches each inside
``persist.fetch`` when it writes that part, and what reaches the store is byte
for byte what the same model writes from host arrays."""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.core import persistence
from predictionio_tpu.core.persistence import (
    load_models,
    save_models,
    serialize_models_sharded,
)
from predictionio_tpu.data.storage import localfs_models
from predictionio_tpu.data.storage.localfs_models import LocalFSModels
from predictionio_tpu.data.storage.s3_models import S3Models
from predictionio_tpu.obs.tracing import trace
from tests.test_lifecycle import _spy_on_files
from tests.test_model_store import FakeS3Client

THRESHOLD = 4096
#: rows of 16 float32: six leaves over the threshold, of unequal sizes (more
#: than the writers in flight), and two under it
ROWS = {"embed": 2048, "head": 2048, "l0.q": 256, "l0.k": 64, "l1.q": 512,
        "l1.k": 128, "l0.norm": 4, "l1.norm": 4}


def device_model() -> dict:
    rng = np.random.default_rng(41)
    params = {
        k: jnp.asarray(rng.standard_normal((rows, 16)).astype(np.float32))
        for k, rows in ROWS.items()
    }
    return {"params": params, "vocab": {"i0": 0, "i1": 1},
            "history": np.arange(40, dtype=np.int32)}


def host_model() -> dict:
    """The same model as ``SequenceAlgorithm.train`` handed it over before:
    every weight fetched (a read-only array, which pickle writes as such)."""
    m = device_model()
    return {**m, "params": {k: np.asarray(v) for k, v in m["params"].items()}}


BIG = sorted(k for k, rows in ROWS.items() if rows * 64 >= THRESHOLD)


class Stores:
    """The local store (hands ``write_part`` its files) and a store that asks
    for each part's bytes (the base ``insert_parts``), with what each holds."""

    @staticmethod
    def localfs(tmp_path):
        store = LocalFSModels(tmp_path)
        return store, lambda: {
            p.name: p.read_bytes() for p in tmp_path.iterdir()}

    @staticmethod
    def s3(tmp_path):
        client = FakeS3Client()
        return S3Models("models", client=client), lambda: dict(client.objects)


@pytest.fixture(params=["localfs", "s3"])
def store_and_contents(request, tmp_path):
    return getattr(Stores, request.param)(tmp_path)


def _saved(store, model) -> dict:
    """``save_models`` as the workflow runs it; the finished span as a dict."""
    with trace("train.persist.save_models", ring=False) as span:
        save_models(store, "inst", [model], threshold=THRESHOLD)
    return span.to_dict()


def _spans(tree: dict, name: str) -> list[dict]:
    found = [tree] if tree["name"] == name else []
    for child in tree.get("children", []):
        found += _spans(child, name)
    return found


def test_device_leaves_round_trip(store_and_contents):
    store, _ = store_and_contents
    model = device_model()
    _saved(store, model)
    [out] = load_models(store, "inst")
    want = host_model()
    assert set(out["params"]) == set(want["params"])
    for k, v in want["params"].items():
        assert type(out["params"][k]) is np.ndarray, k
        assert out["params"][k].dtype == np.float32
        np.testing.assert_array_equal(out["params"][k], v)
    np.testing.assert_array_equal(out["history"], want["history"])
    assert out["vocab"] == want["vocab"]


def test_the_store_holds_the_bytes_host_arrays_write(tmp_path, piece_bytes):
    """Every part file and the manifest, name for name and byte for byte,
    through the files and through the bytes, whole or in pieces."""
    for kind in ("localfs", "s3"):
        held = {}
        for side, model in (("host", host_model()), ("device", device_model())):
            (tmp_path / kind / side).mkdir(parents=True)
            store, contents = getattr(Stores, kind)(tmp_path / kind / side)
            _saved(store, model)
            held[side] = contents()
        assert len(held["host"]) == len(BIG) + 1, kind
        assert held["device"] == held["host"], kind


def test_an_aliased_device_leaf_is_one_part(tmp_path):
    table = device_model()["params"]["embed"]
    model = {"embed": table, "head": table, "norm": jnp.arange(8.0)}
    manifest, parts = serialize_models_sharded([model], threshold=THRESHOLD)
    assert list(parts) == ["leaf00000"]
    host = {k: np.asarray(v) for k, v in model.items()}
    host["head"] = host["embed"]
    assert manifest == serialize_models_sharded([host], threshold=THRESHOLD)[0]
    store = LocalFSModels(tmp_path)
    span = _saved(store, model)
    assert span["parts"] == span["fetched_parts"] == 1
    [out] = load_models(store, "inst")
    assert out["embed"] is out["head"]
    np.testing.assert_array_equal(out["embed"], np.asarray(table))


def test_nothing_is_fetched_before_the_store_asks(monkeypatch):
    """Making the manifest and the parts' mapping, sizing and ordering the
    parts: no ``persist.fetch``, and no leaf turned into a host array."""
    fetched = []

    def spying(real):
        def spy(a, *args, **kw):
            if isinstance(a, jax.Array):
                fetched.append(a.shape)
            return real(a, *args, **kw)
        return spy

    model = device_model()
    monkeypatch.setattr(np, "asarray", spying(np.asarray))
    monkeypatch.setattr(jax, "device_get", spying(jax.device_get))
    with trace("outer", ring=False) as span:
        manifest, parts = serialize_models_sharded([model], threshold=THRESHOLD)
        sizes = {name: parts.part_nbytes(name) for name in parts}
        parts.fetch_ahead(list(parts))
    # the two leaves under the threshold are pulled as ever, into the manifest
    assert sorted(fetched) == [(4, 16), (4, 16)]
    assert span.children == [] and parts.fetched == {}
    assert sorted(sizes.values()) == sorted(ROWS[k] * 64 for k in BIG)
    assert len(manifest) < THRESHOLD
    one = parts["leaf00000"]
    assert parts.fetched == {"leaf00000": sizes["leaf00000"]}
    assert len(one) > sizes["leaf00000"]


@pytest.fixture(params=[None, 20_000], ids=["whole", "pieces"])
def piece_bytes(request, monkeypatch):
    """Every device part whole (the default is far over these sizes), or the
    larger ones in pieces of whole rows: 2048 rows of 64 bytes in 7 pieces of
    293 rows (the last reaches back over 3), 512 rows in 2 of 256."""
    if request.param:
        monkeypatch.setattr(persistence, "FETCH_PIECE_BYTES", request.param)
    return request.param


def test_each_part_is_fetched_once_inside_its_writer(
    store_and_contents, piece_bytes
):
    store, _ = store_and_contents
    span = _saved(store, device_model())
    fetches = _spans(span, "persist.fetch")
    by_part: dict[str, list] = {}
    for f in fetches:
        by_part.setdefault(f["part"], []).append(f)
    assert sorted(sum(f["bytes"] for f in fs) for fs in by_part.values()) == (
        sorted(ROWS[k] * 64 for k in BIG))
    for fs in by_part.values():
        assert [f["piece"] for f in fs] == list(range(len(fs)))
    assert sorted(len(fs) for fs in by_part.values()) == (
        [1, 1, 1, 2, 7, 7] if piece_bytes else [1] * 6)
    assert span["fetched_parts"] == len(BIG)
    assert span["fetched_bytes"] == sum(ROWS[k] * 64 for k in BIG)
    if isinstance(store, LocalFSModels):
        assert span["parts"] == span["streamed_parts"] == len(BIG)
        assert span["writers"] == localfs_models.PART_WRITERS
        writers = span["children"]
        assert {w["name"] for w in writers} == {"persist.part"}
        for w in writers:
            fs = w["children"]
            assert {f["name"] for f in fs} == {"persist.fetch"}
            assert {f["part"] for f in fs} == {w["part"]}
            # the file has a header
            assert 0 < sum(f["bytes"] for f in fs) < w["bytes"]
            assert sum(f["duration_s"] for f in fs) <= w["duration_s"]


def test_host_arrays_are_fetched_from_nowhere(tmp_path):
    span = _saved(LocalFSModels(tmp_path), host_model())
    assert span["parts"] == span["streamed_parts"] == len(BIG)
    assert span["fetched_parts"] == span["fetched_bytes"] == 0
    assert _spans(span, "persist.fetch") == []


def test_device_parts_flush_in_commit_order(tmp_path, monkeypatch):
    """A part that came off the device is fsynced before its rename, the
    directory after the last part's rename and before the manifest's."""
    store = LocalFSModels(tmp_path)
    events = _spy_on_files(monkeypatch, store.root)
    _saved(store, device_model())
    monkeypatch.undo()
    manifest = str(store._file("inst:manifest"))
    renames = [path for op, path in events if op == "replace"]
    assert renames[-1] == manifest and len(renames) == len(BIG) + 1
    for dst in renames:
        at = events.index(("replace", dst))
        (tmp,) = {p for op, p in events if op == "open" and p.startswith(dst + ".")}
        assert events.index(("fsync", tmp)) < at
    last_part = events.index(("replace", renames[-2]))
    dir_syncs = [i for i, e in enumerate(events) if e == ("fsync", str(store.root))]
    assert any(last_part < i < events.index(("replace", manifest)) for i in dir_syncs)
    assert dir_syncs[-1] > events.index(("replace", manifest))


def test_a_fetch_that_fails_leaves_no_manifest_and_no_tmp(tmp_path):
    """One leaf deleted on the device before its writer asks for it: every
    writer is joined, the error is raised, and the store shows no checkpoint."""
    model = device_model()
    store = LocalFSModels(tmp_path)
    store.insert_parts("inst", b"old", {"leaf00000": b"o0", "leaf00009": b"o9"})
    model["params"]["l0.q"].delete()
    threads_before = set(threading.enumerate())
    with pytest.raises(RuntimeError, match="deleted"):
        _saved(store, model)
    assert set(threading.enumerate()) <= threads_before
    assert store.get_manifest("inst") is None
    assert load_models(store, "inst") is None
    assert not any(p.name.endswith(".tmp") for p in tmp_path.iterdir())
    assert store.get_part("inst", "leaf00009") is None
    # a re-save of a whole model commits as ever
    _saved(store, device_model())
    [out] = load_models(store, "inst")
    np.testing.assert_array_equal(
        out["params"]["l0.q"], host_model()["params"]["l0.q"])


def test_fetches_are_started_in_the_write_order_a_bounded_number_ahead(
    tmp_path, monkeypatch
):
    """The local store asks for the copies of the part a writer takes on and
    of those behind it, ``PARTS_FETCHING`` in all, largest part first."""
    from predictionio_tpu.core.persistence import LazyParts

    asked = []
    real = LazyParts.fetch_ahead

    def spying(self, names):
        asked.append(list(names))
        return real(self, names)

    monkeypatch.setattr(LazyParts, "fetch_ahead", spying)
    monkeypatch.setattr(localfs_models, "PARTS_FETCHING", 3)
    monkeypatch.setattr(localfs_models, "PART_WRITERS", 1)
    rng = np.random.default_rng(3)
    model = {
        f"t{i}": jnp.asarray(rng.standard_normal((rows, 16)).astype(np.float32))
        for i, rows in enumerate([128, 2048, 512, 64, 1024])
    }
    _saved(LocalFSModels(tmp_path), model)
    # leaves are named in the model's order: t0 leaf00000 ... t4 leaf00004
    order = ["leaf00001", "leaf00004", "leaf00002", "leaf00000", "leaf00003"]
    assert asked == [order[i : i + 3] for i in range(5)]


#: device leaves whose pieces must lay down what ``np.save`` lays down whole:
#: rows that do not divide into the pieces, one row wider than a piece, one
#: axis, no axis, other item sizes
_DEVICE_LEAVES = {
    "float32-1000x7": lambda: jnp.arange(7000, dtype=jnp.float32).reshape(1000, 7),
    "float32-3x5000": lambda: jnp.arange(15000, dtype=jnp.float32).reshape(3, 5000),
    "int32-1d": lambda: jnp.arange(5001, dtype=jnp.int32),
    "bfloat16-3d": lambda: jnp.arange(9 * 40 * 11, dtype=jnp.float32).reshape(
        9, 40, 11).astype(jnp.bfloat16),
    "bool-2d": lambda: jnp.arange(3000).reshape(60, 50) % 3 == 0,
    "float32-0d": lambda: jnp.float32(41.0),
    # a stack of matrices: a piece ends inside a matrix of the first axis
    "float32-stack": lambda: jnp.arange(5 * 40 * 30, dtype=jnp.float32).reshape(
        5, 40, 30),
    "int8-1-row": lambda: jnp.ones((1, 9000), jnp.int8),
}


@pytest.mark.parametrize("piece", [64, 1000, 4096, 1 << 30])
@pytest.mark.parametrize("leaf", sorted(_DEVICE_LEAVES))
def test_a_device_part_in_pieces_is_the_npy_bytes(tmp_path, monkeypatch, leaf, piece):
    import io

    monkeypatch.setattr(persistence, "FETCH_PIECE_BYTES", piece)
    array = _DEVICE_LEAVES[leaf]()
    want = io.BytesIO()
    np.save(want, np.asarray(array), allow_pickle=False)
    manifest, parts = serialize_models_sharded([{"w": array}], threshold=1)
    assert list(parts) == ["leaf00000"]
    assert parts["leaf00000"] == want.getvalue()
    store = LocalFSModels(tmp_path)
    store.insert_parts("inst", manifest, parts)
    on_disk = (tmp_path / "pio_model_inst:part:leaf00000.bin").read_bytes()
    assert on_disk == want.getvalue()
    assert parts.fetched == {"leaf00000": array.nbytes}
    # read back as numpy reads what ``np.save`` wrote (bfloat16 as 2-byte voids)
    back = np.load(io.BytesIO(want.getvalue()), allow_pickle=False)
    [out] = load_models(store, "inst")
    assert (out["w"].dtype, out["w"].shape) == (back.dtype, array.shape)
    assert out["w"].tobytes() == np.asarray(array).tobytes()


def test_pieces_of_one_shape_are_one_program(monkeypatch):
    """Every piece of a leaf, the last (which reaches back) too, and every
    later leaf of that shape: one compiled slice, made by the first retrain."""
    monkeypatch.setattr(persistence, "FETCH_PIECE_BYTES", 20_000)
    persistence._rows.clear_cache()
    model = device_model()
    _, parts = serialize_models_sharded([model], threshold=THRESHOLD)
    for name in parts:
        parts[name]
    # 2048 x 16 in rows of 293 (embed, head), 512 x 16 in rows of 256
    assert persistence._rows._cache_size() == 2
    _, again = serialize_models_sharded([device_model()], threshold=THRESHOLD)
    for name in again:
        again[name]
    assert persistence._rows._cache_size() == 2


def test_a_piece_keeps_nothing_of_a_large_leaf_on_the_host(monkeypatch):
    """A part in pieces: fetch, write, let go.  The leaf itself never holds
    a host copy, and the mapping holds no piece once it has been taken."""
    monkeypatch.setattr(persistence, "FETCH_PIECE_BYTES", 20_000)
    model = device_model()
    _, parts = serialize_models_sharded([model], threshold=THRESHOLD)
    parts.fetch_ahead(list(parts))
    started = {n: len(p._started) for n, p in parts._on_device.items()}
    assert set(started.values()) == {1}  # the first piece of each, no more
    for name in parts:
        parts[name]
    assert all(not p._started for p in parts._on_device.values())
    # asked again after it was written: nothing is started for it
    parts.fetch_ahead(list(parts))
    assert all(not p._started for p in parts._on_device.values())
