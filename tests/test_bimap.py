import numpy as np
import pytest

from predictionio_tpu.data import BiMap
from predictionio_tpu.data.storage.base import CodedColumn


def test_from_keys_dedup_order():
    bm = BiMap.from_keys(["b", "a", "b", "c"])
    assert len(bm) == 3
    assert bm["b"] == 0 and bm["a"] == 1 and bm["c"] == 2
    assert bm.inverse(1) == "a"


def test_vectorized_lookup():
    bm = BiMap.string_int(["u1", "u2", "u3"])
    arr = bm.to_index_array(["u3", "zz", "u1"])
    assert arr.tolist() == [2, -1, 0]
    assert arr.dtype == np.int64


def test_state_roundtrip():
    bm = BiMap.from_keys(["x", "y"])
    bm2 = BiMap.from_state(bm.to_state())
    assert bm2 == bm


def test_invalid_indices_rejected():
    with pytest.raises(ValueError):
        BiMap({"a": 0, "b": 2})
    with pytest.raises(ValueError):
        BiMap({"a": 0, "b": 0})


# -- one algorithm (first-seen dedup), two ways to run it ---------------------


def _interned(n=4000, k=37, seed=0, with_none=False):
    vocab = np.empty(k, object)
    vocab[:] = [f"id{j}" for j in range(k)]
    if with_none:
        vocab[3] = None
    return vocab[np.random.default_rng(seed).integers(0, k, n)]


def _boxed(n, k=7):
    # every row its own str object (what np.array(list_of_fresh_strs, object)
    # or a row-by-row decoder hands over): equal values, distinct pointers
    col = np.empty(n, object)
    col[:] = [f"id{j % k}" for j in range(n)]
    assert len({id(v) for v in col}) == n
    return col


def _two_dictionaries():
    # two shards decoded through their own dictionaries, then concatenated:
    # "id5" is one object in the first half and another in the second
    a, b = _interned(seed=1), _interned(seed=2)
    assert a[0] is not b[0]
    return np.concatenate([a, b])


def _nans():
    nan, other = float("nan"), float("nan")
    col = np.empty(9, object)
    # the same NaN object is one dict key, another NaN object another
    col[:] = [nan, 1.5, nan, other, 1.5, np.nan, other, nan, np.nan]
    return col


def _mixed():
    col = np.empty(10, object)
    col[:] = [1, 1.0, "1", True, 2, b"1", (1, "a"), 2.0, "1", (1, "a")]
    return col


def _coded(col=None, spare=("never-a", "never-b"), codes=None):
    """``col`` as the parquet scan hands a column over (``CodedColumn``):
    a dictionary that is in no first-seen order and holds ``spare`` entries
    no row uses, and an int32 code a row (``codes`` to say them outright)."""
    if col is None:
        col = _interned()
    distinct = list({id(k): k for k in col}.values())  # by object
    dictionary = np.empty(len(distinct) + len(spare), object)
    dictionary[:] = [*spare[:1], *reversed(distinct), *spare[1:]]
    if codes is None:
        code_of = {id(k): j for j, k in enumerate(dictionary.tolist())}
        codes = np.fromiter((code_of[id(k)] for k in col), np.int32, len(col))
    return CodedColumn(np.asarray(codes, np.int32), dictionary)


def _coded_two_codes_one_key():
    # a dictionary nobody unified: "id5" sits at two codes, as two objects
    col = _coded(_two_dictionaries())
    assert len(set(col.dictionary.tolist())) < len(col.dictionary)
    return col


#: name -> (column maker, the way BiMap.factorize must take)
COLUMNS = {
    "interned": (_interned, "factorize"),
    "boxed-few-rows": (lambda: _boxed(60), "factorize"),
    "boxed-many-rows": (lambda: _boxed(2000), "loop"),
    "two-dictionaries": (_two_dictionaries, "factorize"),
    "strided-view": (lambda: _interned(n=3000)[::3], "factorize"),
    "with-none": (lambda: _interned(with_none=True), "factorize"),
    "nan": (_nans, "factorize"),
    "mixed-1-1.0-str": (_mixed, "factorize"),
    "nul-in-string": (
        lambda: np.array(["a", "a\0b", "a", "a\0c", "a\0b"], object)[
            np.array([0, 1, 2, 3, 4, 1, 0, 3])
        ],
        "factorize",
    ),
    "U-dtype": (lambda: _interned().astype("U"), "loop"),
    "int64-dtype": (lambda: np.array([5, 3, 5, 9, 3]), "loop"),
    "empty-object": (lambda: np.empty(0, object), "loop"),
    "empty-U": (lambda: np.empty(0, "U4"), "loop"),
    "list": (lambda: _interned().tolist(), "loop"),
    "tuple": (lambda: tuple(_interned(n=50)), "loop"),
    "generator": (lambda: (k for k in _interned().tolist()), "loop"),
    # a column that comes with the store's codes (ISSUE 37)
    "coded": (_coded, "codes"),
    "coded-no-spare-entries": (lambda: _coded(spare=()), "codes"),
    "coded-null-id": (lambda: _coded(_interned(with_none=True)), "codes"),
    "coded-two-codes-one-key": (_coded_two_codes_one_key, "codes"),
    "coded-nul-in-string": (
        lambda: _coded(np.array(["a", "a\0b", "a", "a\0c", "a\0b"], object)),
        "codes",
    ),
    "coded-rows-picked-by-a-mask": (
        lambda: _coded()[np.arange(4000) % 3 == 0], "codes"),
    "coded-strided-codes": (
        lambda: CodedColumn(_coded().codes[::7], _coded().dictionary),
        "codes",
    ),
    "coded-no-rows": (lambda: _coded(codes=[]), "codes"),
    "coded-no-rows-no-dictionary": (
        lambda: CodedColumn(np.empty(0, np.int32), np.empty(0, object)),
        "codes",
    ),
    "coded-mostly-distinct": (
        # more entries than a quarter of the rows: the pointer pass would
        # give up and loop, the codes need no such cut-off
        lambda: _coded(_interned(n=60, k=37)), "codes"),
}


def _oracle(rows):
    """The loop a row, written out: what every path has to equal."""
    forward = {}
    for k in rows:
        if k not in forward:
            forward[k] = len(forward)
    return forward


@pytest.mark.parametrize("name", COLUMNS)
def test_vectorized_pass_equals_the_loop(name):
    make, path = COLUMNS[name]
    keys = make()
    rows = list(keys)  # what a loop over the column sees, row by row
    columns = (np.ndarray, list, tuple, CodedColumn)
    if not isinstance(keys, columns):
        keys = (k for k in rows)  # the generator again: it is read once
    forward = _oracle(rows)
    expected = np.array([forward[k] for k in rows], np.int64)

    f = BiMap.factorize(keys)
    assert f.path == path
    # vocabulary: the same keys (the same OBJECTS, where the column holds
    # objects) in first-seen order, under the same indices
    assert len(f.vocab) == len(forward)
    for (a, i), (b, j) in zip(f.vocab.items(), forward.items()):
        assert (a is b or a == b) and type(a) is type(b) and i == j
    # the store's codes are int32 and stay so
    assert f.codes.dtype == (np.int32 if path == "codes" else np.int64)
    np.testing.assert_array_equal(f.codes, expected)
    # the vectorized pass hashed each distinct object once (the coded one
    # each dictionary entry that has a row), the loop each row
    if path in ("factorize", "codes"):
        assert f.hashed == len({id(k) for k in rows}) <= len(rows)
    else:
        assert f.hashed == len(rows)

    # from_keys / to_index_array handed the same column take the same pass
    if not isinstance(keys, columns):
        keys = rows
    vocab = BiMap.from_keys(keys)
    assert list(vocab.items()) == list(f.vocab.items())
    idx = vocab.to_index_array(keys)
    assert idx.dtype == np.int64
    np.testing.assert_array_equal(idx, expected)

    # missing: a vocabulary of every other key, looked up with the column
    half = BiMap.from_keys(list(forward)[::2])
    got = half.to_index_array(keys, missing=-7)
    want = np.array([half.get(k, -7) for k in rows], np.int64)
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", [n for n in COLUMNS if n.startswith("coded")])
def test_coded_pass_a_few_rows_at_a_time(name, monkeypatch):
    # a coded column is gathered and scattered in pieces (a quarter of a
    # million rows at a time): the same answers when a piece is seven rows
    from predictionio_tpu.data.storage import base

    monkeypatch.setattr(base, "_ROWS_AT_A_TIME", 7)
    test_vectorized_pass_equals_the_loop(name)


def test_first_seen_object_is_the_key():
    # the loop keeps the FIRST object that held a key; so does the pass
    col = _two_dictionaries()
    f = BiMap.factorize(col)
    first = {}
    for k in col:
        first.setdefault(k, k)
    assert all(a is first[a] for a in f.vocab)


def test_unhashable_rows_raise_as_the_loop_does():
    col = np.empty(3, object)
    col[:] = ["a", ["b"], "a"]
    with pytest.raises(TypeError):
        BiMap.factorize(col)
    with pytest.raises(TypeError):
        BiMap.from_keys(col.tolist())
