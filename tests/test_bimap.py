import numpy as np
import pytest

from predictionio_tpu.data import BiMap


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
    if not isinstance(keys, (np.ndarray, list, tuple)):
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
    assert f.codes.dtype == np.int64
    np.testing.assert_array_equal(f.codes, expected)
    # the vectorized pass hashed each distinct object once, the loop each row
    if path == "factorize":
        assert f.hashed == len({id(k) for k in rows}) <= len(rows)
    else:
        assert f.hashed == len(rows)

    # from_keys / to_index_array handed the same column take the same pass
    if not isinstance(keys, (np.ndarray, list, tuple)):
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
