"""BiMap: bidirectional value <-> index mapping — the id-vocab primitive.

The reference's BiMap (data/.../storage/BiMap.scala:28,105) maps arbitrary
string entity ids to dense integer indices so models can use array layouts;
``BiMap.stringInt`` builds the vocab from an RDD.  Here the vocab is a numpy
string array plus a hash dict, built from any iterable or numpy array, and is
TPU-friendly: ``to_index_array`` vectorizes the forward lookup for columnar
event batches.

One algorithm, first-seen dedup, and two ways to run it, chosen from the
argument alone.  A one-dimensional numpy object array whose rows share few
distinct objects (an event column decoded through its dictionary: 20 M rows
over 165 k interned strings) is factorized by POINTER in one vectorized pass
(``ptr_factorize``), and Python hashes each distinct object once: the same
object is the same dict key whatever it holds (``None``, a NaN, ``1`` beside
``1.0``), and equal keys held by distinct objects merge in the dict as they do
in the loop, so vocabulary and indices are the loop's, element for element.
Anything else — lists, generators, ``U`` arrays, object arrays whose rows are
mostly distinct objects, where the pass would save no hashing — takes the
loop a row.  (Not ``pandas.factorize`` by VALUE: its string table compares
C strings, so ``"a"`` and ``"a\0b"`` would share an index.)

A third way, for a column that comes with the store's own codes (a
``CodedColumn``: int32 codes into a dictionary of distinct values): the
pointer pass is what the codes already are, so ``factorize`` ranks the
dictionary's entries by their first row and renumbers the codes, with no
pass over pointers at all.  Same vocabulary, same indices.
"""

from __future__ import annotations

from typing import (
    Generic,
    Hashable,
    Iterable,
    Iterator,
    Mapping,
    NamedTuple,
    Sequence,
    TypeVar,
)

import numpy as np

K = TypeVar("K", bound=Hashable)


def _distinct(keys) -> tuple[np.ndarray, list] | None:
    """(int64 code of every row, the distinct objects in first-seen order)
    from one pointer-level pass over an object column, or None where the
    caller runs the loop: not a 1-d object array, empty, or rows that are
    mostly distinct objects."""
    if type(keys) is not np.ndarray or keys.dtype != object or keys.ndim != 1:
        return None
    from predictionio_tpu.data.storage.base import ptr_factorize

    f = ptr_factorize(keys)
    if f is None:
        return None
    return f[0], f[1].tolist()


class Factorized(NamedTuple):
    """What ``BiMap.factorize`` made of one column."""

    vocab: "BiMap"
    #: index of every row in ``vocab``: int64, or int32 from a coded column
    codes: np.ndarray
    #: ``factorize``: one vectorized pass over the rows, Python over the
    #: distinct objects; ``loop``: Python over every row; ``codes``: the
    #: column came coded, Python over the dictionary's entries that have a
    #: row
    path: str
    #: how many keys Python hashed to build the vocabulary
    hashed: int


class BiMap(Generic[K]):
    """Immutable bidirectional mapping between keys and dense int64 indices."""

    __slots__ = ("_forward", "_inverse_keys")

    def __init__(self, forward: Mapping[K, int]):
        n = len(forward)
        inv: list = [None] * n
        for k, i in forward.items():
            if not 0 <= i < n:
                raise ValueError(f"BiMap indices must be dense 0..{n - 1}; got {i}")
            if inv[i] is not None:
                raise ValueError(f"BiMap index {i} is not unique")
            inv[i] = k
        self._forward: dict[K, int] = dict(forward)
        self._inverse_keys: list[K] = inv

    # -- construction --------------------------------------------------------
    @classmethod
    def from_keys(cls, keys: Iterable[K]) -> "BiMap[K]":
        """Build a vocab from keys in first-seen order (deduplicating)."""
        f = _distinct(keys)
        forward: dict[K, int] = {}
        for k in (keys if f is None else f[1]):
            if k not in forward:
                forward[k] = len(forward)
        return cls.__new__(cls)._init_unchecked(forward)

    @classmethod
    def factorize(cls, keys: Iterable[K]) -> Factorized:
        """Vocabulary in first-seen order AND every key's index in it, from
        one pass over ``keys``: ``from_keys`` + ``to_index_array`` for a
        column that is needed both ways.

        ``keys`` may be a ``CodedColumn`` (an offer of the event store's, see
        ``EventFrame.coded``); the result is the one its object column
        would give, key for key and index for index.  Dictionary entries
        without a row stay out of the vocabulary, a null id is the key
        ``None`` as it is in an object column."""
        from predictionio_tpu.data.storage.base import CodedColumn

        if isinstance(keys, CodedColumn):
            return cls._factorize_coded(keys)
        f = _distinct(keys)
        if f is None:
            if not isinstance(keys, (Sequence, np.ndarray)):
                keys = list(keys)  # a generator is read once
            vocab = cls.from_keys(keys)
            return Factorized(
                vocab, vocab.to_index_array(keys), "loop", len(keys)
            )
        codes, distinct = f
        vocab = cls.from_keys(distinct)
        if len(vocab) < len(distinct):
            # equal keys held by distinct objects shared an entry
            codes = vocab.to_index_array(distinct)[codes]
        return Factorized(vocab, codes, "factorize", len(distinct))

    @classmethod
    def _factorize_coded(cls, col) -> Factorized:
        # the dictionary's entries that have a row, by their first row
        first = col.first_rows()
        seen = np.flatnonzero(first < len(col))
        seen = seen[np.argsort(first[seen], kind="stable")]
        distinct = col.dictionary[seen].tolist()
        vocab = cls.from_keys(distinct)
        rank = np.full(len(first), -1, np.int32)
        # equal keys at two codes share an entry, as they do in the loop
        rank[seen] = (
            np.arange(len(seen)) if len(vocab) == len(distinct)
            else vocab.to_index_array(distinct)
        )
        return Factorized(vocab, col.lookup(rank), "codes", len(distinct))

    @classmethod
    def string_int(cls, keys: Iterable[str]) -> "BiMap[str]":
        """Name kept for parity with the reference's BiMap.stringInt."""
        return cls.from_keys(keys)  # type: ignore[return-value]

    def _init_unchecked(self, forward: dict[K, int]) -> "BiMap[K]":
        self._forward = forward
        self._inverse_keys = list(forward)
        return self

    # -- lookups -------------------------------------------------------------
    def __getitem__(self, key: K) -> int:
        return self._forward[key]

    def get(self, key: K, default: int | None = None) -> int | None:
        return self._forward.get(key, default)

    def inverse(self, index: int) -> K:
        return self._inverse_keys[index]

    def __contains__(self, key: object) -> bool:
        return key in self._forward

    def __len__(self) -> int:
        return len(self._forward)

    def __iter__(self) -> Iterator[K]:
        return iter(self._forward)

    def items(self):
        return self._forward.items()

    # -- vectorized ----------------------------------------------------------
    def to_index_array(
        self, keys: Sequence[K] | np.ndarray, missing: int = -1
    ) -> np.ndarray:
        """Vectorized forward lookup; unknown keys map to ``missing``."""
        f = _distinct(keys)
        if f is not None:
            return self.to_index_array(f[1], missing)[f[0]]
        get = self._forward.get
        return np.fromiter(
            (get(k, missing) for k in keys), dtype=np.int64, count=len(keys)
        )

    def keys_array(self) -> np.ndarray:
        """The inverse table as a numpy array indexed by position."""
        return np.asarray(self._inverse_keys)

    # -- persistence ---------------------------------------------------------
    def to_state(self) -> np.ndarray:
        return self.keys_array()

    @classmethod
    def from_state(cls, keys: np.ndarray) -> "BiMap":
        return cls.from_keys(k.item() if hasattr(k, "item") else k for k in keys)

    def __eq__(self, other: object) -> bool:
        return isinstance(other, BiMap) and self._forward == other._forward

    def __repr__(self) -> str:
        return f"BiMap(n={len(self)})"
