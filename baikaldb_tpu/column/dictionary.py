"""Host-side string dictionaries: a sorted base, grown by append.

TPUs cannot chase variable-length string bytes; the reference's columnar path
keeps strings as Arrow utf8 arrays and runs string kernels on CPU
(``src/expr/arrow_string_function.cpp``).  The TPU-native design instead
dictionary-encodes every string column at ingest:

- the *codes* (int32) live on device and flow through every kernel;
- the *dictionary* (a numpy array of distinct strings, indexed by code) stays
  on the host, attached to the column metadata.

A dictionary built from a column (``from_arrow``, ``encode``) is sorted, so
its codes are in value order:

- ``=  <  <= >  >=`` against a literal compile to integer comparisons on codes
  (via host-side binary search for the literal's code / insertion point);
- ``LIKE 'prefix%'`` compiles to a code-range test;
- arbitrary string functions (LENGTH, UPPER, SUBSTR, regexp) are evaluated once
  per *distinct* value on the host, producing a lookup table gathered by code on
  device — O(|dict|) host work instead of O(N) row work.

A store's resident image takes writes as patches (storage/column_store), so
its dictionaries grow: a new value is *appended* — it gets the next code past
everything there, and no resident code moves.  Such a dictionary is a
snapshot ``(lineage, n)`` of a :class:`Lineage`, the append-only state every
snapshot of one store column shares: the sorted base, then a tail in arrival
order.  Codes below ``n`` mean the same value in every snapshot.  While a
snapshot has no tail its codes are still in value order and it behaves (and
keys executables) exactly as a sorted dictionary of its values.  Once it has
a tail, order lives in data: every code has an int32 *order key*
(``Lineage.keys``; the base spaced ``gap`` apart, an appended value the
midpoint of its neighbours' keys), comparisons and LIKE prefixes bind their
bounds as keys (``lower_bound`` / ``upper_bound`` / ``prefix_range`` answer
in key space), and sorts, MIN / MAX and comparisons read ``order_data`` —
the codes' keys, gathered from the key array the program is handed as an
input (``KEYS_KEY``), not baked into it.  A tailed snapshot keys executables
on its base and its lineage, not on its values (the key array's capacity is
a leaf shape), so an append does not retrace.  A program that did read a tailed snapshot's
values while it was traced (a string function's table, a join's merge)
records ``(lineage, n)`` in :data:`BAKED`; the session traces it again once
a later snapshot holds codes past ``n``.

Cross-column string ops (joins, group-bys spanning two tables) remap one side's
codes through a host-computed translation table (`translate_codes`).
"""

from __future__ import annotations

import bisect
import contextlib
import contextvars
import itertools
import threading

import jax
import numpy as np

NULL_CODE = np.int32(-1)
#: the batches-dict key the order-key arrays of tailed dictionaries ride
#: under (lineage token -> int32[capacity]); read inside a trace through
#: :func:`bind_keys`
KEYS_KEY = "__dict_keys__"
KEY_MAX = np.int32(2**31 - 1)       # past every order key
_MIN_KEY_CAP = 1024

_LINEAGE_IDS = itertools.count(1)
# (lineage id -> largest n read) while a program traces; None outside
BAKED: contextvars.ContextVar = contextvars.ContextVar("dict_baked",
                                                       default=None)
_BOUND_KEYS: contextvars.ContextVar = contextvars.ContextVar("dict_keys",
                                                             default=None)


@contextlib.contextmanager
def recording_bakes():
    """Collect, while a program traces, the tailed snapshots whose values
    it read: -> the dict it fills (lineage id -> n)."""
    out: dict = {}
    tok = BAKED.set(out)
    try:
        yield out
    finally:
        BAKED.reset(tok)


@contextlib.contextmanager
def bind_keys(keys: dict):
    """The order-key arrays a traced program was handed (``KEYS_KEY``)."""
    tok = _BOUND_KEYS.set(keys or {})
    try:
        yield
    finally:
        _BOUND_KEYS.reset(tok)


def _pow2(n: int, floor: int = _MIN_KEY_CAP) -> int:
    return max(floor, 1 << max(0, int(n) - 1).bit_length())


class Lineage:
    """The append-only state every snapshot of one store column's
    dictionary shares: the sorted ``base``, a ``tail`` of appended values,
    and an int32 order key a code (built on the first append)."""

    def __init__(self, base: np.ndarray, fingerprint=None):
        self.id = next(_LINEAGE_IDS)
        self.token = f"L{self.id}"
        self.base = base
        self.nb = len(base)
        self.tail: list = []            # appended values, code nb + i
        self.tail_index: dict = {}      # value -> code
        self.tail_sorted: list = []     # appended values in value order
        self.tail_sorted_keys: list = []
        self.keys = None                # int32[capacity], built lazily
        self.epoch = 0                  # bumped when the keys are respaced
        self.respaces = 0
        self._fp = fingerprint
        self._values = (0, None)        # (n, base + tail[:n - nb]) cache
        self._dev = None                # (epoch, n, device array)
        self._lock = threading.RLock()

    @property
    def n(self) -> int:
        with self._lock:
            return self.nb + len(self.tail)

    def fingerprint(self) -> bytes:
        if self._fp is None:
            self._fp = _content_digest(self.base)
        return self._fp

    def values(self, n: int) -> np.ndarray:
        if n <= self.nb:
            return self.base[:n] if n < self.nb else self.base
        with self._lock:
            have_n, have = self._values
            if have_n != n:
                have = np.concatenate([np.asarray(self.base, dtype=object),
                                       np.asarray(self.tail[:n - self.nb],
                                                  dtype=object)])
                self._values = (n, have)
            return have

    def tail_values(self, codes) -> list:
        """The appended values of ``codes`` (each at least ``nb``)."""
        with self._lock:
            return [self.tail[int(x) - self.nb] for x in codes]

    # -- order keys -------------------------------------------------------
    def _spacing(self, n: int) -> int:
        return max(1, int(KEY_MAX) // (n + 2))

    def _build_keys_locked(self) -> None:
        gap = self._spacing(self.nb)
        keys = np.full(_pow2(self.nb + 1), -1, np.int32)
        keys[:self.nb] = (np.arange(1, self.nb + 1, dtype=np.int64)
                          * gap).astype(np.int32)
        self.keys = keys

    def _respace(self) -> None:
        """Every key again ``gap`` apart in value order (an appended value
        found no room between its neighbours)."""
        n = self.n
        tv = np.asarray(self.tail_sorted, dtype=object)
        pos_b = np.searchsorted(self.base, tv) if len(tv) else \
            np.zeros(0, np.int64)
        rank = np.empty(n, np.int64)
        rank[:self.nb] = np.arange(self.nb) + np.searchsorted(
            pos_b, np.arange(self.nb), side="right")
        tail_rank = pos_b + np.arange(len(tv))
        gap = self._spacing(n)
        keys = np.full(len(self.keys), -1, np.int32)
        keys[:self.nb] = ((rank[:self.nb] + 1) * gap).astype(np.int32)
        sorted_keys = []
        for v, r in zip(self.tail_sorted, tail_rank):
            k = np.int32((int(r) + 1) * gap)
            keys[self.tail_index[v]] = k
            sorted_keys.append(int(k))
        self.tail_sorted_keys = sorted_keys
        self.keys = keys
        self.epoch += 1
        self.respaces += 1

    def _neighbour_keys(self, v) -> tuple:
        """(key of the largest value below v, key of the smallest above)."""
        i = int(np.searchsorted(self.base, v))
        lo = int(self.keys[i - 1]) if i > 0 else 0
        hi = int(self.keys[i]) if i < self.nb else int(KEY_MAX)
        j = bisect.bisect_left(self.tail_sorted, v)
        if j > 0:
            lo = max(lo, self.tail_sorted_keys[j - 1])
        if j < len(self.tail_sorted):
            hi = min(hi, self.tail_sorted_keys[j])
        return lo, hi

    def append(self, v) -> int:
        """The code of ``v``, appended when absent (caller holds the
        store's lock: one writer at a time)."""
        code = self.code_of(v, self.n)
        if code is not None:
            return code
        with self._lock:
            if self.keys is None:
                self._build_keys_locked()
            code = self.n
            if code >= len(self.keys):
                grown = np.full(2 * len(self.keys), -1, np.int32)
                grown[:len(self.keys)] = self.keys
                self.keys = grown
            lo, hi = self._neighbour_keys(v)
            if hi - lo < 2:
                self.tail_index[v] = code
                self.tail.append(v)
                j = bisect.bisect_left(self.tail_sorted, v)
                self.tail_sorted.insert(j, v)
                self.tail_sorted_keys.insert(j, 0)
                self._respace()
                return code
            k = (lo + hi) // 2
            self.keys[code] = k
            j = bisect.bisect_left(self.tail_sorted, v)
            self.tail_sorted.insert(j, v)
            self.tail_sorted_keys.insert(j, k)
            self.tail_index[v] = code
            self.tail.append(v)     # last: a reader sees n only once set
            return code

    def keys_device(self):
        """The order keys on the device: uploaded whole once an epoch and
        capacity, then only the keys appended since."""
        import jax.numpy as jnp

        with self._lock:
            if self.keys is None:
                self._build_keys_locked()
            n, dev = self.n, self._dev
            if dev is not None and dev[0] == self.epoch \
                    and dev[2].shape[0] == len(self.keys):
                if dev[1] == n:
                    return dev[2]
                idx = np.arange(dev[1], n, dtype=np.int32)
                arr = _scatter_keys(dev[2], idx, self.keys[idx])
            else:
                arr = jnp.asarray(self.keys)
            self._dev = (self.epoch, n, arr)  # tpulint: disable=TRACERLEAK
            return arr

    # -- lookups over the snapshot [0, n) ----------------------------------
    def code_of(self, s, n: int):
        i = int(np.searchsorted(self.base, s))
        if i < self.nb and self.base[i] == s:
            return i
        with self._lock:
            c = self.tail_index.get(s)
        return c if c is not None and c < n else None

    def key_bound(self, s, side: str) -> int:
        """The smallest order key of a value >= s (``left``) or > s
        (``right``); KEY_MAX when there is none."""
        with self._lock:
            if self.keys is None:
                self._build_keys_locked()
            i = int(np.searchsorted(self.base, s, side=side))
            k = int(self.keys[i]) if i < self.nb else int(KEY_MAX)
            j = (bisect.bisect_left if side == "left"
                 else bisect.bisect_right)(self.tail_sorted, s)
            if j < len(self.tail_sorted):
                k = min(k, self.tail_sorted_keys[j])
            return k


def _scatter_keys(dev, idx: np.ndarray, vals: np.ndarray):
    """``dev`` with ``vals`` at ``idx``, padded to a power of two so the
    program compiles once a size class."""
    m = _pow2(len(idx), 8)
    pi = np.full(m, dev.shape[0], np.int32)
    pv = np.zeros(m, np.int32)
    pi[:len(idx)], pv[:len(idx)] = idx, vals
    return _set_keys(dev, pi, pv)


@jax.jit
def _set_keys(dev, idx, vals):
    return dev.at[idx].set(vals, mode="drop")


def _content_digest(arr) -> bytes:
    import hashlib

    h = hashlib.blake2b(digest_size=16)
    h.update(str(len(arr)).encode())
    if arr.dtype.kind == "U":
        h.update(str(arr.dtype).encode())
        h.update(arr.tobytes())
    else:
        for v in arr:
            b = str(v).encode("utf-8")
            h.update(len(b).to_bytes(4, "little"))
            h.update(b)
    return h.digest()


class Dictionary:
    """The dictionary of one string column: a sorted array of values, or a
    snapshot ``(lineage, n)`` of a growing one (module docstring)."""

    __slots__ = ("_values", "_lin", "_n", "_id", "_ft_index", "_ft_state",
                 "_hash_cache", "_fp")

    def __init__(self, values: np.ndarray, lineage: Lineage | None = None,
                 n: int | None = None):
        # values must be sorted unique unicode/objects (unless a lineage
        # snapshot, which reads its own)
        self._values = values
        self._lin = lineage
        self._n = len(values) if lineage is None else int(n)
        self._id = id(values) if lineage is None else lineage.token
        self._ft_index = None   # lazily-built fulltext index (index/fulltext)
        self._ft_state = None   # per-dictionary BM25 state (fulltext)
        self._hash_cache = None
        self._fp = None         # lazy content fingerprint (see __eq__)

    # -- growth -----------------------------------------------------------
    @staticmethod
    def growable(d: "Dictionary") -> "Dictionary":
        """The first snapshot of a new lineage whose base is ``d``'s
        values (no tail yet: it keys executables as ``d`` does)."""
        if d._lin is not None:
            return d
        lin = Lineage(d._values, d._fp)
        return Dictionary(d._values, lin, lin.nb)

    @property
    def lineage(self) -> Lineage | None:
        return self._lin

    @property
    def tailed(self) -> bool:
        """True when codes past the sorted base exist: the codes are then
        not in value order, and order is read from the keys."""
        return self._lin is not None and self._n > self._lin.nb

    def current(self) -> "Dictionary":
        """The newest snapshot of this dictionary's lineage (itself for a
        sorted dictionary)."""
        lin = self._lin
        if lin is None or lin.n == self._n:
            return self
        return Dictionary(lin.base, lin, lin.n)

    def _baked(self) -> None:
        """A read of a tailed snapshot's values: noted while a program
        traces, which is then valid only up to this ``n``."""
        rec = BAKED.get()
        if rec is not None and self.tailed:
            lin = self._lin
            rec[lin.id] = max(rec.get(lin.id, 0), self._n)

    @property
    def values(self) -> np.ndarray:
        if self._lin is None:
            return self._values
        self._baked()
        return self._lin.values(self._n)

    # -- value equality ---------------------------------------------------
    # Dictionaries ride pytree aux data (column/batch.Column), so jax.jit
    # keys compiled executables on them.  Identity semantics would retrace
    # every query on a string column after ANY table rebuild (each allocates
    # a fresh Dictionary even when the distinct values are unchanged).
    # Content equality via a cached digest keeps aux comparison O(1) after
    # the first hash, and a changed value set (which really does invalidate
    # traced code constants) still misses.  A tailed snapshot is keyed on
    # its base, its lineage and the capacity of its keys: its values are
    # data (module docstring), so an append inside the capacity keeps the
    # executable.
    def _fingerprint(self) -> bytes:
        if self._fp is None:
            if self._lin is None:
                self._fp = _content_digest(self._values)
            elif not self.tailed:
                self._fp = self._lin.fingerprint()
            else:
                # the key array's capacity is a leaf shape: the session's
                # shape key carries it (exec/session._run_plan)
                lin = self._lin
                self._fp = b"T" + lin.fingerprint() + lin.token.encode()
        return self._fp

    def __eq__(self, other):
        if self is other:
            return True
        if not isinstance(other, Dictionary):
            return NotImplemented
        return self._fingerprint() == other._fingerprint()

    def __hash__(self):
        return hash(self._fingerprint())

    # -- construction ---------------------------------------------------
    @staticmethod
    def encode(strings) -> tuple["Dictionary", np.ndarray]:
        """Encode an iterable of python strings (None allowed) -> (dict, codes)."""
        arr = np.asarray(["" if s is None else s for s in strings], dtype=object)
        mask = np.asarray([s is None for s in strings], dtype=bool)
        uniq, inv = np.unique(arr.astype(str), return_inverse=True)
        codes = inv.astype(np.int32)
        codes[mask] = NULL_CODE
        return Dictionary(uniq), codes

    @staticmethod
    def from_arrow(arr) -> tuple["Dictionary", np.ndarray]:
        """Encode a pyarrow string/dictionary Array -> (dict, codes)."""
        import pyarrow.compute as pc

        d = pc.dictionary_encode(arr.combine_chunks() if hasattr(arr, "combine_chunks") else arr)
        if hasattr(d, "chunks"):
            d = d.combine_chunks()
        values = np.asarray(d.dictionary.to_pylist(), dtype=str)
        null_mask = np.asarray(d.indices.is_null())
        if len(values) == 0:
            # all-NULL column: empty dictionary, every code NULL
            return Dictionary(values), np.full(len(arr), NULL_CODE, np.int32)
        codes = d.indices.fill_null(0).to_numpy(zero_copy_only=False).astype(np.int32)
        order = np.argsort(values, kind="stable")
        rank = np.empty_like(order)
        rank[order] = np.arange(len(order))
        codes = np.where(null_mask, NULL_CODE, rank[np.clip(codes, 0, None)]).astype(np.int32)
        return Dictionary(values[order]), codes

    # -- lookups --------------------------------------------------------
    def __len__(self) -> int:
        self._baked()
        return self._n

    def code_of(self, s: str) -> int | None:
        """Exact code of s, or None if absent."""
        if self._lin is not None:
            self._baked()
            return self._lin.code_of(s, self._n)
        i = int(np.searchsorted(self._values, s))
        if i < len(self._values) and self._values[i] == s:
            return i
        return None

    # Bounds are in *order space*: codes while the codes are in value order,
    # order keys once the snapshot has a tail (compare them with
    # ``order_data``, never with raw codes).
    def lower_bound(self, s: str) -> int:
        if self.tailed:
            self._baked()
            return self._lin.key_bound(s, "left")
        base = self._values if self._lin is None else self._lin.base
        return int(np.searchsorted(base, s, side="left"))

    def upper_bound(self, s: str) -> int:
        if self.tailed:
            self._baked()
            return self._lin.key_bound(s, "right")
        base = self._values if self._lin is None else self._lin.base
        return int(np.searchsorted(base, s, side="right"))

    def prefix_range(self, prefix: str) -> tuple[int, int]:
        """[lo, hi) order-space range of values starting with prefix
        (LIKE 'p%')."""
        # upper sentinel: max code point so astral-plane chars stay in range
        return self.lower_bound(prefix), \
            self.upper_bound(prefix + "\U0010FFFF")

    def map_values(self, fn, out_dtype) -> np.ndarray:
        """Host-evaluate fn over distinct values -> device gather table."""
        return np.asarray([fn(v) for v in self.values], dtype=out_dtype)

    def match_mask(self, pred) -> np.ndarray:
        """Boolean per-code table for an arbitrary string predicate."""
        return np.asarray([bool(pred(v)) for v in self.values], dtype=bool)

    def value_hashes(self) -> np.ndarray:
        """Per-code uint32 hash of the VALUE (not the code).  Equal strings
        hash equal across different dictionaries, so shuffle partitioning of
        string keys (parallel/shuffle.py) co-locates matches from two tables
        without a host-side dictionary merge; collisions only affect load
        balance, never correctness."""
        if self._hash_cache is None:
            import zlib

            self._hash_cache = np.asarray(
                [zlib.crc32(v.encode("utf-8")) for v in self.values],
                dtype=np.uint32)
        return self._hash_cache

    def decode(self, codes: np.ndarray) -> np.ndarray:
        out = np.empty(len(codes), dtype=object)
        valid = codes >= 0
        if self._lin is None:
            out[valid] = self._values[codes[valid]]
        else:
            lin = self._lin
            c = codes[valid]
            got = np.empty(len(c), dtype=object)
            inb = c < lin.nb
            got[inb] = lin.base[c[inb]]
            got[~inb] = lin.tail_values(c[~inb])
            out[valid] = got
        out[~valid] = None
        return out


def order_data(c):
    """What orders a column's rows: its data, or — for a tailed string
    dictionary — each code's order key, gathered from the key array the
    program was handed (a baked copy where none was: an eager caller)."""
    import jax.numpy as jnp

    d = c.dictionary
    if not getattr(d, "tailed", False):
        return c.data
    keys = (_BOUND_KEYS.get() or {}).get(d.lineage.token)
    if keys is None:
        d._baked()
        keys = jnp.asarray(d.lineage.keys)
    return jnp.take(keys, jnp.clip(c.data, 0, keys.shape[0] - 1))


def merge(a: Dictionary, b: Dictionary) -> tuple[Dictionary, np.ndarray, np.ndarray]:
    """Merge two dictionaries -> (merged, remap_a, remap_b).

    remap_x maps old codes of x into the merged dictionary; used to align two
    string columns before a device-side join/compare (the TPU analog of the
    reference comparing raw bytes in hash-join keys, src/exec/joiner.cpp).
    """
    values = np.union1d(a.values, b.values)
    remap_a = np.searchsorted(values, a.values).astype(np.int32)
    remap_b = np.searchsorted(values, b.values).astype(np.int32)
    return Dictionary(values), remap_a, remap_b


def translate_codes(codes: np.ndarray, remap: np.ndarray) -> np.ndarray:
    out = np.where(codes >= 0, remap[np.clip(codes, 0, None)], NULL_CODE)
    return out.astype(np.int32)
