"""Chunked columnar segments for out-of-core streaming scans.

The reference's cold/OLAP tier scans tables that don't fit anywhere near
RAM by reading Parquet segments from external storage
(COLD_DATA_CF/olap.proto); the device-side analog of "doesn't fit" here is
HBM: ``device_table_batch`` materializes a whole table on the accelerator,
so table size is bounded by device memory.  This module breaks that bound:

- a table snapshot is encoded ONCE through the shared host codec
  (column/batch._arrow_to_numpy) — table-wide string dictionaries, so
  per-chunk partial aggregates merge by code and hoisted string literals
  bind against one dictionary — then sliced into fixed-capacity chunks;
- each chunk persists as a Parquet segment in the coldfs tier (the
  ``coldfs.get`` failpoint therefore fires mid-streamed-scan, and reads
  retry under the PR 5 bounded-backoff-with-full-jitter policy);
- per-chunk zone maps (min/max/has_null, canonicalized exactly like
  ``column_store._zone_scalar``) let selective predicates skip whole
  chunks before any host->device transfer;
- ``load_chunk`` decodes one segment into a device ColumnBatch whose
  pytree structure is IDENTICAL for every chunk of the set (validity
  presence decided over the whole table, fixed capacity, explicit sel),
  so the streaming fold's jitted step compiles once.

The chunk set caches on the TableStore keyed by (version, chunk_rows),
mirroring the ``_table_device`` idiom.
"""

from __future__ import annotations

import io
import random
import time
from typing import Optional

import numpy as np

from ..column.batch import Column, ColumnBatch, _arrow_to_numpy
from ..obs import trace
from ..types import LType
from ..utils import metrics
from ..utils.flags import FLAGS, define
from .column_store import _zone_scalar

define("streaming_chunk_rows", 1 << 16,
       "row capacity of one streaming scan chunk: the unit of host->device "
       "transfer and the per-chunk device budget (steady-state residency "
       "is two chunks — current + prefetched)")
define("stream_retry_max", 3,
       "coldfs chunk reads retry up to this many times on a missing/"
       "failed segment (the PR 5 policy: backoff doubling + full jitter)")
define("stream_backoff_ms", 5.0,
       "initial backoff for chunk-read retries; doubles per attempt, "
       "sleeping uniform(0, backoff)")


class _HostCol:
    """Host-side column stub: what plan/paramize.bind needs from a scan
    source (string-compare params bind codes against ``.dictionary``)."""

    __slots__ = ("ltype", "dictionary")

    def __init__(self, ltype, dictionary):
        self.ltype = ltype
        self.dictionary = dictionary


class StreamChunkSet:
    """One table version sliced into fixed-capacity encoded chunks."""

    def __init__(self, table_key: str, version: int, snapshot, fs):
        import pyarrow.compute as pc

        self.table_key = table_key
        self.version = version
        self.fs = fs
        cr = max(1, int(FLAGS.streaming_chunk_rows))
        self.capacity = cr
        nrows = snapshot.num_rows
        self.total_rows = nrows
        self.n_chunks = max(1, -(-nrows // cr))
        self.live = [max(0, min(cr, nrows - i * cr))
                     for i in range(self.n_chunks)]
        self.names: tuple = ()
        self.ltypes: dict = {}
        self._dicts: dict = {}
        self._has_validity: dict = {}
        self._dtypes: dict = {}
        self.zones: dict = {}        # col -> [ (zmin, zmax, has_null) | None ]
        self._ram: dict = {}         # chunk id -> parquet bytes fallback
        names, encoded = [], {}
        for fld in snapshot.schema:
            arr = snapshot.column(fld.name).combine_chunks()
            data, validity, ltype, d = _arrow_to_numpy(arr, fld.type)
            names.append(fld.name)
            self.ltypes[fld.name] = ltype
            self._dicts[fld.name] = d
            # validity presence is a PYTREE-STRUCTURE decision: decided over
            # the whole table so every chunk traces to the same program even
            # when the nulls all sit in one chunk
            self._has_validity[fld.name] = validity is not None
            self._dtypes[fld.name] = data.dtype
            encoded[fld.name] = (data, validity)
            if (ltype.is_integer or ltype.is_float or ltype is LType.DATE
                    or ltype.is_temporal):
                zones = []
                for i in range(self.n_chunks):
                    if not self.live[i]:
                        zones.append(None)
                        continue
                    col = arr.slice(i * cr, self.live[i])
                    if col.null_count == len(col):
                        zones.append((None, None, True))
                        continue
                    mm = pc.min_max(col).as_py()
                    zones.append((_zone_scalar(mm["min"], ltype),
                                  _zone_scalar(mm["max"], ltype),
                                  col.null_count > 0))
                self.zones[fld.name] = zones
        self.names = tuple(names)
        for i in range(self.n_chunks):
            self._persist(i, encoded)
        # the encoded full-table arrays are NOT retained: from here on a
        # chunk's bytes live in coldfs (or the RAM fallback) until loaded

    # -- scan-source duck typing (what _collect_batches consumers need) --
    def __len__(self) -> int:
        return self.capacity

    def column(self, name: str) -> _HostCol:
        return _HostCol(self.ltypes[name], self._dicts[name])

    # -- persistence -----------------------------------------------------
    def _seg_name(self, i: int) -> str:
        return f"stream/{self.table_key}/v{self.version}/c{i}"

    def _persist(self, i: int, encoded: dict) -> None:
        import pyarrow as pa
        import pyarrow.parquet as pq

        lo = i * self.capacity
        arrays, names = [], []
        for name in self.names:
            data, validity = encoded[name]
            arrays.append(pa.array(data[lo:lo + self.live[i]]))
            names.append(name)
            if validity is not None:
                arrays.append(pa.array(validity[lo:lo + self.live[i]]))
                names.append(f"__v_{name}")
        buf = io.BytesIO()
        pq.write_table(pa.table(arrays, names=names), buf)
        payload = buf.getvalue()
        if self.fs is None:
            self._ram[i] = payload
            return
        name = self._seg_name(i)
        self.fs.put(name, payload)
        if not self.fs.exists(name):
            # coldfs.put dropped the bytes (manifest-without-segment): keep
            # the RAM copy so the scan cannot lose the chunk
            self._ram[i] = payload

    def _read_segment(self, i: int) -> bytes:
        if self.fs is None or i in self._ram:
            return self._ram[i]
        name = self._seg_name(i)
        backoff = max(0.0, float(FLAGS.stream_backoff_ms)) / 1000.0
        attempts = max(0, int(FLAGS.stream_retry_max)) + 1
        rng = random.Random()           # plain jitter, NOT the chaos RNG
        last = None
        for attempt in range(attempts):
            try:
                return self.fs.get(name)
            except (FileNotFoundError, OSError) as e:
                last = e
                if attempt + 1 >= attempts:
                    break
                metrics.stream_retries.add(1)
                time.sleep(rng.uniform(0.0, backoff))
                backoff *= 2.0
        raise last

    # -- pruning + device load -------------------------------------------
    def pruned(self, ranges: dict) -> list[int]:
        """Chunk ids whose zone maps can satisfy every [lo, hi] constraint
        (the prune_regions contract: conservative — any uncertainty keeps
        the chunk; an all-NULL chunk can satisfy no comparison)."""
        keep = []
        for i in range(self.n_chunks):
            if not self.live[i]:
                continue
            alive = True
            for col, (lo, hi) in (ranges or {}).items():
                zones = self.zones.get(col)
                if zones is None or zones[i] is None:
                    continue
                zmin, zmax, _ = zones[i]
                if zmin is None:
                    alive = False
                    break
                lt = self.ltypes[col]
                lo_c = _zone_scalar(lo, lt)
                hi_c = _zone_scalar(hi, lt)
                if lo_c is not None and zmax < lo_c:
                    alive = False
                    break
                if hi_c is not None and zmin > hi_c:
                    alive = False
                    break
            if alive:
                keep.append(i)
        return keep

    def device_struct(self):
        """The ShapeDtypeStruct pytree every ``load_chunk`` result matches —
        what the streaming fold traces against before any chunk loads."""
        import jax
        import jax.numpy as jnp

        cap = self.capacity
        cols = []
        for name in self.names:
            data = jax.ShapeDtypeStruct((cap,), self._dtypes[name])
            validity = jax.ShapeDtypeStruct((cap,), jnp.bool_) \
                if self._has_validity[name] else None
            cols.append(Column(data, validity, self.ltypes[name],
                               self._dicts[name]))
        return ColumnBatch(self.names, cols,
                           jax.ShapeDtypeStruct((cap,), jnp.bool_),
                           None, live_prefix=True)

    def load_chunk(self, i: int, dead: bool = False):
        """-> (device ColumnBatch, bytes moved host->device, {seam: ms}).

        Every chunk of the set has the same structure: fixed capacity,
        explicit ``sel = arange < live`` (all-False when ``dead`` — the
        empty-input stand-in when pruning removed every chunk), validity
        arrays exactly on the columns the whole table has them.

        The three seams (segment read; parquet decode + numpy padding;
        host->device put, which returns once the transfer is enqueued) are
        timed here and handed back: this runs on the stager thread, which
        has no statement to credit (exec/streaming.py does that).  Each
        column's put is issued before the next column is padded, so decode
        and h2d are sums over the columns."""
        import jax.numpy as jnp
        import pyarrow.parquet as pq

        with trace.timed("stream.stage.read") as t_read:
            payload = self._read_segment(i)
        with trace.timed("stream.stage.decode") as sp:
            t = pq.read_table(io.BytesIO(payload))
        decode_ms, h2d_ms = sp.ms, 0.0
        live = 0 if dead else self.live[i]
        cap = self.capacity
        cols, nbytes = [], 0
        for name in self.names:
            with trace.timed("stream.stage.decode") as sp:
                data = t.column(name).to_numpy(zero_copy_only=False)
                data = np.ascontiguousarray(data.astype(self._dtypes[name],
                                                        copy=False))
                if len(data) < cap:
                    pad = np.zeros(cap - len(data), dtype=data.dtype)
                    data = np.concatenate([data, pad])
                validity = None
                if self._has_validity[name]:
                    if f"__v_{name}" in t.column_names:
                        validity = t.column(f"__v_{name}").to_numpy(
                            zero_copy_only=False).astype(bool)
                    else:
                        validity = np.ones(self.live[i], dtype=bool)
                    if len(validity) < cap:
                        validity = np.concatenate(
                            [validity, np.zeros(cap - len(validity), bool)])
            decode_ms += sp.ms
            nbytes += data.nbytes + (validity.nbytes if validity is not None
                                     else 0)
            with trace.timed("stream.stage.h2d") as sp:
                cols.append(Column.from_numpy(data, self.ltypes[name],
                                              validity, self._dicts[name]))
            h2d_ms += sp.ms
        sel = np.arange(cap) < live
        nbytes += sel.nbytes
        with trace.timed("stream.stage.h2d") as sp:
            batch = ColumnBatch(self.names, cols, jnp.asarray(sel), None,
                                live_prefix=True)
        return batch, nbytes, {"stream.stage.read": t_read.ms,
                               "stream.stage.decode": decode_ms,
                               "stream.stage.h2d": h2d_ms + sp.ms}


class ChunkSource:
    """One execution's view of a chunk set: the chunk ids this query's
    predicate zone maps kept.  This is what rides the batches dict in a
    ScanNode's slot — exec/streaming.py recognizes it and takes the
    chunk-folded path instead of feeding it to a jitted program."""

    def __init__(self, chunks: StreamChunkSet, keep: list[int]):
        self.chunks = chunks
        self.keep = keep

    def __len__(self) -> int:
        return self.chunks.capacity

    @property
    def names(self) -> tuple:
        return self.chunks.names

    def column(self, name: str) -> _HostCol:
        return self.chunks.column(name)


def chunk_set(store, table_key: str, fs) -> StreamChunkSet:
    """The store's chunk set for its current version (the _table_device
    caching idiom: rebuilt only when the version or chunk size moves)."""
    with store._lock:
        v = store.version
        key = (v, max(1, int(FLAGS.streaming_chunk_rows)))
        cached = getattr(store, "_stream_chunks", None)
        if cached is not None and getattr(store, "_stream_chunks_key",
                                          None) == key:
            return cached
        cs = StreamChunkSet(table_key, v, store.snapshot(), fs)
        store._stream_chunks = cs
        store._stream_chunks_key = key
        return cs
