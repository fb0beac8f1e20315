"""Device-resident columnar batches — the TPU analog of Arrow RecordBatch.

The reference's execution unit is a row batch (``include/runtime/row_batch.h``)
with a columnar sibling built by ``Chunk`` (``include/runtime/chunk.h:27``:
tuples -> arrow::ArrayBuilders -> RecordBatch).  Here the execution unit is a
:class:`ColumnBatch`: a pytree of fixed-width jax arrays (one per column, plus
optional validity masks and an optional row-selection mask) that flows through
jit-compiled kernels.

Key deviations from the Arrow model, driven by XLA:

- **Static shapes**: a batch's row count is a compile-time constant.  Filters do
  NOT shrink batches; they refine the ``sel`` mask (late materialization).  The
  ``compact`` kernel (ops/compact.py) materializes a dense prefix when an op
  needs one.
- **Strings are int32 codes** into host-side sorted dictionaries
  (column/dictionary.py).
- **Validity is a bool array**, not a bitmask — XLA vectorizes bool ops fine and
  bit-twiddling would fight the VPU.  ``validity=None`` means all-valid.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..types import Field, LType, Schema
from .dictionary import NULL_CODE, Dictionary


@jax.tree_util.register_pytree_node_class
@dataclass
class Column:
    """One column: device data + optional validity + static metadata."""

    data: Any                       # jnp array [N]
    validity: Optional[Any] = None  # jnp bool [N] or None (all valid)
    ltype: LType = LType.INT64      # static
    dictionary: Optional[Dictionary] = None  # static, host-side (strings only)

    # -- pytree protocol ------------------------------------------------
    def tree_flatten(self):
        return (self.data, self.validity), (self.ltype, self.dictionary)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, validity = children
        ltype, dictionary = aux
        return cls(data=data, validity=validity, ltype=ltype, dictionary=dictionary)

    def __len__(self) -> int:
        return self.data.shape[0]

    def valid_mask(self) -> Any:
        if self.validity is None:
            return jnp.ones(jnp.shape(self.data), dtype=bool)
        return self.validity

    def with_data(self, data, validity="keep") -> "Column":
        if validity == "keep":
            validity = self.validity
        return replace(self, data=data, validity=validity)

    # -- host conversion ------------------------------------------------
    @staticmethod
    def from_numpy(arr: np.ndarray, ltype: LType, validity: np.ndarray | None = None,
                   dictionary: Dictionary | None = None) -> "Column":
        return Column(jnp.asarray(arr), None if validity is None else jnp.asarray(validity),
                      ltype, dictionary)

    def to_numpy(self):
        """-> (np data, np validity-or-None); strings stay as codes."""
        v = None if self.validity is None else np.asarray(self.validity)
        return np.asarray(self.data), v


@jax.tree_util.register_pytree_node_class
@dataclass
class ColumnBatch:
    """An ordered set of equal-length columns plus an optional selection mask.

    ``sel`` (bool [N] or None) marks live rows — the late-materialization analog
    of the reference's filtered RowBatch.  ``num_rows`` when set is a *traced
    scalar* giving the count of live rows among the leading prefix (set by
    ``compact``); None means sel/all rows are authoritative.

    ``live_prefix`` (static) is the capacity-bucketing promise: every live row
    sits in a leading prefix and ``sel`` equals ``arange(capacity) < live``
    (set by ``pad_batch`` on bucketed store batches).  Consumers may then skip
    the stable-partition gather that ``compact`` otherwise needs.
    """

    names: tuple  # static
    columns: list  # list[Column]
    sel: Optional[Any] = None
    num_rows: Optional[Any] = None
    live_prefix: bool = False  # static

    def tree_flatten(self):
        return (self.columns, self.sel, self.num_rows), \
            (self.names, self.live_prefix)

    @classmethod
    def tree_unflatten(cls, aux, children):
        columns, sel, num_rows = children
        return cls(names=aux[0], columns=list(columns), sel=sel,
                   num_rows=num_rows, live_prefix=aux[1])

    # -- accessors ------------------------------------------------------
    def __len__(self) -> int:
        return 0 if not self.columns else self.columns[0].data.shape[0]

    @property
    def capacity(self) -> int:
        return len(self)

    def column(self, name: str) -> Column:
        return self.columns[self.names.index(name)]

    def __contains__(self, name: str) -> bool:
        return name in self.names

    def sel_mask(self) -> Any:
        if self.sel is None:
            return jnp.ones(len(self), dtype=bool)
        return self.sel

    def live_count(self):
        """Traced count of live rows."""
        if self.num_rows is not None:
            return self.num_rows
        if self.sel is None:
            return jnp.int32(len(self))
        return jnp.sum(self.sel).astype(jnp.int32)

    # -- functional updates --------------------------------------------
    def with_sel(self, sel) -> "ColumnBatch":
        return ColumnBatch(self.names, self.columns, sel, None)

    def and_sel(self, mask) -> "ColumnBatch":
        sel = mask if self.sel is None else jnp.logical_and(self.sel, mask)
        return ColumnBatch(self.names, self.columns, sel, None)

    def select(self, names: list[str]) -> "ColumnBatch":
        cols = [self.column(n) for n in names]
        return ColumnBatch(tuple(names), cols, self.sel, self.num_rows,
                           live_prefix=self.live_prefix)

    def append_column(self, name: str, col: Column) -> "ColumnBatch":
        return ColumnBatch(self.names + (name,), self.columns + [col],
                           self.sel, self.num_rows,
                           live_prefix=self.live_prefix)

    def rename(self, names: list[str]) -> "ColumnBatch":
        return ColumnBatch(tuple(names), self.columns, self.sel,
                           self.num_rows, live_prefix=self.live_prefix)

    def gather(self, idx, valid=None) -> "ColumnBatch":
        """Row gather; idx traced int array, valid optional mask for out rows."""
        cols = []
        for c in self.columns:
            data = jnp.take(c.data, idx, axis=0, mode="clip")
            if c.validity is not None:
                v = jnp.take(c.validity, idx, mode="clip")
                if valid is not None:
                    v = jnp.logical_and(v, valid)
            else:
                v = valid
            cols.append(replace(c, data=data, validity=v))
        return ColumnBatch(self.names, cols, None, None)

    def schema(self) -> Schema:
        return Schema(tuple(Field(n, c.ltype) for n, c in zip(self.names, self.columns)))

    # -- host <-> device ------------------------------------------------
    @staticmethod
    def from_arrow(table) -> "ColumnBatch":
        """Build from a pyarrow Table/RecordBatch (host->device ingest).

        The analog of the reference's row->column conversion
        (src/store/row2column, include/runtime/chunk.h), with string columns
        dictionary-encoded (see column/dictionary.py).
        """
        import pyarrow as pa

        names, cols = [], []
        for fld in table.schema:
            arr = table.column(fld.name)
            if hasattr(arr, "combine_chunks"):
                arr = arr.combine_chunks()
            names.append(fld.name)
            cols.append(_arrow_to_column(arr, fld.type))
        return ColumnBatch(tuple(names), cols)

    def to_arrow(self):
        """Densify + decode back to a pyarrow Table (device->host egress).

        Used by the result-packet layer (the reference renders MySQL packets in
        src/exec/packet_node.cpp from Arrow tables on the vectorized path)."""
        import pyarrow as pa

        # every leaf in one transfer: the copies are started together and
        # waited for once, not one device round trip a leaf
        sel, n, leaves = jax.device_get(
            (self.sel, self.num_rows,
             [(c.data, c.validity) for c in self.columns]))
        sel = None if sel is None else np.asarray(sel)
        n = None if n is None else int(n)
        arrays, fields = [], []
        for name, c, (data, valid) in zip(self.names, self.columns, leaves):
            data = np.asarray(data)
            valid = None if valid is None else np.asarray(valid)
            if n is not None:
                data = data[:n]
                valid = None if valid is None else valid[:n]
            elif sel is not None:
                data = data[sel]
                valid = None if valid is None else valid[sel]
            arrays.append(_column_to_arrow(c, data, valid))
            fields.append(pa.field(name, arrays[-1].type))
        return pa.table(arrays, schema=pa.schema(fields))

    def to_pylist(self) -> list[dict]:
        return self.to_arrow().to_pylist()


# ----------------------------------------------------------------------
_ARROW_LTYPE = None


def _arrow_ltype_map():
    global _ARROW_LTYPE
    if _ARROW_LTYPE is None:
        import pyarrow as pa

        _ARROW_LTYPE = {
            pa.bool_(): LType.BOOL,
            pa.int8(): LType.INT8,
            pa.int16(): LType.INT16,
            pa.int32(): LType.INT32,
            pa.int64(): LType.INT64,
            pa.uint32(): LType.UINT32,
            pa.uint64(): LType.UINT64,
            pa.float32(): LType.FLOAT32,
            pa.float64(): LType.FLOAT64,
            pa.date32(): LType.DATE,
            pa.timestamp("us"): LType.DATETIME,
        }
    return _ARROW_LTYPE


def _arrow_to_numpy(arr, typ):
    """Host half of the Arrow->device codec: -> (np data, np validity-or-
    None, ltype, dictionary-or-None).  The streaming chunk layer
    (storage/streamchunks.py) encodes a whole snapshot through this once —
    table-wide string dictionaries, the null-fill discipline — and slices
    chunks host-side; resident ingest wraps the same arrays in jnp below."""
    import pyarrow as pa
    import pyarrow.compute as pc

    if pa.types.is_string(typ) or pa.types.is_large_string(typ) or pa.types.is_dictionary(typ):
        d, codes = Dictionary.from_arrow(arr)
        validity = codes != NULL_CODE if arr.null_count else None
        return codes, validity, LType.STRING, d
    if pa.types.is_decimal(typ):
        arr = pc.cast(arr, pa.float64())
        typ = pa.float64()
    if pa.types.is_date32(typ):
        ltype = LType.DATE
        work = arr.cast(pa.int32())
    elif pa.types.is_timestamp(typ):
        ltype = LType.DATETIME
        work = arr.cast(pa.timestamp("us")).cast(pa.int64())
    else:
        ltype = _arrow_ltype_map().get(typ)
        if ltype is None:
            raise TypeError(f"unsupported arrow type {typ}")
        work = arr
    if arr.null_count:
        validity = ~np.asarray(arr.is_null())
        if not pa.types.is_floating(work.type):
            # fill nulls at the Arrow level: pyarrow's to_numpy renders a
            # null-bearing int array as float64+NaN, which corrupts 64-bit
            # integers beyond 2^53 (caught in round-2 regression)
            fill = False if pa.types.is_boolean(work.type) else 0
            work = pc.fill_null(work, fill)
        np_data = work.to_numpy(zero_copy_only=False)
        if np_data.dtype.kind == "f":
            np_data = np.nan_to_num(np_data)
        return np_data.astype(ltype.np_dtype, copy=False), validity, ltype, None
    np_data = work.to_numpy(zero_copy_only=False)
    return np_data.astype(ltype.np_dtype, copy=False), None, ltype, None


def _arrow_to_column(arr, typ) -> Column:
    data, validity, ltype, d = _arrow_to_numpy(arr, typ)
    return Column(jnp.asarray(data),
                  None if validity is None else jnp.asarray(validity),
                  ltype, d)


def _column_to_arrow(c: Column, data: np.ndarray, valid: np.ndarray | None):
    import pyarrow as pa

    if c.ltype is LType.STRING:
        if c.dictionary is None:
            return pa.array(data.astype(np.int32), type=pa.int32())
        strings = c.dictionary.decode(data.astype(np.int32))
        if valid is not None:
            strings[~valid] = None
        return pa.array(strings, type=pa.string())
    mask = None if valid is None else ~valid
    if c.ltype is LType.DATE:
        return pa.array(data.astype("int32"), type=pa.date32(), mask=mask)
    if c.ltype in (LType.DATETIME, LType.TIMESTAMP):
        return pa.array(data.astype("int64"), type=pa.timestamp("us"), mask=mask)
    return pa.array(data, mask=mask)


def bucket_capacity(n: int, minimum: int = 1) -> int:
    """Smallest power-of-two >= max(n, minimum, 1): the capacity bucket a
    batch of ``n`` rows pads into.  A table growing inside one bucket keeps
    its device shape, so every executable compiled against it stays valid;
    only a bucket crossing (or shrink below the previous bucket) retraces."""
    return 1 << (max(int(n), int(minimum), 1) - 1).bit_length()


def pad_batch(batch: ColumnBatch, capacity: int) -> ColumnBatch:
    """Pad to ``capacity`` rows with dead rows (``sel=False`` tail).

    The fill is NULL-safe per dtype — zeros / False / code 0 — the same
    "real-looking but dead" payload filtered-out rows already carry, so any
    kernel correct under sel masks is correct over the padded tail.  When the
    input had no sel (all rows live) the result is marked ``live_prefix``:
    live rows form a leading prefix, which lets ``compact`` skip its gather.
    """
    n = len(batch)
    if capacity < n:
        raise ValueError(f"pad_batch: capacity {capacity} < {n} rows")
    prefix = batch.sel is None
    if capacity == n:
        if batch.sel is None:
            # attach an explicit all-live mask: the pytree structure must not
            # flip between sel=None and sel=array as the row count moves
            # through an exact power of two (that flip alone would retrace)
            return ColumnBatch(batch.names, batch.columns,
                               jnp.ones(n, dtype=bool), batch.num_rows,
                               live_prefix=True)
        return batch
    pad = capacity - n
    cols = []
    for c in batch.columns:
        data = jnp.concatenate(
            [c.data, jnp.zeros((pad,) + c.data.shape[1:], c.data.dtype)])
        validity = None
        if c.validity is not None:
            validity = jnp.concatenate([c.validity, jnp.zeros((pad,), bool)])
        cols.append(Column(data, validity, c.ltype, c.dictionary))
    sel = jnp.concatenate([batch.sel_mask(), jnp.zeros((pad,), bool)])
    return ColumnBatch(batch.names, cols, sel, None, live_prefix=prefix)


@jax.jit
def _take_rows(arrays, sel, idx, n):
    live = jnp.arange(idx.shape[0]) < n
    if sel is not None:
        live = jnp.logical_and(live, jnp.take(sel, idx, mode="clip"))
    return [None if a is None else jnp.take(a, idx, axis=0, mode="clip")
            for a in arrays], live


def gather_padded(batch: ColumnBatch, positions: np.ndarray,
                  capacity: int) -> ColumnBatch:
    """The rows of ``batch`` at host ``positions``, as ``capacity`` rows
    with a dead tail: ``sel`` is off past ``len(positions)`` and wherever
    the gathered row's own ``sel`` was.  Only the positions cross host ->
    device, in one program over the arrays alone — each column keeps its
    ``Dictionary`` OBJECT, and the program is keyed on shapes, never on the
    literals that chose the positions nor on dictionary content."""
    idx = np.zeros(capacity, np.int32)
    idx[:len(positions)] = positions
    arrays = [a for c in batch.columns for a in (c.data, c.validity)]
    out, live = _take_rows(arrays, batch.sel, idx, len(positions))
    cols = [replace(c, data=d, validity=v)
            for c, d, v in zip(batch.columns, out[0::2], out[1::2])]
    return ColumnBatch(batch.names, cols, live, None)


def concat_batches(batches: list[ColumnBatch]) -> ColumnBatch:
    """Concatenate same-schema batches (densified) along rows."""
    assert batches
    first = batches[0]
    cols = []
    for i, name in enumerate(first.names):
        parts_d, parts_v, any_v = [], [], False
        for b in batches:
            c = b.columns[i]
            parts_d.append(c.data)
            v = c.valid_mask() if c.validity is not None else None
            parts_v.append(v)
            any_v = any_v or v is not None
        data = jnp.concatenate(parts_d)
        validity = None
        if any_v:
            validity = jnp.concatenate([
                v if v is not None else jnp.ones(d.shape[0], dtype=bool)
                for v, d in zip(parts_v, parts_d)
            ])
        cols.append(replace(first.columns[i], data=data, validity=validity))
    sels = [b.sel_mask() if b.sel is not None else None for b in batches]
    sel = None
    if any(s is not None for s in sels):
        sel = jnp.concatenate([
            s if s is not None else jnp.ones(len(b), dtype=bool)
            for s, b in zip(sels, batches)
        ])
    return ColumnBatch(first.names, cols, sel, None)
