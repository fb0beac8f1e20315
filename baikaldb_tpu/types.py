"""Logical type system for baikaldb_tpu.

The reference models MySQL types in ``include/common/expr_value.h`` (ExprValue, a
tagged scalar holding every MySQL primitive type) and maps them onto Arrow types
for the vectorized path (``src/expr/arrow_function.cpp``).  On TPU we instead map
every logical type onto a *fixed-width physical dtype* that XLA can tile onto the
MXU/VPU:

- integers      -> int32 / int64
- floats        -> float32 / float64
- DECIMAL       -> float64 (round 1; scaled-int128 is not XLA friendly)
- BOOL          -> bool
- DATE          -> int32 days since epoch
- DATETIME/TS   -> int64 microseconds since epoch
- STRING        -> int32 dictionary codes; the dictionary itself lives on the
                  host (see column/dictionary.py).  Dictionaries are kept
                  *sorted*, so ordering comparisons on codes are valid.

NULL semantics follow MySQL three-valued logic; every column carries an optional
validity bitmask (see column/batch.py), the analog of Arrow validity buffers
used throughout the reference's columnar path (``include/runtime/chunk.h``).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass

import numpy as np


class LType(enum.Enum):
    """Logical column type (reference: pb::PrimitiveType in proto/common.proto)."""

    BOOL = "bool"
    INT8 = "int8"
    INT16 = "int16"
    INT32 = "int32"
    INT64 = "int64"
    UINT32 = "uint32"
    UINT64 = "uint64"
    FLOAT32 = "float32"
    FLOAT64 = "float64"
    DECIMAL = "decimal"
    DATE = "date"          # int32 days since 1970-01-01
    DATETIME = "datetime"  # int64 microseconds since epoch
    TIMESTAMP = "timestamp"
    STRING = "string"      # int32 dictionary code
    NULL = "null"

    # ------------------------------------------------------------------
    @property
    def np_dtype(self) -> np.dtype:
        return np.dtype(_PHYSICAL[self])

    @property
    def is_string(self) -> bool:
        return self is LType.STRING

    @property
    def is_integer(self) -> bool:
        return self in (
            LType.BOOL, LType.INT8, LType.INT16, LType.INT32, LType.INT64,
            LType.UINT32, LType.UINT64,
        )

    @property
    def is_float(self) -> bool:
        return self in (LType.FLOAT32, LType.FLOAT64, LType.DECIMAL)

    @property
    def is_numeric(self) -> bool:
        return self.is_integer or self.is_float

    @property
    def is_temporal(self) -> bool:
        return self in (LType.DATE, LType.DATETIME, LType.TIMESTAMP)


_PHYSICAL = {
    LType.BOOL: np.bool_,
    LType.INT8: np.int8,
    LType.INT16: np.int16,
    LType.INT32: np.int32,
    LType.INT64: np.int64,
    LType.UINT32: np.uint32,
    LType.UINT64: np.uint64,
    LType.FLOAT32: np.float32,
    LType.FLOAT64: np.float64,
    LType.DECIMAL: np.float64,
    LType.DATE: np.int32,
    LType.DATETIME: np.int64,
    LType.TIMESTAMP: np.int64,
    LType.STRING: np.int32,
    LType.NULL: np.bool_,
}

# Numeric promotion ladder, mirroring MySQL implicit-cast rules used by the
# reference's type inference (src/physical_plan/expr_optimizer.cpp).
_RANK = {
    LType.BOOL: 0, LType.INT8: 1, LType.INT16: 2, LType.INT32: 3,
    LType.UINT32: 4, LType.INT64: 5, LType.UINT64: 6,
    LType.FLOAT32: 7, LType.FLOAT64: 8, LType.DECIMAL: 8,
    LType.DATE: 3, LType.DATETIME: 5, LType.TIMESTAMP: 5,
}


def promote(a: LType, b: LType) -> LType:
    """Common type for a binary numeric op (MySQL-style promotion)."""
    if a == b:
        return a
    if a is LType.NULL:
        return b
    if b is LType.NULL:
        return a
    if a.is_string or b.is_string:
        # string vs numeric/temporal comparison: MySQL casts to double
        return LType.FLOAT64
    if (a.is_numeric and b.is_numeric) or a.is_temporal or b.is_temporal:
        ra, rb = _RANK[a], _RANK[b]
        hi = a if ra >= rb else b
        # a float against a non-float is DOUBLE, as MySQL has it (FLOAT
        # arithmetic is done in double precision): ``f * 2 + 1 > x`` over a
        # FLOAT column keeps the rows a float64 evaluation keeps, where a
        # float32 one rounds ~1 row in 1e8 across the boundary (PR 35; the
        # test below this one was written as ``not hi.is_float``, which no
        # pair of ranks can meet)
        if a.is_float != b.is_float and a.is_numeric and b.is_numeric:
            return LType.FLOAT64
        if hi.is_temporal:
            return LType.INT64 if hi is not LType.DATE else LType.INT32
        return hi
    raise TypeError(f"cannot promote {a} vs {b}")


@dataclass(frozen=True)
class Field:
    """A named, typed column in a schema (reference: pb::FieldInfo,
    include/common/schema_factory.h)."""

    name: str
    ltype: LType
    nullable: bool = True

    def __repr__(self) -> str:  # compact for plan dumps
        n = "" if self.nullable else " NOT NULL"
        return f"{self.name}:{self.ltype.value}{n}"


@dataclass(frozen=True)
class Schema:
    fields: tuple[Field, ...]

    def __post_init__(self):
        object.__setattr__(self, "_index", {f.name: i for i, f in enumerate(self.fields)})

    def field(self, name: str) -> Field:
        return self.fields[self._index[name]]

    def index(self, name: str) -> int:
        return self._index[name]

    def __contains__(self, name: str) -> bool:
        return name in self._index

    def names(self) -> list[str]:
        return [f.name for f in self.fields]

    def __len__(self) -> int:
        return len(self.fields)
