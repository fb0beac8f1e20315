"""baikaldb_tpu — a TPU-native distributed HTAP query engine.

A ground-up rebuild of the capabilities of BaikalDB (reference:
/root/reference, C++17: MySQL protocol -> planner -> volcano/Acero executor ->
Raft/RocksDB stores) re-designed for TPU:

- columnar batches are pytrees of fixed-width jax arrays (column/),
- SQL expressions compile to fused XLA ops instead of an interpreted
  ExprNode tree (expr/),
- relational operators are data-parallel kernels — segment reductions,
  sort-joins, mask-based selection (ops/),
- distribution is a jax.sharding Mesh with XLA collectives (psum /
  all_to_all over ICI) instead of brpc-shuffled RecordBatches (parallel/),
- the SQL frontend, planner, catalog and storage tiers live on the host
  (sql/, plan/, meta/, storage/).

int64/float64 columns require jax x64 mode; enabled at import, together
with the persistent compile cache (utils/compilecache.enable).  Neither
initialises a backend.
"""

import jax as _jax

_jax.config.update("jax_enable_x64", True)

from .utils import compilecache as _compilecache  # noqa: E402

_compilecache.enable()

from .types import Field, LType, Schema  # noqa: E402,F401
from .column.batch import Column, ColumnBatch  # noqa: E402,F401
from .column.dictionary import Dictionary  # noqa: E402,F401
from .expr.ast import AggCall, Call, ColRef, Lit, col, lit, call  # noqa: E402,F401

__version__ = "0.1.0"
