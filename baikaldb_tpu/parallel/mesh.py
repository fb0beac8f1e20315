"""Device mesh + row-axis sharding of column batches.

The reference scales by hash/range-partitioning rows into Regions across
store nodes and scatter-gathering per-region plans over brpc
(SURVEY.md §2.14).  The TPU-native analog: one `jax.sharding.Mesh` whose
"shard" axis plays the role of the store fleet; tables shard on the row axis
with `NamedSharding`, and per-shard kernels + XLA collectives (psum /
all_to_all over ICI) replace the RPC fan-out + coordinator merge.

Padding discipline: every shard must hold the same row count (SPMD), so
sharded batches are padded up to a multiple of the mesh size with dead rows
(sel=False) — the moral equivalent of the reference's uneven region sizes,
handled by masks instead of variable-length RPC payloads.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..column.batch import Column, ColumnBatch, bucket_capacity, pad_batch

AXIS = "shard"


def make_mesh(n_devices: int | None = None) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:n]), (AXIS,))


def pad_rows(batch: ColumnBatch, multiple: int) -> ColumnBatch:
    """Pad to a row-count multiple with dead rows (sel=False)."""
    n = len(batch)
    target = max(multiple, math.ceil(n / multiple) * multiple)
    return pad_batch(batch, target)


def shard_batch(batch: ColumnBatch, mesh: Mesh) -> ColumnBatch:
    """Row-shard a batch across the mesh (device_put with NamedSharding).

    With ``FLAGS.batch_bucketing`` each per-device slice pads to a
    power-of-two capacity bucket, so a sharded table growing inside one
    bucket keeps the shard_map program's shapes (the single-device
    executable-reuse story, per mesh device).

    Host-side dispatch seam: runs OUTSIDE any jit trace (device_put is the
    ingest boundary), so the span here is legal despite this module being
    tpulint hot scope — registered in tools/tpulint_suppressions.txt."""
    from ..obs import trace
    from ..utils import metrics
    from ..utils.flags import FLAGS

    with trace.timed("mesh.shard", rows=len(batch),
                     devices=int(mesh.devices.size)) as sp:
        n = mesh.devices.size
        if FLAGS.batch_bucketing:
            per = -(-max(len(batch), 1) // n)
            per = bucket_capacity(per,
                                  max(1, int(FLAGS.batch_bucket_min) // n))
            b = pad_batch(batch, per * n)
        else:
            b = pad_rows(batch, n)
        sharding = NamedSharding(mesh, P(AXIS))
        cols = [Column(jax.device_put(c.data, sharding),
                       None if c.validity is None
                       else jax.device_put(c.validity, sharding),
                       c.ltype, c.dictionary) for c in b.columns]
        sel = jax.device_put(b.sel_mask(), sharding)
        out = ColumnBatch(b.names, cols, sel, None)
    metrics.mesh_shard_ms.add(sp.ms)
    return out
