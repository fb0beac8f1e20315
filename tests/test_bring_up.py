"""What the chip cannot be asked in tier-1: the entry points refuse to hide
the device they ran on, and the compile cache is placed from outside."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def _run(code_or_script: list, env: dict, timeout: float = 300):
    return subprocess.run([sys.executable] + code_or_script, cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_chip_smoke_refuses_the_cpu():
    """conftest pinned this environment to the CPU: the smoke names the
    platform it found and exits non-zero before it generates any data."""
    r = _run(["chip_smoke.py"], dict(os.environ), timeout=120)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr, r.stderr[-2000:]
    assert "device:" in r.stdout and "tpch:" not in r.stdout
    assert '"ok"' not in r.stdout


_CACHE_CHILD = """
import jax
from baikaldb_tpu.utils.flags import set_flag
from baikaldb_tpu.exec.session import Session
set_flag("aot_cache", False)
s = Session()
s.execute("CREATE TABLE cc (g BIGINT, v DOUBLE)")
s.execute("INSERT INTO cc VALUES (1, 1.5), (2, 2.5), (1, 3.0)")
assert s.query("SELECT g, SUM(v) sv FROM cc GROUP BY g ORDER BY g")[0]["sv"] == 4.5
print("CACHE_DIR=" + str(jax.config.jax_compilation_cache_dir))
"""


def _cache_dir_of_child(env: dict) -> str:
    r = _run(["-c", _CACHE_CHILD], env)
    assert r.returncode == 0, r.stderr[-2000:]
    return [ln for ln in r.stdout.splitlines()
            if ln.startswith("CACHE_DIR=")][-1][len("CACHE_DIR="):]


def test_compile_cache_env_var_wins(tmp_path):
    own = REPO / ".jax_cache"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    before = set(os.listdir(own)) if own.is_dir() else set()
    assert _cache_dir_of_child(env) == str(tmp_path)
    mine = set(os.listdir(tmp_path))
    assert mine, "the query's compiles were not cached there"
    after = set(os.listdir(own)) if own.is_dir() else set()
    # other test workers write .jax_cache meanwhile, under their own
    # programs' keys: the child's keys are the names it left in tmp_path
    assert not (after - before) & mine, \
        "a process with the variable set wrote .jax_cache"


def test_compile_cache_default_is_the_checkout():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    assert _cache_dir_of_child(env) == str(REPO / ".jax_cache")


def test_double_key_hash_lowers_without_64bit_bitcast():
    """The TPU's x64 rewriter aborts on any 64-bit bitcast_convert (which
    jnp.frexp / jnp.signbit of a DOUBLE lower to): the key hash of a DOUBLE
    column — mesh repartition, radix join, HLL — must lower without one."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np

    from baikaldb_tpu.utils.hashing import hash_columns, partition_ids

    x = np.array([0.0, -0.0, 1.5, -1.5, 0.1, 1e300, np.inf, np.nan, -np.nan])
    fn = jax.jit(lambda a: hash_columns([a]))
    casts = re.findall(r"bitcast_convert.*", fn.lower(jnp.asarray(x)).as_text())
    assert casts and not [c for c in casts if "64" in c], casts
    h = np.asarray(fn(jnp.asarray(x)))
    assert h[0] == h[1] and h[7] == h[8]          # -0.0 == 0.0, one NaN
    assert len({int(v) for v in h[[0, 2, 3, 4, 5, 7]]}) == 6
    # the stated limit (split64's docstring): past the float32 range a
    # DOUBLE hashes as its sign's infinity, below it as zero
    far = np.asarray(fn(jnp.asarray(
        [1e300, 2e300, np.inf, -1e300, 1e-300, 3e-300, 0.0])))
    assert far[0] == far[1] == far[2] != far[3]
    assert far[4] == far[5] == far[6]
    r = np.random.default_rng(3).normal(size=20_000)
    p = np.asarray(partition_ids([jnp.asarray(r)], 4))
    assert np.array_equal(p, np.asarray(partition_ids([jnp.asarray(r)], 4)))
    assert np.bincount(p, minlength=4).min() > 4_000      # spreads


def test_64bit_min_max_merge_lowers_no_64bit_min_max_all_reduce():
    """The TPU lowers no 64-bit max/min all-reduce ("Supported lowering only
    of Sum all reduce"): BIGINT/DOUBLE partials must merge through an
    all_gather and a local reduce, and still give the exact extremum."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from baikaldb_tpu.parallel.agg import merge_collective
    from baikaldb_tpu.parallel.mesh import AXIS, make_mesh

    mesh = make_mesh(8)
    rng = np.random.default_rng(5)
    for x in (rng.normal(size=(8, 6)), rng.integers(-2**40, 2**40, (8, 6)),
              rng.normal(size=(8, 6)).astype(np.float32)):
        for op, ref in (("min", np.min), ("max", np.max)):
            fn = jax.jit(jax.shard_map(
                lambda v: merge_collective(op, v[0]), mesh=mesh,
                in_specs=(P(AXIS),), out_specs=P(), check_vma=False))
            assert np.array_equal(np.asarray(fn(jnp.asarray(x))), ref(x, axis=0))
            txt = fn.lower(jnp.asarray(x)).as_text()
            wide = [m.group(0) for m in re.finditer(
                r'"stablehlo\.all_reduce".*?\}\) : \((.*?)\)', txt, re.S)
                if "64" in m.group(1)]
            assert not wide, wide
            assert ("stablehlo.all_gather" in txt) == (x.dtype.itemsize == 8)


def test_client_bounds_the_handshake_not_the_query():
    """A first compile on the chip outlasts any fixed read timeout (Q18 at
    SF1: 321 s): the client's 30 s cover connect + handshake only."""
    from baikaldb_tpu.client.mysql_client import Connection
    from baikaldb_tpu.server.mysql_server import MySQLServer

    srv = MySQLServer(port=0).start()
    try:
        conn = Connection(port=srv.port)
        assert conn.sock.gettimeout() is None
        assert int(conn.query("SELECT 1 + 1").rows[0][0]) == 2
        conn.close()
    finally:
        srv.stop()


def test_smoke_expects_the_kernels_the_dense_dispatch_lowers(monkeypatch):
    """chip_smoke requires each Pallas GROUP BY to have compiled Mosaic calls
    of the kernels its table names.  Lowered for the TPU from here (no chip
    needed to lower), the engine's dense aggregate over the same aggregate
    lists must call exactly those — and ``mosaic_kernels`` must find them."""
    import re

    import jax
    import jax.numpy as jnp

    import chip_smoke
    from baikaldb_tpu.column.batch import Column, ColumnBatch
    from baikaldb_tpu.ops.hashagg import (AggSpec, group_aggregate_dense,
                                          partial_specs)
    from baikaldb_tpu.types import LType

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = 65536                               # one streamed chunk
    for ng, _kind, aggs, want in chip_smoke.PALLAS_QUERIES:
        batch = ColumnBatch(("g", "v"), [
            Column(jnp.zeros(n, jnp.int32), None, LType.INT32),
            Column(jnp.zeros(n, jnp.float32), None, LType.FLOAT32)],
            None, None)
        specs = [AggSpec("count_star", None, "n")] + [
            AggSpec(op.lower(), "v", name)
            for op, name in re.findall(r"(\w+)\(v\) (\w+)", aggs)]
        parts, _ = partial_specs(specs)      # what a streamed chunk folds
        for sp in (specs, parts):
            text = jax.jit(
                lambda b: group_aggregate_dense(b, ["g"], [ng], sp)) \
                .trace(batch).lower(lowering_platforms=("tpu",)).as_text()
            assert chip_smoke.mosaic_kernels(text) == want, (ng, aggs)


def test_smoke_reads_the_modules_a_served_query_compiled(tmp_path,
                                                         monkeypatch):
    """``compiled_kernels`` sees what a query over the wire compiled (the
    server compiles on its own thread), finds no Mosaic call on the CPU, and
    leaves jax's dump option as it found it."""
    import jax

    import chip_smoke
    from baikaldb_tpu.server.mysql_server import MySQLServer

    monkeypatch.setattr(chip_smoke, "IR_DIR", tmp_path)
    srv = MySQLServer(port=0).start()
    try:
        wire = chip_smoke.Wire(srv.port)
        wire.execute("CREATE TABLE kk (g INT, v FLOAT)")
        wire.execute("INSERT INTO kk VALUES (1, 1.5), (2, 2.5), (1, 3.0)")
        (cols, rows), kernels = chip_smoke.compiled_kernels(
            "dense kk", lambda: wire.select(
                "kk", "SELECT g, COUNT(*) n, SUM(v) s FROM kk GROUP BY g "
                      "ORDER BY g", runs=1))
        wire.conn.close()
    finally:
        srv.stop()
    assert [tuple(map(float, r)) for r in rows] == [(1, 2, 4.5), (2, 1, 2.5)]
    assert kernels == set()
    assert list((tmp_path / "dense_kk").glob("*.mlir"))
    assert jax.config.read("jax_dump_ir_to") == ""
