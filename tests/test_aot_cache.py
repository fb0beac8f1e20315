"""AOT persistent executable cache (utils/compilecache.AOT +
storage/aot_tier): zero-compile warm starts must be bit-identical, and the
tier must be impossible to poison — corrupt bytes, foreign jax versions and
alien topologies degrade to a counted compile, never a wrong result or a
crash.  The suite runs with the tier OFF (conftest); every test here opts
in against tmp directories."""

from __future__ import annotations

import glob
import os
import threading

import numpy as np
import pyarrow as pa
import pytest

import jax

from baikaldb_tpu.exec.session import Database, Session
from baikaldb_tpu.storage.aot_tier import (ArtifactDisk, ArtifactError,
                                           pack_artifact, unpack_artifact,
                                           unpack_meta)
from baikaldb_tpu.utils import compilecache, metrics
from baikaldb_tpu.utils.flags import FLAGS, set_flag

SQL = ("SELECT g, COUNT(*) n, SUM(v) sv FROM at WHERE v > 0.1 "
       "GROUP BY g ORDER BY g")


@pytest.fixture
def aot(tmp_path):
    prev_dir = str(FLAGS.aot_cache_dir)
    prev_max = int(FLAGS.aot_cache_disk_max)
    set_flag("aot_cache", True)
    set_flag("aot_cache_dir", str(tmp_path / "aot"))
    compilecache.AOT.reset_records()
    yield compilecache.AOT
    compilecache.AOT.drain(120)
    compilecache.AOT.detach_peer()
    set_flag("aot_cache", False)
    set_flag("aot_cache_dir", prev_dir)
    set_flag("aot_cache_disk_max", prev_max)


def _fresh(db=None, mesh=None, rows=2000, seed=0):
    # a restarted node: nothing of the in-process tier survives
    compilecache.AOT.forget_live()
    s = Session(db, mesh=mesh) if db is not None else Session(mesh=mesh)
    s.execute("CREATE TABLE at (id BIGINT, g BIGINT, v DOUBLE)")
    rng = np.random.default_rng(seed)
    s.load_arrow("at", pa.table({
        "id": np.arange(rows, dtype=np.int64),
        "g": rng.integers(0, 8, rows).astype(np.int64),
        "v": rng.normal(size=rows)}))
    return s


def _artifacts(aot):
    return sorted(glob.glob(os.path.join(aot.root(), "*.aotx")))


# -- container format (no jax involved) ------------------------------------

def test_pack_unpack_roundtrip_and_corruption(tmp_path):
    meta = {"kind": "plan", "plan_sig": "sig"}
    data = pack_artifact(meta, b"BLOB" * 100, b"AUX" * 10)
    m, blob, aux = unpack_artifact(data)
    assert blob == b"BLOB" * 100 and aux == b"AUX" * 10
    assert m["kind"] == "plan" and m["sha256"]
    # truncation at every interesting boundary
    for cut in (3, 10, len(data) // 2, len(data) - 1):
        with pytest.raises(ArtifactError):
            unpack_artifact(data[:cut])
    # single-bit flips in header, blob, and aux regions
    for pos in (20, len(data) // 2, len(data) - 5):
        flipped = bytearray(data)
        flipped[pos] ^= 0x40
        with pytest.raises(ArtifactError):
            unpack_artifact(bytes(flipped))
    with pytest.raises(ArtifactError):
        unpack_artifact(b"NOTANARTIFACT" * 10)
    with pytest.raises(ArtifactError):
        unpack_meta(b"AOTX1\n" + (2 ** 40).to_bytes(8, "big"))


def test_artifact_disk_lru_bound(tmp_path):
    disk = ArtifactDisk(str(tmp_path), max_entries=3)
    for i in range(6):
        disk.put(f"k{i}", pack_artifact({"i": i}, b"x" * 10, b""))
    assert len(disk.keys()) == 3
    # most recently written survive
    assert disk.get("k5") is not None and disk.get("k0") is None


# -- round-trip bit-identity ------------------------------------------------

def test_plan_roundtrip_zero_compiles_bit_identical(aot):
    s1 = _fresh()
    want = s1.query(SQL)
    assert aot.drain(120), "publish queue did not drain"
    assert len(_artifacts(aot)) == 1
    # a restarted node: same engine state, empty plan/jit caches
    r0 = metrics.xla_retraces.value
    h0 = metrics.aot_cache_hits.value
    s2 = _fresh()
    got = s2.query(SQL)
    assert got == want                      # byte-for-byte result rows
    assert metrics.aot_cache_hits.value == h0 + 1
    assert metrics.xla_retraces.value == r0, \
        "AOT warm start must not trace/compile"
    # steady state on the deserialized executable stays compile-free
    for _ in range(3):
        assert s2.query(SQL) == want
    assert metrics.xla_retraces.value == r0


def test_off_switch_restores_compile_behavior(aot):
    s1 = _fresh()
    s1.query(SQL)
    assert aot.drain(120)
    set_flag("aot_cache", False)
    r0 = metrics.xla_retraces.value
    h0 = metrics.aot_cache_hits.value
    s2 = _fresh()
    s2.query(SQL)
    assert metrics.xla_retraces.value > r0, "off-switch must compile"
    assert metrics.aot_cache_hits.value == h0


def test_mesh_roundtrip_zero_compiles_bit_identical(aot):
    from baikaldb_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8)
    s1 = _fresh(mesh=mesh)
    want = s1.query(SQL)
    assert aot.drain(180)
    r0 = metrics.xla_retraces.value
    s2 = _fresh(mesh=mesh)
    got = s2.query(SQL)
    assert got == want
    assert metrics.xla_retraces.value == r0, \
        "mesh AOT warm start must not trace/compile"


def test_batched_dispatch_roundtrip_bit_identical(aot):
    """The vmapped combiner executable round-trips too: a restarted node
    serves its first concurrent tick from the artifact (egress column meta
    included) with zero traces."""
    prev_tick = float(FLAGS.batch_dispatch_tick_ms)
    prev_on = bool(FLAGS.batch_dispatch)
    prev_max = int(FLAGS.batch_dispatch_max_group)
    set_flag("batch_dispatch_tick_ms", 60.0)
    set_flag("batch_dispatch", True)
    # 9 concurrent members: one bypasses inline, eight fill the group to
    # max_group so it fires FULL — the padded group size (and with it the
    # artifact key) is deterministic across both node lifetimes
    set_flag("batch_dispatch_max_group", 8)
    try:
        def run_burst(db):
            sqls = [f"SELECT v FROM at WHERE id = {i}" for i in range(9)]
            sessions = [Session(db) for _ in range(9)]
            out: dict = {}
            errs: list = []
            start = threading.Barrier(9)

            def worker(s, sql):
                start.wait()
                try:
                    out[sql] = s.query(sql)
                except Exception as e:      # noqa: BLE001
                    errs.append(e)

            ts = [threading.Thread(target=worker, args=(sessions[i], q))
                  for i, q in enumerate(sqls)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert not errs, errs
            return out

        db1 = Database()
        s = _fresh(db1)
        s.query("SELECT v FROM at WHERE id = 0")    # warm the plan group
        g0 = metrics.batched_groups.value
        want = run_burst(db1)
        if metrics.batched_groups.value == g0:
            pytest.skip("no combiner tick formed on this host")
        assert aot.drain(180)
        arts = _artifacts(aot)
        kinds = set()
        for f in arts:
            with open(f, "rb") as fh:
                kinds.add(unpack_meta(fh.read(1 << 16)).get("kind"))
        assert "batched" in kinds, kinds
        # restarted node: the burst must serve without a single trace
        db2 = Database()
        s2 = _fresh(db2)
        s2.query("SELECT v FROM at WHERE id = 0")
        aot.drain(180)              # inline-warmup publishes settle first
        r0 = metrics.xla_retraces.value
        got = run_burst(db2)
        assert metrics.xla_retraces.value == r0, \
            "batched AOT warm start must not trace/compile"
        for sql, rows in want.items():
            assert got[sql] == rows
    finally:
        set_flag("batch_dispatch_tick_ms", prev_tick)
        set_flag("batch_dispatch", prev_on)
        set_flag("batch_dispatch_max_group", prev_max)


# -- poisoning / staleness --------------------------------------------------

def _only_sound_artifacts(aot):
    """What is on disk once the publisher is idle: nothing, or what the
    fallback's fresh compile published over the bad artifact (the publisher
    only serialises since PR 33, so it is usually there already) — whole,
    of this jax, never the bytes that were put there."""
    import jax

    assert aot.drain(120)
    for f in _artifacts(aot):
        meta, _blob, _aux = unpack_artifact(open(f, "rb").read())
        assert meta["jax"] == jax.__version__

def test_corrupt_artifact_falls_back_and_evicts(aot):
    s1 = _fresh()
    want = s1.query(SQL)
    assert aot.drain(120)
    files = _artifacts(aot)
    assert files
    for f in files:
        data = bytearray(open(f, "rb").read())
        data[len(data) // 2] ^= 0xFF        # bit-flip the payload
        open(f, "wb").write(bytes(data))
    fb0 = metrics.aot_cache_fallbacks.value
    ev0 = metrics.aot_cache_evictions.value
    s2 = _fresh()
    assert s2.query(SQL) == want            # never a wrong result
    assert metrics.aot_cache_fallbacks.value > fb0
    assert metrics.aot_cache_evictions.value > ev0
    _only_sound_artifacts(aot)      # the poisoned one does not linger


def test_truncated_artifact_falls_back(aot):
    s1 = _fresh()
    want = s1.query(SQL)
    assert aot.drain(120)
    for f in _artifacts(aot):
        data = open(f, "rb").read()
        open(f, "wb").write(data[:len(data) // 3])
    fb0 = metrics.aot_cache_fallbacks.value
    s2 = _fresh()
    assert s2.query(SQL) == want
    assert metrics.aot_cache_fallbacks.value > fb0
    _only_sound_artifacts(aot)


def test_jax_version_mismatch_is_clean_miss(aot):
    s1 = _fresh()
    want = s1.query(SQL)
    assert aot.drain(120)
    [f] = _artifacts(aot)
    meta, blob, aux = unpack_artifact(open(f, "rb").read())
    meta.pop("sha256"), meta.pop("blob_len"), meta.pop("aux_len")
    meta["jax"] = "0.0.0-other"
    open(f, "wb").write(pack_artifact(meta, blob, aux))
    m0 = metrics.aot_cache_misses.value
    fb0 = metrics.aot_cache_fallbacks.value
    r0 = metrics.xla_retraces.value
    s2 = _fresh()
    assert s2.query(SQL) == want
    assert metrics.aot_cache_misses.value > m0, "stale version must MISS"
    assert metrics.aot_cache_fallbacks.value == fb0, \
        "a clean version miss is not a fallback"
    assert metrics.xla_retraces.value > r0, "miss must compile"
    _only_sound_artifacts(aot)      # the stale-version one was evicted


def test_topology_mismatch_keys_differ():
    """A mesh program's artifact key can never collide with the
    single-device key of the same plan (and vice versa): the backend/
    topology fingerprint is part of the identity."""
    from baikaldb_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8)
    k1 = compilecache.aot_key("plan", "sig", ("shape",), "infp", None)
    k2 = compilecache.aot_key("plan", "sig", ("shape",), "infp", mesh)
    assert k1 != k2
    assert compilecache.backend_fingerprint(mesh).endswith(
        ":mesh=" + "x".join(str(int(d)) for d in mesh.devices.shape))


def test_input_fingerprint_tracks_dictionary_content():
    """String-dictionary content is part of the executable's identity (it
    rides pytree aux data into the trace): changed values = new key."""
    from baikaldb_tpu.column.batch import Column, ColumnBatch
    from baikaldb_tpu.column.dictionary import Dictionary
    import jax.numpy as jnp

    def batch(values):
        d = Dictionary(np.asarray(values, dtype=object))
        from baikaldb_tpu.types import LType
        col = Column(jnp.zeros(4, jnp.int32), None, LType.STRING, d)
        return {"db.t": ColumnBatch(("s",), [col])}

    f1 = compilecache.input_fingerprint(batch(["a", "b"]))
    f2 = compilecache.input_fingerprint(batch(["a", "b"]))
    f3 = compilecache.input_fingerprint(batch(["a", "c"]))
    assert f1 == f2
    assert f1 != f3


# -- concurrency / bounds ---------------------------------------------------

def test_concurrent_first_touch_publishes_one_artifact(aot):
    dbs = [Database(), Database()]
    sessions = [_fresh(db) for db in dbs]
    start = threading.Barrier(2)
    errs: list = []

    def worker(s):
        start.wait()
        try:
            s.query(SQL)
        except Exception as e:              # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(s,)) for s in sessions]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs
    assert aot.drain(120)
    assert len(_artifacts(aot)) == 1, \
        "racing first touches must publish exactly one artifact"


def test_disk_tier_stays_bounded(aot):
    set_flag("aot_cache_disk_max", 3)
    s = _fresh()
    ev0 = metrics.aot_cache_evictions.value
    for i in range(5):
        # distinct statement shapes -> distinct executables/artifacts
        s.query(f"SELECT g, COUNT(*) c{i} FROM at WHERE v > 0.{i + 1} "
                f"AND id > {i} GROUP BY g ORDER BY g")
        assert aot.drain(120)
    assert len(_artifacts(aot)) <= 3
    assert metrics.aot_cache_evictions.value > ev0


def test_overflow_fallback_recompiles_and_republishes(aot):
    """An artifact whose baked join cap is undersized for live data must
    fall back to compile (counted) and republish settled caps — never
    loop or truncate."""
    db1 = Database()
    s1 = Session(db1)
    s1.execute("CREATE TABLE jt (k BIGINT, v BIGINT)")
    s1.execute("INSERT INTO jt VALUES " + ", ".join(
        f"({i % 4}, {i})" for i in range(64)))
    jsql = ("SELECT a.k, COUNT(*) n FROM jt a JOIN jt b ON a.k = b.k "
            "GROUP BY a.k ORDER BY a.k")
    want = s1.query(jsql)
    assert aot.drain(120)
    # a "restarted node" with the same shapes/key domain (same plan, same
    # artifact key) but one SKEWED key whose join fan-out blows past the
    # artifact's baked capacity
    db2 = Database()
    s2 = Session(db2)
    s2.execute("CREATE TABLE jt (k BIGINT, v BIGINT)")
    vals = [(0, i) for i in range(61)] + [(1, 100), (2, 101), (3, 102)]
    s2.execute("INSERT INTO jt VALUES " + ", ".join(
        f"({k}, {v})" for k, v in vals))
    fb0 = metrics.aot_cache_fallbacks.value
    h0 = metrics.aot_cache_hits.value
    rows = s2.query(jsql)
    assert rows and rows[0]["n"] == 61 * 61
    assert metrics.aot_cache_hits.value > h0, "artifact must load first"
    assert metrics.aot_cache_fallbacks.value > fb0, \
        "baked-cap overflow must count as an AOT fallback"
    # the original node still answers correctly from its artifact
    assert s1.query(jsql) == want


# -- observability ----------------------------------------------------------

def test_information_schema_and_explain_surface(aot):
    s = _fresh()
    s.query(SQL)
    assert aot.drain(120)
    rows = s.query("SELECT kind, source, status FROM "
                   "information_schema.aot_cache")
    assert rows and all(r["status"] == "ok" for r in rows)
    assert any(r["kind"] == "plan" for r in rows)
    txt = s.execute("EXPLAIN ANALYZE " + SQL).plan_text
    aot_lines = [ln for ln in txt.splitlines() if ln.startswith("-- aot:")]
    assert aot_lines and "enabled=1" in aot_lines[0]


def test_aotcache_cli_list_gc_verify(aot, capsys):
    s = _fresh()
    s.query(SQL)
    assert aot.drain(120)
    import tools.aotcache as cli

    assert cli.main(["--list", "--dir", aot.root()]) == 0
    assert cli.main(["--verify", "--dir", aot.root()]) == 0
    assert cli.main(["--gc", "--dir", aot.root()]) == 0
    assert len(_artifacts(aot)) == 1        # current-version artifact kept
    # payload corruption: verify must fail nonzero (gc is header-level
    # only — deep checks are --verify's job)
    [f] = _artifacts(aot)
    data = bytearray(open(f, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(f, "wb").write(bytes(data))
    assert cli.main(["--verify", "--dir", aot.root()]) == 1
    # header corruption: the cheap gc walk sweeps it
    open(f, "wb").write(bytes(data[:16]))
    assert cli.main(["--gc", "--dir", aot.root()]) == 0
    assert not _artifacts(aot)
    capsys.readouterr()


def test_in_bucket_dml_never_serves_stale_dictionary(aot):
    """jit retraces when a string dictionary's content changes (pytree
    aux); a deserialized AOT program cannot — so an AOT pair is pinned to
    the exact store versions it loaded under, and ANY DML (even inside
    the capacity bucket) re-derives the artifact key.  A changed
    dictionary is then a clean miss; reusing the old executable would
    decode new codes against the stale dictionary."""
    db1 = Database()
    s1 = Session(db1)
    s1.execute("CREATE TABLE st (id BIGINT, name VARCHAR(8))")
    s1.execute("INSERT INTO st VALUES (1, 'aa'), (2, 'bb'), (3, 'cc')")
    q = "SELECT name, COUNT(*) n FROM st GROUP BY name ORDER BY name"
    want = s1.query(q)
    assert [r["name"] for r in want] == ["aa", "bb", "cc"]
    assert aot.drain(120)
    # restarted node serves from the artifact...
    db2 = Database()
    s2 = Session(db2)
    s2.execute("CREATE TABLE st (id BIGINT, name VARCHAR(8))")
    s2.execute("INSERT INTO st VALUES (1, 'aa'), (2, 'bb'), (3, 'cc')")
    r0 = metrics.xla_retraces.value
    assert s2.query(q) == want
    assert metrics.xla_retraces.value == r0
    # ...then in-bucket DML mints a NEW dictionary value: the cached AOT
    # pair must not answer with the old dictionary baked in
    s2.execute("INSERT INTO st VALUES (4, 'zz')")
    got = s2.query(q)
    assert [r["name"] for r in got] == ["aa", "bb", "cc", "zz"]
    assert {"name": "zz", "n": 1} in [dict(r) for r in got]
