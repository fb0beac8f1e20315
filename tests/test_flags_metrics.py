"""Config/flag system (utils/flags.py, SURVEY §5.6) and metrics counters
(utils/metrics.py, §5.5): registry semantics, the three config channels
(file/argv, SET GLOBAL, meta heartbeat push), and the SQL surfacing
(SHOW VARIABLES/STATUS, information_schema.metrics/flags)."""

import numpy as np
import pytest

from baikaldb_tpu.utils.flags import FlagError, FlagRegistry
from baikaldb_tpu.utils.metrics import (Counter, Gauge, LatencyRecorder,
                                        Registry)


def _reg():
    r = FlagRegistry()
    r.define("rate", 100.0, "a float")
    r.define("retries", 3, "an int")
    r.define("verbose", False, "a bool")
    r.define("tag", "hot", "a string")
    return r


def test_defaults_and_types():
    r = _reg()
    assert r.rate == 100.0 and r.retries == 3 and r.verbose is False
    r.set_flag("rate", "250")          # string coerces to the defined type
    assert r.rate == 250.0
    r.set_flag("verbose", "on")
    assert r.verbose is True
    with pytest.raises(FlagError):
        r.set_flag("retries", "abc")
    with pytest.raises(FlagError):
        r.set_flag("nope", 1)
    with pytest.raises(FlagError):
        r.define("rate", 999.0)        # conflicting re-define


def test_load_args_and_file(tmp_path):
    r = _reg()
    rest = r.load_args(["--rate=1.5", "--noverbose", "--retries", "7", "pos"])
    assert r.rate == 1.5 and r.verbose is False and r.retries == 7
    assert rest == ["pos"]
    conf = tmp_path / "gflags.conf"
    conf.write_text("# comment\n--rate=9\n--verbose=true\n\n--unknown=1\n")
    with pytest.raises(FlagError):
        r.load_file(str(conf))
    r.load_file(str(conf), ignore_unknown=True)
    assert r.rate == 9.0 and r.verbose is True


def test_listeners_fire_on_change():
    r = _reg()
    seen = []
    r.on_change("retries", seen.append)
    r.set_flag("retries", 5)
    r.set_flag("retries", "6")
    assert seen == [5, 6]


def test_metrics_counter_latency_gauge():
    reg = Registry()
    c = Counter("reqs", registry=reg)
    for _ in range(5):
        c.add(2)
    assert c.value == 10 and c.per_second() > 0
    lat = LatencyRecorder("lat", registry=reg)
    for ms in (1.0, 2.0, 3.0, 100.0):
        lat.observe(ms)
    with lat.time():
        pass
    st = lat.stats()
    assert st["count"] == 5 and st["max_ms"] == 100.0
    assert st["p50_ms"] <= st["p95_ms"] <= st["p99_ms"]
    Gauge("depth", lambda: 42, registry=reg)
    exposed = reg.expose()
    assert exposed["reqs"]["value"] == 10
    assert exposed["depth"]["value"] == 42
    assert "lat.p99_ms" in reg.dump().replace(" : ", ".").replace(
        "\n", " ") or True  # dump renders one line per field
    assert any(line.startswith("lat.p99_ms") for line in reg.dump().splitlines())


def test_set_global_and_show(tmp_path):
    from baikaldb_tpu.exec.session import Session
    from baikaldb_tpu.utils.flags import FLAGS

    s = Session()
    old = FLAGS.slow_query_ms
    try:
        s.execute("SET GLOBAL slow_query_ms = 123")
        assert FLAGS.slow_query_ms == 123.0
        r = s.query("SHOW VARIABLES LIKE 'slow_query_ms'")
        assert r == [{"Variable_name": "slow_query_ms", "Value": "123.0"}]
        with pytest.raises(Exception):
            s.execute("SET GLOBAL no_such_flag = 1")
        # session vars: silent success, no flag touched
        s.execute("SET @mine = 7")
        s.execute("SET autocommit = 1")
        assert s.session_vars["@mine"] == 7
    finally:
        FLAGS.set_flag("slow_query_ms", old)


def test_metrics_flow_through_sql():
    from baikaldb_tpu.exec.session import Session
    from baikaldb_tpu.utils import metrics

    s = Session()
    s.execute("CREATE TABLE m (id BIGINT PRIMARY KEY, v DOUBLE)")
    s.execute("INSERT INTO m VALUES (1, 2.0), (2, 3.0)")
    q0 = metrics.queries_total.value
    h0 = metrics.plan_cache_hits.value
    s.query("SELECT SUM(v) FROM m")
    s.query("SELECT SUM(v) FROM m")      # second run hits the plan cache
    assert metrics.queries_total.value >= q0 + 2
    assert metrics.plan_cache_hits.value >= h0 + 1
    rows = s.query("SELECT field, value FROM information_schema.metrics "
                   "WHERE name = 'query_latency' AND field = 'count'")
    assert rows and rows[0]["value"] >= 2
    flags = s.query("SELECT name FROM information_schema.flags")
    assert {"slow_query_ms", "join_retry_max"} <= {r["name"] for r in flags}
    st = s.query("SHOW STATUS LIKE 'queries_total.value'")
    assert int(st[0]["Value"]) >= 2


def test_meta_pushes_params_to_fleet():
    """The update_instance_param loop: meta stages an override, the store's
    next heartbeat response carries it, the store applies it to FLAGS."""
    from baikaldb_tpu.meta.service import HeartbeatRequest, MetaService
    from baikaldb_tpu.utils.flags import FLAGS

    meta = MetaService()
    meta.add_instance("s1")
    meta.set_instance_param("*", "slow_query_ms", 777)
    meta.set_instance_param("s1", "join_retry_max", 2)
    resp = meta.heartbeat(HeartbeatRequest("s1"))
    assert resp.param_overrides == {"slow_query_ms": 777,
                                    "join_retry_max": 2}
    # another instance only sees the cluster-wide override
    meta.add_instance("s2")
    resp2 = meta.heartbeat(HeartbeatRequest("s2"))
    assert resp2.param_overrides == {"slow_query_ms": 777}

    old_s, old_j = FLAGS.slow_query_ms, FLAGS.join_retry_max
    try:
        from baikaldb_tpu.raft.fleet import StoreFleet
        fleet = StoreFleet(meta, ["s1", "s2", "s3"])
        fleet.heartbeat_all()
        assert FLAGS.slow_query_ms == 777.0
        assert FLAGS.join_retry_max == 2
    finally:
        FLAGS.set_flag("slow_query_ms", old_s)
        FLAGS.set_flag("join_retry_max", old_j)


def test_pallas_dense_groupby_integration(monkeypatch):
    """group_aggregate_dense routes through the Pallas kernels when the
    backend/flag/shape gate passes, and the results match the segment path."""
    import functools

    import jax
    import jax.numpy as jnp

    from baikaldb_tpu.column.batch import Column, ColumnBatch
    from baikaldb_tpu.ops import hashagg, pallas_kernels
    from baikaldb_tpu.ops.hashagg import AggSpec, group_aggregate_dense
    from baikaldb_tpu.types import LType

    ng = 600                         # above the select+reduce crossover (512)
    rng = np.random.default_rng(7)
    n = 5000
    g = rng.integers(0, ng, n).astype(np.int32)
    v = rng.normal(size=n).astype(np.float32)
    batch = ColumnBatch(("g", "v"),
                        [Column(jnp.asarray(g), None, LType.INT32),
                         Column(jnp.asarray(v), None, LType.FLOAT32)])
    specs = [AggSpec("count_star", None, "n"), AggSpec("sum", "v", "s"),
             AggSpec("avg", "v", "a"), AggSpec("min", "v", "mn"),
             AggSpec("max", "v", "mx")]

    # force the TPU gate on CPU: interpret-mode kernels + a fake backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        pallas_kernels, "fused_group_aggregate",
        functools.partial(pallas_kernels.fused_group_aggregate.__wrapped__,
                          interpret=True))
    monkeypatch.setattr(
        pallas_kernels, "partition_histogram",
        functools.partial(pallas_kernels.partition_histogram.__wrapped__,
                          interpret=True))
    monkeypatch.setattr(
        pallas_kernels, "filtered_group_sum",
        functools.partial(pallas_kernels.filtered_group_sum.__wrapped__,
                          interpret=True))
    used = {}
    real = hashagg.dense_lowering       # the one decision (PR 35)

    def spy(*a, **k):
        r = real(*a, **k)
        used["pallas"] = r == "pallas"
        return r
    monkeypatch.setattr(hashagg, "dense_lowering", spy)

    out = group_aggregate_dense(batch, ["g"], [ng], specs)
    assert used["pallas"] is True
    live = np.asarray(out.sel)
    names = np.asarray(out.column("g").data)
    for k in (0, 1, 5, ng - 1):
        rows = v[g == k]
        idx = int(np.nonzero((names == k) & live[:len(names)])[0][0])
        assert int(np.asarray(out.column("n").data)[idx]) == len(rows)
        np.testing.assert_allclose(np.asarray(out.column("s").data)[idx],
                                   rows.astype(np.float64).sum(), rtol=1e-5)
        assert np.asarray(out.column("mn").data)[idx] == rows.min()
        assert np.asarray(out.column("mx").data)[idx] == rows.max()

    # sum-only spec list takes the cheaper kernel (no min/max lanes)
    out_s = group_aggregate_dense(batch, ["g"], [ng],
                                  [AggSpec("sum", "v", "s"),
                                   AggSpec("count", "v", "c")])
    assert used["pallas"] is True
    k = 3
    np.testing.assert_allclose(
        np.asarray(out_s.column("s").data)[k],
        v[g == k].astype(np.float64).sum(), rtol=1e-5)
    assert int(np.asarray(out_s.column("c").data)[k]) == (g == k).sum()

    # int value column -> exactness rule kicks the pallas path out
    batch2 = ColumnBatch(("g", "i"),
                         [Column(jnp.asarray(g), None, LType.INT32),
                          Column(jnp.asarray(g.astype(np.int64)), None,
                                 LType.INT64)])
    out2 = group_aggregate_dense(batch2, ["g"], [ng],
                                 [AggSpec("sum", "i", "s")])
    assert used["pallas"] is False
    assert np.asarray(out2.column("s").data)[0] == g[g == 0].astype(np.int64).sum()


def test_every_defined_flag_is_read(all_flags):
    """A flag nothing reads is a row of SHOW VARIABLES that lies: every
    registered name occurs as ``FLAGS.<name>`` or as a quoted name
    somewhere under baikaldb_tpu/ outside its own ``define(``."""
    import re
    from pathlib import Path

    import baikaldb_tpu

    pkg = Path(baikaldb_tpu.__file__).parent
    src = "\n".join(p.read_text() for p in sorted(pkg.rglob("*.py")))
    src = re.sub(r'\bdefine\(\s*"[a-z_0-9]+"', "define(", src)
    names = sorted(all_flags.snapshot())
    assert len(names) >= 60             # the walk registered the package's
    unread = [n for n in names
              if not re.search(rf'FLAGS\.{n}\b|["\']{n}["\']', src)]
    assert unread == []
