"""First-run check of the served SQL path on the accelerator.

    python chip_smoke.py [--seed N]

One process, no children: a ``Database`` served by ``MySQLServer`` in-process
(the objects ``python -m baikaldb_tpu.server`` builds), TPC-H SF1 and the
BASELINE.json 100M-row filter + GROUP BY table loaded from ``--seed``,
queried over the MySQL wire with the in-repo client and compared with plain
pandas / pyarrow computations over the same Arrow tables.  It exits non-zero
unless jax found a TPU, and when any phase raises, an answer is wrong or
the engine swallowed an exception on the way.  The last stdout line is
``{"ok": true, "device": {...}}``.  It changes no backend, cache or path
setting: where the compile cache lives is utils/compilecache.enable's call.
The one jax option it touches is ``jax_dump_ir_to``, around the Pallas
GROUP BYs: the modules jax hands the compiler there land in
``chiprun_out/chip_smoke_ir/`` and must call the Mosaic kernels by name.
This is the bring-up record.  Where the north-star table is *measured* is
the benchmark's cell ``tpu_northstar_100m.groupby`` (PR 35: the same table
and statement at 16 / 1,000 / 4,000 groups, resident, every answer held to
float64 at 1e-10; ``PERF.md`` sections 4-6); this file's phase stays as it
was, with its own looser tolerances.
Its limit is 1,200 s and most of it is first compiles (Q18's two join-cap
recompiles alone are 210 s; CHANGES.md PR 21 has the readings): trim before
adding, and name every cut in the output as the "cut for the time limit"
lines do.
"""

import argparse
import json
import re
import shutil
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pandas as pd
import pyarrow as pa

import baikaldb_tpu
from baikaldb_tpu import native
from baikaldb_tpu.client.mysql_client import Connection
from baikaldb_tpu.exec.session import Database, Session
from baikaldb_tpu.models import tpch
from baikaldb_tpu.server.mysql_server import MySQLServer
from baikaldb_tpu.utils import compilecache, metrics
from baikaldb_tpu.utils.flags import FLAGS

# f64 aggregates vs pandas/numpy: the relative bound tests/test_tpch_full.py
# uses (_approx); f32 Pallas sums: the bound tests/test_pallas.py states
F64_RTOL = 1e-6
PALLAS_RTOL = PALLAS_ATOL = 1e-4

NORTH_STAR_ROWS = 100_000_000
GROUP_COUNTS = (16, 1000, 4000)
MESH_DEVICES = 4

IR_DIR = Path(__file__).resolve().parent / "chiprun_out" / "chip_smoke_ir"


def say(msg: str) -> None:
    print(msg, flush=True)


def close(got, want, rtol=F64_RTOL, atol=0.0) -> bool:
    return abs(got - want) <= atol + rtol * max(1.0, abs(want))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke: wrong answer: {what}")


# -- 1. device ---------------------------------------------------------------

def phase_device() -> dict:
    from importlib import metadata

    devs = jax.devices()
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs)}
    say(f"device: {device}")
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    say(f"versions: jax {jax.__version__}, jaxlib "
        f"{metadata.version('jaxlib')}, libtpu {libtpu}, "
        f"baikaldb_tpu {baikaldb_tpu.__version__}")
    say(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    say(f"native row core: available={native.available()}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: jax found platform {d.platform!r}, "
                         "not a tpu; nothing was run")
    return device


# -- timed queries --------------------------------------------------------------

def timed(label: str, query, runs: int = 2):
    """Call ``query()`` ``runs`` times; print wall ms, rows, retraces, join-cap
    retries, shuffle rounds and the access path of each run, and require
    that only the first run traces.  -> the last result."""
    res = None
    for i in range(runs):
        r0 = metrics.xla_retraces.value
        c0 = metrics.compile_ms.stats()["count"]
        h0 = metrics.shuffle_rounds.value
        s0 = metrics.stream_chunks.value
        k0 = metrics.stream_chunks_skipped.value
        p0 = metrics.point_lookups.value
        x0 = metrics.index_scans.value
        t0 = time.perf_counter()
        res = query()
        ms = (time.perf_counter() - t0) * 1e3
        retraces = metrics.xla_retraces.value - r0
        # every attempt of the overflow-retry loop that re-traces the plan
        # observes compile_ms once: attempts beyond the first are
        # join/shuffle-cap retries
        cap_retries = max(0, metrics.compile_ms.stats()["count"] - c0 - 1)
        folded = metrics.stream_chunks.value - s0
        skipped = metrics.stream_chunks_skipped.value - k0
        if folded or skipped:
            path = f"stream({folded}/{folded + skipped} chunks)"
        elif metrics.point_lookups.value > p0:
            path = "point"
        elif metrics.index_scans.value > x0:
            path = "index"
        else:
            path = "full"
        rows = res.rows if hasattr(res, "rows") else res
        say(f"  {label} run {i + 1}: {ms:.1f} ms rows={len(rows)} "
            f"retraces={retraces} cap_retries={cap_retries} "
            f"shuffle_rounds={metrics.shuffle_rounds.value - h0} "
            f"access={path}")
        if i > 0:
            check(retraces == 0, f"{label}: run {i + 1} retraced")
    return res


class Wire:
    """One client connection to the served database."""

    def __init__(self, port: int):
        self.conn = Connection(port=port)

    def select(self, label: str, sql: str, runs: int = 2):
        """-> (columns, rows) of ``sql``, run and reported by :func:`timed`."""
        res = timed(label, lambda: self.conn.query(sql), runs)
        return res.columns, res.rows

    def execute(self, sql: str):
        return self.conn.query(sql)


def as_dicts(columns, rows) -> list:
    return [dict(zip(columns, r)) for r in rows]


# -- 2 + 3. TPC-H over the wire ----------------------------------------------

def _day(iso: str):
    return np.datetime64(iso)


# the columns the references read, converted to pandas once per table
REF_COLUMNS = {
    "lineitem": ["l_orderkey", "l_quantity", "l_extendedprice", "l_discount",
                 "l_tax", "l_returnflag", "l_linestatus", "l_shipdate"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority",
               "o_totalprice"],
    "customer": ["c_custkey", "c_name", "c_mktsegment", "c_acctbal"],
}


def ref_frames(tables: dict) -> dict:
    return {name: tables[name].select(cols).to_pandas(
        strings_to_categorical=True, date_as_object=False)
        for name, cols in REF_COLUMNS.items()}


def ref_q1(frames) -> pd.DataFrame:
    f = frames["lineitem"]
    f = f[f.l_shipdate <= _day("1998-09-02")].copy()
    f["disc_price"] = f.l_extendedprice * (1 - f.l_discount)
    f["charge"] = f.disc_price * (1 + f.l_tax)
    g = f.groupby(["l_returnflag", "l_linestatus"], observed=True).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"), sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"), avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"), count_order=("l_quantity", "count"))
    return g.reset_index().astype({"l_returnflag": str, "l_linestatus": str}) \
        .sort_values(["l_returnflag", "l_linestatus"])


def check_q1(rows: list, want: pd.DataFrame) -> None:
    check(len(rows) == len(want), f"q1 rows {len(rows)} != {len(want)}")
    for r, (_, w) in zip(rows, want.iterrows()):
        check((r["l_returnflag"], r["l_linestatus"])
              == (w.l_returnflag, w.l_linestatus), "q1 group keys")
        check(int(r["count_order"]) == w.count_order, "q1 count_order")
        for c in ("sum_qty", "sum_base_price", "sum_disc_price",
                  "sum_charge", "avg_qty", "avg_price", "avg_disc"):
            check(close(float(r[c]), w[c]), f"q1 {c}: {r[c]} vs {w[c]}")


def ref_q6(frames) -> float:
    f = frames["lineitem"]
    f = f[(f.l_shipdate >= _day("1994-01-01"))
          & (f.l_shipdate < _day("1995-01-01"))
          & (f.l_discount >= 0.05) & (f.l_discount <= 0.07)
          & (f.l_quantity < 24)]
    return float((f.l_extendedprice * f.l_discount).sum())


def ref_q3(frames) -> pd.DataFrame:
    c, o, li = frames["customer"], frames["orders"], frames["lineitem"]
    j = (c[c.c_mktsegment == "BUILDING"]
         .merge(o[o.o_orderdate < _day("1995-03-15")], left_on="c_custkey",
                right_on="o_custkey")
         .merge(li[li.l_shipdate > _day("1995-03-15")], left_on="o_orderkey",
                right_on="l_orderkey"))
    j["rev"] = j.l_extendedprice * (1 - j.l_discount)
    return (j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"])["rev"]
            .sum().reset_index()
            .sort_values(["rev", "o_orderdate"], ascending=[False, True])
            .head(10))


def check_q3(rows: list, want: pd.DataFrame, what: str = "q3") -> None:
    check(len(rows) == len(want), f"{what} rows {len(rows)} != {len(want)}")
    for r, (_, w) in zip(rows, want.iterrows()):
        check(int(r["l_orderkey"]) == w.l_orderkey, f"{what} l_orderkey")
        check(close(float(r["revenue"]), w.rev),
              f"{what} revenue {r['revenue']} vs {w.rev}")


def ref_q18(frames) -> pd.DataFrame:
    c, o, li = frames["customer"], frames["orders"], frames["lineitem"]
    big = li.groupby("l_orderkey")["l_quantity"].sum()
    j = (c.merge(o[o.o_orderkey.isin(big[big > 212].index)],
                 left_on="c_custkey", right_on="o_custkey")
         .merge(li, left_on="o_orderkey", right_on="l_orderkey"))
    return (j.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                       "o_totalprice"], observed=True)["l_quantity"]
            .sum().reset_index()
            .sort_values(["o_totalprice", "o_orderdate"],
                         ascending=[False, True]).head(100))


def check_q18(rows: list, want: pd.DataFrame) -> None:
    check(len(rows) == len(want), f"q18 rows {len(rows)} != {len(want)}")
    for r, (_, w) in zip(rows, want.iterrows()):
        check(int(r["o_orderkey"]) == w.o_orderkey, "q18 o_orderkey")
        check(close(float(r["total_qty"]), w.l_quantity), "q18 total_qty")


def phase_tpch(db: Database, wire: Wire, scale: float, seed: int) -> dict:
    """Load TPC-H, run Q1/Q6/Q3/Q18 + point/insert/explain over the wire.
    -> {"frames", "q1", "q3"}: the reference frames and answers the mesh
    phase compares against."""
    t0 = time.perf_counter()
    tables = tpch.load_into(Session(db=db), scale=scale, seed=seed)
    say(f"tpch: SF{scale:g} seed={seed} loaded in "
        f"{time.perf_counter() - t0:.1f} s: lineitem="
        f"{tables['lineitem'].num_rows} orders={tables['orders'].num_rows} "
        f"arrow_bytes={sum(t.nbytes for t in tables.values())}")

    frames = ref_frames(tables)
    want_q1 = ref_q1(frames)
    check_q1(as_dicts(*wire.select("q1", tpch.QUERIES["q1"])), want_q1)
    got = wire.select("q6", tpch.QUERIES["q6"])[1]
    want = ref_q6(frames)
    check(len(got) == 1 and close(float(got[0][0]), want),
          f"q6 {got} vs {want}")
    want_q3 = ref_q3(frames)
    check_q3(as_dicts(*wire.select("q3", tpch.QUERIES["q3"])), want_q3)
    check_q18(as_dicts(*wire.select("q18", tpch.QUERIES["q18"])),
              ref_q18(frames))

    # pk point read: compared with the Arrow row it was loaded from
    o = tables["orders"]
    i = o.num_rows // 2
    key = o.column("o_orderkey")[i].as_py()
    cols, rows = wire.select(
        "point", "SELECT o_orderkey, o_custkey, o_totalprice, o_clerk "
                 f"FROM orders WHERE o_orderkey = {key}")
    check(len(rows) == 1, f"point select returned {len(rows)} rows")
    r = dict(zip(cols, rows[0]))
    check(int(r["o_custkey"]) == o.column("o_custkey")[i].as_py()
          and float(r["o_totalprice"]) == o.column("o_totalprice")[i].as_py()
          and r["o_clerk"] == o.column("o_clerk")[i].as_py(),
          f"point select {r}")

    # an acknowledged INSERT is read back by the next SELECT.  The row sits
    # outside every filter above (ship date past the generator's range, an
    # order key no order has), so the references stay valid afterwards.
    ack = wire.execute(
        "INSERT INTO lineitem VALUES (0, 1, 1, 1, 7.5, 10.0, 0.0, 0.0, 'N', "
        "'O', '1999-06-01', '1999-06-01', '1999-06-01', 'NONE', 'MAIL', "
        "'chip smoke')")
    check(ack.affected_rows == 1, f"insert acked {ack.affected_rows} rows")
    _, rows = wire.select(
        "read-back", "SELECT COUNT(*), SUM(l_quantity) FROM lineitem "
                     "WHERE l_orderkey = 0", runs=1)
    check(int(rows[0][0]) == 1 and float(rows[0][1]) == 7.5,
          f"insert read back as {rows}")

    plan = wire.execute("EXPLAIN ANALYZE " + tpch.QUERIES["q6"]).rows
    # a resident execution reports "-- run:", a streamed one "-- stream:"
    check(any(r[0].startswith(("-- run:", "-- stream:")) for r in plan),
          "EXPLAIN ANALYZE timed no execution")
    for r in plan:
        say(f"  explain: {r[0]}")
    return {"frames": frames, "q1": want_q1, "q3": want_q3}


# -- 4 + 5. the north-star shape and the dense group-by kernels ---------------

def _group_col(ng: int) -> str:
    return "g" if ng == GROUP_COUNTS[0] else f"g{ng}"


def ref_groups(g_live: np.ndarray, v_live: np.ndarray) -> pd.DataFrame:
    """count/sum/avg/min/max of v per g (pyarrow sums a FLOAT in DOUBLE)."""
    out = pa.table({"g": g_live, "v": v_live}).group_by("g").aggregate(
        [("v", "count"), ("v", "sum"), ("v", "min"), ("v", "max")]) \
        .to_pandas().set_index("g").rename(columns={
            "v_count": "n", "v_sum": "s", "v_min": "mn", "v_max": "mx"})
    out["a"] = out.s / out.n
    return out


def check_groups(label, columns, rows, want, rtol, atol) -> None:
    check(len(rows) == len(want), f"{label} rows {len(rows)} != {len(want)}")
    worst = 0.0
    for r in rows:
        r = dict(zip(columns, r))
        w = want.loc[int(r["g"])]
        check(int(r["n"]) == w.n, f"{label} count g={r['g']}")
        for c in ("s", "a", "mn", "mx"):
            if c not in r:
                continue
            got = float(r[c])
            # min/max of a FLOAT column are exact picks, not sums
            ok = got == w[c] if c in ("mn", "mx") \
                else close(got, w[c], rtol, atol)
            check(ok, f"{label} {c} g={r['g']}: {got} vs {w[c]}")
            worst = max(worst, abs(got - w[c]) / max(1.0, abs(w[c])))
    say(f"  {label}: {len(rows)} groups match, worst rel err {worst:.3g}")


def mosaic_kernels(ir_text: str) -> set:
    """Names of the Pallas kernels a StableHLO module calls through Mosaic
    (``stablehlo.custom_call @tpu_custom_call ... kernel_name = "..."``)."""
    return {name for line in ir_text.splitlines() if "@tpu_custom_call" in line
            for name in re.findall(r'kernel_name = "(\w+)"', line)}


def compiled_kernels(label: str, query):
    """Call ``query()`` while jax writes every module it hands the compiler
    to ``IR_DIR/<label>``.  -> (its result, the Mosaic kernels they call)."""
    out = IR_DIR / label.replace(" ", "_")
    shutil.rmtree(out, ignore_errors=True)
    before = jax.config.read("jax_dump_ir_to")
    jax.config.update("jax_dump_ir_to", str(out))
    try:
        res = query()
    finally:
        jax.config.update("jax_dump_ir_to", before)
    modules = sorted(out.glob("*.mlir"))
    kernels = set().union(*(mosaic_kernels(m.read_text()) for m in modules))
    say(f"  {label}: {len(modules)} modules compiled, Mosaic kernels "
        f"{sorted(kernels) or 'none'}")
    return res, kernels


# (group count, label, aggregates beside COUNT(*), the Pallas kernels the
# query must compile — ops/hashagg._pallas_dense_cols: SUM/AVG with MIN/MAX
# on the same column _agg_kernel, SUM/AVG alone _sum_kernel; COUNT(*) is the
# fused kernel's own count (v holds no NULL) and _hist_kernel only where no
# value column is aggregated)
PALLAS_QUERIES = [
    (1000, "agg", "SUM(v) s, AVG(v) a, MIN(v) mn, MAX(v) mx", {"_agg_kernel"}),
    (1000, "sum", "SUM(v) s, AVG(v) a", {"_sum_kernel"}),
    (1000, "count", "", {"_hist_kernel"}),
    (4000, "agg", "SUM(v) s, AVG(v) a, MIN(v) mn, MAX(v) mx", {"_agg_kernel"}),
]


def phase_groupby(db: Database, wire: Wire, n_rows: int, seed: int) -> None:
    rng = np.random.default_rng(seed)
    cols = {_group_col(ng): rng.integers(0, ng, n_rows, dtype=np.int32)
            for ng in GROUP_COUNTS}
    cols["v"] = rng.standard_normal(n_rows, dtype=np.float32)
    ddl = ", ".join(f"{_group_col(ng)} INT" for ng in GROUP_COUNTS)
    wire.execute(f"CREATE TABLE t ({ddl}, v FLOAT)")
    t0 = time.perf_counter()
    Session(db=db).load_arrow("t", pa.table(cols))
    say(f"groupby: t has {n_rows} rows x {len(cols)} columns "
        f"(load_arrow {time.perf_counter() - t0:.1f} s)")

    # the engine evaluates v*2+1 > 0.5 over a FLOAT column in DOUBLE; the
    # mask is taken in cache-sized pieces (whole-array temporaries cost 8 s)
    v = cols["v"]
    mask = np.empty(n_rows, dtype=bool)
    for i in range(0, n_rows, 1 << 22):
        piece = v[i:i + (1 << 22)]
        mask[i:i + len(piece)] = piece.astype(np.float64) * 2 + 1 > 0.5
    v_live = v[mask]
    refs = {ng: ref_groups(cols[_group_col(ng)][mask], v_live)
            for ng in GROUP_COUNTS}

    say("  cut for the time limit: every query over t runs once, not twice "
        "(a pass takes 10-15 s); SUM/AVG without MIN/MAX and COUNT(*) alone "
        "run at one group count only; at 16 groups the north-star query is "
        "the select+reduce GROUP BY (COUNT, SUM, AVG, MIN), the one that "
        "adds MAX is cut")
    # BASELINE.json's headline query, default flags then resident.  16
    # groups: the select+reduce lowering (ops/segments.py)
    north = ("SELECT g, COUNT(*) n, SUM(v) s, AVG(v) a, MIN(v) mn FROM t "
             "WHERE v*2+1 > 0.5 GROUP BY g")
    c, r = wire.select("north-star default", north, runs=1)
    check_groups("north-star default", c, r, refs[GROUP_COUNTS[0]],
                 F64_RTOL, 0.0)
    streaming = int(bool(FLAGS.streaming_scan))
    wire.execute("SET GLOBAL streaming_scan = 0")
    c, r = wire.select("north-star resident", north, runs=1)
    wire.execute(f"SET GLOBAL streaming_scan = {streaming}")
    check_groups("north-star resident", c, r, refs[GROUP_COUNTS[0]],
                 F64_RTOL, 0.0)

    # 1,000 and 4,000 groups: the Pallas kernels, named in what was compiled
    for ng, kind, aggs, want in PALLAS_QUERIES:
        g, label = _group_col(ng), f"dense {ng} {kind}"
        items = ", ".join(filter(None, (f"{g} g", "COUNT(*) n", aggs)))
        sql = f"SELECT {items} FROM t WHERE v*2+1 > 0.5 GROUP BY {g}"
        (c, r), kernels = compiled_kernels(
            label, lambda: wire.select(label, sql, runs=1))
        check(want <= kernels, f"{label} compiled no Mosaic call of "
                               f"{sorted(want - kernels)}")
        check_groups(label, c, r, refs[ng], PALLAS_RTOL, PALLAS_ATOL)


# -- 7. four chips -------------------------------------------------------------

def phase_mesh(wire: Wire, tpch_refs: dict) -> None:
    """The deployment's mesh, entered as the benchmark's mesh cell enters
    it: ``SET GLOBAL mesh_devices`` over the wire connection, after which
    every SELECT of that connection is one shard_map program; set back to
    0 at the end, so the phases after it run on one device as before."""
    n = len(jax.devices())
    if n < MESH_DEVICES:
        say(f"mesh: not run, {n} device(s)")
        return

    def run(label: str, sql: str, runs: int = 2) -> list:
        m0 = metrics.mesh_programs.value
        rows = as_dicts(*wire.select(f"mesh {label}", sql, runs))
        check(metrics.mesh_programs.value - m0 == runs,
              f"mesh {label}: not a mesh program")
        return rows

    say(f"mesh: {MESH_DEVICES} devices, lineitem row-sharded")
    wire.execute(f"SET GLOBAL mesh_devices = {MESH_DEVICES}")
    check_q1(run("q1", tpch.QUERIES["q1"]), tpch_refs["q1"])
    check_q3(run("q3 default", tpch.QUERIES["q3"]), tpch_refs["q3"])
    # MIN/MAX of a DOUBLE merge in-network: the chip lowers no 64-bit max
    # all-reduce, parallel/agg._pextremum all_gathers and reduces locally
    rows = run("min/max merge",
               "SELECT l_returnflag rf, l_linestatus ls, "
               "MIN(l_extendedprice) mn, MAX(l_extendedprice) mx "
               "FROM lineitem WHERE l_orderkey > 0 "
               "GROUP BY l_returnflag, l_linestatus "
               "ORDER BY l_returnflag, l_linestatus")
    want = tpch_refs["frames"]["lineitem"] \
        .groupby(["l_returnflag", "l_linestatus"], observed=True) \
        .l_extendedprice.agg(["min", "max"]).reset_index() \
        .astype({"l_returnflag": str, "l_linestatus": str}) \
        .sort_values(["l_returnflag", "l_linestatus"])
    check(len(rows) == len(want), "min/max merge rows")
    for r, (_, w) in zip(rows, want.iterrows()):
        check((r["rf"], r["ls"]) == (w.l_returnflag, w.l_linestatus)
              and close(float(r["mn"]), w["min"])
              and close(float(r["mx"]), w["max"]),
              f"min/max merge {r} vs {tuple(w)}")
    say("  cut for the time limit: Q3 with repartition forced "
        "(mpp_broadcast_rows=0, dense_join_span_max=0) is a 118 s compile "
        "and is not run; q3 default above and the GROUP BY below each "
        "compile an all_to_all (see their shuffle_rounds)")
    # a DOUBLE-keyed shuffle: the key hash of a 64-bit float
    # (utils/hashing.split64).  Over customer, not lineitem: it is here for
    # the lowering, and its compile alone took 125 s over lineitem's shards
    h0 = metrics.shuffle_rounds.value
    rows = run("double-key group-by",
               "SELECT c_acctbal b, COUNT(*) n FROM customer "
               "GROUP BY c_acctbal ORDER BY c_acctbal")
    check(metrics.shuffle_rounds.value > h0, "double-key group-by ran no "
                                              "shuffle round")
    want = tpch_refs["frames"]["customer"].c_acctbal.value_counts() \
        .sort_index()
    check(len(rows) == len(want)
          and all(close(float(r["b"]), b) and int(r["n"]) == n
                  for r, (b, n) in zip(rows, want.items())),
          "double-key group-by")
    wire.execute("SET GLOBAL mesh_devices = 0")
    for d in jax.devices():
        say(f"  {d}: bytes_in_use="
            f"{(d.memory_stats() or {}).get('bytes_in_use')}")


# -- 6. what was swallowed -------------------------------------------------------

def phase_counters() -> None:
    drained = compilecache.AOT.drain(300)
    say(f"counters: aot publisher drained={drained} "
        f"publishes={metrics.aot_cache_publishes.value} "
        f"hits={metrics.aot_cache_hits.value} "
        f"misses={metrics.aot_cache_misses.value}")
    swallowed = {k: v["value"] for k, v in metrics.REGISTRY.expose().items()
                 if k.startswith("swallowed.") and v["value"]}
    say(f"counters: swallowed={swallowed or 0}")
    for c in (metrics.dispatch_fallbacks, metrics.aot_cache_fallbacks,
              metrics.plan_cache_param_fallbacks,
              metrics.shuffle_overflow_retries, metrics.xla_retraces):
        say(f"counters: {c.name}={c.value}")
    c = metrics.compile_ms.stats()
    say(f"counters: plan compiles={c['count']} avg_ms={c['avg_ms']}")
    check(not swallowed and drained
          and metrics.swallowed_exceptions.value == 0,
          f"swallowed exceptions on the smoke path: {swallowed}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    t_start = time.perf_counter()
    device = phase_device()
    db = Database()
    srv = MySQLServer(db, port=0).start()
    try:
        wire = Wire(srv.port)
        say(f"server: MySQLServer on 127.0.0.1:{srv.port}")
        refs = phase_tpch(db, wire, 1.0, args.seed)
        # ahead of the 100M-row phase, which hides the AOT publisher's work:
        # it compiles every mesh program a second time in the background
        # (~120 s for the last one) and phase_counters waits for it
        phase_mesh(wire, refs)
        phase_groupby(db, wire, NORTH_STAR_ROWS, args.seed)
        phase_counters()
        wire.conn.close()
    finally:
        srv.stop()
    say(f"chip_smoke: all phases passed in "
        f"{time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
