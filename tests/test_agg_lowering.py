"""The three lowerings of a dense GROUP BY, visible (PR 35): the one
decision (``ops/hashagg.dense_lowering``), EXPLAIN's label, and the counters
``agg_select_reduce_runs`` / ``agg_pallas_runs`` / ``agg_scatter_runs``.

On the CPU the dense aggregate is the scatter, so the served counts here
land on ``agg_scatter_runs``; the select+reduce and Pallas choices are read
from plans and programs lowered for the TPU from the CPU, as
``tests/test_bring_up.py`` lowers the Mosaic kernels (no chip needed to
lower).
"""

import numpy as np
import pyarrow as pa
import pytest

import jax

from baikaldb_tpu.exec import executor
from baikaldb_tpu.exec.session import Database, Session
from baikaldb_tpu.ops.hashagg import AggSpec, LOWERINGS, dense_lowering
from baikaldb_tpu.types import LType
from baikaldb_tpu.utils import metrics

COUNTERS = {"select_reduce": metrics.agg_select_reduce_runs,
            "pallas": metrics.agg_pallas_runs,
            "scatter": metrics.agg_scatter_runs}
NORTH = ("SELECT {k}, COUNT(*) n, SUM(v) s, AVG(v) a, MIN(v) mn FROM t "
         "WHERE v*2+1 > {x} GROUP BY {k}")


def _counts() -> dict:
    return {k: c.value for k, c in COUNTERS.items()}


def _grew(before: dict) -> dict:
    return {k: v - before[k] for k, v in _counts().items() if v != before[k]}


@pytest.fixture(scope="module")
def north():
    """The north-star table at 20,000 rows."""
    rng = np.random.default_rng(35)
    n = 20_000
    s = Session(db=Database())
    s.execute("CREATE TABLE t (g INT, g1000 INT, g4000 INT, g9000 INT, "
              "i BIGINT, v FLOAT, d DOUBLE)")
    s.load_arrow("t", pa.table({
        "g": rng.integers(0, 16, n, dtype=np.int32),
        "g1000": rng.integers(0, 1000, n, dtype=np.int32),
        "g4000": rng.integers(0, 4000, n, dtype=np.int32),
        "g9000": (np.arange(n) % 9000).astype(np.int32),
        "i": rng.integers(0, 100, n),
        "v": rng.standard_normal(n, dtype=np.float32),
        "d": rng.standard_normal(n)}))
    return s


def _label(s: Session, sql: str) -> str:
    lines = [ln.strip() for ln in s.execute("EXPLAIN " + sql)
             .plan_text.splitlines() if ln.strip().startswith("Agg(")]
    assert len(lines) == 1, lines
    return lines[0]


# -- the decision -------------------------------------------------------------

FLOAT_SPECS = [AggSpec("count_star", None, "n"), AggSpec("sum", "v", "s"),
               AggSpec("avg", "v", "a"), AggSpec("min", "v", "mn")]
TYPES = {"v": LType.FLOAT32, "d": LType.FLOAT64, "i": LType.INT64}


@pytest.mark.parametrize("backend, ng, specs, want", [
    ("cpu", 17, FLOAT_SPECS, "scatter"),
    ("cpu", 1001, FLOAT_SPECS, "scatter"),
    ("tpu", 17, FLOAT_SPECS, "select_reduce"),
    ("tpu", 511, FLOAT_SPECS, "select_reduce"),
    ("tpu", 512, FLOAT_SPECS, "pallas"),        # 513 segments with the dead bucket
    ("tpu", 1001, FLOAT_SPECS, "pallas"),
    ("tpu", 4001, FLOAT_SPECS, "pallas"),
    ("tpu", 4095, FLOAT_SPECS, "pallas"),
    ("tpu", 4096, FLOAT_SPECS, "scatter"),
    ("tpu", 1001, [AggSpec("sum", "i", "s")], "scatter"),       # integer sum
    ("tpu", 17, [AggSpec("sum", "i", "s")], "select_reduce"),
    ("tpu", 1001, [AggSpec("sum", "d", "s")], "pallas"),
    ("tpu", 1001, [AggSpec("max", "d", "m")], "scatter"),       # f64 extremum
    ("tpu", 1001, [AggSpec("count", "v", "c", distinct=True)], "scatter"),
    ("tpu", 1001, [AggSpec("stddev", "v", "sd")], "scatter"),
    ("tpu", 1001, [AggSpec("count_star", None, "n")], "pallas"),
])
def test_dense_lowering_is_one_decision(monkeypatch, backend, ng, specs, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    got = dense_lowering(specs, TYPES.__getitem__, ng)
    assert got == want and got in LOWERINGS


# -- served on the CPU: the scatter -------------------------------------------

@pytest.mark.parametrize("key, domain", [("g", 16), ("g1000", 1000),
                                         ("g4000", 4000)])
def test_an_execution_counts_its_dense_aggregate(north, key, domain):
    """+1 an execution by the lowering the program was traced with; a new
    literal runs the same program and counts again; EXPLAIN names it."""
    before = _counts()
    rows = north.query(NORTH.format(k=key, x="0.50"))
    assert 0 < len(rows) <= domain
    assert _grew(before) == {"scatter": 1}
    traces = metrics.xla_retraces.value
    north.query(NORTH.format(k=key, x="0.75"))
    north.query(NORTH.format(k=key, x="1.00"))
    assert metrics.xla_retraces.value == traces
    assert _grew(before) == {"scatter": 3}
    label = _label(north, NORTH.format(k=key, x="0.25"))
    assert f"dense[{domain}][scatter]" in label
    assert _grew(before) == {"scatter": 3}      # EXPLAIN runs nothing


def test_two_dense_aggregates_count_twice_and_others_not_at_all(north):
    before = _counts()
    north.query("SELECT a.g, a.n, b.m FROM (SELECT g, COUNT(*) n FROM t "
                "GROUP BY g) a JOIN (SELECT g, MAX(i) m FROM t GROUP BY g) b "
                "ON a.g = b.g")
    assert _grew(before) == {"scatter": 2}
    before = _counts()
    north.query("SELECT COUNT(*) c, SUM(v) s FROM t WHERE v > 0")   # scalar
    north.query("SELECT d, COUNT(*) c FROM t GROUP BY d")           # sorted
    assert _grew(before) == {}


def test_show_status_lists_the_three_counters(north):
    names = {str(r[0]).partition(".")[0]
             for r in north.execute("SHOW STATUS").rows}
    assert {"agg_select_reduce_runs", "agg_pallas_runs",
            "agg_scatter_runs"} <= names


# -- lowered for the TPU from the CPU -----------------------------------------

@pytest.mark.parametrize("key, domain, want, passes", [
    ("g", 16, "select_reduce", 1), ("g1000", 1000, "pallas", 0),
    ("g4000", 4000, "pallas", 0), ("g9000", 9000, "scatter", 1)])
def test_tpu_plan_names_and_traces_its_lowering(north, monkeypatch, key,
                                                domain, want, passes):
    """The plan of a north-star statement, labelled and lowered as the chip
    would: EXPLAIN says which lowering, the traced program records the same
    one and the passes it makes only to count rows (``v`` holds no NULL: one
    on a segment arm, none beside the fused kernel), and the module holds a
    Mosaic call exactly where it is Pallas: the fused kernel alone."""
    sql = NORTH.format(k=key, x="0.50")
    captured = {}
    real = Session._run_plan

    def spy(self, entry, batches, shape_key):
        captured["plan"], captured["batches"] = entry["plan"], batches
        return real(self, entry, batches, shape_key)

    monkeypatch.setattr(Session, "_run_plan", spy)
    north.query(sql)
    monkeypatch.setattr(Session, "_run_plan", real)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert f"dense[{domain}][{want}]" in _label(north, sql)
    raw = executor.compile_plan(captured["plan"])
    text = jax.jit(raw).trace(captured["batches"]) \
        .lower(lowering_platforms=("tpu",)).as_text()
    assert raw.agg_lowerings == [want] and raw.agg_count_passes == [passes]
    assert text.count("tpu_custom_call") == (want == "pallas")
    assert "_hist_kernel" not in text
    assert ("stablehlo.scatter" in text) == (want == "scatter")
    assert executor.traced_extra(raw, False) == {
        "agg_lowerings": (want,), "agg_count_passes": passes}


def test_an_aot_loaded_program_counts_what_its_artifact_recorded():
    shim = executor.AotRawShim([], {"agg_lowerings": ("select_reduce",
                                                      "pallas", "pallas"),
                                    "agg_count_passes": 2})
    before, passes = _counts(), metrics.agg_count_passes.value
    executor.count_lowerings(shim)
    assert _grew(before) == {"select_reduce": 1, "pallas": 2}
    assert metrics.agg_count_passes.value == passes + 2
    old = executor.AotRawShim([{"cap": 8}], None)   # an artifact from before
    assert old.agg_lowerings == [] and old.exchange_bytes == [0]
    assert old.agg_count_passes == [0]
    executor.count_lowerings(old)
    assert _grew(before) == {"select_reduce": 1, "pallas": 2}
    assert metrics.agg_count_passes.value == passes + 2
