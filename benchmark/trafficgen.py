"""The one traffic generator: a mix of SQL transactions read from a data file.

A traffic file (``benchmark/traffic/<name>.json``) holds

- ``clients``: how many closed-loop clients, each with its own connection;
- ``statements``: name -> ``{"sql", "params", "ref", "annotation"}``.  ``sql``
  is a ``str.format`` template over the statement's parameters.  ``params``
  are drawn in the order listed, each one of

  - ``{"int": [lo, hi]}``: uniform whole number, both ends included;
  - ``{"special": [lo, hi], "iter": 12, "pct": 1, "res": 75}``: sysbench's
    default ``rand_type`` over the same range (``benchmark/dists.py``);
  - ``{"expr": "...", "format": ".2f"}``: arithmetic over the parameters
    drawn before it (``format`` optional);
  - ``{"date": "1998-12-01", "minus_days": "..."}``: an ISO date.

  A bound or an expression may name the variables the configuration's loader
  returns (``table_size``, ...).  ``ref`` names the plain reference that
  answers the statement (``module:attribute``);
- ``transactions``: the list a client cycles through, each ``{"name",
  "begin", "commit", "annotation", "steps": [{"statement", "repeat"}]}``;
  ``begin``/``commit`` may be null (autocommit);
- ``warmup_rounds``: how often each kind of transaction runs on each
  connection, one connection after another, before the window; and
  ``warmup_together_s``: for how long all clients then run at once;
- ``prepared`` (default false): every statement, ``begin`` and ``commit``
  included, goes out as sysbench's ``db-ps-mode=auto`` sends it: prepared
  once a connection (COM_STMT_PREPARE of its template with a ``?`` for each
  parameter, ``prepared_form``), then COM_STMT_EXECUTE with the parameters
  bound in template order.  ``sql`` stays the text form, for the record and
  the reference, and the draws do not change.  Otherwise a statement goes
  out as COM_QUERY of its text.

Every client draws from ``numpy.random.default_rng([seed, stream, client])``:
the statements of a run are a pure function of the seed, and the window's
stream is apart from the warm-up's.
"""

import ast
import datetime
import itertools
import json
import operator
import string
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from benchmark import dists

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
WINDOW_STREAM, WARMUP_STREAM = 1, 2

_OPS = {ast.Add: operator.add, ast.Sub: operator.sub, ast.Mult: operator.mul,
        ast.Div: operator.truediv}


def arith(expr, names: dict):
    """Value of a number or of an arithmetic expression over ``names``."""
    if not isinstance(expr, str):
        return expr

    def ev(node):
        if isinstance(node, ast.Constant) \
                and isinstance(node.value, (int, float)):
            return node.value
        if isinstance(node, ast.Name):
            if node.id not in names:
                raise KeyError(f"traffic: unknown name {node.id!r} in "
                               f"{expr!r}; known: {sorted(names)}")
            return names[node.id]
        if isinstance(node, ast.BinOp) and type(node.op) in _OPS:
            return _OPS[type(node.op)](ev(node.left), ev(node.right))
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            return -ev(node.operand)
        raise ValueError(f"traffic: {expr!r} is not plain arithmetic")

    return ev(ast.parse(expr, mode="eval").body)


def draw_params(spec: dict, rng, variables: dict) -> dict:
    """One draw of a statement's parameters, in the order listed."""
    out: dict = {}
    for name, how in spec.items():
        names = {**variables, **out}
        if "int" in how:
            lo, hi = (int(arith(b, names)) for b in how["int"])
            out[name] = int(rng.integers(lo, hi + 1))
        elif "special" in how:
            lo, hi = (int(arith(b, names)) for b in how["special"])
            out[name] = int(dists.special(
                rng, lo, hi, 1, how["iter"], how["pct"], how["res"])[0])
        elif "date" in how:
            day = datetime.date.fromisoformat(how["date"]) \
                - datetime.timedelta(days=int(arith(how["minus_days"], names)))
            out[name] = day.isoformat()
        elif "expr" in how:
            v = arith(how["expr"], names)
            out[name] = format(v, how["format"]) if "format" in how else v
        else:
            raise ValueError(f"traffic: parameter {name!r}: unknown kind "
                             f"{sorted(how)}")
    return out


def prepared_form(template: str) -> tuple:
    """A ``str.format`` template as a statement to prepare -> (its SQL with
    a ``?`` for each field, the fields' names in that order).  A field that
    is a whole quoted literal (``'{c}'``) loses its quotes: a bound string
    needs none.  A field inside a longer literal, or one with a format
    spec or a conversion, cannot be bound and is refused."""
    sql, names = "", []
    for text, name, spec, conv in string.Formatter().parse(template):
        sql += text
        if name is None:
            continue
        if spec or conv:
            raise ValueError(f"traffic: {{{name}}} in {template!r}: a "
                             "prepared parameter takes no format")
        names.append(name)
        sql += "\0"
    sql = sql.replace("'\0'", "?")
    prefixes = itertools.accumulate(sql.split("\0")[:-1])
    if any(p.count("'") % 2 for p in prefixes):
        raise ValueError(f"traffic: {template!r}: a prepared parameter "
                         "inside a quoted literal")
    return sql.replace("\0", "?"), tuple(names)


@dataclass
class Statement:
    name: str
    sql: str
    params: dict
    prepare: str | None = None      # sent prepared: the SQL with ``?``
    args: tuple = ()                # its parameters, in ``?`` order


@dataclass
class Transaction:
    name: str
    annotation: str
    begin: str | None
    commit: str | None
    statements: list = field(default_factory=list)
    prepared: bool = False          # begin and commit too


def load_traffic(name: str) -> dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        return json.load(f)


class Client:
    """The transactions of one client, drawn from the seed."""

    def __init__(self, traffic: dict, variables: dict, seed: int, index: int,
                 stream: int = WINDOW_STREAM):
        self.traffic, self.variables, self.index = traffic, variables, index
        self.rng = np.random.default_rng([seed, stream, index])
        self.turn = index       # clients start the cycle at different places
        self.prepared = {name: prepared_form(s["sql"])
                         for name, s in traffic["statements"].items()} \
            if traffic.get("prepared") else {}

    def next(self) -> Transaction:
        kinds = self.traffic["transactions"]
        t = kinds[self.turn % len(kinds)]
        self.turn += 1
        txn = Transaction(t["name"], t.get("annotation", "client.txn"),
                          t.get("begin"), t.get("commit"),
                          prepared=bool(self.prepared))
        for step in t["steps"]:
            s = self.traffic["statements"][step["statement"]]
            for _ in range(step.get("repeat", 1)):
                p = draw_params(s.get("params", {}), self.rng, self.variables)
                st = Statement(step["statement"], s["sql"].format(**p), p)
                if self.prepared:
                    st.prepare, names = self.prepared[step["statement"]]
                    st.args = tuple(p[a] for a in names)
                txn.statements.append(st)
        return txn
