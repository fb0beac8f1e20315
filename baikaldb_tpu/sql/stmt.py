"""Statement AST nodes (reference: include/sqlparser/{dml,ddl}.h arena AST;
here plain dataclasses the planners consume)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from ..expr.ast import Expr


@dataclass
class TableRef:
    database: Optional[str]
    name: str
    alias: Optional[str] = None
    subquery: Optional["SelectStmt"] = None  # derived table

    @property
    def label(self) -> str:
        return self.alias or self.name


@dataclass
class JoinClause:
    kind: str          # inner | left | right | cross | semi | anti
    table: TableRef
    on: Optional[Expr] = None
    using: list[str] = field(default_factory=list)


@dataclass
class SelectItem:
    expr: Optional[Expr]   # None for plain *
    alias: Optional[str] = None
    star_table: Optional[str] = None  # "t.*"


@dataclass
class OrderItem:
    expr: Expr
    asc: bool = True


@dataclass
class SelectStmt:
    items: list[SelectItem]
    table: Optional[TableRef] = None
    joins: list[JoinClause] = field(default_factory=list)
    where: Optional[Expr] = None
    group_by: list[Expr] = field(default_factory=list)
    having: Optional[Expr] = None
    order_by: list[OrderItem] = field(default_factory=list)
    limit: Optional[int] = None
    offset: int = 0
    distinct: bool = False
    union: Optional[tuple[str, "SelectStmt"]] = None  # ("all"|"distinct", rhs)
    ctes: list[tuple[str, "SelectStmt"]] = field(default_factory=list)
    # SELECT ... INTO OUTFILE 'path' (reference: full_export_node streaming
    # export): (path, field_sep, line_sep) or None
    into_outfile: Optional[tuple] = None


@dataclass
class InsertStmt:
    table: TableRef
    columns: list[str]
    rows: list[list]              # literal rows
    select: Optional[SelectStmt] = None
    replace: bool = False
    # ON DUPLICATE KEY UPDATE assignments: (col, ("lit", v) | ("values", c))
    on_dup: list = field(default_factory=list)


@dataclass
class UpdateStmt:
    table: TableRef
    assignments: list[tuple[str, Expr]]
    where: Optional[Expr] = None


@dataclass
class DeleteStmt:
    table: TableRef
    where: Optional[Expr] = None


@dataclass
class ColumnDef:
    name: str
    type_name: str
    nullable: bool = True
    primary: bool = False
    auto_increment: bool = False


@dataclass
class CreateTableStmt:
    table: TableRef
    columns: list[ColumnDef]
    primary_key: list[str] = field(default_factory=list)
    indexes: list[tuple[str, str, list[str]]] = field(default_factory=list)  # (kind,name,cols)
    if_not_exists: bool = False
    options: dict = field(default_factory=dict)


@dataclass
class AlterTableStmt:
    table: TableRef
    action: str       # add_column | drop_column | add_rollup | drop_rollup
    #                 # | add_index | drop_index
    column: Optional[ColumnDef] = None
    column_name: str = ""
    rollup_name: str = ""
    rollup_keys: list = field(default_factory=list)
    rollup_aggs: list = field(default_factory=list)   # column names
    index_kind: str = "key"      # key | unique | fulltext
    index_name: str = ""
    index_cols: list = field(default_factory=list)
    partition_name: str = ""     # add_partition | drop_partition
    partition_upper: object = None   # None = MAXVALUE


@dataclass
class DropTableStmt:
    table: TableRef
    if_exists: bool = False


@dataclass
class CreateViewStmt:
    """CREATE [OR REPLACE] VIEW name [(cols)] AS select (reference: view
    DDL, ddl_planner.cpp)."""
    table: TableRef
    select_sql: str              # the view body, stored as SQL text
    columns: list = field(default_factory=list)
    or_replace: bool = False


@dataclass
class DropViewStmt:
    table: TableRef
    if_exists: bool = False


@dataclass
class CreateMatViewStmt:
    """CREATE MATERIALIZED VIEW name AS select — an incrementally
    maintained GROUP BY rollup (cdc/views.py)."""
    table: TableRef
    select_sql: str              # the view body, stored as SQL text
    if_not_exists: bool = False


@dataclass
class DropMatViewStmt:
    table: TableRef
    if_exists: bool = False


@dataclass
class CreateSubscriptionStmt:
    """CREATE SUBSCRIPTION name [ON table] — a durable named CDC cursor
    (cdc/streams.py)."""
    name: str
    table: Optional[TableRef] = None
    if_not_exists: bool = False


@dataclass
class DropSubscriptionStmt:
    name: str
    if_exists: bool = False


@dataclass
class FetchStmt:
    """FETCH [n] FROM subscription — deliver the next batch of change
    events and durably advance the cursor past them."""
    name: str
    limit: int = 0               # 0 = cdc.streams.FETCH_BATCH


@dataclass
class TruncateStmt:
    table: TableRef


@dataclass
class CreateDatabaseStmt:
    name: str
    if_not_exists: bool = False


@dataclass
class DropDatabaseStmt:
    name: str
    if_exists: bool = False


@dataclass
class UseStmt:
    database: str


@dataclass
class ShowStmt:
    what: str   # tables | databases | create_table | columns | index |
    #             variables | status | processlist | grants | regions |
    #             profile | profiles
    database: Optional[str] = None
    table: Optional[TableRef] = None
    pattern: Optional[str] = None
    user: Optional[str] = None
    query_id: Optional[int] = None    # SHOW PROFILE FOR QUERY n
    full: bool = False                # SHOW FULL PROCESSLIST: untruncated Info


@dataclass
class DescribeStmt:
    table: TableRef


@dataclass
class ExplainStmt:
    stmt: SelectStmt
    fmt: Optional[str] = None


@dataclass
class TxnStmt:
    kind: str      # begin | commit | rollback


@dataclass
class KillStmt:
    """KILL [QUERY|CONNECTION] <id> (reference: the kill path through
    state_machine.cpp).  ``target_id`` is a processlist connection id;
    QUERY cancels the statement it is running, CONNECTION additionally
    tears the connection down."""
    kind: str            # query | connection
    target_id: int


@dataclass
class SetStmt:
    """SET [GLOBAL|SESSION] name = value (reference: setkv_planner.cpp).

    GLOBAL names hit the process flag registry (utils/flags.py); session
    names (incl. @user variables) live on the Session."""
    name: str
    value: object
    scope: str = "session"      # session | global
    more: list = field(default_factory=list)    # extra (name, value) pairs


@dataclass
class CreateUserStmt:
    name: str
    password: str = ""
    if_not_exists: bool = False


@dataclass
class DropUserStmt:
    name: str
    if_exists: bool = False


@dataclass
class GrantStmt:
    level: str                          # all | select
    db: str                             # database name or "*"
    user: str


@dataclass
class RevokeStmt:
    db: str
    user: str


@dataclass
class LoadDataStmt:
    path: str
    table: TableRef
    sep: str = ","
    ignore_lines: int = 0


@dataclass
class HandleStmt:
    """Operator admin command (reference: handle_helper.cpp command map)."""
    command: str
    args: list = field(default_factory=list)


@dataclass
class PrepareStmt:
    """PREPARE name FROM 'sql' (reference: COM_STMT_PREPARE and the textual
    PREPARE of state_machine.cpp).  The body is stored as text and re-parsed
    per EXECUTE; the auto-parameterized plan cache (plan/paramize.py) makes
    every EXECUTE of one shape share a single compiled executable."""
    name: str
    sql: str


@dataclass
class ExecuteStmt:
    """EXECUTE name [USING @var | literal, ...]."""
    name: str
    params: list = field(default_factory=list)  # ("var", name) | ("lit", v)


@dataclass
class DeallocateStmt:
    """DEALLOCATE | DROP PREPARE name."""
    name: str
