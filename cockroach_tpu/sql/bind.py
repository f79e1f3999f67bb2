"""AST -> logical Plan binding: name resolution, literal typing, join
ordering, aggregate extraction.

Reference seams:
- optbuilder (pkg/sql/opt/optbuilder/builder.go:242): AST -> relational
  expression with resolved columns — this file's job.
- join ordering (pkg/sql/opt/xform join reordering rules): the reference
  runs Cascades exploration with stats costing; this binder uses a
  greedy heuristic — start from the largest (fact) relation and
  repeatedly attach the connected relation whose join KEEPS the smallest
  share of the tree's rows, by estimate (sql/plan.keep_share, the share
  that sizes a Shrink: a join here costs its lanes, which are static, so
  a dimension that filters nothing costs the same wherever it sits
  unless a Shrink below it has cut the lanes; Q9's filtered part goes
  under supplier, partsupp and orders), and among equal shares the
  smallest, letting each dimension first absorb its own satellites (so
  customer joins orders before orders joins lineitem, Q3's shape) and
  counting their filters for it. Stage `sql.join_rank`: one event a
  tree that had a choice, `rows` = the steps the share decided against
  the size.
- semi-join conversion (norm rules ConvertSemiToInnerJoin reversed):
  an inner join whose right side contributes no downstream columns and is
  unique on its join keys is executed as `semi` — the shape every
  hand-written TPC-H plan here used.
- IN (subquery) -> semi join, NOT IN -> anti join (decorrelation's
  trivial case).
- correlated [NOT] EXISTS (subquery) as a top-level WHERE conjunct ->
  plan.Apply over the join tree (`_bind_exists`; plan.decorrelate turns
  it into a semi / anti join, the first pass of normalize()): the
  subquery binds in a sub-binder whose `outer` is this one, so a name it
  cannot resolve itself resolves one level up; its `inner = outer`
  conjuncts are the Apply's `correlation`, ONE other comparison of an
  inner column with an outer one (`l2.l_suppkey <> l1.l_suppkey`, TPC-H
  Q21) its `residual`. A correlated subquery anywhere else (under OR, in
  a projection, IN), an uncorrelated EXISTS, a second residual, or any
  other correlated predicate is a BindError.
- alias scoping: a FROM item whose TABLE is named twice in its FROM list
  (a self-join) or also in the enclosing query's (a correlated subquery
  over the same table) has its columns named `<alias>.<column>` in every
  plan node (`_resolve_from`; a renaming Project over its Scan), so that the
  aliases stay apart below the binder; every other item keeps the
  table's own names, and a statement without such an item binds to the
  plan it always did. A bare name that two items could mean is a
  BindError when it is used.

Literal typing: SQL numeric literals are untyped; the binder retypes
them against the other operand (DECIMAL(s) columns make `0.05` a
scale-s scaled integer — ops/expr.py evaluates `Lit(v, DECIMAL(s))` as
`round(v*10^s)`), and DATE +- INTERVAL folds at bind time so the device
only ever sees int day comparisons.

Parameters: a constant subexpression over `$n` (`$1`, `$1 + interval '1'
year`, `$2 - 0.01`) takes its type where a literal would, from the operand
beside it in a comparison, BETWEEN or + - * arithmetic, and becomes a
`Param` naming a slot of `Binder.param_slots` (sql/params.py evaluates the
slots per Bind). `col LIKE $n` over a dictionary-coded column makes a
slot whose bound value is a table over the dictionary (`_pattern_slot`).
One that finds no typed operand (a projection, `$1 = $2`, IN, string
ordering) raises ParamOutOfScope: the session then binds that statement
as text. The values the binder is given are the binding its estimates are
taken at; no value enters the plan's programs.

Derived tables: `FROM (SELECT ...) AS alias` as the statement's only FROM
item is merged into the statement that reads it before anything is bound
(`_merge_derived`: the specification's text of TPC-H Q9), so it binds to
the plan of the flattened text and a `$n` inside it is a slot of the
statement's one list.
"""

from __future__ import annotations

import dataclasses
import datetime
from dataclasses import dataclass, field as dc_field
from decimal import Decimal
from typing import Dict, List, Optional, Sequence, Set, Tuple

from cockroach_tpu.coldata.batch import (
    DATE, DECIMAL, FLOAT, Field, INT, Kind, Schema,
)
from cockroach_tpu.ops.agg import AggSpec
from cockroach_tpu.ops.expr import (
    BinOp, BoolOp, Case, Cast, Cmp, Col, Expr, Extract, InList, IsNull,
    Like, Lit, Not, Param, VecDistance, VecLit,
)
from cockroach_tpu.ops.sort import SortKey
from cockroach_tpu.sql import parser as P
from cockroach_tpu.sql.params import (
    ParamOutOfScope, ParamSlot, date_add, is_param_const, sample_of,
)
from cockroach_tpu.sql.plan import (
    Aggregate, Apply, Catalog, Distinct, Filter, Join, Limit, OrderBy, Plan,
    Project, Scan, VectorTopK, _plan_columns, _rebuild, _walk_plan,
    keep_share,
)


class BindError(ValueError):
    pass


def _subst_cols(e: Expr, mapping: Dict[str, str]) -> Expr:
    """Structurally rewrite Col(name) references per `mapping`."""
    import dataclasses

    if isinstance(e, Col):
        return Col(mapping[e.name]) if e.name in mapping else e
    if not dataclasses.is_dataclass(e):
        return e
    changes = {}
    for f in dataclasses.fields(e):
        v = getattr(e, f.name)
        if isinstance(v, Expr):
            nv = _subst_cols(v, mapping)
            if nv is not v:
                changes[f.name] = nv
        elif isinstance(v, tuple):
            nv = tuple(
                _subst_cols(item, mapping) if isinstance(item, Expr)
                else tuple(_subst_cols(s, mapping) if isinstance(s, Expr)
                           else s for s in item)
                if isinstance(item, tuple) else item
                for item in v)
            if nv != v:
                changes[f.name] = nv
    return dataclasses.replace(e, **changes) if changes else e


_AGG_FUNCS = {"sum", "avg", "min", "max", "count"}

_CAST_TYPES = {
    "int": INT, "integer": INT, "bigint": INT, "smallint": INT,
    "float": FLOAT, "double": FLOAT, "real": FLOAT, "date": DATE,
}


def _fold_dates(node: P.Node) -> P.Node:
    """Constant-fold DATE +- INTERVAL into a DateLit and arithmetic of two
    numeric literals into one, recursing through the whole AST (bind-time
    calendar and decimal arithmetic; the device never sees intervals)."""
    if isinstance(node, P.Binary):
        left = _fold_dates(node.left)
        right = _fold_dates(node.right)
        if (node.op in ("+", "-") and isinstance(left, P.DateLit)
                and isinstance(right, P.IntervalLit)):
            n = right.n if node.op == "+" else -right.n
            return P.DateLit(date_add(left.days, n, right.unit))
        if (node.op in ("+", "-", "*") and isinstance(left, P.Num)
                and isinstance(right, P.Num)):
            # literal arithmetic is exact, as a parameter's is
            # (sql/params._fold): `0.09 + 0.01` reaches a DECIMAL column
            # as 0.10, not as a float32 sum just under it
            a, b = Decimal(left.text), Decimal(right.text)
            return P.Num(format(a + b if node.op == "+" else
                                a - b if node.op == "-" else a * b, "f"))
        return P.Binary(node.op, left, right)
    if isinstance(node, P.Unary):
        return P.Unary(node.op, _fold_dates(node.arg))
    if isinstance(node, P.Between):
        return P.Between(_fold_dates(node.arg), _fold_dates(node.lo),
                         _fold_dates(node.hi), node.negate)
    if isinstance(node, P.InListAst):
        return P.InListAst(_fold_dates(node.arg),
                           [_fold_dates(v) for v in node.values],
                           node.negate)
    if isinstance(node, P.FuncCall):
        return P.FuncCall(node.name, [_fold_dates(a) for a in node.args],
                          node.star, node.distinct, node.params)
    if isinstance(node, P.CaseAst):
        return P.CaseAst(
            [(_fold_dates(c), _fold_dates(v)) for c, v in node.whens],
            _fold_dates(node.otherwise)
            if node.otherwise is not None else None)
    if isinstance(node, P.CastAst):
        return P.CastAst(_fold_dates(node.arg), node.to)
    if isinstance(node, P.ExtractAst):
        return P.ExtractAst(node.part, _fold_dates(node.arg))
    return node


@dataclass
class _Rel:
    """One relation in the FROM list (or an IN-subquery pseudo-relation)."""

    key: str                       # alias or table name (unique)
    table: Optional[str] = None    # base table name; None for subqueries
    subplan: Optional[Plan] = None
    filters: List[Expr] = dc_field(default_factory=list)
    est: float = float(1 << 20)
    forced_semi: Optional[str] = None  # "semi" | "anti" for IN-subqueries
    unique_cols: Optional[Tuple[str, ...]] = None  # pk / group-by cols


@dataclass
class _Edge:
    a: str
    b: str
    pairs: List[Tuple[str, str]]  # (a-side col, b-side col)


@dataclass(frozen=True, eq=False)
class _OpenParam(Expr):
    """A constant subexpression over `$n` on its way to the operand that
    types it (Binder._retype closes it into a Param)."""

    node: P.Node

    def type(self, schema):
        raise ParamOutOfScope(
            "a parameter stands where no operand beside it gives its type")


_PARAM_KINDS = (Kind.DATE, Kind.INT, Kind.DECIMAL, Kind.STRING, Kind.FLOAT)

# in a conjunct's `refs`: it names a column of the enclosing query
_OUTER = "<outer>"

# the comparisons a correlated subquery may make between one of its
# columns and one of the outer query's, and each read from the other side
_CORRELATED_OPS = {"=": "==", "<>": "!=", "!=": "!=", "<": "<", "<=": "<=",
                   ">": ">", ">=": ">="}
_MIRRORED = {"==": "==", "!=": "!=", "<": ">", "<=": ">=", ">": "<",
             ">=": "<="}
_RESIDUAL_KINDS = (Kind.INT, Kind.DATE, Kind.DECIMAL, Kind.FLOAT)


class Binder:
    def __init__(self, catalog: Catalog, params: Optional[Sequence] = None,
                 outer: Optional["Binder"] = None):
        self.catalog = catalog
        # the binder of the enclosing query, for a correlated subquery's:
        # names this one cannot resolve resolve there (one level up)
        self.outer = outer
        # plan names of THIS query's columns that its subqueries read
        self._sub_refs: Set[str] = set()
        # did any FROM item of the statement take alias-qualified names?
        self._renamed_any = False
        # the values of the binding this plan is made at (estimates read
        # them through Param.sample), or None: a `$n` is then unbound
        self.params = params
        self.param_slots: List[ParamSlot] = []
        self._open_params = 0
        self._having_aggs: Optional["_AggCollector"] = None
        # one entry a join tree whose orderer had a choice: the steps at
        # which the share a join keeps chose another relation than the
        # size would have (stage `sql.join_rank`; a subquery's binder
        # appends to the statement's list)
        self.join_ranks: List[int] = []

    # ---------------------------------------------------------------- bind

    def bind(self, stmt: P.SelectStmt) -> Plan:
        plan = self._bind_select(stmt)
        if self._open_params:
            raise ParamOutOfScope(
                "a parameter stands where no operand beside it gives its "
                "type")
        if self._renamed_any:
            plan = _share_scan_columns(plan)
        return plan

    def scope_one_table(self, table: str, schema: Schema) -> None:
        """The scope of a statement over ONE table under its own names
        (UPDATE and DELETE bind their expressions in it)."""
        self._schemas = {table: schema}
        self._col_to_rel = {n: table for n in schema.names()}
        self._global = schema
        self._renames = {}
        self._by_source = {n: [table] for n in schema.names()}
        self._alias_tables = {table: table}

    def _resolve_from(self, stmt: P.SelectStmt) -> Dict[str, _Rel]:
        """The FROM list -> its relations, and this query's scope: the
        schema of every item under its PLAN names (`<alias>.<column>` for
        an item whose table is named twice here, or in the enclosing
        query too; the table's own otherwise), which item a plan name
        belongs to, and which items a bare source name could mean."""
        tables = [tref.name for tref in stmt.tables]
        enclosing = (set(self.outer._alias_tables.values())
                     if self.outer is not None else set())
        rels: Dict[str, _Rel] = {}
        schemas: Dict[str, Schema] = {}
        col_to_rel: Dict[str, str] = {}
        self._renames: Dict[str, Dict[str, str]] = {}
        self._by_source: Dict[str, List[str]] = {}
        for tref in stmt.tables:
            key = tref.alias or tref.name
            if key in rels:
                raise BindError(f"duplicate table/alias {key!r} (each "
                                "FROM item of a self-join needs an alias "
                                "of its own)")
            schema = source = self.catalog.table_schema(tref.name)
            pk = self._pk(tref.name)
            if tables.count(tref.name) > 1 or tref.name in enclosing:
                ren = {n: f"{key}.{n}" for n in schema.names()}
                self._renames[key] = ren
                self._renamed_any = True
                schema = Schema(
                    [dataclasses.replace(f, name=ren[f.name])
                     for f in schema.fields], schema.dicts)
                pk = None if pk is None else tuple(ren[c] for c in pk)
            rels[key] = _Rel(key, table=tref.name,
                             est=float(self._rows(tref.name)),
                             unique_cols=pk)
            schemas[key] = schema
            for name in schema.names():
                if name in col_to_rel:
                    raise BindError(f"ambiguous column {name!r} "
                                    f"(in {col_to_rel[name]} and {key})")
                col_to_rel[name] = key
            for name in source.names():
                self._by_source.setdefault(name, []).append(key)
        self._schemas = schemas
        self._col_to_rel = col_to_rel
        # a correlated subquery types its outer references too; its own
        # names come last and win
        self._global = self._merge_schemas(
            ([self.outer._global] if self.outer is not None else [])
            + list(schemas.values()))
        self._alias_tables = {(tref.alias or tref.name): tref.name
                              for tref in stmt.tables}
        return rels

    def _bind_select(self, stmt: P.SelectStmt) -> Plan:
        stmt = _merge_derived(stmt)
        # -- resolve FROM tables ------------------------------------------
        rels = self._resolve_from(stmt)

        # -- outer joins: linear (syntactic) join order -------------------
        # LEFT/RIGHT/FULL OUTER joins are not freely reorderable; they
        # bind in FROM order with their ON equi-conditions, and the WHERE
        # applies wholesale ABOVE the joins (normalize()'s pushdown sinks
        # what is sound past NULL-extending sides).
        if any(t.how != "inner" for t in stmt.tables):
            plan = self._linear_join_tree(stmt)
            if stmt.where is not None:
                e, _refs = self._bind_scalar(_fold_dates(stmt.where))
                plan = Filter(plan, e)
            plan = self._select_and_aggregate(plan, stmt)
            if stmt.distinct:
                plan = self._exact_shape(plan)
                plan = Distinct(plan)
            plan = self._order_limit(plan, stmt)
            return self._exact_shape(plan)

        # -- WHERE decomposition ------------------------------------------
        edges: List[_Edge] = []
        post_filters: List[Expr] = []
        conjuncts = self._split_and(stmt.where) if stmt.where else []
        sub_n = 0
        applies: List[Apply] = []  # over the join tree, input filled in
        for ast in conjuncts:
            ast = _fold_dates(ast)
            exists = _as_exists(ast)
            if exists is not None:
                applies.append(self._bind_exists(*exists))
                continue
            if isinstance(ast, (P.InSubquery,)):
                arg, refs = self._bind_scalar(ast.arg)
                if not isinstance(arg, Col) or len(refs) != 1:
                    raise BindError("IN (subquery) needs a plain column "
                                    "on the left")
                sub = self._sub_binder().bind(ast.query)
                sub_cols = _plan_columns(sub, self.catalog)
                key = f"__sub{sub_n}"
                sub_n += 1
                rels[key] = _Rel(
                    key, subplan=sub, est=float(1 << 16),
                    forced_semi="anti" if ast.negate else "semi")
                edges.append(_Edge(next(iter(refs)), key,
                                   [(arg.name, sub_cols[0])]))
                continue
            self._place_conjunct(ast, rels, edges, post_filters)

        # -- select-item / aggregate analysis -----------------------------
        plan = self._join_tree(rels, edges, stmt, post_filters)
        # a correlated subquery filters the rows of the relation it names
        # and commutes with that relation's inner joins: above them all it
        # probes what the most selective of them left (a Shrink's lanes)
        for ap in applies:
            plan = dataclasses.replace(ap, input=plan)
        for f in post_filters:
            plan = Filter(plan, f)
        plan = self._select_and_aggregate(plan, stmt)
        if stmt.distinct:
            # DISTINCT dedups over the SELECT list ONLY: hidden
            # passthroughs must drop before dedup (SQL consequently
            # restricts ORDER BY to selected expressions here)
            plan = self._exact_shape(plan)
            plan = Distinct(plan)
        plan = self._order_limit(plan, stmt)
        # SQL defines the output shape EXACTLY: drop hidden columns
        # (scan passthroughs, ORDER BY-only refs, HAVING-only
        # aggregates) with a final projection above sort/limit
        return self._exact_shape(plan)

    def _place_conjunct(self, ast: P.Node, rels: Dict[str, _Rel],
                        edges: List[_Edge], post_filters: List[Expr]) -> bool:
        """A WHERE conjunct over this query's own FROM items goes where
        it binds: a join edge between two of them, a filter of the one it
        names, or a filter above the joins. -> False, and nothing placed,
        for a conjunct that names the enclosing query."""
        pair = self._as_join_pred(ast)
        if pair is not None:
            (ra, ca), (rb, cb) = pair
            if ra != rb:
                self._add_edge(edges, ra, rb, ca, cb)
                return True
        e, refs = self._bind_scalar(ast)
        if _OUTER in refs:
            return False
        if len(refs) == 1:
            rels[next(iter(refs))].filters.append(e)
        else:
            post_filters.append(e)
        return True

    def _exact_shape(self, plan: Plan) -> Plan:
        names = getattr(self, "_select_names", None)
        if names is not None and \
                names != _plan_columns(plan, self.catalog):
            plan = Project(plan, tuple((n, Col(n)) for n in names))
        return plan

    def _bind_vec_distance(self, op: str, left: Expr,
                           right: Expr) -> Expr:
        """`a <-> b` / `a <=> b` -> VecDistance. A string literal operand
        is coerced to a VecLit via the pgvector `'[1.0,2.0,...]'` text
        form (how prepared-statement query vectors arrive)."""
        from cockroach_tpu.ops.vector import parse_vector_literal

        def coerce(e: Expr) -> Expr:
            if isinstance(e, Lit) and isinstance(e.value, str):
                try:
                    return VecLit(parse_vector_literal(e.value))
                except ValueError as err:
                    raise BindError(f"bad vector literal: {err}")
            return e

        left, right = coerce(left), coerce(right)
        dims = []
        for e in (left, right):
            try:
                t = e.type(self._global)
            except (KeyError, ValueError):
                t = None
            if t is None or t.kind is not Kind.VECTOR:
                raise BindError(
                    f"operand of {op!r} must be a VECTOR column or a "
                    "'[...]' vector literal")
            dims.append(t.dim)
        if dims[0] != dims[1]:
            raise BindError(
                f"vector dimension mismatch: {dims[0]} vs {dims[1]}")
        return VecDistance("l2" if op == "<->" else "cos", left, right)

    # ----------------------------------------------------- expr binding --

    def _bind_scalar(self, node: P.Node) -> Tuple[Expr, Set[str]]:
        """AST -> IR expr (no aggregates allowed) + referenced rel keys."""
        refs: Set[str] = set()
        e = self._bx(_fold_dates(node), refs, allow_agg=False, aggs=None)
        return e, refs

    def _bx(self, node: P.Node, refs: Set[str], allow_agg: bool,
            aggs) -> Expr:
        if is_param_const(node):
            if self.params is None:
                raise BindError("the statement has parameters and no "
                                "values were bound")
            self._open_params += 1
            return _OpenParam(node)
        if isinstance(node, P.ColRef):
            return self._col(node, refs)
        if isinstance(node, P.Num):
            return Lit(node.value)
        if isinstance(node, P.Str):
            return Lit(node.value)
        if isinstance(node, P.DateLit):
            return Lit(node.days, INT)
        if isinstance(node, P.NullLit):
            return Lit(None, INT)
        if isinstance(node, P.BoolLit):
            return Lit(node.value)
        if isinstance(node, P.IntervalLit):
            raise BindError("INTERVAL only supported in date arithmetic")
        if isinstance(node, P.Unary):
            arg = self._bx(node.arg, refs, allow_agg, aggs)
            if node.op == "not":
                return Not(arg)
            if isinstance(arg, Lit) and isinstance(arg.value, (int, float)):
                return Lit(-arg.value, arg.ty)
            return BinOp("-", Lit(0), arg)
        if isinstance(node, P.Binary):
            if node.op in ("and", "or"):
                parts = tuple(self._bx(p, refs, allow_agg, aggs)
                              for p in self._flatten(node, node.op))
                return BoolOp(node.op, parts)
            left = self._bx(node.left, refs, allow_agg, aggs)
            right = self._bx(node.right, refs, allow_agg, aggs)
            if node.op == "||":
                from cockroach_tpu.ops.expr import StrFunc

                return StrFunc("concat", (left, right))
            if node.op in ("<->", "<=>"):
                return self._bind_vec_distance(node.op, left, right)
            left, right = self._retype(left, right)
            if node.op in ("+", "-", "*", "/"):
                if node.op == "/" and (isinstance(left, Param)
                                       or isinstance(right, Param)):
                    raise ParamOutOfScope("a parameter under division")
                return BinOp(node.op, left, right)
            op = {"=": "==", "<>": "!=", "!=": "!="}.get(node.op, node.op)
            if isinstance(left, Param) and left.ty.kind is Kind.STRING:
                # the code compare reads the column on the left
                left, right = right, left
                op = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}.get(op, op)
            if (isinstance(right, Param) and right.ty.kind is Kind.STRING
                    and op not in ("==", "!=")):
                raise ParamOutOfScope("a string parameter under an "
                                      "ordering comparison")
            return Cmp(op, left, right)
        if isinstance(node, P.Between):
            arg = self._bx(node.arg, refs, allow_agg, aggs)
            lo = self._bx(node.lo, refs, allow_agg, aggs)
            hi = self._bx(node.hi, refs, allow_agg, aggs)
            a1, lo = self._retype(arg, lo)
            a2, hi = self._retype(arg, hi)
            e = BoolOp("and", (Cmp(">=", a1, lo), Cmp("<=", a2, hi)))
            return Not(e) if node.negate else e
        if isinstance(node, P.InListAst):
            arg = self._bx(node.arg, refs, allow_agg, aggs)
            values = []
            for v in node.values:
                bound = self._bx(v, refs, allow_agg, aggs)
                if not isinstance(bound, Lit):
                    raise BindError("IN list items must be literals")
                _, bound = self._retype(arg, bound)
                values.append(bound.value)
            e = InList(arg, tuple(values))
            return Not(e) if node.negate else e
        if isinstance(node, P.LikeAst):
            arg = self._bx(node.arg, refs, allow_agg, aggs)
            pattern = node.pattern
            if isinstance(pattern, P.Placeholder):
                pattern = self._pattern_slot(pattern, arg)
            return Like(arg, pattern, node.negate)
        if isinstance(node, P.IsNullAst):
            arg = self._bx(node.arg, refs, allow_agg, aggs)
            return IsNull(arg, node.negate)
        if isinstance(node, P.CaseAst):
            whens = tuple(
                (self._bx(c, refs, allow_agg, aggs),
                 self._bx(v, refs, allow_agg, aggs))
                for c, v in node.whens)
            other = (self._bx(node.otherwise, refs, allow_agg, aggs)
                     if node.otherwise is not None else None)
            return Case(whens, other)
        if isinstance(node, P.CastAst):
            arg = self._bx(node.arg, refs, allow_agg, aggs)
            ty = node.to
            if ty.startswith(("decimal", "numeric")):
                scale = 0
                if "(" in ty:
                    parts = ty[ty.index("(") + 1:-1].split(",")
                    scale = int(parts[1]) if len(parts) > 1 else 0
                return Cast(arg, DECIMAL(scale))
            if ty not in _CAST_TYPES:
                raise BindError(f"unsupported cast type {ty!r}")
            return Cast(arg, _CAST_TYPES[ty])
        if isinstance(node, P.ExtractAst):
            if node.part not in ("year", "month", "day"):
                raise BindError(f"unsupported extract part {node.part!r}")
            return Extract(node.part,
                           self._bx(node.arg, refs, allow_agg, aggs))
        if isinstance(node, P.FuncCall):
            if node.name in _AGG_FUNCS:
                if not allow_agg:
                    raise BindError(
                        f"aggregate {node.name}() not allowed here")
                return aggs.add(node, self, refs)
            if node.name in ("abs", "mod", "sign", "floor", "ceil",
                             "coalesce", "nullif", "greatest", "least",
                             "length"):
                from cockroach_tpu.ops.expr import Col as _Col, ScalarFunc

                args = [self._bx(a, refs, allow_agg, aggs)
                        for a in node.args]
                arity = {"abs": 1, "sign": 1, "floor": 1, "ceil": 1,
                         "length": 1, "mod": 2, "nullif": 2}
                want = arity.get(node.name)
                if want is not None and len(args) != want:
                    raise BindError(f"{node.name}() takes {want} "
                                    f"argument(s)")
                if node.name in ("coalesce", "greatest", "least") \
                        and len(args) < 1:
                    raise BindError(f"{node.name}() needs arguments")
                # literals take the first typed argument's type
                if len(args) > 1:
                    for i in range(1, len(args)):
                        args[0], args[i] = self._retype(args[0], args[i])
                table = None
                if node.name == "length":
                    a0 = args[0]
                    if not (isinstance(a0, _Col)
                            and a0.type(self._global).kind
                            is Kind.STRING):
                        raise BindError(
                            "length() takes a STRING column")
                    d = self._global.dictionary(a0.name)
                    if d is None:
                        raise BindError(
                            f"column {a0.name!r} has no dictionary")
                    table = tuple(len(str(s)) for s in d)
                return ScalarFunc(node.name, tuple(args), table)
            if node.name in ("upper", "lower", "substring", "concat"):
                from cockroach_tpu.ops.expr import StrFunc

                args = tuple(self._bx(a, refs, allow_agg, aggs)
                             for a in node.args)
                for a in args:
                    if a.type(self._global).kind is not Kind.STRING:
                        raise BindError(
                            f"{node.name}() takes STRING arguments")
                return StrFunc(node.name, args, tuple(node.params))
            raise BindError(f"unknown function {node.name!r}")
        if isinstance(node, (P.InSubquery, P.ExistsAst)):
            raise BindError("subqueries are only supported as top-level "
                            "WHERE conjuncts (col [NOT] IN (SELECT ...), "
                            "[NOT] EXISTS (SELECT ...)): not under OR, "
                            "not in a projection, not under an outer join")
        raise BindError(f"cannot bind {type(node).__name__}")

    def _resolve(self, ref: P.ColRef) -> Optional[Tuple[str, str]]:
        """-> (the FROM item of THIS query that `ref` names, the column's
        plan name), or None: not a name of this scope. A bare name that
        two items could mean, and a qualified one its item lacks, raise."""
        if ref.qualifier is not None:
            key = ref.qualifier
            if key not in self._schemas:
                return None
            name = self._renames.get(key, {}).get(ref.name, ref.name)
            if name not in self._schemas[key].names():
                raise BindError(f"column {ref.name!r} not in {key!r}")
            return key, name
        keys = self._by_source.get(ref.name, ())
        if len(keys) > 1:
            raise BindError(f"ambiguous column {ref.name!r} (in "
                            f"{' and '.join(keys)}): qualify it")
        if not keys:
            return None
        return keys[0], self._renames.get(keys[0], {}).get(ref.name,
                                                           ref.name)

    def _col(self, ref: P.ColRef, refs: Set[str]) -> Col:
        hit = self._resolve(ref)
        if hit is not None:
            refs.add(hit[0])
            return Col(hit[1])
        up = self.outer._resolve(ref) if self.outer is not None else None
        if up is not None:
            # an outer reference: a column of the enclosing query's row
            refs.add(_OUTER)
            self.outer._sub_refs.update((up[1], ref.name))
            return Col(up[1])
        if ref.qualifier is not None:
            raise BindError(f"unknown table/alias {ref.qualifier!r}")
        raise BindError(f"unknown column {ref.name!r}")

    def _flatten(self, node: P.Binary, op: str) -> List[P.Node]:
        out: List[P.Node] = []
        for side in (node.left, node.right):
            if isinstance(side, P.Binary) and side.op == op:
                out.extend(self._flatten(side, op))
            else:
                out.append(side)
        return out

    def _retype(self, left: Expr, right: Expr) -> Tuple[Expr, Expr]:
        """Give untyped numeric literals the scale of the other operand
        (DECIMAL columns make `0.05` an exact scaled integer)."""

        def fix(lit: Expr, other: Expr) -> Expr:
            if isinstance(lit, _OpenParam):
                return self._close_param(lit, other)
            if not isinstance(lit, Lit):
                return lit
            try:
                ty = other.type(self._global)
            except (KeyError, ValueError):
                return lit
            # '1999-01-01' compared against a DATE column: parse as a
            # date (Postgres string-to-date coercion in comparisons)
            if (ty.kind is Kind.DATE and isinstance(lit.value, str)):
                import datetime as _dt

                try:
                    d = _dt.date.fromisoformat(lit.value)
                except ValueError:
                    raise BindError(
                        f"invalid date literal {lit.value!r}")
                return Lit((d - _dt.date(1970, 1, 1)).days, INT)
            if not (lit.ty is None
                    and isinstance(lit.value, (int, float))
                    and not isinstance(lit.value, bool)):
                return lit
            if ty.kind is Kind.DECIMAL:
                return Lit(float(lit.value), ty)
            return lit

        return fix(left, right), fix(right, left)

    def _close_param(self, open_: _OpenParam, other: Expr) -> Param:
        """Type a parameter expression from the operand beside it, as
        _retype types a literal: a new slot of the statement."""
        if isinstance(other, (_OpenParam, Lit)):
            raise ParamOutOfScope("a parameter compared with a constant")
        try:
            ty = other.type(self._global)
        except (KeyError, ValueError, TypeError):
            ty = self._agg_result_type(other)
        if ty is None:
            raise ParamOutOfScope("the operand beside a parameter has no "
                                  "type")
        column = other.name if isinstance(other, Col) else None
        if ty.kind not in _PARAM_KINDS or (ty.kind is Kind.STRING
                                           and column is None):
            raise ParamOutOfScope(f"a parameter of type {ty!r}")
        self._open_params -= 1
        slot = ParamSlot(len(self.param_slots), open_.node, ty,
                         column if ty.kind is Kind.STRING else None,
                         self._global)
        for seen in self.param_slots:
            # `$2` beside two date columns is one argument
            if (seen.ty, seen.column, seen.node) == (ty, slot.column,
                                                     slot.node):
                slot = seen
                break
        else:
            self.param_slots.append(slot)
        return Param(slot.index, ty, sample_of(slot, self.params))

    def _pattern_slot(self, node: P.Placeholder, arg: Expr) -> Param:
        """`col LIKE $n`: a slot whose bound value is the pattern's bool
        table over `col`'s dictionary, made once a Bind on the host
        (sql/params.evaluate) and passed to the program as an argument of
        its own. One dictionary-coded column only: the table is indexed
        by that column's codes."""
        if self.params is None:
            raise BindError("the statement has parameters and no values "
                            "were bound")
        if not (isinstance(arg, Col)
                and arg.type(self._global).kind is Kind.STRING
                and self._global.dictionary(arg.name) is not None):
            raise ParamOutOfScope("a LIKE pattern parameter over anything "
                                  "but one dictionary-coded column")
        tables = [s for s in self.param_slots if s.table is not None]
        for seen in tables:
            if (seen.column, seen.node) == (arg.name, node):
                slot = seen
                break
        else:
            rel = self._col_to_rel.get(arg.name)
            slot = ParamSlot(len(self.param_slots), node,
                             arg.type(self._global), arg.name,
                             self._global, table=len(tables),
                             relation=self._alias_tables.get(rel, rel))
            self.param_slots.append(slot)
        return Param(slot.index, slot.ty, sample_of(slot, self.params),
                     table=slot.table)

    def _agg_result_type(self, e: Expr):
        """The type of an aggregate's result that the HAVING being bound
        names first (`having sum(l_quantity) > $1`): the schema HAVING's
        literals are retyped against was made before HAVING's own
        aggregates were collected, so it does not hold the column yet."""
        if self._having_aggs is None or not isinstance(e, Col):
            return None
        out = self._having_aggs.output_schema(self._global)
        return out.field(e.name).type if e.name in out.names() else None

    def _split_and(self, node: P.Node) -> List[P.Node]:
        if isinstance(node, P.Binary) and node.op == "and":
            return self._split_and(node.left) + self._split_and(node.right)
        return [node]

    def _as_join_pred(self, ast: P.Node):
        """col_a = col_b across two relations -> ((rel_a, col_a),
        (rel_b, col_b)); None otherwise."""
        if not (isinstance(ast, P.Binary) and ast.op == "="):
            return None
        if not (isinstance(ast.left, P.ColRef)
                and isinstance(ast.right, P.ColRef)):
            return None
        ra: Set[str] = set()
        rb: Set[str] = set()
        a = self._col(ast.left, ra)
        b = self._col(ast.right, rb)
        if _OUTER in ra | rb:
            return None
        return (next(iter(ra)), a.name), (next(iter(rb)), b.name)

    @staticmethod
    def _add_edge(edges: List[_Edge], ra: str, rb: str, ca: str, cb: str):
        for e in edges:
            if {e.a, e.b} == {ra, rb}:
                if e.a == ra:
                    e.pairs.append((ca, cb))
                else:
                    e.pairs.append((cb, ca))
                return
        edges.append(_Edge(ra, rb, [(ca, cb)]))

    # -------------------------------------------- correlated subqueries --

    def _sub_binder(self, outer: Optional["Binder"] = None) -> "Binder":
        """The binder of a subquery of this statement. One statement, one
        list of slots: a `$n` inside the subquery is an argument of the
        same program, and its join orderer counts on the same page."""
        sub = Binder(self.catalog, params=self.params, outer=outer)
        sub.param_slots = self.param_slots
        sub.join_ranks = self.join_ranks
        return sub

    def _bind_exists(self, query: P.SelectStmt, negate: bool) -> Apply:
        """`[NOT] EXISTS (query)`, a top-level WHERE conjunct -> the Apply
        that filters this query's rows by it (its `input` is filled in
        once the join tree stands)."""
        sub_binder = self._sub_binder(outer=self)
        sub, correlation, residual = sub_binder._bind_correlated(query)
        self._renamed_any |= sub_binder._renamed_any
        self._open_params += sub_binder._open_params
        return Apply(None, sub, correlation,
                     "not_exists" if negate else "exists", None, residual)

    def _bind_correlated(self, stmt: P.SelectStmt):
        """An EXISTS subquery, in the sub-binder -> (its plan, projected
        to the columns the Apply reads; the correlation, (outer column,
        inner column) an `inner = outer` conjunct; the residual
        `Cmp(op, Col(inner), Col(outer))` or None). Its select list
        decides nothing. Conjuncts over its own FROM items alone bind as
        any query's; one that names the outer query is a comparison of
        ONE inner column with ONE outer column, an equality or at most
        one other."""
        stmt = _merge_derived(stmt)
        if (stmt.group_by or stmt.having is not None
                or stmt.limit is not None or stmt.offset
                or any(isinstance(n, P.WindowCall)
                       or (isinstance(n, P.FuncCall)
                           and n.name in _AGG_FUNCS)
                       for ast, _alias in stmt.items
                       for n in _ast_nodes(ast))):
            raise BindError("EXISTS over a subquery with GROUP BY, HAVING, "
                            "aggregates, window functions, LIMIT or OFFSET "
                            "is not supported")
        if any(t.how != "inner" for t in stmt.tables):
            raise BindError("an outer join inside an EXISTS subquery is "
                            "not supported")
        rels = self._resolve_from(stmt)
        edges: List[_Edge] = []
        post_filters: List[Expr] = []
        correlation: List[Tuple[str, str]] = []
        residual: Optional[Cmp] = None
        for ast in (self._split_and(stmt.where) if stmt.where else []):
            ast = _fold_dates(ast)
            if any(isinstance(n, (P.InSubquery, P.ExistsAst))
                   for n in _ast_nodes(ast)):
                raise BindError("a subquery nested in an EXISTS subquery "
                                "is not supported")
            if self._place_conjunct(ast, rels, edges, post_filters):
                continue
            cmp_ = self._correlated_cmp(ast)
            if cmp_.op == "==":
                correlation.append((cmp_.right.name, cmp_.left.name))
            elif residual is None:
                residual = cmp_
            else:
                raise BindError("a correlated subquery with more than one "
                                "comparison beside its equalities is not "
                                "supported")
        if not correlation:
            raise BindError("an EXISTS subquery needs an equality between "
                            "one of its columns and one of the outer "
                            "query's (an uncorrelated EXISTS, or one "
                            "correlated by an inequality alone, is not "
                            "supported)")
        if residual is not None:
            for side in (residual.left, residual.right):
                if side.type(self._global).kind not in _RESIDUAL_KINDS:
                    raise BindError(
                        f"a correlated {residual.op!r} comparison over "
                        f"{side.name!r}: only numbers and dates order as "
                        "their stored values do")
        reads = [inner for _outer, inner in correlation]
        if residual is not None:
            reads.append(residual.left.name)
        reads = list(dict.fromkeys(reads))
        self._sub_refs.update(reads)  # kept above this subquery's joins
        plan = self._join_tree(rels, edges, stmt, post_filters)
        for f in post_filters:
            plan = Filter(plan, f)
        if _plan_columns(plan, self.catalog) != reads:
            plan = Project(plan, tuple((n, Col(n)) for n in reads))
        return plan, tuple(correlation), residual

    def _correlated_cmp(self, ast: P.Node) -> Cmp:
        """A conjunct of a subquery that names the outer query ->
        `Cmp(op, Col(inner), Col(outer))`, the comparison read with the
        subquery's column on the left; anything else raises."""
        if (isinstance(ast, P.Binary) and ast.op in _CORRELATED_OPS
                and isinstance(ast.left, P.ColRef)
                and isinstance(ast.right, P.ColRef)):
            la: Set[str] = set()
            lb: Set[str] = set()
            a, b = self._col(ast.left, la), self._col(ast.right, lb)
            op = _CORRELATED_OPS[ast.op]
            if _OUTER in lb and _OUTER not in la:
                return Cmp(op, a, b)
            if _OUTER in la and _OUTER not in lb:
                return Cmp(_MIRRORED[op], b, a)
        raise BindError("a correlated predicate must compare ONE column "
                        "of the subquery with ONE column of the outer "
                        "query (=, <>, <, <=, >, >=) as a conjunct of the "
                        "subquery's WHERE")

    # ------------------------------------------------------- join tree --

    def _linear_join_tree(self, stmt: P.SelectStmt) -> Plan:
        """FROM-order join tree for queries with outer joins (the
        reference keeps outer joins in their syntactic association too,
        absent explicit reordering rules)."""
        from cockroach_tpu.sql.plan import Scan

        trefs = stmt.tables
        plan: Plan = Scan(trefs[0].name)
        joined = {trefs[0].alias or trefs[0].name}
        for tref in trefs[1:]:
            key = tref.alias or tref.name
            if tref.on is None:
                raise BindError("outer JOIN requires an ON condition")
            left_on: List[str] = []
            right_on: List[str] = []
            for c in self._split_and(tref.on):
                pair = self._as_join_pred(_fold_dates(c))
                if pair is None:
                    raise BindError("outer-join ON conditions must be "
                                    "column equalities")
                (ra, ca), (rb, cb) = pair
                if ra in joined and rb == key:
                    left_on.append(ca)
                    right_on.append(cb)
                elif rb in joined and ra == key:
                    left_on.append(cb)
                    right_on.append(ca)
                else:
                    raise BindError(
                        f"ON condition must link {key!r} to an "
                        "already-joined table")
            plan = Join(plan, Scan(tref.name), tuple(left_on),
                        tuple(right_on), how=tref.how)
            joined.add(key)
        return plan

    def _join_tree(self, rels: Dict[str, _Rel], edges: List[_Edge],
                   stmt: P.SelectStmt, post_filters: List[Expr]) -> Plan:
        if len(rels) == 1:
            (rel,) = rels.values()
            return self._rel_plan(rel, stmt)

        # columns needed above the joins: select/group/having/order refs
        # + post-join filter refs
        needed: Set[str] = set()
        for ast, _alias in stmt.items:
            self._collect_cols(ast, needed)
        for ast in stmt.group_by:
            self._collect_cols(ast, needed)
        if stmt.having is not None:
            self._collect_cols(stmt.having, needed)
        for ast, _d in stmt.order_by:
            self._collect_cols(ast, needed)
        for e in post_filters:
            self._ir_cols(e, needed)
        needed |= self._sub_refs  # what the correlated subqueries read

        # cost-ranked estimates: ANALYZE stats give per-conjunct
        # selectivities (histograms + distinct counts, sql/stats.py);
        # without stats, the flat 0.2 filter discount stands in
        from cockroach_tpu.exec import stats as exec_stats
        from cockroach_tpu.sql.stats import estimate_rows

        est = {}
        share = {}  # of a probe's rows, what a join to `k` alone keeps
        for k, r in rels.items():
            stats = (self.catalog.table_stats(r.table)
                     if r.table else None)
            if stats is not None:
                est[k] = estimate_rows(stats, r.est, r.filters)
                base = float(stats.row_count)
            else:
                est[k] = r.est * (0.2 if r.filters else 1.0)
                base = r.est
            # 1.0 for every relation with no filter of its own, an
            # IN (subquery) among them (no filter list, no base table)
            share[k] = keep_share(est[k], base)
        fact = max((k for k in rels if rels[k].forced_semi is None),
                   key=lambda k: est[k])

        remaining = dict(rels)
        plan = self._rel_plan(remaining.pop(fact), stmt)
        joined = {fact}
        pending = list(edges)

        def reach(start, within: Set[str]) -> Set[str]:
            """`start` and what it reaches over pending edges inside
            `within`."""
            seen = set(start)
            todo = list(seen)
            while todo:
                at = todo.pop()
                for e in pending:
                    for mine, other in ((e.a, e.b), (e.b, e.a)):
                        if mine == at and other in within \
                                and other not in seen:
                            seen.add(other)
                            todo.append(other)
            return seen

        def rank(k: str, cands) -> Tuple[float, float]:
            """(the share of the tree's rows that attaching `k` keeps, by
            estimate; `k`'s size). A join costs its LANES here, and only
            a Shrink cuts lanes: the relation that removes most goes
            under the others, whatever its size, and among equal shares
            the smallest goes first. `k` brings its satellites (what
            reaches the tree only through it: nation behind supplier), so
            a filter on one of them counts for `k`."""
            left = set(remaining) - {k}
            satellites = reach([k], left) - reach(set(cands) - {k}, left)
            keeps = 1.0
            for sat in sorted(satellites):  # one product in every process
                keeps *= share[sat]
            return keeps, est[k]

        # one entry a step that had a choice: did the share decide it
        # against the size?
        choices: List[bool] = []

        def attach_to(plan: Plan, joined: Set[str]) -> Plan:
            while True:
                cands = {}
                for e in pending:
                    for mine, other in ((e.a, e.b), (e.b, e.a)):
                        if mine in joined and other in remaining:
                            cands.setdefault(other, []).append(e)
                if not cands:
                    return plan
                key = min(cands, key=lambda k: rank(k, cands))
                if len(cands) > 1:
                    choices.append(est[key] > min(est[k] for k in cands))
                rel = remaining.pop(key)
                # satellites: relations connected to `key` but not to the
                # current tree join into `key` first (Q3: customer->orders)
                sub = self._rel_plan(rel, stmt)
                sub_joined = {key}
                sub = attach_to(sub, sub_joined)
                joined_edges = [e for e in pending
                                if (e.a in joined and e.b in sub_joined)
                                or (e.b in joined and e.a in sub_joined)]
                for e in joined_edges:
                    pending.remove(e)
                left_on: List[str] = []
                right_on: List[str] = []
                for e in joined_edges:
                    for ca, cb in e.pairs:
                        if e.a in joined:
                            left_on.append(ca)
                            right_on.append(cb)
                        else:
                            left_on.append(cb)
                            right_on.append(ca)
                how = self._join_kind(rel, sub_joined, rels, right_on,
                                      needed, pending)
                plan = Join(plan, sub, tuple(left_on), tuple(right_on),
                            how=how)
                joined |= sub_joined
                # nested attach consumed edges internal to sub already

        # the inner attach for satellites uses the same pending list: edges
        # between two not-yet-joined relations are picked up when one side
        # becomes part of a subtree
        plan = attach_to(plan, joined)
        if remaining:
            raise BindError(
                f"cross join required for {sorted(remaining)} "
                "(no join predicate connects them)")
        if choices:
            exec_stats.add("sql.join_rank", rows=sum(choices))
            self.join_ranks.append(sum(choices))
        return plan

    def _join_kind(self, rel: _Rel, sub_joined: Set[str],
                   rels: Dict[str, _Rel], right_on: Sequence[str],
                   needed: Set[str], pending: List[_Edge]) -> str:
        if rel.forced_semi:
            return rel.forced_semi
        if len(sub_joined) > 1:
            return "inner"  # subtree outputs: be conservative
        # right side unused above and unique on its join keys -> semi
        right_cols = set(self._schemas[rel.key].names()
                         if rel.table else
                         _plan_columns(rel.subplan, self.catalog))
        still_needed = right_cols & needed
        for e in pending:
            for ca, cb in e.pairs:
                still_needed |= ({ca, cb} & right_cols)
        if still_needed:
            return "inner"
        if rel.unique_cols and set(rel.unique_cols) <= set(right_on):
            return "semi"
        return "inner"

    def _rel_plan(self, rel: _Rel, stmt: P.SelectStmt) -> Plan:
        if rel.subplan is not None:
            return rel.subplan
        # prune scan columns to those referenced anywhere in the query
        used: Set[str] = set()
        for ast, _alias in stmt.items:
            self._collect_cols(ast, used)
        for ast in stmt.group_by:
            self._collect_cols(ast, used)
        if stmt.where is not None:
            self._collect_cols(stmt.where, used)
        if stmt.having is not None:
            self._collect_cols(stmt.having, used)
        for ast, _d in stmt.order_by:
            self._collect_cols(ast, used)
        used |= self._sub_refs
        # the scan reads the table's own names; an item under
        # alias-qualified names renames them straight above it
        source = self.catalog.table_schema(rel.table)
        cols = tuple(n for n in source.names() if n in used)
        plan: Plan = Scan(rel.table, cols or None)
        ren = self._renames.get(rel.key)
        if ren is not None:
            plan = Project(plan, tuple((ren[n], Col(n))
                                       for n in cols or source.names()))
        for f in rel.filters:
            plan = Filter(plan, f)
        return plan

    def _collect_cols(self, ast: P.Node, out: Set[str]):
        if isinstance(ast, P.ColRef):
            out.add(ast.name)
            if ast.qualifier in self._renames:
                # ... and the plan name, where the item has another
                out.add(self._renames[ast.qualifier].get(ast.name,
                                                         ast.name))
            return
        if isinstance(ast, P.SelectStmt):
            return  # subquery scope is separate
        for v in getattr(ast, "__dict__", {}).values():
            if isinstance(v, P.Node):
                self._collect_cols(v, out)
            elif isinstance(v, (list, tuple)):
                for item in v:
                    if isinstance(item, P.Node):
                        self._collect_cols(item, out)
                    elif (isinstance(item, tuple) and item
                          and isinstance(item[0], P.Node)):
                        for sub in item:
                            if isinstance(sub, P.Node):
                                self._collect_cols(sub, out)

    def _ir_cols(self, e: Expr, out: Set[str]):
        if isinstance(e, Col):
            out.add(e.name)
        for v in getattr(e, "__dict__", {}).values():
            if isinstance(v, Expr):
                self._ir_cols(v, out)
            elif isinstance(v, (list, tuple)):
                for item in v:
                    if isinstance(item, Expr):
                        self._ir_cols(item, out)
                    elif isinstance(item, tuple):
                        for sub in item:
                            if isinstance(sub, Expr):
                                self._ir_cols(sub, out)

    # ------------------------------------------- select list / aggregate --

    def _select_and_aggregate(self, plan: Plan, stmt: P.SelectStmt) -> Plan:
        if any(self._has_window(ast) for ast, _ in stmt.items):
            return self._select_windows(plan, stmt)
        collector = _AggCollector(self)
        refs: Set[str] = set()

        items: List[Tuple[str, Expr]] = []  # (output name, post-agg expr)
        for idx, (ast, alias) in enumerate(stmt.items):
            ast = _fold_dates(ast)
            e = self._bx(ast, refs, allow_agg=True, aggs=collector)
            name = alias or self._default_name(ast, e, idx)
            items.append((name, e))

        # `sum(x) AS revenue` names the AggSpec output directly (before
        # HAVING binds, so structural dedup resolves to the final name).
        # First alias wins per spec; every item's expr is then rewritten,
        # so other references to the old output stay consistent.
        renames: Dict[str, str] = {}
        spec_outs = {a.out for a in collector.specs}
        for (ast, alias), (name, e) in zip(stmt.items, items):
            if (isinstance(ast, P.FuncCall) and ast.name in _AGG_FUNCS
                    and alias and isinstance(e, Col)
                    and alias != e.name
                    and e.name in spec_outs
                    and e.name not in renames
                    and alias not in self._col_to_rel
                    and alias not in spec_outs
                    and alias not in renames.values()):
                renames[e.name] = alias
        for old, new in renames.items():
            collector.rename(old, new)
        if renames:
            items = [(n, _subst_cols(e, renames)) for n, e in items]

        has_agg = bool(collector.specs) or bool(stmt.group_by)
        having_expr = None
        if stmt.having is not None:
            # make aggregate outputs typable for literal retyping
            self._global = self._merge_schemas(
                [self._global, collector.output_schema(self._global)])
            self._having_aggs = collector
            having_expr = self._bx(_fold_dates(stmt.having), refs,
                                   allow_agg=True, aggs=collector)
            self._having_aggs = None
            has_agg = True

        self._select_names = [n for n, _ in items]
        self._select_items = list(items)
        if not has_agg:
            # plain projection; skip when it is an identity rename (the
            # final exact-shape projection in bind() drops any extra
            # passthrough columns after ORDER BY resolves)
            if all(isinstance(e, Col) and e.name == n for n, e in items):
                return plan
            return Project(plan, tuple((n, e) for n, e in items))

        # group keys: bind each GROUP BY entry; entries may be column
        # names, select aliases, or expressions matching a select item
        alias_map = {alias: i for i, (_, alias) in enumerate(stmt.items)
                     if alias}
        keys: List[Tuple[str, Expr]] = []
        for g_ast in stmt.group_by:
            g_ast = _fold_dates(g_ast)
            if isinstance(g_ast, P.ColRef) and g_ast.qualifier is None \
                    and g_ast.name in alias_map \
                    and g_ast.name not in self._col_to_rel:
                i = alias_map[g_ast.name]
                keys.append((g_ast.name, items[i][1]))
                continue
            ge = self._bx(g_ast, refs, allow_agg=False, aggs=None)
            if isinstance(ge, Col):
                keys.append((ge.name, ge))
                continue
            # computed key: find the select item with the same structure
            name = None
            for n, e in items:
                if repr(e) == repr(ge):
                    name = n
                    break
            keys.append((name or f"__g{len(keys)}", ge))

        key_names = [n for n, _ in keys]

        # select items that ARE group keys read the key's output column
        # (select n_name as nation ... group by nation)
        key_by_repr = {repr(e): n for n, e in keys}
        items = [(n, Col(key_by_repr[repr(e)])
                  if repr(e) in key_by_repr else e)
                 for n, e in items]

        # pre-aggregation projection: group keys + aggregate inputs
        pre_outputs: List[Tuple[str, Expr]] = []
        seen = set()
        for n, e in keys:
            if n not in seen:
                pre_outputs.append((n, e))
                seen.add(n)
        for n, e in collector.inputs:
            if n not in seen:
                pre_outputs.append((n, e))
                seen.add(n)
        if not all(isinstance(e, Col) and e.name == n
                   for n, e in pre_outputs):
            plan = Project(plan, tuple(pre_outputs))
        elif set(n for n, _ in pre_outputs) != set(
                _plan_columns(plan, self.catalog)):
            plan = Project(plan, tuple(pre_outputs))

        if collector.distinct_cols:
            dset = sorted(set(collector.distinct_cols))
            if len(dset) > 1:
                raise BindError("only one COUNT(DISTINCT col) column "
                                "per query is supported")
            if any(a.out not in collector.distinct_outs
                   for a in collector.specs):
                raise BindError("mixing COUNT(DISTINCT) with plain "
                                "aggregates is not supported")
            # dedup (group keys, col) rows before the aggregate; the
            # count spec then counts exactly the distinct values
            dkeys = tuple(key_names) + (
                () if dset[0] in key_names else (dset[0],))
            plan = Distinct(plan, dkeys)

        plan = Aggregate(plan, tuple(key_names), tuple(collector.specs))

        if having_expr is not None:
            plan = Filter(plan, having_expr)
        self._last_collector = collector  # ORDER BY agg-expr resolution

        # post-aggregation projection only when a select item computes
        # over aggregate outputs or renames one (identity projections are
        # skipped: the aggregate's outputs already carry the right names,
        # and extra hidden columns — HAVING-only aggregates — are
        # harmless, matching the hand-written plans)
        out_names = set(key_names) | {a.out for a in collector.specs}
        identity = all(isinstance(e, Col) and e.name == n
                       and n in out_names for n, e in items)
        if not identity:
            exprs = list(items)
            # keep hidden outputs that ORDER BY still references
            have = {n for n, _ in exprs}
            for ast, _d in stmt.order_by:
                bound = self._try_bind_order_ref(ast, collector, items,
                                                 out_names)
                if bound is not None and bound not in have:
                    exprs.append((bound, Col(bound)))
                    have.add(bound)
            plan = Project(plan, tuple(exprs))
        return plan

    # ------------------------------------------------------- windows --

    def _has_window(self, ast: P.Node) -> bool:
        if isinstance(ast, P.WindowCall):
            return True
        for v in getattr(ast, "__dict__", {}).values():
            if isinstance(v, P.Node) and self._has_window(v):
                return True
            if isinstance(v, (list, tuple)):
                for item in v:
                    if isinstance(item, P.Node) and self._has_window(item):
                        return True
        return False

    def _select_windows(self, plan: Plan, stmt: P.SelectStmt) -> Plan:
        """Select list containing window functions: one Window plan node
        per distinct OVER clause, then a final projection. Windows over
        GROUP BY output are not supported yet."""
        from cockroach_tpu.ops.window import WINDOW_FUNCS, WindowSpec
        from cockroach_tpu.sql.plan import Window

        if stmt.group_by or stmt.having is not None:
            raise BindError("window functions over GROUP BY are not "
                            "supported yet")
        groups: Dict[str, Tuple[Tuple[str, ...], Tuple[SortKey, ...],
                                List[WindowSpec]]] = {}
        items: List[Tuple[str, Expr]] = []
        n_win = 0
        for idx, (ast, alias) in enumerate(stmt.items):
            ast = _fold_dates(ast)
            if not isinstance(ast, P.WindowCall):
                refs: Set[str] = set()
                e = self._bx(ast, refs, allow_agg=False, aggs=None)
                items.append((alias or self._default_name(ast, e, idx), e))
                continue
            call = ast.call
            if call.name not in WINDOW_FUNCS:
                raise BindError(f"unknown window function {call.name!r}")
            if call.distinct:
                raise BindError("DISTINCT window aggregates not supported")
            part_cols = []
            for p_ast in ast.partition_by:
                refs = set()
                pe = self._bx(p_ast, refs, allow_agg=False, aggs=None)
                if not isinstance(pe, Col):
                    raise BindError("PARTITION BY supports plain columns")
                part_cols.append(pe.name)
            order_keys = []
            for o_ast, desc in ast.order_by:
                refs = set()
                oe = self._bx(o_ast, refs, allow_agg=False, aggs=None)
                if not isinstance(oe, Col):
                    raise BindError("window ORDER BY supports plain "
                                    "columns")
                order_keys.append(SortKey(oe.name, descending=desc))
            col = None
            offset = 1
            if call.star:
                pass
            elif not call.args and call.name not in (
                    "row_number", "rank", "dense_rank", "count"):
                raise BindError(f"{call.name}() needs an argument")
            elif call.args:
                refs = set()
                arg = self._bx(call.args[0], refs, allow_agg=False,
                               aggs=None)
                if not isinstance(arg, Col):
                    raise BindError("window function arguments must be "
                                    "plain columns")
                col = arg.name
                if len(call.args) > 1:
                    off = self._bx(call.args[1], set(), False, None)
                    if not (isinstance(off, Lit)
                            and isinstance(off.value, int)):
                        raise BindError("lag/lead offset must be an "
                                        "integer literal")
                    offset = off.value
            out = alias or f"{call.name}_{n_win}"
            n_win += 1
            spec = WindowSpec(call.name, col, out, offset)
            gkey = repr((tuple(part_cols), tuple(order_keys)))
            groups.setdefault(
                gkey, (tuple(part_cols), tuple(order_keys), []))
            groups[gkey][2].append(spec)
            items.append((out, Col(out)))
        for part_cols, order_keys, specs in groups.values():
            plan = Window(plan, part_cols, order_keys, tuple(specs))
        self._select_names = [n for n, _ in items]
        out_cols = _plan_columns(plan, self.catalog)
        if [n for n, _ in items] != out_cols or not all(
                isinstance(e, Col) and e.name == n for n, e in items):
            plan = Project(plan, tuple(items))
        return plan

    def _default_name(self, ast: P.Node, e: Expr, idx: int) -> str:
        if isinstance(e, Col):
            # `n1.n_name` is sent back as `n_name`, as SQL names it
            return ast.name if isinstance(ast, P.ColRef) else e.name
        if isinstance(ast, P.FuncCall):
            return ast.name
        return f"col{idx}"

    def _try_bind_order_ref(self, ast: P.Node, collector, items,
                            out_names) -> Optional[str]:
        if isinstance(ast, P.ColRef) and ast.qualifier is None:
            if ast.name in out_names:
                return ast.name
        if isinstance(ast, P.FuncCall) and ast.name in _AGG_FUNCS:
            spec = collector.find(ast, self)
            if spec is not None:
                return spec.out
        return None

    # --------------------------------------------------- order by / limit

    def _order_limit(self, plan: Plan, stmt: P.SelectStmt) -> Plan:
        vec = self._vector_topk(plan, stmt)
        if vec is not None:
            return vec
        if stmt.order_by:
            out_cols = _plan_columns(plan, self.catalog)
            sort_keys = []
            for ast, desc in stmt.order_by:
                name = self._order_name(ast, out_cols, stmt)
                sort_keys.append(SortKey(name, descending=desc))
            plan = OrderBy(plan, tuple(sort_keys))
        if stmt.limit is not None:
            plan = Limit(plan, stmt.limit, stmt.offset)
        elif stmt.offset:
            # OFFSET without LIMIT: int32-rank-safe "unbounded" limit
            plan = Limit(plan, (1 << 31) - 1 - stmt.offset, stmt.offset)
        return plan

    def _vector_topk(self, plan: Plan,
                     stmt: P.SelectStmt) -> Optional[Plan]:
        """`ORDER BY emb <-> '[..]' LIMIT k` -> VectorTopK (the vector
        search node). Fires only for a single ascending distance ORDER BY
        with a plain LIMIT; the distance need not be in the select list
        (when it IS selected, the generic OrderBy-on-alias path already
        handles it and this intercept never sees a Binary)."""
        if (len(stmt.order_by) != 1 or stmt.limit is None or stmt.offset
                or stmt.distinct):
            return None
        ast, desc = stmt.order_by[0]
        if desc or not (isinstance(ast, P.Binary)
                        and ast.op in ("<->", "<=>")):
            return None
        e, _refs = self._bind_scalar(ast)
        left, right = e.left, e.right
        if isinstance(left, VecLit) and isinstance(right, Col):
            left, right = right, left
        if not (isinstance(left, Col) and isinstance(right, VecLit)):
            raise BindError("vector ORDER BY needs a VECTOR column on "
                            "one side and a literal on the other")
        out_cols = _plan_columns(plan, self.catalog)
        # the same distance selected as an item: order by that column
        # through the generic TopK path (same VecDistance evaluation,
        # so results are identical to the VectorTopK lowering)
        for n, ie in getattr(self, "_select_items", []):
            if repr(ie) == repr(e) and n in out_cols:
                return Limit(OrderBy(plan, (SortKey(n),)),
                             stmt.limit, 0)
        if left.name not in out_cols:
            raise BindError(
                f"vector ORDER BY column {left.name!r} is not available "
                "at the top of the plan (aggregated/projected away)")
        from cockroach_tpu.util.settings import (
            Settings, VECTOR_ANN, VECTOR_NPROBE,
        )

        st = Settings()
        # ANN only over a bare scan: residual filters/joins/projections
        # must see exact distances (the index ranks the WHOLE table)
        ann = bool(st.get(VECTOR_ANN)) and isinstance(plan, Scan)
        return VectorTopK(plan, left.name, right.values, e.metric,
                          int(stmt.limit), ann,
                          int(st.get(VECTOR_NPROBE)))

    def _order_name(self, ast: P.Node, out_cols: List[str],
                    stmt: P.SelectStmt) -> str:
        ast = _fold_dates(ast)
        if isinstance(ast, P.Num):
            i = int(ast.text) - 1
            if not 0 <= i < len(stmt.items):
                raise BindError(f"ORDER BY position {ast.text} out of range")
            item_ast, alias = stmt.items[i]
            if alias:
                return alias
            if isinstance(item_ast, P.ColRef):
                return item_ast.name
            raise BindError("ORDER BY position refers to an unnamed "
                            "expression; add an alias")
        if isinstance(ast, P.ColRef) and ast.qualifier is None:
            if ast.name in out_cols:
                return ast.name
            raise BindError(f"ORDER BY column {ast.name!r} is not in the "
                            f"output (have {out_cols})")
        if isinstance(ast, P.ColRef):
            # `n1.n_name`: the column under its plan name, or the select
            # item that sends it back under another
            hit = self._resolve(ast)
            if hit is not None:
                if hit[1] in out_cols:
                    return hit[1]
                for n, e in getattr(self, "_select_items", []):
                    if isinstance(e, Col) and e.name == hit[1] \
                            and n in out_cols:
                        return n
        if isinstance(ast, P.FuncCall) and ast.name in _AGG_FUNCS:
            # match the aggregate structurally against the collected specs
            collector = getattr(self, "_last_collector", None)
            if collector is not None:
                spec = collector.find(ast, self)
                if spec is not None and spec.out in out_cols:
                    return spec.out
        raise BindError("ORDER BY supports output columns, aliases, "
                        "positions, or aggregate expressions that appear "
                        "in the select list")

    # --------------------------------------------------------- catalog --

    def _rows(self, table: str) -> int:
        fn = getattr(self.catalog, "table_rows", None)
        if fn is not None:
            try:
                return int(fn(table))
            except (KeyError, NotImplementedError):
                pass
        return 1 << 20

    def _pk(self, table: str) -> Optional[Tuple[str, ...]]:
        fn = getattr(self.catalog, "table_pk", None)
        if fn is not None:
            try:
                return fn(table)
            except (KeyError, NotImplementedError):
                pass
        return None

    @staticmethod
    def _merge_schemas(schemas) -> Schema:
        fields: List[Field] = []
        dicts = {}
        for s in schemas:
            fields.extend(s.fields)
            dicts.update(s.dicts)
        return Schema(fields, dicts)


class _AggCollector:
    """Extracts aggregate calls from select/having expressions, returning
    Col refs to the aggregate's output; dedupes structurally."""

    def __init__(self, binder: Binder):
        self.binder = binder
        self.specs: List[AggSpec] = []
        self.inputs: List[Tuple[str, Expr]] = []  # pre-projection columns
        self._by_repr: Dict[str, AggSpec] = {}
        self.distinct_cols: List[str] = []  # COUNT(DISTINCT col) inputs
        self.distinct_outs: Set[str] = set()

    def add(self, call: P.FuncCall, binder: Binder,
            refs: Set[str]) -> Col:
        spec = self._make(call, binder, refs)
        return Col(spec.out)

    def find(self, call: P.FuncCall, binder: Binder) -> Optional[AggSpec]:
        key = self._key(call, binder)
        return self._by_repr.get(key) if key is not None else None

    def _key(self, call: P.FuncCall, binder: Binder) -> Optional[str]:
        try:
            refs: Set[str] = set()
            if call.star:
                return "count_star"
            arg = binder._bx(call.args[0], refs, allow_agg=False, aggs=None)
            d = "distinct " if call.distinct else ""
            return f"{call.name}({d}{arg!r})"
        except BindError:
            return None

    def _make(self, call: P.FuncCall, binder: Binder,
              refs: Set[str]) -> AggSpec:
        if call.distinct:
            # COUNT(DISTINCT col): plan-level rewrite — a Distinct node
            # (group keys + col) dedups BEFORE the aggregate, so a plain
            # count over the deduped stream IS the distinct count
            if call.name != "count" or call.star or len(call.args) != 1:
                raise BindError(
                    "DISTINCT aggregates: only COUNT(DISTINCT col) "
                    "is supported")
            arg = binder._bx(call.args[0], refs, allow_agg=False,
                             aggs=None)
            if not isinstance(arg, Col):
                raise BindError("COUNT(DISTINCT ...) needs a plain "
                                "column argument")
            key = f"count(distinct {arg!r})"
            if key in self._by_repr:
                return self._by_repr[key]
            if arg.name not in {n for n, _ in self.inputs}:
                self.inputs.append((arg.name, arg))
            self.distinct_cols.append(arg.name)
            spec = AggSpec("count", arg.name, self._fresh("count"))
            self.specs.append(spec)
            self._by_repr[key] = spec
            self.distinct_outs.add(spec.out)
            return spec
        if call.star:
            key = "count_star"
            if key in self._by_repr:
                return self._by_repr[key]
            spec = AggSpec("count_star", None, self._fresh("count"))
            self.specs.append(spec)
            self._by_repr[key] = spec
            return spec
        if len(call.args) != 1:
            raise BindError(f"{call.name}() takes one argument")
        arg = binder._bx(call.args[0], refs, allow_agg=False, aggs=None)
        key = f"{call.name}({arg!r})"
        if key in self._by_repr:
            return self._by_repr[key]
        if isinstance(arg, Col):
            in_name = arg.name
        else:
            in_name = self._fresh(f"__in{len(self.inputs)}")
        if in_name not in {n for n, _ in self.inputs}:
            self.inputs.append((in_name, arg))
        func = {"count": "count"}.get(call.name, call.name)
        spec = AggSpec(func, in_name, self._fresh(call.name))
        self.specs.append(spec)
        self._by_repr[key] = spec
        return spec

    def _fresh(self, base: str) -> str:
        names = {a.out for a in self.specs}
        if base not in names:
            return base
        i = 1
        while f"{base}_{i}" in names:
            i += 1
        return f"{base}_{i}"

    def rename(self, old: str, new: str) -> None:
        import dataclasses

        for i, spec in enumerate(self.specs):
            if spec.out == old:
                renamed = dataclasses.replace(spec, out=new)
                self.specs[i] = renamed
                for k, v in list(self._by_repr.items()):
                    if v is spec:
                        self._by_repr[k] = renamed
                if old in self.distinct_outs:
                    self.distinct_outs.discard(old)
                    self.distinct_outs.add(new)
                return

    def output_schema(self, global_schema: Schema) -> Schema:
        """Synthetic fields typing the aggregate outputs (for literal
        retyping in HAVING)."""
        fields = []
        for spec in self.specs:
            if spec.func in ("count", "count_star"):
                fields.append(Field(spec.out, INT))
                continue
            try:
                in_expr = next(e for n, e in self.inputs
                               if n == spec.col)
            except StopIteration:
                in_expr = Col(spec.col) if spec.col else None
            try:
                in_ty = (in_expr.type(global_schema)
                         if in_expr is not None else INT)
            except (KeyError, ValueError):
                continue
            fields.append(Field(
                spec.out, FLOAT if spec.func == "avg" else in_ty))
        return Schema(fields)


def _as_exists(ast: P.Node) -> Optional[Tuple[P.SelectStmt, bool]]:
    """`EXISTS (q)` -> (q, False), `NOT EXISTS (q)` -> (q, True), else
    None."""
    if isinstance(ast, P.ExistsAst):
        return ast.query, ast.negate
    if (isinstance(ast, P.Unary) and ast.op == "not"
            and isinstance(ast.arg, P.ExistsAst)):
        return ast.arg.query, not ast.arg.negate
    return None


def _share_scan_columns(plan: Plan) -> Plan:
    """A table that several aliases scan is read ONCE where it can be:
    a Scan straight under an alias's renaming Project, which names what
    it reads, takes the columns of the table's WIDEST scan in the plan
    if those include its own; plan.build() then makes value-equal Scans
    one operator over one device image (Q21: three aliases of lineitem,
    72 MB, not three images), and the program never unpacks a column
    nothing reads."""
    widest: Dict[str, Tuple[str, ...]] = {}
    for node in _walk_plan(plan):
        if isinstance(node, Scan) and node.columns:
            if len(node.columns) > len(widest.get(node.table, ())):
                widest[node.table] = node.columns

    def rec(node: Plan) -> Plan:
        if (isinstance(node, Project) and isinstance(node.input, Scan)
                and node.input.columns):
            wide = widest[node.input.table]
            if set(node.input.columns) < set(wide):
                return Project(Scan(node.input.table, wide), node.outputs)
            return node
        kids = tuple(rec(k) for k in node.inputs())
        return _rebuild(node, kids) if kids else node

    return rec(plan)


# ------------------------------------------------------- derived tables

def _merge_derived(stmt: P.SelectStmt) -> P.SelectStmt:
    """`SELECT ... FROM (SELECT <items> FROM <tables> WHERE ...) AS alias
    [WHERE ...] GROUP BY ... ORDER BY ...` -> the one SELECT it means: the
    inner FROM and WHERE, and every outer reference to an inner output
    column replaced by the expression that computes it. The derived table
    is the statement's only FROM item and is itself a plain
    select-project-join: no DISTINCT, GROUP BY, HAVING, aggregate, window,
    ORDER BY or LIMIT of its own (those need the sub-plan kept apart; a
    BindError here)."""
    if not any(t.subquery is not None for t in stmt.tables):
        return stmt
    if len(stmt.tables) != 1:
        raise BindError("a derived table joined to other FROM items is "
                        "not supported")
    tref = stmt.tables[0]
    inner = _merge_derived(tref.subquery)
    if (inner.distinct or inner.group_by or inner.having is not None
            or inner.order_by or inner.limit is not None or inner.offset):
        raise BindError("a derived table with DISTINCT, GROUP BY, HAVING, "
                        "ORDER BY or LIMIT is not supported")
    exported: Dict[str, P.Node] = {}
    for ast, alias in inner.items:
        name = alias or (ast.name if isinstance(ast, P.ColRef) else None)
        if name is None or name == "*" or name in exported:
            raise BindError("every column of a derived table needs one "
                            "name of its own")
        if any(isinstance(n, P.WindowCall)
               or (isinstance(n, P.FuncCall) and n.name in _AGG_FUNCS)
               for n in _ast_nodes(ast)):
            raise BindError("a derived table with aggregates or window "
                            "functions is not supported")
        exported[name] = ast

    def inline(node, keep=()):
        """`node` with the derived table's columns written out. A bare
        name in `keep` (an outer select alias, in GROUP BY or ORDER BY)
        stays: it names the outer item."""
        if isinstance(node, P.ColRef):
            if node.qualifier not in (None, tref.alias):
                raise BindError(f"unknown table/alias {node.qualifier!r}")
            if node.qualifier is None and node.name in keep:
                return node
            if node.name not in exported:
                raise BindError(f"column {node.name!r} is not a column of "
                                f"the derived table {tref.alias!r}")
            return exported[node.name]
        return _map_children(node, lambda n: inline(n, keep))

    items = []
    for ast, alias in stmt.items:
        if alias is None and isinstance(ast, P.ColRef):
            alias = ast.name    # the output keeps the inner column's name
        items.append((inline(ast), alias))
    named = {alias for _ast, alias in items if alias}
    where = inner.where
    if stmt.where is not None:
        where = P.Parser._conjoin(where, inline(stmt.where))
    return P.SelectStmt(
        items=items, distinct=stmt.distinct, tables=list(inner.tables),
        where=where,
        group_by=[inline(g, named) for g in stmt.group_by],
        having=None if stmt.having is None else inline(stmt.having),
        order_by=[(inline(o, named), desc) for o, desc in stmt.order_by],
        limit=stmt.limit, offset=stmt.offset)


def _map_children(node, fn):
    """A copy of the AST node with `fn` applied to every child node (in
    lists and in (node, ...) tuples too); a subquery keeps its own
    scope."""
    import dataclasses

    if isinstance(node, P.SelectStmt) or not dataclasses.is_dataclass(node):
        return node

    def walk(v):
        if isinstance(v, P.SelectStmt):
            return v
        if isinstance(v, P.Node):
            return fn(v)
        if isinstance(v, list):
            return [walk(x) for x in v]
        if isinstance(v, tuple):
            return tuple(walk(x) for x in v)
        return v

    return dataclasses.replace(node, **{
        f.name: walk(getattr(node, f.name))
        for f in dataclasses.fields(node)})


def _ast_nodes(node):
    """`node` and every AST node under it (a subquery keeps its own
    scope)."""
    import dataclasses

    yield node
    if isinstance(node, P.SelectStmt) or not dataclasses.is_dataclass(node):
        return
    pending = [getattr(node, f.name) for f in dataclasses.fields(node)]
    while pending:
        v = pending.pop()
        if isinstance(v, P.Node):
            yield from _ast_nodes(v)
        elif isinstance(v, (list, tuple)):
            pending.extend(v)


def plan_sql(sql: str, catalog: Catalog) -> Plan:
    """SQL text -> bound logical plan (parse + bind)."""
    ast = P.parse(sql)
    if isinstance(ast, P.ExplainStmt):
        raise BindError("EXPLAIN goes through sql.explain.execute()")
    return Binder(catalog).bind(ast)


def run_sql(sql: str, catalog: Catalog, capacity: int = 1 << 17,
            mesh=None):
    """SQL text -> executed result columns (the conn_executor analog:
    parse -> bind -> normalize -> build -> run)."""
    from cockroach_tpu.sql.plan import run

    return run(plan_sql(sql, catalog), catalog, capacity, mesh=mesh)


# --------------------------------------------------------- changefeed bind

_CHANGEFEED_OPTIONS = {
    "resolved",          # emit resolved-timestamp messages
    "sink",              # 'file:<dir>' or a memory-sink token
    "max_polls",         # finite feed: stop after N poll cycles
    "target_wall",       # finite feed: stop once frontier.wall >= this
    "poll_interval_ms",  # sleep between poll cycles
    "once",              # single poll then SUCCEEDED
    "run",               # run inline via adopt_and_run (default for
                         # finite feeds)
    "limit",             # EXPERIMENTAL CHANGEFEED: row budget
}


def bind_changefeed(ast, catalog):
    """Resolve CREATE/EXPERIMENTAL CHANGEFEED against the catalog: the
    target table must exist and every option must be known (the
    reference rejects unknown changefeed options at plan time too)."""
    desc = catalog.desc(ast.table)
    unknown = set(ast.options) - _CHANGEFEED_OPTIONS
    if unknown:
        raise BindError(
            f"unknown changefeed option(s): {', '.join(sorted(unknown))}")
    return desc, dict(ast.options)
