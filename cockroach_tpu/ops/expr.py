"""Scalar expression IR + JAX compiler — filter & projection kernels.

Reference: pkg/sql/colexec/colexecproj (binary/unary projection kernels,
55K+80K generated LoC), colexecsel (filter kernels, 62K LoC), and the
row engine's tree-walking evaluator (pkg/sql/sem/eval). One symbolic IR
here compiles to jnp expressions over a Batch; `jax.jit` does the
per-type monomorphization execgen did at build time.

Semantics follow SQL:
- three-valued logic: any NULL operand of arithmetic/comparison yields
  NULL; AND/OR are Kleene (NULL AND FALSE = FALSE, NULL OR TRUE = TRUE);
- a filter keeps rows whose predicate is TRUE (NULL drops);
- decimals are int64 scaled by 10^scale: +/- align scales, * adds scales,
  / produces float32 (exact decimal division is a planner rewrite);
- strings are dictionary codes; predicates against literals are resolved
  host-side through the schema's dictionary (equality -> code compare,
  LIKE -> boolean lookup table indexed by code);
- a bound parameter (`Param`) is an ARGUMENT of the program that
  evaluates it (an entry of the statement's one packed vector; a LIKE
  pattern's boolean table over its column's dictionary is an argument of
  its own beside the vector), never a constant of it: the program that
  takes the arguments opens `traced_params` around its trace, and outside
  one a `Param` refuses to evaluate (ParamOutsideProgram).

Dates are int32 days since epoch; EXTRACT uses the standard civil-calendar
integer algorithm so it stays on device.
"""

from __future__ import annotations

import contextlib
import re
import threading
import weakref
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from cockroach_tpu.coldata.batch import (
    Batch, ColType, Column, Kind, Schema, BOOL, INT, FLOAT, DATE, DECIMAL,
    STRING, TIMESTAMP,
)


class Expr:
    """Base class. Subclasses are frozen dataclasses => hashable, usable as
    static args to jit-compiled stage functions."""

    def type(self, schema: Schema) -> ColType:
        raise NotImplementedError

    # sugar
    def __add__(self, o): return BinOp("+", self, _lit(o))
    def __sub__(self, o): return BinOp("-", self, _lit(o))
    def __mul__(self, o): return BinOp("*", self, _lit(o))
    def __truediv__(self, o): return BinOp("/", self, _lit(o))
    def __rsub__(self, o): return BinOp("-", _lit(o), self)
    def __radd__(self, o): return BinOp("+", _lit(o), self)
    def __rmul__(self, o): return BinOp("*", _lit(o), self)
    def __eq__(self, o): return Cmp("==", self, _lit(o))  # type: ignore
    def __ne__(self, o): return Cmp("!=", self, _lit(o))  # type: ignore
    def __lt__(self, o): return Cmp("<", self, _lit(o))
    def __le__(self, o): return Cmp("<=", self, _lit(o))
    def __gt__(self, o): return Cmp(">", self, _lit(o))
    def __ge__(self, o): return Cmp(">=", self, _lit(o))
    def __and__(self, o): return BoolOp("and", (self, _lit(o)))
    def __or__(self, o): return BoolOp("or", (self, _lit(o)))
    def __invert__(self): return Not(self)
    # defining __eq__ would otherwise null out hashability; identity hash
    # keeps exprs usable as jit static args / dict keys
    __hash__ = object.__hash__


def _lit(v):
    return v if isinstance(v, Expr) else Lit(v)


@dataclass(frozen=True, eq=False)
class Col(Expr):
    name: str

    def type(self, schema):
        return schema.field(self.name).type


@dataclass(frozen=True, eq=False)
class Lit(Expr):
    value: object
    ty: Optional[ColType] = None

    def type(self, schema):
        if self.ty is not None:
            return self.ty
        v = self.value
        if isinstance(v, bool):
            return BOOL
        if isinstance(v, int):
            return INT
        if isinstance(v, float):
            return FLOAT
        if isinstance(v, str):
            return STRING
        raise TypeError(f"cannot type literal {v!r}")


@dataclass(frozen=True, eq=False)
class Param(Expr):
    """A value bound at execution (pgwire Bind, sql/params.py): slot
    `index` of the statement's bound arguments, already in the device's
    representation of `ty` (days, scaled integer, dictionary code).
    `sample` is the slot's value at the binding the plan was made at, in
    a Lit's units: the planner's estimates read it (sql/stats.py) and no
    program ever does. A LIKE pattern's slot (`Like.pattern`) has no
    scalar: `table` says which of the statement's table arguments holds
    its membership table over the column's dictionary, and `sample` is
    (dictionary entries that match, dictionary entries)."""

    index: int
    ty: ColType
    sample: object = None
    table: Optional[int] = None

    def type(self, schema):
        return self.ty


class ParamOutsideProgram(Exception):
    """A Param was evaluated by a program that does not take the bound
    values as arguments (the streaming operators' own jits): tracing on
    would bake this binding's value into a program that serves every
    binding. The session answers by binding the statement as text
    (sql/session.py)."""


_params = threading.local()


@contextlib.contextmanager
def _frame(slot: str, values):
    prev = getattr(_params, slot, None)
    setattr(_params, slot, values)
    try:
        yield
    finally:
        setattr(_params, slot, prev)


def bound_args(values):
    """The statement's bound arguments for the runs inside the block, as
    sql/params.evaluate makes them: a tuple of the int64 vector of (value,
    valid) pairs, two entries a slot, and after it one boolean table over
    a dictionary for each LIKE pattern among the slots; what a program
    that takes them (exec/fused.FusedRunner) is called with, after its
    scan images."""
    return _frame("args", values)


def current_args():
    return getattr(_params, "args", None)


def traced_params(values):
    """Inside the trace of a program whose arguments they are: the tuple
    (packed vector, pattern tables...) eval_expr reads a Param from."""
    return _frame("traced", values)


def _traced(expr: "Param"):
    traced = getattr(_params, "traced", None)
    if not traced:
        raise ParamOutsideProgram(
            f"parameter slot {expr.index} evaluated outside a program "
            "that takes the bound values as arguments")
    return traced


def has_params(e) -> bool:
    """Does the expression (or tuple of them) hold a Param?"""
    import dataclasses

    if isinstance(e, Param):
        return True
    if isinstance(e, (tuple, list)):
        return any(has_params(x) for x in e)
    if isinstance(e, Expr) and dataclasses.is_dataclass(e):
        return any(has_params(getattr(e, f.name))
                   for f in dataclasses.fields(e))
    return False


@dataclass(frozen=True, eq=False)
class BinOp(Expr):
    op: str  # + - * /
    left: Expr
    right: Expr

    def type(self, schema):
        lt, rt = self.left.type(schema), self.right.type(schema)
        if lt.kind is Kind.DECIMAL or rt.kind is Kind.DECIMAL:
            ls = lt.scale if lt.kind is Kind.DECIMAL else 0
            rs = rt.scale if rt.kind is Kind.DECIMAL else 0
            if self.op in ("+", "-"):
                return DECIMAL(max(ls, rs))
            if self.op == "*":
                return DECIMAL(ls + rs)
            return FLOAT  # division
        if lt.kind is Kind.FLOAT or rt.kind is Kind.FLOAT or self.op == "/":
            return FLOAT
        if lt.kind is Kind.DATE and rt.kind is Kind.INT:
            return DATE  # date +/- days
        return INT


@dataclass(frozen=True, eq=False)
class Cmp(Expr):
    op: str  # == != < <= > >=
    left: Expr
    right: Expr

    def type(self, schema):
        return BOOL


@dataclass(frozen=True, eq=False)
class BoolOp(Expr):
    op: str  # and / or
    args: Tuple[Expr, ...]

    def type(self, schema):
        return BOOL


@dataclass(frozen=True, eq=False)
class Not(Expr):
    arg: Expr

    def type(self, schema):
        return BOOL


@dataclass(frozen=True, eq=False)
class IsNull(Expr):
    arg: Expr
    negate: bool = False

    def type(self, schema):
        return BOOL


@dataclass(frozen=True, eq=False)
class Case(Expr):
    whens: Tuple[Tuple[Expr, Expr], ...]
    otherwise: Optional[Expr] = None

    def type(self, schema):
        return self.whens[0][1].type(schema)


@dataclass(frozen=True, eq=False)
class Cast(Expr):
    arg: Expr
    to: ColType

    def type(self, schema):
        return self.to


@dataclass(frozen=True, eq=False)
class InList(Expr):
    arg: Expr
    values: Tuple[object, ...]

    def type(self, schema):
        return BOOL


@dataclass(frozen=True, eq=False)
class Like(Expr):
    """SQL LIKE over a dictionary-encoded string column (%/_ wildcards).
    Resolved host-side: pattern -> bool table over the dictionary, a
    constant of the program for a literal pattern; for a bound one
    (`pattern` a Param with a `table`) sql/params.py evaluates the table
    once a Bind and the program takes it as an argument."""

    arg: Expr  # must be a STRING Col
    pattern: object  # str | Param
    negate: bool = False

    def type(self, schema):
        return BOOL


@dataclass(frozen=True, eq=False)
class Extract(Expr):
    part: str  # "year" | "month" | "day"
    arg: Expr

    def type(self, schema):
        return INT


@dataclass(frozen=True, eq=False)
class VecLit(Expr):
    """Constant query vector, e.g. the '[1.0,2.0,...]' literal of
    `embedding <-> '[...]'`. Stored as a hashable float tuple so the
    expression stays usable as a jit static arg."""

    values: Tuple[float, ...]

    def type(self, schema):
        return ColType(Kind.VECTOR, len(self.values))


@dataclass(frozen=True, eq=False)
class VecDistance(Expr):
    """`<->` (Euclidean) / `<=>` (cosine distance) between a VECTOR
    column and a query vector (VecLit or another VECTOR column).
    pgvector operator semantics: `<=>` is 1 - cosine similarity."""

    metric: str  # "l2" | "cos"
    left: Expr
    right: Expr

    def type(self, schema):
        return FLOAT


@dataclass(frozen=True, eq=False)
class ScalarFunc(Expr):
    """Device-evaluable scalar builtins (pkg/sql/sem/builtins subset):
    abs, mod, sign, floor, ceil, coalesce, nullif, greatest, least,
    length (string dictionary lookup, table resolved at bind time)."""

    func: str
    args: Tuple[Expr, ...]
    # length(): host-resolved per-code lengths of the column dictionary
    table: Optional[Tuple[int, ...]] = None

    def type(self, schema):
        if self.func == "length":
            return INT
        if self.func == "sign":
            return INT
        ts = [a.type(schema) for a in self.args]
        if self.func in ("floor", "ceil"):
            return INT
        for t in ts:  # first non-null-literal argument type
            if t is not None:
                return t
        return INT


@dataclass(frozen=True, eq=False)
class StrFunc(Expr):
    """Computed string expression: upper/lower/substring/concat.

    Evaluated by the ROW engine only (exec/rowexec.py) — the device
    representation is dictionary codes, and a computed string is a NEW
    string the output dictionary mints on the host (the planner routes
    any projection containing a StrFunc through RowMapOp, the same seam
    exact decimal division uses). Reference: pkg/sql/sem/builtins
    string builtins over datums."""

    func: str                 # "upper" | "lower" | "substring" | "concat"
    args: Tuple[Expr, ...]
    params: Tuple[int, ...] = ()  # substring (start, length), 1-based

    def type(self, schema):
        return STRING


# ---------------------------------------------------------------------------


def _rescale(values, from_scale: int, to_scale: int):
    if to_scale == from_scale:
        return values
    if to_scale > from_scale:
        return values * jnp.int64(10 ** (to_scale - from_scale))
    # round-half-away-from-zero when dropping digits; // floors toward
    # -inf, so negatives round on the magnitude and re-negate
    div = jnp.int64(10 ** (from_scale - to_scale))
    half = div // 2
    return jnp.where(values >= 0,
                     (values + half) // div,
                     -((-values + half) // div))


def _decimal_to_float(values, scale: int):
    return values.astype(jnp.float32) / jnp.float32(10 ** scale)


def _per_dictionary(cache: dict, d: np.ndarray, build):
    """`build(d)`, made once for a dictionary array and kept in `cache`
    (id(d) -> (weakref to it, what was built)) for as long as it lives: a
    served catalog's dictionaries are asked about at every Bind."""
    hit = cache.get(id(d))
    if hit is not None and hit[0]() is d:
        return hit[1]
    key = id(d)
    made = build(d)
    cache[key] = (weakref.ref(d, lambda _r: cache.pop(key, None)), made)
    return made


# {string: first code}: a string bound against a dictionary
# (sql/params.slot_value) is one dict probe, not a walk of the dictionary
_CODE_INDEX: Dict[int, Tuple[weakref.ref, Dict[str, int]]] = {}


def _code_index(d: np.ndarray) -> Dict[str, int]:
    # filled from the back, so a string that occurs twice keeps its
    # first code (what np.nonzero(d == s)[0][0] gave)
    return _per_dictionary(
        _CODE_INDEX, d,
        lambda d: dict(zip(d[::-1].tolist(), range(len(d) - 1, -1, -1))))


def _string_code(schema: Schema, col: str, s: str) -> int:
    """Host-side: literal string -> dictionary code (-1 if absent)."""
    d = schema.dictionary(col)
    if d is None:
        raise ValueError(f"column {col} has no dictionary")
    return _code_index(d).get(s, -1)


def _find_string_col(e: Expr) -> Optional[str]:
    return e.name if isinstance(e, Col) else None


def eval_expr(expr: Expr, batch: Batch, schema: Schema) -> Column:
    """Evaluate to a Column of batch.capacity lanes."""
    cap = batch.capacity

    if isinstance(expr, Col):
        return batch.col(expr.name)

    if isinstance(expr, Lit):
        ty = expr.type(schema)
        if expr.value is None:
            return Column(jnp.zeros((cap,), ty.dtype),
                          jnp.zeros((cap,), jnp.bool_))
        v = expr.value
        if ty.kind is Kind.DECIMAL and isinstance(v, (int, float)) \
                and not isinstance(v, bool):
            v = round(v * 10 ** ty.scale)
        if ty.kind is Kind.STRING:
            raise ValueError("string literals must appear inside Cmp/InList/Like")
        return Column(jnp.full((cap,), v, dtype=ty.dtype))

    if isinstance(expr, Param):
        # the statement's one int64 vector of (value, valid) pairs
        # (sql/params.evaluate); a float32 rides as its bit pattern
        packed = _traced(expr)[0]
        raw, valid = packed[2 * expr.index], packed[2 * expr.index + 1] != 0
        if expr.ty.kind is Kind.FLOAT:
            import jax as _jax

            value = _jax.lax.bitcast_convert_type(raw.astype(jnp.int32),
                                                  jnp.float32)
        else:
            value = raw.astype(expr.ty.dtype)
        return Column(jnp.full((cap,), value, dtype=expr.ty.dtype),
                      jnp.full((cap,), valid, dtype=jnp.bool_))

    if isinstance(expr, BinOp):
        lt, rt = expr.left.type(schema), expr.right.type(schema)
        lc = eval_expr(expr.left, batch, schema)
        rc = eval_expr(expr.right, batch, schema)
        validity = _combine_validity(lc, rc)
        out_ty = expr.type(schema)

        if out_ty.kind is Kind.DECIMAL:
            ls = lt.scale if lt.kind is Kind.DECIMAL else 0
            rs = rt.scale if rt.kind is Kind.DECIMAL else 0
            lv = lc.values.astype(jnp.int64)
            rv = rc.values.astype(jnp.int64)
            if expr.op in ("+", "-"):
                s = out_ty.scale
                lv, rv = _rescale(lv, ls, s), _rescale(rv, rs, s)
                vals = lv + rv if expr.op == "+" else lv - rv
            elif expr.op == "*":
                vals = lv * rv
            else:
                raise AssertionError(expr.op)
            return Column(vals, validity)

        if out_ty.kind is Kind.FLOAT:
            lv = _as_float(lc.values, lt)
            rv = _as_float(rc.values, rt)
            if expr.op == "/":
                validity = _and_validity(validity, rv != 0)
                vals = lv / jnp.where(rv == 0, jnp.float32(1), rv)
            else:
                vals = {"+": lv + rv, "-": lv - rv, "*": lv * rv}[expr.op]
            return Column(vals, validity)

        lv, rv = lc.values, rc.values
        if out_ty.kind is Kind.DATE:
            vals = {"+": lv + rv.astype(lv.dtype),
                    "-": lv - rv.astype(lv.dtype)}[expr.op]
            return Column(vals, validity)
        lv = lv.astype(jnp.int64)
        rv = rv.astype(jnp.int64)
        vals = {"+": lv + rv, "-": lv - rv, "*": lv * rv}[expr.op]
        return Column(vals, validity)

    if isinstance(expr, Cmp):
        lt, rt = expr.left.type(schema), expr.right.type(schema)
        if lt.kind is Kind.STRING and isinstance(expr.right, Param):
            # a bound string arrives as its dictionary code (looked up on
            # the host at bind time; -1: absent, equal to no row)
            lc = eval_expr(expr.left, batch, schema)
            rc = eval_expr(expr.right, batch, schema)
            vals = lc.values == rc.values
            return Column(~vals if expr.op == "!=" else vals,
                          _combine_validity(lc, rc))
        # string vs literal: compare dictionary codes
        if lt.kind is Kind.STRING and isinstance(expr.right, Lit):
            col = _find_string_col(expr.left)
            code = _string_code(schema, col, expr.right.value)
            lc = eval_expr(expr.left, batch, schema)
            if expr.op in ("==", "!="):
                vals = lc.values == jnp.int32(code)
                if expr.op == "!=":
                    vals = ~vals
                return Column(vals, lc.validity)
            # ordering comparison against a literal: build host-side table
            d = schema.dictionary(col)
            table = _cmp_table(d, expr.op, expr.right.value)
            return Column(table[jnp.clip(lc.values, 0, len(d) - 1)], lc.validity)
        lc = eval_expr(expr.left, batch, schema)
        rc = eval_expr(expr.right, batch, schema)
        validity = _combine_validity(lc, rc)
        if lt.kind is Kind.STRING and rt.kind is Kind.STRING:
            lname, rname = _find_string_col(expr.left), _find_string_col(expr.right)
            lref = schema.field(lname).dict_ref if lname else None
            rref = schema.field(rname).dict_ref if rname else None
            if lref != rref or lref is None:
                raise NotImplementedError(
                    "comparing string columns with different dictionaries; "
                    "re-encode to a shared dictionary first")
            if expr.op in ("==", "!="):
                lv, rv = lc.values, rc.values
            else:
                # codes are in first-occurrence order, not lexicographic:
                # map through a host-built rank table
                d = schema.dictionary(lname)
                rank = jnp.asarray(np.argsort(np.argsort(d.astype(str))))
                lv = rank[jnp.clip(lc.values, 0, len(d) - 1)]
                rv = rank[jnp.clip(rc.values, 0, len(d) - 1)]
            vals = {
                "==": lv == rv, "!=": lv != rv, "<": lv < rv,
                "<=": lv <= rv, ">": lv > rv, ">=": lv >= rv,
            }[expr.op]
            return Column(vals, validity)
        lv, rv = _numeric_align(lc.values, lt, rc.values, rt)
        vals = {
            "==": lv == rv, "!=": lv != rv, "<": lv < rv,
            "<=": lv <= rv, ">": lv > rv, ">=": lv >= rv,
        }[expr.op]
        return Column(vals, validity)

    if isinstance(expr, BoolOp):
        cols = [eval_expr(a, batch, schema) for a in expr.args]
        # Kleene: track (value, known)
        if expr.op == "and":
            val = jnp.ones((cap,), jnp.bool_)
            known_false = jnp.zeros((cap,), jnp.bool_)
            any_null = jnp.zeros((cap,), jnp.bool_)
            for c in cols:
                v = c.values
                nv = jnp.zeros((cap,), jnp.bool_) if c.validity is None else ~c.validity
                known_false |= (~v & ~nv)
                any_null |= nv
                val &= jnp.where(nv, True, v)
            validity = known_false | ~any_null
            return Column(val & ~known_false, validity)
        else:
            known_true = jnp.zeros((cap,), jnp.bool_)
            any_null = jnp.zeros((cap,), jnp.bool_)
            val = jnp.zeros((cap,), jnp.bool_)
            for c in cols:
                v = c.values
                nv = jnp.zeros((cap,), jnp.bool_) if c.validity is None else ~c.validity
                known_true |= (v & ~nv)
                any_null |= nv
                val |= jnp.where(nv, False, v)
            validity = known_true | ~any_null
            return Column(val | known_true, validity)

    if isinstance(expr, Not):
        c = eval_expr(expr.arg, batch, schema)
        return Column(~c.values, c.validity)

    if isinstance(expr, IsNull):
        c = eval_expr(expr.arg, batch, schema)
        isnull = (jnp.zeros((cap,), jnp.bool_) if c.validity is None
                  else ~c.validity)
        return Column(~isnull if expr.negate else isnull)

    if isinstance(expr, ScalarFunc):
        f = expr.func
        cs = [eval_expr(a, batch, schema) for a in expr.args]
        if f == "length":
            tbl = jnp.asarray(expr.table, jnp.int64)
            c = cs[0]
            code = jnp.clip(c.values.astype(jnp.int32), 0,
                            len(expr.table) - 1)
            return Column(tbl[code], c.validity)
        if f == "coalesce":
            vals = cs[0].values
            valid = cs[0].valid_mask()
            for c in cs[1:]:
                vals = jnp.where(valid, vals,
                                 c.values.astype(vals.dtype))
                valid = valid | c.valid_mask()
            return Column(vals, valid)
        if f == "nullif":
            a, b = cs
            eq = ((a.values == b.values.astype(a.values.dtype))
                  & a.valid_mask() & b.valid_mask())
            return Column(a.values, a.valid_mask() & ~eq)
        if f == "abs":
            c = cs[0]
            return Column(jnp.abs(c.values), c.validity)
        if f == "sign":
            c = cs[0]
            return Column(jnp.sign(c.values).astype(jnp.int64),
                          c.validity)
        if f == "mod":
            a, b = cs
            bv = b.values.astype(a.values.dtype)
            validity = _combine_validity(a, b)
            validity = _and_validity(validity, bv != 0)  # mod 0 -> NULL
            safe = jnp.where(bv == 0, jnp.ones((), bv.dtype), bv)
            import jax as _jax

            return Column(_jax.lax.rem(a.values, safe), validity)
        if f in ("greatest", "least"):
            op = jnp.maximum if f == "greatest" else jnp.minimum
            vals = cs[0].values
            valid = cs[0].valid_mask()
            for c in cs[1:]:
                other = c.values.astype(vals.dtype)
                both = valid & c.valid_mask()
                vals = jnp.where(both, op(vals, other),
                                 jnp.where(c.valid_mask() & ~valid,
                                           other, vals))
                valid = valid | c.valid_mask()
            return Column(vals, valid)  # SQL: NULL args are skipped
        if f in ("floor", "ceil"):
            c = cs[0]
            ty = expr.args[0].type(schema)
            if ty is not None and ty.kind is Kind.DECIMAL:
                s = jnp.int64(10 ** ty.scale)
                v = c.values.astype(jnp.int64)
                q = (v // s) if f == "floor" else -((-v) // s)
                return Column(q, c.validity)
            if jnp.issubdtype(c.values.dtype, jnp.floating):
                fn = jnp.floor if f == "floor" else jnp.ceil
                return Column(fn(c.values).astype(jnp.int64),
                              c.validity)
            return Column(c.values.astype(jnp.int64), c.validity)
        raise ValueError(f"unknown scalar function {f!r}")

    if isinstance(expr, Case):
        out_ty = expr.type(schema)
        vals = None
        validity = None
        decided = jnp.zeros((cap,), jnp.bool_)
        for cond, res in expr.whens:
            cc = eval_expr(cond, batch, schema)
            hit = cc.values & cc.valid_mask() & ~decided
            rc = eval_expr(res, batch, schema)
            if vals is None:
                vals = jnp.where(hit, rc.values, jnp.zeros((), rc.values.dtype))
                validity = jnp.where(hit, rc.valid_mask(), False)
            else:
                vals = jnp.where(hit, rc.values.astype(vals.dtype), vals)
                validity = jnp.where(hit, rc.valid_mask(), validity)
            decided |= hit
        if expr.otherwise is not None:
            oc = eval_expr(expr.otherwise, batch, schema)
            vals = jnp.where(decided, vals, oc.values.astype(vals.dtype))
            validity = jnp.where(decided, validity, oc.valid_mask())
        # rows not decided and no ELSE => NULL
        return Column(vals, validity)

    if isinstance(expr, Cast):
        c = eval_expr(expr.arg, batch, schema)
        ft = expr.arg.type(schema)
        tt = expr.to
        v = c.values
        if ft.kind is Kind.DECIMAL and tt.kind is Kind.FLOAT:
            v = _decimal_to_float(v, ft.scale)
        elif ft.kind is Kind.DECIMAL and tt.kind is Kind.DECIMAL:
            v = _rescale(v, ft.scale, tt.scale)
        elif tt.kind is Kind.DECIMAL:
            v = v.astype(jnp.int64) * jnp.int64(10 ** tt.scale) if ft.kind is not Kind.FLOAT \
                else jnp.round(v * jnp.float32(10 ** tt.scale)).astype(jnp.int64)
        else:
            v = v.astype(tt.dtype)
        return Column(v, c.validity)

    if isinstance(expr, InList):
        ty = expr.arg.type(schema)
        c = eval_expr(expr.arg, batch, schema)
        if ty.kind is Kind.STRING:
            col = _find_string_col(expr.arg)
            codes = [_string_code(schema, col, s) for s in expr.values]
            hit = jnp.zeros((cap,), jnp.bool_)
            for code in codes:
                hit |= c.values == jnp.int32(code)
            return Column(hit, c.validity)
        hit = jnp.zeros((cap,), jnp.bool_)
        for v in expr.values:
            if ty.kind is Kind.DECIMAL and isinstance(v, float):
                v = round(v * 10 ** ty.scale)
            hit |= c.values == jnp.asarray(v, c.values.dtype)
        return Column(hit, c.validity)

    if isinstance(expr, Like):
        col = _find_string_col(expr.arg)
        d = schema.dictionary(col)
        c = eval_expr(expr.arg, batch, schema)
        validity = c.validity
        if isinstance(expr.pattern, Param):
            # a bound pattern: its table is this binding's argument, and a
            # NULL pattern (the slot's valid entry) makes every row NULL
            traced = _traced(expr.pattern)
            table = traced[1 + expr.pattern.table]
            bound = traced[0][2 * expr.pattern.index + 1] != 0
            validity = _and_validity(validity,
                                     jnp.full((cap,), bound, jnp.bool_))
        else:
            table = jnp.asarray(like_table(d, expr.pattern))
        # (a bound table is as long as sql/params.table_lanes makes it, not
        # as the dictionary: nothing of the program is the dictionary's size)
        hit = table[jnp.clip(c.values, 0, table.shape[0] - 1)]
        hit &= c.values >= 0
        if expr.negate:
            hit = ~hit
        return Column(hit, validity)

    if isinstance(expr, Extract):
        c = eval_expr(expr.arg, batch, schema)
        y, m, dday = _civil_from_days(c.values.astype(jnp.int64))
        part = {"year": y, "month": m, "day": dday}[expr.part]
        return Column(part.astype(jnp.int64), c.validity)

    if isinstance(expr, VecLit):
        q = jnp.asarray(expr.values, jnp.float32)
        return Column(jnp.broadcast_to(q, (cap, q.shape[0])))

    if isinstance(expr, VecDistance):
        from cockroach_tpu.ops.vector import cosine_distance, l2_distance

        lc = eval_expr(expr.left, batch, schema)
        rc = eval_expr(expr.right, batch, schema)
        validity = _combine_validity(lc, rc)
        fn = l2_distance if expr.metric == "l2" else cosine_distance
        return Column(fn(lc.values, rc.values), validity)

    raise TypeError(f"cannot evaluate {type(expr).__name__}")


# (every entry joined by NUL, the offset each entry starts at): what
# like_table searches for a pattern's longest literal
_DICT_TEXT: Dict[int, Tuple[weakref.ref, Tuple[str, np.ndarray]]] = {}
_SEP = "\x00"


def _joined(d: np.ndarray) -> Tuple[str, np.ndarray]:
    entries = [str(x) for x in d]
    starts = np.zeros(len(entries), np.int64)
    np.cumsum([len(x) + 1 for x in entries[:-1]], out=starts[1:])
    return _SEP.join(entries), starts


def like_table(dictionary: np.ndarray, pattern: str) -> np.ndarray:
    """Which entries of `dictionary` match the LIKE `pattern` (`%` any
    run, `_` any one character): a bool array over the dictionary's codes.
    Every entry that matches holds the pattern's longest literal run, so
    one search of the joined dictionary for it leaves the regular
    expression only the entries that do (`%green%` over 200,000 part
    names: 11,000), and nothing where the pattern is that literal between
    two `%`: holding it is matching; a pattern without a literal is
    matched entry by entry."""
    n = len(dictionary)
    if pattern and not pattern.strip("%"):
        return np.ones(n, np.bool_)
    rx = re.compile(_like_to_regex(pattern), re.S)
    literal = max(re.split("[%_]", pattern), key=len)
    if len(literal) < 3 or _SEP in literal or n < 64:
        # (a literal of a character or two is in most entries)
        return np.fromiter((rx.fullmatch(x) is not None
                            for x in dictionary), np.bool_, n)
    text, starts = _per_dictionary(_DICT_TEXT, dictionary, _joined)
    found = np.fromiter((m.start() for m in
                         re.finditer(re.escape(literal), text)), np.int64)
    holds = np.searchsorted(starts, found, side="right") - 1
    out = np.zeros(n, np.bool_)
    if pattern == "%" + literal + "%":
        # (no find spans two entries: the separator is not in the literal)
        out[holds] = True
        return out
    for i in np.unique(holds):
        out[i] = rx.fullmatch(dictionary[i]) is not None
    return out


def _like_to_regex(pattern: str) -> str:
    out = []
    for ch in pattern:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return "".join(out)


def _cmp_table(dictionary: np.ndarray, op: str, literal: str):
    f = {"<": np.less, "<=": np.less_equal,
         ">": np.greater, ">=": np.greater_equal}[op]
    return jnp.asarray(f(dictionary.astype(str), literal))


def _combine_validity(lc: Column, rc: Column):
    if lc.validity is None and rc.validity is None:
        return None
    return lc.valid_mask() & rc.valid_mask()


def _and_validity(validity, extra):
    if validity is None:
        return extra
    return validity & extra


def _as_float(values, ty: ColType):
    if ty.kind is Kind.DECIMAL:
        return _decimal_to_float(values, ty.scale)
    return values.astype(jnp.float32)


def _numeric_align(lv, lt: ColType, rv, rt: ColType):
    """Align two columns for comparison."""
    if lt.kind is Kind.DECIMAL or rt.kind is Kind.DECIMAL:
        ls = lt.scale if lt.kind is Kind.DECIMAL else 0
        rs = rt.scale if rt.kind is Kind.DECIMAL else 0
        s = max(ls, rs)
        if lt.kind is Kind.FLOAT or rt.kind is Kind.FLOAT:
            return _as_float(lv, lt), _as_float(rv, rt)
        return (_rescale(lv.astype(jnp.int64), ls, s),
                _rescale(rv.astype(jnp.int64), rs, s))
    if lt.kind is Kind.FLOAT or rt.kind is Kind.FLOAT:
        return _as_float(lv, lt), _as_float(rv, rt)
    return lv, rv


def _civil_from_days(z):
    """days-since-epoch -> (year, month, day); Howard Hinnant's algorithm."""
    z = z + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = jnp.where(m <= 2, y + 1, y)
    return y, m, d


def filter_mask(expr: Expr, batch: Batch, schema: Schema):
    """Predicate -> boolean keep-mask (TRUE only; NULL/FALSE drop)."""
    c = eval_expr(expr, batch, schema)
    return c.values & c.valid_mask()
