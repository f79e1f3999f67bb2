"""Row-at-a-time fallback engine — exact datum semantics on the host.

Reference (SURVEY.md §2.3 + §7.4 item 6): the reference's vectorized
engine falls back to datum-backed vectors (col/coldataext) or the row
engine (rowexec) for types/ops with no native columnar representation —
decimals beyond int64, exact division. This is that seam: `RowMapOp`
evaluates a projection per row with Python's arbitrary-precision int +
decimal.Decimal, then re-encodes into device columns.

The planner routes a Project here when `sql.tpu.exact_arithmetic` is on
and the projection contains decimal division — the one arithmetic op the
int64-scaled device path degrades to float32 (ops/expr.py BinOp "/").
Everything else stays on the TPU path; the fallback batch's capacity and
selection are preserved so the operator composes transparently.
"""

from __future__ import annotations

import datetime
import re
from decimal import Decimal, ROUND_HALF_UP
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from cockroach_tpu.coldata.batch import (
    Batch, ColType, Column, DECIMAL, FLOAT, INT, Kind, Schema,
)
from cockroach_tpu.ops.expr import (
    BinOp, BoolOp, Case, Cast, Cmp, Col, Expr, Extract, InList, IsNull,
    Like, Lit, Not,
)
from cockroach_tpu.util.settings import Settings

EXACT_ARITHMETIC = Settings.register(
    "sql.tpu.exact_arithmetic",
    False,
    "route decimal division through the exact row-at-a-time fallback",
)

DIV_SCALE = 6  # result scale of exact decimal division (numeric-ish)


# ------------------------------------------------------------ typing -----

def exact_type(e: Expr, schema: Schema) -> ColType:
    """Expr type under EXACT rules: decimal / decimal -> DECIMAL(6)
    instead of the device path's float32."""
    if isinstance(e, BinOp) and e.op == "/":
        lt, rt = exact_type(e.left, schema), exact_type(e.right, schema)
        if Kind.DECIMAL in (lt.kind, rt.kind) or \
                (lt.kind is Kind.INT and rt.kind is Kind.INT):
            return DECIMAL(DIV_SCALE)
        return FLOAT
    if isinstance(e, BinOp):
        lt, rt = exact_type(e.left, schema), exact_type(e.right, schema)
        if Kind.DECIMAL in (lt.kind, rt.kind):
            ls = lt.scale if lt.kind is Kind.DECIMAL else 0
            rs = rt.scale if rt.kind is Kind.DECIMAL else 0
            if e.op in ("+", "-"):
                return DECIMAL(max(ls, rs))
            if e.op == "*":
                return DECIMAL(ls + rs)
        return e.type(schema)
    if isinstance(e, Case):
        return exact_type(e.whens[0][1], schema)
    return e.type(schema)


def has_string_compute(e: Expr) -> bool:
    """Does the expression mint NEW strings (StrFunc anywhere)? Such
    projections must run on the row engine: the device representation is
    dictionary codes and the dictionary grows host-side."""
    from cockroach_tpu.ops.expr import StrFunc

    if isinstance(e, StrFunc):
        return True
    for v in getattr(e, "__dict__", {}).values():
        if isinstance(v, Expr) and has_string_compute(v):
            return True
        if isinstance(v, tuple):
            for item in v:
                if isinstance(item, Expr) and has_string_compute(item):
                    return True
    return False


def has_decimal_division(e: Expr, schema: Schema) -> bool:
    if isinstance(e, BinOp) and e.op == "/":
        lt = e.left.type(schema)
        rt = e.right.type(schema)
        if Kind.DECIMAL in (lt.kind, rt.kind):
            return True
    for v in getattr(e, "__dict__", {}).values():
        if isinstance(v, Expr) and has_decimal_division(v, schema):
            return True
        if isinstance(v, tuple):
            for item in v:
                if isinstance(item, Expr) \
                        and has_decimal_division(item, schema):
                    return True
                if isinstance(item, tuple):
                    for sub in item:
                        if isinstance(sub, Expr) \
                                and has_decimal_division(sub, schema):
                            return True
    return False


# ------------------------------------------------------ datum evaluation --

def _decode(vals, validity, ty: ColType, dictionary) -> List:
    out = []
    for i in range(len(vals)):
        if validity is not None and not bool(validity[i]):
            out.append(None)
        elif ty.kind is Kind.DECIMAL:
            out.append(Decimal(int(vals[i])).scaleb(-ty.scale))
        elif ty.kind is Kind.STRING and dictionary is not None:
            out.append(str(dictionary[int(vals[i])]))
        elif ty.kind is Kind.FLOAT:
            out.append(float(vals[i]))
        elif ty.kind is Kind.BOOL:
            out.append(bool(vals[i]))
        else:
            out.append(int(vals[i]))
    return out


def eval_datum(e: Expr, row: Dict[str, object], schema: Schema):
    """Evaluate one row with exact host semantics; None = SQL NULL."""
    from cockroach_tpu.ops.expr import ScalarFunc, StrFunc

    if isinstance(e, Col):
        return row[e.name]
    if isinstance(e, ScalarFunc):
        vals = [eval_datum(a, row, schema) for a in e.args]
        f = e.func
        if f == "coalesce":
            return next((v for v in vals if v is not None), None)
        if f == "nullif":
            a, b = vals
            return None if (a is not None and a == b) else a
        if f in ("greatest", "least"):
            nn = [v for v in vals if v is not None]
            if not nn:
                return None
            return max(nn) if f == "greatest" else min(nn)
        if vals[0] is None or (len(vals) > 1 and vals[1] is None):
            return None
        if f == "abs":
            return abs(vals[0])
        if f == "sign":
            return (vals[0] > 0) - (vals[0] < 0)
        if f == "mod":
            if vals[1] == 0:
                return None
            import math

            return math.fmod(vals[0], vals[1])
        if f == "length":
            return len(str(vals[0]))
        if f == "floor":
            import math

            return int(math.floor(vals[0]))
        if f == "ceil":
            import math

            return int(math.ceil(vals[0]))
    if isinstance(e, StrFunc):
        vals = [eval_datum(a, row, schema) for a in e.args]
        if any(v is None for v in vals):
            return None
        if e.func == "concat":
            return "".join(str(v) for v in vals)
        v = str(vals[0])
        if e.func == "upper":
            return v.upper()
        if e.func == "lower":
            return v.lower()
        start, ln = e.params  # SQL substring: 1-based start
        return v[max(start - 1, 0):max(start - 1, 0) + ln]
    if isinstance(e, Lit):
        v = e.value
        if v is None:
            return None
        if e.ty is not None and e.ty.kind is Kind.DECIMAL:
            return Decimal(str(v))
        return v
    if isinstance(e, BinOp):
        lv = eval_datum(e.left, row, schema)
        rv = eval_datum(e.right, row, schema)
        if lv is None or rv is None:
            return None
        if e.op == "/":
            if rv == 0:
                return None  # division by zero -> NULL (device parity)
            if isinstance(lv, (Decimal, int)) and \
                    isinstance(rv, (Decimal, int)):
                q = Decimal(lv) / Decimal(rv)
                return q.quantize(Decimal(1).scaleb(-DIV_SCALE),
                                  rounding=ROUND_HALF_UP)
            return float(lv) / float(rv)
        if isinstance(lv, Decimal) or isinstance(rv, Decimal):
            lv, rv = Decimal(lv), Decimal(rv)
        return {"+": lambda: lv + rv, "-": lambda: lv - rv,
                "*": lambda: lv * rv}[e.op]()
    if isinstance(e, Cmp):
        lv = eval_datum(e.left, row, schema)
        rv = eval_datum(e.right, row, schema)
        if lv is None or rv is None:
            return None
        if isinstance(lv, Decimal) or isinstance(rv, Decimal):
            lv, rv = Decimal(str(lv)), Decimal(str(rv))
        return {"==": lv == rv, "!=": lv != rv, "<": lv < rv,
                "<=": lv <= rv, ">": lv > rv, ">=": lv >= rv}[e.op]
    if isinstance(e, BoolOp):
        vals = [eval_datum(a, row, schema) for a in e.args]
        if e.op == "and":
            if any(v is False for v in vals):
                return False
            return None if any(v is None for v in vals) else True
        if any(v is True for v in vals):
            return True
        return None if any(v is None for v in vals) else False
    if isinstance(e, Not):
        v = eval_datum(e.arg, row, schema)
        return None if v is None else (not v)
    if isinstance(e, IsNull):
        v = eval_datum(e.arg, row, schema)
        return (v is not None) if e.negate else (v is None)
    if isinstance(e, Case):
        for cond, val in e.whens:
            if eval_datum(cond, row, schema) is True:
                return eval_datum(val, row, schema)
        return (eval_datum(e.otherwise, row, schema)
                if e.otherwise is not None else None)
    if isinstance(e, Cast):
        v = eval_datum(e.arg, row, schema)
        if v is None:
            return None
        if e.to.kind is Kind.DECIMAL:
            return Decimal(str(v)).quantize(
                Decimal(1).scaleb(-e.to.scale), rounding=ROUND_HALF_UP)
        if e.to.kind is Kind.INT:
            return int(v)
        if e.to.kind is Kind.FLOAT:
            return float(v)
        return v
    if isinstance(e, Extract):
        v = eval_datum(e.arg, row, schema)
        if v is None:
            return None
        d = datetime.date(1970, 1, 1) + datetime.timedelta(days=int(v))
        return {"year": d.year, "month": d.month, "day": d.day}[e.part]
    if isinstance(e, InList):
        v = eval_datum(e.arg, row, schema)
        if v is None:
            return None
        return v in e.values
    if isinstance(e, Like) and isinstance(e.pattern, str):
        v = eval_datum(e.arg, row, schema)
        if v is None:
            return None
        pat = "^" + "".join(
            ".*" if ch == "%" else "." if ch == "_" else re.escape(ch)
            for ch in e.pattern) + "$"
        hit = re.match(pat, str(v)) is not None
        return (not hit) if e.negate else hit
    raise NotImplementedError(f"row engine: {type(e).__name__}")


def _expr_cols(e: Expr, out: set) -> None:
    if isinstance(e, Col):
        out.add(e.name)
    for v in getattr(e, "__dict__", {}).values():
        if isinstance(v, Expr):
            _expr_cols(v, out)
        elif isinstance(v, tuple):
            for item in v:
                if isinstance(item, Expr):
                    _expr_cols(item, out)
                elif isinstance(item, tuple):
                    for sub in item:
                        if isinstance(sub, Expr):
                            _expr_cols(sub, out)


# --------------------------------------------------------------- RowMapOp

class RowMapOp:
    """Projection evaluated row-at-a-time with exact datum semantics.
    Drop-in for MapOp(project): same capacity/sel, new columns."""

    def __init__(self, child, outputs: Sequence[Tuple[str, Expr]]):
        from cockroach_tpu.coldata.batch import Field

        self.child = child
        self.outputs = list(outputs)
        in_schema = child.schema
        fields = []
        # plain Col outputs pass the device column through untouched —
        # only computed expressions take the per-row datum path
        self._passthrough: Dict[str, str] = {}
        self._computed: List[Tuple[str, Expr]] = []
        # computed STRING outputs mint codes into a FRESH dictionary
        # (the same growth path session INSERT uses for new literals);
        # the schema's dict mapping is updated as batches flow
        self._minted: Dict[str, Dict[str, int]] = {}
        dicts = dict(in_schema.dicts)
        for name, e in self.outputs:
            ty = exact_type(e, in_schema)
            dict_ref = None
            if isinstance(e, Col):
                dict_ref = in_schema.field(e.name).dict_ref
                self._passthrough[name] = e.name
            else:
                if ty.kind is Kind.STRING:
                    dict_ref = f"__computed__:{id(self)}:{name}"
                    self._minted[name] = {}
                    dicts[dict_ref] = np.zeros(0, dtype=object)
                self._computed.append((name, e))
            fields.append(Field(name, ty, dict_ref=dict_ref))
        self.schema = Schema(fields, dicts)
        # decode only the columns the computed expressions reference
        needed: set = set()
        for _, e in self._computed:
            _expr_cols(e, needed)
        self._needed = [f for f in in_schema if f.name in needed]

    def batches(self) -> Iterator[Batch]:
        from cockroach_tpu.exec import stats as _stats

        in_schema = self.child.schema
        for b in self.child.batches():
            with _stats.timed("host.rowmap", rows=int(b.length)):
                yield self._one(b, in_schema)

    def _one(self, b, in_schema) -> Batch:
        cap = b.capacity
        sel = np.asarray(b.sel)
        idxs = np.nonzero(sel)[0]
        cols_np = {}
        for f in self._needed:
            c = b.col(f.name)
            cols_np[f.name] = _decode(
                np.asarray(c.values)[idxs],
                (np.asarray(c.validity)[idxs]
                 if c.validity is not None else None),
                f.type, in_schema.dictionary(f.name))
        rows = [{n: cols_np[n][j] for n in cols_np}
                for j in range(len(idxs))]

        out_cols: Dict[str, Column] = {}
        for name, src in self._passthrough.items():
            out_cols[name] = b.col(src)
        for name, e in self._computed:
            ty = self.schema.field(name).type
            vals = np.zeros(cap, dtype=ty.dtype)
            valid = np.zeros(cap, dtype=bool)
            minted = self._minted.get(name)
            for j, i in enumerate(idxs):
                v = eval_datum(e, rows[j], in_schema)
                if v is None:
                    continue
                valid[i] = True
                if minted is not None:
                    code = minted.setdefault(str(v), len(minted))
                    vals[i] = code
                    continue
                if ty.kind is Kind.DECIMAL:
                    scaled = int(Decimal(str(v)).scaleb(ty.scale)
                                 .to_integral_value(ROUND_HALF_UP))
                    if not (-(1 << 63) <= scaled < (1 << 63)):
                        raise OverflowError(
                            f"{name}: exact decimal {v} exceeds the "
                            "int64 device encoding")
                    vals[i] = scaled
                else:
                    vals[i] = v
            out_cols[name] = Column(jnp.asarray(vals),
                                    jnp.asarray(valid))
        # publish grown dictionaries for downstream decoding
        for name, minted in self._minted.items():
            ref = self.schema.field(name).dict_ref
            self.schema.dicts[ref] = np.asarray(
                sorted(minted, key=minted.get), dtype=object)
        return Batch(out_cols, b.sel, b.length)

    def pipeline(self):
        # a host-side row loop cannot fuse into a jitted program: the
        # row engine is a pipeline breaker by construction
        return self.batches, (lambda x: x)
