"""Bound parameters as data: what pgwire's Bind hands the session.

Reference: pkg/sql/pgwire (conn.go handleBind decodes each parameter by
its format code and OID into a datum) and sem/tree.PlaceholderInfo (one
plan per statement, placeholders typed from context). Here the binder
(sql/bind.py) types every constant subexpression over `$n` from its
sibling operand and registers it as a SLOT; this module turns the values
of one Bind into the slots' device scalars, once, on the host:

- a date is days since 1970-01-01 (int32), a decimal the scaled integer
  at the column's scale (exact: Python's Decimal, never a float), an int
  an int64, a string the code its column's dictionary gives it (-1 when
  the dictionary does not hold it: equal to no row);
- `$1 + interval '1' year`, `$2 - 0.01`: arithmetic over parameters and
  literals only is folded here, per binding, and reaches the program as
  one argument;
- NULL binds as NULL (the slot's `valid` is False);
- a LIKE pattern over a dictionary-coded column (`p_name like $1`) has no
  scalar to send: the pattern is matched against the column's dictionary
  here, once a Bind (stage `sql.bind_like`), and the boolean table over
  the dictionary's codes (padded to a power of two: `table_lanes`) is an
  argument of the program beside the scalars' vector, where a literal
  pattern's table is a constant of it.

A value the slot's type cannot hold exactly (0.055 against a DECIMAL(2)
column, 'abc' against a date) raises ValueOutOfScope: that binding is
pasted into the text and planned as literals, as every binding was
before (`substitute`; counter `sql_bind_textual_total`).
"""

from __future__ import annotations

import datetime
import re
import struct
from dataclasses import dataclass
from decimal import Decimal, InvalidOperation
from typing import Optional, Sequence, Tuple

import numpy as np

from cockroach_tpu.coldata.batch import ColType, Kind
from cockroach_tpu.sql import parser as P

_EPOCH = datetime.date(1970, 1, 1)
_PG_EPOCH = datetime.date(2000, 1, 1)


class ParamOutOfScope(ValueError):
    """The statement cannot take its parameters as program arguments;
    bind it as text."""


class ValueOutOfScope(ParamOutOfScope):
    """This binding's values do not fit the statement's slots; bind this
    one as text (the next binding may fit)."""


# ------------------------------------------------ wire values -> Python --

OID_BOOL, OID_INT8, OID_INT2, OID_INT4 = 16, 20, 21, 23
OID_TEXT, OID_FLOAT4, OID_FLOAT8, OID_VARCHAR = 25, 700, 701, 1043
OID_DATE, OID_NUMERIC = 1082, 1700


def _numeric_binary(b: bytes) -> Decimal:
    """PostgreSQL's binary NUMERIC: ndigits, weight, sign, dscale, then
    base-10000 digits."""
    ndigits, weight, sign, dscale = struct.unpack(">hhHh", b[:8])
    if sign == 0xC000:
        raise ValueError("NaN is not a bindable NUMERIC")
    digits = struct.unpack(f">{ndigits}h", b[8:8 + 2 * ndigits])
    value = Decimal(0)
    for i, d in enumerate(digits):
        value += Decimal(d).scaleb(4 * (weight - i))
    value = value.quantize(Decimal(1).scaleb(-dscale))
    return -value if sign == 0x4000 else value


_BINARY = {
    OID_INT2: lambda b: struct.unpack(">h", b)[0],
    OID_INT4: lambda b: struct.unpack(">i", b)[0],
    OID_INT8: lambda b: struct.unpack(">q", b)[0],
    OID_FLOAT4: lambda b: struct.unpack(">f", b)[0],
    OID_FLOAT8: lambda b: struct.unpack(">d", b)[0],
    OID_BOOL: lambda b: bool(b and b[0]),
    OID_DATE: lambda b: _PG_EPOCH + datetime.timedelta(
        days=struct.unpack(">i", b)[0]),
    OID_NUMERIC: _numeric_binary,
    OID_TEXT: lambda b: b.decode(),
    OID_VARCHAR: lambda b: b.decode(),
}


def decode_binary(raw: bytes, oid: int):
    """A binary-format parameter (format code 1) by the OID its Parse
    declared -> int, float, bool, Decimal, date or str."""
    dec = _BINARY.get(oid)
    if dec is None:
        raise ValueError(
            f"binary parameter format not supported for OID {oid} "
            "(use text)")
    return dec(raw)


# ------------------------------------------------- binding as text -------

_PLACEHOLDER = re.compile(r"\$(\d+)")


def count_placeholders(sql: str) -> int:
    return max((int(m.group(1)) for m in _PLACEHOLDER.finditer(sql)),
               default=0)


def _as_literal(v) -> str:
    if v is None:
        return "NULL"
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, (int, Decimal)):
        return str(v)
    if isinstance(v, float):
        return repr(v)
    if isinstance(v, datetime.date):
        return "'" + v.isoformat() + "'"
    try:
        float(v)
        return v
    except ValueError:
        return "'" + v.replace("'", "''") + "'"


def substitute(sql: str, values: Sequence) -> str:
    """`$n` replaced by the n-th value written as a literal (numbers as
    they are, anything else quoted): the statement as a client without
    parameters would have sent it."""

    def repl(m):
        i = int(m.group(1)) - 1
        if i >= len(values):
            raise ValueError(f"parameter ${i + 1} not bound")
        return _as_literal(values[i])

    return _PLACEHOLDER.sub(repl, sql)


# ------------------------------------------------------ slots ------------

@dataclass
class ParamSlot:
    """One program argument of a parameterised statement: the constant
    subexpression `node` (a `$n`, or arithmetic over `$n` and literals),
    typed `ty` by the binder from the operand beside it. `column` and
    `schema` name the dictionary a STRING slot is looked up in. A LIKE
    pattern's slot has a `table`: its place among the statement's table
    arguments (`relation` is the column's table, for EXPLAIN)."""

    index: int
    node: P.Node
    ty: ColType
    column: Optional[str] = None
    schema: object = None
    table: Optional[int] = None
    relation: Optional[str] = None

    def describe(self) -> str:
        if self.table is not None:
            return (f"{render(self.node)} "
                    f"pattern({self.relation}.{self.column})")
        kind = {Kind.STRING: "string(code)", Kind.DATE: "date",
                Kind.INT: "int", Kind.FLOAT: "float"}.get(
            self.ty.kind, f"decimal({self.ty.scale})")
        return f"{render(self.node)} {kind}"


def render(node: P.Node) -> str:
    if isinstance(node, P.Placeholder):
        return f"${node.index}"
    if isinstance(node, P.Num):
        return node.text
    if isinstance(node, P.Str):
        return "'" + node.value + "'"
    if isinstance(node, P.DateLit):
        return "date '" + (_EPOCH + datetime.timedelta(
            days=node.days)).isoformat() + "'"
    if isinstance(node, P.IntervalLit):
        return f"interval '{node.n}' {node.unit}"
    if isinstance(node, P.Unary):
        return "-" + render(node.arg)
    return f"{render(node.left)} {node.op} {render(node.right)}"


def is_param_const(node: P.Node) -> bool:
    """A constant subexpression that holds a `$n`: placeholders and
    literals under + - * and unary minus, no column."""

    def walk(n) -> Optional[bool]:  # None: not constant
        if isinstance(n, P.Placeholder):
            return True
        if isinstance(n, (P.Num, P.Str, P.DateLit, P.IntervalLit)):
            return False
        if isinstance(n, P.Unary) and n.op == "-":
            return walk(n.arg)
        if isinstance(n, P.Binary) and n.op in ("+", "-", "*"):
            a, b = walk(n.left), walk(n.right)
            return None if a is None or b is None else a or b
        return None

    return bool(walk(node))


def date_add(days: int, n: int, unit: str) -> int:
    """Calendar arithmetic of DATE + INTERVAL (the day clamps to the
    target month's length), in days since 1970-01-01."""
    base = _EPOCH + datetime.timedelta(days)
    if unit == "day":
        return days + n
    total = base.year * 12 + (base.month - 1) \
        + n * (12 if unit == "year" else 1)
    y, m = divmod(total, 12)
    for day in range(base.day, 0, -1):
        try:
            return (datetime.date(y, m + 1, day) - _EPOCH).days
        except ValueError:
            continue
    raise ValueError("date out of range")


class _Null(Exception):
    pass


def _coerce(v, kind: Kind):
    """A decoded wire value as the slot's kind wants its leaves."""
    if v is None:
        raise _Null()
    if kind is Kind.DATE:
        if isinstance(v, str):
            v = datetime.date.fromisoformat(v.strip())
        if isinstance(v, datetime.date):
            return (v - _EPOCH).days
        raise ParamOutOfScope(f"{v!r} is not a date")
    if kind is Kind.STRING:
        if isinstance(v, str):
            return v
        raise ParamOutOfScope(f"{v!r} is not a string")
    if isinstance(v, (bool, datetime.date)):
        raise ParamOutOfScope(f"{v!r} is not a number")
    if kind is Kind.FLOAT:
        return float(v)
    try:
        d = Decimal(v.strip()) if isinstance(v, str) else Decimal(str(v))
    except InvalidOperation:
        raise ParamOutOfScope(f"{v!r} is not a number") from None
    if not d.is_finite():
        raise ParamOutOfScope(f"{v!r} is not a finite number")
    return d


def _fold(node: P.Node, values: Sequence, kind: Kind):
    """The slot's expression at one binding: int days for a DATE slot, a
    str for STRING, a float for FLOAT, else an exact Decimal."""
    if isinstance(node, P.Placeholder):
        if node.index > len(values):
            raise ValueError(f"parameter ${node.index} not bound")
        return _coerce(values[node.index - 1], kind)
    if isinstance(node, P.Num):
        return float(node.text) if kind is Kind.FLOAT else Decimal(node.text)
    if isinstance(node, P.Str):
        return _coerce(node.value, kind)
    if isinstance(node, P.DateLit):
        return node.days
    if isinstance(node, P.Unary):
        return -_fold(node.arg, values, kind)
    if isinstance(node, P.IntervalLit):
        raise ParamOutOfScope("INTERVAL outside date arithmetic")
    if kind is Kind.STRING:
        raise ParamOutOfScope("arithmetic on a string")
    if kind is Kind.DATE:
        if node.op == "*":
            raise ParamOutOfScope("a date multiplied")
        sign = 1 if node.op == "+" else -1
        left = _fold(node.left, values, kind)
        if isinstance(node.right, P.IntervalLit):
            return date_add(left, sign * node.right.n, node.right.unit)
        if isinstance(node.right, P.Num) and "." not in node.right.text:
            return left + sign * int(node.right.text)
        raise ParamOutOfScope("date arithmetic other than +- interval "
                              "or whole days")
    a, b = _fold(node.left, values, kind), _fold(node.right, values, kind)
    return a + b if node.op == "+" else a - b if node.op == "-" else a * b


_INT32 = (-(1 << 31), (1 << 31) - 1)
_INT64 = (-(1 << 63), (1 << 63) - 1)


def _whole(d, bounds) -> int:
    if isinstance(d, Decimal):
        if d != d.to_integral_value():
            raise ParamOutOfScope(f"{d} is not exact at the slot's scale")
        d = int(d)
    if not bounds[0] <= d <= bounds[1]:
        raise ParamOutOfScope(f"{d} out of range")
    return d


def slot_value(slot: ParamSlot, values: Sequence) -> Tuple:
    """-> (value, valid): numpy scalars of the slot's device dtype."""
    kind = slot.ty.kind
    try:
        v = _fold(slot.node, values, kind)
    except _Null:
        return np.dtype(slot.ty.dtype).type(0), np.bool_(False)
    if kind is Kind.DATE:
        out = np.int32(_whole(v, _INT32))
    elif kind is Kind.STRING:
        from cockroach_tpu.ops.expr import _string_code

        out = np.int32(_string_code(slot.schema, slot.column, v))
    elif kind is Kind.FLOAT:
        out = np.float32(v)
    elif kind is Kind.DECIMAL:
        out = np.int64(_whole(v.scaleb(slot.ty.scale), _INT64))
    else:
        out = np.int64(_whole(v, _INT64))
    return out, np.bool_(True)


def pattern_table(slot: ParamSlot, values: Sequence):
    """A LIKE pattern slot at one binding -> the bool table over its
    column's dictionary (ops/expr.like_table), or None for a NULL
    pattern."""
    from cockroach_tpu.ops.expr import like_table

    try:
        pattern = _fold(slot.node, values, Kind.STRING)
    except _Null:
        return None
    return like_table(slot.schema.dictionary(slot.column), pattern)


def sample_of(slot: ParamSlot, values: Sequence):
    """The slot's value at this binding as a Lit would hold it (the
    planner's estimates read a Param's `sample` where they read a Lit's
    value: days, an unscaled number; a string has none). A pattern's is
    (dictionary entries it matches, dictionary entries)."""
    if slot.table is not None:
        try:
            table = pattern_table(slot, values)
        except ValueError:
            return None
        return None if table is None else (int(table.sum()), len(table))
    if slot.ty.kind is Kind.STRING:
        return None
    try:
        v = _fold(slot.node, values, slot.ty.kind)
    except (_Null, ValueError, ArithmeticError):
        return None     # evaluate() reports what is wrong with a value
    return v if isinstance(v, int) else float(v)


def table_lanes(entries: int) -> int:
    """The length of a pattern slot's table argument: the dictionary's
    entries rounded up to a power of two, as a capacity is. The program's
    shape then belongs to the statement and not to the data: TPC-H's part
    names are 199,995 to 199,999 distinct strings by the seed (a handful of
    200,000 rows share a name), and each would be a module of its own."""
    return 1 << max(entries - 1, 0).bit_length()


def _bind_pattern(slot: ParamSlot, values: Sequence):
    """-> (entries matched, valid, table): a pattern slot's entry of the
    packed vector and its table argument (`table_lanes` long; no code
    reaches the entries past the dictionary's, which are False). One
    `sql.bind_like` event, its `rows` the dictionary entries the pattern
    was matched against."""
    from cockroach_tpu.exec import stats

    size = len(slot.schema.dictionary(slot.column))
    out = np.zeros(table_lanes(size), np.bool_)
    with stats.timed("sql.bind_like", rows=size):
        table = pattern_table(slot, values)
    if table is None:
        return np.int64(0), np.bool_(False), out
    out[:size] = table
    return np.int64(table.sum()), np.bool_(True), out


def evaluate(slots: Sequence[ParamSlot], values: Sequence) -> tuple:
    """One Bind's values -> the program's trailing arguments: first an
    int64 vector of (value, valid) pairs, slot i at [2i, 2i + 1] (a
    float32's bit pattern rides as an integer; ops/expr.eval_expr unpacks
    a Param by its type), then one bool table over a dictionary for each
    pattern slot, in the order of `ParamSlot.table` (most statements have
    none). One small array is one transfer to the device a statement,
    however many scalar parameters it has. Raises ValueOutOfScope for a
    binding the slots cannot hold."""
    packed = np.zeros(2 * len(slots), np.int64)
    tables = [None] * sum(s.table is not None for s in slots)
    try:
        for s in slots:
            if s.table is not None:
                value, valid, tables[s.table] = _bind_pattern(s, values)
            else:
                value, valid = slot_value(s, values)
            if value.dtype == np.float32:
                value = value.view(np.int32)
            packed[2 * s.index] = value
            packed[2 * s.index + 1] = valid
    except (ValueError, ArithmeticError) as e:
        raise ValueOutOfScope(str(e)) from e
    return (packed, *tables)


@dataclass
class BoundParams:
    """What Bind leaves on the portal for Execute: the decoded values,
    and the program's arguments once the statement's slots are known
    (a warm statement: at Bind; a cold one: when Execute has planned
    it)."""

    values: tuple
    args: Optional[tuple] = None

    def __len__(self):
        return len(self.values)


def describe_binding(values: Sequence, slots: Sequence[ParamSlot] = ()
                     ) -> str:
    """`$1 = 312, $2 = '%green%' (108 of 1997 part.p_name values)`: the
    binding EXPLAIN's estimates were taken at; a pattern with the share of
    its dictionary it matches, which is what the estimate read."""
    matched = {}
    for s in slots:
        sample = sample_of(s, values) if s.table is not None else None
        if sample and isinstance(s.node, P.Placeholder):
            matched[s.node.index] = (f" ({sample[0]} of {sample[1]} "
                                     f"{s.relation}.{s.column} values)")
    return ", ".join(f"${i + 1} = {_as_literal(v)}{matched.get(i + 1, '')}"
                     for i, v in enumerate(values))
