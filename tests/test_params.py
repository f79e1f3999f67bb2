"""Bound parameters as program arguments (ISSUE 31): a statement with `$n`
placeholders runs pgwire Parse/Bind/Execute -> Session -> compile_plan ->
FusedRunner as ONE prepared entry and ONE device program for all bindings.

One module-scoped TPC-H SF 0.01 load from the benchmark's loader, served by
one PgServer; TPC-H Q3 and Q6 with QGEN's substitution parameters, judged
by the benchmark's plain numpy references at each binding
(benchmark/reference/tpch_q3_qgen.py, tpch_q6.py) and by the same statement
sent with the values as literals. The mesh case is in
tests/test_session_distsql.py, which builds the four virtual devices.
"""

import datetime
import hashlib
import re
import struct
from decimal import Decimal

import jax.monitoring
import numpy as np
import pytest

from benchmark import manifest, wire
from benchmark.loaders import tpch as tpch_loader
from benchmark.loaders import tpch_dbgen
from benchmark.paramgen import tpch_qgen
from benchmark.reference import tpch_q3_qgen, tpch_q6
from cockroach_tpu.coldata.batch import DATE, DECIMAL, INT, STRING
from cockroach_tpu.exec import fused, stats
from cockroach_tpu.ops.expr import ParamOutsideProgram, has_params
from cockroach_tpu.sql import params as P_
from cockroach_tpu.sql import parser
from cockroach_tpu.sql.bind import BindError, Binder
from cockroach_tpu.sql.pgwire import PgServer
from cockroach_tpu.sql.session import Session
from cockroach_tpu.storage.mvcc import MVCCStore
from cockroach_tpu.util.metric import default_registry

SEED = 2147483999
CAP = 1 << 14
Q3 = manifest.cell("tpch-sf1-qgen.q3-1stream")["statements"][0]["sql"]
Q6 = manifest.cell("tpch-sf1-qgen.q6-2streams")["statements"][0]["sql"]
Q3_SPEC = {"kind": "tpch_qgen", "query": "q3"}
Q6_SPEC = {"kind": "tpch_qgen", "query": "q6"}


def _draws(spec, n):
    rng = np.random.default_rng([SEED, 31])
    return tpch_qgen.draw(spec, rng, n, tpch_qgen.prepare(spec))


# the corners of each domain plus 20 seeded draws (a draw that repeats an
# earlier binding is dropped: its literal text would be a prepared hit)
Q3_BINDINGS = list(dict.fromkeys(tpch_qgen.corners(Q3_SPEC)
                                 + _draws(Q3_SPEC, 20)))
Q6_BINDINGS = list(dict.fromkeys(tpch_qgen.corners(Q6_SPEC)
                                 + _draws(Q6_SPEC, 20)))

_compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda name, _secs, **_kw: _compiles.append(name)
    if name.endswith("backend_compile_duration") else None)


def _counter(name):
    return default_registry().counter(name).value()


# ------------------------------------------------- a client that binds ----

OIDS = {"text": 25, "date": 1082, "numeric": 1700, "int8": 20}
_PG_EPOCH = datetime.date(2000, 1, 1)


def _numeric(d: Decimal) -> bytes:
    """PostgreSQL's binary NUMERIC (base-10000 digits)."""
    sign, _digits, exp = d.as_tuple()
    ip, _, fp = format(abs(d), "f").partition(".")
    ip = ip.lstrip("0")
    ip = ip.rjust(-(-len(ip) // 4) * 4, "0")
    fp = fp.ljust(-(-len(fp) // 4) * 4, "0")
    groups = [int((ip + fp)[i:i + 4]) for i in range(0, len(ip + fp), 4)]
    weight = len(ip) // 4 - 1
    while groups and groups[0] == 0:
        groups.pop(0)
        weight -= 1
    while groups and groups[-1] == 0:
        groups.pop()
    return struct.pack(">hhHh", len(groups), weight,
                       0x4000 if sign else 0, max(-exp, 0)) \
        + b"".join(struct.pack(">h", g) for g in groups)


def _binary(kind, text):
    if kind == "date":
        return struct.pack(
            ">i", (datetime.date.fromisoformat(text) - _PG_EPOCH).days)
    if kind == "numeric":
        return _numeric(Decimal(text))
    if kind == "int8":
        return struct.pack(">q", int(text))
    return text.encode()


def bind_message(sql, values, kinds=None):
    """Parse/Bind/Execute/Sync of the unnamed statement. `kinds` (one of
    OIDS a value) sends every value in BINARY format with its OID declared
    in Parse; None sends text. A value of None is NULL either way."""
    msg = bytearray()
    oids = [OIDS[k] for k in kinds] if kinds else []
    pl = b"\x00" + sql.encode() + b"\x00" + struct.pack(
        f">H{len(oids)}I", len(oids), *oids)
    msg += b"P" + struct.pack(">I", len(pl) + 4) + pl
    bp = bytearray(b"\x00\x00")
    bp += struct.pack(">HH", 1, 1) if kinds else struct.pack(">H", 0)
    bp += struct.pack(">H", len(values))
    for i, v in enumerate(values):
        if v is None:
            bp += struct.pack(">i", -1)
            continue
        raw = _binary(kinds[i], v) if kinds else str(v).encode()
        bp += struct.pack(">i", len(raw)) + raw
    bp += struct.pack(">H", 0)
    msg += b"B" + struct.pack(">I", len(bp) + 4) + bp
    ep = b"\x00" + struct.pack(">i", 0)
    msg += b"E" + struct.pack(">I", len(ep) + 4) + ep
    msg += b"S" + struct.pack(">I", 4)
    return bytes(msg)


class Client(wire.WireClient):
    def bound(self, sql, values, kinds=None):
        self.s.sendall(bind_message(sql, values, kinds))
        return self._read()


@pytest.fixture(scope="module")
def tpch():
    store = MVCCStore()
    loaded = tpch_loader.load(store, {"sf": 0.01},
                              ["lineitem", "orders", "customer"], SEED)
    pg = PgServer(loaded["catalog"], capacity=CAP).start()
    loaded["pg"] = pg
    yield loaded
    pg.close()


@pytest.fixture
def client(tpch):
    c = Client(tpch["pg"].addr, timeout=300.0)
    assert c.query("set vectorize = tpu") == ([], None)
    yield c
    c.close()


def _session(tpch):
    s = Session(tpch["catalog"], capacity=CAP)
    s.execute("set vectorize = tpu")
    return s


def _prepared(tpch, sql):
    s = Session(tpch["catalog"], capacity=CAP)
    with s._prepared_mu:
        return s._prepared.get(sql)


# ------------------------------------------- (a) parser and binder --------

def test_the_parser_knows_a_placeholder():
    ast = parser.parse("select a from t where b = $1 and c < $12 + 1")
    assert ast.where.left.right == parser.Placeholder(1)
    assert ast.where.right.right.left == parser.Placeholder(12)
    assert P_.count_placeholders("select $2, '$9', $11") == 11


@pytest.mark.parametrize("where,want", [
    ("c_mktsegment = $1", [("$1", STRING)]),
    ("$1 = c_mktsegment", [("$1", STRING)]),
    ("c_mktsegment <> $1", [("$1", STRING)]),
    ("o_orderdate < $1", [("$1", DATE)]),
    ("o_orderdate < $1 + interval '3' month", [("$1 + interval '3' month",
                                                DATE)]),
    ("l_discount between $1 - 0.01 and $1 + 0.01",
     [("$1 - 0.01", DECIMAL(2)), ("$1 + 0.01", DECIMAL(2))]),
    ("l_quantity < $1", [("$1", DECIMAL(2))]),
    ("o_shippriority = $1", [("$1", INT)]),
    ("l_extendedprice * (1 - $1) > 100", [("1 - $1", DECIMAL(2))]),
    # one `$n` beside two date columns is ONE slot; beside a date and a
    # decimal it is two, each of its column's type
    ("o_orderdate < $1 and l_shipdate > $1", [("$1", DATE)]),
    ("l_shipdate > $1 and l_quantity < $2 and l_discount > $2",
     [("$1", DATE), ("$2", DECIMAL(2))]),
], ids=lambda v: v if isinstance(v, str) else "")
def test_a_parameter_takes_its_type_from_the_operand_beside_it(
        tpch, where, want):
    sql = ("select count(*) from customer, orders, lineitem where "
           "c_custkey = o_custkey and l_orderkey = o_orderkey and " + where)
    values = ("1995-03-15", "0.05") if "$2" in where else (
        "BUILDING" if "mktsegment" in where else
        "1995-03-15" if "date" in where else "0.05"
        if "discount" in where or "price" in where else "3",)
    binder = Binder(tpch["catalog"], params=values)
    plan = binder.bind(parser.parse(sql))
    assert [(P_.render(s.node), s.ty) for s in binder.param_slots] == want
    assert [s.index for s in binder.param_slots] == list(range(len(want)))
    P_.evaluate(binder.param_slots, values)
    del plan


@pytest.mark.parametrize("sql", [
    "select $1 from customer",
    "select c_custkey from customer where $1 = $2",
    "select c_custkey from customer where c_mktsegment < $1",
    "select c_custkey from customer where c_mktsegment in ($1, $2)",
    "select c_custkey from customer where c_acctbal / $1 > 2",
    "select c_custkey from customer where $1 = 5",
])
def test_a_parameter_with_no_typed_operand_is_out_of_scope(tpch, sql):
    with pytest.raises((P_.ParamOutOfScope, BindError)):
        Binder(tpch["catalog"], params=("1", "2")).bind(parser.parse(sql))


def test_an_unbound_parameter_is_an_error(tpch, client):
    with pytest.raises(BindError, match="no values were bound"):
        Binder(tpch["catalog"]).bind(parser.parse(Q3))
    sql = ("select count(*) from customer where c_mktsegment = $1 "
           "and c_custkey < $2")
    rows, code = client.query(sql)          # simple protocol: no Bind
    assert rows == [] and code is not None
    rows, code = client.bound(sql, ("BUILDING",))   # $2 has no value
    assert rows == [] and code is not None
    assert client.bound(sql, ("BUILDING", "100"))[1] is None


@pytest.mark.parametrize("slot_ty,node,values,want", [
    (DATE, "$1 + interval '1' year", ("1996-02-29",), (9920, True)),
    (DATE, "$1 - interval '1' month", ("1995-03-31",), (9189, True)),
    (DATE, "$1 + 7", (datetime.date(1995, 3, 1),), (9197, True)),
    (DECIMAL(2), "$1 - 0.01", ("0.06",), (5, True)),
    (DECIMAL(2), "$1 + 0.01", (Decimal("0.09"),), (10, True)),
    (DECIMAL(2), "1 - $1", (0.25,), (75, True)),
    (DECIMAL(4), "$1 * 3", ("0.0125",), (375, True)),
    (INT, "$1 * 2 + 1", (20,), (41, True)),
    (INT, "-$1", ("7",), (-7, True)),
    (DATE, "$1", (None,), (0, False)),
    (DECIMAL(2), "$1 - 0.01", (None,), (0, False)),
])
def test_parameter_arithmetic_folds_exactly_on_the_host(
        slot_ty, node, values, want):
    ast = parser.parse(f"select a from t where b < {node}").where.right
    value, valid = P_.slot_value(P_.ParamSlot(0, ast, slot_ty), values)
    assert (int(value), bool(valid)) == want
    assert value.dtype == np.dtype(slot_ty.dtype)


@pytest.mark.parametrize("slot_ty,node,values", [
    (DECIMAL(2), "$1", ("0.055",)),        # not exact at the column's scale
    (DECIMAL(2), "$1 * 0.5", ("0.05",)),
    (INT, "$1", ("1.5",)),
    (DATE, "$1", ("yesterday",)),
    (DATE, "$1", (7,)),
    (INT, "$1", ("9223372036854775808",)),
])
def test_a_value_the_slot_cannot_hold_is_out_of_scope(slot_ty, node, values):
    ast = parser.parse(f"select a from t where b < {node}").where.right
    with pytest.raises(P_.ParamOutOfScope):
        P_.evaluate([P_.ParamSlot(0, ast, slot_ty)], values)


@pytest.mark.parametrize("text", ["0", "1", "-1", "0.06", "-0.06", "24",
                                  "10000", "123456.789", "0.0001",
                                  "99999999.99", "100000000"])
def test_binary_numeric_round_trips(text):
    assert P_.decode_binary(_numeric(Decimal(text)), 1700) == Decimal(text)


def test_the_generators_segments_are_the_loaders():
    assert list(tpch_qgen.SEGMENTS) == list(tpch_dbgen.SEGMENTS)
    assert len(set(tpch_qgen.corners(Q3_SPEC))) == 10
    assert len(set(tpch_qgen.corners(Q6_SPEC))) == 8
    # the same seed gives the same stream; the domain is the specification's
    assert _draws(Q3_SPEC, 50) == _draws(Q3_SPEC, 50)
    q3, q6 = _draws(Q3_SPEC, 2000), _draws(Q6_SPEC, 2000)
    assert len(set(q3)) == 155 and len(set(q6)) == 80
    assert min(d for _s, d in q3) == "1995-03-01"
    assert max(d for _s, d in q3) == "1995-03-31"
    assert {d for d, _x, _q in q6} == {f"{y}-01-01" for y in range(1993, 1998)}
    assert {x for _d, x, _q in q6} == {f"0.0{i}" for i in range(2, 10)}
    assert {q for _d, _x, q in q6} == {"24", "25"}


# ---------------- (b), (c) every binding: one program, the right rows -----

def _literal(sql, values, dates):
    out = sql
    for i, v in reversed(list(enumerate(values, 1))):
        out = out.replace(f"${i}", f"date '{v}'" if i in dates
                          else v if re.fullmatch(r"[\d.]+", v) else f"'{v}'")
    return out


@pytest.mark.parametrize("name,sql,bindings,kinds,dates,module", [
    ("q3", Q3, Q3_BINDINGS, ("text", "date"), {2}, tpch_q3_qgen),
    ("q6", Q6, Q6_BINDINGS, ("date", "numeric", "int8"), {1}, tpch_q6),
], ids=["q3", "q6"])
def test_every_binding_runs_the_one_program_and_answers_exactly(
        tpch, client, name, sql, bindings, kinds, dates, module):
    ref = module.Reference(tpch["data"], tpch["dicts"], {})
    literal = Client(tpch["pg"].addr, timeout=300.0)
    assert literal.query("set vectorize = tpu") == ([], None)
    col = stats.enable()
    textual = _counter("sql_bind_textual_total")
    as_data = _counter("sql_bind_params_total")
    try:
        first = None
        for i, values in enumerate(bindings):
            rows, code = client.bound(sql, values)
            assert code is None, (values, code)
            if first is None:
                first = len(_compiles)   # the one program is compiled now
            # (b) text and binary formats, the literal text, the reference
            assert client.bound(sql, values, kinds) == (rows, None), values
            oks, compared = ref.check([(values, [tuple(r) for r in rows])])
            assert oks == [True], (values, rows, compared)
            compiles = len(_compiles)
            assert literal.query(_literal(sql, values, dates)) == (
                rows, None), values
            if i:
                # a new literal text is a new program; a new binding not
                assert len(_compiles) > compiles
            _compiles[:] = _compiles[:compiles]
        # (c) no backend compile after the first binding's
        assert len(_compiles) == first
    finally:
        literal.close()
        stats.disable()
    prep = _prepared(tpch, sql)
    assert prep is not None and prep.slots
    runner = prep.op._fused_runner
    assert runner._takes_params and len(runner._exec_cache) == 1
    assert len([p for p in runner._progs.values() if p is not None]) \
        == len(runner._progs) <= 2       # one, or two after a flow restart
    # every program traced here (the one with parameters and a literal
    # text a binding) aggregated ONCE over its materialized input: Q6 over
    # lineitem's flat-unpacked chunks with no lax.scan (ISSUE 32); a PR
    # that moves it back under the chunk fold fails here, not on the chip.
    # Q3's aggregate reads the order its compacting join left, in place
    # (ISSUE 36), in every one of them; Q6's has no join under it
    assert "fused.agg_folded" not in col.stages
    taken, other = (("fused.agg_ordered", "fused.agg_materialized")
                    if name == "q3" else
                    ("fused.agg_materialized", "fused.agg_ordered"))
    assert col.stages[taken].events >= 1 and other not in col.stages
    assert col.stages["sql.prepared_hit"].events == 2 * len(bindings) - 1
    assert col.stages["sql.bind_params"].rows == 2 * len(bindings) * len(kinds)
    assert _counter("sql_bind_textual_total") == textual
    assert _counter("sql_bind_params_total") - as_data \
        == 2 * len(bindings) * len(kinds)
    # the control: the reference in float32 is not the answer
    oks, _ = ref.check([(bindings[0], ref.control_rows(bindings[0],
                                                       "float32"))])
    assert (name, oks) == (name, [False])


def test_the_references_agree_with_a_row_loop(tpch):
    """The shared, vectorised work of the references against a plain loop
    over the rows, at two bindings each."""
    d = tpch["data"]
    c, o, l = d["customer"], d["orders"], d["lineitem"]
    seg_of = dict(zip(c["c_custkey"].tolist(), c["c_mktsegment"].tolist()))
    order_of = {k: (dt, pr, seg_of.get(cu)) for k, dt, pr, cu in zip(
        o["o_orderkey"].tolist(), o["o_orderdate"].tolist(),
        o["o_shippriority"].tolist(), o["o_custkey"].tolist())}
    lines = list(zip(l["l_orderkey"].tolist(), l["l_shipdate"].tolist(),
                     l["l_extendedprice"].tolist(), l["l_discount"].tolist(),
                     l["l_quantity"].tolist()))
    days = lambda t: (datetime.date.fromisoformat(t)
                      - datetime.date(1970, 1, 1)).days
    q3 = tpch_q3_qgen.Reference(d, tpch["dicts"], {})
    for segment, date in (("BUILDING", "1995-03-15"),
                          ("HOUSEHOLD", "1995-03-31")):
        code, day = tpch["dicts"]["c_mktsegment"].index(segment), days(date)
        revenue = {}
        for key, ship, px, disc, _q in lines:
            dt, _pr, seg = order_of[key]
            if seg == code and dt < day and ship > day:
                revenue[key] = revenue.get(key, 0) + px * (100 - disc)
        want = sorted(revenue, key=lambda k: (-revenue[k], order_of[k][0]))
        assert q3.answer((segment, date)) == [
            (k, revenue[k], order_of[k][0], order_of[k][1])
            for k in want[:10]]
    q6 = tpch_q6.Reference(d, tpch["dicts"], {})
    for date, disc, qty in (("1994-01-01", "0.06", "24"),
                            ("1996-01-01", "0.09", "25")):
        lo, hi = days(date), days(str(int(date[:4]) + 1) + date[4:])
        x, q = int(Decimal(disc) * 100), int(qty) * 100
        want = sum(px * dc for _k, ship, px, dc, qy in lines
                   if lo <= ship < hi and x - 1 <= dc <= x + 1 and qy < q)
        assert q6.answer((date, disc, qty)) == want
    assert q6.answer(("2005-01-01", "0.06", "24")) is None


# ------------------------------------------- (d) a capacity overflows -----

RESTART = (
    "select o_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue "
    "from orders, lineitem where l_orderkey = o_orderkey "
    "and o_orderdate < $1 and l_shipdate > $2 "
    "group by o_orderkey order by revenue desc, o_orderkey limit 5")


def test_an_overflowing_binding_restarts_once_and_is_remembered(tpch):
    """The plan is sized at its first binding (a date that keeps next to
    no order: the join's Shrink gets the floor of 4,096 lanes); a binding
    that keeps every line overflows it, restarts the flow once, answers
    exactly, and leaves the widening on the statement's one tree: no later
    binding restarts."""
    # (a SET clears the catalog's shared prepared cache: both sessions
    # are set up before the statement is first planned)
    sess, literal = _session(tpch), _session(tpch)
    restarts = lambda: _counter("sql_flow_restarts_total")

    def run(*values):
        bound, text = sess.bind_params(RESTART, values)
        assert bound is not None and text == RESTART
        before = restarts()
        _kind, payload, _schema = sess.execute(text, params=bound)
        lit = _literal(RESTART, values, {1, 2})
        _k, want, _s = literal.execute(lit)
        for name in want:
            np.testing.assert_array_equal(payload[name], want[name], name)
        return restarts() - before, len(payload["o_orderkey"])

    assert run("1992-01-03", "1998-01-01")[0] == 0
    prep = _prepared(tpch, RESTART)
    shrinks = lambda: sorted(
        op.capacity for op in fused.walk_operators(prep.op)
        if isinstance(op, fused.ShrinkOp))
    assert shrinks() == [4096]
    n_restarts, n_rows = run("1998-12-31", "1992-01-01")
    assert (n_restarts, n_rows) == (1, 5)
    assert shrinks()[0] > 4096
    assert _prepared(tpch, RESTART) is prep          # the same entry
    assert run("1998-12-01", "1992-02-01")[0] == 0   # remembered
    assert run("1992-01-03", "1998-01-01")[0] == 0
    assert len(prep.op._fused_runner._progs) == 2


# ------------------------------------- (e) absent strings and NULL --------

def test_an_absent_string_matches_nothing_and_null_binds_as_null(client):
    assert client.bound(Q3, ("NO SUCH SEGMENT", "1995-03-15")) == ([], None)
    assert client.bound(Q3, (None, "1995-03-15")) == ([], None)
    assert client.bound(Q3, ("BUILDING", None)) == ([], None)
    assert client.bound(Q3, ("BUILDING", None), ("text", "date")) == (
        [], None)
    assert client.bound(Q6, ("1994-01-01", None, "24")) == ([(None,)], None)
    count = "select count(*) from customer where c_mktsegment <> $1"
    (everyone,), = client.query("select count(*) from customer")[0]
    assert client.bound(count, ("NO SUCH SEGMENT",)) == ([(everyone,)], None)
    assert client.bound(count, (None,)) == ([("0",)], None)
    building, = client.bound(count, ("BUILDING",))[0][0]
    assert 0 < int(building) < int(everyone)


def test_a_float_parameter_rides_as_its_bit_pattern(tpch, client):
    """The operand beside the parameter is a float32 expression (a decimal
    division), so the slot is FLOAT and its value's bits travel in the
    statement's one int64 argument vector."""
    sql = ("select count(*) from lineitem "
           "where l_extendedprice / l_quantity > $1")
    counts = []
    for value in ("1000.5", "1500.25", "-3", "99999"):
        want = client.query(sql.replace("$1", value))
        assert want[1] is None
        assert client.bound(sql, (value,)) == want
        counts.append(int(want[0][0][0]))
    assert counts[2] > counts[0] > counts[1] > counts[3] == 0
    prep = _prepared(tpch, sql)
    assert [s.ty.kind.value for s in prep.slots] == ["float"]
    assert len(prep.op._fused_runner._progs) == 1
    (args,) = P_.evaluate(prep.slots, ("1.5",))
    assert args.dtype == np.int64 and args.tolist() == [
        int(np.float32(1.5).view(np.int32)), 1]


# ------------------------------ outside the scope: bound as text, counted --

@pytest.mark.parametrize("sql,values,literal", [
    ("select c_custkey from customer where c_custkey < $1 "
     "order by c_custkey limit $2", ("10", "3"),
     "select c_custkey from customer where c_custkey < 10 "
     "order by c_custkey limit 3"),
    ("select count(*) from customer where c_mktsegment in ($1, $2)",
     ("BUILDING", "MACHINERY"),
     "select count(*) from customer where c_mktsegment in "
     "('BUILDING', 'MACHINERY')"),
    ("select count(*) from customer where c_mktsegment < $1", ("C",),
     "select count(*) from customer where c_mktsegment < 'C'"),
    # in scope, but not this value: 0.055 is not a DECIMAL(2)
    ("select count(*) from lineitem where l_discount < $1", ("0.055",),
     "select count(*) from lineitem where l_discount < 0.055"),
])
def test_a_statement_outside_the_scope_is_bound_as_text(client, sql, values,
                                                        literal):
    textual = _counter("sql_bind_textual_total")
    want = client.query(literal)
    assert want[1] is None
    assert client.bound(sql, values) == want
    assert client.bound(sql, values) == want
    assert _counter("sql_bind_textual_total") == textual + 2


def test_a_param_outside_a_program_that_takes_it_refuses(tpch):
    """The streaming operators' own jits take no bound values: a Param
    there raises instead of tracing this binding in, and the session
    answers the statement as bound text."""
    sess = _session(tpch)
    values = ("BUILDING", "1995-03-15")
    bound, text = sess.bind_params(Q3, values)
    _k, want, _s = sess.execute(text, params=bound)
    prep = _prepared(tpch, Q3)
    maps = [op for op in fused.walk_operators(prep.op)
            if isinstance(op, fused.MapOp)
            and has_params([p for _k, p in op.steps])]
    assert len(maps) == 3
    with pytest.raises(ParamOutsideProgram):
        next(iter(maps[0].batches()))
    textual = _counter("sql_bind_textual_total")
    runner = prep.op._fused_runner
    try:
        prep.op._fused_runner = None
        fused_try, fused.try_compile = fused.try_compile, lambda op: None
        _k, got, _s = sess.execute(Q3, params=P_.BoundParams(values))
    finally:
        fused.try_compile = fused_try
        prep.op._fused_runner = runner
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], name)
    assert _counter("sql_bind_textual_total") == textual + 1


# --------------------------------------------- EXPLAIN, spans, counters ---

def test_explain_prints_the_parameters_and_the_binding(client):
    rows, code = client.bound("explain " + Q3, ("BUILDING", "1995-03-15"))
    assert code is None
    lines = [r[0] for r in rows]
    assert "parameters: $1 string(code), $2 date" in lines
    assert "estimates taken at: $1 = 'BUILDING', $2 = '1995-03-15'" in lines
    rows, _ = client.bound("explain " + Q6, ("1994-01-01", "0.06", "24"))
    assert ("parameters: $1 date, $1 + interval '1' year date, "
            "$2 - 0.01 decimal(2), $2 + 0.01 decimal(2), "
            "$3 decimal(2)") in [r[0] for r in rows]


def test_the_bound_statement_is_one_trace_with_its_params_tagged(tpch):
    from cockroach_tpu.util import tracing

    sess = _session(tpch)
    bound, text = sess.bind_params(Q3, ("MACHINERY", "1995-03-09"))
    with tracing.tracer().span("test.root") as root:
        sess.execute(text, params=bound)
    (span,) = [s for s in root.children if s.name == "session.execute"]
    assert span.tags["params"] == 2 and span.tags["tier"] == "fused"


# ------------------------------- (f) the literal texts' programs stand ----

_LOC = re.compile(r"\s*loc\([^)]*\)|^#loc.*$", re.M)
# sha256 (first 16 hex digits) of the StableHLO text with `loc` stripped,
# of the accepted cells' literal statements at SF 0.01, seed 7, capacity
# 131,072, `vectorize = tpu`, CPU. Both are PR 43's, which changed every
# sort but ORDER BY's on purpose (no stable sort: tests/
# test_sort_sites.py). Q1's moved with `Batch.compact` alone, which packs
# its 12 result lanes by one u32 sort where a `(pred, i32)` argsort stood
# (43752e10756b36c7 from before PR 31 until then: a PR that leaves it
# alone loads the cache entry its parent compiled); Q3's with its joins'
# key sorts (6f17850c22770fad from PR 36, 3a343d0cc3806b81 from PR 34),
# and again with PR 49, whose compacting joins find their runs' build
# lanes with one 32-bit scan (8b40d3229b955834 from PR 43 until then;
# Q1 has no join and kept PR 43's): a PR that moves it recompiles both
# Q3 cells and measures them.
LITERAL_PROGRAMS = {"tpch-sf1.q1-2streams": "33fe0d227358292c",
                    "tpch-sf1.q3-1stream": "abce5d0fc0c95e03"}


@pytest.mark.parametrize("cell", sorted(LITERAL_PROGRAMS))
def test_a_literal_statements_stablehlo_is_unchanged(cell, monkeypatch):
    stmt = manifest.cell(cell)["statements"][0]
    loaded = tpch_loader.load(MVCCStore(), {"sf": 0.01}, stmt["tables"], 7)
    texts = []
    lower = fused.lower_program

    def recording(fn, args):
        lowered = lower(fn, args)
        texts.append(lowered.as_text())
        return lowered

    monkeypatch.setattr(fused, "lower_program", recording)
    sess = Session(loaded["catalog"], capacity=131072)
    sess.execute("set vectorize = tpu")
    sess.execute(stmt["sql"])
    assert [hashlib.sha256(_LOC.sub("", t).encode()).hexdigest()[:16]
            for t in texts] == [LITERAL_PROGRAMS[cell]]
    prep = next(iter(sess._prepared.values()))
    assert not prep.slots and not prep.op._fused_runner._takes_params
