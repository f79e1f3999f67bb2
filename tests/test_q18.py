"""TPC-H Q18 as a served deployment (ISSUE 33): the large-state
aggregation under HAVING with QGEN's QUANTITY bound as data and customer
names as the specification writes them.

The system (pgwire's extended protocol -> Session -> compile_plan ->
FusedRunner) against the benchmark's plain reference
(benchmark/reference/tpch_q18.py) at SF 0.01 on the CPU: through Session
and through the wire, on three seeds, at bindings with many rows, few and
none, and at a tie on both sort keys at the cut; the typed HAVING slot
(one program for four bindings); c_name through scan image, GROUP BY and
wire with a dictionary over 65,535 entries; the int-key lowering and the
sorted lanes counted with the lanes written out.
"""

import datetime
from decimal import Decimal

import jax
import numpy as np
import pytest

from benchmark import manifest, wire
from benchmark.loaders import tpch_cname
from benchmark.paramgen import tpch_qgen_q18
from benchmark.reference import tpch_q18
from cockroach_tpu.coldata.batch import Kind
from cockroach_tpu.exec import fused, stats
from cockroach_tpu.exec.operators import JoinOp, ScanOp, walk_operators
from cockroach_tpu.ops import expr as expr_mod
from cockroach_tpu.sql import params as P_
from cockroach_tpu.sql import parser
from cockroach_tpu.sql.bind import Binder
from cockroach_tpu.sql.pgwire import PgServer
from cockroach_tpu.sql.session import Session
from cockroach_tpu.storage.mvcc import MVCCStore
from cockroach_tpu.util.metric import default_registry

CELL = "tpch-sf1-q18.q18-1stream"
CAP = 1 << 17          # the configuration's: one chunk a table at SF 0.01
TABLES = ["lineitem", "orders", "customer"]
Q18 = manifest.cell(CELL)["statements"][0]["sql"]
Q3 = manifest.cell("tpch-sf1.q3-1stream")["statements"][0]["sql"]
_EPOCH = datetime.date(1970, 1, 1)


def _counter(name):
    return default_registry().counter(name).value()


def _serve(gen):
    loaded = tpch_cname.load_from(gen, MVCCStore(), TABLES)
    loaded["pg"] = PgServer(loaded["catalog"], capacity=CAP).start()
    loaded["ref"] = tpch_q18.Reference(loaded["data"], loaded["dicts"], {})
    return loaded


def _session(loaded):
    s = Session(loaded["catalog"], capacity=CAP)
    s.execute("set vectorize = tpu")
    return s


def _client(loaded):
    c = wire.WireClient(loaded["pg"].addr, timeout=300.0)
    assert c.query("set vectorize = tpu") == ([], None)
    return c


def _as_wire(payload, names):
    """A Session payload as the text rows pgwire renders."""
    n = len(payload["c_custkey"])
    return [(names[payload["c_name"][i]], str(payload["c_custkey"][i]),
             str(payload["o_orderkey"][i]),
             (_EPOCH + datetime.timedelta(
                 days=int(payload["o_orderdate"][i]))).isoformat(),
             str(Decimal(int(payload["o_totalprice"][i])).scaleb(-2)),
             str(Decimal(int(payload["sum"][i])).scaleb(-2)))
            for i in range(n)]


def _bindings(ref):
    """QUANTITY with many rows (over the LIMIT), with a few, with none."""
    top = int(ref.qty.max()) // 100
    many = next(q for q in range(top, 0, -1)
                if len(ref.answer((str(q),))) > 100)
    few = next(q for q in range(top, 0, -1)
               if 0 < len(ref.answer((str(q),))))
    assert 0 < len(ref.answer((str(few),))) < 100
    return [(str(many),), (str(few),), (str(top),)]


# ------------------------------- the system against the plain reference ---

@pytest.fixture(scope="module", params=[7, 2147483999, 3300000001])
def served(request):
    loaded = _serve(tpch_cname.TPCHCName(sf=0.01, seed=request.param))
    yield loaded
    loaded["pg"].close()


def test_q18_is_exact_through_session_and_through_the_wire(served):
    ref = served["ref"]
    sess, client = _session(served), _client(served)
    textual = _counter("sql_bind_textual_total")
    try:
        sizes = []
        for values in _bindings(ref):
            bound, text = sess.bind_params(Q18, values)
            assert isinstance(bound, P_.BoundParams) and text == Q18
            _kind, payload, _schema = sess.execute(text, params=bound)
            rows, code = client.query_extended(Q18, values)
            assert code is None, (values, code)
            got = [tuple(r) for r in rows]
            for answer in (_as_wire(payload, served["dicts"]["c_name"]),
                           got):
                oks, compared = ref.check([(values, answer)])
                assert oks == [True], (values, compared, answer[:3])
            assert all(r[0] == f"Customer#{int(r[1]):09d}" for r in got)
            sizes.append(len(got))
        assert sizes[0] == 100 and 0 < sizes[1] < 100 and sizes[2] == 0
    finally:
        client.close()
    assert _counter("sql_bind_textual_total") == textual


def test_the_float32_control_is_not_the_answer(served):
    ref = served["ref"]
    values = _bindings(ref)[0]
    rows = ref.control_rows(values, "float32")
    assert len(rows) == 100
    oks, compared = ref.check([(values, rows)])
    assert oks == [False]
    # by o_totalprice (cents pass 2^24), never by the quantity sums
    assert {c["name"]: c["ok"] for c in compared} == {
        "rows_missing_or_extra": True, "cells_mismatched": False}
    exact = ref.control_rows(values, None)
    assert [r[5] for r in rows] == [r[5] for r in exact]
    assert ref.check([(values, exact)])[0] == [True]


class _Tied(tpch_cname.TPCHCName):
    """Orders whose sort keys (o_totalprice, o_orderdate) take 7 x 3
    values: every row of Q18's answer ties with dozens, at the cut too."""

    def rows(self, name, lo, hi):
        out = super().rows(name, lo, hi)
        if name == "orders":
            r = np.arange(lo, hi, dtype=np.int64)
            out["o_totalprice"] = 1000 * (r % 7) + 500
            out["o_orderdate"] = (9000 + r % 3).astype(np.int32)
        return out


def test_rows_that_tie_on_both_sort_keys_at_the_cut_are_a_set():
    loaded = _serve(_Tied(sf=0.01, seed=7))
    try:
        ref = loaded["ref"]
        values = _bindings(ref)[0]
        passing = ref.answer(values)
        cut = (passing[99][4], passing[99][3])
        tied_at_cut = [w for w in passing if (w[4], w[3]) == cut]
        assert (passing[100][4], passing[100][3]) == cut \
            and len(tied_at_cut) > 2
        client = _client(loaded)
        try:
            rows, code = client.query_extended(Q18, values)
        finally:
            client.close()
        got = [tuple(r) for r in rows]
        assert code is None and len(got) == 100
        assert ref.check([(values, got)])[0] == [True]
        # any of the tied rows may stand at the cut ...
        last = next(w for w in tied_at_cut
                    if str(w[2]) not in {r[2] for r in got})
        swapped = got[:99] + [(
            last[0], str(last[1]), str(last[2]),
            (_EPOCH + datetime.timedelta(days=last[3])).isoformat(),
            str(Decimal(last[4]).scaleb(-2)),
            str(Decimal(last[5]).scaleb(-2)))]
        assert ref.check([(values, swapped)])[0] == [True]
        # ... a row that does not tie there may not, nor one row twice,
        # nor the rows out of the statement's order
        assert ref.check([(values, got[:99] + [got[0]])])[0] == [False]
        assert ref.check([(values, got[1:] + [got[0]])])[0] == [False]
        assert ref.check([(values, got[:99])])[0] == [False]
    finally:
        loaded["pg"].close()


# ------------------------------------------ the typed HAVING parameter ---

def test_a_parameter_beside_an_aggregates_result_is_typed_from_it(served):
    binder = Binder(served["catalog"], params=("312",))
    binder.bind(parser.parse(Q18))
    (slot,) = binder.param_slots
    assert (slot.ty.kind, slot.ty.scale) == (Kind.DECIMAL, 2)
    assert slot.describe() == "$1 decimal(2)"
    # '312' is 31200 exactly at the sum's scale; no float on the way
    assert P_.evaluate([slot], ("312",))[0].tolist() == [31200, 1]
    assert P_.evaluate([slot], ("312.25",))[0].tolist() == [31225, 1]
    with pytest.raises(P_.ValueOutOfScope):
        P_.evaluate([slot], ("312.005",))


def test_four_bindings_run_one_program(served, monkeypatch):
    texts = []
    lower = fused.lower_program

    def recording(fn, args):
        lowered = lower(fn, args)
        texts.append(lowered.as_text())
        return lowered

    monkeypatch.setattr(fused, "lower_program", recording)
    sess = _session(served)
    # a session of its own prepared cache: the entry is made here
    sess._prepared = type(sess._prepared)()
    as_data = _counter("sql_bind_params_total")
    textual = _counter("sql_bind_textual_total")
    col = stats.enable()
    try:
        lowered_after_first = None
        for q in ("312", "313", "314", "315"):
            bound, text = sess.bind_params(Q18, (q,))
            assert isinstance(bound, P_.BoundParams) and text == Q18
            sess.execute(text, params=bound)
            if lowered_after_first is None:
                lowered_after_first = len(texts)
        restarts = col.stages["flow.restart"].events \
            if "flow.restart" in col.stages else 0
    finally:
        stats.disable()
    # the first binding lowers the statement's ONE program (no guard
    # restarts it: the last join's build columns, 91 bits at SF1, ride
    # no sort since ISSUE 34); the other three lower nothing
    assert len(texts) == lowered_after_first == 1
    assert all("312" not in t.split("main")[0] for t in texts)
    prep = sess._prepared.get(Q18)
    assert prep is not None and len(prep.slots) == 1
    runner = prep.op._fused_runner
    assert runner._takes_params
    assert len([p for p in runner._progs.values() if p]) == len(texts)
    assert _counter("sql_bind_params_total") == as_data + 4
    assert _counter("sql_bind_textual_total") == textual
    assert col.stages["sql.prepared_hit"].events == 3
    assert restarts == 0
    assert col.stages["fused.join_compact"].events == 2
    assert [j.build_mode for j in walk_operators(prep.op)
            if isinstance(j, JoinOp)] == ["unique"] * 3


def test_q18_program_sorts_per_join(served, monkeypatch):
    """Q18's served program at SF 0.01 (tests/test_fused's
    test_q3_program_sorts_per_join, for this statement): BOTH joins under
    a Shrink lower with it, the semi join and the lineitem join whose
    build carries o_custkey, o_totalprice, o_orderdate, c_custkey and
    c_name (no u64 holds them at SF1; the row index rides the sort
    instead). Per compacted join: the key sort and the compaction's
    one-operand sort at lcap + rcap lanes, no resort by destination, ONE
    cummax (the row index under the run id), and no sort of its own at
    lcap: the sorts at 131,072 lanes are the int-key aggregate's (key,
    packed inputs), its compaction of the run ends (at this scale; SF1
    hands on the uncompacted view) and the HAVING's Shrink, one u32
    operand each (`(u32, i32)` and a `(pred, i32)` argsort until PR
    43). The customer
    join has no Shrink above it and resorts, with the split cummax. No
    sort is stable (a signature's last member)."""
    from tests.test_fused import (
        _cummaxes, _head_scans, _record_joins, _sorts,
    )

    sess = _session(served)
    sess._prepared = type(sess._prepared)()
    bound, text = sess.bind_params(Q18, ("312",))
    sess.execute(text, params=bound)
    prep = sess._prepared.get(Q18)
    runner = prep.op._fused_runner
    _entry, args = runner._prepare()
    compacted, two_step = _record_joins(monkeypatch)
    scans = [n for n in walk_operators(prep.op) if isinstance(n, ScanOp)]
    prog, _box = runner._make_prog([id(sc) for sc in scans])
    col = stats.enable()
    try:
        jaxpr = jax.make_jaxpr(prog)(
            *args, *P_.evaluate(prep.slots, ("312",)))
    finally:
        stats.disable()
    assert col.stages["fused.join_compact"].events == 2
    assert compacted == [(CAP, 4096, "semi"), (CAP, 16384, "inner")]
    assert two_step == [(CAP, CAP, "inner")]
    sorts = _sorts(jaxpr.jaxpr)
    cummaxes = _cummaxes(jaxpr.jaxpr)
    for lcap, rcap, _how in compacted:
        at_n = sorted(s[1:] for s in sorts if s[0] == lcap + rcap)
        assert at_n == [("uint32", 1, False), ("uint32", 2, False)]
        assert cummaxes.count(lcap + rcap) == 0   # no 64-bit scan (PR 49)
        assert _head_scans(jaxpr.jaxpr, lcap + rcap) == 1
    assert sorted(s[1:] for s in sorts if s[0] == 2 * CAP) == [
        ("int32", 2, False), ("uint32", 2, False)]
    assert cummaxes.count(2 * CAP) == 2
    assert sorted(s[1:] for s in sorts if s[0] == CAP) == [
        ("uint32", 1, False), ("uint32", 1, False), ("uint32", 2, False)]
    assert not [s for s in sorts if s[1] == "bool"]


@pytest.mark.parametrize("value", ["312.005", "many"])
def test_a_value_the_sums_scale_cannot_hold_is_bound_as_text(served, value):
    sess = _session(served)
    textual = _counter("sql_bind_textual_total")
    bound, text = sess.bind_params(Q18, (value,))
    assert bound is None and "$1" not in text and value in text
    assert _counter("sql_bind_textual_total") == textual + 1
    # the next binding is typed again
    assert isinstance(sess.bind_params(Q18, ("313",))[0], P_.BoundParams)


def test_explain_prints_the_having_parameter_and_the_lowerings(served):
    client = _client(served)
    try:
        rows, code = client.query_extended("explain " + Q18, ("312",))
        assert code is None
        lines = [r[0] for r in rows]
        assert "parameters: $1 decimal(2)" in lines
        assert "estimates taken at: $1 = 312" in lines
        rows, code = client.query_extended("explain analyze " + Q18,
                                           ("312",))
        assert code is None
        table = {ln.split()[0]: int(ln.split()[ln.split().index("ev") - 1])
                 for (ln,) in rows
                 if ln.startswith("fused.") and "ev" in ln.split()}
    finally:
        client.close()
    # a traced program: one int-key aggregate (the HAVING's, over the
    # scan) and one in place over the last join's key order (ISSUE 36)
    traced = table.get("fused.compile", 0)
    if traced:
        assert table["fused.agg_int_key"] == traced
        assert table["fused.agg_ordered"] == traced
        assert "fused.agg_materialized" not in table
        assert "fused.agg_folded" not in table
    assert table["fused.sort_lanes"] == table["fused.exec"]


# ------------------------------------ the lowerings and the sorted lanes ---

@pytest.mark.parametrize("sql,values,lanes,int_key,ordered", [
    # the int-key aggregate's input, orders + customer, orders + the
    # HAVING's Shrink (4,096), lineitem + the semi join's Shrink (16,384);
    # the last aggregate (five keys: the last join's key and four columns
    # of its build) reads that join's order and sorts nothing
    (Q18, ("312",), [131072, 131072 + 131072, 131072 + 4096,
                     131072 + 16384], 1, 1),
    # lineitem + the orders Shrink (4,096 at SF 0.01), orders + customer
    (Q3, None, [131072 + 4096, 131072 + 131072], 0, 1),
], ids=["q18", "q3"])
def test_the_aggregates_lowering_and_the_sorted_lanes_are_counted(
        served, sql, values, lanes, int_key, ordered):
    sess = _session(served)
    sess._prepared = type(sess._prepared)()
    col = stats.enable()
    try:
        for _ in range(2):
            if values is None:
                sess.execute(sql)
            else:
                bound, text = sess.bind_params(sql, values)
                sess.execute(text, params=bound)
    finally:
        stats.disable()
    traced = col.stages["fused.compile"].events
    counted = {n: col.stages[n].events if n in col.stages else 0
               for n in ("fused.agg_int_key", "fused.agg_ordered",
                         "fused.agg_materialized", "fused.agg_folded")}
    assert counted == {"fused.agg_int_key": int_key * traced,
                       "fused.agg_ordered": ordered * traced,
                       "fused.agg_materialized": 0,
                       "fused.agg_folded": 0}
    # one event a dispatch, the program's lanes at each
    sort = col.stages["fused.sort_lanes"]
    assert sort.events == col.stages["fused.exec"].events
    assert sort.rows == sum(lanes) * sort.events


# ------------------------------------- c_name as the specification's ---

def test_c_name_round_trips_with_a_dictionary_over_65535_entries():
    gen = tpch_cname.TPCHCName(sf=0.5, seed=7)
    loaded = tpch_cname.load_from(gen, MVCCStore(), ["customer"])
    schema = loaded["catalog"].table_schema("customer")
    assert len(schema.dictionary("c_name")) == 75000
    assert schema.field("c_name").wire == "i4"
    assert tpch_cname.stored_width("customer", "c_name") == 4
    assert tpch_cname.stored_width("customer", "c_custkey") == 4
    pg = PgServer(loaded["catalog"], capacity=CAP).start()
    client = wire.WireClient(pg.addr, timeout=300.0)
    try:
        assert client.query("set vectorize = tpu") == ([], None)
        rows, code = client.query(
            "select c_custkey, c_name from customer "
            "where c_custkey > 74995 order by c_custkey")
        assert code is None
        assert [tuple(r) for r in rows] == [
            (str(k), f"Customer#{k:09d}") for k in range(74996, 75001)]
        rows, code = client.query(
            "select c_name, count(*) from customer "
            "where c_custkey between 65530 and 65541 group by c_name")
        assert code is None
        assert sorted(tuple(r) for r in rows) == [
            (f"Customer#{k:09d}", "1") for k in range(65530, 65542)]
        rows, code = client.query_extended(
            "select c_custkey from customer where c_name = $1",
            ("Customer#000070001",))
        assert ([tuple(r) for r in rows], code) == ([("70001",)], None)
    finally:
        client.close()
        pg.close()


def test_a_strings_code_is_one_probe_of_an_index_built_once():
    class _Schema:
        def __init__(self, d):
            self.d = d

        def dictionary(self, col):
            return self.d

    d = np.asarray(["b", "a", "c", "a", "Customer#000000009"], dtype=object)
    schema = _Schema(d)
    for s in ("a", "b", "c", "Customer#000000009", "absent", ""):
        hits = np.nonzero(d == s)[0]          # the linear search it was
        assert expr_mod._string_code(schema, "x", s) == (
            int(hits[0]) if len(hits) else -1)
    assert expr_mod._code_index(d) is expr_mod._code_index(d)
    with pytest.raises(ValueError):
        expr_mod._string_code(_Schema(None), "x", "a")
    key = id(d)
    del schema, d
    assert key not in expr_mod._CODE_INDEX


# ---------------------------------------- the parameter stream, manifest ---

def test_the_parameter_stream_draws_qgens_quantity():
    spec = {"kind": "tpch_qgen_q18"}
    state = tpch_qgen_q18.prepare(spec)
    draws = tpch_qgen_q18.draw(spec, np.random.default_rng([7, 0]), 400,
                               state)
    assert set(draws) == {("312",), ("313",), ("314",), ("315",)}
    assert min(draws.count(v) for v in set(draws)) > 60
    again = tpch_qgen_q18.draw(spec, np.random.default_rng([7, 0]), 400,
                               state)
    other = tpch_qgen_q18.draw(spec, np.random.default_rng([7, 1]), 400,
                               state)
    assert again == draws and other != draws
    assert tpch_qgen_q18.corners(spec) == [("312",), ("315",)]
    # the rehearsal's range comes from the configuration, not the cell
    cfg = manifest.config("tpch-sf1-q18")
    rehearsal = dict(spec, **cfg["rehearse"]["params"])
    assert tpch_qgen_q18.corners(rehearsal) == [("250",), ("253",)]
    assert set(tpch_qgen_q18.draw(
        rehearsal, np.random.default_rng(1), 200,
        tpch_qgen_q18.prepare(rehearsal))) == {
            ("250",), ("251",), ("252",), ("253",)}
    with pytest.raises(ValueError):
        tpch_qgen_q18.prepare({"quantity": [5, 4]})


def test_the_manifest_holds_the_new_entries():
    bench = manifest.benchmark()
    assert manifest.validate(bench) == []
    entry = manifest.entry(bench, CELL)
    assert (entry["config"], entry["chips"]) == ("tpch-sf1-q18", 1)
    cfg, cell = manifest.config("tpch-sf1-q18"), manifest.cell(CELL)
    assert cfg["loader"]["name"] == "tpch_cname"
    assert cfg["warmup"] == ["qgen_domain"] and cfg["capacity"] == CAP
    assert "substitution_parameters" not in cfg["reduced"]
    (stmt,) = cell["statements"]
    assert stmt["protocol"] == "extended" and "> $1" in stmt["sql"]
    assert stmt["params"] == {"kind": "tpch_qgen_q18"}
    assert cell["traffic_params"] == {"clients": 1, "warmup_per_client": 3}
    reported = {m["name"] for m in manifest.metrics_for(bench, CELL,
                                                        "per_layer")}
    assert {"sort_lanes_m", "stmt_program_roofline", "bind_ms",
            "fused_wait_ms", "device_idle_pct",
            "window_restarts"} <= reported
    (lanes,) = [m for m in bench["per_layer"] if m["name"] == "sort_lanes_m"]
    assert lanes["workloads"][:3] == [CELL, "tpch-sf1.q3-1stream",
                                      "tpch-sf1-qgen.q3-1stream"]
    # 6 + 14 + 8 bytes a row of the three images: about 58 MB at SF1
    from benchmark import bytes_model

    assert bytes_model.statement_bytes(
        stmt, tpch_cname, {"lineitem": 6_000_000, "orders": 1_500_000,
                           "customer": 150_000}) == 58_200_000
