"""TPC-H Q9 as a served deployment (ISSUE 37): the six-table join in the
specification's own text (a derived table in FROM), QGEN's COLOR bound as
data under LIKE, part names as the specification writes them.

The system (pgwire's extended protocol -> Session -> compile_plan ->
FusedRunner) against the benchmark's plain reference
(benchmark/reference/tpch_q9.py) at SF 0.01 on the CPU: through Session
and through the wire, on three seeds, at five colours, at a pattern
nothing matches, at `%` and at a NULL binding; the pattern slot (one
prepared entry and one program over the bindings, the table made once a
Bind); the derived table merged into the flattened text's plan; the
loader's names; the join key packings counted with the hashed lanes
written out.
"""

import re
from decimal import Decimal

import numpy as np
import pytest

from benchmark import manifest
from benchmark.layer_metrics import hash_key_lanes_m
from benchmark.loaders import tpch as tpch_loader
from benchmark.loaders import tpch_cname, tpch_dbgen, tpch_pname
from benchmark.paramgen import tpch_qgen_q9
from benchmark.reference import tpch_q9
from cockroach_tpu.exec import fused, stats
from cockroach_tpu.exec.operators import JoinOp, ShrinkOp, walk_operators
from cockroach_tpu.ops import expr as expr_mod
from cockroach_tpu.sql import params as P_
from cockroach_tpu.sql import parser
from cockroach_tpu.sql.bind import BindError, Binder
from cockroach_tpu.sql.pgwire import PgServer
from cockroach_tpu.sql.plan import normalize
from cockroach_tpu.sql.session import Session
from cockroach_tpu.storage.mvcc import MVCCStore
from cockroach_tpu.util.metric import default_registry
from tests.test_params import Client

CELL = "tpch-sf1-q9.q9-1stream"
CAP = 1 << 17          # the configuration's: one chunk a table at SF 0.01
STMT = manifest.cell(CELL)["statements"][0]
TABLES = STMT["tables"]
Q9 = STMT["sql"]
Q3 = manifest.cell("tpch-sf1.q3-1stream")["statements"][0]["sql"]
# the text tests/test_sql.py runs: the derived table written out by hand
Q9_FLAT = " ".join("""
    select n_name as nation, extract(year from o_orderdate) as o_year,
           sum(l_extendedprice * (1 - l_discount)
               - ps_supplycost * l_quantity) as sum_profit
    from part, supplier, lineitem, partsupp, orders, nation
    where s_suppkey = l_suppkey and ps_suppkey = l_suppkey
      and ps_partkey = l_partkey and p_partkey = l_partkey
      and o_orderkey = l_orderkey and s_nationkey = n_nationkey
      and p_name like $1
    group by nation, o_year order by nation, o_year desc""".split())
COLOURS = ["%green%", "%almond%", "%yellow%", "%hot%", "%navajo%"]
PATTERNS = COLOURS + ["%nothing%", "%", None]


def _counter(name):
    return default_registry().counter(name).value()


def _serve(gen, tables=TABLES):
    loaded = tpch_cname.load_from(gen, MVCCStore(), tables)
    loaded["pg"] = PgServer(loaded["catalog"], capacity=CAP).start()
    loaded["ref"] = tpch_q9.Reference(loaded["data"], loaded["dicts"], {})
    return loaded


def _session(loaded, own_cache=False):
    s = Session(loaded["catalog"], capacity=CAP)
    s.execute("set vectorize = tpu")
    if own_cache:    # the entry is made here, whatever ran before
        s._prepared = type(s._prepared)()
    return s


def _client(loaded):
    c = Client(loaded["pg"].addr, timeout=300.0)
    assert c.query("set vectorize = tpu") == ([], None)
    return c


def _as_wire(payload, nations):
    """A Session payload as the text rows pgwire renders."""
    return [(nations[payload["nation"][i]], str(payload["o_year"][i]),
             str(Decimal(int(payload["sum_profit"][i])).scaleb(-4)))
            for i in range(len(payload["o_year"]))]


def _run(sess, sql, values):
    bound, text = sess.bind_params(sql, values)
    assert isinstance(bound, P_.BoundParams) and text == sql
    return sess.execute(text, params=bound)[1]


# ------------------------------- the system against the plain reference ---

@pytest.fixture(scope="module", params=[7, 2147483999, 3300000001])
def served(request):
    loaded = _serve(tpch_pname.TPCHPName(sf=0.01, seed=request.param))
    yield loaded
    loaded["pg"].close()


@pytest.fixture(scope="module")
def one():
    """One seed, with customer beside Q9's six tables (Q3 runs here too)."""
    loaded = _serve(tpch_pname.TPCHPName(sf=0.01, seed=7),
                    TABLES + ["customer"])
    yield loaded
    loaded["pg"].close()


@pytest.fixture
def lowered_texts(monkeypatch):
    """The text of every program exec/fused lowers during the test."""
    texts = []
    lower = fused.lower_program

    def recording(fn, args):
        lowered = lower(fn, args)
        texts.append(lowered.as_text())
        return lowered

    monkeypatch.setattr(fused, "lower_program", recording)
    return texts


@pytest.mark.parametrize("pattern", PATTERNS)
def test_q9_is_exact_through_session_and_through_the_wire(served, pattern):
    ref = served["ref"]
    sess, client = _session(served), _client(served)
    textual = _counter("sql_bind_textual_total")
    try:
        values = (pattern,)
        payload = _run(sess, Q9, values)
        rows, code = client.bound(Q9, values)
        assert code is None, (values, code)
        got = [tuple(r) for r in rows]
        for answer in (_as_wire(payload, served["dicts"]["n_name"]), got):
            oks, compared = ref.check([(values, answer)])
            assert oks == [True], (values, compared, answer[:3])
        hits = sum(tpch_q9.matcher(pattern)(n) for n in ref.names)
        assert (len(got) == 0) == (hits == 0)
        if pattern in COLOURS:
            assert 60 < hits < 160 and len(got) > 100
        if pattern == "%":
            assert hits == len(ref.names)
    finally:
        client.close()
    assert _counter("sql_bind_textual_total") == textual


def test_the_float32_control_is_not_the_answer(served):
    ref = served["ref"]
    values = ("%green%",)
    rows = ref.control_rows(values, "float32")
    oks, compared = ref.check([(values, rows)])
    assert oks == [False]
    # by sum_profit (a group's profit passes 2^24 ten-thousandths), never
    # by the groups themselves
    assert {c["name"]: c["ok"] for c in compared} == {
        "rows_missing_or_extra": True, "cells_mismatched": False}
    exact = ref.control_rows(values, None)
    assert [r[:2] for r in rows] == [r[:2] for r in exact]
    assert ref.check([(values, exact)])[0] == [True]
    assert ref.check([(values, exact[1:])])[0] == [False]
    assert ref.check([(values, exact[1:] + exact[:1])])[0] == [False]


@pytest.mark.parametrize("pattern,covered", [
    ("%", True), ("%%", True), ("%green%", True), ("green%", True),
    ("%green", True), ("green", True), (None, True),
    ("%gr_en%", False), ("%green%blue%", False), ("_", False)])
def test_the_reference_says_which_patterns_it_covers(pattern, covered):
    if not covered:
        with pytest.raises(ValueError):
            tpch_q9.matcher(pattern)
        return
    passes = tpch_q9.matcher(pattern)
    names = ["green", "dark green lace", "lace green", "green lace", ""]
    rx = None if pattern is None else re.compile(
        expr_mod._like_to_regex(pattern), re.S)
    assert [passes(n) for n in names] == [
        rx is not None and rx.fullmatch(n) is not None for n in names]


# ------------------------------------------------- the pattern as data ---

def test_bindings_share_one_prepared_entry_and_one_program(one,
                                                           lowered_texts):
    texts = lowered_texts
    sess = _session(one, own_cache=True)
    as_data = _counter("sql_bind_params_total")
    textual = _counter("sql_bind_textual_total")
    bindings = COLOURS + ["%nothing%", None]
    col = stats.enable()
    try:
        for pattern in bindings:
            _run(sess, Q9, (pattern,))
        restarts = col.stages["flow.restart"].events \
            if "flow.restart" in col.stages else 0
    finally:
        stats.disable()
    # the first binding lowers the statement's ONE program; no pattern is
    # in it, and no other binding lowers anything
    assert len(texts) == 1 and "green" not in texts[0]
    prep = sess._prepared.get(Q9)
    assert prep is not None and len(prep.slots) == 1
    (slot,) = prep.slots
    assert (slot.table, slot.column, slot.relation) == (0, "p_name", "part")
    assert slot.describe() == "$1 pattern(part.p_name)"
    runner = prep.op._fused_runner
    assert runner._takes_params
    assert len([p for p in runner._progs.values() if p]) == 1
    assert _counter("sql_bind_params_total") == as_data + len(bindings)
    assert _counter("sql_bind_textual_total") == textual
    assert col.stages["sql.prepared_hit"].events == len(bindings) - 1
    assert restarts == 0
    assert [j.build_mode for j in walk_operators(prep.op)
            if isinstance(j, JoinOp)] == ["unique"] * 5


def test_a_bind_matches_the_pattern_against_the_dictionary_once(one):
    sess = _session(one)
    _run(sess, Q9, ("%green%",))          # the entry exists from here on
    size = len(one["dicts"]["p_name"])
    col = stats.enable()
    try:
        for n, pattern in enumerate(COLOURS + [None], start=1):
            bound, _text = sess.bind_params(Q9, (pattern,))
            like = col.stages["sql.bind_like"]
            assert (like.events, like.rows) == (n, n * size)
            assert col.stages["sql.bind_params"].events == n
            # what Bind leaves for Execute: the packed (hits, valid) pair
            # and the table over the dictionary, both host arrays
            packed, table = bound.args
            want = np.array([tpch_q9.matcher(pattern)(s)
                             for s in one["ref"].names])
            assert table.dtype == np.bool_ and (table[:size] == want).all()
            assert len(table) == P_.table_lanes(size) == 2048
            assert not table[size:].any()
            assert packed.tolist() == [want.sum(), pattern is not None]
    finally:
        stats.disable()


@pytest.mark.parametrize("entries,lanes", [
    (0, 1), (1, 1), (2, 2), (3, 4), (2000, 2048), (199995, 262144),
    (199999, 262144), (262144, 262144), (262145, 524288)])
def test_a_pattern_table_is_a_power_of_two_long(entries, lanes):
    assert P_.table_lanes(entries) == lanes


def test_the_program_is_the_statements_not_the_dictionarys(lowered_texts):
    """At SF1 the distinct part names are 199,995 to 199,999 by the seed
    (the chip, PR 37: each size was a whole-query compile of 550 s). The
    table argument is a power of two long and nothing else of the program
    reads the dictionary's size: one module whatever the seed."""
    texts, sizes = lowered_texts, []
    for extra in ([], ["zz a name no part has"]):
        gen = tpch_pname.TPCHPName(sf=0.01, seed=7)
        names, codes = gen._names()
        gen._pname = (np.append(names, extra).astype(object), codes)
        loaded = tpch_cname.load_from(gen, MVCCStore(), TABLES)
        sizes.append(len(loaded["dicts"]["p_name"]))
        payload = _run(_session(loaded), Q9, ("%green%",))
        ref = tpch_q9.Reference(loaded["data"], loaded["dicts"], {})
        assert ref.check([(("%green%",), _as_wire(
            payload, loaded["dicts"]["n_name"]))])[0] == [True]
    assert sizes[1] == sizes[0] + 1
    assert len(texts) == 2 and texts[0] == texts[1]


@pytest.mark.parametrize("pattern", ["%green%", "%"])
def test_the_literal_text_and_the_bound_text_give_the_same_rows(one,
                                                                pattern):
    client = _client(one)
    try:
        bound = client.bound(Q9, (pattern,))
        literal = client.query(Q9.replace("$1", f"'{pattern}'"))
        flat = client.bound(Q9_FLAT, (pattern,))
    finally:
        client.close()
    assert bound[1] is None and bound == literal == flat
    assert one["ref"].check([((pattern,), [tuple(r) for r in bound[0]])])[
        0] == [True]


@pytest.mark.parametrize("pattern", ["%green%", "%nothing%", "%", None])
def test_not_like_takes_the_pattern_as_data_too(one, pattern):
    sql = "select count(*) from part where p_name not like $1"
    names = one["ref"].names
    want = 0 if pattern is None else sum(
        not tpch_q9.matcher(pattern)(n) for n in names)
    textual = _counter("sql_bind_textual_total")
    client = _client(one)
    try:
        assert client.bound(sql, (pattern,)) == ([(str(want),)], None)
        if pattern is not None:
            assert client.query(sql.replace("$1", f"'{pattern}'")) == (
                [(str(want),)], None)
    finally:
        client.close()
    assert _counter("sql_bind_textual_total") == textual


@pytest.mark.parametrize("pattern", [
    "%", "%%", "", "%green%", "green%", "%green", "%gr__n%", "_____ %",
    "%green%l_ce%", "%e%", "almond%", "%\x00%", "%.*%"])
def test_like_table_is_the_regular_expression_entry_by_entry(one, pattern):
    d = one["catalog"].table_schema("part").dictionary("p_name")
    assert len(d) > 1900
    rx = re.compile(expr_mod._like_to_regex(pattern), re.S)
    want = np.array([rx.fullmatch(s) is not None for s in d])
    got = expr_mod.like_table(d, pattern)
    assert got.dtype == np.bool_ and (got == want).all()
    if pattern == "%green%":
        assert 60 < got.sum() < 160


@pytest.mark.parametrize("sql,values", [
    # LIKE over anything but one dictionary-coded column
    ("select count(*) from lineitem where l_quantity like $1", ("1%",)),
    ("select count(*) from part where upper(p_name) like $1", ("%GREEN%",)),
])
def test_a_pattern_outside_the_scope_is_bound_as_text(one, sql, values):
    sess = _session(one)
    textual = _counter("sql_bind_textual_total")
    bound, text = sess.bind_params(sql, values)
    assert bound is None and "$1" not in text and values[0] in text
    assert _counter("sql_bind_textual_total") == textual + 1


def test_the_estimate_reads_the_bindings_own_hit_count(one):
    cat = one["catalog"]
    binder = Binder(cat, params=("%green%",))
    plan = binder.bind(parser.parse(
        "select count(*) from part where p_name like $1"))
    hits = sum("green" in n for n in one["ref"].names)
    size = len(one["ref"].names)
    (slot,) = binder.param_slots
    assert P_.sample_of(slot, ("%green%",)) == (hits, size)
    from cockroach_tpu.sql.plan import estimate_cardinality, Filter

    filt = next(p for p in _plan_nodes(plan) if isinstance(p, Filter))
    rows = cat.table_rows("part")
    assert estimate_cardinality(filt, cat) == pytest.approx(
        rows * hits / size)
    # a literal pattern keeps the flat tenth
    lit = Binder(cat).bind(parser.parse(
        "select count(*) from part where p_name like '%green%'"))
    filt = next(p for p in _plan_nodes(lit) if isinstance(p, Filter))
    assert estimate_cardinality(filt, cat) == pytest.approx(rows * 0.1)


def _plan_nodes(plan):
    yield plan
    for kid in plan.inputs():
        yield from _plan_nodes(kid)


def test_explain_prints_the_pattern_slot_and_the_key_packings(one):
    client = _client(one)
    hits = sum("green" in n for n in one["ref"].names)
    try:
        rows, code = client.bound("explain " + Q9, ("%green%",))
        assert code is None
        lines = [r[0] for r in rows]
        assert "parameters: $1 pattern(part.p_name)" in lines
        assert (f"estimates taken at: $1 = '%green%' ({hits} of "
                f"{len(one['ref'].names)} part.p_name values)") in lines
        # the order's reason beside the order: the semi join keeps the
        # pattern's share of part, the other four joins everything
        share = 100 * hits / len(one["ref"].names)
        assert [ln.split(" (keeps ~")[1].split("%)")[0]
                for ln in lines if " join on " in ln] == [
            "100.0", "100.0", "100.0", f"{share:.1f}", "100.0"]
        rows, code = client.bound("explain analyze " + Q9, ("%green%",))
        assert code is None
        table = {ln.split()[0]: int(ln.split()[ln.split().index("ev") - 1])
                 for (ln,) in rows
                 if ln.startswith("fused.") and "ev" in ln.split()}
        # one join tree with a choice, one step the share decided
        assert [ln.split()[3:] for (ln,) in rows
                if ln.startswith("sql.join_rank")] == [
            ["1", "ev", "1", "rows"]]
    finally:
        client.close()
    traced = table.get("fused.compile", 0)
    if traced:      # a traced program counts its lowerings
        assert table["fused.join_key_int"] == 4 * traced
        assert table["fused.join_key_hash"] == traced
        assert table["fused.join_compact"] == traced
        assert table["fused.agg_dense"] == traced
    assert table["fused.hash_key_lanes"] == table["fused.sort_lanes"] >= 1


# ------------------------------------------ the specification's own text ---

def test_the_derived_table_binds_to_the_flattened_texts_plan(one):
    cat = one["catalog"]
    plans = []
    for text in (Q9, Q9_FLAT):
        binder = Binder(cat, params=("%green%",))
        plans.append(normalize(binder.bind(parser.parse(text)), cat))
        assert [s.describe() for s in binder.param_slots] == [
            "$1 pattern(part.p_name)"]
    assert repr(plans[0]) == repr(plans[1])
    assert "subquery" not in repr(plans[0])
    stmt = parser.parse(Q9)
    assert stmt.tables[0].subquery is not None \
        and stmt.tables[0].alias == "profit"


@pytest.mark.parametrize("sql,want", [
    # the outer query may name, compute over and filter the inner columns
    ("select k, n from (select n_nationkey as k, n_name as n from nation) "
     "as t where k < 3 order by k",
     "select n_nationkey as k, n_name as n from nation "
     "where n_nationkey < 3 order by k"),
    ("select t.k + 1 as k1 from (select n_nationkey as k from nation "
     "where n_regionkey = 1) t order by k1",
     "select n_nationkey + 1 as k1 from nation where n_regionkey = 1 "
     "order by k1"),
    ("select r, count(*) as c from (select n_regionkey as r, n_nationkey "
     "from nation) as t group by r order by r",
     "select n_regionkey as r, count(*) as c from nation group by r "
     "order by r"),
])
def test_a_derived_table_is_the_select_it_means(one, sql, want):
    client = _client(one)
    try:
        got, expected = client.query(sql), client.query(want)
    finally:
        client.close()
    assert got[1] is None and got == expected and got[0]


@pytest.mark.parametrize("sql", [
    "select a from (select n_nationkey as a from nation) as t, region",
    "select a from (select n_nationkey as a from nation) as t, "
    "(select r_regionkey as b from region) as u",
    "select a from (select n_regionkey as a, count(*) as c from nation "
    "group by a) as t",
    "select a from (select n_nationkey + 1 from nation) as t",
    "select n_name from (select n_nationkey as a from nation) as t",
    "select u.a from (select n_nationkey as a from nation) as t",
])
def test_a_wider_derived_table_stays_a_bind_error(one, sql):
    with pytest.raises(BindError):
        Binder(one["catalog"]).bind(parser.parse(sql))


# --------------------------------------------------- the loader's names ---

def test_part_names_are_five_distinct_words_of_the_list():
    assert tpch_qgen_q9.COLORS == tpch_pname.WORDS
    assert len(tpch_pname.WORDS) == 92 \
        and set(tpch_pname.WORDS) < set(tpch_dbgen.COLORS)
    gen = tpch_pname.TPCHPName(sf=0.4, seed=2147483999)
    part = gen.table("part")
    names = gen.schema("part").dicts["p_name"]
    # the codes pass 16 bits: the image holds them in four bytes
    assert part["p_name"].max() > 65535 and len(names) > 65535
    assert tpch_pname.stored_width("part", "p_name") == 4
    assert tpch_pname.stored_width("part", "p_partkey") == 4
    assert gen.schema("part").field("p_name").wire == "i4"
    assert list(names) == sorted(set(names))
    for name in names[:: len(names) // 500]:
        words = name.split(" ")
        assert len(words) == len(set(words)) == 5 \
            and set(words) <= set(tpch_pname.WORDS)
    # a pure function of (seed, row), whatever the chunk
    again = tpch_pname.TPCHPName(sf=0.4, seed=2147483999)
    chunk = again.rows("part", 1000, 1010)["p_name"]
    assert [again.schema("part").dicts["p_name"][c] for c in chunk] == [
        names[c] for c in part["p_name"][1000:1010]]
    other = tpch_pname.TPCHPName(sf=0.4, seed=7).table("part")
    assert (other["p_name"] != part["p_name"]).any()
    # every word is drawn about as often as any other
    counts = np.bincount(tpch_pname.name_words(
        np.arange(80000, dtype=np.int64), 7).ravel(), minlength=92)
    assert counts.min() > 0.9 * counts.mean() \
        and counts.max() < 1.1 * counts.mean()


@pytest.mark.parametrize("table", TABLES)
def test_every_other_column_is_the_tpch_loaders(table):
    seed = 3300000001
    plain = tpch_dbgen.TPCH(sf=0.01, seed=seed)
    named = tpch_pname.TPCHPName(sf=0.01, seed=seed)
    a, b = plain.table(table), named.table(table)
    assert list(a) == list(b)
    for col in a:
        if col != "p_name":
            assert (np.asarray(a[col]) == np.asarray(b[col])).all(), col
            assert tpch_pname.stored_width(table, col) == \
                tpch_loader.stored_width(table, col)
    assert [f.name for f in plain.schema(table)] == [
        f.name for f in named.schema(table)]


def test_the_parameter_stream_draws_from_the_92_words():
    spec = STMT["params"]
    state = tpch_qgen_q9.prepare(spec)
    a = tpch_qgen_q9.draw(spec, np.random.default_rng([7, 0]), 500, state)
    b = tpch_qgen_q9.draw(spec, np.random.default_rng([7, 0]), 500, state)
    assert a == b and len(set(a)) > 80
    assert all(len(p) == 1 and p[0][0] == p[0][-1] == "%"
               and p[0][1:-1] in tpch_pname.WORDS for p in a)
    assert tpch_qgen_q9.corners(spec) == [("%almond%",), ("%yellow%",)]
    # a rehearsal draws from the same words
    cfg = manifest.config("tpch-sf1-q9")
    assert cfg["rehearse"]["params"] == {}
    assert cfg["reduced"] == ["sf", "text_columns", "random_streams",
                              "query_set"]


def test_the_manifest_holds_the_new_entries():
    bench = manifest.benchmark()
    assert manifest.validate(bench) == []
    entry = manifest.entry(bench, CELL)
    assert (entry["config"], entry["chips"]) == ("tpch-sf1-q9", 1)
    cfg, cell = manifest.config("tpch-sf1-q9"), manifest.cell(CELL)
    assert cfg["loader"]["name"] == "tpch_pname"
    assert cfg["warmup"] == ["qgen_domain"] and cfg["capacity"] == CAP
    assert cfg["session_setup"] == []
    (stmt,) = cell["statements"]
    assert stmt["protocol"] == "extended" and "like $1" in stmt["sql"] \
        and "from ( select" in stmt["sql"]
    assert cell["traffic_params"] == {"clients": 1, "warmup_per_client": 3}
    assert cell["expect"]["zero_counters"] == [
        "serving.fallback_total", "sql_bind_textual_total"]
    # every metric Q18's cell reports, and the two this cell brings
    q18 = {m["name"] for m in manifest.metrics_for(
        bench, "tpch-sf1-q18.q18-1stream", "per_layer")}
    mine = {m["name"] for m in manifest.metrics_for(bench, CELL,
                                                    "per_layer")}
    assert mine - q18 == {"bind_like_ms", "hash_key_lanes_m"} \
        and q18 <= mine
    for name in mine - q18:
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        # Q9's cells alone: this one, and since ISSUE 40 its twin on the
        # mesh wherever that one's program emits the stage (bind_like_ms)
        assert m["workloads"][0] == CELL and m["moves"] == "stmt_p50_ms"
        assert set(m["workloads"][1:]) <= {"tpch-sf1-q9-mesh4.q9-1stream"}
    # 19 + 12 + 6 + 8 + 5 bytes a row of five images and 16 of nation's
    # (no narrow wire is declared for its columns): 134 MB at SF1
    from benchmark import bytes_model

    assert bytes_model.statement_bytes(
        stmt, tpch_pname, {"lineitem": 6_000_000, "partsupp": 800_000,
                           "orders": 1_500_000, "part": 200_000,
                           "supplier": 10_000, "nation": 25}) == 134_250_400


# ------------------------------------------- the key packings, counted ---

def test_q9_counts_its_join_keys_and_its_hashed_lanes(one):
    sess = _session(one, own_cache=True)
    col = stats.enable()
    try:
        _run(sess, Q9, ("%green%",))
        _run(sess, Q9, ("%almond%",))
    finally:
        stats.disable()
    prep = sess._prepared.get(Q9)
    joins = [j for j in walk_operators(prep.op) if isinstance(j, JoinOp)]
    (shrink,) = [s for s in walk_operators(prep.op)
                 if isinstance(s, ShrinkOp)]
    # orders, partsupp on the two-column key and supplier x nation, all
    # three over the Shrink; under it the part semi join, compacting: the
    # orderer attaches the relation that REMOVES most first (ISSUE 39),
    # so the one join at lineitem's full width is the one that keeps 5%
    assert [(j.how, tuple(j.probe_on)) for j in joins] == [
        ("inner", ("l_orderkey",)),
        ("inner", ("l_suppkey", "l_partkey")),
        ("inner", ("l_suppkey",)),
        ("semi", ("l_partkey",)),
        ("inner", ("s_nationkey",))]
    supplier, semi = joins[2], joins[3]
    assert supplier.probe is shrink and shrink.child is semi
    # one trace: four integer keys, one hashed; every table is one chunk
    # of CAP lanes at SF 0.01, and the Shrink's capacity is the probe of
    # the two joins above it
    assert col.stages["fused.compile"].events == 1
    assert col.stages["fused.join_key_int"].events == 4
    assert col.stages["fused.join_key_hash"].events == 1
    assert col.stages["fused.join_compact"].events == 1
    hashed = shrink.capacity + CAP
    lanes = col.stages["fused.hash_key_lanes"]
    assert (lanes.events, lanes.rows) == (2, 2 * hashed)
    sort = col.stages["fused.sort_lanes"]
    assert (sort.events, sort.rows) == (
        2, 2 * (2 * 2 * CAP + 3 * (shrink.capacity + CAP)))
    ctx = {"window": {"stages": {"fused.hash_key_lanes": {
        "events": lanes.events, "rows": lanes.rows}}}}
    assert hash_key_lanes_m.read(ctx) == hashed / 1e6
    assert hash_key_lanes_m.read({"window": {"stages": {}}}) is None


def test_a_program_of_integer_keys_counts_no_hashed_lane(one):
    sess = _session(one, own_cache=True)
    col = stats.enable()
    try:
        sess.execute(Q3)
    finally:
        stats.disable()
    assert col.stages["fused.join_key_int"].events == 2
    assert "fused.join_key_hash" not in col.stages
    lanes = col.stages["fused.hash_key_lanes"]
    assert (lanes.events, lanes.rows) == (1, 0)
    assert hash_key_lanes_m.read({"window": {"stages": {
        "fused.hash_key_lanes": {"events": 1, "rows": 0}}}}) == 0.0
