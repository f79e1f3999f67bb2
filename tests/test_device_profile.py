"""Device time by plan operator (ISSUE 35): the `crdb.op<N>.<Kind>` scopes
the tracers open, exec/device_profile.py (owners of a compiled program's
instructions, self time, the profile of the program's own executions),
`EXPLAIN ANALYZE (DEVICE)` and `profile_prepared`.

The CPU backend stands in for the chip: its trace carries every thunk as
a host event with an `hlo_op` stat, which the profile reads as chip
`device_ordinal`. Compiles that must show scopes run with the persistent
cache off: its key does not see debug information, so an entry written by
a tree without scopes would be loaded in their place.
"""

import contextlib
import faulthandler
import hashlib
import re
import sys

import jax
import numpy as np
import pytest

from benchmark import manifest
from benchmark.loaders import tpch as tpch_loader
from benchmark.loaders import tpch_cname
from cockroach_tpu.exec import device_profile as dp
from cockroach_tpu.exec import fused
from cockroach_tpu.exec.operators import (
    JoinOp, ShrinkOp, walk_operators,
)
from cockroach_tpu.parallel import dist_flow, make_mesh
from cockroach_tpu.sql import params as P_
from cockroach_tpu.sql import parser
from cockroach_tpu.sql.session import Session, SessionCatalog
from cockroach_tpu.sql.sqlstats import fingerprint
from cockroach_tpu.storage.mvcc import MVCCStore
from cockroach_tpu.util import compile_cache
from cockroach_tpu.util.settings import Settings

SEED = 2147483999
Q3 = manifest.cell("tpch-sf1.q3-1stream")["statements"][0]
Q18 = manifest.cell("tpch-sf1-q18.q18-1stream")["statements"][0]


@pytest.fixture
def time_limit():
    """A limit of the test's own, under the suite's watchdog
    (tests/conftest.py arms one a test; the last one armed counts)."""
    def arm(seconds):
        faulthandler.dump_traceback_later(seconds, exit=True,
                                          file=sys.__stderr__)
    return arm


def _served(loader, stmt, params=None, capacity=131072):
    """The statement run once on the fused tier, its program compiled
    here (persistent cache off) -> (session, prepared entry)."""
    loaded = loader.load(MVCCStore(), {"sf": 0.01}, stmt["tables"], SEED)
    sess = Session(loaded["catalog"], capacity=capacity)
    sess.execute("set vectorize = tpu")
    with compile_cache.persistent_cache_disabled():
        if params is None:
            sess.execute(stmt["sql"])
        else:
            bound, text = sess.bind_params(stmt["sql"], params)
            sess.execute(text, params=bound)
    (prep,) = sess._prepared.values()
    return sess, prep


def _program_text(prep):
    (prog, *_trace_facts), _args = prep.op._fused_runner._prepare()
    return prog.as_text()


# ------------------------------------ (a) every operator has its scope ----

@pytest.mark.parametrize("loader,stmt,params", [
    (tpch_loader, Q3, None), (tpch_cname, Q18, ("313",))],
    ids=["q3", "q18"])
def test_every_operator_of_the_tree_is_a_scope_of_the_program(
        loader, stmt, params):
    _sess, prep = _served(loader, stmt, params)
    text = _program_text(prep)
    ops = list(walk_operators(prep.op))
    for n, op in enumerate(ops):
        assert f"crdb.op{n}.{type(op).__name__}/" in text, (n, op)
    assert "crdb.result/" in text
    # no scope the tree does not have
    assert {m for m in re.findall(r"crdb\.(op\d+\.\w+)", text)} == {
        fused.op_scope_name(n, op) for n, op in enumerate(ops)}
    # a join lowered as one step with the Shrink above it is the JOIN's:
    # the Shrink's own scope is nobody's innermost
    pairs = [(n, op) for n, op in enumerate(ops)
             if isinstance(op, ShrinkOp) and isinstance(op.child, JoinOp)]
    assert pairs
    _module, owner = dp.owners_of_text(text)
    owned = {scope for scope, _how in owner.values()}
    for n, shrink in pairs:
        assert fused.op_scope_name(n, shrink) not in owned
        assert fused.op_scope_name(n + 1, shrink.child) in owned


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs four virtual CPU devices")
def test_the_mesh_tells_exchange_and_merge_from_their_operators():
    loaded = tpch_loader.load(MVCCStore(), {"sf": 0.01}, Q3["tables"], SEED)
    s = Settings()
    old = s.get(dist_flow.BROADCAST_LIMIT)
    s.set(dist_flow.BROADCAST_LIMIT, 4096)    # Q3's big join goes BY_HASH
    cat = loaded["catalog"].with_mesh(make_mesh(4))
    try:
        sess = Session(cat, capacity=4096)
        sess.execute("set distsql = always")
        with compile_cache.persistent_cache_disabled():
            sess.execute(Q3["sql"])
        (prep,) = sess._prepared.values()
        runner = dp.served_runner(prep.op)
        assert isinstance(runner, dist_flow.DistFusedRunner)
        prog, _flag_ops, _args = runner._prepare()
        text = prog.compiled.as_text()
        ops = list(walk_operators(prep.op))
        by_hash = [n for n, op in enumerate(ops) if isinstance(op, JoinOp)
                   and f"crdb.op{n}.JoinOp.exchange/" in text]
        assert len(by_hash) == 1
        assert re.search(r"crdb\.op\d+\.HashAggOp\.merge/", text)
        assert "crdb.result/" in text
        prof = runner.device_profile(2)
        assert prof.chips == 4 and prof.scoped
        rows = dp.operator_rows(prep.op, prof)
        parts = {(r["kind"], r["part"]) for r in rows if r["part"]}
        assert ("JoinOp", "exchange") in parts
        assert ("HashAggOp", "merge") in parts
        total = (sum(r["device_ms"] for r in rows)
                 + prof.scope_ms("result") + prof.unattributed_ms)
        assert total == pytest.approx(prof.busy_ms, rel=1e-3)
        assert prof.busy_max_ms >= prof.busy_ms
    finally:
        cat.with_mesh(None)
        s.set(dist_flow.BROADCAST_LIMIT, old)


# ------------------------------------------ (b) owners, on a small text ----

def _meta(scope):
    return f', metadata={{op_name="jit(prog)/{scope}/add"}}' if scope else ""


HLO = f"""HloModule jit_prog, is_scheduled=true

%fused_a (p0: s32[8]) -> s32[8] {{
  %p0 = s32[8]{{0}} parameter(0)
  %in_a = s32[8]{{0}} negate(%p0){_meta("crdb.op1.MapOp/crdb.op2.ScanOp")}
  ROOT %xla_made = s32[8]{{0}} abs(%in_a)
}}

%fused_b (p0.1: s32[8]) -> s32[8] {{
  %p0.1 = s32[8]{{0}} parameter(0)
  %first_b = s32[8]{{0}} negate(%p0.1){_meta("crdb.op1.MapOp/crdb.op2.ScanOp")}
  ROOT %root_b = s32[8]{{0}} abs(%first_b){_meta("crdb.op1.MapOp")}
}}

%body (carry: (s32[8])) -> (s32[8]) {{
  %carry = (s32[8]{{0}}) parameter(0)
  %got = s32[8]{{0}} get-tuple-element(%carry), index=0
  %open = s32[8]{{0}} negate(%got)
  %step = s32[8]{{0}} abs(%open){_meta("crdb.op1.MapOp/crdb.op3.JoinOp")}
  ROOT %next = (s32[8]{{0}}) tuple(%step)
}}

%cond (c: (s32[8])) -> pred[] {{
  %c = (s32[8]{{0}}) parameter(0)
  ROOT %go = pred[] constant(false)
}}

ENTRY %main (arg: s32[8]) -> s32[8] {{
  %arg = s32[8]{{0}} parameter(0)
  %one_sided = s32[8]{{0}} copy(%arg)
  %named = s32[8]{{0}} negate(%one_sided){_meta("crdb.op0.TopKOp/crdb.op1.MapOp")}
  %u1 = s32[8]{{0}} copy(%named)
  %u2 = s32[8]{{0}} bitcast(%u1)
  %after = s32[8]{{0}} abs(%u2){_meta("crdb.op1.MapOp")}
  %unscoped_fusion = s32[8]{{0}} fusion(%arg), kind=kLoop, calls=%fused_a
  %tail = s32[8]{{0}} copy(%unscoped_fusion)
  %named_fusion = s32[8]{{0}} fusion(%tail), kind=kLoop, calls=%fused_b{_meta("crdb.op1.MapOp")}
  %boundary = s32[8]{{0}} copy(%after)
  %other = s32[8]{{0}} abs(%boundary){_meta("crdb.op0.TopKOp")}
  %mixed = s32[8]{{0}} add(%after, %other)
  %top2 = s32[8]{{0}} abs(%mixed){_meta("crdb.op0.TopKOp")}
  %init = (s32[8]{{0}}) tuple(%other)
  %loop = (s32[8]{{0}}) while(%init), condition=%cond, body=%body{_meta("crdb.op3.JoinOp")}
  %out = s32[8]{{0}} get-tuple-element(%loop), index=0
  ROOT %packed = s32[8]{{0}} add(%out, %named_fusion){_meta("crdb.result")}
}}
"""


@pytest.mark.parametrize("name,want", [
    ("named", ("op1.MapOp", dp.NAMED)),             # innermost wins
    ("packed", ("result", dp.NAMED)),
    ("named_fusion", ("op1.MapOp", dp.NAMED)),      # its own op_name
    ("u1", ("op1.MapOp", dp.INFERRED)),             # through two unscoped
    ("u2", ("op1.MapOp", dp.INFERRED)),
    # a fusion without a name is its root's, and XLA's own tail of the
    # scan sits between the scan's slice and the scan's first consumer
    # INSIDE the named fusion that reads it
    ("unscoped_fusion", ("op2.ScanOp", dp.INFERRED)),
    ("tail", ("op2.ScanOp", dp.INFERRED)),
    # op0 and op1 feed it and op0 alone reads it: between two of op0's
    # (a value of another operator that XLA shared does not unseat it)
    ("mixed", ("op0.TopKOp", dp.INFERRED)),
    ("one_sided", (None, "- -> op1.MapOp")),
    ("boundary", (None, "op1.MapOp -> op0.TopKOp")),
    ("loop", ("op3.JoinOp", dp.NAMED)),
    ("step", ("op3.JoinOp", dp.NAMED)),
    # op0 feeds the loop and op3 follows: open by dataflow, so its caller's
    ("open", ("op3.JoinOp", dp.INFERRED)),
])
def test_owners_named_inferred_or_nobody(name, want):
    module, owner = dp.owners_of_text(HLO)
    assert module == "jit_prog"
    assert owner[name] == want


def test_scope_of_takes_the_innermost_component():
    assert dp.scope_of("jit(prog)/crdb.op0.TopKOp/crdb.op5.JoinOp/while/"
                       "body/crdb.op5.JoinOp.exchange/sort") == \
        "op5.JoinOp.exchange"
    assert dp.scope_of("jit(prog)/crdb.result/jit(argsort)/iota") == "result"
    assert dp.scope_of("jit(prog)/jit(argsort)/crdb.opx/iota") is None


# --------------------------------- (c) self time, launch, drain, offset ----

def test_self_time_counts_a_while_and_its_body_once():
    spans = [(0.0, 100.0),      # the while
             (10.0, 30.0), (30.0, 50.0),    # its body, twice
             (35.0, 40.0),      # nested in the second
             (120.0, 130.0)]    # after a gap
    got = dp.self_times(spans)
    assert got == [60.0, 20.0, 15.0, 5.0, 10.0]
    assert sum(got) == 110.0    # the union


def _events(lane_shift_ns):
    """Two executions of three instructions; the device lane reads
    `lane_shift_ns` EARLIER than the host lane."""
    dev, host = [], {"fused.dispatch": [], "fused.wait": []}
    for base in (1_000_000.0, 40_000_000.0):
        host["fused.dispatch"].append((base, base + 200_000))
        host["fused.wait"].append((base + 200_000, base + 5_300_000))
        t = base + 300_000 - lane_shift_ns      # launched 0.3 ms in
        for name, dur in (("sort.1", 3_000_000), ("gap", 0),
                          ("fusion.2", 1_000_000), ("copy.3", 500_000)):
            if name == "gap":
                t += 250_000
                continue
            dev.append((name, t, t + dur, 0))
            t += dur
    return {"device": dev, "host": host}


OWNER = {"sort.1": ("op1.JoinOp", dp.NAMED),
         "fusion.2": ("op1.JoinOp", dp.INFERRED),
         "copy.3": (None, "op1.JoinOp -> op0.TopKOp")}


@pytest.mark.parametrize("shift_ms", [0.0, 2.0])
def test_launch_drain_and_the_offset_of_the_lanes(shift_ms):
    prof = dp.reduce_events(_events(shift_ms * 1e6), OWNER,
                            ("fused.dispatch", "fused.wait"))
    assert (prof.executions, prof.chips, prof.scoped) == (2, 1, True)
    assert prof.busy_ms == pytest.approx(4.5)
    assert prof.gaps_ms == pytest.approx(0.25)
    assert prof.scopes == {"op1.JoinOp": [pytest.approx(3.0),
                                          pytest.approx(1.0)]}
    assert prof.unattributed_ms == pytest.approx(0.5)
    assert prof.unattributed_ops == [
        ["copy.3", pytest.approx(0.5), "op1.JoinOp -> op0.TopKOp"]]
    assert prof.top_ops == [
        ["sort.1", pytest.approx(3.0), "op1.JoinOp", dp.NAMED],
        ["fusion.2", pytest.approx(1.0), "op1.JoinOp", dp.INFERRED],
        ["copy.3", pytest.approx(0.5), None, "op1.JoinOp -> op0.TopKOp"]]
    # the wait ends 0.25 ms after the last instruction
    if shift_ms == 0.0:
        assert prof.lane_offset_ms == 0.0
        assert prof.launch_ms == pytest.approx(0.3)
        assert prof.drain_ms == pytest.approx(0.25)
    else:
        # a launch before its own dispatch is the lanes' offset, as far
        # as the launch shows it; both ends are corrected by it
        assert prof.lane_offset_ms == pytest.approx(1.7)
        assert prof.launch_ms == pytest.approx(0.0)
        assert prof.drain_ms == pytest.approx(0.55)


# ------------------------- (d) scopes do not move a compile-cache key ----

_LOC = re.compile(r"\s*loc\([^)]*\)|^#loc.*$", re.M)


def _jax_cache_key(lowered):
    from jax._src import cache_key, compiler

    return cache_key.get(
        lowered.compiler_ir(), np.array(jax.devices()[:1]),
        compiler.get_compile_options(num_replicas=1, num_partitions=1),
        jax.devices()[0].client)


def test_scopes_move_neither_jaxs_cache_key_nor_the_vaults(monkeypatch):
    keys = []
    lower = fused.lower_program

    def recording(fn, args):
        lowered = lower(fn, args)
        keys.append((
            _jax_cache_key(lowered),
            hashlib.sha256(lowered.as_text().encode()).hexdigest(),
            "crdb.op" in lowered.as_text(debug_info=True)))
        return lowered

    monkeypatch.setattr(fused, "lower_program", recording)
    _served(tpch_loader, Q3)
    monkeypatch.setattr(fused, "scope",
                        lambda name: contextlib.nullcontext())
    _served(tpch_loader, Q3)
    (with_scopes, without) = keys
    assert with_scopes[2] and not without[2]
    assert with_scopes[:2] == without[:2]


# --------------------------- (e) EXPLAIN ANALYZE (DEVICE), end to end ----

def _small(catalog):
    sess = Session(catalog, capacity=1 << 12)
    for text in (
            "create table t (a int primary key, b int)",
            "create table u (k int primary key, ta int, c int)",
            "create table empty (ea int primary key, eb int)",
            "insert into t values " + ", ".join(
                f"({i}, {i % 7})" for i in range(200)),
            "insert into u values " + ", ".join(
                f"({i}, {i % 200}, {i % 13})" for i in range(1000)),
            "set vectorize = tpu"):
        sess.execute(text)
    return sess


JOIN_AGG = ("select b, sum(c) as s from t, u where a = ta "
            "group by b order by b")


def test_explain_analyze_device_one_line_an_operator(time_limit):
    time_limit(240)
    sess = _small(SessionCatalog(MVCCStore()))
    with compile_cache.persistent_cache_disabled():
        kind, lines, _ = sess.execute("explain analyze (device) " + JOIN_AGG)
    assert kind == "explain"
    assert any("EXPLAIN ANALYZE (DEVICE) splits it by operator" in ln
               for ln in lines)
    head = lines.index(
        "device time by operator (self time of one execution):")
    table = lines[head + 1:]
    ms = re.compile(r"\s([0-9.]+) device-ms")
    ops = [ln for ln in table if re.match(r"  op\d+ ", ln)]
    kinds = [ln.split()[1] for ln in ops]
    assert [ln.split()[0] for ln in ops] == [
        f"op{n}" for n in range(len(ops))]
    assert "JoinOp" in kinds and "HashAggOp" in kinds and \
        kinds.count("ScanOp") == 2
    (join,) = [ln for ln in ops if " JoinOp " in ln]
    assert "inner" in join and "named" in join and "inferred" in join
    (result,) = [ln for ln in table if ln.startswith("  result ")]
    (nobody,) = [ln for ln in table if ln.startswith("  unattributed ")]
    (device,) = [ln for ln in table if ln.startswith("device: busy ")]
    busy = float(re.search(r"busy ([0-9.]+) ms", device).group(1))
    parts = sum(float(ms.search(ln).group(1))
                for ln in ops + [result, nobody])
    assert busy > 0
    assert parts == pytest.approx(busy, rel=1e-3, abs=2e-3 * len(ops))
    assert "5 executions, 1 chip" in device
    assert "scopes: none" not in "\n".join(table)
    # most of this program is the join's and the aggregates' own
    share = float(re.search(r"\(\s*([0-9.]+)%\)", nobody).group(1))
    assert share < 25.0


def test_explain_analyze_takes_both_options_and_no_other():
    ast = parser.parse("explain analyze (debug, device) select a from t")
    assert (ast.analyze, ast.debug, ast.device) == (True, True, True)
    ast = parser.parse("explain analyze (device) select a from t")
    assert (ast.debug, ast.device) == (False, True)
    assert not parser.parse("explain analyze select a from t").device
    with pytest.raises(parser.ParseError):
        parser.parse("explain analyze (verbose) select a from t")


# ------------------------------ (f) an executable without any scope ----

def test_an_executable_without_scopes_is_all_unattributed(monkeypatch):
    sess = _small(SessionCatalog(MVCCStore()))
    monkeypatch.setattr(fused, "scope",
                        lambda name: contextlib.nullcontext())
    with compile_cache.persistent_cache_disabled():
        sess.execute(JOIN_AGG)
    (prep,) = [p for p in sess._prepared.values() if p.op is not None]
    prof = dp.served_runner(prep.op).device_profile(2)
    assert not prof.scoped and prof.scopes == {}
    assert prof.busy_ms > 0
    assert prof.unattributed_ms == pytest.approx(prof.busy_ms)
    lines = dp.render(prep.op, prof)
    assert lines[1] == ("  scopes: none (executable compiled by a tree "
                        "without them)")
    assert "(100.0%)" in [ln for ln in lines
                          if ln.startswith("  unattributed")][0]


# --------------------- (g) a statement no whole-query runner served ----

def test_a_statement_the_fused_runner_refuses_prints_no_table():
    sess = _small(SessionCatalog(MVCCStore()))
    kind, lines, _ = sess.execute(
        "explain analyze (device) select count(*) from empty")
    assert kind == "explain"
    text = "\n".join(lines)
    assert "device time by operator: no whole-query device program " \
           "served this statement (tier=fused, which handed it to the " \
           "streaming runtime)" in text
    assert "fused.fallback_unsupported" in text
    assert not re.search(r"device-ms \(\s*[0-9.]+%\)", text)
    assert "device: busy" not in text and "splits it by operator" not in text


# ------------------- (h) profile_prepared: each runner's last binding ----

class _Spy:
    """A compiled program that remembers what it was called with."""

    def __init__(self, prog):
        self.prog, self.calls = prog, []

    def __call__(self, *args):
        self.calls.append(args)
        return self.prog(*args)

    def as_text(self):
        return self.prog.as_text()


def test_profile_prepared_runs_each_statement_at_its_last_binding(
        time_limit):
    time_limit(240)
    cat = SessionCatalog(MVCCStore())
    sess = _small(cat)
    bound_sql = "select count(*) as n, sum(c) as s from u where c < $1"
    with compile_cache.persistent_cache_disabled():
        sess.execute(JOIN_AGG)
        for value in ("3", "11"):
            bound, text = sess.bind_params(bound_sql, (value,))
            assert bound is not None
            sess.execute(text, params=bound)
        sess.execute("select count(*) from empty")     # streaming: no runner
    spies = {}
    for sql, prep in sess._prepared.items():
        runner = dp.served_runner(prep.op) if prep.op is not None else None
        if runner is None:
            continue
        for key, entry in runner._progs.items():
            spies[sql] = spy = _Spy(entry[0])
            runner._progs[key] = (spy,) + entry[1:]
    got = dp.profile_prepared(cat, repeats=2)
    assert set(got) == {fingerprint(JOIN_AGG), fingerprint(bound_sql)}
    for prof in got.values():
        assert prof["executions"] == 2 and prof["scoped"]
        assert prof["busy_ms"] > 0 and prof["operators"]
    literal, bound_calls = spies[JOIN_AGG].calls, spies[bound_sql].calls
    assert len(literal) == 2 and len(bound_calls) == 2
    slots = sess._prepared[bound_sql].slots
    (want,) = P_.evaluate(slots, ("11",))
    for call in bound_calls:
        np.testing.assert_array_equal(np.asarray(call[-1]), want)
    # the literal statement's program takes its images and nothing else
    assert all(isinstance(a, tuple) for a in literal[0])
