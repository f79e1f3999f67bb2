"""Whole-flow fusion (exec/fused.py): differential vs the streaming
runtime, overflow-restart behavior, and fallback coverage.

The reference keeps its in-memory operators and disk spillers honest with
one fixture corpus run under multiple configs (colexectestutils.RunTests
re-runs with forced spilling); here the two executors are the fused
single-program path and the streaming operator tree, and every query must
produce identical results through both.
"""

import re

import jax
import numpy as np
import pytest

from cockroach_tpu.exec import collect
from cockroach_tpu.exec import fused
from cockroach_tpu.exec.operators import (
    HashAggOp, JoinOp, MapOp, ScanOp, SortOp,
)
from cockroach_tpu.coldata.batch import Field, INT, Schema
from cockroach_tpu.ops.agg import AggSpec
from cockroach_tpu.ops.expr import Col
from cockroach_tpu.ops.sort import SortKey
from cockroach_tpu.workload.tpch import TPCH
from cockroach_tpu.workload import tpch_queries as Q


def _sorted_rows(res, names):
    cols = [np.asarray(res[n]) for n in names]
    order = np.lexsort(cols[::-1])
    return [tuple(c[i] for c in cols) for i in order]


@pytest.mark.parametrize("qn", [1, 3, 6, 9, 18])
def test_fused_matches_streaming_tpch(qn):
    gen = TPCH(sf=0.01)
    flow_f = Q.QUERIES[qn](gen, 1 << 13)
    flow_s = Q.QUERIES[qn](gen, 1 << 13)
    assert fused.try_compile(flow_f) is not None
    rf = collect(flow_f, fuse=True)
    rs = collect(flow_s, fuse=False)
    names = [f.name for f in flow_f.schema]
    assert _sorted_rows(rf, names) == _sorted_rows(rs, names)


def _int_scan(data, capacity, nullable=()):
    """One chunk source of integer columns; a column in `nullable` reads
    its validity from data["<name>__valid"]."""
    schema = Schema([Field(n, INT, nullable=n in nullable) for n in data
                     if not n.endswith("__valid")])

    def chunks():
        yield data

    return ScanOp(schema, chunks, capacity)


def test_fused_join_overflow_restarts():
    # every probe row matches every build row: 8x8=64 pairs exceed the
    # initial out_capacity (cap * expansion = 8), forcing FlowRestart
    # retries that double expansion until 64 fits
    probe = _int_scan({"a": np.zeros(8, dtype=np.int64)}, 8)
    build = _int_scan({"b": np.zeros(8, dtype=np.int64),
                       "bv": np.arange(8, dtype=np.int64)}, 8)
    join = JoinOp(probe, build, ["a"], ["b"], how="inner")
    runner = fused.try_compile(join)
    assert runner is not None
    res = collect(join)
    assert len(res["bv"]) == 64
    assert join.expansion >= 8


def test_fused_agg_overflow_restarts():
    # more groups than the accumulator: generic fold overflow -> restart.
    # workmem is sized so the materialized input does NOT fit (forcing the
    # chunked fold) but the growing accumulator does — until expansion
    # reaches 8, where the flow degrades to the streaming/grace path.
    n = 64
    scan = _int_scan({"k": np.arange(n, dtype=np.int64),
                      "v": np.ones(n, dtype=np.int64)}, 8)

    def chunks():
        for a in range(0, n, 8):
            yield {"k": np.arange(a, a + 8, dtype=np.int64),
                   "v": np.ones(8, dtype=np.int64)}

    scan._chunks = chunks
    agg = HashAggOp(scan, ["k"], [AggSpec("sum", "v", "s")],
                    workmem=600)
    res = collect(agg)
    got = sorted(zip(res["k"].tolist(), res["s"].tolist()))
    assert got == [(k, 1) for k in range(n)]
    assert agg.expansion >= 8


def test_fused_falls_back_on_custom_operator():
    class Weird(SortOp):
        pass

    scan = _int_scan({"k": np.arange(4, dtype=np.int64)}, 4)
    op = Weird(scan, [SortKey("k")])
    # subclass of a supported op still fuses; a genuinely unknown type not
    assert fused.try_compile(op) is not None

    class Custom:
        schema = scan.schema

        def batches(self):
            return iter(())

    assert fused.try_compile(Custom()) is None


def test_fused_empty_scan_falls_back():
    schema = Schema([Field("k", INT)])

    def chunks():
        return iter(())

    scan = ScanOp(schema, chunks, 4)
    agg = HashAggOp(scan, [], [AggSpec("count_star", None, "c")])
    res = collect(agg)  # scalar agg over empty input: one row, count 0
    assert list(res["c"]) == [0]


def test_aggregate_directly_over_a_join_matches_streaming():
    """A HashAggOp directly on a unique-build inner JoinOp, grouped by the
    join key (no Shrink, no MapOp between: a tree only a hand builds):
    the whole join and one aggregation over it, with the streaming
    JoinOp+HashAggOp's rows, group keys on the probe OR the build join
    column, with build group columns along."""
    rng = np.random.default_rng(7)
    nb, np_ = 32, 200
    bk = rng.permutation(500)[:nb]
    bd = rng.integers(100, 4000, nb)
    pk = rng.integers(0, 500, np_)
    pv = rng.integers(-30, 90, np_)
    for key_side in ("k", "fk"):
        probe = _int_scan({"fk": pk, "v": pv}, 64)  # 4 chunks of 64
        build = _int_scan({"k": bk, "d": bd}, nb)
        join = JoinOp(probe, build, ["fk"], ["k"], how="inner")
        agg = HashAggOp(join, [key_side, "d"],
                        [AggSpec("sum", "v", "s"),
                         AggSpec("count_star", None, "n"),
                         AggSpec("avg", "v", "m")])
        runner = fused.try_compile(agg)
        assert runner is not None
        rf = collect(agg, fuse=True)

        probe2 = _int_scan({"fk": pk, "v": pv}, 64)
        build2 = _int_scan({"k": bk, "d": bd}, nb)
        agg2 = HashAggOp(JoinOp(probe2, build2, ["fk"], ["k"],
                                how="inner"), [key_side, "d"],
                         [AggSpec("sum", "v", "s"),
                          AggSpec("count_star", None, "n"),
                          AggSpec("avg", "v", "m")])
        rs = collect(agg2, fuse=False)
        names = [key_side, "d", "s", "n", "m"]
        assert _sorted_rows(rf, names) == _sorted_rows(rs, names)


def test_aggregate_over_a_join_with_duplicate_build_keys_restarts_exactly():
    """Duplicate build keys trip the unique join's deferred fallback: the
    rerun joins by expansion and the answer stays exact."""
    rng = np.random.default_rng(9)
    bk = rng.integers(0, 20, 32)            # duplicates guaranteed
    bd = rng.integers(0, 100, 32)
    pk = rng.integers(0, 25, 100)
    pv = rng.integers(0, 50, 100)
    probe = _int_scan({"fk": pk, "v": pv}, 50)
    build = _int_scan({"k": bk, "d": bd}, 32)
    join = JoinOp(probe, build, ["fk"], ["k"], how="inner")
    agg = HashAggOp(join, ["fk", "d"], [AggSpec("sum", "v", "s")])
    rf = collect(agg, fuse=True)

    probe2 = _int_scan({"fk": pk, "v": pv}, 50)
    build2 = _int_scan({"k": bk, "d": bd}, 32)
    agg2 = HashAggOp(JoinOp(probe2, build2, ["fk"], ["k"], how="inner"),
                     ["fk", "d"], [AggSpec("sum", "v", "s")])
    rs = collect(agg2, fuse=False)
    assert _sorted_rows(rf, ["fk", "d", "s"]) \
        == _sorted_rows(rs, ["fk", "d", "s"])


# -- an aggregate DIRECTLY on a join (the tree the group-join collapse took
#    until PR 44): the join and the aggregate each lower as themselves ------

def _null_rows(res):
    """collect()'s columns as sorted rows, None where a value is NULL."""
    names = [n for n in res if not n.endswith("__valid")]
    rows = [tuple(None if not res[n + "__valid"][i] else res[n][i].item()
                  for n in names) for i in range(len(res[names[0]]))]
    return sorted(rows, key=repr)


def _agg_on_join(case):
    """-> HashAggOp / JoinOp(inner, probe fk = build k): 200 probe rows
    (fk, v) in chunks of 64 against 32 build rows (k, d); `case` bends
    one thing."""
    seed = int(case[4]) if case.startswith("seed") else 44
    rng = np.random.default_rng(seed)
    nb, n = 32, 200
    bk = rng.permutation(500)[:nb].astype(np.int64)
    pk = rng.integers(0, 500, n).astype(np.int64)
    pk[::3] = bk[rng.integers(0, nb, len(pk[::3]))]      # matches for sure
    pv = rng.integers(-30, 90, n).astype(np.int64)
    probe = {"fk": pk, "v": pv}
    build = {"k": bk, "d": rng.integers(100, 4000, nb).astype(np.int64)}
    nullable, group_by, kw = set(), ["fk", "d"], {}
    if case.endswith("build_key"):
        group_by = ["k", "d"]
    elif case == "null_keys_and_inputs":
        probe["fk__valid"] = (rng.random(n) > 0.2).astype(np.uint8)
        probe["v__valid"] = (rng.random(n) > 0.3).astype(np.uint8)
        build["k__valid"] = (np.arange(nb) != 5).astype(np.uint8)
        nullable = {"fk", "v", "k"}
    elif case == "a_group_of_null_inputs":
        probe["fk"][:8] = bk[0]
        probe["v__valid"] = (probe["fk"] != bk[0]).astype(np.uint8)
        nullable = {"v"}
    elif case == "duplicate_build_keys":
        build["k"] = rng.integers(0, 20, nb).astype(np.int64)
        probe["fk"] = rng.integers(0, 25, n).astype(np.int64)
    elif case == "more_groups_than_the_accumulator":
        # folded (the input is over the budget) into an accumulator of
        # one chunk's lanes: every probe row its own group
        build = {"k": np.arange(256, dtype=np.int64),
                 "d": np.arange(256, dtype=np.int64) * 3}
        probe = {"fk": np.arange(n, dtype=np.int64), "v": pv}
        kw = {"workmem": 3000}
    elif case == "wide_build_columns":
        for i in range(6):
            build[f"d{i}"] = rng.integers(-1 << 50, 1 << 50, nb)
        group_by = ["fk", "d"] + [f"d{i}" for i in range(6)]
    elif case == "inputs_past_31_bits":
        probe["v"] = rng.integers(-1 << 45, 1 << 45, n)
    elif case == "keys_spanning_more_than_2_32":
        spread = bk * np.int64(1 << 33) - np.int64(1 << 41)
        build["k"] = spread
        probe["fk"] = np.where(np.arange(n) % 3 == 0,
                               spread[rng.integers(0, nb, n)],
                               rng.integers(-1 << 41, 1 << 41, n))

    join = JoinOp(_int_scan(probe, 64, nullable),
                  _int_scan(build, len(build["k"]), nullable),
                  ["fk"], ["k"], how="inner")
    return HashAggOp(join, group_by,
                     [AggSpec("sum", "v", "s"), AggSpec("count", "v", "c"),
                      AggSpec("count_star", None, "n")], **kw)


@pytest.mark.parametrize("case", [
    "seed1_probe_key", "seed2_probe_key", "seed3_probe_key",
    "seed1_build_key", "seed2_build_key", "seed3_build_key",
    "null_keys_and_inputs", "a_group_of_null_inputs",
    "duplicate_build_keys", "more_groups_than_the_accumulator",
    "wide_build_columns", "inputs_past_31_bits",
    "keys_spanning_more_than_2_32"])
def test_aggregate_on_a_raw_join_answers_as_the_streaming_runtime(case):
    """What the group-join kernel's own tests held it to, now held by the
    two operators that answer such a tree: fused against streaming, row
    for row, NULLs as NULLs."""
    agg = _agg_on_join(case)
    assert fused.try_compile(agg) is not None
    got, branches = _agg_branches(lambda: collect(agg, fuse=True))
    want = collect(_agg_on_join(case), fuse=False)
    assert _null_rows(got) == _null_rows(want) and len(want["n"]) >= 10
    # one of the five lowerings, and never the in-place one: no Shrink
    # compacted this join, so nothing proves an order
    assert branches and set(branches) <= {"materialized", "folded"}
    if case == "duplicate_build_keys":
        assert agg.child.build_mode != "unique" and len(branches) == 1 \
            and sum(branches.values()) >= 2      # a trace a restart
    if case == "more_groups_than_the_accumulator":
        assert "folded" in branches and agg.expansion > 1
    if case == "a_group_of_null_inputs":
        assert any(r[2] is None and r[3] == 0 and r[4] >= 8
                   for r in _null_rows(got))


def test_columnar_baselines_match_oracles():
    """The bench's vectorized-numpy baselines must agree with the row-wise
    oracles — otherwise vs_baseline measures against a wrong answer."""
    gen = TPCH(sf=0.01)
    o3 = {(k, r, d) for k, r, d in Q.q3_oracle(gen)}
    c3 = {(k, r, d) for k, r, d, _p in Q.q3_oracle_columnar(gen)}
    assert o3 == c3
    assert Q.q9_oracle_columnar(gen) == Q.q9_oracle(gen)
    assert Q.q18_oracle_columnar(gen) == Q.q18_oracle(gen)


def test_fused_respects_workmem_fallback():
    # a sort whose input exceeds workmem must fall back (streaming external
    # sort), still producing correct output
    n = 256
    scan = _int_scan({"k": np.arange(n, dtype=np.int64)[::-1].copy()}, n)
    srt = SortOp(scan, [SortKey("k")], workmem=64)  # 64 bytes: force spill
    res = collect(srt)
    np.testing.assert_array_equal(res["k"], np.arange(n))


# -- a selective join under a Shrink lowers as one step --------------------

def _sql_catalog():
    from cockroach_tpu.sql import TPCHCatalog

    gen = TPCH(sf=0.01)
    return gen, TPCHCatalog(gen)


def _stage_events(fn, prefix):
    """fn() under a fresh stats collection -> (result, {stage: events} of
    the stages named `prefix`...)."""
    from cockroach_tpu.exec import stats

    col = stats.enable()
    try:
        out = fn()
    finally:
        stats.disable()
    return out, {name: st.events for name, st in col.stages.items()
                 if name.startswith(prefix)}


def _join_compacts(fn):
    """-> (fn(), events of counter `fused.join_compact`: one per
    Join+Shrink pair per traced program)."""
    out, events = _stage_events(fn, "fused.join_compact")
    return out, events.get("fused.join_compact", 0)


def test_q3_sql_compacts_both_joins_and_matches_oracle():
    """The served Q3 text through Session: oracle rows; both of its
    joins sit under a Shrink and lower with it; Q1 has neither."""
    from cockroach_tpu.sql.session import Session
    from tests.test_sql import Q1_SQL, Q3_SQL

    gen, cat = _sql_catalog()
    sess = Session(cat, capacity=1 << 14)
    sess.execute("set vectorize = tpu")
    (_k, got, _s), n = _join_compacts(lambda: sess.execute(Q3_SQL))
    rows = [(int(got["l_orderkey"][i]), int(got["revenue"][i]),
             int(got["o_orderdate"][i]))
            for i in range(len(got["l_orderkey"]))]
    assert rows == Q.q3_oracle(gen)
    assert n == 2
    (_k, got, _s), n = _join_compacts(lambda: sess.execute(Q1_SQL))
    assert len(got["l_returnflag"]) == len(Q.q1_oracle(gen))
    assert n == 0


def _scoped_eqns(jaxpr, stack=""):
    """(name stack with the enclosing equations', equation) of every
    equation of `jaxpr`, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        yield here, eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _scoped_eqns(inner, here)


def _eqns(jaxpr):
    """Every equation of `jaxpr`, those of its sub-jaxprs included."""
    return (eqn for _stack, eqn in _scoped_eqns(jaxpr))


def _sorts(jaxpr):
    """(lanes, dtype of the first operand, operands, is_stable) of every
    sort equation."""
    return [(eqn.invars[0].aval.shape[0], str(eqn.invars[0].aval.dtype),
             len(eqn.invars), eqn.params["is_stable"])
            for eqn in _eqns(jaxpr) if eqn.primitive.name == "sort"]


def _cummaxes(jaxpr, dtype="int64"):
    """Lanes (the whole operand's) of every cummax equation over `dtype`:
    at int64 a resorting carry join's run broadcast (two, the 62-bit
    payload's halves), at int32 a compacting join's run heads (one, in
    ops/prefix.blocked_cummax's rows of 512: the lanes padded to them)."""
    return [int(np.prod(eqn.invars[0].aval.shape)) for eqn in _eqns(jaxpr)
            if eqn.primitive.name == "cummax"
            and str(eqn.invars[0].aval.dtype) == dtype]


def _head_scans(jaxpr, n):
    """How many s32 cummaxes of `jaxpr` run over `n` lanes padded to
    blocked_cummax's rows: a compacting join's ONE scan (PR 49)."""
    from cockroach_tpu.ops.prefix import _BLOCK

    return _cummaxes(jaxpr, "int32").count(-(-n // _BLOCK) * _BLOCK)


def _record_joins(monkeypatch):
    """-> (compacted, two_step): lists that fill with (lcap, rcap, how)
    of every join the tracer lowers as one step with its Shrink, and of
    every join it hands to hash_join_prepared."""
    compacted, two_step = [], []
    real_compact, real_join = (fused.probe_unique_compact,
                               fused.hash_join_prepared)

    def compact(probe, ub, probe_on, how, capacity):
        compacted.append((probe.capacity, ub.batch.capacity, how))
        return real_compact(probe, ub, probe_on, how, capacity)

    def join(probe, bt, *a, **kw):
        two_step.append((probe.capacity, bt.batch.capacity, kw["how"]))
        return real_join(probe, bt, *a, **kw)

    monkeypatch.setattr(fused, "probe_unique_compact", compact)
    monkeypatch.setattr(fused, "hash_join_prepared", join)
    return compacted, two_step


def _mesh_jaxpr(root, n_dev, limit):
    """-> (jaxpr, flag_ops) of `root`'s distributed program over `n_dev`
    virtual devices, with the broadcast limit at `limit` rows (what
    DistFusedRunner._lower traces, without compiling it)."""
    from jax.sharding import PartitionSpec as P

    from cockroach_tpu.parallel import dist_flow, make_mesh
    from cockroach_tpu.parallel.repartition import shard_map
    from cockroach_tpu.util.settings import Settings

    s = Settings()
    old = s.get(dist_flow.BROADCAST_LIMIT)
    s.set(dist_flow.BROADCAST_LIMIT, limit)
    try:
        runner = dist_flow.DistFusedRunner(root, make_mesh(n_dev))
        scans, sources, chunks = runner._prime()
        sharded, repart, images = runner._materialize(scans, sources,
                                                      chunks)
        assert len(repart) == 1
        box = {}
        step = runner._make_step(scans, sharded, repart, box)
        specs = tuple((P("x"), P("x")) if id(sc) in sharded
                      else (P(), P()) for sc in scans)
        fn = shard_map(step, mesh=runner.mesh, in_specs=specs,
                       out_specs=P(), check_rep=False)
        jaxpr = jax.make_jaxpr(fn)(*(
            (images[id(sc)].bufs, images[id(sc)].ms) for sc in scans))
        return jaxpr, box["flag_ops"]
    finally:
        s.set(dist_flow.BROADCAST_LIMIT, old)


Q3_PROGRAMS = ["compact", "two_step", "mesh", "mesh_lanes"]


def _q3_program(program, monkeypatch):
    """-> (jaxpr, flag_ops, compacted, two_step) of Q3's program at this
    scale: `compact` is the served one-chip program, `two_step` the same
    with the one-step lowering switched off (PR 25's program), `mesh`
    the mesh cell's on four shards, `mesh_lanes` that without the
    planner's estimates (a tree built by hand carries none)."""
    from cockroach_tpu.exec.operators import ScanOp, walk_operators
    from cockroach_tpu.sql.bind import plan_sql
    from cockroach_tpu.sql.plan_compile import compile_plan
    from tests.test_sql import Q3_SQL

    _gen, cat = _sql_catalog()
    compacted, two_step = _record_joins(monkeypatch)
    if program == "two_step":
        monkeypatch.setattr(fused._Tracer, "_compactable",
                            lambda self, op: False)
    cp = compile_plan(plan_sql(Q3_SQL, cat), cat, 1 << 14, sql=Q3_SQL,
                      setting="tpu")
    if program.startswith("mesh"):
        if len(jax.devices()) < 4:
            pytest.skip("needs four virtual CPU devices")
        if program == "mesh_lanes":
            for op in walk_operators(cp.op):
                if not isinstance(op, ScanOp):
                    del op.est_rows
        # lineitem's four chunks shard one to a device; orders + customer
        # (two chunks) are over the limit, so the inner join goes BY_HASH
        jaxpr, flag_ops = _mesh_jaxpr(cp.op, 4, 1 << 14)
        return jaxpr, flag_ops, compacted, two_step
    _prog, args = cp.runner._prepare()
    compacted.clear()
    two_step.clear()
    scans = [n for n in walk_operators(cp.op) if isinstance(n, ScanOp)]
    prog, _box = cp.runner._make_prog([id(s) for s in scans])
    return jax.make_jaxpr(prog)(*args), None, compacted, two_step


@pytest.mark.parametrize("program", Q3_PROGRAMS)
def test_q3_program_sorts_per_join(program, monkeypatch):
    """Per join of Q3's fused program: exactly two sorts at lcap + rcap
    lanes, the key sort and the compaction's single-operand sort, none
    by destination and no sort at lcap; with the one-step lowering
    switched off (PR 25's program): key sort and resort at lcap + rcap,
    and the Shrink's one-operand u32 sort at lcap (a `(pred, i32)`
    argsort until PR 43). On a four-shard mesh (the mesh cell's program
    at this scale) the local semi join compacts and the BY_HASH inner
    join takes the two steps (_DistTracer._compactable), behind the
    router's two destination sorts, which carry a side's lanes as
    operands under ONE u32 key (destination x lanes + lane); the join's
    lanes follow the buckets, which the planner's estimates size (ISSUE
    30); without estimates (`mesh_lanes`) they are the buckets the lanes
    give. No sort of any of them is stable (the fourth member of a
    signature; PR 43): each key is total, or its ties reach no output."""
    jaxpr, flag_ops, compacted, two_step = _q3_program(program, monkeypatch)
    sorts = _sorts(jaxpr.jaxpr)
    if program.startswith("mesh"):
        # the semi join orders x customer is local to a shard (16,384
        # lanes a side) and compacts: key sort and compaction sort
        assert compacted == [(16384, 16384, "semi")]
        assert sorts.count((32768, "uint32", 2, False)) == 1
        # the router: one sort by (destination, lane) a side, carrying the
        # side's four column lanes as operands: a shard's 16,384 lineitem
        # lanes into buckets of 8,192, its 4,096 shrunk orders lanes into
        # buckets of 4,096 by their lanes. The planner expects 32,851
        # lineitem rows to pass the date (8,212 a shard, 2,053 a
        # destination: still 8,192) and 1,439 orders (359 a shard, 89 a
        # destination): buckets of 256
        assert sorts.count((16384, "uint32", 5, False)) == 1
        assert sorts.count((4096, "uint32", 5, False)) == 1
        build_bucket = {"mesh": 256, "mesh_lanes": 4096}[program]
        # so the inner join sees 4 x 8,192 probe and 4 x 256 (or 4,096)
        # build lanes, and takes the two steps: key sort, resort to probe
        # order, and the Shrink's one-operand sort over the probe lanes,
        # which is also what the semi join's compaction sorts
        n = 4 * 8192 + 4 * build_bucket
        assert two_step == [(32768, 4 * build_bucket, "inner")]
        assert sorts.count((n, "uint32", 2, False)) == 1
        assert sorts.count((n, "int32", 2, False)) == 1
        assert sorts.count((32768, "uint32", 1, False)) == 2
        assert not [s for s in sorts if s[1] == "bool"]
        # estimated buckets give the router's flag a restart target of its
        # own, ahead of the join's
        guards = [type(f).__name__ for f in flag_ops].count("_BucketGuard")
        assert guards == {"mesh": 1, "mesh_lanes": 0}[program]
        return
    assert (len(compacted), len(two_step)) == {
        "compact": (2, 0), "two_step": (0, 2)}[program]
    cummaxes = _cummaxes(jaxpr.jaxpr)
    for lcap, rcap, _how in compacted:
        at_n = sorted(s[1:] for s in sorts if s[0] == lcap + rcap)
        assert at_n == [("uint32", 1, False), ("uint32", 2, False)]
        assert (lcap, "uint32", 1, False) not in sorts
        # the run heads' positions: one 32-bit scan and none of 64
        # bits, where the resorting form's 62-bit payload takes two
        assert cummaxes.count(lcap + rcap) == 0
        assert _head_scans(jaxpr.jaxpr, lcap + rcap) == 1
    for lcap, rcap, _how in two_step:
        at_n = sorted(s[1:] for s in sorts if s[0] == lcap + rcap)
        assert at_n == [("int32", 2, False), ("uint32", 2, False)]
        assert sorts.count((lcap, "uint32", 1, False)) == 1
        assert cummaxes.count(lcap + rcap) == 2


def test_q3_aggregate_sorts_gathers_and_scatters_nothing():
    """Beside test_q3_program_sorts_per_join[compact]: under Q3's
    aggregate (its `crdb.op<N>.HashAggOp` scope) the program holds no
    sort, no gather and no scatter at all: the join below it left the
    Shrink's lanes grouped, and the aggregate reads them in place with
    scans over those lanes (ISSUE 36). The join's own C-row gathers are
    the join's."""
    from cockroach_tpu.exec.operators import ScanOp, walk_operators
    from cockroach_tpu.sql.bind import plan_sql
    from cockroach_tpu.sql.plan_compile import compile_plan
    from tests.test_sql import Q3_SQL

    _gen, cat = _sql_catalog()
    cp = compile_plan(plan_sql(Q3_SQL, cat), cat, 1 << 14, sql=Q3_SQL,
                      setting="tpu")
    _prog, args = cp.runner._prepare()
    scans = [n for n in walk_operators(cp.op) if isinstance(n, ScanOp)]
    prog, _box = cp.runner._make_prog([id(s) for s in scans])
    jaxpr, branches = _agg_branches(lambda: jax.make_jaxpr(prog)(*args))
    assert branches == {"ordered": 1}
    (shrink,) = [op for op in walk_operators(cp.op)
                 if type(op).__name__ == "ShrinkOp"
                 and type(op.child).__name__ == "JoinOp"
                 and op.child.how == "inner"]
    under = [(eqn.primitive.name, [v.aval.shape for v in eqn.invars])
             for stack, eqn in _scoped_eqns(jaxpr.jaxpr)
             # the innermost operator scope is the equation's owner (a
             # child is lowered inside its parent's scope)
             if re.findall(r"crdb\.op\d+\.(\w+)", stack)[-1:]
             == ["HashAggOp"]]
    names = {n for n, _shapes in under}
    banned = names & {"sort", "gather", "scatter", "scatter-add",
                      "scatter_add", "argsort"}
    assert not banned, banned
    # what it does hold: running sums and maxima over the Shrink's lanes
    # (in blocks: ops/prefix.py), and nothing over any wider array
    assert {"cumsum", "cummax"} <= names
    lanes = shrink.capacity
    assert all(int(np.prod(shape)) <= lanes
               for _n, shapes in under for shape in shapes)


# -- an aggregate over a compacting join reads the order the join left ------

_GROUPED = [("key", "fk"), ("dd", "d"), ("ee", "e"), ("w", "w")]


def _agg_over_shrunk_join(group_by, how="inner", build_mode="unique",
                          steps=None, capacity=512, dup_build=False):
    """-> HashAggOp(group_by) / MapOp(steps) / ShrinkOp(capacity) /
    JoinOp(how) of 256 probe rows (fk, v, w) against 64 build rows
    (k unique unless dup_build, d = k % 7, e = 3k). The default steps
    rename fk to `key`, d to `dd`, e to `ee`, keep w and compute x = 2v;
    a semi join emits no build column."""
    from cockroach_tpu.exec.operators import ShrinkOp

    rng = np.random.default_rng(36)
    pk = rng.integers(0, 400, 256)
    bk = (rng.integers(0, 40, 64) if dup_build
          else rng.permutation(400)[:64])
    probe = _int_scan({"fk": pk, "v": rng.integers(-50, 90, 256),
                       "w": rng.integers(0, 5, 256)}, 64)
    build = _int_scan({"k": bk, "d": bk % 7, "e": bk * 3}, 64)
    join = JoinOp(probe, build, ["fk"], ["k"], how=how,
                  build_mode=build_mode)
    if steps is None:
        steps = [("project", [(n, Col(c)) for n, c in _GROUPED
                              if how != "semi" or c in ("fk", "w")]
                  + [("x", Col("v") * 2)])]
    return HashAggOp(MapOp(ShrinkOp(join, capacity), steps), group_by,
                     [AggSpec("sum", "x", "s"),
                      AggSpec("count_star", None, "n"),
                      AggSpec("min", "x", "lo")])


def _agg_answers(make):
    """-> (rows of make() through the fused runner, the lowerings it
    counted, rows of a second make() through the streaming runtime)."""
    agg = make()
    names = list(agg.group_by) + ["s", "n", "lo"]
    got, branches = _agg_branches(lambda: collect(agg, fuse=True))
    want = collect(make(), fuse=False)
    return _sorted_rows(got, names), branches, _sorted_rows(want, names)


def _steps_filter_then_project():
    return [("filter", Col("w") > 1),
            ("project", [("key", Col("fk")), ("dd", Col("d")),
                         ("x", Col("v") * 2)])]


def _steps_project_then_filter():
    return [("project", [("key", Col("k")), ("dd", Col("d")),
                         ("x", Col("v") * 2), ("w", Col("w"))]),
            ("filter", Col("w") < 3)]


def _steps_computed_key():
    return [("project", [("key", Col("fk") + 0), ("dd", Col("d")),
                         ("x", Col("v") * 2)])]


@pytest.mark.parametrize("group_by,kw", [
    (["key", "dd"], {}),                       # renamed probe key + build
    (["ee", "key", "dd"], {}),                 # the key anywhere among them
    (["key", "dd"], {"steps": _steps_filter_then_project}),   # holes
    (["dd", "key"], {"steps": _steps_project_then_filter}),   # build's key
], ids=["renamed", "key_in_the_middle", "filter_below", "filter_above"])
def test_aggregate_over_a_compacting_join_aggregates_in_place(group_by, kw):
    """The precondition of _Tracer._ordered_input holds: counted
    `fused.agg_ordered`, nothing hashed, the streaming runtime's rows.
    A filter between the join and the aggregate punches holes into the
    runs and is grouped exactly (run ends against the next LIVE lane)."""
    kw = dict(kw, steps=kw["steps"]()) if "steps" in kw else kw
    got, branches, want = _agg_answers(
        lambda: _agg_over_shrunk_join(group_by, **kw))
    assert branches == {"ordered": 1}
    assert got == want and len(got) >= 10


@pytest.mark.parametrize("group_by,kw", [
    (["dd", "ee"], {}),                        # the join key is not there
    (["key", "w"], {}),                        # w is a PROBE column
    (["key", "dd"], {"steps": _steps_computed_key}),  # not a bare column
    (["key", "dd"], {"build_mode": "expand"}),         # build not unique
    (["key", "w"], {"how": "semi"}),           # probe-lane order, no key's
    (["key", "dd"], {"two_step": True}),       # the join did not compact
], ids=["no_join_key", "probe_column", "computed_key", "expand_build",
        "semi_join", "two_step"])
def test_aggregate_keeps_the_hash_path_where_the_order_is_not_proved(
        group_by, kw, monkeypatch):
    kw = dict(kw)
    if "steps" in kw:
        kw["steps"] = kw["steps"]()
    if kw.pop("two_step", False):
        monkeypatch.setattr(fused._Tracer, "_compactable",
                            lambda self, op: False)
    got, branches, want = _agg_answers(
        lambda: _agg_over_shrunk_join(group_by, **kw))
    assert branches == {"materialized": 1}
    assert got == want and len(got) > 5


# -- the one decision: which of the five lowerings an aggregate takes -------

def _keyed_scan(n=200, capacity=64, keys=("k",), spread=10):
    """`n` rows in chunks of `capacity`: integer keys in 0..spread-1 and
    one summed column."""
    rng = np.random.default_rng(44)
    data = {k: rng.integers(0, spread, n).astype(np.int64) for k in keys}
    data["v"] = rng.integers(-40, 90, n).astype(np.int64)
    return _int_scan(data, capacity)


def _coded_scan():
    """A dictionary-coded string key and a bool key (static domains)."""
    from cockroach_tpu.coldata.batch import BOOL, STRING

    rng = np.random.default_rng(45)
    schema = Schema([Field("mode", STRING, dict_ref="m"), Field("f", BOOL),
                     Field("v", INT)],
                    dicts={"m": np.array(["AIR", "MAIL", "SHIP"])})
    data = {"mode": rng.integers(0, 3, 200).astype(np.int32),
            "f": rng.random(200) > 0.5,
            "v": rng.integers(-40, 90, 200).astype(np.int64)}

    def chunks():
        yield data

    return ScanOp(schema, chunks, 64)


_SUMS = [AggSpec("sum", "v", "s"), AggSpec("count_star", None, "n")]

# row -> (the minimal tree, the lowering _agg_partial counts for it, the
# out_capacity it hands ops/agg.int_key_aggregate if it does), in the
# order _agg_partial asks
_LOWERINGS = {
    "ordered": (
        lambda: _agg_over_shrunk_join(["key", "dd"]), "ordered", None),
    "ordered_past_a_filter": (
        lambda: _agg_over_shrunk_join(
            ["key", "dd"], steps=_steps_filter_then_project()),
        "ordered", None),
    "int_key_compacted": (
        lambda: HashAggOp(_keyed_scan(), ["k"], _SUMS), "int_key", 256),
    "int_key_run_ends_view": (      # over 2^18 lanes: left to the reader
        lambda: HashAggOp(_keyed_scan(1000, 1 << 19), ["k"], _SUMS),
        "int_key", 0),
    "int_key_with_a_min_and_a_max": (   # extremes ride the sort too (PR 50)
        lambda: HashAggOp(_keyed_scan(), ["k"],
                          _SUMS + [AggSpec("min", "v", "lo"),
                                   AggSpec("max", "v", "hi")]),
        "int_key", 256),
    "int_key_off_for_a_first_value": (  # sums, counts and extremes only
        lambda: HashAggOp(_keyed_scan(), ["k"],
                          _SUMS + [AggSpec("any_not_null", "v", "a")]),
        "materialized", None),
    "by_slot_dictionary_and_bool": (
        lambda: HashAggOp(_coded_scan(), ["mode", "f"], _SUMS),
        "dense", None),
    "by_slot_ranged_key": (         # ahead of the int-key sort
        lambda: HashAggOp(_keyed_scan(), ["k"], _SUMS,
                          key_domains={"k": (0, 9)}), "dense", None),
    "by_slot_folded": (
        lambda: HashAggOp(_keyed_scan(), ["k"], _SUMS, workmem=-1,
                          key_domains={"k": (0, 9)}), "dense", None),
    "hash_folded": (
        lambda: HashAggOp(_keyed_scan(keys=("k", "j"), spread=5),
                          ["k", "j"], _SUMS, workmem=4000), "folded", None),
    "hash_whole": (
        lambda: HashAggOp(_keyed_scan(keys=("k", "j")), ["k", "j"], _SUMS),
        "materialized", None),
    "scalar": (
        lambda: HashAggOp(_keyed_scan(), [], _SUMS), "materialized", None),
}


@pytest.mark.parametrize("row", list(_LOWERINGS))
def test_agg_partial_counts_the_one_lowering_it_takes(row, monkeypatch):
    """_Tracer._agg_partial is the one decision: in place, the int-key
    sort, by slot, hash (folded over workmem, or whole). Each minimal
    tree counts exactly its lowering's event, once, and answers as the
    streaming runtime."""
    make, lowering, out_capacity = _LOWERINGS[row]
    handed = []
    real = fused.int_key_aggregate

    def spy(batch, key, aggs, **kw):
        handed.append(kw["out_capacity"])
        return real(batch, key, aggs, **kw)

    monkeypatch.setattr(fused, "int_key_aggregate", spy)
    got, branches = _agg_branches(lambda: collect(make(), fuse=True))
    assert branches == {lowering: 1}
    assert handed == ([] if out_capacity is None else [out_capacity])
    want = collect(make(), fuse=False)
    assert _null_rows(got) == _null_rows(want) and len(want["n"]) >= 1


@pytest.mark.parametrize("row,lowering,merge", [
    # a shard's partial is the int-key sort's, as on one chip (the mesh's
    # override went with ISSUE 45); few groups, so the gathered partials
    # merge by a hash aggregate on every shard
    ("int_key_compacted", "int_key", "hash"),
    # group g at lane g on every shard: D lanes merged pair by pair
    ("by_slot_ranged_key", "dense", "dense"),
])
def test_agg_partial_on_four_shards_merges_as_its_lowering_says(
        row, lowering, merge, monkeypatch):
    from cockroach_tpu.parallel import dist_flow, make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual CPU devices")
    seen = []

    def spy(name):
        real = getattr(dist_flow, name)

        def merge(first, *a, **kw):
            seen.append((name, first.capacity))
            return real(first, *a, **kw)

        monkeypatch.setattr(dist_flow, name, merge)

    spy("hash_aggregate")
    spy("dense_merge")
    make = _LOWERINGS[row][0]
    progs = dict(dist_flow._PROGS)
    dist_flow._PROGS.clear()    # another test's entry would skip the trace
    try:
        got, branches = _agg_branches(
            lambda: dist_flow.collect_distributed(make(), make_mesh(4),
                                                  strict=True))
    finally:
        dist_flow._PROGS.update(progs)
    assert branches == {lowering: 1}
    # 10 keys and the NULL slot; the gathered hash partials: 4 x a shard's
    assert seen == {"hash": [("hash_aggregate", 4 * 64)],
                    "dense": [("dense_merge", 11)] * 3}[merge]
    assert _null_rows(got) == _null_rows(collect(make(), fuse=False))


def test_shrink_overflow_under_the_ordered_aggregate_restarts_exactly():
    """More matches than the Shrink holds: the compacted lanes are a cut
    of the key order, the overflow flag discards the program's answer,
    widen() grows the Shrink and the NEW trace decides the precondition
    again: ordered both times, the rows exact."""
    made = []

    def make():
        made.append(_agg_over_shrunk_join(["key", "dd"], capacity=16))
        return made[-1]

    got, branches, want = _agg_answers(make)
    assert made[0].child.child.capacity == 16 * 16
    assert branches == {"ordered": 2}       # one a traced program
    assert got == want and len(got) >= 10


def test_join_fallback_under_the_ordered_aggregate_rehashes_exactly():
    """Duplicate build keys: the first trace compacts the join and
    aggregates in place, the join's fallback flag discards that answer,
    JoinOp.widen() leaves the unique path, the rerun's join does not
    compact and its aggregate hashes (twice: the row-matrix rung flags
    the duplicates too, then the general expansion answers): the order
    is never assumed."""
    got, branches, want = _agg_answers(
        lambda: _agg_over_shrunk_join(["key", "dd"], dup_build=True))
    assert branches == {"ordered": 1, "materialized": 2}
    assert got == want and len(got) >= 10


def test_mesh_q3_aggregates_by_hash():
    """On the four-shard mesh Q3's BY_HASH join takes the two steps
    (_DistTracer._compactable), so its local aggregate proves no order
    and hashes; the merge hashes an all_gather'ed concatenation."""
    from cockroach_tpu.sql.bind import plan_sql
    from cockroach_tpu.sql.plan_compile import compile_plan
    from tests.test_sql import Q3_SQL

    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual CPU devices")
    _gen, cat = _sql_catalog()
    cp = compile_plan(plan_sql(Q3_SQL, cat), cat, 1 << 14, sql=Q3_SQL,
                      setting="tpu")
    _out, branches = _agg_branches(lambda: _mesh_jaxpr(cp.op, 4, 1 << 14))
    assert branches == {"materialized": 1}


@pytest.mark.parametrize("query", ["q3", "q18"])
def test_q3_and_q18_sql_aggregate_in_place_and_match_oracles(query):
    """Q3's one aggregate and Q18's last (five keys: the last join's key
    and columns of its unique build) read the order their compacting
    join left: `fused.agg_ordered` 1 a traced program; Q18's first, over
    the scan, stays `fused.agg_int_key`. Oracle rows."""
    from cockroach_tpu.sql.session import Session
    from tests.test_sql import Q18_SQL, Q3_SQL

    gen, cat = _sql_catalog()
    sess = Session(cat, capacity=1 << 14)
    sess.execute("set vectorize = tpu")
    sql = Q3_SQL if query == "q3" else Q18_SQL.format(threshold=150)
    (_k, got, _s), events = _stage_events(lambda: sess.execute(sql),
                                          "fused.")
    traced = events["fused.compile"]
    branches = {name[len("fused.agg_"):]: n for name, n in events.items()
                if name.startswith("fused.agg_")}
    if query == "q3":
        assert branches == {"ordered": traced}
        rows = [(int(got["l_orderkey"][i]), int(got["revenue"][i]),
                 int(got["o_orderdate"][i]))
                for i in range(len(got["l_orderkey"]))]
        assert rows == Q.q3_oracle(gen)
    else:
        assert branches == {"ordered": traced, "int_key": traced}
        rows = [(int(got["c_name"][i]), int(got["c_custkey"][i]),
                 int(got["o_orderkey"][i]), int(got["o_orderdate"][i]),
                 int(got["o_totalprice"][i]), int(got["sum_qty"][i]))
                for i in range(len(got["c_name"]))]
        assert rows and rows == Q.q18_oracle(gen, 150)


def _shrunk_join(how, capacity=512, second_parent=False):
    """-> (root, probe keys, matched mask): a ShrinkOp over a JoinOp of
    256 probe rows against 64 unique build keys; with `second_parent`
    the join is also read by an aggregate that semi-joins back in."""
    from cockroach_tpu.exec.operators import ShrinkOp

    rng = np.random.default_rng(21)
    pk = rng.integers(0, 400, 256)
    bk = rng.permutation(400)[:64]
    probe = _int_scan({"fk": pk, "v": np.arange(256)}, 64)
    build = _int_scan({"k": bk, "d": bk * 7}, 64)
    join = JoinOp(probe, build, ["fk"], ["k"], how=how)
    root = ShrinkOp(join, capacity)
    if second_parent:
        counted = HashAggOp(join, ["fk"],
                            [AggSpec("count_star", None, "n")])
        root = JoinOp(root, counted, ["fk"], ["fk"], how="semi")
        assert fused._shared_ops(root) == {id(join)}
    return root, pk, np.isin(pk, bk)


def _matched_v(res):
    return sorted(res["v"].tolist())


def test_left_join_under_shrink_takes_two_steps():
    root, _pk, _hit = _shrunk_join("left")
    res, n = _join_compacts(lambda: collect(root, fuse=True))
    assert n == 0
    assert _matched_v(res) == list(range(256))


def test_inner_join_under_shrink_compacts():
    root, _pk, hit = _shrunk_join("inner")
    res, n = _join_compacts(lambda: collect(root, fuse=True))
    assert n == 1
    assert _matched_v(res) == np.nonzero(hit)[0].tolist()
    assert (res["d"] == res["fk"] * 7).all()
    assert (res["k"] == res["fk"]).all()


def test_compacted_join_overflow_widens_the_shrink():
    """More matches than the Shrink's capacity: its flag restarts the
    flow, widen() grows it 16x and the rerun answers right."""
    from cockroach_tpu.exec.operators import ShrinkOp

    root, _pk, hit = _shrunk_join("semi", capacity=16)
    res, n = _join_compacts(lambda: collect(root, fuse=True))
    assert root.capacity == 16 * ShrinkOp.GROWTH
    assert n == 2  # one per traced program
    assert _matched_v(res) == np.nonzero(hit)[0].tolist()


def test_join_read_by_two_parents_takes_two_steps():
    """A join another parent also reads keeps its probe lane layout
    (one materialization through _mat_memo, no compaction)."""
    root, _pk, hit = _shrunk_join("semi", second_parent=True)
    res, n = _join_compacts(lambda: collect(root, fuse=True))
    assert n == 0
    assert _matched_v(res) == np.nonzero(hit)[0].tolist()


@pytest.mark.parametrize("path", ["fused", "dist"])
@pytest.mark.parametrize("qn", [9, 18])
def test_shrunk_joins_match_oracles(qn, path):
    """Q9 and Q18 Shrink over selective joins (sql/plan.insert_shrinks):
    the one-step lowering fires on the single-chip tracer and inside
    shard_map (_DistTracer), and the answers are the oracles'."""
    gen = TPCH(sf=0.01)
    flow = Q.QUERIES[qn](gen, 1 << 12)
    if path == "dist":
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU mesh")
        from cockroach_tpu.parallel import make_mesh
        from cockroach_tpu.parallel.dist_flow import collect_distributed

        res, n = _join_compacts(
            lambda: collect_distributed(flow, make_mesh(8)))
    else:
        res, n = _join_compacts(lambda: collect(flow, fuse=True))
    assert n >= 1
    if qn == 9:
        nnames = gen.schema("nation").dicts["n_name"]
        got = {(str(nnames[int(nm)]), int(y)): int(v)
               for nm, y, v in zip(res["n_name"], res["o_year"],
                                   res["sum_profit"])}
        assert got == Q.q9_oracle(gen)
    else:
        got = [(int(cn), int(ck), int(ok), int(od), int(tp), int(q))
               for cn, ck, ok, od, tp, q in zip(
                   res["c_name"], res["c_custkey"], res["o_orderkey"],
                   res["o_orderdate"], res["o_totalprice"],
                   res["sum_qty"])]
        assert got == Q.q18_oracle(gen)


# -- a scalar aggregate within the operator budget aggregates once ---------

_AGG_ROWS, _AGG_CAP = 300, 64     # five chunks of 64 lanes (stacked: 8)


def _agg_table():
    """-> (columns as numpy: q, p and d in cents, n with NULLs; its mask)
    of table `t`, made from a fixed seed."""
    rng = np.random.default_rng(32)
    cols = {"q": rng.integers(1, 51, _AGG_ROWS),
            "p": rng.integers(100, 100000, _AGG_ROWS),
            "d": rng.integers(0, 11, _AGG_ROWS),
            "n": rng.integers(-50, 50, _AGG_ROWS)}
    return cols, rng.random(_AGG_ROWS) < 0.3


@pytest.fixture(scope="module")
def agg_catalog():
    from cockroach_tpu.sql.session import Session, SessionCatalog
    from cockroach_tpu.storage.mvcc import MVCCStore

    cat = SessionCatalog(MVCCStore())
    sess = Session(cat, capacity=_AGG_CAP)
    sess.execute("create table t (id int primary key, q int, "
                 "p decimal(2), d decimal(2), n int)")
    c, null = _agg_table()
    sess.execute("insert into t values " + ", ".join(
        f"({i}, {c['q'][i]}, {c['p'][i] / 100:.2f}, {c['d'][i] / 100:.2f}, "
        f"{'null' if null[i] else c['n'][i]})" for i in range(_AGG_ROWS)))
    return cat


def _agg_session(cat, *setup):
    from cockroach_tpu.sql.session import Session

    sess = Session(cat, capacity=_AGG_CAP)
    for text in ("set vectorize = tpu",) + setup:
        assert sess.execute(text)[0] == "ok"
    return sess


def _agg_branches(fn):
    """-> (fn(), {"materialized" | "folded" | "ordered" | "int_key":
    events} of the counters `fused.agg_<lowering>`: one event per
    HashAggOp per traced program)."""
    out, events = _stage_events(fn, "fused.agg_")
    return out, {name[len("fused.agg_"):]: n for name, n in events.items()}


def _q6_shape(c, null):
    m = (c["d"] >= 4) & (c["d"] <= 6) & (c["q"] < 24)
    return {"revenue": int((c["p"][m] * c["d"][m]).sum())}


def _count_star(c, null):
    return {"c": int((c["q"] < 24).sum())}


def _count_avg(c, null):
    m = (c["q"] < 24) & ~null
    return {"cn": int(m.sum()),
            "an": np.float32(c["n"][m].sum() / m.sum())}


def _min_max(c, null):
    m = (c["q"] < 24) & ~null
    return {"mn": int(c["n"][m].min()), "mx": int(c["n"][m].max()),
            "pmin": int(c["p"][c["q"] < 24].min())}


def _no_row(c, null):
    return {"revenue": None, "c": 0, "cn": 0, "mx": None}


@pytest.mark.parametrize("sql,values,want", [
    ("select sum(p * d) as revenue from t where d between $1 - 0.01 "
     "and $1 + 0.01 and q < $2", ("0.05", "24"), _q6_shape),
    ("select count(*) as c from t where q < 24", None, _count_star),
    ("select count(n) as cn, avg(n) as an from t where q < 24", None,
     _count_avg),
    ("select min(n) as mn, max(n) as mx, min(p) as pmin from t "
     "where q < 24", None, _min_max),
    ("select sum(p * d) as revenue, count(*) as c, count(n) as cn, "
     "max(n) as mx from t where q < 0", None, _no_row),
], ids=["q6_shape_bound", "count_star", "count_avg_nullable", "min_max",
        "no_row"])
def test_scalar_aggregate_materializes_within_workmem_else_folds(
        agg_catalog, monkeypatch, sql, values, want):
    """A scalar aggregate over a multi-chunk scan: one aggregation over
    the flat-unpacked image (no loop in the program) while the input fits
    the operator's workmem, the chunk fold under lax.scan once it does
    not; both programs answer as numpy does, in one row."""
    from cockroach_tpu.exec.operators import walk_operators

    texts = []
    lower = fused.lower_program

    def recording(fn, args):
        lowered = lower(fn, args)
        texts.append(lowered.as_text())
        return lowered

    monkeypatch.setattr(fused, "lower_program", recording)
    sess = _agg_session(agg_catalog)
    bound = None
    if values is not None:
        bound, text = sess.bind_params(sql, values)
        assert bound is not None and text == sql

    def run():
        _kind, payload, _schema = sess.execute(sql, params=bound)
        return payload

    got, branches = _agg_branches(run)
    assert branches == {"materialized": 1}
    with sess._prepared_mu:
        prep = sess._prepared.get(sql)
    agg, = [op for op in walk_operators(prep.op)
            if isinstance(op, HashAggOp)]
    # under any input's bytes (count(*) reads no column: 0 bytes a lane)
    agg.workmem = -1
    folded, branches = _agg_branches(run)
    assert branches == {"folded": 1}
    # (a one-chunk fold would have no loop either: the scan is multi-chunk)
    assert [t.count("stablehlo.while") for t in texts] == [0, 1]
    c, null = _agg_table()
    for name, value in want(c, null).items():
        for res in (got, folded):
            assert len(res[name]) == 1
            if value is None:
                assert not res[name + "__valid"][0], name
            else:
                assert res[name + "__valid"][0], name
                assert res[name][0] == value, name
    for name in got:
        np.testing.assert_array_equal(got[name], folded[name], name)


def test_scalar_aggregate_over_a_streamed_join_follows_the_budget():
    """A scalar aggregate above a chunkable join: within the budget the
    join runs whole (_mat_join) under ONE aggregation; over it the join
    probes chunk by chunk inside the fold. The same exact answer."""
    rng = np.random.default_rng(11)
    bk = rng.permutation(400)[:48]
    pk = rng.integers(0, 400, 300)
    pv = rng.integers(-30, 90, 300)
    hit = np.isin(pk, bk)

    def tree(**kw):
        probe = _int_scan({"fk": pk, "v": pv}, 64)   # 5 chunks of 64
        build = _int_scan({"k": bk}, 64)
        join = JoinOp(probe, build, ["fk"], ["k"], how="inner")
        return HashAggOp(join, [], [AggSpec("sum", "v", "s"),
                                    AggSpec("count_star", None, "c"),
                                    AggSpec("min", "v", "lo")], **kw)

    for kw, branch in (({}, "materialized"), ({"workmem": -1}, "folded")):
        res, branches = _agg_branches(lambda: collect(tree(**kw), fuse=True))
        assert branches == {branch: 1}
        assert (int(res["s"][0]), int(res["c"][0]), int(res["lo"][0])) == (
            int(pv[hit].sum()), int(hit.sum()), int(pv[hit].min()))


def test_scalar_aggregate_over_a_sharded_scan_matches_one_chip(agg_catalog):
    """On a four-shard mesh (_DistTracer) the local partial of a scalar
    aggregate is the same ONE aggregation over the shard's materialized
    lanes; the merged answer is the one-chip answer."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual CPU devices")
    from cockroach_tpu.parallel import make_mesh

    sql = ("select sum(p * d) as revenue, count(*) as c, count(n) as cn, "
           "min(n) as mn, max(n) as mx from t where q < 24")
    one, branches = _agg_branches(
        lambda: _agg_session(agg_catalog).execute(sql)[1])
    assert branches == {"materialized": 1}
    agg_catalog.with_mesh(make_mesh(4))
    try:
        sess = _agg_session(agg_catalog, "set distsql = always")
        dist, branches = _agg_branches(lambda: sess.execute(sql)[1])
        lines = sess.execute("explain " + sql)[1]
    finally:
        agg_catalog.with_mesh(None)
    assert branches == {"materialized": 1}
    assert "  scan t: sharded (5 chunks of 64 rows)" in lines
    c, null = _agg_table()
    m = c["q"] < 24
    assert int(dist["revenue"][0]) == int((c["p"][m] * c["d"][m]).sum())
    assert int(dist["c"][0]) == int(m.sum())
    for name in one:
        np.testing.assert_array_equal(one[name], dist[name], name)
