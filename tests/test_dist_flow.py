"""Distributed whole-query execution (parallel/dist_flow.py) on the
virtual 8-device CPU mesh — real TPC-H queries through the exec/ operator
trees, value-checked against oracles and against the single-chip executor
(the fakedist differential posture, SURVEY.md §4.2/§4.6).
"""

import jax
import numpy as np
import pytest

from cockroach_tpu.exec import collect
from cockroach_tpu.exec.operators import HashAggOp, JoinOp, ShrinkOp
from cockroach_tpu.ops.agg import AggSpec
from cockroach_tpu.parallel import make_mesh
from cockroach_tpu.parallel.dist_flow import (
    BROADCAST_LIMIT, DistFusedRunner, _Exchange, collect_distributed,
)
from cockroach_tpu.parallel.repartition import exchange_bucket
from cockroach_tpu.util.settings import Settings
from cockroach_tpu.workload.tpch import TPCH
from cockroach_tpu.workload import tpch_queries as Q
from tests.test_fused import _eqns, _int_scan, _mesh_jaxpr

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device CPU mesh")


def _mesh():
    return make_mesh(8)


def test_q3_distributed_matches_oracle():
    gen = TPCH(sf=0.01)
    res = collect_distributed(Q.q3(gen, 1 << 12), _mesh())
    got = sorted(zip(res["l_orderkey"].tolist(), res["revenue"].tolist(),
                     res["o_orderdate"].tolist()))
    assert got == sorted(Q.q3_oracle(gen))


def test_q9_distributed_matches_oracle():
    gen = TPCH(sf=0.01)
    res = collect_distributed(Q.q9(gen, 1 << 12), _mesh())
    nnames = gen.schema("nation").dicts["n_name"]
    got = {(str(nnames[int(n)]), int(y)): int(v)
           for n, y, v in zip(res["n_name"], res["o_year"],
                              res["sum_profit"])}
    assert got == Q.q9_oracle(gen)


def test_q1_distributed_matches_single_chip():
    gen = TPCH(sf=0.01)
    dist = collect_distributed(Q.q1(gen, 1 << 12), _mesh())
    from cockroach_tpu.exec import collect

    local = collect(Q.q1(gen, 1 << 12))
    for name in ("l_returnflag", "l_linestatus", "sum_qty", "sum_charge",
                 "count_order"):
        np.testing.assert_array_equal(dist[name], local[name])


def test_repartitioned_join_path():
    """Force the BY_HASH a2a path (P3) by shrinking the broadcast limit:
    results must stay exact when builds are co-partitioned over the mesh."""
    gen = TPCH(sf=0.01)
    s = Settings()
    old = s.get(BROADCAST_LIMIT)
    s.set(BROADCAST_LIMIT, 4096)  # orders/cust builds exceed this at 0.01
    try:
        runner = DistFusedRunner(Q.q3(gen, 1 << 12), _mesh())
        _, stacked, chunks = runner._prime()
        _sharded, repart = runner._classify(chunks)
        assert repart, "expected at least one repartitioned join"
        res = collect_distributed(Q.q3(gen, 1 << 12), _mesh())
        got = sorted(zip(res["l_orderkey"].tolist(),
                         res["revenue"].tolist(),
                         res["o_orderdate"].tolist()))
        assert got == sorted(Q.q3_oracle(gen))
    finally:
        s.set(BROADCAST_LIMIT, old)


def test_q18_distributed_matches_oracle():
    gen = TPCH(sf=0.01)
    res = collect_distributed(Q.q18(gen, capacity=1 << 12), _mesh())
    got = [(int(cn), int(ck), int(ok), int(od), int(tp), int(q))
           for cn, ck, ok, od, tp, q in zip(
               res["c_name"], res["c_custkey"], res["o_orderkey"],
               res["o_orderdate"], res["o_totalprice"], res["sum_qty"])]
    assert got == Q.q18_oracle(gen)


# -- the join is lowered once, in exec/fused.py (ISSUE 29) -------------------

def _shared_join(two_parents: bool):
    """-> (root, join): a BY_HASH join of 2,048 probe rows against 512
    unique keys, summed per key under a Shrink; with `two_parents` a
    second aggregate counts the SAME JoinOp per key and joins back in."""
    rng = np.random.default_rng(29)
    fk = rng.integers(0, 600, 2048)
    bk = rng.permutation(600)[:512]
    join = JoinOp(_int_scan({"fk": fk, "v": np.arange(2048)}, 256),
                  _int_scan({"k": bk, "d": bk * 7}, 128),
                  ["fk"], ["k"], how="inner")
    root = HashAggOp(ShrinkOp(join, 4096), ["fk"],
                     [AggSpec("sum", "v", "sv")])
    if two_parents:
        counted = HashAggOp(ShrinkOp(join, 4096), ["k"],
                            [AggSpec("count_star", None, "n")])
        root = JoinOp(root, counted, ["fk"], ["k"], how="inner")
    return root, join


def _classified_by_hand(monkeypatch, join):
    """Both of `join`'s scans sharded, the join BY_HASH. By hand because
    _classify declines the two-parent tree (it counts the scans under the
    root join's build, not the aggregate's groups, finds them over the
    limit and will not nest a repartition in a build); the tracer takes
    it: both aggregates merge across the mesh, so the root joins two
    replicated sides on every device."""
    n_dev = 4
    repart = {id(join): _Exchange(exchange_bucket(256, n_dev),
                                  exchange_bucket(512 // n_dev, n_dev))}
    monkeypatch.setattr(
        DistFusedRunner, "_classify",
        lambda self, chunks: ({id(join.probe), id(join.build)}, repart))


def test_join_two_parents_read_is_routed_once(monkeypatch):
    """_DistTracer materializes through _Tracer._mat's memo: a BY_HASH
    join that two parents read is traced, routed and flagged once."""
    counts = {}
    for two_parents in (False, True):
        root, join = _shared_join(two_parents)
        _classified_by_hand(monkeypatch, join)
        jaxpr, flag_ops = _mesh_jaxpr(root, 4, 1 << 18)
        counts[two_parents] = sum(eqn.primitive.name == "all_to_all"
                                  for eqn in _eqns(jaxpr.jaxpr))
        assert sum(f is join for f in flag_ops) == 1
    # a side's two columns and its selection lane, both sides
    assert counts == {False: 6, True: 6}


def test_join_two_parents_read_answers_as_one_chip(monkeypatch):
    root, join = _shared_join(True)
    _classified_by_hand(monkeypatch, join)
    dist = collect_distributed(root, make_mesh(4), strict=True)
    local = collect(_shared_join(True)[0], fuse=True)
    names = ("fk", "sv", "k", "n")
    rows = lambda res: sorted(zip(*(res[c].tolist() for c in names)))
    assert rows(dist) == rows(local) and len(rows(dist)) > 400


# -- buckets from the planner's estimate (ISSUE 30) --------------------------

@pytest.mark.parametrize("by_lanes, by_est, parts, want", [
    (1 << 20, None, 1, 1 << 20),      # no estimate: the lanes' bucket
    (1 << 20, 1 << 19, 1, 1 << 19),   # an estimate: its bucket
    (1 << 18, 1 << 19, 1, 1 << 18),   # never more than the lanes give
    (1 << 16, 1 << 19, 16, 1 << 15),  # a streamed chunk takes its share
    (1 << 16, 1 << 19, 64, 1 << 13),
    (1 << 16, 1 << 9, 64, 64),        # exchange_bucket's floor
    (1 << 16, None, 64, 1 << 16),
])
def test_side_bucket_rule(by_lanes, by_est, parts, want):
    from cockroach_tpu.parallel.dist_flow import side_bucket

    assert side_bucket(by_lanes, by_est, 4, parts) == want


def _estimated_join(probe_est=None, build_est=None):
    """-> (runner, join): _shared_join's BY_HASH join on a four-device
    mesh, with the planner's stamp on its sides where given."""
    root, join = _shared_join(False)
    for side, est in ((join.probe, probe_est), (join.build, build_est)):
        if est is not None:
            side.est_rows = est
    return DistFusedRunner(root, make_mesh(4)), join


# the pair a tree without estimates gets: a probe chunk's 256 lanes, and a
# shard's share of the build's 512 scan rows (the parent's, ISSUE 29)
_LANES_PAIR = (exchange_bucket(256, 4), exchange_bucket(512 // 4, 4))


@pytest.mark.parametrize("probe_est, build_est, want", [
    (None, None, _Exchange(*_LANES_PAIR)),
    # 4,000 rows: 1,000 a shard, 250 a destination, twice the room: 512
    (4000, None, _Exchange(*_LANES_PAIR, 512, None)),
    # 520 build rows: 130 a shard, 32 a destination: the floor of 64,
    # which is what the lanes give too, so the estimate adds nothing
    (4000, 520, _Exchange(*_LANES_PAIR, 512, None)),
    (None, 100, _Exchange(*_LANES_PAIR)),
    # an estimate beyond the lanes is kept for the probe (the whole side's
    # lanes are the tracer's to know) and capped there (side_bucket)
    (1 << 20, None, _Exchange(*_LANES_PAIR, 1 << 17, None)),
])
def test_exchange_is_sized_from_the_estimate_where_there_is_one(
        probe_est, build_est, want):
    runner, join = _estimated_join(probe_est, build_est)
    assert runner._exchange(join, 512) == want
    assert want.estimated == (want.probe_est is not None)
    # a full bucket has sent the join back to its lanes: the estimates
    # no longer count, whatever they say
    join._lanes_buckets = 1
    assert runner._exchange(join, 512) == _Exchange(*_LANES_PAIR)


def test_a_build_estimate_under_the_lanes_sizes_the_build_bucket():
    root, join = _shared_join(False)
    join.build.est_rows = 600
    runner = DistFusedRunner(root, make_mesh(4))
    # 16,384 scan rows under the build: 4,096 a shard, buckets of 2,048;
    # 600 estimated: 150 a shard, 37 a destination: 128
    x = runner._exchange(join, 16384)
    assert (x.build, x.build_est) == (2048, 128)
    assert x.estimated and x.probe_est is None


def _config_key_of(probe_est):
    runner, join = _estimated_join(probe_est)
    scans, _sources, chunks = runner._prime()
    sharded, repart = runner._classify(chunks)
    assert set(repart) == {id(join)}
    layout = {id(sc): ("sharded" if id(sc) in sharded else "replicated", 2)
              for sc in scans}
    return runner._config_key(layout, repart)


def test_config_key_carries_the_estimates_bucket_pair():
    """Two states of the statistics share a program until their estimates
    round to different buckets; a tree without an estimate keeps the
    parent's key, entry for entry."""
    s = Settings()
    old = s.get(BROADCAST_LIMIT)
    s.set(BROADCAST_LIMIT, 256)   # the build's 512 rows go BY_HASH
    try:
        bare, a, b, c = (_config_key_of(e) for e in (None, 3000, 4000, 5000))
    finally:
        s.set(BROADCAST_LIMIT, old)
    joins = [[e for e in k if e[0] == "JoinOp"] for k in (bare, a, b, c)]
    # 3,000 and 4,000 rows both give buckets of 512; 5,000 gives 1,024
    assert joins[1] == joins[2] and a == b
    assert joins[3] != joins[2] and c != b
    assert [j[0][5:] for j in joins] == [(), (512, None), (512, None),
                                         (1024, None)]
    # without an estimate: the five entries the key has without any (the
    # operator's name, expansion, workmem, seed, build mode)
    assert len(joins[0][0]) == 5 and len(bare) == len(a)
    assert [e for e in bare if e[0] != "JoinOp"] == [
        e for e in a if e[0] != "JoinOp"]


def test_a_low_estimate_restarts_once_on_the_lanes_buckets():
    """The router's flag has a restart target of its own where the
    buckets are estimated: a full bucket sends the join back to the
    buckets its lanes give, in one restart, and the answer is the
    single-chip one."""
    from cockroach_tpu.parallel import dist_flow
    from cockroach_tpu.util.metric import default_registry

    want = collect(_shared_join(False)[0])
    bucket_restarts = default_registry().counter(
        "sql_distsql_bucket_restarts_total")
    flow_restarts = default_registry().counter("sql_flow_restarts_total")
    s = Settings()
    old = s.get(BROADCAST_LIMIT)
    s.set(BROADCAST_LIMIT, 256)
    try:
        # 2,048 probe rows over four shards, 128 for each destination of a
        # shard, into buckets sized for 40 rows in all: 64
        root, join = _shared_join(False)
        join.probe.est_rows = 40
        b0, f0 = bucket_restarts.value(), flow_restarts.value()
        got = collect_distributed(root, make_mesh(4), strict=True)
        assert bucket_restarts.value() == b0 + 1
        assert flow_restarts.value() == f0 + 1
        assert join._lanes_buckets == 1 and join.expansion == 1
        estimated, lanes = dist_flow._PROGS.values()
        assert "_BucketGuard" in estimated.flag_types
        assert "_BucketGuard" not in lanes.flag_types
        assert estimated.a2a_bytes < lanes.a2a_bytes
        # the widened tree runs again without a restart or a compile
        again = collect_distributed(root, make_mesh(4), strict=True)
        assert bucket_restarts.value() == b0 + 1
        assert len(dist_flow._PROGS) == 2
    finally:
        s.set(BROADCAST_LIMIT, old)
    for res in (got, again):
        order = np.argsort(res["fk"])
        np.testing.assert_array_equal(res["fk"][order],
                                      np.sort(want["fk"]))
        np.testing.assert_array_equal(
            res["sv"][order], want["sv"][np.argsort(want["fk"])])


def test_a_sound_estimate_answers_without_a_restart():
    """Buckets from an estimate that holds: the router's flag is a flag
    of its own, stays down, and a cached program finds its guard again
    on a new tree of the same plan."""
    from cockroach_tpu.parallel import dist_flow
    from cockroach_tpu.util.metric import default_registry

    want = collect(_shared_join(False)[0])
    bucket_restarts = default_registry().counter(
        "sql_distsql_bucket_restarts_total")
    s = Settings()
    old = s.get(BROADCAST_LIMIT)
    s.set(BROADCAST_LIMIT, 256)
    try:
        b0 = bucket_restarts.value()
        for _ in range(2):          # the second tree hits the program cache
            root, join = _shared_join(False)
            join.probe.est_rows = 1100   # 275 a shard: buckets of 256
            res = collect_distributed(root, make_mesh(4), strict=True)
            order = np.argsort(res["fk"])
            np.testing.assert_array_equal(
                res["sv"][order], want["sv"][np.argsort(want["fk"])])
            assert getattr(join, "_lanes_buckets", 0) == 0
        (prog,) = dist_flow._PROGS.values()
        assert prog.flag_types.count("_BucketGuard") == 1
        guards = [f for f in prog.flag_ops(list(dist_flow.walk_operators(root)))
                  if isinstance(f, dist_flow._BucketGuard)]
        assert [g.op for g in guards] == [join]
        assert bucket_restarts.value() == b0
    finally:
        s.set(BROADCAST_LIMIT, old)
