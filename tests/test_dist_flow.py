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
    BROADCAST_LIMIT, DistFusedRunner, collect_distributed,
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
    repart = {id(join): (exchange_bucket(256, n_dev),
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
