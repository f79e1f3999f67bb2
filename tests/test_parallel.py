"""M6 distribution tests on the virtual 8-device CPU mesh.

The analog of the reference's fakedist logictest configs (3 in-process
nodes + fake span resolver, SURVEY.md §4.2): real collectives, no real
chips. Every path here is exactly what runs on a TPU slice.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from cockroach_tpu.coldata.batch import Batch, Column
from cockroach_tpu.ops.agg import AggSpec
from cockroach_tpu.parallel import (
    distributed_aggregate, distributed_hash_join, make_mesh, shard_batch,
)


def make_batch(cols, sel=None):
    out = {}
    cap = None
    for n, (v, val) in cols.items():
        v = np.asarray(v)
        cap = len(v)
        out[n] = Column(jnp.asarray(v),
                        None if val is None else jnp.asarray(np.asarray(val)))
    if sel is None:
        sel = np.ones(cap, dtype=bool)
    sel = jnp.asarray(np.asarray(sel))
    return Batch(out, sel, jnp.sum(sel).astype(jnp.int32))


def test_shard_batch_layout():
    mesh = make_mesh(8)
    b = make_batch({"k": (np.arange(64, dtype=np.int64), None)})
    sb = shard_batch(b, mesh, "x")
    assert sb.col("k").values.sharding.is_fully_replicated is False
    assert sb.length.sharding.is_fully_replicated


def test_distributed_aggregate_matches_local():
    mesh = make_mesh(8)
    rng = np.random.default_rng(0)
    n = 1024
    k = rng.integers(0, 17, n).astype(np.int64)
    v = rng.integers(0, 1000, n).astype(np.int64)
    b = shard_batch(make_batch({"k": (k, None), "v": (v, None)}), mesh)
    out, ovf = jax.jit(
        lambda bb: distributed_aggregate(
            bb, mesh, ["k"], [AggSpec("sum", "v", "s"),
                              AggSpec("count_star", None, "n"),
                              AggSpec("min", "v", "mn")])
    )(b)
    assert not bool(ovf)
    ng = int(out.length)
    assert ng == len(set(k.tolist()))
    got = {}
    kk = np.asarray(out.col("k").values)
    for i in range(ng):
        got[int(kk[i])] = (int(out.col("s").values[i]),
                           int(out.col("n").values[i]),
                           int(out.col("mn").values[i]))
    for key in set(k.tolist()):
        m = k == key
        assert got[key] == (v[m].sum(), m.sum(), v[m].min())


def test_distributed_aggregate_respects_sel():
    mesh = make_mesh(8)
    n = 64
    k = np.zeros(n, dtype=np.int64)
    v = np.ones(n, dtype=np.int64)
    sel = np.arange(n) % 2 == 0
    b = shard_batch(make_batch({"k": (k, None), "v": (v, None)}, sel=sel), mesh)
    out, ovf = distributed_aggregate(b, mesh, ["k"],
                                     [AggSpec("count_star", None, "n")])
    assert not bool(ovf)
    assert int(out.col("n").values[0]) == 32


def test_distributed_aggregate_partial_cap_overflow():
    """More live groups on a chip than partial_cap => overflow flag set
    and result length clamped (no silent group drop)."""
    mesh = make_mesh(8)
    n = 512
    k = np.arange(n, dtype=np.int64)  # 64 distinct groups per chip
    v = np.ones(n, dtype=np.int64)
    b = shard_batch(make_batch({"k": (k, None), "v": (v, None)}), mesh)
    out, ovf = distributed_aggregate(
        b, mesh, ["k"], [AggSpec("sum", "v", "s")], partial_cap=16)
    assert bool(ovf)
    assert int(out.length) <= 8 * 16


def test_distributed_hash_join_matches_oracle():
    mesh = make_mesh(8)
    rng = np.random.default_rng(1)
    lk = rng.integers(0, 50, 512).astype(np.int64)
    rk = rng.integers(0, 50, 256).astype(np.int64)
    rv = np.arange(256, dtype=np.int64)
    probe = shard_batch(make_batch({"lk": (lk, None)}), mesh)
    build = shard_batch(make_batch({"rk": (rk, None), "rv": (rv, None)}), mesh)
    out, ovf = jax.jit(
        lambda p, b: distributed_hash_join(
            p, b, mesh, ["lk"], ["rk"], bucket_cap=512, out_capacity=4096)
    )(probe, build)
    assert not bool(ovf)
    want = sum(1 for a in lk for c in rk if a == c)
    assert int(out.length) == want
    # spot-check pairs
    sel = np.asarray(out.sel)
    got_l = np.asarray(out.col("lk").values)[sel]
    got_r = np.asarray(out.col("rk").values)[sel]
    np.testing.assert_array_equal(got_l, got_r)


def test_distributed_join_overflow_flag():
    mesh = make_mesh(8)
    lk = np.zeros(256, dtype=np.int64)  # all rows hash to one device
    rk = np.zeros(256, dtype=np.int64)
    probe = shard_batch(make_batch({"lk": (lk, None)}), mesh)
    build = shard_batch(make_batch({"rk": (rk, None), "rv": (lk, None)}), mesh)
    out, ovf = distributed_hash_join(
        probe, build, mesh, ["lk"], ["rk"], bucket_cap=8, out_capacity=64)
    assert bool(ovf)


def test_host_mesh_runs_distributed_query():
    """The 2-D (hosts, chips) DCN mesh (parallel/mesh.host_mesh) carries
    a real distributed query: rows shard over the intra-host 'chips'
    axis exactly as over a flat ICI mesh — the flat-vs-2-D choice is
    pure topology (VERDICT r4: host_mesh must not stay dead code)."""
    from cockroach_tpu.parallel.dist_flow import collect_distributed
    from cockroach_tpu.parallel.mesh import host_mesh
    from cockroach_tpu.workload.tpch import TPCH
    from cockroach_tpu.workload import tpch_queries as Q

    mesh = host_mesh(per_host=4)  # 1 host x 4 chips on the CPU mesh
    assert mesh.axis_names == ("hosts", "chips")
    gen = TPCH(sf=0.01)
    res = collect_distributed(Q.q6(gen, 1 << 12), mesh, axis="chips")
    assert int(res["revenue"][0]) == Q.q6_oracle(gen)


def test_make_mesh_rounds_non_pow2_down_with_warning():
    """Collectives + pow2 shard buckets assume a pow2 axis: a ragged
    device count rounds DOWN loudly instead of stranding the tail."""
    import pytest

    if len(jax.devices()) < 6:
        pytest.skip("needs >= 6 devices")
    with pytest.warns(UserWarning, match="power of two"):
        mesh = make_mesh(6)
    assert int(mesh.shape["x"]) == 4


def test_host_mesh_errors_are_actionable():
    import pytest

    from cockroach_tpu.parallel.mesh import host_mesh

    with pytest.raises(ValueError,
                       match="at least one device per process"):
        host_mesh(per_host=0)
    with pytest.raises(ValueError, match="needs"):
        host_mesh(per_host=1 << 20)


# ------------------------------------------------- the router, bit for bit --

def _numpy_router(cols, sel, dest, n_dev, bucket_cap):
    """The plain router: walk each device's rows in order, append a live
    row to its destination's list while the bucket has room; device d
    then receives bucket d of every source, in source order, each padded
    with zeros to bucket_cap."""
    per_dev = len(sel) // n_dev
    recv = {n: [[np.zeros(bucket_cap, v.dtype) for _ in range(n_dev)]
                for _ in range(n_dev)] for n, v in cols.items()}
    recv_sel = np.zeros((n_dev, n_dev, bucket_cap), bool)
    overflow = np.zeros(n_dev, bool)
    for src in range(n_dev):
        filled = [0] * n_dev
        for i in range(src * per_dev, (src + 1) * per_dev):
            if not sel[i]:
                continue
            d = int(dest[i])
            if filled[d] == bucket_cap:
                overflow[src] = True
                continue
            for n, v in cols.items():
                recv[n][d][src][filled[d]] = v[i]
            recv_sel[d, src, filled[d]] = True
            filled[d] += 1
    return ({n: np.concatenate([np.concatenate(r) for r in v])
             for n, v in recv.items()}, recv_sel.reshape(-1), overflow)


def _router_case(name):
    """(columns, sel, dest-or-None, bucket_cap) of 4 devices x 64 rows;
    a column is (values, validity or None). dest None: BY_RANGE on "v"
    with an empty range."""
    n_dev, per_dev = 4, 64
    n = n_dev * per_dev
    rng = np.random.default_rng(28)
    v = rng.integers(-1 << 62, 1 << 62, n, dtype=np.int64)
    cols = {"v": (v, None)}
    sel = rng.random(n) > 0.25
    dest = rng.integers(0, n_dev, n).astype(np.int32)
    bucket_cap = 32
    if name == "one_destination_overflows":
        dest[:] = 2
    elif name == "run_starts_past_cap_minus_bucket":
        # device rows 0..39 go to 0, the rest to 3: 3's run starts at
        # row 40 (less the dead rows), past 64 - 48
        sel[:] = True
        dest[:] = np.where(np.arange(n) % per_dev < 40, 0, 3)
        bucket_cap = 48
    elif name == "all_dead":
        sel[:] = False
    elif name == "validity_lane":
        cols["w"] = (rng.integers(0, 1 << 31, n).astype(np.int32),
                     rng.random(n) > 0.5)
    elif name == "bool_and_int64_high_words":
        cols["b"] = (rng.random(n) > 0.5, None)
        # equal low words: only the high words tell the rows apart
        cols["h"] = ((np.arange(n, dtype=np.int64) << 32) | 7, None)
    elif name == "every_destination_overflows":
        # 16 live rows a destination a device into buckets of 8, dead
        # rows between them: each run keeps its FIRST 8 rows in lane
        # order, which is what the (destination, lane) key sorts by
        sel[:] = np.arange(n) % 5 != 0
        dest[:] = np.arange(n) % n_dev
        bucket_cap = 8
    elif name == "one_live_row":
        sel[:] = np.arange(n) == 77
    elif name == "empty_range":
        cols["v"] = (rng.integers(0, 400, n).astype(np.int64), None)
        dest = None
    else:
        assert name == "uniform", name
    return cols, sel, dest, bucket_cap


@pytest.mark.parametrize("case", [
    "uniform", "one_destination_overflows",
    "run_starts_past_cap_minus_bucket", "all_dead", "validity_lane",
    "bool_and_int64_high_words", "every_destination_overflows",
    "one_live_row", "empty_range"])
def test_router_matches_numpy_router_bit_for_bit(case):
    """The router's one sort is keyed on `destination x lanes + lane`, a
    unique u32, and unstable (PR 43); the plain router walks the rows in
    order, which is what the stable sort by destination alone gave."""
    from jax.sharding import PartitionSpec as P
    from cockroach_tpu.parallel.repartition import (
        _batch_pspecs, _route_and_exchange, range_repartition_local,
        shard_map,
    )

    n_dev = 4
    mesh = make_mesh(n_dev)
    cols, sel, dest, bucket_cap = _router_case(case)
    batch = make_batch(cols, sel=sel)
    # device 2 owns [200, 200): nothing
    bounds = np.array([100, 200, 200], np.int64)
    if dest is None:
        dest = np.searchsorted(bounds, cols["v"][0], side="right")

    def local(b, d):
        if case == "empty_range":
            out, ovf = range_repartition_local(
                b, "v", jnp.asarray(bounds), "x", n_dev, bucket_cap)
        else:
            out, ovf = _route_and_exchange(b, d, "x", n_dev, bucket_cap)
        return (out.columns, out.sel), ovf[None]

    (got, got_sel), got_ovf = jax.jit(shard_map(
        local, mesh=mesh, in_specs=(_batch_pspecs(batch, "x"), P("x")),
        out_specs=(P("x"), P("x")), check_rep=False))(
            batch, jnp.asarray(dest, dtype=jnp.int32))

    flat = {n: v for n, (v, _) in cols.items()}
    flat.update({n + "__valid": val for n, (_, val) in cols.items()
                 if val is not None})
    want, want_sel, want_ovf = _numpy_router(flat, sel, dest, n_dev,
                                             bucket_cap)
    assert np.array_equal(np.asarray(got_ovf), want_ovf)
    assert want_ovf.any() == (case in ("one_destination_overflows",
                                       "every_destination_overflows"))
    assert np.array_equal(np.asarray(got_sel), want_sel)
    for n, (_, val) in cols.items():
        values = np.asarray(got[n].values)
        assert values.dtype == want[n].dtype
        assert np.array_equal(values, want[n]), n
        assert (got[n].validity is None) == (val is None)
        if val is not None:
            assert np.array_equal(np.asarray(got[n].validity),
                                  want[n + "__valid"]), n


@pytest.mark.parametrize("router", ["by_hash", "by_range"])
def test_router_lowers_to_all_to_all_and_no_scatter(router):
    """Everything in this program stems from the router: it exchanges
    with all_to_all, and no scatter places rows in buckets one element
    at a time (PERF.md section 6, PR 27 and PR 28)."""
    from jax.sharding import PartitionSpec as P
    from cockroach_tpu.parallel.repartition import (
        _batch_pspecs, hash_repartition_local, range_repartition_local,
        shard_map,
    )

    n_dev = 4
    mesh = make_mesh(n_dev)
    n = n_dev * 64
    batch = make_batch({
        "k": (np.arange(n, dtype=np.int64), None),
        "w": (np.arange(n, dtype=np.int32), np.arange(n) % 3 == 0)})

    def local(b):
        if router == "by_hash":
            out, ovf = hash_repartition_local(b, ("k",), "x", n_dev, 32)
        else:
            out, ovf = range_repartition_local(
                b, "k", jnp.asarray([64, 128, 192]), "x", n_dev, 32)
        return (out.columns, out.sel), ovf[None]

    text = jax.jit(shard_map(
        local, mesh=mesh, in_specs=(_batch_pspecs(batch, "x"),),
        out_specs=(P("x"), P("x")), check_rep=False)).lower(batch).as_text()
    assert "all_to_all" in text
    assert "scatter" not in text
