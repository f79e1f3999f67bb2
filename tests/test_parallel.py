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
from cockroach_tpu.parallel import make_mesh, shard_batch


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


# ------------------------------------------- the BY_HASH router's contract --

ROUTE_DEV = 4


def _route(batch, keys, bucket_cap, seed=1):
    """hash_repartition_local under shard_map on four virtual devices ->
    ({column: (device, lanes) values}, (device, lanes) sel, (device,)
    overflow): what each device RECEIVED."""
    from jax.sharding import PartitionSpec as P
    from cockroach_tpu.parallel.repartition import (
        _batch_pspecs, hash_repartition_local, shard_map,
    )

    def local(b):
        out, ovf = hash_repartition_local(b, tuple(keys), "x", ROUTE_DEV,
                                          bucket_cap, seed=seed)
        return ({n: c.values for n, c in out.columns.items()}, out.sel,
                ovf[None])

    cols, sel, ovf = jax.jit(shard_map(
        local, mesh=make_mesh(ROUTE_DEV),
        in_specs=(_batch_pspecs(batch, "x"),),
        out_specs=(P("x"), P("x"), P("x")), check_rep=False))(batch)
    return ({n: np.asarray(v).reshape(ROUTE_DEV, -1)
             for n, v in cols.items()},
            np.asarray(sel).reshape(ROUTE_DEV, -1), np.asarray(ovf))


def _named_device(batch, keys, seed=1):
    """The device each row's key hash names (the router's own rule: the
    hash's high bits, so the low ones stay free for the local join)."""
    from cockroach_tpu.ops.hash import hash_columns

    h = np.asarray(hash_columns(batch, tuple(keys), seed=seed))
    return ((h >> np.uint64(42)) % np.uint64(ROUTE_DEV)).astype(np.int64)


def test_hash_router_delivers_every_live_row_once_where_its_hash_names():
    rng = np.random.default_rng(44)
    n = ROUTE_DEV * 64
    k = rng.integers(0, 1 << 40, n).astype(np.int64)
    row = np.arange(n, dtype=np.int64)
    b = make_batch({"k": (k, None), "row": (row, None)})
    cols, sel, ovf = _route(b, ["k"], bucket_cap=64)
    assert not ovf.any()
    dest = _named_device(b, ["k"])
    assert len(set(dest.tolist())) == ROUTE_DEV     # the case spreads
    arrived = {}
    for d in range(ROUTE_DEV):
        for r, key in zip(cols["row"][d][sel[d]], cols["k"][d][sel[d]]):
            assert int(r) not in arrived                 # once
            arrived[int(r)] = d
            assert int(key) == int(k[r])                 # with its columns
    assert arrived == {i: int(dest[i]) for i in range(n)}


def test_hash_router_sends_no_dead_lane():
    rng = np.random.default_rng(45)
    n = ROUTE_DEV * 64
    k = rng.integers(0, 1000, n).astype(np.int64)
    live = rng.random(n) > 0.6
    b = make_batch({"k": (k, None), "row": (np.arange(n), None)}, sel=live)
    cols, sel, ovf = _route(b, ["k"], bucket_cap=64)
    assert not ovf.any()
    got = sorted(int(r) for d in range(ROUTE_DEV)
                 for r in cols["row"][d][sel[d]])
    assert got == np.flatnonzero(live).tolist()
    # a lane that carries no row carries nothing of a dead one either
    assert not cols["k"][~sel].any() and not cols["row"][~sel].any()


def test_hash_router_full_bucket_raises_the_flag_and_keeps_the_first_rows():
    rng = np.random.default_rng(46)
    n, per_dev, cap = ROUTE_DEV * 64, 64, 8
    k = rng.integers(0, 1 << 40, n).astype(np.int64)
    b = make_batch({"k": (k, None), "row": (np.arange(n), None)})
    cols, sel, ovf = _route(b, ["k"], bucket_cap=cap)
    dest = _named_device(b, ["k"])
    for src in range(ROUTE_DEV):
        mine = np.arange(src * per_dev, (src + 1) * per_dev)
        runs = [mine[dest[mine] == d] for d in range(ROUTE_DEV)]
        # the flag is the sending device's, raised iff a run outgrew it
        assert bool(ovf[src]) == any(len(r) > cap for r in runs)
        for d, run in enumerate(runs):
            # bucket `src` of device d: the run's FIRST `cap` rows, in
            # lane order; the rest are dropped, which the flag says
            lanes = slice(src * cap, (src + 1) * cap)
            assert (cols["row"][d][lanes][sel[d][lanes]].tolist()
                    == run[:cap].tolist())
    assert ovf.any()


def test_hash_router_one_hot_key_raises_the_flag():
    n = ROUTE_DEV * 64
    b = make_batch({"k": (np.full(n, 7, np.int64), None),
                    "row": (np.arange(n), None)})
    cols, sel, ovf = _route(b, ["k"], bucket_cap=16)
    assert ovf.all()       # every device's 64 rows name one bucket of 16
    (hot,) = set(_named_device(b, ["k"]).tolist())
    assert sel[hot].sum() == ROUTE_DEV * 16
    assert not np.delete(sel, hot, axis=0).any()
    # with room for the skew, nothing is dropped and no flag is raised
    cols, sel, ovf = _route(b, ["k"], bucket_cap=64)
    assert not ovf.any() and sel[hot].sum() == n


def test_hash_router_brings_both_sides_of_a_key_to_one_device():
    """Routed with the same seed, a probe row and the build rows of its
    key meet: the joins each device runs on what it received count, in
    sum, the pairs of the whole join."""
    rng = np.random.default_rng(47)
    lk = rng.integers(0, 50, ROUTE_DEV * 128).astype(np.int64)
    rk = rng.integers(0, 50, ROUTE_DEV * 64).astype(np.int64)
    probe = make_batch({"lk": (lk, None)}, sel=rng.random(len(lk)) > 0.1)
    build = make_batch({"rk": (rk, None)}, sel=rng.random(len(rk)) > 0.1)
    pcols, psel, povf = _route(probe, ["lk"], bucket_cap=128)
    bcols, bsel, bovf = _route(build, ["rk"], bucket_cap=64)
    assert not povf.any() and not bovf.any()
    local = sum(
        int((pcols["lk"][d][psel[d]][:, None]
             == bcols["rk"][d][bsel[d]][None, :]).sum())
        for d in range(ROUTE_DEV))
    want = int((lk[np.asarray(probe.sel)][:, None]
                == rk[np.asarray(build.sel)][None, :]).sum())
    assert local == want and want > 0


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
