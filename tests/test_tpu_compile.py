"""Compiles for a DESCRIBED TPU v5e:2x2 — nothing attached, nothing run.

The chip's compiler is installed here and refuses here what it would
refuse there (a slice not aligned to the tiling, more scoped vmem than a
kernel may use, a program that does not fit HBM, a kernel Mosaic cannot
lower), so the main path's programs are compiled at real widths on every
PR at no chip time: the Pallas limb kernel, Q1's whole-query program at
SF1's chunk bucket, the sort-join at 1M x 1M, and the four-device
BY_HASH repartition. Q3's whole program takes minutes and stays in
scripts/rehearse_tpu_compile.py.

Only one process may load the TPU's library, and xdist workers import
every test file: the topology is described INSIDE the module-scoped
fixture below (never at import, never autouse, never in conftest.py), so
every worker collects the same tests and only the one given this file
loads the library. All such tests live in this one file for the same
reason. The persistent compilation cache is off around them: it can
store a program compiled for a described chip but never load it back.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from cockroach_tpu.exec.fused import TPU_COMPILE_OPTIONS

HBM_BYTES = 16 << 30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    from cockroach_tpu.util.compile_cache import persistent_cache_disabled

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    with persistent_cache_disabled():
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *sds):
    compiled = jax.jit(fn).lower(*sds).compile(TPU_COMPILE_OPTIONS)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert used < HBM_BYTES, f"program needs {used / 2**30:.1f} GiB"
    return compiled


def test_limb_kernel_compiles_at_sf1_shape(one_chip):
    """ops/pallas_kernels.py, interpret=False, at (rows, limb rows, lanes)
    = (1<<22, 56, 128): the largest shape ops/agg.py routes to it."""
    from cockroach_tpu.ops.pallas_kernels import dense_limb_matmul_sums

    rows, limbs, lanes = 1 << 22, 56, 128
    compiled = _compile(
        lambda p, l: dense_limb_matmul_sums(p, l, n_lanes=lanes,
                                            interpret=False),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((limbs, rows), jnp.float32,
                             sharding=one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_q1_fused_program_compiles_at_sf1_bucket(one_chip):
    """Q1's SQL text -> compile_plan -> FusedRunner._make_prog, lowered as
    exec/fused.py lowers it (aot_compile: abstract stacked images) at
    SF1's bucket — 64 chunks x 131,072 rows — and compiled with the
    options _compile_lowered uses on a TPU. Planned at SF0.01: Q1 has no
    inner capacity that depends on the scale."""
    from cockroach_tpu.coldata.arrow import pack_layout
    from cockroach_tpu.exec.operators import ScanOp, walk_operators
    from cockroach_tpu.sql import TPCHCatalog
    from cockroach_tpu.sql.bind import plan_sql
    from cockroach_tpu.sql.plan_compile import compile_plan
    from cockroach_tpu.util.settings import PALLAS, Settings
    from cockroach_tpu.workload.tpch import TPCH

    from test_sql import Q1_SQL

    capacity, chunks = 1 << 17, 64
    catalog = TPCHCatalog(TPCH(sf=0.01))
    cp = compile_plan(plan_sql(Q1_SQL, catalog), catalog, capacity,
                      sql=Q1_SQL, setting="tpu")
    assert cp.runner is not None, "Q1 left the fusion grammar"
    scans = [n for n in walk_operators(cp.op) if isinstance(n, ScanOp)]
    prog, _box = cp.runner._make_prog([id(s) for s in scans])
    sds = tuple(
        (jax.ShapeDtypeStruct(
            (chunks, pack_layout(sc.schema, sc.capacity)[1]), jnp.uint8,
            sharding=one_chip),
         jax.ShapeDtypeStruct((chunks,), jnp.int32, sharding=one_chip))
        for sc in scans)
    # `auto` asks jax.default_backend() — the CPU, here; `on` traces what
    # the chip would run (and cannot lower for the CPU, hence set late)
    old = Settings().get(PALLAS)
    Settings().set(PALLAS, "on")
    try:
        _compile(prog, *sds)
    finally:
        Settings().set(PALLAS, old)


def test_sortjoin_compiles_at_1m_x_1m(one_chip):
    """ops/sortjoin.py unique-build join, 1M build x 1M probe lanes: the
    narrow (key, iota) sorts are what keeps this compile bounded."""
    from cockroach_tpu.coldata.batch import Batch, Column
    from cockroach_tpu.ops import sortjoin

    n = 1 << 20

    def join(bk, bv, pk, pv):
        live = jnp.ones(n, jnp.bool_)
        build = Batch({"k": Column(bk), "v": Column(bv)}, live,
                      jnp.int32(n))
        probe = Batch({"fk": Column(pk), "w": Column(pv)}, live,
                      jnp.int32(n))
        ub = sortjoin.prepare_unique(build, ("k",))
        res = sortjoin.probe_unique(probe, ub, ("fk",))
        return res.batch.col("v").values, res.batch.sel, res.overflow

    i64 = jax.ShapeDtypeStruct((n,), jnp.int64, sharding=one_chip)
    assert "sort" in _compile(join, i64, i64, i64, i64).as_text()


def test_four_device_repartition_has_all_to_all(topo):
    """parallel/repartition.py's BY_HASH router under shard_map on a
    four-device Mesh of the described chips: the compiler must put an
    all-to-all over ICI in, the per-device program must fit, and the
    buckets are cut out of the destination-sorted lanes: a scatter would
    place them one element at a time (144 ms a 64-bit column at
    4,194,304 lanes; PERF.md section 6, PR 27)."""
    from jax import lax

    from cockroach_tpu.coldata.batch import Batch, Column
    from cockroach_tpu.parallel.repartition import (
        hash_repartition_local, shard_map,
    )

    n_dev, local = 4, 1 << 15
    mesh = Mesh(np.array(topo.devices[:n_dev]), ("x",))

    def step(k, v, sel):
        b = Batch({"k": Column(k), "v": Column(v)}, sel,
                  jnp.sum(sel).astype(jnp.int32))
        out, ovf = hash_repartition_local(b, ("k",), "x", n_dev,
                                          local // n_dev * 2)
        return (out.col("k").values, out.col("v").values, out.sel,
                lax.psum(ovf.astype(jnp.int32), "x"))

    fn = shard_map(step, mesh=mesh, in_specs=(P("x"), P("x"), P("x")),
                   out_specs=(P("x"), P("x"), P("x"), P()),
                   check_rep=False)
    rows = NamedSharding(mesh, P("x"))
    compiled = _compile(
        fn,
        jax.ShapeDtypeStruct((n_dev * local,), jnp.int64, sharding=rows),
        jax.ShapeDtypeStruct((n_dev * local,), jnp.int64, sharding=rows),
        jax.ShapeDtypeStruct((n_dev * local,), jnp.bool_, sharding=rows))
    text = compiled.as_text()
    assert "all-to-all" in text
    assert " scatter(" not in text


@pytest.mark.parametrize("n_dev,lanes", [(1, 524288), (4, 262144)])
def test_q9s_aggregate_by_slot_compiles_with_the_limb_kernel(topo, n_dev,
                                                              lanes):
    """ops/agg.dense_aggregate over (a dictionary of 25, a year ranged
    1992..1998) at the lanes Q9's aggregate sees at SF1, with the Pallas
    limb kernel as `sql.tpu.pallas = auto` picks it on a TPU: on one chip,
    and under shard_map on four with the lane-wise merge of the gathered
    208-lane partials (ISSUE 42): the kernel lowers inside shard_map, and
    nothing sorts the input's lanes."""
    import functools

    from jax import lax

    from cockroach_tpu.coldata.batch import Batch, Column
    from cockroach_tpu.ops.agg import AggSpec, dense_aggregate, dense_merge
    from cockroach_tpu.parallel.repartition import shard_map
    from cockroach_tpu.util.settings import PALLAS, Settings

    gb, aggs = ("n", "y"), (AggSpec("sum", "v", "sv"),)
    sizes, doms = (26, 8), {"y": (1992, 1998)}

    def step(n, y, v, sel):
        b = Batch({"n": Column(n), "y": Column(y), "v": Column(v)}, sel,
                  jnp.sum(sel).astype(jnp.int32))
        part, outside = dense_aggregate(b, gb, aggs, sizes, doms,
                                        with_flag=True)
        if n_dev > 1:
            parts = jax.tree_util.tree_map(
                lambda x: lax.all_gather(x, "x"), part)
            part = functools.reduce(
                lambda a, c: dense_merge(a, c, gb, aggs),
                [jax.tree_util.tree_map(lambda x: x[i], parts)
                 for i in range(n_dev)])
            outside = lax.psum(outside.astype(jnp.int32), "x") > 0
        out = part.compact()
        return out.col("n").values, out.col("y").values, \
            out.col("sv").values, out.sel, outside

    if n_dev == 1:
        fn, sh = step, SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.array(topo.devices[:n_dev]), ("x",))
        fn = shard_map(step, mesh=mesh, in_specs=(P("x"),) * 4,
                       out_specs=(P(),) * 5, check_rep=False)
        sh = NamedSharding(mesh, P("x"))
    s = Settings()
    old = s.get(PALLAS)
    s.set(PALLAS, "on")     # `auto` asks jax.default_backend(): the CPU here
    try:
        compiled = _compile(
            fn,
            jax.ShapeDtypeStruct((n_dev * lanes,), jnp.int32, sharding=sh),
            jax.ShapeDtypeStruct((n_dev * lanes,), jnp.int64, sharding=sh),
            jax.ShapeDtypeStruct((n_dev * lanes,), jnp.int64, sharding=sh),
            jax.ShapeDtypeStruct((n_dev * lanes,), jnp.bool_, sharding=sh))
    finally:
        s.set(PALLAS, old)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the one sort left is compact()'s, over the domain's 208 lanes
    sorts = [ln for ln in text.splitlines() if " sort(" in ln]
    assert sorts and all("[208]" in ln and str(lanes) not in ln
                         for ln in sorts)
    assert ("all-gather" in text) == (n_dev > 1)
