"""Compile, for a DESCRIBED TPU v5e:2x2 (nothing attached, nothing run), the
programs chip_smoke.py executes at SF1 — the third rehearsal of the
on-chip-measurement guide. What the chip's compiler refuses it refuses
here, at no chip time; each compile's seconds are the smoke's cold budget.

    JAX_PLATFORMS=cpu python scripts/rehearse_tpu_compile.py [--sf 1] \
        [--only kernel,q1,q6,q3,q21,q3_dist4,q9_dist4,q18_dist4]

Plans are built from SF1 data (TPCH.mvcc_load, so the planner sees SF1's
statistics and every inner capacity is SF1's), lowered exactly as
exec/fused.py and parallel/dist_flow.py lower them, from abstract shapes
placed on the described devices, and compiled with fused.TPU_COMPILE_OPTIONS.
tests/test_tpu_compile.py keeps the quick compiles (and the sort-join's);
Q3's whole programs (minutes) stay here. One JSON line per program.
`q9_dist4` (not in the default list: a quarter of an hour and more) is the
benchmark cell tpch-sf1-q9-mesh4.q9-1stream's program: Q9 prepared, from
that cell's loader and text, its bound pattern's table a replicated
argument. `q18_dist4` (not in the default list either) is
tpch-sf1-q18-mesh4.q18-1stream's: Q18 prepared, its aggregate merged
BY_HASH and its two computed builds gathered; its line carries EXPLAIN's
distribution lines, so the layout and the lanes are read here before a
chip compile is paid.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import chip_smoke  # noqa: E402 — the SQL texts and CAPACITY the smoke uses


def report(name, lowered, t_lower, **extra):
    from cockroach_tpu.exec.fused import TPU_COMPILE_OPTIONS

    t0 = time.perf_counter()
    compiled = lowered.compile(TPU_COMPILE_OPTIONS)
    dt = time.perf_counter() - t0
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    print(json.dumps({
        "program": name, "lower_s": round(t_lower, 2),
        "compile_s": round(dt, 2),
        "tpu_custom_call": "tpu_custom_call" in text,
        "all_to_all": "all-to-all" in text,
        "code_mb": round(mem.generated_code_size_in_bytes / 1e6, 1),
        "args_mb": round(mem.argument_size_in_bytes / 1e6, 1),
        "temp_mb": round(mem.temp_size_in_bytes / 1e6, 1), **extra}),
        flush=True)
    return compiled


def timed_lower(fn, *sds):
    t0 = time.perf_counter()
    lowered = jax.jit(fn).lower(*sds)
    return lowered, time.perf_counter() - t0


def scan_shapes(scans, gen, lead):
    """Abstract stacked-image arguments: `lead(sc, n_chunks)` ->
    (leading dim, sharding)."""
    from cockroach_tpu.coldata.arrow import pack_layout

    out = []
    for sc in scans:
        n_chunks = -(-gen.num_rows(sc.table) // sc.capacity)
        rows, sh = lead(sc, n_chunks)
        nb = pack_layout(sc.schema, sc.capacity)[1]
        out.append((jax.ShapeDtypeStruct((rows, nb), jnp.uint8, sharding=sh),
                    jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=sh)))
    return tuple(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", default="kernel,q1,q6,q3,q3_dist4")
    args = ap.parse_args()
    only = set(args.only.split(","))

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    import cockroach_tpu  # noqa: F401
    from cockroach_tpu.util.settings import PALLAS, Settings

    if "kernel" in only:
        from cockroach_tpu.ops.pallas_kernels import dense_limb_matmul_sums

        for rows, limbs, lanes in ((1 << 20, 24, 8), (1 << 22, 56, 128),
                                   (4096, 8, 6)):
            lowered, tl = timed_lower(
                lambda p, l, lanes=lanes: dense_limb_matmul_sums(
                    p, l, n_lanes=lanes, interpret=False),
                jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip),
                jax.ShapeDtypeStruct((limbs, rows), jnp.float32,
                                     sharding=one_chip))
            report(f"limb_kernel({rows},{limbs},{lanes})", lowered, tl)

    if "q21" in only:
        from benchmark.loaders import tpch_sname

        prepared_fused(one_chip, args, "q21_fused",
                       "tpch-sf1-q21.q21-1stream", tpch_sname, ("FRANCE",))
    if "q9_dist4" in only:
        q9_dist4(topo, args)
    if "q18_dist4" in only:
        q18_dist4(topo, args)
    fused_names = [q for q in ("q1", "q6", "q3") if q in only]
    if not fused_names and "q3_dist4" not in only:
        return
    from cockroach_tpu.exec.operators import ScanOp, walk_operators
    from cockroach_tpu.sql.bind import plan_sql
    from cockroach_tpu.sql.plan_compile import compile_plan
    from cockroach_tpu.storage.mvcc import MVCCStore
    from cockroach_tpu.workload.tpch import TPCH

    gen = TPCH(sf=args.sf, seed=args.seed)
    t0 = time.perf_counter()
    catalog = gen.mvcc_load(MVCCStore(), ("lineitem", "orders", "customer"))
    print(json.dumps({"loaded_sf": args.sf,
                      "seconds": round(time.perf_counter() - t0, 1)}),
          flush=True)
    # `auto` asks jax.default_backend(), which is the CPU here; `on`
    # traces the kernel the chip would run (interpret=False)
    Settings().set(PALLAS, "on")
    sqls = {"q1": chip_smoke.Q1_SQL, "q6": chip_smoke.Q6_SQL,
            "q3": chip_smoke.Q3_SQL}

    for name in fused_names:
        cp = compile_plan(plan_sql(sqls[name], catalog), catalog,
                          chip_smoke.CAPACITY, sql=sqls[name],
                          setting="tpu")
        assert cp.runner is not None, f"{name} is outside the fusion grammar"
        scans = [n for n in walk_operators(cp.op) if isinstance(n, ScanOp)]
        prog, _box = cp.runner._make_prog([id(s) for s in scans])
        sds = scan_shapes(scans, gen, lambda sc, n: (_pow2(n), one_chip))
        lowered, tl = timed_lower(prog, *sds)
        report(name + "_fused", lowered, tl,
               chunks={sc.table: int(a[0].shape[0])
                       for sc, a in zip(scans, sds)})

    if "q3_dist4" in only:
        from cockroach_tpu.parallel import dist_flow, ingest

        mesh = Mesh(np.array(topo.devices[:4]), ("x",))
        # nothing forced: the default broadcast limit is customer's
        # padded rows at SF1, as in chip_smoke.py --chips 4 and in the
        # benchmark's tpch-sf1-mesh4 cell (setting `auto`, as they run)
        cp = compile_plan(plan_sql(chip_smoke.Q3_SQL, catalog), catalog,
                          chip_smoke.CAPACITY, sql=chip_smoke.Q3_SQL,
                          setting="tpu")
        runner = dist_flow.DistFusedRunner(cp.op, mesh, "x")
        scans = [n for n in walk_operators(cp.op) if isinstance(n, ScanOp)]
        chunks = {id(sc): -(-gen.num_rows(sc.table) // sc.capacity)
                  for sc in scans}
        sharded, repart = runner._classify(chunks)
        assert repart, "no repartitioned join: the all_to_all is not forced"

        def lead(sc, n):
            if id(sc) in sharded:
                return 4 * _pow2(-(-n // 4)), NamedSharding(mesh, P("x"))
            return _pow2(n), NamedSharding(mesh, P())

        sds = scan_shapes(scans, gen, lead)
        t0 = time.perf_counter()
        box = {}
        lowered = runner._lower(scans, sharded, repart, sds, box)
        report("q3_dist4", lowered, time.perf_counter() - t0,
               # (bucket traced, the bucket the side's lanes give)
               buckets={side: b for (side, _), b in box["buckets"].items()},
               sharded=sorted(sc.table for sc in scans
                              if id(sc) in sharded),
               roles={sc.table: (ingest.SHARDED if id(sc) in sharded
                                 else ingest.REPLICATED) for sc in scans})


def _pow2(n):
    return 1 << max(0, (n - 1).bit_length())


def q9_dist4(topo, args):
    """The mesh cell's Q9 as DistFusedRunner lowers it for four described
    chips: layout by _classify at the default broadcast limit, the plan
    made at '%green%', the bound values' shapes after the images."""
    from benchmark.loaders import tpch_pname

    repart = prepared_dist4(topo, args, "q9_dist4",
                            "tpch-sf1-q9-mesh4.q9-1stream", tpch_pname,
                            ("%green%",))
    assert len(repart) == 2, "Q9 at SF1 routes partsupp and orders"


def q18_dist4(topo, args):
    """The mesh cell's Q18 likewise, the plan made at QUANTITY 313: the
    aggregate on l_orderkey routed BY_HASH, both computed builds
    gathered, no join routed."""
    from benchmark.loaders import tpch_cname

    repart = prepared_dist4(topo, args, "q18_dist4",
                            "tpch-sf1-q18-mesh4.q18-1stream", tpch_cname,
                            ("313",))
    kinds = sorted(type(x).__name__ for x in repart.values())
    assert kinds == ["_AggRoute", "_Gather", "_Gather"], kinds


def _prepared_plan(args, cell, loader, values):
    """`cell`'s one prepared statement over `loader`'s SF tables with
    `values` bound -> (its bound arguments, the compiled plan, its scans,
    scan_shapes' generator: the row counts the benchmark's loader gave)."""
    from benchmark import manifest
    from cockroach_tpu.exec.operators import ScanOp, walk_operators
    from cockroach_tpu.sql import params as _params
    from cockroach_tpu.sql import parser
    from cockroach_tpu.sql.bind import Binder
    from cockroach_tpu.sql.plan_compile import compile_plan
    from cockroach_tpu.storage.mvcc import MVCCStore
    from cockroach_tpu.util.settings import PALLAS, Settings

    stmt = manifest.cell(cell)["statements"][0]
    t0 = time.perf_counter()
    loaded = loader.load(MVCCStore(), {"sf": args.sf}, stmt["tables"],
                         args.seed)
    print(json.dumps({"loaded_sf": args.sf, "loader": loader.__name__,
                      "seconds": round(time.perf_counter() - t0, 1)}),
          flush=True)
    Settings().set(PALLAS, "on")
    catalog = loaded["catalog"]
    binder = Binder(catalog, params=values)
    plan = binder.bind(parser.parse(stmt["sql"]))
    bound = _params.evaluate(binder.param_slots, values)
    cp = compile_plan(plan, catalog, chip_smoke.CAPACITY, sql=stmt["sql"],
                      setting="tpu")
    scans = [n for n in walk_operators(cp.op) if isinstance(n, ScanOp)]
    gen = SimpleNamespace(num_rows=loaded["rows"].__getitem__)
    return bound, cp, scans, gen


def prepared_fused(one_chip, args, name, cell, loader, values) -> None:
    """Lower and compile `cell`'s one prepared statement as FusedRunner
    does for one described chip (not in the default list: Q21's takes
    minutes)."""
    bound, cp, scans, gen = _prepared_plan(args, cell, loader, values)
    assert cp.runner is not None, f"{name} is outside the fusion grammar"
    prog, box = cp.runner._make_prog([id(s) for s in scans])
    sds = scan_shapes(scans, gen, lambda sc, n: (_pow2(n), one_chip))
    sds += tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                 for a in bound)
    lowered, tl = timed_lower(prog, *sds)
    report(name, lowered, tl,
           chunks={sc.table: int(a[0].shape[0])
                   for sc, a in zip(scans, sds)},
           sort_lanes=box["sort_lanes"],
           join_residual_lanes=box.get("join_residual_lanes"),
           params=[list(a.shape) for a in bound])


def prepared_dist4(topo, args, name, cell, loader, values) -> dict:
    """Lower and compile `cell`'s one prepared statement as
    DistFusedRunner does for four described chips, from `loader`'s SF
    tables with `values` bound; -> _classify's placements."""
    from cockroach_tpu.exec.operators import walk_operators
    from cockroach_tpu.ops.expr import bound_args
    from cockroach_tpu.parallel import dist_flow, ingest

    bound, cp, scans, gen = _prepared_plan(args, cell, loader, values)
    mesh = Mesh(np.array(topo.devices[:4]), ("x",))
    runner = dist_flow.DistFusedRunner(cp.op, mesh, "x")
    chunks = {id(sc): -(-gen.num_rows(sc.table) // sc.capacity)
              for sc in scans}
    sharded, repart = runner._classify(chunks)

    def lead(sc, n):
        if id(sc) in sharded:
            return 4 * _pow2(-(-n // 4)), NamedSharding(mesh, P("x"))
        return _pow2(n), NamedSharding(mesh, P())

    sds = scan_shapes(scans, gen, lead)
    with bound_args(bound):
        sds += runner._bound_shapes()
    t0 = time.perf_counter()
    box = {}
    lowered = runner._lower(scans, sharded, repart, sds, box)
    by_op = {id(op): (dist_flow._join_keys(op) if hasattr(op, "probe_on")
                      else ", ".join(op.group_by))
             for op in walk_operators(cp.op) if id(op) in repart}
    report(name, lowered, time.perf_counter() - t0,
           buckets={f"{side} {by_op[j]}": b
                    for (side, j), b in box["buckets"].items()},
           a2a_mb=round(box["a2a_bytes"] / 1e6, 2),
           sort_lanes=box["sort_lanes"],
           hash_key_lanes=box["hash_key_lanes"],
           agg_route=box.get("agg_route"),
           gather_build=box.get("gather_build"),
           params=[list(a.shape) for a in bound],
           distribution=runner.describe(chunks),
           roles={sc.table: (ingest.SHARDED if id(sc) in sharded
                             else ingest.REPLICATED) for sc in scans})
    return repart


if __name__ == "__main__":
    main()
