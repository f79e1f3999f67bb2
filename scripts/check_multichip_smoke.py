"""Multichip smoke gate (<60 s): the sharded-at-ingest DistSQL path on
an 8-device virtual CPU mesh.

Checks, in one child process (the dryrun_multichip re-exec recipe: the
CPU platform and its virtual device count must be set before any backend
initializes):

1. TPC-H Q3 executes DISTRIBUTED (ingest-sharded scans, forced BY_HASH
   a2a repartition, two-stage agg, merged top-K) bit-exact vs the host
   oracle;
2. the warm re-run is ONE dispatch: cached ingest-sharded images +
   cached compiled program (dist.prime_skipped, zero dist.compile /
   scan.stack / ingest events);
3. a forced device loss at the a2a seam takes the SHRINK-THE-MESH rung
   (recompile on the surviving pow2 sub-mesh, never straight to
   single-chip) and still matches the oracle exactly.

Run: python scripts/check_multichip_smoke.py   (exits non-zero on fail)
"""

import os
import subprocess
import sys
import time

_CHILD_ENV = "_COCKROACH_TPU_MCSMOKE_CHILD"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGET_S = 60.0


def _child() -> int:
    sys.path.insert(0, ROOT)
    import jax

    jax.config.update("jax_platforms", "cpu")
    # same persistent cpu compile cache the test suite uses
    from cockroach_tpu.util.compile_cache import enable_persistent_cache

    enable_persistent_cache(default=os.path.join(ROOT, ".jax_cache_cpu"))
    assert len(jax.devices()) >= 8, "virtual mesh did not come up"

    from cockroach_tpu.exec import stats
    from cockroach_tpu.parallel import make_mesh
    from cockroach_tpu.parallel.dist_flow import (
        BROADCAST_LIMIT, collect_distributed,
    )
    from cockroach_tpu.parallel.mesh import DeviceLost
    from cockroach_tpu.util.fault import registry
    from cockroach_tpu.util.settings import Settings
    from cockroach_tpu.workload.tpch import TPCH
    from cockroach_tpu.workload import tpch_queries as Q

    def ev(col, name):
        s = col.stages.get(name)
        return s.events if s else 0

    gen = TPCH(sf=0.01)
    mesh = make_mesh(8)
    # force the BY_HASH a2a path so the gate covers repartitioned
    # execution, not just broadcast joins
    Settings().set(BROADCAST_LIMIT, 4096)
    exp = sorted(Q.q3_oracle(gen))

    def rows(res):
        return sorted(zip(res["l_orderkey"].tolist(),
                          res["revenue"].tolist(),
                          res["o_orderdate"].tolist()))

    # 1) cold sharded execution, bit-exact
    got = rows(collect_distributed(Q.q3(gen, 1 << 12), mesh))
    assert got == exp, "cold sharded Q3 diverged from the oracle"
    print("multichip-smoke: cold sharded Q3 bit-exact "
          f"({len(got)} rows, a2a repartition forced)")

    # 2) warm re-run: single dispatch
    col = stats.enable()
    got = rows(collect_distributed(Q.q3(gen, 1 << 12), mesh))
    stats.disable()
    assert got == exp, "warm sharded Q3 diverged"
    assert ev(col, "dist.prime_skipped") == 1, "warm probe missed"
    assert ev(col, "dist.exec") == 1, "warm run was not one dispatch"
    for stage in ("dist.compile", "scan.stack", "dist.ingest_shard",
                  "dist.ingest_replicate"):
        assert ev(col, stage) == 0, f"warm run did {stage}"
    print("multichip-smoke: warm Q3 = ONE dispatch "
          "(cached ingest shards + cached program)")

    # 3) forced device loss -> shrink-the-mesh rung, still bit-exact
    reg = registry()
    reg.arm("dist.a2a", after=0,
            make=lambda: DeviceLost("injected ICI loss",
                                    survivors=[0, 1, 2, 3]))
    col = stats.enable()
    try:
        got = rows(collect_distributed(Q.q3(gen, 1 << 12), mesh))
    finally:
        stats.disable()
        reg.disarm()
    assert got == exp, "post-shrink Q3 diverged"
    assert ev(col, "resilience.shrink.dist") == 1, "shrink rung not taken"
    assert ev(col, "resilience.degrade.dist") == 0, \
        "fell to single-chip instead of shrinking"
    print("multichip-smoke: device loss -> recompiled on the 4-device "
          "sub-mesh, bit-exact (never left the distributed tier)")
    return 0


def main() -> int:
    if os.environ.get(_CHILD_ENV) == "1":
        return _child()
    env = dict(os.environ)
    env[_CHILD_ENV] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    t0 = time.monotonic()
    res = subprocess.run([sys.executable, os.path.abspath(__file__)],
                         env=env, cwd=ROOT)
    dt = time.monotonic() - t0
    if res.returncode != 0:
        print(f"multichip-smoke: FAIL (rc={res.returncode})")
        return 1
    if dt > BUDGET_S:
        print(f"multichip-smoke: FAIL — took {dt:.1f}s "
              f"(budget {BUDGET_S:.0f}s)")
        return 1
    print(f"multichip-smoke: OK in {dt:.1f}s (budget {BUDGET_S:.0f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
