"""Per-kernel attribution of the fused Q3 warm time on the real chip.

VERDICT r2 discipline: attribute, then fix. Times each suspect kernel
at bench shapes with REAL syncs (np.asarray readback of a scalar-ish
slice), so the dispatch round trip is in every reading.

Run on the chip: python scripts/attribute_q3.py
"""

import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from cockroach_tpu.coldata.batch import Batch, Column


def timed(fn, *args, reps=4):
    out = fn(*args)
    np.asarray(jax.tree_util.tree_leaves(out)[0])[:1]
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        np.asarray(jax.tree_util.tree_leaves(out)[0])[:1]
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def main():
    rng = np.random.default_rng(0)
    n = 1 << 20

    # 1. raw 1-D permutation gather
    x = jnp.asarray(rng.integers(0, 1 << 40, n).astype(np.int64))
    perm = jnp.asarray(rng.permutation(n).astype(np.int32))
    t = timed(jax.jit(lambda x, p: x[p]), x, perm)
    print(f"gather 1M int64 by perm:      {t * 1e3:7.1f} ms")

    # 2. row-matrix gather (8 cols at once, ops/rowmat.py shape)
    xm = jnp.asarray(rng.integers(0, 1 << 40, (n, 8)).astype(np.int64))
    t = timed(jax.jit(lambda x, p: x[p, :]), xm, perm)
    print(f"row-matrix gather 1Mx8 int64: {t * 1e3:7.1f} ms")

    # 3. sort carrying 1 payload vs gather-after-argsort
    keys = jnp.asarray(rng.integers(0, 6_000_000, n).astype(np.int64))
    t = timed(jax.jit(lambda k: jnp.sort(k)), keys)
    print(f"sort 1M keys only:            {t * 1e3:7.1f} ms")
    t = timed(jax.jit(lambda k, v: jax.lax.sort((k, v), num_keys=1)),
              keys, x)
    print(f"sort 1M keys + 1 payload:     {t * 1e3:7.1f} ms")

    # 4. hash_join at Q3 shape (1M probe x 300K build)
    from cockroach_tpu.ops.join import hash_join_prepared, prepare_build

    bk = rng.permutation(1_500_000)[:300_000].astype(np.int64)
    build = Batch({"bk": Column(jnp.asarray(bk)),
                   "od": Column(jnp.asarray(
                       rng.integers(0, 10000, 300_000).astype(np.int64))),
                   "pr": Column(jnp.asarray(
                       rng.integers(0, 5, 300_000).astype(np.int64)))},
                  jnp.ones(300_000, bool),
                  jnp.asarray(300_000, dtype=jnp.int32))
    probe = Batch({"k": Column(keys),
                   "rev": Column(x)},
                  jnp.ones(n, bool), jnp.asarray(n, dtype=jnp.int32))
    prep = jax.jit(lambda b: prepare_build(b, ("bk",)))
    bt = prep(build)
    jax.block_until_ready(bt)
    joinf = jax.jit(lambda p, t: hash_join_prepared(
        p, t, ("k",), ("bk",), how="inner", out_capacity=n))
    t = timed(lambda p: joinf(p, bt), probe)
    print(f"hash join 1M x 300K:          {t * 1e3:7.1f} ms")
    t = timed(prep, build)
    print(f"join build 300K:              {t * 1e3:7.1f} ms")

    # 5. hash aggregate fold step at Q3 shape (1M rows, ~300K groups)
    from cockroach_tpu.ops.agg import AggSpec, hash_aggregate

    t = timed(jax.jit(lambda b: hash_aggregate(
        b, ("k",), (AggSpec("sum", "rev", "s"),))), probe)
    print(f"hash agg 1M rows ~300K grps:  {t * 1e3:7.1f} ms")

    # 6. compact (sel-based compaction)
    sel = jnp.asarray(rng.random(n) > 0.45)
    pb = Batch({"k": Column(keys), "rev": Column(x)}, sel,
               jnp.asarray(int(np.asarray(sel).sum()), dtype=jnp.int32))
    t = timed(jax.jit(lambda b: b.compact()), pb)
    print(f"compact 1M (55% live):        {t * 1e3:7.1f} ms")


if __name__ == "__main__":
    main()
