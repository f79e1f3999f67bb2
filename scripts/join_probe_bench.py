"""Quick TPU microbench for the unique sort-join (round-4 kernel work).

Usage: python scripts/join_probe_bench.py [log2_rows]
"""
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

import cockroach_tpu  # noqa: F401
from cockroach_tpu.coldata.batch import Batch, Column
from cockroach_tpu.ops.join import hash_join_prepared, prepare_build


n = 1 << int(sys.argv[1] if len(sys.argv) > 1 else 22)
mode = sys.argv[2] if len(sys.argv) > 2 else "unique"
rng = np.random.default_rng(0)
bkeys = rng.permutation(n).astype(np.int64)
pkeys = rng.integers(0, n, n).astype(np.int64)
build = Batch.from_columns({
    "bk": Column(jnp.asarray(bkeys)),
    "bv": Column(jnp.asarray(np.arange(n, dtype=np.int64)))})
probe = Batch.from_columns({
    "pk": Column(jnp.asarray(pkeys)),
    "pv": Column(jnp.asarray(np.arange(n, dtype=np.int64)))})
_ = np.asarray(build.col("bk").values[:8])  # enter sync (post-readback) mode

prep = jax.jit(lambda b: prepare_build(b, ("bk",), mode=mode))
joinf = jax.jit(lambda p, bt: hash_join_prepared(
    p, bt, ("pk",), ("bk",), how="inner", out_capacity=n))
t0 = time.perf_counter()
bt = jax.block_until_ready(prep(build))
print(f"prep compile+run {time.perf_counter() - t0:.1f}s", flush=True)
t0 = time.perf_counter()
res = jax.block_until_ready(joinf(probe, bt))
print(f"probe compile+run {time.perf_counter() - t0:.1f}s", flush=True)
print("overflow", bool(np.asarray(res.overflow)),
      "matches", int(np.asarray(res.batch.length)), flush=True)

tb, tp = [], []
for _ in range(5):
    t0 = time.perf_counter()
    bt = jax.block_until_ready(prep(build))
    tb.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    jax.block_until_ready(joinf(probe, bt))
    tp.append(time.perf_counter() - t0)
b, p = statistics.median(tb), statistics.median(tp)
print(f"n={n}: build warm {b*1e3:.1f}ms probe warm {p*1e3:.1f}ms "
      f"-> {(n * 16 * 2) / (b + p) / 1e9:.2f} GB/s")
