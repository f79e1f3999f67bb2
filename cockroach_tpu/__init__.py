"""cockroach_tpu — a TPU-native distributed SQL execution framework.

A from-scratch rebuild of the capabilities of CockroachDB (the reference at
/root/reference) designed TPU-first: the DistSQL vectorized execution layer
(reference: pkg/sql/colexec*) runs as jit-compiled JAX/XLA/Pallas kernels on
TPU; cross-node repartitioning (reference: colflow/routers.go HashRouter +
FlowStream gRPC) rides ICI collectives (`lax.all_to_all` / `all_gather` /
`ppermute`) under `shard_map`; the MVCC storage engine (reference:
pkg/storage over Pebble) is native C++ emitting Arrow batches straight into
device memory.

Package layout (mirrors SURVEY.md §2's component inventory):
  coldata/   columnar batch format           (ref: pkg/col/coldata)
  ops/       TPU compute kernels             (ref: pkg/sql/colexec* 83 .eg.go)
  exec/      flow runtime + operators        (ref: colflow, flowinfra, execinfra)
  parallel/  mesh + collective repartition   (ref: colflow/routers, colrpc)
  storage/   C++ MVCC LSM + Arrow scanner    (ref: pkg/storage, col_mvcc.go)
  kv/        txns, routing, range cache      (ref: pkg/kv, kvclient/kvcoord)
  sql/       parser, planner, executor       (ref: pkg/sql front/mid-end)
  raft/      replication consensus           (ref: pkg/raft)
  util/      hlc, memory monitor, settings   (ref: pkg/util/{hlc,mon}, pkg/settings)
  workload/  TPC-H / YCSB generators         (ref: pkg/workload)

64-bit note: SQL needs int64 keys (TPC-H SF100 orderkeys exceed int32) and
exact decimal arithmetic (represented as int64-scaled integers). We therefore
enable jax x64 globally; all float arrays are explicitly float32 so the TPU
path never sees float64.
"""

import jax

jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: a whole-query program takes seconds to
# minutes to compile for the TPU, so every process after the first should
# start warm. util/compile_cache.py is the only place that decides where
# the cache is (JAX_COMPILATION_CACHE_DIR wins, else <checkout>/.jax_cache).
from cockroach_tpu.util.compile_cache import (  # noqa: E402
    resolve as _resolve_compile_cache,
)

_resolve_compile_cache()

__version__ = "0.1.0"
