"""Smoke check: pow2 chunk-count bucketing bounds compiled-program
cardinality.

Without bucketing, every distinct chunk count (data scale) produced its
own fused config key -> its own XLA compile (minutes for a join query on
the TPU). With stacked_image padding chunk counts to the next power of
two, one plan SHAPE must map to at most log2(max_chunks)+1 distinct keys
no matter how many scales run.

Run: JAX_PLATFORMS=cpu python scripts/check_key_bucketing.py
Exits non-zero on violation (CI smoke gate; no device compiles — only
key construction is exercised).
"""

import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the dist-key check below constructs meshes of 1/2/4/8 devices (key
# construction only — no compiles): force the virtual CPU mesh before
# any backend initializes (tests/conftest.py recipe)
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np

from cockroach_tpu.coldata.batch import Field, INT, Schema
from cockroach_tpu.exec.fused import FusedRunner
from cockroach_tpu.exec.operators import HashAggOp, JoinOp, ScanOp
from cockroach_tpu.ops.agg import AggSpec

CAPACITY = 64
MAX_CHUNKS = 48  # scan sizes 1..48 chunks, i.e. up to 3072 rows at cap 64


def _scan(n_rows):
    data = {"k": np.arange(n_rows, dtype=np.int64) % 7,
            "v": np.ones(n_rows, dtype=np.int64)}

    def chunks():
        yield data

    return ScanOp(Schema([Field("k", INT), Field("v", INT)]),
                  chunks, CAPACITY)


def _agg_plan(n_rows):
    return HashAggOp(_scan(n_rows), ["k"], [AggSpec("sum", "v", "s")])


def _join_plan(n_rows):
    probe = _scan(n_rows)
    build = ScanOp(Schema([Field("bk", INT), Field("bv", INT)]),
                   lambda: iter([{"bk": np.arange(CAPACITY, dtype=np.int64),
                                  "bv": np.arange(CAPACITY,
                                                  dtype=np.int64)}]),
                   CAPACITY)
    return JoinOp(probe, build, ["k"], ["bk"])


def keys_for(mk_plan):
    """Config keys across every chunk count 1..MAX_CHUNKS for one plan
    shape — key construction only, no compilation."""
    from cockroach_tpu.exec.operators import walk_operators

    keys = set()
    for n_chunks in range(1, MAX_CHUNKS + 1):
        plan = mk_plan(n_chunks * CAPACITY)
        runner = FusedRunner(plan)
        chunk_counts = {id(op): (n_chunks
                                 if any(f.name == "k" for f in op.schema)
                                 else 1)
                        for op in walk_operators(plan)
                        if isinstance(op, ScanOp)}
        keys.add(runner._config_key(plan, chunk_counts))
    return keys


def ycsb_op_buckets():
    """YCSB-E micro-query batching pads the op batch the same way the
    fused config keys pad chunk counts: every op count 1..MAX_CHUNKS
    must land in one of the pow2 jit shape buckets."""
    from cockroach_tpu.workload.ycsb import batch_bucket

    return {batch_bucket(n) for n in range(1, MAX_CHUNKS + 1)}


def serving_shape_cache():
    """Cross-session serving batches pad to pow2 the same way: driving
    a ServingScanRunner through EVERY batch size 1..MAX_CHUNKS must
    leave at most log2+1 compiled shapes in its jit cache (counted from
    the jit cache itself, so a padding regression can't hide)."""
    from cockroach_tpu.exec.fused import ServingScanRunner

    pks = np.arange(CAPACITY, dtype=np.int64)
    runner = ServingScanRunner(pks, {"v": pks * 3},
                               {"v": np.ones(CAPACITY, dtype=bool)},
                               window=8)
    for b in range(1, MAX_CHUNKS + 1):
        z = np.zeros(b, dtype=np.int64)
        runner.run(z, np.full(b, 4, dtype=np.int64),
                   np.full(b, 8, dtype=np.int64))
    return runner._batched._cache_size()


def serving_class_shape_caches():
    """Every widened serving class honours the same pow2 batch padding:
    drive each class runner through batch sizes 1..MAX_CHUNKS and count
    its compiled program shapes. Yields (class name, cache size)."""
    from cockroach_tpu.exec.fused import (
        ServingAggRunner, ServingTopKRunner, ServingVectorRunner,
    )

    pks = np.arange(CAPACITY, dtype=np.int64)
    ones = np.ones(CAPACITY, dtype=bool)
    agg = ServingAggRunner(
        pks, {"v": pks * 3}, {"v": ones},
        aggs=(("count_star", None), ("sum", "v"), ("avg", "v")),
        names=("c", "s", "a"), window=8)
    for b in range(1, MAX_CHUNKS + 1):
        z = np.zeros(b, dtype=np.int64)
        agg.run(z, np.full(b, 4, dtype=np.int64))
    yield "agg", agg._batched._cache_size()

    topk = ServingTopKRunner(
        pks, {"v": pks * 3}, {"v": ones},
        order_vals=(pks * 7) % 13, order_valid=ones,
        descending=False, window=8)
    for b in range(1, MAX_CHUNKS + 1):
        z = np.zeros(b, dtype=np.int64)
        topk.run(z, np.full(b, 4, dtype=np.int64),
                 np.full(b, 3, dtype=np.int64))
    yield "topk", topk._batched._cache_size()

    vecs = np.arange(CAPACITY * 4, dtype=np.float32).reshape(
        CAPACITY, 4)
    vec = ServingVectorRunner(pks, {"pk": pks}, {"pk": ones},
                              vecs, ones, metric="l2", k=3)
    for b in range(1, MAX_CHUNKS + 1):
        vec.run(np.zeros((b, 4), dtype=np.float32))
    yield "vector", vec._batched._cache_size()


def dist_keys_by_mesh():
    """Distributed config keys must stay bounded per (mesh size x pow2
    chunk bucket): driving one plan shape through every chunk count
    1..MAX_CHUNKS on meshes of 1/2/4/8 devices may produce at most
    log2(MAX_CHUNKS)+1 keys PER MESH — the sharded-bucket analog of the
    single-chip check above (key construction only, no compiles).
    Yields (mesh size, key count)."""
    import jax

    from cockroach_tpu.exec.operators import walk_operators
    from cockroach_tpu.exec.operators import _pow2_at_least
    from cockroach_tpu.parallel import make_mesh
    from cockroach_tpu.parallel.dist_flow import DistFusedRunner
    from cockroach_tpu.parallel.ingest import REPLICATED, SHARDED

    sizes = [n for n in (1, 2, 4, 8) if n <= len(jax.devices())]
    for n_dev in sizes:
        mesh = make_mesh(n_dev)
        keys = set()
        for n_chunks in range(1, MAX_CHUNKS + 1):
            plan = _join_plan(n_chunks * CAPACITY)
            runner = DistFusedRunner(plan, mesh)
            chunks = {id(op): (n_chunks
                               if any(f.name == "k" for f in op.schema)
                               else 1)
                      for op in walk_operators(plan)
                      if isinstance(op, ScanOp)}
            sharded, repart = runner._classify(chunks)
            layout = {}
            for op in walk_operators(plan):
                if not isinstance(op, ScanOp):
                    continue
                n = chunks[id(op)]
                if id(op) in sharded:
                    layout[id(op)] = (
                        SHARDED, _pow2_at_least(max(1, -(-n // n_dev))))
                else:
                    layout[id(op)] = (REPLICATED, _pow2_at_least(n))
            keys.add(runner._config_key(layout, repart))
        yield n_dev, len(keys)


def main() -> int:
    # pow2 buckets covering 1..MAX_CHUNKS: {1, 2, 4, ..., 2^ceil(log2 max)}
    bound = math.ceil(math.log2(MAX_CHUNKS)) + 1
    failures = 0
    for name, mk in (("hash-agg", _agg_plan), ("hash-join", _join_plan)):
        n_keys = len(keys_for(mk))
        ok = n_keys <= bound
        print(f"{name:<10} chunk counts 1..{MAX_CHUNKS} -> {n_keys} "
              f"config keys (bound {bound}): {'OK' if ok else 'FAIL'}")
        failures += 0 if ok else 1
    buckets = ycsb_op_buckets()
    ok = (len(buckets) <= bound
          and all(b & (b - 1) == 0 for b in buckets))
    print(f"{'ycsb-ops':<10} op counts    1..{MAX_CHUNKS} -> {len(buckets)} "
          f"batch buckets (bound {bound}): {'OK' if ok else 'FAIL'}")
    failures += 0 if ok else 1
    n_shapes = serving_shape_cache()
    ok = n_shapes <= bound
    print(f"{'serving':<10} batch sizes  1..{MAX_CHUNKS} -> {n_shapes} "
          f"jit shapes    (bound {bound}): {'OK' if ok else 'FAIL'}")
    failures += 0 if ok else 1
    for cls, n_shapes in serving_class_shape_caches():
        ok = n_shapes <= bound
        print(f"{'serving-' + cls:<14} batch sizes 1..{MAX_CHUNKS} -> "
              f"{n_shapes} jit shapes (bound {bound}): "
              f"{'OK' if ok else 'FAIL'}")
        failures += 0 if ok else 1
    for n_dev, n_keys in dist_keys_by_mesh():
        ok = n_keys <= bound
        print(f"{'dist@' + str(n_dev):<10} chunk counts 1..{MAX_CHUNKS} -> "
              f"{n_keys} config keys (bound {bound} per mesh): "
              f"{'OK' if ok else 'FAIL'}")
        failures += 0 if ok else 1
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
