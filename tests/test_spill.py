"""Out-of-core execution tests: Grace hash join, external (grace) hash
aggregation, external sort — all forced by tiny workmem budgets, results
differential-tested against the in-memory paths, and the stats collector
asserts the spill path actually executed (the reference forces spilling
the same way: logictest fakedist-disk sets SQLExecUseDisk,
logictestbase.go:49).
"""

import numpy as np
import pytest

from cockroach_tpu.exec import collect, stats
from cockroach_tpu.exec.operators import (
    HashAggOp, JoinOp, ScanOp, SortOp,
)
from cockroach_tpu.coldata.batch import Field, INT, Schema
from cockroach_tpu.ops.agg import AggSpec
from cockroach_tpu.ops.sort import SortKey


def _scan(data, capacity):
    schema = Schema([Field(n, INT) for n in data])

    def chunks():
        yield data

    return ScanOp(schema, chunks, capacity)


@pytest.fixture
def flow_stats():
    s = stats.enable()
    yield s
    stats.disable()


def test_grace_agg_matches_in_memory(rng, flow_stats):
    n = 2000
    data = {"k": rng.integers(0, 700, n).astype(np.int64),
            "v": rng.integers(0, 100, n).astype(np.int64)}
    want = collect(HashAggOp(_scan(data, 128), ["k"],
                             [AggSpec("sum", "v", "s"),
                              AggSpec("count_star", None, "n"),
                              AggSpec("min", "v", "mn")]))
    got = collect(HashAggOp(_scan(data, 128), ["k"],
                            [AggSpec("sum", "v", "s"),
                             AggSpec("count_star", None, "n"),
                             AggSpec("min", "v", "mn")],
                            workmem=1 << 12))  # 4 KiB: forces grace
    assert flow_stats.stage("agg.grace_spill").events >= 1

    def norm(r):
        return sorted(zip(r["k"].tolist(), r["s"].tolist(),
                          r["n"].tolist(), r["mn"].tolist()))
    assert norm(got) == norm(want)
    from cockroach_tpu.exec.spill import host_spill_monitor
    assert host_spill_monitor().used == 0


def test_external_sort_matches_in_memory(rng, flow_stats):
    n = 3000
    data = {"a": rng.integers(0, 50, n).astype(np.int64),
            "b": rng.integers(0, 1000, n).astype(np.int64)}
    keys = [SortKey("a"), SortKey("b", descending=True)]
    want = collect(SortOp(_scan(data, 256), keys))
    got = collect(SortOp(_scan(data, 256), keys, workmem=256 * 16))
    assert flow_stats.stage("sort.external_spill").events >= 1
    np.testing.assert_array_equal(got["a"], want["a"])
    np.testing.assert_array_equal(got["b"], want["b"])
    # and it is actually ordered
    a = got["a"]
    assert (np.diff(a) >= 0).all()
    from cockroach_tpu.exec.spill import host_spill_monitor
    assert host_spill_monitor().used == 0


def test_external_sort_merges_device_sorted_runs(rng, flow_stats):
    """VERDICT r3 item 7: the device sorts every run; the host only
    merges. Asserted via the new stage counters + exactness on a
    multi-key sort with duplicates across runs (stability matters)."""
    n = 5000
    data = {"a": rng.integers(0, 8, n).astype(np.int64),
            "b": rng.integers(-100, 100, n).astype(np.int64),
            "pay": np.arange(n, dtype=np.int64)}  # non-key: pins stability
    keys = [SortKey("a", descending=True), SortKey("b")]
    got = collect(SortOp(_scan(data, 128), keys, workmem=128 * 24),
                  fuse=False)
    assert flow_stats.stage("sort.device_run").events >= 2
    assert flow_stats.stage("sort.host_merge").events == 1
    order = np.lexsort((np.arange(n), data["b"], -data["a"]))
    np.testing.assert_array_equal(got["a"], data["a"][order])
    np.testing.assert_array_equal(got["b"], data["b"][order])
    np.testing.assert_array_equal(got["pay"], data["pay"][order])


def test_grace_agg_partition_retry_no_flow_restart(rng, flow_stats):
    """A grace-agg partition overflowing its fold capacity retries ALONE
    (doubled capacity) instead of restarting the whole flow."""
    # all groups distinct: ~1500 groups per grace partition exceeds the
    # 1024-row fold floor, forcing at least one per-partition retry
    n = 12000
    data = {"k": np.arange(n, dtype=np.int64),
            "v": np.ones(n, dtype=np.int64)}
    agg = HashAggOp(_scan(data, 512), ["k"],
                    [AggSpec("sum", "v", "s")], workmem=900)
    got = collect(agg, fuse=False)
    assert flow_stats.stage("agg.grace_spill").events >= 1
    assert flow_stats.stage("agg.grace_partition_retry").events >= 1
    assert agg.expansion == 1  # the flow itself never restarted
    assert sorted(got["k"].tolist()) == list(range(n))
    assert (got["s"] == 1).all()


def test_grace_partitioner_spill_replay_roundtrip(rng, flow_stats):
    """Direct GracePartitioner exercise (not via a join): every row that
    goes in comes back out of exactly one partition, co-partitioned by
    key, and the host-spill accounting fully releases on close."""
    from cockroach_tpu.exec.spill import (
        BlockSource, GracePartitioner, host_spill_monitor,
    )

    n = 900
    data = {"k": rng.integers(0, 50, n).astype(np.int64),
            "v": np.arange(n, dtype=np.int64)}
    scan = _scan(data, 64)

    gp = GracePartitioner(["k"], num_partitions=4)
    gp.consume_stream(scan.batches())
    assert host_spill_monitor().used > 0
    assert sum(p.n_rows for p in gp.partitions) == n

    seen = []
    keys_by_part = []
    for part in gp.partitions:
        part_keys = set()
        for b in BlockSource(part, scan.schema, 64).batches():
            sel = np.asarray(b.sel)
            ks = np.asarray(b.col("k").values)[sel]
            vs = np.asarray(b.col("v").values)[sel]
            seen.extend(zip(ks.tolist(), vs.tolist()))
            part_keys.update(ks.tolist())
        keys_by_part.append(part_keys)
    # exact row multiset roundtrip
    assert sorted(seen) == sorted(zip(data["k"].tolist(),
                                      data["v"].tolist()))
    # same key never lands in two partitions (Grace invariant)
    for i in range(len(keys_by_part)):
        for j in range(i + 1, len(keys_by_part)):
            assert not (keys_by_part[i] & keys_by_part[j])
    gp.close()
    assert host_spill_monitor().used == 0


def test_join_result_overflow_flag():
    """out_capacity smaller than the true match count must raise the
    overflow flag (int64-counted, ops/join.py) — FlowRestart's doubling
    trigger; a roomy capacity must not."""
    import jax.numpy as jnp

    from cockroach_tpu.coldata.batch import Batch, Column
    from cockroach_tpu.ops.join import hash_join

    # all 32 probe rows match all 32 build rows: 1024 true pairs
    probe = Batch.from_columns(
        {"a": Column(jnp.zeros(32, dtype=jnp.int64)),
         "pv": Column(jnp.arange(32, dtype=jnp.int64))})
    build = Batch.from_columns(
        {"b": Column(jnp.zeros(32, dtype=jnp.int64)),
         "bv": Column(jnp.arange(32, dtype=jnp.int64))})

    res = hash_join(probe, build, ["a"], ["b"], how="inner",
                    out_capacity=64)
    assert bool(res.overflow)
    assert int(np.asarray(res.batch.sel).sum()) <= 64

    res = hash_join(probe, build, ["a"], ["b"], how="inner",
                    out_capacity=2048)
    assert not bool(res.overflow)
    assert int(np.asarray(res.batch.sel).sum()) == 1024
    # and the emitted pairs are the full cross product
    sel = np.asarray(res.batch.sel)
    pairs = set(zip(np.asarray(res.batch.col("pv").values)[sel].tolist(),
                    np.asarray(res.batch.col("bv").values)[sel].tolist()))
    assert pairs == {(p, b) for p in range(32) for b in range(32)}


def test_disk_queue_roundtrip_blocks():
    from cockroach_tpu.exec.spill import DiskQueueFile, SpilledBlock

    f = DiskQueueFile()
    blocks = [
        SpilledBlock(3, {"a": np.asarray([1, 2, 3], np.int64),
                         "b": np.asarray([0.5, 1.5, 2.5], np.float32)},
                     {"a": np.asarray([True, False, True]),
                      "b": None}),
        SpilledBlock(2, {"a": np.asarray([9, 8], np.int64),
                         "b": np.asarray([7.0, 6.0], np.float32)},
                     {"a": None, "b": None}),
    ]
    for b in blocks:
        f.append(b)
    out = list(f.replay())
    assert len(out) == 2
    np.testing.assert_array_equal(out[0].values["a"], [1, 2, 3])
    np.testing.assert_array_equal(out[0].validity["a"],
                                  [True, False, True])
    assert out[0].validity["b"] is None
    np.testing.assert_array_equal(out[1].values["b"],
                                  np.asarray([7.0, 6.0], np.float32))
    f.close()
    import os
    assert not os.path.exists(f.path)
