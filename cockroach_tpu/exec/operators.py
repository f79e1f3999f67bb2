"""Streaming operator tree over jit-compiled stage kernels.

Reference seams this mirrors (SURVEY.md §2.2-2.3):
- `colexecop.Operator` Init/Next pull contract (operator.go:22) becomes
  `Operator.batches()` generators driven by the host;
- `colbuilder.NewColOperator` (execplan.go:785) — the planner assembles
  these objects (sql/ planner in M5);
- the disk-spilling wrappers (colexecdisk/disk_spiller.go:208) become the
  join overflow-retry loop and (later) Grace partitioning in spill.py.

Operators carry a `Schema` for their output; all device work happens in
jit-compiled closures cached per (operator, batch capacity) — the analog
of execgen's per-type specialization, done by XLA per-shape.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cockroach_tpu.coldata.arrow import numpy_to_batch
from cockroach_tpu.coldata.batch import (
    BOOL, Batch, ColType, Column, Field, FLOAT, INT, Kind, Schema,
    concat_batches, first_selected, mask_padding,
)
from cockroach_tpu.ops.agg import AggSpec, hash_aggregate
from cockroach_tpu.ops.expr import Expr, Col, eval_expr, filter_mask
from cockroach_tpu.ops.join import hash_join
from cockroach_tpu.ops.sort import SortKey, sort_batch, top_k_batch
from cockroach_tpu.exec import stats
from cockroach_tpu.util import cancel as _cancel
from cockroach_tpu.util import retry as _retry
from cockroach_tpu.util import tracing as _tracing
from cockroach_tpu.util.fault import maybe_fail
from cockroach_tpu.util.mon import BytesMonitor
from cockroach_tpu.util.settings import Settings


class FlowRestart(Exception):
    """Raised at end-of-stream when a deferred capacity check failed
    (join expansion overflow). The flow driver (collect) discards results,
    widens the failed operator, and reruns — the in-HBM analog of the
    reference's spill-on-OOM operator swap (disk_spiller.go:208): optimistic
    fast path, pay only on overflow. Keeping the check DEFERRED keeps the
    steady-state loop free of device->host syncs, each of which stalls
    the dispatch pipeline for a host round trip."""

    def __init__(self, op: "Operator"):
        self.op = op
        super().__init__("flow restart: operator capacity overflow")


class Operator:
    """Base: a node in the flow tree producing a stream of device Batches."""

    schema: Schema

    def batches(self) -> Iterator[Batch]:
        raise NotImplementedError

    def pipeline(self):
        """Fusion seam: (stream_thunk, traceable_fn) such that
        `traceable_fn(item)` for item in `stream_thunk()` yields this
        operator's batches. Pipeline breakers return their own batches with
        the identity fn; per-batch transforms (MapOp) compose onto their
        child so a consumer jits source-to-sink in ONE program — critical
        on TPU, where every separate dispatch pays launch latency and every
        un-fused intermediate pays an HBM round trip.
        """
        return self.batches, (lambda b: b)


def _prefetch(it: Iterator, depth: int = 4) -> Iterator:
    """Producer-thread prefetch: host-side chunk prep (datagen slicing,
    packing) and the jnp.asarray transfer dispatch run on a background
    thread while the consumer executes — the reference's outbox/inbox
    goroutine concurrency (SURVEY.md §7.4 item 3): transfers stay in
    flight while the device computes.

    If the consumer abandons the stream early (LIMIT, empty build side),
    closing this generator stops the producer and closes the source
    iterator so it can release resources (the drain path — flows must not
    leak on early exit, flowinfra/flow.go cancellation).
    """
    import queue as _queue
    import threading

    q: "_queue.Queue" = _queue.Queue(maxsize=depth)
    _END = object()
    err: list = []
    stop = threading.Event()
    # The producer runs on its own thread, where the thread-local span
    # stack is empty — hand it the active trace (the in-process analog of
    # SetupFlowRequest.TraceInfo) so transfer retries reach the recording.
    carrier = _tracing.tracer().carrier()

    def halted():
        return stop.is_set() or flow_stopper().should_stop

    def produce():
        try:
            for item in it:
                while not halted():
                    try:
                        q.put(item, timeout=0.1)
                        break
                    except _queue.Full:
                        continue
                if halted():
                    break
        except BaseException as e:  # propagate to consumer
            err.append(e)
        finally:
            if halted():
                close = getattr(it, "close", None)
                if close is not None:
                    close()
            while True:
                try:
                    q.put(_END, timeout=0.1)
                    break
                except _queue.Full:
                    if halted():
                        break

    from cockroach_tpu.util.stop import StopperStopped

    def produce_tracked():
        try:
            with flow_stopper().task("scan-prefetch"):
                if carrier is not None:
                    with _tracing.tracer().from_carrier(
                            carrier, "scan.prefetch"):
                        produce()
                else:
                    produce()
        except StopperStopped as e:
            # engine shutting down: work submitted after Stop() FAILS
            # (the reference returns ErrUnavailable); deliver the error +
            # end-of-stream so the consumer raises instead of blocking
            err.append(e)
            q.put(_END)

    t = threading.Thread(target=produce_tracked, daemon=True)
    t.start()
    try:
        while True:
            # timeout-poll instead of a bare blocking get: a CancelRequest
            # must interrupt a consumer stuck behind a stalled producer
            # (e.g. a blocking fault seam) — the checkpoint is a no-op
            # when no statement cancel context is active on this thread
            try:
                item = q.get(timeout=0.1)
            except _queue.Empty:
                _cancel.checkpoint()
                continue
            if item is _END:
                if err:
                    raise err[0]
                return
            yield item
    finally:
        stop.set()


def _read_ahead(it: Iterator, depth: int = 2) -> Iterator:
    """Double-buffered pull: keep `depth` items materialized ahead of the
    consumer so the host->device transfer of chunk N+1 (dispatched inside
    the producer's jnp.asarray/device_put) overlaps device execution of
    chunk N's consumer. Same-thread, no queue — jax transfers dispatch
    asynchronously, so merely *pulling* the next item early starts its
    copy. Complements _prefetch: ScanOp streams already run a producer
    thread, but BlockSource replay (grace-spill partitions) and other bare
    generators transfer lazily on next()."""
    from collections import deque

    buf: "deque" = deque()
    it = iter(it)
    while True:
        while len(buf) < depth:
            try:
                buf.append(next(it))
            except StopIteration:
                while buf:
                    yield buf.popleft()
                return
        yield buf.popleft()


_flow_stopper = None


def flow_stopper():
    """Process stopper owning the flow runtime's background threads
    (prefetch producers); `flow_stopper().stop()` drains them — the
    util/stop.Stopper seam (stopper.go:152) the server layer will own."""
    global _flow_stopper
    if _flow_stopper is None:
        from cockroach_tpu.util.stop import Stopper

        _flow_stopper = Stopper()
    return _flow_stopper


def _pow2_at_least(n: int) -> int:
    size = 1
    while size < n:
        size *= 2
    return size


# --------------------------------------------------------------------- scan

HBM_CACHE_BUDGET = Settings.register(
    "storage.hbm_cache_bytes",
    8 << 30,
    "HBM budget for device-resident table shards (the block-cache analog)",
)

_hbm_cache_monitor: Optional["BytesMonitor"] = None


def hbm_cache_monitor() -> "BytesMonitor":
    """Process-wide monitor accounting HBM held by resident scans — the
    analog of the reference's block cache sizing (Pebble cache +
    mon.BytesMonitor root, util/mon/bytes_usage.go:174)."""
    global _hbm_cache_monitor
    if _hbm_cache_monitor is None:
        _hbm_cache_monitor = BytesMonitor(
            "hbm-table-cache", budget=Settings().get(HBM_CACHE_BUDGET))
    return _hbm_cache_monitor


class ScanOp(Operator):
    """Source from host chunks (numpy column dicts). The seam where the C++
    MVCC scanner's Arrow output enters the device (ref: colfetcher
    ColBatchScan, colbatch_scan.go:212).

    Ingest packs every column of a chunk into ONE uint8 buffer (narrow
    Field.wire dtypes) -> ONE host->device transfer, then a traceable
    unpack (bitcast slices + widening) reconstructs the Batch on device —
    the unpack fuses into the consumer's program via pipeline(). (The
    per-column jnp.asarray path pays a transfer's fixed latency per
    column; one large transfer runs at the link's bandwidth.)

    With `resident=True` the packed device buffers are pinned in HBM after
    the first full pass (accounted against `hbm_cache_monitor`), so warm
    re-scans never cross the host->device link — the TPU analog of the
    reference's warm Pebble block cache, which is exactly the state
    BASELINE.md's measurement protocol specifies (warm cache, median of
    >=5 runs). If the budget is exhausted the scan silently stays
    streaming-only.

    With `cache_key` set (a content-identity tuple from
    Catalog.scan_cache_key) the stacked image is shared through the
    process-wide ScanImageCache (exec/scan_cache.py): a fresh plan build
    over an unchanged table borrows the cached HBM copy instead of
    re-packing and re-transferring it. The cache owns the HBM accounting
    for shared images; the per-op monitor pin only covers private ones.
    """

    def __init__(self, schema: Schema, chunks: Callable[[], Iterator[Dict[str, np.ndarray]]],
                 capacity: int, resident: bool = False,
                 monitor: Optional["BytesMonitor"] = None,
                 cache_key: Optional[tuple] = None,
                 table: Optional[str] = None):
        self.schema = schema
        self._chunks = chunks
        self.capacity = capacity
        self.resident = resident
        self.cache_key = cache_key
        # source table name (when the planner knows it): tags vault
        # artifacts so DDL/ANALYZE can garbage-collect them by table
        self.table = table
        self._monitor = monitor
        self._cache: Optional[list] = None
        self._cache_account = None
        self._stacked: Optional[tuple] = None
        self._stacked_account = None
        self._stacked_chunks: Optional[int] = None  # real (un-padded) count
        from cockroach_tpu.coldata.arrow import make_unpack
        self._unpack = make_unpack(schema, capacity)
        self._unpack_jit = jax.jit(self._unpack)

    def _raw_stream(self):
        if self._stacked is None and self.cache_key is not None:
            self._borrow_cached()
        if self._stacked is not None:
            # the stacked image is the canonical resident representation
            # (one HBM copy); streaming passes read row slices of it —
            # only the real chunks, not the pow2 padding tail
            bufs, ms = self._stacked
            n = self._stacked_chunks or bufs.shape[0]
            return iter([(bufs[i], ms[i]) for i in range(n)])
        if self._cache is not None:
            return iter(list(self._cache))

        from cockroach_tpu.coldata.arrow import pack_chunk
        from cockroach_tpu.util.mon import BudgetExceededError

        def gen():
            acct = None
            if self.resident:
                mon = self._monitor or hbm_cache_monitor()
                acct = mon.make_account()
            cache: list = []
            complete = False
            try:
                for chunk in self._chunks():
                    n = len(next(iter(chunk.values())))
                    for a in range(0, n, self.capacity):
                        piece = {k: v[a:a + self.capacity]
                                 for k, v in chunk.items()}
                        with stats.timed("scan.pack",
                                         rows=min(n - a, self.capacity)):
                            buf, m = pack_chunk(piece, self.schema, self.capacity)
                        def transfer(buf=buf, m=m):
                            maybe_fail("scan.transfer")
                            return (jnp.asarray(buf), jnp.int32(m))

                        with stats.timed("scan.transfer", bytes=buf.nbytes):
                            item = _retry.with_retry(transfer,
                                                     name="scan.transfer")
                        if acct is not None:
                            try:
                                acct.grow(buf.nbytes)
                                cache.append(item)
                            except BudgetExceededError:
                                acct.close()
                                acct, cache = None, []
                        yield item
                complete = True
                if acct is not None:
                    # only a COMPLETE pass becomes the resident image (an
                    # early-exiting consumer, e.g. LIMIT, must not pin a
                    # prefix)
                    self._cache = cache
                    self._cache_account = acct
            finally:
                if not complete and acct is not None:
                    acct.close()  # abandoned stream releases its accounting

        return _prefetch(gen())

    def evict(self):
        """Drop the resident image and release its HBM accounting (a
        cache-borrowed image is just un-referenced; the shared copy stays
        until LRU eviction or storage-write invalidation)."""
        self._cache = None
        if self._cache_account is not None:
            self._cache_account.close()
            self._cache_account = None
        self._stacked = None
        self._stacked_chunks = None
        if self._stacked_account is not None:
            self._stacked_account.close()
            self._stacked_account = None

    def _borrow_cached(self) -> Optional[tuple]:
        """Adopt the shared image for this scan's cache key, if present."""
        from cockroach_tpu.exec.scan_cache import scan_image_cache

        hit = scan_image_cache().get(self.cache_key)
        if hit is None:
            return None
        st, n_real = hit
        self._stacked = st
        self._stacked_chunks = n_real
        return st

    def _drop_chunk_cache(self):
        self._cache = None
        if self._cache_account is not None:
            self._cache_account.close()
            self._cache_account = None

    def stacked_image(self) -> Optional[tuple]:
        """(bufs (N, nbytes), ms (N,)) device arrays holding every chunk of
        this scan — the input format of fused whole-flow programs
        (exec/fused.py), which lax.scan over the leading axis. Returns None
        for an empty scan. N is padded to the next power of two with empty
        (m=0) chunks: trailing pads unpack to all-dead batches, so the fused
        config key buckets to ~log2(max chunks) distinct program shapes per
        plan instead of one per exact chunk count.

        When the scan is resident the stack REPLACES the per-chunk cache as
        the pinned image (one HBM copy of the table, accounted against the
        HBM cache monitor; streaming passes then read row slices of it).
        On budget exhaustion the stack is rebuilt per call instead of
        pinned. Non-resident scans pay the host->device transfers on every
        call, exactly like a streaming pass."""
        from cockroach_tpu.util.mon import BudgetExceededError

        if self._stacked is not None:
            return self._stacked
        if self.cache_key is not None:
            st = self._borrow_cached()
            if st is not None:
                return st
        items = self._cache
        if items is None:
            items = list(self._raw_stream())  # populates cache if resident
            if self._cache is not None:
                items = self._cache
        if not items:
            return None
        n_real = len(items)
        pad = _pow2_at_least(n_real) - n_real

        def stack():
            maybe_fail("scan.stack")
            zbuf = jnp.zeros_like(items[0][0])
            bufs = jnp.stack([b for b, _ in items] + [zbuf] * pad)
            ms = jnp.stack([jnp.asarray(m, jnp.int32) for _, m in items]
                           + [jnp.int32(0)] * pad)
            return bufs, ms

        with stats.timed("scan.stack",
                         bytes=sum(b.nbytes for b, _ in items)):
            _tracing.set_tag(chunks=n_real)
            bufs, ms = _retry.with_retry(stack, name="scan.stack")
        st = (bufs, ms)
        if self.cache_key is not None:
            from cockroach_tpu.exec.scan_cache import scan_image_cache

            if scan_image_cache().put(self.cache_key, (st, n_real),
                                      bufs.nbytes + ms.nbytes):
                # the shared cache owns the HBM accounting for this image
                self._stacked = st
                self._stacked_chunks = n_real
                self._drop_chunk_cache()
                return st
        if self._cache is not None:
            mon = self._monitor or hbm_cache_monitor()
            acct = mon.make_account()
            try:
                acct.grow(bufs.nbytes + ms.nbytes)
                self._stacked = st
                self._stacked_chunks = n_real
                self._stacked_account = acct
                # release the chunk-cache copy: one resident image, not two
                self._drop_chunk_cache()
            except BudgetExceededError:
                acct.close()
        return st

    def pipeline(self):
        return self._raw_stream, (lambda item: self._unpack(*item))

    def batches(self) -> Iterator[Batch]:
        for item in self._raw_stream():
            yield self._unpack_jit(*item)


# ---------------------------------------------------------------- map (fuse)

class MapOp(Operator):
    """A fused chain of filters and projections — one jitted kernel.

    steps: ("filter", expr) | ("project", [(name, expr)]).
    A project step defines the COMPLETE output column list (reference:
    DistSQL post-processing spec's render exprs).
    """

    def __init__(self, child: Operator, steps: Sequence[Tuple[str, object]]):
        self.child = child
        self.steps = list(steps)
        self.schema = self._infer_schema(child.schema)
        self._fn = jax.jit(self._run)

    def _infer_schema(self, schema: Schema) -> Schema:
        for kind, payload in self.steps:
            if kind == "project":
                fields = []
                for name, e in payload:
                    ty = e.type(schema)
                    dict_ref = None
                    if isinstance(e, Col) and ty.kind is Kind.STRING:
                        dict_ref = schema.field(e.name).dict_ref
                    fields.append(Field(name, ty, dict_ref))
                schema = Schema(fields, schema.dicts)
        return schema

    def _run(self, batch: Batch) -> Batch:
        schema = self.child.schema
        for kind, payload in self.steps:
            if kind == "filter":
                batch = batch.filter(filter_mask(payload, batch, schema))
            else:
                cols = {name: eval_expr(e, batch, schema)
                        for name, e in payload}
                batch = Batch(cols, batch.sel, batch.length)
                schema = self._infer_schema_once(schema, payload)
        return batch

    def _infer_schema_once(self, schema, payload):
        fields = []
        for name, e in payload:
            ty = e.type(schema)
            dict_ref = None
            if isinstance(e, Col) and ty.kind is Kind.STRING:
                dict_ref = schema.field(e.name).dict_ref
            fields.append(Field(name, ty, dict_ref))
        return Schema(fields, schema.dicts)

    def pipeline(self):
        stream, f = self.child.pipeline()
        run = self._run
        return stream, (lambda item: run(f(item)))

    def batches(self) -> Iterator[Batch]:
        if not hasattr(self, "_fused_jit"):
            stream, f = self.pipeline()
            self._fused_stream, self._fused_jit = stream, jax.jit(f)
        for item in self._fused_stream():
            yield self._fused_jit(item)


# ----------------------------------------------------------------- hash agg

_MERGE_FUNC = {"sum": "sum", "count": "sum", "count_star": "sum",
               "sum_hi32": "sum", "sum_lo32": "sum",
               "min": "min", "max": "max", "bool_and": "bool_and",
               "bool_or": "bool_or", "any_not_null": "any_not_null"}


def _grow_to(b: Batch, acc_cap: int) -> Batch:
    """Traceable: normalize a compact partial into the accumulator shape —
    capacity acc_cap, every column carrying an explicit validity (so the
    fold's pytree structure is identical from the first batch on)."""
    idx = jnp.arange(acc_cap, dtype=jnp.int32) % b.capacity
    sel = jnp.arange(acc_cap) < b.length
    cols = {n: Column(c.values[idx], c.valid_mask()[idx])
            for n, c in b.columns.items()}
    return Batch(mask_padding(cols, sel), sel, b.length)


def _fold_step(acc: Batch, part: Batch, acc_cap: int, group_by, merge_aggs,
               seed: int = 0):
    """Traceable (acc, part) -> (acc', overflow): merge-aggregate the
    concatenated pair, slice back to acc_cap. Compact outputs guarantee
    live groups are a prefix, so the slice loses nothing unless
    total groups > acc_cap — reported via the overflow flag (which also
    carries the hash-grouping collision bit: both are answered by the
    same widen-and-rerun restart)."""
    merged, coll = hash_aggregate(concat_batches([acc, part]), group_by,
                                  merge_aggs, seed=seed, method="hash",
                                  with_flag=True)
    overflow = (merged.length > acc_cap) | coll
    idx = jnp.arange(acc_cap, dtype=jnp.int32) % merged.capacity
    sel = jnp.arange(acc_cap) < merged.length
    length = jnp.minimum(merged.length, jnp.int32(acc_cap))
    cols = {n: Column(c.values[idx], c.valid_mask()[idx])
            for n, c in merged.columns.items()}
    return Batch(mask_padding(cols, sel), sel, length), overflow


class HashAggOp(Operator):
    """Streaming GROUP BY: per-batch partial aggregation folded into a
    fixed-capacity device accumulator (ref: hash_aggregator.go:62; the
    partial/final split is the reference's distributed two-stage
    aggregation, aggregators placed on data nodes + final on gateway).

    The fold is one async dispatch per batch with ZERO host syncs until
    end-of-stream: partial(item) -> merge(acc, partial) re-aggregates the
    concatenated pair with merge functions and slices back to the
    accumulator capacity. If total live groups ever exceed that capacity
    a deferred overflow flag trips FlowRestart AFTER the final batch is
    yielded (one end-of-stream readback, same posture as JoinOp) and the
    retry doubles `expansion`. A host sync per batch would serialize the
    device behind the host — the fold's no-sync property IS the
    performance design.
    """

    def __init__(self, child: Operator, group_by: Sequence[str],
                 aggs: Sequence[AggSpec], expansion: int = 1,
                 workmem: Optional[int] = None,
                 key_domains: Optional[Dict[str, Tuple[int, int]]] = None):
        self.child = child
        self.group_by = list(group_by)
        # planner hint (stats-derived, sql/plan.key_domains): group key ->
        # its value range [lo, hi]. Beside the dictionary and bool keys it
        # makes the key space small and static (ops/agg.dense_key_sizes):
        # the aggregate then lowers dense, group g at lane g. A live key
        # outside its range (a row written after ANALYZE) raises the
        # deferred flag and widen() drops the ranges.
        self.key_domains = dict(key_domains) if key_domains else None
        self.user_aggs = list(aggs)
        self.expansion = expansion  # acc capacity multiplier (restart doubles)
        self.seed = 0  # hash-grouping seed (restart re-seeds)
        from cockroach_tpu.util.settings import WORKMEM
        self.workmem = (Settings().get(WORKMEM) if workmem is None else workmem)
        # decompose avg -> sum + count for mergeability
        self.internal: List[AggSpec] = []
        self._avg_parts: Dict[str, Tuple[str, str]] = {}
        names = set()
        self._wide_sums: List[str] = []
        for a in aggs:
            if a.func == "avg":
                s_name, c_name = f"__avg_sum_{a.out}", f"__avg_cnt_{a.out}"
                self.internal += [AggSpec("sum", a.col, s_name),
                                  AggSpec("count", a.col, c_name)]
                self._avg_parts[a.out] = (s_name, c_name)
            elif a.func == "sum" and a.wide:
                # exact-beyond-int64 sums: two independent int64 halves on
                # device; `<out>__hi * 2**32 + <out>__lo` recombines
                # exactly on the host (arbitrary-precision ints /
                # decimal128 in the arrow layer)
                self.internal += [
                    AggSpec("sum_hi32", a.col, f"{a.out}__hi"),
                    AggSpec("sum_lo32", a.col, f"{a.out}__lo")]
                self._wide_sums.append(a.out)
            else:
                self.internal.append(a)
            names.add(a.out)
        self.schema = self._infer_schema(child.schema)
        # schema of the internal (pre-finalize) aggregate rows — what the
        # fold accumulator holds and what the grace path spills/replays
        self._internal_schema = Schema(
            [child.schema.field(n) for n in self.group_by]
            + [Field(a.out, self._agg_out_type(a, child.schema))
               for a in self.internal],
            child.schema.dicts)
        stream, f = child.pipeline()
        self._stream = stream
        self._chunk_fn = f
        self._merge_aggs = tuple(AggSpec(_MERGE_FUNC[a.func], a.out, a.out)
                                 for a in self.internal)
        self._finalize = jax.jit(self._final_project)
        self._make_kernels()
        self._make_dense()

    def _make_dense(self):
        """The dense (sort-free) path for small static key domains — see
        ops/agg.py dense_aggregate; partials fold lane-wise so the whole
        streaming aggregation compiles without a single sort HLO. ONE
        decision (`_dense_sizes`) for this operator's own fold, the fused
        tracer and the distributed one; with ranged keys every partial
        carries the out-of-range flag. Called at construction and again by
        widen() once the ranges are dropped."""
        from cockroach_tpu.ops.agg import (
            dense_aggregate, dense_key_sizes, dense_merge,
        )
        self._dense_sizes = (
            dense_key_sizes(self.child.schema, self.group_by,
                            self.key_domains)
            if self.group_by else None)
        if self._dense_sizes is None:
            self.key_domains = None  # a range no dense lowering reads
            return
        sizes, doms = tuple(self._dense_sizes), self.key_domains
        gb, internal = tuple(self.group_by), tuple(self.internal)
        f = self._chunk_fn

        def partial(item):
            return dense_aggregate(f(item), gb, internal, sizes, doms,
                                   with_flag=True)

        def fold(carry, item):
            part, fl = partial(item)
            return dense_merge(carry[0], part, gb, internal), carry[1] | fl

        self._dense_partial = jax.jit(partial)
        self._dense_fold = jax.jit(fold)
        self._dense_final = jax.jit(
            lambda acc: self._final_project(acc.compact()))

    def _make_kernels(self):
        """(Re)build the jitted partial/merge kernels for the CURRENT seed
        — called at construction and again by widen() after a re-seed."""
        f, seed = self._chunk_fn, self.seed
        gb, internal = tuple(self.group_by), tuple(self.internal)
        self._partial = jax.jit(
            lambda item: hash_aggregate(f(item), gb, internal, seed=seed,
                                        method="hash", with_flag=True))
        self._merge_partial = jax.jit(
            lambda b: hash_aggregate(b, gb, self._merge_aggs, seed=seed,
                                     method="hash", with_flag=True))
        self._fold_jit: Dict[Tuple[int, int], Callable] = {}
        self._grow_jit: Dict[Tuple[int, int], Callable] = {}
        # whole-stream stacked-fold programs (seed-dependent via _partial)
        self._stacked_jit: Dict[tuple, Callable] = {}

    def widen(self):
        """FlowRestart remedy: a dense aggregate's flag (a key outside
        its range, stale statistics: the only flag it raises) drops the
        ranges, so that the keys without a static domain hash again;
        otherwise double the accumulator expansion (group overflow) AND
        re-seed the key hash (collision)."""
        if self.key_domains:
            self.key_domains = None
            self._make_dense()
            self._stacked_jit.clear()
            return
        self.expansion *= 2
        self.seed += 1
        self._make_kernels()

    def _agg_out_type(self, a: AggSpec, schema: Schema) -> ColType:
        if a.func in ("count", "count_star"):
            return INT
        if a.func == "avg":
            return FLOAT
        if a.func in ("bool_and", "bool_or"):
            return BOOL
        return schema.field(a.col).type

    def _infer_schema(self, schema: Schema) -> Schema:
        fields = [schema.field(n) for n in self.group_by]
        for a in self.user_aggs:
            if a.func == "sum" and a.wide:
                fields.append(Field(f"{a.out}__hi", INT))
                fields.append(Field(f"{a.out}__lo", INT))
            else:
                fields.append(Field(a.out, self._agg_out_type(a, schema)))
        return Schema(fields, schema.dicts)

    def _final_project(self, batch: Batch) -> Batch:
        cols = {n: batch.col(n) for n in self.group_by}
        for a in self.user_aggs:
            if a.func == "avg":
                s_name, c_name = self._avg_parts[a.out]
                s, c = batch.col(s_name), batch.col(c_name)
                sv = s.values.astype(jnp.float32)
                ty = self.child.schema.field(a.col).type
                if ty.kind is Kind.DECIMAL:
                    sv = sv / jnp.float32(10 ** ty.scale)
                cnt = jnp.maximum(c.values, 1).astype(jnp.float32)
                cols[a.out] = Column(sv / cnt, s.validity)
            elif a.func == "sum" and a.wide:
                cols[f"{a.out}__hi"] = batch.col(f"{a.out}__hi")
                cols[f"{a.out}__lo"] = batch.col(f"{a.out}__lo")
            else:
                cols[a.out] = batch.col(a.out)
        return Batch(cols, batch.sel, batch.length)

    def _grow_traceable(self, acc_cap: int) -> Callable:
        return lambda b: _grow_to(b, acc_cap)

    def _fold_traceable(self, acc_cap: int) -> Callable:
        group_by, merge_aggs = tuple(self.group_by), self._merge_aggs
        seed = self.seed
        return lambda acc, part: _fold_step(acc, part, acc_cap, group_by,
                                            merge_aggs, seed=seed)

    def _grow(self, in_cap: int, acc_cap: int) -> Callable:
        key = (in_cap, acc_cap)
        if key not in self._grow_jit:
            # the partial is consumed into the fresh accumulator and never
            # read again — donate it (callers must read part.length BEFORE
            # this call; the donated buffers are deleted)
            self._grow_jit[key] = jax.jit(self._grow_traceable(acc_cap),
                                          donate_argnums=(0,))
        return self._grow_jit[key]

    def _fold(self, acc_cap: int, part_cap: int) -> Callable:
        key = (acc_cap, part_cap)
        if key not in self._fold_jit:
            # both the old accumulator and the partial die at this step;
            # donating them keeps the fold at one live accumulator instead
            # of doubling HBM on every batch
            self._fold_jit[key] = jax.jit(self._fold_traceable(acc_cap),
                                          donate_argnums=(0, 1))
        return self._fold_jit[key]

    def _stacked_scan(self) -> Optional[ScanOp]:
        """The source ScanOp when this op's input chain is MapOp* ->
        ScanOp and the scan's image is already device-resident (pinned,
        shared through the ScanImageCache, or chunk-cached so stacking is
        a device-side stack, not a re-transfer). None otherwise — the
        per-chunk loop is then no worse than stacking would be."""
        from cockroach_tpu.exec.scan_cache import scan_image_cache

        node = self.child
        while isinstance(node, MapOp):
            node = node.child
        if not isinstance(node, ScanOp):
            return None
        if (node._stacked is not None or node._cache is not None
                or (node.cache_key is not None
                    and scan_image_cache().contains(node.cache_key))):
            return node
        return None

    def _try_stacked_fold(self) -> Optional[Tuple[list, bool]]:
        """Whole-stream aggregation as ONE device dispatch: lax.scan the
        per-chunk partial+merge over the stacked scan image (the same
        machinery fused._Tracer._fold uses inside whole-query programs).
        Returns ([result batches], restart?) or None when the input isn't
        a resident stacked scan or the accumulator would blow workmem (the
        grace path needs the chunk stream)."""
        from cockroach_tpu.exec import spill as _spill

        sc = self._stacked_scan()
        if sc is None:
            return None
        st = sc.stacked_image()
        if st is None:
            return None  # empty scan: the loop path has the semantics
        bufs, ms = st
        if self._dense_sizes is not None:
            prog = self._stacked_jit.get(("dense", bufs.shape))
            if prog is None:
                dpartial, dfold = self._dense_partial, self._dense_fold
                dfinal = self._dense_final

                def dense_prog(bufs, ms):
                    carry = dpartial((bufs[0], ms[0]))
                    if bufs.shape[0] > 1:
                        def body(carry, x):
                            return dfold(carry, x), None
                        carry, _ = jax.lax.scan(body, carry,
                                                (bufs[1:], ms[1:]))
                    return dfinal(carry[0]), carry[1]

                # AOT-compile OUTSIDE the fold bucket: agg.fold tracks
                # the recurring per-query cost; the once-per-shape XLA
                # compile amortizes like fused.compile does
                with stats.timed("agg.stacked_compile"):
                    prog = jax.jit(dense_prog).lower(bufs, ms).compile()
                self._stacked_jit[("dense", bufs.shape)] = prog
            with stats.timed("agg.fold"):
                out, outside = prog(bufs, ms)
            stats.add("agg.fold_stacked")
            # the key space is statically complete (no overflow); only a
            # ranged key can ask for a restart, ONE readback, and only then
            return [out], bool(self.key_domains) and bool(outside)

        acc_cap = _pow2_at_least(sc.capacity * self.expansion)
        row_bytes = _spill.estimate_row_bytes(self._internal_schema)
        if self.group_by and acc_cap * row_bytes > self.workmem:
            return None
        prog = self._stacked_jit.get(("hash", acc_cap, bufs.shape))
        if prog is None:
            partial, finalize = self._partial, self._final_project
            group_by, merge_aggs = tuple(self.group_by), self._merge_aggs
            seed = self.seed

            def hash_prog(bufs, ms):
                part0, coll0 = partial((bufs[0], ms[0]))
                ovf = (part0.length > jnp.int32(acc_cap)) | coll0
                acc = _grow_to(part0, acc_cap)
                if bufs.shape[0] > 1:
                    def body(carry, x):
                        a, fl = carry
                        part, coll = partial(x)
                        a2, o = _fold_step(a, part, acc_cap, group_by,
                                           merge_aggs, seed=seed)
                        return (a2, fl | o | coll), None
                    (acc, ovf), _ = jax.lax.scan(body, (acc, ovf),
                                                 (bufs[1:], ms[1:]))
                return finalize(acc), ovf

            with stats.timed("agg.stacked_compile"):
                prog = jax.jit(hash_prog).lower(bufs, ms).compile()
            self._stacked_jit[("hash", acc_cap, bufs.shape)] = prog
        with stats.timed("agg.fold"):
            out, ovf = prog(bufs, ms)
        stats.add("agg.fold_stacked")
        # ONE end-of-stream readback for the deferred flag — same posture
        # as the per-chunk fold's final overflow check
        return [out], bool(self.group_by) and bool(ovf)

    def batches(self) -> Iterator[Batch]:
        from cockroach_tpu.exec import spill as _spill

        folded = self._try_stacked_fold()
        if folded is not None:
            out, restart = folded
            yield from out
            if restart:
                raise FlowRestart(self)
            return

        if self._dense_sizes is not None:
            carry = None
            for item in self._stream():
                with stats.timed("agg.fold"):
                    carry = (self._dense_partial(item) if carry is None
                             else self._dense_fold(carry, item))
            if carry is not None:
                yield self._dense_final(carry[0])
            # dense key space is statically complete: no overflow possible;
            # a ranged key's stale range is the one deferred flag (ONE
            # end-of-stream readback, as the hash fold's below)
            if carry is not None and self.key_domains and bool(carry[1]):
                raise FlowRestart(self)  # widen() drops the ranges
            return

        acc: Optional[Batch] = None
        overflow = None
        acc_cap = 0
        row_bytes = _spill.estimate_row_bytes(self._internal_schema)
        it = self._stream()
        for item in it:
            with stats.timed("agg.fold"):
                part, coll = self._partial(item)
                if acc is None:
                    acc_cap = _pow2_at_least(part.capacity * self.expansion)
                    if self.group_by and acc_cap * row_bytes > self.workmem:
                        # accumulator would blow the budget: switch to the
                        # out-of-core path before allocating it
                        yield from self._grace_batches(part, it)
                        return
                    # overflow reads the partial BEFORE _grow donates
                    # (and deletes) its buffers
                    overflow = (part.length > jnp.int32(acc_cap)) | coll
                    acc = self._grow(part.capacity, acc_cap)(part)
                else:
                    acc, ovf = self._fold(acc_cap, part.capacity)(acc, part)
                    overflow = overflow | ovf | coll
        if acc is None:
            if self.group_by:
                return  # zero groups
            empty = numpy_to_batch(
                {f.name: np.zeros(0, dtype=np.int64)
                 for f in self.child.schema},
                self.child.schema, capacity=1)
            empty = empty.with_sel(jnp.zeros(1, dtype=jnp.bool_))
            yield self._finalize(jax.jit(
                lambda b: hash_aggregate(b, self.group_by, self.internal)
            )(empty))
            return
        yield self._finalize(acc)
        # deferred overflow check: ONE readback, after the sink has already
        # consumed (and synced) the final batch — effectively free
        if self.group_by and bool(overflow):
            raise FlowRestart(self)

    def _grace_batches(self, first_part: Batch, rest) -> Iterator[Batch]:
        """Out-of-core GROUP BY: spill per-batch PARTIALS (already
        key-compressed) into host partitions by group-key hash, then
        merge-aggregate each partition in HBM. Partitions share no keys,
        so the union of per-partition results is exact. The reference's
        external hash aggregator does the same with disk partitions
        (colexecdisk, via hashBasedPartitioner)."""
        from cockroach_tpu.exec import spill as _spill

        stats.add("agg.grace_spill")
        row_bytes = _spill.estimate_row_bytes(self._internal_schema)
        # per-partition fold capacity sized to the budget
        cap = 1 << 10
        while cap * 2 * row_bytes <= self.workmem and cap < (1 << 22):
            cap *= 2
        P = _spill.DEFAULT_NUM_PARTITIONS * self.expansion
        gp = _spill.GracePartitioner(self.group_by, num_partitions=P)
        try:
            gp.consume(first_part)
            for item in rest:
                gp.consume(self._partial(item)[0])
            for p in range(P):
                if gp.partitions[p].n_rows == 0:
                    continue
                # per-partition retry (mirrors the grace JOIN's,
                # _grace_batches below): a partition whose live groups
                # exceed its fold capacity re-runs ALONE with a doubled
                # capacity — spilled blocks are replayable, so the rest of
                # the flow never restarts
                local_cap = cap
                for attempt in range(4):
                    src = _spill.BlockSource(
                        gp.partitions[p], self._internal_schema, cap)
                    acc = None
                    overflow = None
                    for b in src.batches():
                        part, coll = self._merge_partial(b)
                        if acc is None:
                            overflow = (part.length
                                        > jnp.int32(local_cap)) | coll
                            acc = self._grow(part.capacity, local_cap)(part)
                        else:
                            acc, ovf = self._fold(
                                local_cap, part.capacity)(acc, part)
                            overflow = overflow | ovf | coll
                    if acc is None:
                        break
                    if bool(overflow):
                        # bounded growth (<= 8x the budgeted fold cap);
                        # past that, restart the flow with more
                        # partitions (the budget-respecting remedy)
                        if attempt == 3:
                            raise FlowRestart(self)
                        local_cap *= 2
                        stats.add("agg.grace_partition_retry")
                        continue
                    yield self._finalize(acc)
                    break
        finally:
            gp.close()


class OrderedAggOp(HashAggOp):
    """Streaming GROUP BY over input whose equal keys arrive in contiguous
    runs (reference orderedAggregator): the per-chunk partial skips the
    sort entirely (ops/agg.ordered_aggregate). Runs that straddle chunk
    boundaries re-merge in the shared fold, so correctness never depends
    on run containment — the sort is purely elided work. The planner picks
    this over HashAggOp when the child's ordering covers the group keys
    (sort-avoiding plans, the reference's ordered-agg rule)."""

    def _make_kernels(self):
        super()._make_kernels()
        f = self._chunk_fn
        gb, internal = tuple(self.group_by), tuple(self.internal)
        from cockroach_tpu.ops.agg import ordered_aggregate

        self._partial = jax.jit(
            lambda item: (ordered_aggregate(f(item), gb, internal),
                          jnp.bool_(False)))


# -------------------------------------------------------------------- join

class JoinOp(Operator):
    """Streaming hash join: materialize the build side (right child) on
    device, stream the probe side (ref: hashjoiner.go build/probe phases).
    Overflow retries double out_capacity (the in-HBM analog of the disk
    spiller swap); right/full-outer emit unmatched build rows at EOS.

    Out-of-core: if the build side exceeds `workmem` while materializing,
    the join swaps MID-BUILD to Grace hash partitioning — everything
    buffered so far plus the rest of both streams is routed into host-RAM
    partitions by join-key hash, and each partition joins in HBM
    (recursing with a fresh hash level if still too big). This is the
    reference's diskSpiller + hashBasedPartitioner pair
    (disk_spiller.go:208, hash_based_partitioner.go:115)."""

    def __init__(self, probe: Operator, build: Operator,
                 probe_on: Sequence[str], build_on: Sequence[str],
                 how: str = "inner", expansion: int = 1,
                 workmem: Optional[int] = None, grace_level: int = 0,
                 build_mode: str = "unique"):
        self.probe, self.build = probe, build
        self.probe_on, self.build_on = list(probe_on), list(build_on)
        self.how = how
        self.expansion = expansion
        # "unique": sort-join fast path (ops/sortjoin.py) assuming unique
        # build keys — covers every FK->PK join; a duplicate key raises
        # the deferred fallback flag and widen() drops to "expand" (the
        # general ragged-expansion path), mirroring the reference's
        # optimistic in-memory op + disk-spiller swap.
        self.build_mode = build_mode
        from cockroach_tpu.util.settings import WORKMEM
        self.workmem = (Settings().get(WORKMEM) if workmem is None else workmem)
        self.grace_level = grace_level
        if how in ("semi", "anti"):
            self.schema = probe.schema
        else:
            overlap = set(probe.schema.names()) & set(build.schema.names())
            if overlap:
                raise ValueError(f"join column collision: {overlap}")
            dicts = dict(build.schema.dicts)
            dicts.update(probe.schema.dicts)
            self.schema = Schema(
                list(probe.schema.fields) + list(build.schema.fields), dicts)

    def _try_stacked_build(self) -> Optional[Batch]:
        """Build-side materialization as ONE device dispatch when the
        build chain is MapOp* -> ScanOp over an already device-resident
        stacked image: flat-unpack the whole stack, run the map chain,
        compact, and repack to exactly the pow2 capacity the per-chunk
        path would have produced. None when not resident, the build could
        exceed workmem (the chunked path must stream into grace spill),
        or the chain has other operator shapes."""
        from cockroach_tpu.exec import spill as _spill
        from cockroach_tpu.exec.scan_cache import scan_image_cache

        maps: List[MapOp] = []
        node = self.build
        while isinstance(node, MapOp):
            maps.append(node)
            node = node.child
        if not isinstance(node, ScanOp):
            return None
        sc = node
        if not (sc._stacked is not None or sc._cache is not None
                or (sc.cache_key is not None
                    and scan_image_cache().contains(sc.cache_key))):
            return None
        st = sc.stacked_image()
        if st is None:
            return None
        bufs, ms = st
        n_real = sc._stacked_chunks or bufs.shape[0]
        row_bytes = _spill.estimate_row_bytes(self.build.schema)
        budget_rows = max(1, self.workmem // max(row_bytes, 1))
        cap_sum = n_real * sc.capacity
        if (self.grace_level < _spill.MAX_GRACE_LEVELS
                and cap_sum > budget_rows):
            return None
        out_cap = _pow2_at_least(max(cap_sum, 1))
        if not hasattr(self, "_stacked_build_jit"):
            self._stacked_build_jit = {}
        key = (bufs.shape[0], out_cap)
        prog = self._stacked_build_jit.get(key)
        if prog is None:
            from cockroach_tpu.coldata.arrow import make_flat_unpack

            unpack = make_flat_unpack(sc.schema, sc.capacity)
            runs = tuple(m._run for m in reversed(maps))

            def build_prog(bufs, ms):
                b = unpack(bufs, ms)
                for r in runs:
                    b = r(b)
                merged = b.compact()
                idx = jnp.arange(out_cap, dtype=jnp.int32) % merged.capacity
                sel = jnp.arange(out_cap) < merged.length
                out = merged.gather(idx, sel=sel, length=merged.length)
                return Batch(mask_padding(out.columns, sel), sel,
                             out.length)

            # AOT-compile OUTSIDE the build bucket: join.build tracks
            # the recurring per-query cost; the once-per-shape XLA
            # compile amortizes like fused.compile does
            with stats.timed("join.stacked_compile"):
                prog = jax.jit(build_prog).lower(bufs, ms).compile()
            self._stacked_build_jit[key] = prog
        with stats.timed("join.build"):
            built = prog(bufs, ms)  # async dispatch, no host sync
        stats.add("join.build_stacked")
        return built

    def _materialize_build(self):
        """-> ("mem", Batch|None) or ("grace", GracePartitioner with the
        full build stream already spilled)."""
        from cockroach_tpu.exec import spill as _spill

        built = self._try_stacked_build()
        if built is not None:
            return "mem", built
        stream, f = self.build.pipeline()
        if not hasattr(self, "_compact_jit"):
            # NOT donate_argnums: the items can be a resident ScanOp's
            # per-chunk cache entries (the same device buffers on every
            # pass) — donation would delete the cache out from under the
            # next scan
            self._compact_jit = jax.jit(lambda item: f(item).compact())
            self._repack_jit = {}
        row_bytes = _spill.estimate_row_bytes(self.build.schema)
        budget_rows = max(1, self.workmem // max(row_bytes, 1))
        # at max recursion depth stop spilling and do the partition in
        # memory best-effort (the reference similarly bails out of
        # repartitioning on pathological skew rather than recursing
        # forever, hash_based_partitioner.go re-partition loop)
        spilling_allowed = self.grace_level < _spill.MAX_GRACE_LEVELS
        parts: List[Batch] = []
        cap_sum = 0
        # join.build times ONLY this operator's own work (compaction,
        # partitioning, repack): the child stream's production is pulled
        # OUTSIDE the timer — its scans/maps/aggs bill their own stages,
        # and folding them in here double-counted every upstream second
        #
        # double-buffered pull: chunk N+1's host->device transfer
        # dispatches while chunk N's compaction executes (helps the
        # un-prefetched BlockSource replay streams in particular)
        it = _read_ahead(stream())
        for item in it:
            with stats.timed("join.build"):
                part = self._compact_jit(item)
            # budget decision on CAPACITIES (static, sync-free upper
            # bound of live rows), mirroring the monitor-before-alloc
            # order of the reference's colmem.Allocator
            if spilling_allowed and cap_sum + part.capacity > budget_rows:
                gp = _spill.GracePartitioner(
                    self.build_on,
                    num_partitions=_spill.DEFAULT_NUM_PARTITIONS,
                    level=self.grace_level)
                try:
                    with stats.timed("join.build"):
                        for p in parts:
                            gp.consume(p)
                        gp.consume(part)
                    for rest in it:
                        with stats.timed("join.build"):
                            gp.consume(self._compact_jit(rest))
                except BaseException:
                    # a FlowRestart (or fault) from the build stream
                    # mid-partitioning: release the spill accounting
                    # before the flow unwinds, or the host-spill
                    # monitor leaks the partial partitions
                    gp.close()
                    raise
                return "grace", gp
            parts.append(part)
            cap_sum += part.capacity
        if not parts:
            return "mem", None
        # Sync-free repack: every compaction above was DISPATCHED without
        # blocking, and the merge capacity derives from the chunk
        # capacities (pow2 of their sum, a static sync-free bound on live
        # rows — bounded in turn by budget_rows, since grace spill fires
        # past it) instead of a ~90ms host readback of the true lengths.
        # The lengths stay on device and flow into the repack program's
        # own sel mask; heavily filtered build sides repack somewhat wider
        # than pow2(true length) — dead lanes, not correctness.
        cap = _pow2_at_least(max(cap_sum, 1))
        if len(parts) == 1 and parts[0].capacity == cap:
            # already one compacted batch of the target shape: the repack
            # would be an identity program (one saved dispatch per build)
            return "mem", parts[0]
        key = (tuple(p.capacity for p in parts), cap)
        if key not in self._repack_jit:
            def repack(ps, out_cap=cap):
                merged = concat_batches(ps).compact()
                idx = jnp.arange(out_cap, dtype=jnp.int32) % merged.capacity
                sel = jnp.arange(out_cap) < merged.length
                out = merged.gather(idx, sel=sel, length=merged.length)
                return Batch(mask_padding(out.columns, sel), sel, out.length)
            # the compacted parts are consumed here and never read again
            # (fresh _compact_jit outputs, not cache entries): donate them
            # so build-side HBM peaks at one copy during the repack
            self._repack_jit[key] = jax.jit(repack, donate_argnums=(0,))
        return "mem", self._repack_jit[key](parts)

    def _grace_batches(self, build_gp) -> Iterator[Batch]:
        """Partition the probe stream the same way, then join partition
        pairs in HBM. Correct for every join type because rows can only
        match within their shared hash partition."""
        from cockroach_tpu.exec import spill as _spill

        # the try must start BEFORE the probe partitioning loop: a
        # FlowRestart (or fault) from the probe stream there would
        # otherwise leak both partitioners' host-spill accounting
        probe_gp = _spill.GracePartitioner(
            self.probe_on, num_partitions=build_gp.P, level=self.grace_level)
        try:
            pstream, pf = self.probe.pipeline()
            pcompact = jax.jit(lambda item: pf(item).compact())
            for item in pstream():
                probe_gp.consume(pcompact(item))

            # replay partitions in batches that individually fit the
            # budget so each recursion level makes progress toward an
            # in-memory join
            row_bytes = _spill.estimate_row_bytes(self.build.schema)
            budget_rows = max(1, self.workmem // max(row_bytes, 1))
            parent_cap = getattr(self.probe, "capacity", None) or 1 << 16
            capacity = 256
            while capacity * 2 <= budget_rows and capacity < parent_cap:
                capacity *= 2
            for p in range(build_gp.P):
                probe_src = _spill.BlockSource(
                    probe_gp.partitions[p], self.probe.schema, capacity)
                build_src = _spill.BlockSource(
                    build_gp.partitions[p], self.build.schema, capacity)
                sub = JoinOp(probe_src, build_src, self.probe_on,
                             self.build_on, how=self.how,
                             expansion=self.expansion, workmem=self.workmem,
                             grace_level=self.grace_level + 1,
                             build_mode=self.build_mode)
                # per-partition overflow retry: buffer the partition's
                # output so a FlowRestart can re-run JUST this partition
                for attempt in range(9):
                    try:
                        out = list(sub.batches())
                        break
                    except FlowRestart:
                        if attempt == 8:
                            raise
                        sub.widen()
                yield from out
        finally:
            probe_gp.close()
            build_gp.close()

    def widen(self):
        """FlowRestart remedy — descend the mode ladder: payload-carry
        unique ("unique", flags on duplicate build keys, on a key
        outside the narrow packing's [0, 2^30) and, where the join
        resorts to probe order, on a bit-packed payload over 62 bits;
        the compacting form under a Shrink carries a row index and has
        no such width) -> row-matrix unique ("unique-mat", flags on
        duplicate build keys) -> general expansion -> doubled output
        expansion. Checks the EFFECTIVE mode: a join statically
        downgraded (wide build side) was already running expand, so its
        first restart must widen, not burn a rerun on a no-op mode
        flip."""
        from cockroach_tpu.ops.join import effective_build_mode

        eff = effective_build_mode(self.build_mode,
                                   self.build.schema.names(),
                                   self.build_on)
        if eff == "unique":
            self.build_mode = "unique-mat"
        elif eff == "unique-mat":
            self.build_mode = "expand"
        else:
            self.build_mode = "expand"
            self.expansion *= 2

    @functools.lru_cache(maxsize=64)
    def _join_fn(self, out_capacity: int, per_batch_how: str):
        """Jitted probe program: fused probe-side pipeline + probe of the
        PREPARED build (the build-side hash sort runs once per
        materialization, not once per probe batch)."""
        from cockroach_tpu.ops.join import hash_join_prepared

        probe_on, build_on = tuple(self.probe_on), tuple(self.build_on)
        _, f = self.probe.pipeline()
        track = self.how in ("right", "outer")
        return jax.jit(lambda item, bt: hash_join_prepared(
            f(item), bt, probe_on, build_on,
            how=per_batch_how, out_capacity=out_capacity,
            track_build=track))

    def batches(self) -> Iterator[Batch]:
        kind, build = self._materialize_build()
        if kind == "grace":
            stats.add("join.grace_spill")
            yield from self._grace_batches(build)
            return
        per_batch_how = {"outer": "left", "right": "inner"}.get(self.how, self.how)
        if build is None:
            # empty build side
            if self.how in ("inner", "semi", "right"):
                return
            for b in self.probe.batches():
                if self.how == "anti":
                    yield b
                else:  # left/outer: all probe rows unmatched
                    empty_build_cols = {
                        f.name: Column(
                            jnp.zeros((b.capacity,), f.type.dtype),
                            jnp.zeros((b.capacity,), jnp.bool_))
                        for f in self.build.schema}
                    cols = dict(b.columns)
                    cols.update(empty_build_cols)
                    yield Batch(cols, b.sel, b.length)
            return

        from cockroach_tpu.ops.join import (
            effective_build_mode, prepare_build,
        )

        mode = effective_build_mode(self.build_mode,
                                    self.build.schema.names(),
                                    self.build_on)
        if mode == "unique":
            # streaming dispatches dominate here (~107ms each): a carry
            # payload-width restart would rerun the WHOLE flow, and the
            # carry's gather savings are noise next to the dispatch
            # floor — go straight to the row-matrix unique path (the
            # fused single-program path keeps the carry fast path)
            mode = "unique-mat"
        if getattr(self, "_prepare_mode", None) != mode:
            build_on = tuple(self.build_on)
            self._prepare_jit = jax.jit(
                lambda b: prepare_build(b, build_on, mode=mode))
            self._prepare_mode = mode
        bt = self._prepare_jit(build)
        matched_r = jnp.zeros((build.capacity,), dtype=jnp.bool_)
        track_r = self.how in ("right", "outer")
        stream, _f = self.probe.pipeline()
        probe_cap = getattr(self.probe, "capacity", None)
        overflow = jnp.bool_(False)  # deferred: ONE check at end-of-stream
        for item in stream():
            if probe_cap is None:
                probe_cap = jax.eval_shape(_f, item).sel.shape[0]
            out_cap = probe_cap * self.expansion
            res = self._join_fn(out_cap, per_batch_how)(item, bt)
            overflow = overflow | res.overflow
            if track_r:
                matched_r = matched_r | res.matched_build
            yield res.batch
        if bool(overflow):
            raise FlowRestart(self)
        if track_r:
            from cockroach_tpu.ops.join import _null_columns
            unmatched = build.sel & ~matched_r
            rows = jnp.arange(build.capacity, dtype=jnp.int32)
            cols = {
                f.name: Column(
                    jnp.zeros((build.capacity,), f.type.dtype),
                    jnp.zeros((build.capacity,), jnp.bool_))
                for f in self.probe.schema}
            cols.update(_null_columns(build, rows, unmatched))
            yield Batch(cols, unmatched, jnp.sum(unmatched).astype(jnp.int32))


# ------------------------------------------------------------ sort / top-k

class SortOp(Operator):
    """ORDER BY. In-HBM when the input fits `workmem` (concat + one
    bitonic sort); otherwise an EXTERNAL sort: each batch is compacted and
    device-SORTED and spilled to host RAM together with its sorted integer
    key columns (ops/sort.py lex_keys), then the host merges the sorted
    runs with a binary tree of linear two-way merges over a packed 64-bit
    key and emits ordered capacity-sized batches — the reference's
    external-sort shape (colexecdisk/external_sort.go: sorted partitions
    on disk, merge phase on replay), with the device doing the O(n log n)
    sorting and the host only the O(n log R) merge."""

    def __init__(self, child: Operator, keys: Sequence[SortKey],
                 workmem: Optional[int] = None):
        self.child = child
        self.keys = list(keys)
        self.schema = child.schema
        from cockroach_tpu.util.settings import WORKMEM
        self.workmem = (Settings().get(WORKMEM) if workmem is None else workmem)
        self._sort_jit = {}

    def batches(self) -> Iterator[Batch]:
        from cockroach_tpu.exec import spill as _spill

        if not hasattr(self, "_compact_jit"):
            stream, f = self.child.pipeline()
            self._stream = stream
            self._compact_jit = jax.jit(lambda item: f(item).compact())
        row_bytes = _spill.estimate_row_bytes(self.schema)
        budget_rows = max(1, self.workmem // max(row_bytes, 1))
        parts: List[Batch] = []
        cap_sum = 0
        it = self._stream()
        for item in it:
            part = self._compact_jit(item)
            if cap_sum + part.capacity > budget_rows:
                yield from self._external_batches(parts, item, it)
                return
            parts.append(part)
            cap_sum += part.capacity
        if not parts:
            return
        key = tuple(p.capacity for p in parts)
        if key not in self._sort_jit:
            keys, schema = tuple(self.keys), self.child.schema
            def run(ps):
                merged = ps[0] if len(ps) == 1 else concat_batches(ps)
                return sort_batch(merged, keys, schema)
            self._sort_jit[key] = jax.jit(run)
        yield self._sort_jit[key](parts)

    def _external_batches(self, buffered: List[Batch], item, it
                          ) -> Iterator[Batch]:
        """TRUE external sort (colexecdisk/external_sort.go shape): the
        DEVICE sorts every run before it spills (batch + its already-
        sorted integer sort keys, ops/sort.py lex_keys), and the host only
        MERGES sorted runs — a binary merging tree of linear two-way
        numpy merges over a packed 64-bit key (per-key ranges measured at
        merge time; falls back to one np.lexsort only when the combined
        key ranges cannot pack into 64 bits). Device does the O(n log n)
        work; host does O(n log R)."""
        from cockroach_tpu.exec import spill as _spill
        from cockroach_tpu.ops.sort import lex_keys, sort_batch

        stats.add("sort.external_spill")
        keys_t, schema = tuple(self.keys), self.child.schema
        sorted_of = {}

        def sort_and_keys(cap):
            if cap not in sorted_of:
                def f(b: Batch):
                    s = sort_batch(b, keys_t, schema)  # device-sorted run
                    return s, lex_keys(s, keys_t, schema)
                sorted_of[cap] = jax.jit(f)
            return sorted_of[cap]

        acct = _spill.host_spill_monitor().make_account()
        runs: List[Tuple[_spill.SpilledBlock, List[np.ndarray]]] = []
        try:
            def spill_one(b: Batch):
                with stats.timed("sort.device_run"):
                    s, lk = sort_and_keys(b.capacity)(b)
                block = _spill.batch_to_block(s)
                n = block.n_rows
                host_keys = [np.asarray(k)[:n] for k in lk]
                acct.grow(block.nbytes + sum(k.nbytes for k in host_keys))
                stats.add("spill.write", rows=n, bytes=block.nbytes)
                runs.append((block, host_keys))

            for b in buffered:
                spill_one(b)
            spill_one(self._compact_jit(item))
            for rest in it:
                spill_one(self._compact_jit(rest))
            if not runs:
                return

            with stats.timed("sort.host_merge"):
                order = _merge_sorted_runs(runs)
            total = order.shape[0]
            cols = {}
            validity = {}
            for f in self.schema:
                cols[f.name] = np.concatenate(
                    [r[0].values[f.name] for r in runs])[order]
                vs = [r[0].validity[f.name] for r in runs]
                if any(v is not None for v in vs):
                    validity[f.name] = np.concatenate([
                        v if v is not None else np.ones(r[0].n_rows, bool)
                        for r, v in zip(runs, vs)])[order]
                else:
                    validity[f.name] = None
            cap = getattr(self.child, "capacity", None) or 1 << 16
            for a in range(0, total, cap):
                n = min(cap, total - a)
                out_cols = {}
                for f in self.schema:
                    vals = np.zeros(cap, dtype=cols[f.name].dtype)
                    vals[:n] = cols[f.name][a:a + n]
                    v = validity[f.name]
                    jv = None
                    if v is not None:
                        pv = np.zeros(cap, dtype=bool)
                        pv[:n] = v[a:a + n]
                        jv = jnp.asarray(pv)
                    out_cols[f.name] = Column(jnp.asarray(vals), jv)
                sel = jnp.arange(cap) < n
                stats.add("spill.replay", rows=n)
                yield Batch(out_cols, sel, jnp.int32(n))
        finally:
            acct.close()


def _merge_sorted_runs(runs) -> np.ndarray:
    """Global order over the concatenation of sorted runs.

    runs: [(SpilledBlock, [lexsort key arrays, least-significant first])]
    where each run's rows are ALREADY in key order. Packs all key columns
    into one uint64 per row using their measured ranges, then merges runs
    pairwise with linear searchsorted interleaves (a binary merging tree).
    When the combined key bits exceed 64 (full-range multi-key sorts),
    degrades to one np.lexsort over the concatenation — still correct,
    no longer merge-shaped."""
    n_keys = len(runs[0][1])
    all_keys = [np.concatenate([r[1][i] for r in runs])
                for i in range(n_keys)]
    lengths = [r[0].n_rows for r in runs]
    if sum(lengths) == 0:
        return np.zeros(0, dtype=np.int64)
    starts = np.cumsum([0] + lengths[:-1])

    bits, los = [], []
    for k in all_keys:  # least-significant first
        lo, hi = int(k.min()), int(k.max())
        span = hi - lo + 1
        bits.append(max(1, int(span - 1).bit_length()))
        los.append(lo)
    if sum(bits) > 64:
        return np.lexsort(all_keys)

    packed = np.zeros(sum(lengths), dtype=np.uint64)
    shift = 0
    for k, b, lo in zip(all_keys, bits, los):
        packed |= (k.astype(np.int64) - lo).astype(np.uint64) << np.uint64(
            shift)
        shift += b

    merged = [(packed[s:s + n], np.arange(s, s + n, dtype=np.int64))
              for s, n in zip(starts, lengths)]
    while len(merged) > 1:
        nxt = []
        for i in range(0, len(merged) - 1, 2):
            (ka, ia), (kb, ib) = merged[i], merged[i + 1]
            # stable two-way merge: a's elements before equal b elements
            pos_a = np.arange(len(ka)) + np.searchsorted(kb, ka, "left")
            pos_b = np.arange(len(kb)) + np.searchsorted(ka, kb, "right")
            k = np.empty(len(ka) + len(kb), dtype=np.uint64)
            idx = np.empty(len(ka) + len(kb), dtype=np.int64)
            k[pos_a], k[pos_b] = ka, kb
            idx[pos_a], idx[pos_b] = ia, ib
            nxt.append((k, idx))
        if len(merged) % 2:
            nxt.append(merged[-1])
        merged = nxt
    return merged[0][1]


class WindowOp(Operator):
    """Window functions over (PARTITION BY, ORDER BY) — the
    colexecwindow analog (SURVEY.md §2.2). Sorts the input by the
    partition+order keys (reusing SortOp, including its external-sort
    spill path), then computes every window column with the segmented
    scans in ops/window.py in ONE jitted program over the materialized
    sorted result. Output is sorted by (partition, order) — a stronger
    guarantee than SQL requires."""

    def __init__(self, child: Operator, partition_by: Sequence[str],
                 order_by: Sequence[SortKey], specs):
        from cockroach_tpu.coldata.batch import Field
        from cockroach_tpu.ops.window import WindowSpec  # noqa: F401

        self.child = child
        self.partition_by = list(partition_by)
        self.order_by = list(order_by)
        self.specs = list(specs)
        sort_keys = ([SortKey(c) for c in self.partition_by]
                     + self.order_by)
        self._sorted = (SortOp(child, sort_keys) if sort_keys else child)
        self.schema = child.schema.extend(
            [Field(s.out, s.out_type(child.schema))
             for s in self.specs])

        from cockroach_tpu.ops.window import compute_windows

        pb = tuple(self.partition_by)
        ob = tuple(self.order_by)
        specs_t = tuple(self.specs)
        schema = child.schema

        def run(ps):
            whole = (ps[0] if len(ps) == 1
                     else concat_batches(ps)).compact()
            new_cols = compute_windows(whole, pb, ob, specs_t, schema)
            cols = dict(whole.columns)
            cols.update(mask_padding(new_cols, whole.sel))
            return Batch(cols, whole.sel, whole.length)

        # one jitted fn: jax caches traces per input pytree shape itself
        self._run = jax.jit(run)

    def batches(self) -> Iterator[Batch]:
        parts = [b for b in self._sorted.batches()]
        if not parts:
            return
        yield self._run(parts)


class TopKOp(Operator):
    """ORDER BY + LIMIT k: per-batch top-k, then top-k of the winners
    (ref: sorttopk.go topKSorter)."""

    def __init__(self, child: Operator, keys: Sequence[SortKey], k: int):
        self.child = child
        self.keys = list(keys)
        self.k = k
        self.schema = child.schema

    def batches(self) -> Iterator[Batch]:
        if not hasattr(self, "_topk_jit"):
            stream, f = self.child.pipeline()
            self._stream = stream
            keys, schema, k = tuple(self.keys), self.child.schema, self.k
            self._topk_jit = jax.jit(
                lambda item: top_k_batch(f(item), keys, k, schema))
            self._final_jit = jax.jit(
                lambda ws: top_k_batch(concat_batches(ws), keys, k, schema))
        winners = [self._topk_jit(item) for item in self._stream()]
        if not winners:
            return
        if len(winners) == 1:
            yield winners[0]
            return
        yield self._final_jit(winners)


class LimitOp(Operator):
    def __init__(self, child: Operator, limit: int, offset: int = 0):
        self.child = child
        self.limit = limit
        self.offset = offset
        self.schema = child.schema

        @jax.jit
        def _take(batch: Batch, carry):
            # global rank among selected rows across the whole stream
            rank = jnp.cumsum(batch.sel.astype(jnp.int32)) - 1 + carry
            keep = batch.sel & (rank >= offset) & (rank < offset + limit)
            new_carry = carry + jnp.sum(batch.sel).astype(jnp.int32)
            return batch.with_sel(keep), new_carry

        self._take = _take

    def batches(self) -> Iterator[Batch]:
        # Device-side carry of selected-rows-seen; termination is checked
        # one batch LATE (against the previous carry) so the readback syncs
        # a value whose computation already finished while the current
        # batch was being dispatched — no pipeline stall per batch
        # (VERDICT r1 weak #7).
        bound = self.offset + self.limit
        carry = jnp.int32(0)
        prev_carry = None
        for b in self.child.batches():
            out, carry = self._take(b, carry)
            yield out
            if prev_carry is not None and int(prev_carry) >= bound:
                return
            prev_carry = carry


def shrink_batch(m: Batch, C: int):
    """-> (`m`'s first C selected rows as a C-lane batch, did it hold
    more). Gathers ONLY the C winning rows (the selected lanes first, in
    lane order, then a (C, W) row gather) — a full compact() would
    row-gather every capacity lane just to slice C of them (~150 ms per
    6M-lane shrink on v5e). What ShrinkOp lowers to, and how the mesh
    cuts a shard's rows to the result window before it gathers them."""
    length = jnp.minimum(m.length, C).astype(jnp.int32)
    sel = jnp.arange(C) < length
    out = m.gather(first_selected(m.sel, C), sel=sel, length=length)
    return (Batch(mask_padding(out.columns, sel), sel, length),
            m.length > C)


class ShrinkOp(Operator):
    """Adaptive capacity compaction: compact the child's (materialized)
    output into a SMALL static capacity, flagging overflow for the
    FlowRestart driver (capacity grows 16x per restart).

    Why: static shapes make a 60-row HAVING result ride its input's
    multi-million-lane capacity into every downstream operator (Q18's
    filtered aggregate feeds a join build side); compacting it to a
    4K-lane batch collapses those operators' sort/gather costs. The
    optimistic-capacity + deferred-flag posture matches the engine's
    join-expansion and hash-collision retries (disk_spiller.go:208's
    optimistic/general pairing).

    What it costs: ONE single-operand u32 sort of the child's full
    capacity (coldata/batch.first_selected: the miss bit above the lane
    index, what the compacting joins run) plus a (C, W) row gather of
    the child's columns. Over a unique-build inner or semi join that
    nothing else reads, the fused
    runner lowers the pair as ONE step (fused._Tracer._mat_join,
    sortjoin.probe_unique_compact): the join compacts its matches in key
    order, its resort to probe order never runs, and the row gather packs
    the probe's columns only. The capacity, widen() and the overflow
    flag are this operator's either way. The lane order of a shrunk
    batch is no contract of THIS operator; where it lowered with an
    inner join as one step, the join's is (key order:
    sortjoin.probe_unique_compact), and the tracer that saw the step
    taken may use it (fused._Tracer._ordered_input)."""

    START_CAPACITY = 1 << 12
    GROWTH = 16

    def __init__(self, child: Operator, capacity: int = START_CAPACITY):
        self.child = child
        self.capacity = capacity
        self.schema = child.schema

    def widen(self):
        self.capacity *= self.GROWTH

    def shrink_traceable(self, m: Batch):
        """-> (shrunk batch, overflow flag): shrink_batch at this
        operator's capacity."""
        return shrink_batch(m, self.capacity)

    def batches(self) -> Iterator[Batch]:
        parts = [b for b in self.child.batches()]
        if not parts:
            return
        merged = concat_batches(parts) if len(parts) > 1 else parts[0]
        out, flag = self.shrink_traceable(merged)
        if bool(flag):
            raise FlowRestart(self)
        yield out


class DistinctOp(Operator):
    """Cross-batch DISTINCT == GROUP BY keys with no aggregates."""

    def __init__(self, child: Operator, keys: Optional[Sequence[str]] = None):
        keys = list(keys) if keys else child.schema.names()
        self._agg = HashAggOp(child, keys, [])
        self.schema = self._agg.schema

    def batches(self) -> Iterator[Batch]:
        return self._agg.batches()


class VectorANNOp(Operator):
    """Clustered-ANN vector top-K over a bare scan (the approximate arm
    of the VectorTopK plan node). Builds an IVF-flat VectorIndex
    (ops/vector.py) from the scan's rows and probes it with ONE jitted
    dispatch per query; the index — centroids + grouped member tensors,
    device-resident — is cached in the scan-image cache keyed off the
    scan's content identity (cache_key + a "vecindex" suffix), so MVCC
    write-version rotation invalidates it exactly like scan images.

    Live maintenance: a version rotation caused by APPEND-ONLY writes
    (the previous image is a bit-identical prefix of the new one) does
    NOT rebuild — the new rows join their nearest centroids via
    VectorIndex.append, and only past DRIFT_REBUILD appended fraction
    does the index re-cluster from scratch."""

    # live-maintenance tier: the last built (vectors, index) pair per
    # table/column, keyed by the WRITE-STABLE cache-key prefix ("mvcc",
    # engine, tid) so an INSERT finds it after the versioned key rotates
    _live: Dict[tuple, tuple] = {}
    DRIFT_REBUILD = 0.25  # appended fraction past which we re-cluster

    def __init__(self, child: Operator, column: str,
                 query: Sequence[float], metric: str, k: int,
                 nprobe: int = 4):
        self.child = child
        self.column = column
        self.query = tuple(float(x) for x in query)
        self.metric = metric
        self.k = int(k)
        self.nprobe = int(nprobe)
        self.schema = child.schema
        self.n_clusters: Optional[int] = None  # stamped after build

    def _scan(self) -> Optional["ScanOp"]:
        base = self.child
        while not isinstance(base, ScanOp):
            nxt = getattr(base, "child", None)
            if nxt is None:
                return None
            base = nxt
        return base

    def _materialize(self):
        """-> (VectorIndex, {name: np values}, {name: np validity|None},
        n_rows), cached across statements under the scan's content key."""
        from cockroach_tpu.exec.scan_cache import scan_image_cache
        from cockroach_tpu.ops.vector import VectorIndex

        scan = self._scan()
        key = None
        if scan is not None and scan.cache_key is not None:
            key = tuple(scan.cache_key) + ("vecindex", self.column,
                                           self.metric)
            hit = scan_image_cache().get(key)
            if hit is not None:
                stats.add("vector.index_hit")
                return hit
        names = self.schema.names()
        vals: Dict[str, list] = {n: [] for n in names}
        valids: Dict[str, list] = {n: [] for n in names}
        n_rows = 0
        for b in self.child.batches():
            sel = np.asarray(b.sel)
            vc = b.columns[self.column]
            if vc.validity is not None:
                # NULL embeddings are unsearchable: keep them out of the
                # index (and of the gathered result rows)
                sel = sel & np.asarray(vc.validity)
            n_rows += int(sel.sum())
            for name in names:
                c = b.columns[name]
                vals[name].append(np.asarray(c.values)[sel])
                valids[name].append(
                    None if c.validity is None
                    else np.asarray(c.validity)[sel])
        host_vals = {}
        host_valid = {}
        for name in names:
            parts = vals[name]
            host_vals[name] = (np.concatenate(parts) if parts
                               else np.empty((0,)))
            vparts = valids[name]
            host_valid[name] = (
                None if not vparts or any(v is None for v in vparts)
                else np.concatenate(vparts))
        index = None
        live_key = (None if key is None
                    else tuple(key[:3]) + ("veclive", self.column,
                                           self.metric))
        if n_rows:
            new_vecs = host_vals[self.column]
            index = self._maintain(live_key, new_vecs, n_rows)
            if index is None:
                with _tracing.child_span("vector.index_build",
                                         rows=n_rows):
                    index = VectorIndex.build(new_vecs,
                                              metric=self.metric)
                stats.add("vector.index_build", rows=n_rows, events=1)
            if live_key is not None:
                if len(self._live) > 64:  # bound host-side vec copies
                    self._live.clear()
                self._live[live_key] = (new_vecs, index)
        value = (index, host_vals, host_valid, n_rows)
        if key is not None and index is not None:
            nbytes = index.nbytes() + sum(
                int(a.nbytes) for a in host_vals.values())
            scan_image_cache().put(key, value, nbytes)
        return value

    def _maintain(self, live_key, new_vecs: np.ndarray, n_rows: int):
        """INSERT path: when the previous build's vector image is a
        bit-identical prefix of the current one (append-only writes, no
        update/delete reordering the scan) and centroid drift stays
        under DRIFT_REBUILD, extend the existing index incrementally —
        members join their nearest centroid — instead of re-clustering
        the world. Returns the maintained index, or None to rebuild."""
        if live_key is None:
            return None
        hit = self._live.get(live_key)
        if hit is None:
            return None
        old_vecs, index = hit
        old_n = len(old_vecs)
        fresh = n_rows - old_n
        if (fresh < 0 or index.n != old_n
                or not np.array_equal(new_vecs[:old_n], old_vecs)):
            return None  # update/delete (or another feed) reshaped rows
        if fresh == 0:
            return index
        if (index.appended + fresh) / float(n_rows) > self.DRIFT_REBUILD:
            stats.add("vector.index_drift_rebuild", rows=n_rows,
                      events=1)
            return None
        with _tracing.child_span("vector.index_append", rows=fresh):
            index.append(new_vecs[old_n:], start_id=old_n)
        stats.add("vector.index_append", rows=fresh, events=1)
        return index

    def batches(self) -> Iterator[Batch]:
        index, host_vals, host_valid, n_rows = self._materialize()
        if index is None or n_rows == 0:
            return
        self.n_clusters = index.n_clusters
        with _tracing.child_span("vector.ann.search", k=self.k,
                                 nprobe=self.nprobe,
                                 clusters=index.n_clusters):
            ids, dists = index.search(np.asarray(self.query, np.float32),
                                      k=self.k, nprobe=self.nprobe)
        stats.add("vector.ann_search", rows=self.k, events=1)
        ok = ids >= 0
        safe = np.where(ok, ids, 0)
        cols = {}
        for name in self.schema.names():
            v = host_vals[name][safe]
            validity = host_valid[name]
            cols[name] = Column(
                jnp.asarray(v),
                None if validity is None else jnp.asarray(validity[safe]))
        sel = jnp.asarray(ok)
        out = Batch(mask_padding(cols, sel), sel,
                    jnp.int32(int(ok.sum())))
        yield out


def child_operators(op: Operator) -> List[Operator]:
    """Direct children of an operator node — the single tree-walk
    definition shared by the fused compiler, bench tooling, and (later)
    the planner. New operator types with non-`child` edges register here."""
    if isinstance(op, JoinOp):
        return [op.probe, op.build]
    if isinstance(op, DistinctOp):
        return [op._agg]
    if isinstance(op, WindowOp):
        return [op._sorted]  # execution flows through the internal sort
    child = getattr(op, "child", None)
    return [child] if child is not None else []


def walk_operators(op: Operator):
    """Pre-order traversal (deduplicated by identity)."""
    seen = set()

    def rec(node):
        if id(node) in seen:
            return
        seen.add(id(node))
        yield node
        for c in child_operators(node):
            yield from rec(c)

    yield from rec(op)


# ------------------------------------------------------------------- sinks

def run_flow(op: Operator, reset: Callable[[], None],
             consume: Callable[[Batch], None], max_restarts: int = 8,
             fuse: bool = True) -> None:
    """Drive the flow to completion with the FlowRestart retry loop: on a
    deferred capacity-check failure the failed operator's expansion doubles
    and the whole flow reruns from the scan (`reset` discards the sink's
    partial output first). Queries are not checkpointed, exactly like the
    reference's optimistic retry posture (disk_spiller.go:208 swaps
    operators the same lazy way). All sinks go through this one driver so
    they share identical retry semantics; batches stream to `consume` so
    device memory never holds the whole result.

    When the tree fits the fusion grammar (exec/fused.py) the whole query
    runs as ONE device program; the streaming tree remains both the
    fallback and the out-of-core path."""
    # admission control: one slot per running flow when enabled
    from cockroach_tpu.util.admission import flow_queue

    queue = flow_queue()
    if queue is not None:
        with queue.admit():
            return _run_flow_inner(op, reset, consume, max_restarts, fuse)
    return _run_flow_inner(op, reset, consume, max_restarts, fuse)


SPILL_TIER_WORKMEM = Settings.register(
    "sql.resilience.spill_workmem_bytes",
    32 << 20,
    "per-operator workmem while running the forced-spill ladder tier "
    "(small enough that every blocking operator takes its Grace/external "
    "out-of-core path)",
)


def _clamp_workmem_for_spill(op: Operator) -> Callable[[], None]:
    """Clamp every operator's workmem to the spill-tier budget so blocking
    operators take their Grace/external out-of-core paths (the ladder's
    analog of disk_spiller.go:208 swapping in the disk-backed operator).
    Returns a restore callable — the clamp must not outlive the tier."""
    limit = int(Settings().get(SPILL_TIER_WORKMEM))
    saved: List[Tuple[Operator, int]] = []
    for sub in walk_operators(op):
        wm = getattr(sub, "workmem", None)
        if wm is not None and wm > limit:
            saved.append((sub, wm))
            sub.workmem = limit

    def restore():
        for sub, wm in saved:
            sub.workmem = wm

    return restore


def _run_tier(driver, reset: Callable[[], None],
              consume: Callable[[Batch], None], max_restarts: int,
              reg) -> None:
    """Drive one ladder tier to completion: the FlowRestart widening loop
    plus in-place retry of transient (RETRYABLE) faults under the
    sql.resilience backoff policy. RESOURCE and TERMINAL errors propagate
    to the ladder, which decides whether a cheaper tier exists."""
    from cockroach_tpu.util import log as _log

    opts = _retry.options_from_settings()
    backoffs = opts.backoffs()
    restarts = 0
    while True:
        _cancel.checkpoint()
        reset()
        try:
            for b in driver.batches():
                _cancel.checkpoint()
                consume(b)
            return
        except FlowRestart as fr:
            if restarts == max_restarts:
                raise
            restarts += 1
            reg.counter("sql_flow_restarts_total",
                        "deferred-flag flow restarts").inc()
            _tracing.record("flow.restart", n=restarts,
                            op=type(fr.op).__name__)
            _log.get_logger().info(
                _log.Channel.SQL_EXEC,
                "flow restart {}: widening {}", restarts - 1,
                type(fr.op).__name__)
            widen = getattr(fr.op, "widen", None)
            if widen is not None:
                widen()
            else:
                fr.op.expansion *= 2
        except Exception as e:  # noqa: BLE001 — classifier decides
            if _retry.classify(e) != _retry.RETRYABLE:
                raise
            pause = next(backoffs, None)
            if pause is None:
                raise  # retry budget exhausted: the ladder steps down
            _cancel.checkpoint()
            _retry.record_retry("flow", pause)
            opts.sleep(pause)


def _run_flow_inner(op: Operator, reset: Callable[[], None],
                    consume: Callable[[Batch], None],
                    max_restarts: int = 8, fuse: bool = True) -> None:
    from cockroach_tpu.util import circuit as _circuit
    from cockroach_tpu.util import log as _log
    from cockroach_tpu.util.metric import default_registry

    reg = default_registry()
    reg.counter("sql_queries_total", "queries run by the flow driver").inc()
    # registered with the first flow, so that a reader of the registry
    # can tell "no restart yet" (0) from "this program has no such counter"
    reg.counter("sql_flow_restarts_total", "deferred-flag flow restarts")
    q_hist = reg.histogram("sql_query_seconds",
                           "end-to-end query wall time")
    t_start = time.perf_counter()

    # The degradation ladder (fused -> streaming -> forced-spill; the
    # distributed rung lives in parallel/dist_flow.py above this). Each
    # rung has a process-wide circuit breaker: a tier that keeps failing
    # trips open and later queries skip straight past it instead of
    # re-paying its compile + failure.
    tiers: List[Tuple[str, object]] = []
    if fuse:
        from cockroach_tpu.exec import fused as _fused

        # the runner is cached on the root: its compiled-program cache is
        # what makes repeat runs of one flow free of re-lowering
        runner = getattr(op, "_fused_runner", None)
        if runner is None:
            runner = _fused.try_compile(op)
            op._fused_runner = runner
        if runner is not None:
            tiers.append(("fused", runner))
    tiers.append(("streaming", op))
    tiers.append(("spill", op))

    for i, (tier, driver) in enumerate(tiers):
        # a cancelled statement must not start (or degrade into) another
        # tier — a deadline that fired mid-fused must not pay for spill
        _cancel.checkpoint()
        last_tier = i == len(tiers) - 1
        br = _circuit.breaker("flow." + tier)
        if not br.allow():
            if not last_tier:
                stats.add(f"resilience.skip.{tier}")
                _tracing.record("breaker.skip", tier=tier)
                continue
            # every rung is tripped but the query still has to run: the
            # final rung executes as a forced probe
            stats.add(f"resilience.forced.{tier}")
            _tracing.record("breaker.forced", tier=tier)
        restore = (_clamp_workmem_for_spill(op) if tier == "spill"
                   else None)
        try:
            try:
                with _tracing.child_span("flow." + tier):
                    _run_tier(driver, reset, consume, max_restarts, reg)
            finally:
                if restore is not None:
                    restore()
        except FlowRestart:
            # widening exhausted: every tier runs the same plan shapes and
            # would overflow identically — surface the original restart
            # (the session maps it to pgcode 40001: the CLIENT may retry)
            raise
        except Exception as e:  # noqa: BLE001 — classifier decides
            if _retry.classify(e) == _retry.TERMINAL:
                raise
            br.failure()
            if last_tier:
                raise
            reg.counter("sql_resilience_degradations_total",
                        "execution-ladder tier step-downs").inc()
            stats.add(f"resilience.degrade.{tier}")
            _tracing.record("degrade", from_tier=tier,
                            to_tier=tiers[i + 1][0],
                            error=type(e).__name__)
            _log.get_logger().info(
                _log.Channel.SQL_EXEC,
                "degrading {} -> {}: {}: {}", tier, tiers[i + 1][0],
                type(e).__name__, str(e)[:200])
            continue
        br.success()
        _tracing.tag_root(tier=tier)
        q_hist.observe(time.perf_counter() - t_start)
        return


_SHRINK_MIN_CAP = 1 << 14


@functools.lru_cache(maxsize=None)
def _shrink_for_readback(in_cap: int, out_cap: int):
    """Jitted compact+slice so result readback transfers pow2(length) rows
    instead of the full batch capacity: a capacity-1M final batch is
    megabytes to read back for 4 live rows; this makes readback
    proportional to the ANSWER size."""
    def f(b: Batch) -> Batch:
        c = b.compact()
        idx = jnp.arange(out_cap, dtype=jnp.int32) % in_cap
        sel = jnp.arange(out_cap) < c.length
        out = c.gather(idx, sel=sel, length=c.length)
        return Batch(mask_padding(out.columns, sel), sel, out.length)
    return jax.jit(f)


def _maybe_shrink(b: Batch) -> Batch:
    if isinstance(b.sel, np.ndarray):
        return b  # host-side result (fused packed readback): nothing to do
    cap = b.capacity
    if cap < _SHRINK_MIN_CAP:
        return b
    n = int(b.length)  # one readback; the shrink it buys is far larger
    out_cap = _pow2_at_least(max(n, 1))
    if out_cap * 2 > cap:
        return b
    return _shrink_for_readback(cap, out_cap)(b)


def assemble_wide_sums(result: Dict[str, np.ndarray]) -> None:
    """Recombine wide-sum halves in place: for every `<x>__hi`/`<x>__lo`
    pair, add `<x>` as an object array of exact Python ints
    (hi * 2**32 + lo — values beyond int64 by design; see ops/agg.py
    wide sums). The halves stay available for callers that forward the
    device representation (e.g. the arrow layer)."""
    for name in [n for n in result
                 if n.endswith("__hi") and not n.endswith("__valid")]:
        base = name[:-4]
        lo = result.get(base + "__lo")
        if lo is None:
            continue
        hi = result[name]
        result[base] = np.array(
            [(int(h) << 32) + int(l) for h, l in zip(hi, lo)], dtype=object)
        result[base + "__valid"] = result[name + "__valid"]


def flow_backend(op: Operator, setting: str = "auto") -> str:
    """TPU-aware engine routing (sql/cost.py): a fixed dispatch+readback
    floor makes small flows faster on the LOCAL CPU backend —
    the same XLA programs, a different placement. est_rows comes from
    planner stats stamped onto ScanOps (plan.build)."""
    from cockroach_tpu.sql.cost import route_backend

    est = 0
    known = False
    for sub in walk_operators(op):
        if isinstance(sub, ScanOp):
            rows = getattr(sub, "est_rows", None)
            if rows is not None:
                est += rows
                known = True
    return route_backend(est if known else None, setting)


def _backend_scope(backend: str):
    import contextlib

    import jax as _jax

    if backend == "cpu" and _jax.devices()[0].platform != "cpu":
        stats.add("route.cpu")
        return _jax.default_device(_jax.devices("cpu")[0])
    stats.add(f"route.{backend}")
    return contextlib.nullcontext()


def collect(op: Operator, max_restarts: int = 8,
            fuse: bool = True,
            backend: str = "auto") -> Dict[str, np.ndarray]:
    """Run the flow, return host numpy columns (compacted). Wide-sum
    column pairs are recombined into exact Python-int columns."""
    outs: Dict[str, List[np.ndarray]] = {}
    valids: Dict[str, List[np.ndarray]] = {}

    def reset():
        for f in op.schema:
            outs[f.name] = []
            valids[f.name] = []

    def consume(b: Batch):
        b = _maybe_shrink(b)
        sel = np.asarray(b.sel)
        for f in op.schema:
            c = b.col(f.name)
            outs[f.name].append(np.asarray(c.values)[sel])
            v = (np.ones(int(sel.sum()), bool) if c.validity is None
                 else np.asarray(c.validity)[sel])
            valids[f.name].append(v)

    with _backend_scope(flow_backend(op, backend)):
        run_flow(op, reset, consume, max_restarts, fuse=fuse)
    result = {}
    for f in op.schema:
        result[f.name] = (np.concatenate(outs[f.name])
                          if outs[f.name] else np.zeros(0))
        result[f.name + "__valid"] = (np.concatenate(valids[f.name])
                                      if valids[f.name] else np.zeros(0, bool))
    assemble_wide_sums(result)
    return result


def collect_arrow(op: Operator, max_restarts: int = 8, fuse: bool = True):
    """Run the flow, return a pyarrow Table (decoded strings/decimals).
    Shares the FlowRestart retry driver with collect()."""
    import pyarrow as pa

    from cockroach_tpu.coldata.arrow import batch_to_arrow

    rbs: List = []
    run_flow(op, rbs.clear,
             lambda b: rbs.append(batch_to_arrow(_maybe_shrink(b), op.schema)),
             max_restarts, fuse=fuse)
    if not rbs:
        return pa.table({})
    return pa.Table.from_batches(rbs)
