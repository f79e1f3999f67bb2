"""Whole-flow fusion: compile an operator tree into ONE XLA program.

Every program execution is a host round trip (dispatch, then a readback
before the host can decide anything), and a warm query's kernels are short:
execution COUNT, not kernel time, bounds it. What one round trip costs on
the attached chip is measured by chip_smoke.py (`dispatch_roundtrip_s`).
The streaming runtime (operators.py) dispatches one program per batch per
stage; this module instead compiles the entire query — scan unpack, filters,
projections, join build + probe, aggregation fold, final sort/limit — into
a single jitted program that folds over the scan's resident chunks with
`lax.scan`. That is also simply the XLA-native design: one big traced
dataflow that the compiler can fuse end to end.

Reference seam: colflow's `vectorizedFlowCreator.setupFlow`
(pkg/sql/colflow/vectorized_flow.go:1137) compiles a FlowSpec into one
runnable flow object; here "one flow" literally becomes one XLA executable.
The streaming runtime remains the fallback for everything fusion does not
cover (out-of-core spill paths, right/full-outer streaming joins, empty
scans) — exactly how the reference pairs in-memory operators with disk
spillers (colexecdisk/disk_spiller.go:208): optimistic fast path, general
slow path.

Supported tree grammar (anything else -> streaming fallback):

    Root  := Post* (Fold | Mat)
    Post  := SortOp | LimitOp | MapOp | TopKOp          (over a single batch)
    Fold  := HashAggOp|TopKOp over a Chain              (lax.scan over chunks)
    Chain := MapOp* (JoinOp[inner/left/semi/anti](probe=Chain, build=Mat))*
             ScanOp
    Mat   := any supported subtree materialized as ONE traced Batch

A HashAggOp over a Chain is a Fold only when it must be: while the Chain's
materialized output (lanes x row bytes) fits the operator's workmem, the
aggregate, grouped or scalar, takes the Chain as a Mat (a multi-chunk scan
unpacks flat off the stacked image) and aggregates ONCE; over the budget
it folds chunk by chunk, the out-of-core answer (_Tracer._agg_stream).
_Tracer._agg_partial is the one place an aggregate's lowering is decided,
and counts it, one event a traced HashAggOp: fused.agg_ordered where a
compacting join left the input grouped and ops/agg.run_ends_aggregate
aggregates it in place (_Tracer._ordered_input); fused.agg_int_key where
ops/agg.int_key_aggregate takes a single integer key through ONE sort;
fused.agg_dense where every key has a small static domain, a
dictionary's, a bool's or a range the planner read off the statistics,
and ops/agg.dense_aggregate aggregates by slot, D lanes out, folded or
not; else the hash aggregate, fused.agg_folded over the budget and
fused.agg_materialized within it. A TopKOp over a Chain always folds.

Overflow posture matches streaming: joins and generic agg folds carry
deferred overflow flags through the scan; the runner checks them once after
the sink consumed the result and raises FlowRestart to the shared retry
driver (run_flow), which doubles the failing operator's expansion and
reruns — recompiling the program at the wider capacity.
"""

from __future__ import annotations

import functools
import operator
import threading
from collections import OrderedDict
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cockroach_tpu.coldata.batch import Batch, concat_batches
from cockroach_tpu.exec import stats
from cockroach_tpu.util import cancel as _cancel
from cockroach_tpu.util import retry as _retry
from cockroach_tpu.util import tracing as _tracing
from cockroach_tpu.util.fault import InjectedFault, maybe_fail
from cockroach_tpu.exec.operators import (
    DistinctOp, FlowRestart, HashAggOp, JoinOp, LimitOp, MapOp, Operator,
    ScanOp, ShrinkOp, SortOp, TopKOp, WindowOp, _pow2_at_least,
    child_operators, walk_operators,
)
from cockroach_tpu.ops.agg import (
    INT_KEY_AGG_FUNCS, _identity as _agg_identity, dense_aggregate,
    dense_merge, hash_aggregate, int_key_aggregate, run_ends_aggregate,
)
from cockroach_tpu.ops import expr as _expr
from cockroach_tpu.ops.sort import _sortable_int
from cockroach_tpu.ops.vector import distance_fn
from cockroach_tpu.ops.join import (
    effective_build_mode, hash_join_prepared, prepare_build, scan64_lanes,
)
from cockroach_tpu.ops.sortjoin import compacts, probe_unique_compact
from cockroach_tpu.ops.sort import sort_batch, top_k_batch


class Unsupported(Exception):
    """This tree (or this run's data volume) is outside the fusion grammar;
    the caller falls back to the streaming runtime."""


def _is_oom(e: Exception) -> bool:
    msg = str(e)
    return ("RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg
            or "out of memory" in msg)


class HBMExceeded(Unsupported):
    """This run's data or whole-query program does not fit device memory:
    the one reason the fused tier hands a query it COULD express to the
    streaming runtime. Counted under stats.STREAM_HBM where it is
    caught."""


# Raised scoped-VMEM budget for every whole-query TPU compile: the big
# int64 prefix scans (emulated as u32 pairs) need stack space beyond the
# 16 MiB default; without it XLA refuses at compile time ("Ran out of
# memory in memory space vmem"). Accepted by the installed compiler
# (scripts/rehearse_tpu_compile.py, tests/test_tpu_compile.py).
TPU_COMPILE_OPTIONS = {"xla_tpu_scoped_vmem_limit_kib": 65536}


def _refusal(stage: str, e: Exception) -> Exception:
    """What a failed lower/compile means. A transient backend fault
    (UNAVAILABLE, DEADLINE_EXCEEDED, a failed transfer) is returned as it
    came, so with_retry("fused.compile") retries it in place. Too large
    for HBM is a fact about this run's volume (-> HBMExceeded: stream
    instead, counted); anything else the compiler says — scoped vmem,
    Mosaic, a lowering rule — is a defect of the program and reaches the
    client in the compiler's own words (-> CompileRefused, TERMINAL)."""
    if _retry.classify(e) == _retry.RETRYABLE:
        return e
    msg = str(e)
    low = msg.lower()
    if _is_oom(e) and "vmem" not in low and "mosaic" not in low:
        return HBMExceeded(f"program too large for HBM ({stage}): "
                           f"{msg[:300]}")
    return _retry.CompileRefused(
        f"{stage} refused: {type(e).__name__}: {msg}")


# What tracer code raises on purpose while a program is lowered: the
# fusion grammar's verdicts, a restart, an injected fault, cancellation,
# a budget trip (BudgetExceededError is a MemoryError).
_PROGRAM_VERDICTS = (Unsupported, FlowRestart, InjectedFault,
                     _cancel.QueryCancelled, MemoryError)


def lower_program(fn, args):
    """jax.jit(fn).lower(*args) for a whole-query program. The program's
    own verdicts (_PROGRAM_VERDICTS) pass through; anything else is JAX,
    XLA or Mosaic refusing to lower it (see _refusal)."""
    try:
        return jax.jit(fn).lower(*args)
    except _PROGRAM_VERDICTS:
        raise
    except Exception as e:  # noqa: BLE001 — sorted in _refusal
        refused = _refusal("lowering", e)
        if refused is e:
            raise
        raise refused from e


CHUNKABLE_JOINS = ("inner", "left", "semi", "anti")


def _validate(op: Operator) -> None:
    """Cheap host-side pre-pass: reject trees fusion can never run, before
    any device work. Volume-dependent checks (workmem, chunk counts) happen
    at program-build time instead."""
    if isinstance(op, ScanOp):
        return
    if isinstance(op, MapOp):
        _validate(op.child)
        return
    if isinstance(op, JoinOp):
        if op.grace_level != 0:
            raise Unsupported("grace-partitioned join")
        _validate(op.probe)
        _validate(op.build)
        return
    if isinstance(op, HashAggOp):
        _validate(op.child)
        return
    if isinstance(op, DistinctOp):
        _validate(op._agg)
        return
    if isinstance(op, (SortOp, TopKOp, LimitOp, ShrinkOp)):
        _validate(op.child)
        return
    if isinstance(op, WindowOp):
        # lowers through its internal sort + the segmented-scan window
        # kernels (ops/window.py), all traceable
        _validate(op._sorted)
        return
    raise Unsupported(f"operator {type(op).__name__}")


class _IntKeyAggGuard:
    """FlowRestart target of the int-key aggregate's FALLBACK flag (the
    key's range or the packed inputs outgrew the sort's operands): the
    first trip retries with a 64-bit key operand, the second turns the
    kernel off for this operator, and the rerun hashes. Both attributes
    ride the fused config key, so each state compiles its own program."""

    def __init__(self, op: HashAggOp):
        self.op = op

    def widen(self):
        if not getattr(self.op, "_ia_wide", False):
            self.op._ia_wide = True
        else:
            self.op._ia_ok = False


class _Stream:
    """A per-chunk traceable chain from one scan: fn(item) ->
    (Batch, flags); `cap` is the static output capacity per chunk and
    `flag_ops` names the operator behind each deferred overflow flag."""

    def __init__(self, scan: ScanOp, fn: Callable, cap: int,
                 flag_ops: List[Operator]):
        self.scan = scan
        self.fn = fn
        self.cap = cap
        self.flag_ops = flag_ops


def _build_mode(op: JoinOp) -> str:
    return effective_build_mode(op.build_mode, op.build.schema.names(),
                                op.build_on)


def _or_flags(*flags):
    """OR of the flags that are there; a None adds no operation."""
    return functools.reduce(operator.or_,
                            [f for f in flags if f is not None])


def _join_flags(guard, b_ovf, p_ovf, own) -> tuple:
    """A join's deferred flags: its own, with its exchanges' ORed in; or,
    where the exchanges have a restart target of their own (`guard`,
    _Tracer._route_guard), theirs first and then its own."""
    if guard is None:
        return (_or_flags(b_ovf, p_ovf, own),)
    return (_or_flags(b_ovf, p_ovf), own)


def _flag_targets(guard, op: JoinOp) -> list:
    """The operators behind _join_flags' flags, in its order."""
    return [op] if guard is None else [guard, op]


def _keyed_on_join(join: JoinOp, group_by: Sequence[str]) -> Optional[str]:
    """Does a GROUP BY over `join` (one key a side, unique build) group by
    the join key and, beside it, only by columns of the BUILD side, which
    a unique build makes functions of the key? -> the key's name among
    `group_by` (the probe's, else the build's), or None. Then the rows of
    one key are one group, and rows of equal group keys have equal keys."""
    pon, bon = join.probe_on[0], join.build_on[0]
    key_out = pon if pon in group_by else (bon if bon in group_by else None)
    if key_out is None:
        return None
    build_names = join.build.schema.names()
    if not all(g in build_names for g in group_by if g != key_out):
        return None
    return key_out


def takes_params(root: Operator) -> bool:
    """Does a filter or projection under `root` read a bound parameter
    (ops/expr.Param)? Such a tree's program takes the statement's bound
    values as arguments, after the scan images: FusedRunner's program on
    one chip, and DistFusedRunner's shard_map program on a mesh, where
    they are replicated (`P()`) beside the images."""
    return any(_expr.has_params([payload if kind == "filter"
                                 else [e for _n, e in payload]
                                 for kind, payload in op.steps])
               for op in walk_operators(root) if isinstance(op, MapOp))


def bound_program_args(takes: bool) -> tuple:
    """The bound values of the statement this thread is running
    (ops/expr.bound_args), as the trailing arguments of a whole-query
    program whose tree `takes` them (takes_params; this module's runner
    and parallel/dist_flow.py's); () for a tree without parameters."""
    if not takes:
        return ()
    bound = _expr.current_args()
    if bound is None:
        raise _expr.ParamOutsideProgram(
            "a parameterised plan was run with no values bound")
    return tuple(bound)


SCOPE_PREFIX = "crdb."
RESULT_SCOPE = "result"     # crdb.result: _pack_result
# parts of an operator's scope that the distributed tracer tells apart
# (parallel/dist_flow.py): what it adds to a join, to an aggregate or top-K
EXCHANGE, MERGE = "exchange", "merge"


def scope(name: str):
    """The program's own name for what is lowered inside: a
    jax.named_scope, so every instruction traced under it carries
    `crdb.<name>` in its `op_name` and exec/device_profile.py can book
    the instruction's device time to it. Debug information only: the
    persistent compile cache's key does not see it."""
    return jax.named_scope(SCOPE_PREFIX + name)


def op_scope_name(n: int, op: Operator, part: str = "") -> str:
    """`op<N>.<Kind>[.<part>]`: N is the operator's pre-order position
    under walk_operators(root), the order EXPLAIN ANALYZE (DEVICE)
    prints."""
    return f"op{n}.{type(op).__name__}{'.' + part if part else ''}"


def _shared_ops(root: Operator) -> set:
    """ids of the operators under `root` that more than one parent reads
    (plan-level CSE, sql/plan.build)."""
    seen, shared = set(), set()
    for node in walk_operators(root):
        for c in child_operators(node):
            (shared if id(c) in seen else seen).add(id(c))
    return shared


class _Tracer:
    """Builds the traced program for one config; lives for one trace."""

    def __init__(self, stacked: Dict[int, Tuple[jnp.ndarray, jnp.ndarray]],
                 root: Operator):
        self.stacked = stacked  # id(scan) -> (bufs (N,B), ms (N,))
        # operators more than one parent reads (see _mat_memo)
        self._shared = _shared_ops(root)
        # id(op) -> its pre-order position: the N of its scope's name
        self._op_n = {id(op): n
                      for n, op in enumerate(walk_operators(root))}
        self._scope_top = None  # the innermost open scope's name
        self.flag_ops: List[Operator] = []
        self.flags: List[jnp.ndarray] = []
        # shared-subtree memo: a deduped operator (plan-level CSE,
        # sql/plan.build) materializes ONCE per trace — its flags are
        # appended once and XLA sees one copy of the subgraph
        self._mat_memo: Dict[int, Batch] = {}
        # lanes through the key sorts of this program's materialized
        # joins (probe + build capacity) and sort-based aggregates (the
        # input's capacity): what FusedRunner counts a dispatch as stage
        # fused.sort_lanes
        self.sort_lanes = 0
        # ... and those of them under the hashed u64 key (a join on more
        # than one column, or on a key that is no integer): stage
        # fused.hash_key_lanes
        self.hash_key_lanes = 0
        # lanes through the 64-bit scans its joins still run
        # (ops/join.scan64_lanes; none under a compacting join):
        # stage fused.join_scan64_lanes
        self.join_scan64_lanes = 0
        # probe + build lanes of its semi and anti joins that carry a
        # residual (JoinOp.residual_of, sql/plan.build): stage
        # fused.join_residual_lanes
        self.join_residual_lanes = 0
        # ids of the ShrinkOps that lowered with their join as ONE step in
        # THIS trace (_mat_join returned compacted=True): what leaves
        # their lanes in key order for _ordered_input
        self._compacted: set = set()

    @contextmanager
    def _scope(self, op: Operator, part: str = ""):
        """What is traced inside is `op`'s: instructions carry
        `crdb.op<N>.<Kind>[.<part>]`, innermost last (a child lowered
        inside its parent's scope is the child's). Opening the scope that
        is already the innermost opens nothing."""
        name = op_scope_name(self._op_n[id(op)], op, part)
        if name == self._scope_top:
            yield
            return
        prev, self._scope_top = self._scope_top, name
        try:
            with scope(name):
                yield
        finally:
            self._scope_top = prev

    # -- chunk streams -----------------------------------------------------
    #
    # A stream's stages run later, inside whatever folds them (a lax.scan
    # body under the aggregate's scope): each opens its own operator's
    # scope where it runs, so a fold's step is split by operator too.

    def _stream(self, op: Operator) -> Optional[_Stream]:
        if isinstance(op, ScanOp):
            unpack = op._unpack

            def fn(item):
                with self._scope(op):
                    return unpack(*item), ()

            return _Stream(op, fn, op.capacity, [])
        if isinstance(op, MapOp):
            s = self._stream(op.child)
            if s is None:
                return None
            run = op._run

            def fn(item, f=s.fn):
                b, fl = f(item)
                with self._scope(op):
                    return run(b), fl

            return _Stream(s.scan, fn, s.cap, s.flag_ops)
        if isinstance(op, JoinOp) and op.how in CHUNKABLE_JOINS:
            s = self._stream(op.probe)
            if s is None:
                return None
            build, b_ovf = self._join_build(op)
            mode = _build_mode(op)
            with self._scope(op):
                bt = prepare_build(build, tuple(op.build_on), mode=mode)
            n_chunks = int(self.stacked[id(s.scan)][0].shape[0])
            p_cap, route = self._join_probe(op, s.cap, n_chunks)
            guard = self._route_guard(op)
            out_cap = p_cap * op.expansion
            probe_on, build_on = tuple(op.probe_on), tuple(op.build_on)
            how = op.how

            def fn(item, f=s.fn):
                b, fl = f(item)
                with self._scope(op):
                    b, p_ovf = route(b)
                    res = hash_join_prepared(b, bt, probe_on, build_on,
                                             how=how, out_capacity=out_cap)
                    return res.batch, fl + _join_flags(guard, b_ovf, p_ovf,
                                                       res.overflow)

            if mode == "unique":
                # one output lane per probe row for every chunkable type
                cap = p_cap
            else:
                cap = {"inner": out_cap, "left": out_cap + p_cap,
                       "semi": p_cap, "anti": p_cap}[op.how]
            return _Stream(s.scan, fn, cap,
                           s.flag_ops + _flag_targets(guard, op))
        return None

    # -- how a join's sides reach it ----------------------------------------
    #
    # The ONE lowering of a join (the branch above: build once, probe a
    # chunk; _mat_join: both sides whole) takes its inputs through these
    # two hooks. Here a side is what its subtree materializes; the
    # distributed tracer (parallel/dist_flow.py) sends a co-partitioned
    # join's sides through the BY_HASH exchange first and hands back the
    # router's overflow flag, which the lowering ORs into the join's own,
    # or keeps apart where _route_guard names a restart target for it.
    # A None flag adds no operation to the program.

    def _join_build(self, op: JoinOp) -> Tuple[Batch, Optional[jnp.ndarray]]:
        """-> (the build side as the join sees it, its exchange's overflow
        flag or None)."""
        build = self._mat(op.build)
        if (build.capacity * self._row_bytes(op.build.schema)
                > op.workmem):
            raise Unsupported("join build exceeds workmem")
        return build, None

    def _join_probe(self, op: JoinOp, cap: int,
                    chunks: Optional[int] = None) -> Tuple[int, Callable]:
        """How probe batches of `cap` lanes reach the join: `chunks` of
        them, one at a time (the streamed form), or the whole side as one
        (None). -> (lanes of a batch as the join sees it, route), where
        route(batch) -> (batch, overflow flag or None)."""
        return cap, lambda batch: (batch, None)

    def _route_guard(self, op: JoinOp):
        """The FlowRestart target that answers the flags `op`'s hooks hand
        back, where they have one of their own; None: they are ORed into
        the join's flag and answered as the join's."""
        return None

    def _items(self, scan: ScanOp) -> List[Tuple]:
        bufs, ms = self.stacked[id(scan)]
        return [(bufs[i], ms[i]) for i in range(bufs.shape[0])]

    def _fold(self, s: _Stream, init_of: Callable, step: Callable) -> Tuple:
        """lax.scan `step(acc, batch) -> acc` over the stream's chunks,
        threading the chain's deferred overflow flags through the carry.
        Returns (final_acc, flags_tuple)."""
        bufs, ms = self.stacked[id(s.scan)]
        n = bufs.shape[0]
        b0, fl0 = s.fn((bufs[0], ms[0]))
        acc0 = init_of(b0)
        if n == 1:
            return acc0, fl0

        def body(carry, x):
            acc, fl = carry
            b, fl2 = s.fn(x)
            return (step(acc, b),
                    tuple(a | b_ for a, b_ in zip(fl, fl2))), None

        (acc, fl), _ = jax.lax.scan(body, (acc0, fl0), (bufs[1:], ms[1:]))
        return acc, fl

    # -- single-batch materialization --------------------------------------

    def _row_bytes(self, schema) -> int:
        from cockroach_tpu.exec.spill import estimate_row_bytes
        return estimate_row_bytes(schema)

    def _mat(self, op: Operator) -> Batch:
        hit = self._mat_memo.get(id(op))
        if hit is not None:
            return hit
        with self._scope(op):
            out = self._mat_inner(op)
        self._mat_memo[id(op)] = out
        return out

    def _mat_inner(self, op: Operator) -> Batch:
        if isinstance(op, ScanOp):
            bufs, ms = self.stacked[id(op)]
            if bufs.shape[0] == 1:
                return op._unpack(bufs[0], ms[0])
            # flat unpack: slice+bitcast+reshape per column straight off
            # the stacked image — no per-chunk unpack + N-way concat
            from cockroach_tpu.coldata.arrow import make_flat_unpack

            return make_flat_unpack(op.schema, op.capacity)(bufs, ms)
        if isinstance(op, MapOp):
            return op._run(self._mat(op.child))
        if isinstance(op, DistinctOp):
            return self._mat(op._agg)
        if isinstance(op, JoinOp):
            return self._mat_join(op)[0]
        if isinstance(op, HashAggOp):
            return self._mat_agg(op)
        if isinstance(op, ShrinkOp):
            if self._compactable(op.child):
                m, compacted = self._mat_join(op.child, op)
                if compacted:
                    self._compacted.add(id(op))
                    return m
            else:
                m = self._mat(op.child)
            out, flag = op.shrink_traceable(m)
            self.flag_ops.append(op)
            self.flags.append(flag)
            return out
        if isinstance(op, SortOp):
            m = self._mat(op.child)
            if m.capacity * self._row_bytes(op.schema) > op.workmem:
                raise Unsupported("sort exceeds workmem")
            return sort_batch(m, tuple(op.keys), op.child.schema)
        if isinstance(op, TopKOp):
            keys, k, schema = tuple(op.keys), op.k, op.child.schema
            s = self._stream(op.child)
            if s is not None:

                def init(b):
                    return top_k_batch(b, keys, k, schema)

                def step(acc, b):
                    return top_k_batch(
                        concat_batches([acc, top_k_batch(b, keys, k, schema)]),
                        keys, k, schema)

                acc, fl = self._fold(s, init, step)
                self.flag_ops.extend(s.flag_ops)
                self.flags.extend(fl)
                return acc
            return top_k_batch(self._mat(op.child), keys, k, schema)
        if isinstance(op, WindowOp):
            # materialize the (partition, order)-sorted input and compute
            # every window column with the segmented scans in
            # ops/window.py — the same jitted body WindowOp.batches runs,
            # inlined into the whole-query program here
            return op._run([self._mat(op._sorted)])
        if isinstance(op, LimitOp):
            m = self._mat(op.child)
            rank = jnp.cumsum(m.sel.astype(jnp.int32)) - 1
            keep = m.sel & (rank >= op.offset) & (rank < op.offset + op.limit)
            return m.with_sel(keep)
        raise Unsupported(f"operator {type(op).__name__}")

    def _compactable(self, op: Operator) -> bool:
        """May a ShrinkOp directly above `op` lower with it as one step
        (_mat_join)? An inner or semi join on the unique path that
        nothing else reads: the compacted batch has no probe lane layout
        left for another parent, and the other join types emit (or count)
        unmatched lanes."""
        return (isinstance(op, JoinOp) and op.how in ("inner", "semi")
                and op.grace_level == 0
                and id(op) not in self._shared
                and _build_mode(op) == "unique")

    def _mat_join(self, op: JoinOp,
                  shrink: Optional[ShrinkOp] = None) -> Tuple[Batch, bool]:
        """-> (batch, compacted). With `shrink` (the ShrinkOp above a
        _compactable join) and a build whose key takes the narrow
        packing (`compacts`: nothing is asked of its other columns), the
        pair is ONE step that compacts the matched probe lanes in key
        order (ops/sortjoin.probe_unique_compact): the join never
        restores the probe order that the Shrink would discard one
        operator later, and fetches the build's columns by row index at
        the Shrink's lanes. The join's fallback flag and the Shrink's
        overflow flag keep their operators and their order, so the
        restart ladder is the two-step path's. The step is the JOIN's
        (its scope opens here: the Shrink calls this, not _mat)."""
        with self._scope(op):
            probe = self._mat(op.probe)
            build, b_ovf = self._join_build(op)
            probe_on, build_on = tuple(op.probe_on), tuple(op.build_on)
            bt = prepare_build(build, build_on, mode=_build_mode(op))
            _, route = self._join_probe(op, probe.capacity)
            probe, p_ovf = route(probe)
            guard = self._route_guard(op)
            self.flag_ops.extend(_flag_targets(guard, op))
            self.sort_lanes += probe.capacity + build.capacity
            if getattr(op, "residual_of", None):
                self.join_residual_lanes += probe.capacity + build.capacity
            # the packing the key took (ops/sortjoin.prepare_unique): one
            # integer column rides the sorts as itself, anything else as
            # a 62-bit hash in a u64 operand, verified by a row gather
            if getattr(bt, "key_kind", "hash") == "int":
                stats.add("fused.join_key_int")
            else:
                stats.add("fused.join_key_hash")
                self.hash_key_lanes += probe.capacity + build.capacity
            if shrink is not None and compacts(bt, probe.capacity, op.how):
                res = probe_unique_compact(probe, bt, probe_on, op.how,
                                           shrink.capacity)
                stats.add("fused.join_compact")
                self.flag_ops.append(shrink)
                self.flags.extend(
                    _join_flags(guard, b_ovf, p_ovf, res.fallback)
                    + (res.overflow,))
                return res.batch, True
            res = hash_join_prepared(
                probe, bt, probe_on, build_on, how=op.how,
                out_capacity=probe.capacity * op.expansion)
            self.join_scan64_lanes += scan64_lanes(bt, probe.capacity,
                                                   op.how)
            self.flags.extend(_join_flags(guard, b_ovf, p_ovf, res.overflow))
            return res.batch, False

    def _try_int_agg(self, op: HashAggOp) -> Optional[Batch]:
        """Single-int-key GROUP BY via ops/agg.int_key_aggregate:
        the key and the packed aggregate inputs ride ONE sort — no
        hashing, no argsort(perm) pair, no random gathers (those cost
        Q18's first aggregation ~400ms at 6M rows on v5e). Used when the
        materialized input fits the operator budget; emits the
        uncompacted run-ends view for large group counts (a downstream
        filter/shrink compacts far cheaper than per-group gathers)."""
        if not self._int_keyed(op):
            return None
        child_schema = op.child.schema
        est_rows = 0
        for sub in walk_operators(op.child):
            if isinstance(sub, ScanOp):
                est_rows = max(est_rows,
                               self.stacked[id(sub)][0].shape[0]
                               * sub.capacity)
        if est_rows * self._row_bytes(child_schema) > op.workmem:
            return None
        return self._int_key_agg(op, self._mat(op.child), op.internal)

    def _int_keyed(self, op: HashAggOp) -> bool:
        """Is `op` a GROUP BY on ONE integer column whose aggregates
        ops/agg.int_key_aggregate computes (sums and counts of integers
        and bools), with the kernel not turned off for it
        (_IntKeyAggGuard)? Then so are its partials' merge: the merging
        functions of sums and counts are sums of int64 columns."""
        if not getattr(op, "_ia_ok", True) or len(op.group_by) != 1:
            return False
        if op._dense_sizes is not None:
            return False  # a small static domain: by slot, no sort at all
        child_schema = op.child.schema
        if not jnp.issubdtype(child_schema.field(op.group_by[0]).type.dtype,
                              jnp.integer):
            return False
        for a in op.internal:
            if a.func not in INT_KEY_AGG_FUNCS:
                return False
            if a.col is not None:
                dt = child_schema.field(a.col).type.dtype
                if not (dt == jnp.bool_
                        or jnp.issubdtype(dt, jnp.integer)):
                    return False
        return True

    def _int_key_agg(self, op: HashAggOp, m: Batch, aggs) -> Batch:
        """ops/agg.int_key_aggregate of `m` by `op`'s one key: `aggs` are
        op.internal over the operator's input, or op._merge_aggs over
        partials of it (the mesh's final stage)."""
        # group count <= live rows: small inputs compact to their full
        # bound (overflow impossible); large ones return the run-ends
        # view — a downstream filter/shrink/top-K compacts far cheaper
        # than per-group gathers would
        out_cap = (_pow2_at_least(m.capacity)
                   if m.capacity <= (1 << 18) else 0)
        res = int_key_aggregate(
            m, op.group_by[0], list(aggs), out_capacity=out_cap,
            key64=getattr(op, "_ia_wide", False))
        self.sort_lanes += m.capacity
        self.flag_ops.append(_IntKeyAggGuard(op))
        self.flags.append(res.fallback)
        return res.batch

    def _agg_stream(self, op: HashAggOp) -> Optional[_Stream]:
        """The chunk stream `op` folds over, or None: it aggregates ONCE
        over its materialized input. One aggregation beats a per-chunk
        fold whenever the materialized input fits the operator budget,
        grouped or not: every fold step unpacks its chunk's byte lanes on
        its own, where the flat unpack of the stacked image fuses into the
        filter and the reduction, and a grouped step re-sorts acc + chunk
        (N chunks cost ~2N sorted-agg passes vs ONE at N times the lanes).
        An input over the budget keeps the fold, the out-of-core answer."""
        s = self._stream(op.child)
        if s is not None:
            n_chunks = self.stacked[id(s.scan)][0].shape[0]
            mat_rows = s.cap * n_chunks
            if mat_rows * self._row_bytes(op.child.schema) <= op.workmem:
                s = None
        return s

    def _ordered_input(self, op: HashAggOp) -> Optional[bool]:
        """Does `op`'s input reach it ALREADY grouped, because a
        compacting join left it in key order
        (ops/sortjoin.probe_unique_compact: live rows first, ascending in
        the join key)? Decided from what this trace did and the plan
        shows, nothing else. All of:

        - under `op` stand MapOps only, then a ShrinkOp that lowered with
          its join as one step IN THIS TRACE (_compacted: after a restart
          down the join's ladder, or where _compactable or `compacts`
          refuses, it is not there, and the aggregate hashes as before);
        - the join is inner (a semi join compacts in probe-lane order),
          over a unique build, one key a side;
        - every GROUP BY column is, through the MapOps' projections, a
          bare column of the join's output (renames followed), and
          together they are keyed on the join (_keyed_on_join): the
          join key, and beside it only columns of the build side.

        -> None: not so. Else whether the live lanes are still the
        batch's first (`dense` of ops/agg.run_ends_aggregate): a filter
        on the way punches holes into the runs, which the aggregate then
        looks past with a few scans more; it groups exactly either way.
        Materializes op.child: whether the Shrink lowered with its join
        is known only after."""
        names, dense, node = list(op.group_by), True, op.child
        while isinstance(node, MapOp):
            for kind, payload in reversed(node.steps):
                if kind == "filter":
                    dense = False
                    continue
                exprs = dict(payload)
                if not all(isinstance(exprs.get(n), _expr.Col)
                           for n in names):
                    return None
                names = [exprs[n].name for n in names]
            node = node.child
        if not (isinstance(node, ShrinkOp)
                and isinstance(node.child, JoinOp)):
            return None
        join = node.child
        if (join.how != "inner" or _build_mode(join) != "unique"
                or len(join.probe_on) != 1 or len(join.build_on) != 1
                or _keyed_on_join(join, names) is None):
            return None
        self._mat(op.child)
        return dense if id(node) in self._compacted else None

    def _mat_agg(self, op: HashAggOp) -> Batch:
        acc, dense = self._agg_partial(op)
        return op._final_project(acc.compact() if dense else acc)

    def _agg_partial(self, op: HashAggOp) -> Tuple[Batch, bool]:
        """-> (`op`'s internal accumulator over this trace's input, before
        _final_project; is it DENSE). A dense accumulator holds group g at
        LANE g of the D lanes its keys' static domains span
        (op._dense_sizes), the same layout whatever rows came in: partials
        of it merge lane-wise (dense_merge; the mesh's shards do), and
        compact() gives the D-lane batch every later operator runs at.
        The ONE place the lowering is chosen, first that applies: in
        place, the int-key sort, by slot, hash; counts it, one event a
        traced HashAggOp."""
        group_by, internal = tuple(op.group_by), tuple(op.internal)
        ordered = self._ordered_input(op) if group_by else None
        if ordered is not None:
            # in place: nothing hashed, so no collision flag and no
            # re-seeded restart; the run-ends view at the Shrink's
            # lanes is what top_k_batch, a Shrink, a MapOp and
            # _pack_result take (Q18's first aggregate feeds them it)
            stats.add("fused.agg_ordered")
            return run_ends_aggregate(self._mat(op.child), group_by,
                                      internal, dense=ordered), False
        out = self._try_int_agg(op)
        if out is not None:
            stats.add("fused.agg_int_key")
            return out, False
        # by slot and by hash aggregate once over the materialized input
        # within the budget, and fold the chunk stream `s` over it
        s = self._agg_stream(op)
        if op._dense_sizes is not None:
            # no hash and a statically complete key space: no collision,
            # no overflow. Ranged keys (op.key_domains) carry the ONE flag,
            # a live key outside its range, answered by op.widen(); keys of
            # dictionaries and bools alone add no flag to the program
            stats.add("fused.agg_dense")
            sizes, doms = tuple(op._dense_sizes), op.key_domains

            def partial(b):
                return dense_aggregate(b, group_by, internal, sizes, doms,
                                       with_flag=True)

            if s is not None:
                def step(carry, b):
                    part, fl = partial(b)
                    return dense_merge(carry[0], part, group_by,
                                       internal), carry[1] | fl

                (acc, outside), fl = self._fold(s, partial, step)
                self.flag_ops.extend(s.flag_ops)
                self.flags.extend(fl)
            else:
                acc, outside = partial(self._mat(op.child))
            if doms:
                self.flag_ops.append(op)
                self.flags.append(outside)
            return acc, True
        stats.add("fused.agg_folded" if s is not None
                  else "fused.agg_materialized")
        if s is not None:
            part_cap = s.cap if group_by else 1
            acc_cap = _pow2_at_least(part_cap * op.expansion)
            row_bytes = self._row_bytes(op._internal_schema)
            if group_by and acc_cap * row_bytes > op.workmem:
                raise Unsupported("agg accumulator exceeds workmem")
            seed = op.seed
            grow = op._grow_traceable(acc_cap)
            fold = op._fold_traceable(acc_cap)

            def init(b):
                part, coll = hash_aggregate(b, group_by, internal, seed=seed,
                                            method="hash", with_flag=True)
                acc = grow(part)
                return acc, (part.length > jnp.int32(acc_cap)) | coll

            def step(carry, b):
                acc, ovf = carry
                part, coll = hash_aggregate(b, group_by, internal, seed=seed,
                                            method="hash", with_flag=True)
                acc, o = fold(acc, part)
                return acc, ovf | o | coll

            (acc, ovf), fl = self._fold(s, init, step)
            self.flag_ops.extend(s.flag_ops + ([op] if group_by else []))
            self.flags.extend(list(fl) + ([ovf] if group_by else []))
            return acc, False
        # materialized aggregate: output capacity == input capacity, which
        # by construction holds every group — no overflow is possible, but
        # a hash-grouping collision still forces a re-seeded rerun (a
        # scalar aggregate hashes nothing: no flag, no restart target)
        out, coll = hash_aggregate(self._mat(op.child), group_by, internal,
                                   seed=op.seed, method="hash",
                                   with_flag=True)
        if group_by:
            self.flag_ops.append(op)
            self.flags.append(coll)
        return out, False


# Result rows the fused program packs for the single-transfer readback.
# Bigger final results overflow to the streaming consume path (rare for
# analytic queries; a plain full-table SELECT is not a fusion target).
RESULT_CAP = 1 << 13


def _pack_result(batch: Batch, flags: Sequence[jnp.ndarray],
                 schema, result_cap: int) -> jnp.ndarray:
    """Traceable: compact the final batch and serialize rows[:result_cap],
    every overflow flag, and the true length into ONE uint8 buffer — so the
    host needs exactly one device->host transfer to finish the query (a
    10-column result read column-by-column would pay ten round trips)."""
    b = batch.compact()
    cap = b.capacity
    idx = jnp.arange(result_cap, dtype=jnp.int32) % max(cap, 1)
    sel = jnp.arange(result_cap) < b.length
    header = jnp.concatenate([
        b.length[None].astype(jnp.int32),
        (b.length > result_cap)[None].astype(jnp.int32),
        jnp.asarray([len(flags)], jnp.int32),
        (jnp.stack([f.astype(jnp.int32) for f in flags])
         if flags else jnp.zeros((0,), jnp.int32)),
    ])
    pieces = [jax.lax.bitcast_convert_type(header[:, None], jnp.uint8)
              .reshape(-1)]
    for f in schema:
        c = b.col(f.name)
        v = c.values[idx]
        if v.dtype == jnp.bool_:
            raw = v.astype(jnp.uint8)
        elif v.dtype.itemsize == 1:
            raw = jax.lax.bitcast_convert_type(v, jnp.uint8)
        else:
            raw = jax.lax.bitcast_convert_type(v[:, None], jnp.uint8)
            raw = raw.reshape(-1)
        pieces.append(raw)
        valid = c.valid_mask()[idx] & sel
        pieces.append(valid.astype(jnp.uint8))
    return jnp.concatenate(pieces)


def _unpack_result(host: "np.ndarray", schema, result_cap: int):
    """Host-side mirror of _pack_result: numpy-backed Batch + flag values +
    the result-overflow indicator."""
    import numpy as np

    from cockroach_tpu.coldata.batch import Column as _Col

    head = host[: 4 * 3].view(np.int32)
    length, result_ovf, n_flags = int(head[0]), bool(head[1]), int(head[2])
    off = 4 * (3 + n_flags)
    flags = [bool(x) for x in host[12:off].view(np.int32)]
    cols = {}
    valids = {}
    for f in schema:
        if f.type.dtype == jnp.bool_:
            vals = host[off:off + result_cap].astype(bool)
            off += result_cap
        else:
            dt = np.dtype(f.type.dtype)
            # VECTOR(d) columns are (rows, d): d lanes per row in the
            # packed buffer (mirrors _pack_result's row-major bitcast)
            lanes = f.type.lanes()
            nb = result_cap * lanes * dt.itemsize
            vals = host[off:off + nb].view(dt)
            if lanes > 1:
                vals = vals.reshape(result_cap, lanes)
            off += nb
        valid = host[off:off + result_cap].astype(bool)
        off += result_cap
        cols[f.name] = vals
        valids[f.name] = valid
    n = min(length, result_cap)
    sel = np.arange(result_cap) < n
    batch = _HostBatch(
        {k: _Col(v, valids[k]) for k, v in cols.items()}, sel, n)
    return batch, flags, result_ovf


class _HostBatch:
    """Numpy-backed result batch: satisfies the sink contract of collect /
    collect_arrow (columns/col/sel/length/capacity) without device arrays,
    so consuming it costs zero further device round trips."""

    def __init__(self, columns, sel, length):
        self.columns = columns
        self.sel = sel
        self.length = length

    @property
    def capacity(self):
        return self.sel.shape[0]

    def col(self, name):
        return self.columns[name]


def compile_via_vault(lowered, tables=(), extra_key=None):
    """Compile a lowered program vault-first: probe the persistent plan
    vault (util/plan_vault.py) by content digest of the StableHLO text,
    deserialize on a hit, else pay the XLA compile once and serialize the
    result back. With no vault configured this is exactly
    `FusedRunner._compile_lowered` — the trace/lower cost is unchanged
    either way; only the backend compile is elided. Sharded programs
    pass their placement identity (mesh shape, axis names, shard
    bucket) as `extra_key` so artifacts never cross mesh topologies."""
    from cockroach_tpu.util.plan_vault import plan_vault

    vault = plan_vault()
    if vault is None:
        return FusedRunner._compile_lowered(lowered)
    key = vault.key_for(lowered.as_text(), extra=extra_key)
    loaded = vault.load(key)
    if loaded is not None:
        return loaded
    compiled = FusedRunner._compile_lowered(lowered)
    vault.store(key, compiled, tables=tables)
    return compiled


class FusedRunner:
    """Drives a fused query: primes scans, compiles/executes the single
    program, applies the streaming runtime's FlowRestart contract. Falls
    back to the streaming tree when this run's volume is unsupported."""

    # device-resident arg sets kept per runner; small — each entry is a
    # tuple of *references* to images the ScanImageCache (or a ScanOp pin)
    # already holds, so the HBM cost is accounted elsewhere
    EXEC_CACHE_ENTRIES = 8

    def __init__(self, root: Operator):
        self.root = root
        self.schema = root.schema
        # config key -> (program, flag_ops, result_cap, sort_lanes,
        # hash_key_lanes, join_scan64_lanes, join_residual_lanes), or
        # None for a config that proved unsupported
        self._progs: Dict[tuple, Optional[tuple]] = {}
        # vkey (per-scan content-identity tuple) -> (args, chunks): lets a
        # warm run skip the prime walk (scan.stack + transfer) entirely
        self._exec_cache: "OrderedDict[tuple, Tuple[tuple, Dict[int, int]]]" \
            = OrderedDict()
        # runners are shared across sessions via the prepared-statement
        # cache: _prepare mutates both caches and must not interleave
        # (torn OrderedDict moves, duplicate compiles). RLock because a
        # re-entrant prime (fused fallback driving root.batches inside
        # the same thread) must not self-deadlock.
        self._mu = threading.RLock()
        self._served_once = False
        # a tree that reads bound parameters: the program takes them as
        # its last arguments (the statement's int64 vector of (value,
        # valid) pairs and a bool table for each LIKE pattern among them,
        # the same shapes at every binding), so program, config key and
        # persistent-cache entry belong to the statement and not to a
        # binding
        self._takes_params = takes_params(root)
        # what the last dispatch passed after the images (bound_program_args):
        # device_profile() runs the program at that binding; None until
        # the runner has dispatched
        self._last_bound: Optional[tuple] = None

    @staticmethod
    def _warm_key(scans) -> Optional[tuple]:
        """Content-identity key for the current scan inputs, or None when
        any scan's image residency can't be vouched for. Components:

        - scan already pinned (`_stacked` set): its cache_key if it has
          one, else a per-object pin identity. stacked_image() would
          serve that same pinned image back regardless, so reusing the
          cached args is behaviour-identical to a re-prime.
        - image resident in the process-wide ScanImageCache under the
          scan's versioned cache_key: the key embeds the MVCC write
          version and writes eagerly invalidate, so presence == fresh.
        - anything else (no key, evicted, prefetch-only): no warm path —
          a re-prime might stream different data than the cached args.
        """
        from cockroach_tpu.exec.scan_cache import scan_image_cache

        parts = []
        cache = scan_image_cache()
        for sc in scans:
            if getattr(sc, "_stacked", None) is not None:
                if sc.cache_key is not None:
                    parts.append(sc.cache_key)
                else:
                    parts.append(("pin", id(sc), id(sc._stacked[0])))
            elif sc.cache_key is not None and cache.contains(sc.cache_key):
                parts.append(sc.cache_key)
            else:
                return None
        return tuple(parts)

    # expansions change under FlowRestart retries -> new config -> recompile
    def _config_key(self, op: Operator, chunks: Dict[int, int]) -> tuple:
        out: list = []
        self._collect_key(op, chunks, out)
        return tuple(out)

    def _collect_key(self, op, chunks, out):

        if isinstance(op, ScanOp):
            # chunk counts enter the key pow2-bucketed (stacked_image pads
            # with empty chunks), so SF1/SF10 and repeated runs land on a
            # handful of program shapes per plan; defensively re-bucket in
            # case a caller hands an unpadded count
            from cockroach_tpu.exec.operators import _pow2_at_least

            out.append(("scan", _pow2_at_least(chunks[id(op)]),
                        op.capacity))
            return
        if isinstance(op, (JoinOp, HashAggOp)):
            # expansion (FlowRestart doubles it), workmem (gates the
            # Unsupported/fallback decision), build mode (restart drops
            # unique->expand), the hash-grouping seed (restart
            # re-seeds) and an aggregate's ranged keys (restart drops
            # them) all shape the program
            out.append((type(op).__name__, op.expansion, op.workmem,
                        getattr(op, "seed", 0),
                        getattr(op, "build_mode", ""),
                        tuple(sorted(
                            (getattr(op, "key_domains", None) or {}).items())),
                        getattr(op, "_ia_ok", True),
                        getattr(op, "_ia_wide", False)))
        elif isinstance(op, SortOp):
            out.append(("sort", op.workmem))
        elif isinstance(op, ShrinkOp):
            out.append(("shrink", op.capacity))
        for c in child_operators(op):
            self._collect_key(c, chunks, out)

    @staticmethod
    def _compile_lowered(lowered):
        """One backend compile: with TPU_COMPILE_OPTIONS on a TPU (the CPU
        backend knows no such option), never a second attempt without
        them. A refusal raises (see _refusal) and is served by no lower
        tier; only a transient backend fault comes out as it was, for
        with_retry to try again."""
        options = (TPU_COMPILE_OPTIONS
                   if jax.devices()[0].platform == "tpu" else None)
        try:
            return lowered.compile(options)
        except Exception as e:  # noqa: BLE001 — classified in _refusal
            refused = _refusal("compile", e)
            if refused is e:
                raise
            raise refused from e

    def _vault_compile(self, lowered):
        return compile_via_vault(
            lowered, tables=self._table_tags())

    def _table_tags(self):

        return tuple(sorted({sc.table for sc in walk_operators(self.root)
                             if isinstance(sc, ScanOp)
                             and getattr(sc, "table", None)}))

    def _make_prog(self, scan_ids):
        """The traceable whole-query program plus its tracer side-box
        (flag_ops / result_cap filled in during the trace). Shared by the
        data-driven prepare path and the abstract-shape AOT ladder."""
        tracer_box: dict = {}
        schema = self.schema
        n_scans = len(scan_ids)

        def prog(*stacked_args):
            t = _Tracer(dict(zip(scan_ids, stacked_args)), self.root)
            # a parameterised tree: the bound values are the arguments
            # after the images, and its filters read them while traced
            with _expr.traced_params(stacked_args[n_scans:]):
                out = t._mat(self.root)
            tracer_box["flag_ops"] = list(t.flag_ops)
            # the packed window never exceeds the result's own static
            # capacity — a 12-lane aggregate reads back ~1 KB, not MBs
            tracer_box["result_cap"] = min(RESULT_CAP, out.capacity)
            tracer_box["sort_lanes"] = t.sort_lanes
            tracer_box["hash_key_lanes"] = t.hash_key_lanes
            tracer_box["join_scan64_lanes"] = t.join_scan64_lanes
            tracer_box["join_residual_lanes"] = t.join_residual_lanes
            with scope(RESULT_SCOPE):
                return _pack_result(out, tuple(t.flags), schema,
                                    tracer_box["result_cap"])

        return prog, tracer_box

    @staticmethod
    def _prog_entry(compiled, tracer_box: dict) -> tuple:
        """What _progs keeps of a compiled config: the program and what
        its trace left in the side-box."""
        return (compiled, tracer_box["flag_ops"], tracer_box["result_cap"],
                tracer_box["sort_lanes"], tracer_box["hash_key_lanes"],
                tracer_box["join_scan64_lanes"],
                tracer_box["join_residual_lanes"])

    def _prepare(self):
        # one sessions-shared critical section covering the warm-key
        # probe, prime, exec-cache insert, and compile: concurrent cold
        # runs of the same statement serialize here (second thread gets
        # the first's compiled program instead of racing a duplicate)
        with self._mu:
            return self._prepare_locked()

    def _prepare_locked(self):

        scans = [n for n in walk_operators(self.root)
                 if isinstance(n, ScanOp)]
        scan_ids = [id(sc) for sc in scans]
        vkey = self._warm_key(scans)
        hit = self._exec_cache.get(vkey) if vkey is not None else None
        if hit is not None:
            # warm path: every scanned image is still resident at the
            # exact content version the cached args were built from — no
            # scan walk, no stack, no transfer
            args, chunks = hit
            self._exec_cache.move_to_end(vkey)
            stats.add("prime.skipped")
        else:
            stacked: Dict[int, Tuple] = {}
            chunks = {}
            with stats.timed("fused.prime"):
                _tracing.set_tag(scans=len(scans))
                for sc in scans:
                    try:
                        st = sc.stacked_image()
                    except Exception as e:
                        if _is_oom(e):
                            # table larger than HBM: the streaming
                            # runtime's chunked/out-of-core path is the
                            # correct executor
                            raise HBMExceeded("scan does not fit HBM") \
                                from e
                        raise
                    if st is None:
                        raise Unsupported("empty scan")
                    stacked[id(sc)] = st
                    chunks[id(sc)] = st[0].shape[0]
            # the program takes the stacked images as a positional TUPLE
            # (in deterministic scan-walk order): dict keys like id(scan)
            # differ per process and would bust the persistent compilation
            # cache
            args = tuple(stacked[i] for i in scan_ids)
            # re-key AFTER the prime (stacked_image may have re-fetched a
            # fresher image than the one _warm_key saw)
            vkey = self._warm_key(scans)
            if vkey is not None:
                self._exec_cache[vkey] = (args, dict(chunks))
                self._exec_cache.move_to_end(vkey)
                while len(self._exec_cache) > self.EXEC_CACHE_ENTRIES:
                    self._exec_cache.popitem(last=False)
        key = self._config_key(self.root, chunks)
        if key in self._progs:
            if self._progs[key] is None:
                # this config already proved unsupported (e.g. workmem):
                # don't pay a full re-trace just to rediscover it
                raise Unsupported("cached unsupported config")
            return self._progs[key], args
        if key not in self._progs:
            prog, tracer_box = self._make_prog(scan_ids)
            bound = bound_program_args(self._takes_params)

            def build():
                maybe_fail("fused.compile")
                return self._vault_compile(
                    lower_program(prog, args + bound))

            with stats.timed("fused.compile"):
                # trace + compile eagerly so Unsupported surfaces here
                # (before any batch is yielded) and flag_ops is known.
                # HBMExceeded is an Unsupported: negative-cached, streamed
                # and counted; a CompileRefused propagates
                try:
                    compiled = _retry.with_retry(build, name="fused.compile")
                except Unsupported:
                    self._progs[key] = None
                    raise
            self._progs[key] = self._prog_entry(compiled, tracer_box)
        return self._progs[key], args

    def aot_compile(self, extra_buckets: int = 1) -> int:
        """Compile this plan's pow2 shape-bucket ladder off the query
        path: the current chunk bucket through the normal prepare (prime
        + compile, vault-first), then `extra_buckets` doublings lowered
        from abstract ShapeDtypeStructs — no data transfer, no execution.
        Each rung lands in the in-process program cache AND the plan
        vault, so both this process's first execution and a restarted
        node's are warm. Returns the number of program configs now
        resident (0 when the plan is outside the fusion grammar)."""

        with self._mu:
            try:
                _compiled, args = self._prepare_locked()
            except Unsupported:
                return 0
            done = 1
            scans = [n for n in walk_operators(self.root)
                     if isinstance(n, ScanOp)]
            scan_ids = [id(sc) for sc in scans]
            base = {sid: int(a[0].shape[0])
                    for sid, a in zip(scan_ids, args)}
            for step in range(1, extra_buckets + 1):
                chunks = {sid: c << step for sid, c in base.items()}
                key = self._config_key(self.root, chunks)
                if key in self._progs:
                    if self._progs[key] is not None:
                        done += 1
                    continue
                prog, tracer_box = self._make_prog(scan_ids)
                sds = tuple(
                    (jax.ShapeDtypeStruct(
                        (chunks[sid],) + tuple(a[0].shape[1:]),
                        a[0].dtype),
                     jax.ShapeDtypeStruct(
                        (chunks[sid],) + tuple(a[1].shape[1:]),
                        a[1].dtype))
                    for sid, a in zip(scan_ids, args)) \
                    + bound_program_args(self._takes_params)

                def build(prog=prog, sds=sds):
                    maybe_fail("fused.compile")
                    return self._vault_compile(lower_program(prog, sds))

                with stats.timed("fused.aot_compile"):
                    _tracing.set_tag(step=step)
                    try:
                        compiled = _retry.with_retry(
                            build, name="fused.compile")
                    except Unsupported:
                        # outside the grammar at this volume, or a rung
                        # too large for HBM — negative-cache it; smaller
                        # rungs still serve
                        self._progs[key] = None
                        continue
                self._progs[key] = self._prog_entry(compiled, tracer_box)
                done += 1
            return done

    def batches(self):
        import time as _time

        import numpy as np

        # first-ever execution of this runner is the cold-start number the
        # plan vault exists to shrink: give it its own metric/span so the
        # /_status dashboards can see it directly
        first = not self._served_once
        t_first = _time.perf_counter()
        try:
            with stats.timed("fused.prepare"):
                (prog, flag_ops, result_cap, sort_lanes, hash_key_lanes,
                 join_scan64_lanes, join_residual_lanes), args = \
                    self._prepare()
        except Unsupported as e:
            # this run's volume (or shape) is outside the fusion grammar:
            # delegate wholesale to the streaming runtime
            stats.add("fused.fallback_unsupported")
            if isinstance(e, HBMExceeded):
                stats.add(stats.STREAM_HBM)
            _tracing.record("fused.fallback", reason="unsupported",
                            detail=str(e)[:80])
            from cockroach_tpu.util import log as _log
            _log.get_logger().info(
                _log.Channel.SQL_EXEC,
                "fused fallback -> streaming (unsupported: {})", e)
            yield from self.root.batches()
            return
        bound = self._last_bound = bound_program_args(self._takes_params)

        def dispatch():
            _cancel.checkpoint()
            maybe_fail("fused.exec")
            # fused.exec = dispatch + wait: until prog() returns the host
            # is enqueueing; after that it waits for the device, which may
            # first finish another session's program
            with stats.timed("fused.dispatch"):
                out = prog(*args, *bound)
            # block: without the sync the dispatch returns immediately
            # and the device's execution time is billed to
            # fused.readback; readback measures only the transfer
            with stats.timed("fused.wait"):
                out = jax.block_until_ready(out)
            # one event a dispatch; the lanes are the traced shapes'
            stats.add("fused.sort_lanes", rows=sort_lanes)
            stats.add("fused.hash_key_lanes", rows=hash_key_lanes)
            stats.add("fused.join_scan64_lanes", rows=join_scan64_lanes)
            stats.add("fused.join_residual_lanes", rows=join_residual_lanes)
            return out

        try:
            with stats.timed("fused.exec"):
                buf = _retry.with_retry(dispatch, name="fused.exec")
            with stats.timed("fused.readback", bytes=buf.nbytes):
                host = np.asarray(buf)
            try:
                buf.delete()  # the packed result window is copied out;
                # free its device allocation now instead of at GC time
            except Exception:  # noqa: BLE001 — best-effort release
                pass
        except Exception as e:
            if _is_oom(e):
                # whole-query working set exceeded HBM at run time: the
                # streaming runtime bounds memory per stage (and spills)
                stats.add("fused.fallback_oom")
                stats.add(stats.STREAM_HBM)
                _tracing.record("fused.fallback", reason="oom")
                from cockroach_tpu.util import log as _log
                _log.get_logger().info(
                    _log.Channel.SQL_EXEC,
                    "fused fallback -> streaming (device OOM: {})",
                    str(e)[:200])
                yield from self.root.batches()
                return
            raise
        with stats.timed("fused.unpack"):
            batch, flags, result_ovf = _unpack_result(host, self.schema,
                                                      result_cap)
        # deferred overflow checks come FIRST: a restart discards output
        for fop, fl in zip(flag_ops, flags):
            if fl:
                raise FlowRestart(fop)
        if result_ovf:
            # result larger than the packed window: re-run streaming (the
            # query result itself is the bulk payload — not a fusion win)
            yield from self.root.batches()
            return
        if first:
            self._served_once = True
            dt = _time.perf_counter() - t_first
            from cockroach_tpu.util.metric import default_registry

            default_registry().histogram(
                "sql_first_execution_seconds",
                "wall time of each prepared plan's first-ever fused "
                "execution (prime + compile-or-vault-load + dispatch)"
            ).observe(dt)
            stats.add("fused.first_execution")
        yield batch

    def device_profile(self, repeats: int = 5):
        """Device milliseconds by plan operator of the program this
        runner serves with, at its last binding
        (exec/device_profile.DeviceProfile): a profile of `repeats`
        serial executions of the warm _prepare()'s program. None for a
        runner that has not dispatched, or whose tree is Unsupported
        now."""
        from cockroach_tpu.exec import device_profile as _dp

        bound = self._last_bound
        if bound is None:
            return None
        try:
            with _expr.bound_args(bound or None):
                (prog, *_trace_facts), args = self._prepare()
        except Unsupported:
            return None
        stages = ("fused.dispatch", "fused.wait")
        return _dp.profile(
            _dp.run_annotated(lambda: prog(*args, *bound), stages),
            prog, repeats, stages)


def try_compile(op: Operator) -> Optional[FusedRunner]:
    """FusedRunner for `op`, or None when the tree is outside the fusion
    grammar (caller uses the streaming runtime directly)."""
    try:
        _validate(op)
    except Unsupported:
        return None
    return FusedRunner(op)


# -------------------------------------------------------------- serving --


class _BucketPrograms:
    """Per-pow2-bucket AOT executables for a serving runner. Exposes
    `_cache_size()` with jit's probe name so the shape-cache-bound gates
    (scripts/check_key_bucketing.py, tests/test_serving.py) keep reading
    one number: compiled program shapes resident for this runner."""

    def __init__(self):
        self.progs: Dict[int, Callable] = {}

    def _cache_size(self) -> int:
        return len(self.progs)


class ServingScanRunner:
    """Batch-shaped program variant for the cross-session serving queue
    (sql/serving.py): one table's pk-sorted projection held
    device-resident plus a jitted vmapped range-scan micro-program over
    it — workload/ycsb.ScanTopKBatcher generalized into the serving
    path.

    Each vmap lane locates its [lo, hi) pk range (arithmetic when the
    keys are contiguous, binary search otherwise), gathers a static
    `window` of rows, and masks lanes past the range end / LIMIT. Every
    mask term — idx < n, pk >= lo, pk < hi, lane < lim — holds on a
    PREFIX of the window because the keys are sorted, so `counts[i]`
    rows sliced off the front of lane i are exactly that statement's
    result, in pk order: bit-identical to the streaming path over the
    same MVCC version.

    These runners are the batch-shaped exec-cache entries: FusedRunner
    caches (compiled program, resident args) per prepared statement;
    the serving queue caches one of THESE per (table version,
    projection, window) compatibility key, shared by every member
    statement of the group."""

    def __init__(self, pks: "np.ndarray", columns, valids, window: int,
                 table: Optional[str] = None):
        self.window = int(window)
        self.n = len(pks)
        self.names = tuple(columns)
        self.table = table
        self.nbytes = int(pks.nbytes
                          + sum(columns[c].nbytes for c in columns)
                          + sum(valids[c].nbytes for c in valids))
        if self.n == 0:
            self._batched = None
            return
        pks_np = np.asarray(pks, dtype=np.int64)
        self._keys = jnp.asarray(pks_np)
        self._cols = jnp.stack([jnp.asarray(np.asarray(columns[c],
                                                       dtype=np.int64))
                                for c in self.names])
        self._vals = jnp.stack([jnp.asarray(np.asarray(valids[c],
                                                       dtype=bool))
                                for c in self.names])
        # contiguous keys make the range search arithmetic instead of a
        # binary search over the key column (the YCSB loader's shape)
        pk0 = (int(pks_np[0]) if np.array_equal(
            pks_np, pks_np[0] + np.arange(self.n)) else None)
        n = self.n
        lanes = jnp.arange(self.window)

        # the table arrays enter as ARGUMENTS (in_axes=None), not closure
        # captures: the lowered program is then pure of this process's
        # data, so its compiled executable is a valid plan-vault artifact
        # for any restart serving the same (projection, window) shape
        def one(lo, hi, lim, keys, cols, vals):
            if pk0 is not None:
                start = jnp.clip(lo - pk0, 0, n)
            else:
                start = jnp.searchsorted(keys, lo)
            idx = start + lanes
            cidx = jnp.minimum(idx, n - 1)
            pk = keys[cidx]
            ok = (idx < n) & (pk >= lo) & (pk < hi) & (lanes < lim)
            return cols[:, cidx], vals[:, cidx], ok.sum(dtype=jnp.int32)

        self._fn = jax.vmap(one, in_axes=(0, 0, 0, None, None, None))
        # per-pow2-bucket AOT executables; the caller's batch padding
        # buckets program shapes exactly like ScanTopKBatcher.run()
        self._batched = _BucketPrograms()
        self._compile_mu = threading.Lock()

    def _program(self, bucket: int):
        """The AOT-compiled executable for one pow2 batch bucket:
        in-process cache -> plan vault -> XLA compile, in that order."""
        prog = self._batched.progs.get(bucket)
        if prog is not None:
            return prog
        with self._compile_mu:
            prog = self._batched.progs.get(bucket)
            if prog is not None:
                return prog
            lane = jax.ShapeDtypeStruct((bucket,), self._keys.dtype)
            with stats.timed("serving.compile"):
                _tracing.set_tag(bucket=bucket)
                lowered = jax.jit(self._fn).lower(
                    lane, lane, lane,
                    self._keys, self._cols, self._vals)
                prog = compile_via_vault(
                    lowered,
                    tables=(self.table,) if self.table else ())
            self._batched.progs[bucket] = prog
            return prog

    def compile_bucket(self, batch: int) -> bool:
        """Pre-compile (vault-first) the program for `batch`'s pow2
        bucket without dispatching — the pre-warm job entry point."""
        if self.n == 0:
            return False
        self._program(_pow2_at_least(max(int(batch), 1)))
        return True

    def serve(self, specs):
        """Uniform serving-queue entry point: one payload per member
        spec (collect()-shaped dicts), lane params pulled off the specs.
        The prefix property (class docstring) makes the count-row slice
        bit-identical to the streaming path."""
        los = np.asarray([s.lo for s in specs], np.int64)
        his = np.asarray([s.hi for s in specs], np.int64)
        lims = np.asarray(
            [self.window if s.limit is None
             else min(s.limit, self.window) for s in specs], np.int64)
        vals, valid, counts = self.run(los, his, lims)
        return [_prefix_payload(self.names, vals[i], valid[i],
                                int(counts[i]))
                for i in range(len(specs))]

    def prewarm_batch(self, batch: int) -> None:
        z = np.zeros(batch, dtype=np.int64)
        self.run(z, z, np.full(batch, self.window, dtype=np.int64))

    def run(self, los, his, lims):
        """ONE device dispatch for a batch of range micro-queries.
        Returns (values (B, C, window), valid (B, C, window),
        counts (B,)) as numpy arrays, batch padded to the pow2 bucket
        and sliced back."""
        los = np.asarray(los, dtype=np.int64)
        his = np.asarray(his, dtype=np.int64)
        lims = np.asarray(lims, dtype=np.int64)
        b = len(los)
        if self.n == 0 or b == 0:
            c = len(self.names)
            return (np.zeros((b, c, self.window), np.int64),
                    np.zeros((b, c, self.window), bool),
                    np.zeros(b, np.int32))
        bucket = _pow2_at_least(b)
        if bucket > b:
            pad = np.zeros(bucket - b, dtype=np.int64)
            los = np.concatenate([los, pad])
            his = np.concatenate([his, pad])
            lims = np.concatenate([lims, pad])
        # numpy lane args go straight into the AOT executable (it accepts
        # host arrays); the resident table arrays ride along by reference
        prog = self._program(bucket)
        vals, valid, counts = jax.block_until_ready(
            prog(los, his, lims, self._keys, self._cols, self._vals))
        return (np.asarray(vals)[:b], np.asarray(valid)[:b],
                np.asarray(counts)[:b])


class ResidentServingRunner:
    """ServingScanRunner's device-resident sibling: instead of a
    host-walk snapshot frozen at build time (torn down by the first
    write), it reads the table's ResidentTable visibility image
    (storage/resident.py) and REFRESHES it per dispatch — a write costs
    one delta fold + visibility kernel at the next batch, while the
    vmapped program and its serving-queue slot stay warm (their key is
    the attach generation, stable across writes).

    The table enters the program as arguments — (n, keys, cols, mask) —
    so compiled executables are keyed only by (batch bucket, image
    capacity): pow2 image growth compiles a new shape, everything else
    reuses. Row count `n` rides as a scalar arg because the image's
    sentinel-padded capacity is the static shape, not its live prefix.
    Validity decodes from the row's NULL-bitmap slot in-kernel (static
    bit per projected column), so the image needs no per-column validity
    planes."""

    def __init__(self, rt, names, slots, bits, mask_slot: int,
                 window: int, table: Optional[str] = None):
        self.rt = rt
        self.window = int(window)
        self.names = tuple(names)
        self.table = table
        self._slots = tuple(int(s) for s in slots)
        self._mask_slot = int(mask_slot)
        self._batched = _BucketPrograms()
        self._compile_mu = threading.Lock()
        self._refresh_mu = threading.Lock()
        self._img = None
        self._keys = self._cols = self._mask = None
        self.n = 0
        self.nbytes = 0
        bits_t = tuple(int(b) for b in bits)
        lanes = jnp.arange(self.window)

        def one(lo, hi, lim, n, keys, cols, mask):
            cap = keys.shape[0]
            start = jnp.searchsorted(keys, lo)
            idx = start + lanes
            cidx = jnp.minimum(idx, cap - 1)
            pk = keys[cidx]
            ok = (idx < n) & (pk >= lo) & (pk < hi) & (lanes < lim)
            m = mask[cidx]
            valid = jnp.stack(
                [jnp.ones_like(ok) if b < 0 else (((m >> b) & 1) == 0)
                 for b in bits_t])
            return cols[:, cidx], valid, ok.sum(dtype=jnp.int32)

        self._fn = jax.vmap(one,
                            in_axes=(0, 0, 0, None, None, None, None))

    def alive(self) -> bool:
        return not self.rt._dead

    def _refresh(self):
        """Re-derive the projected device arrays when the resident image
        moved (any write since the last dispatch). Raises
        ResidentUnavailable when the table detached — the serving queue
        then drops this runner and the next batch rebuilds host-side."""
        img = self.rt.image_at(None)
        with self._refresh_mu:
            if img is not self._img:
                self._keys = img.pk_dev
                # slot -1 projects the pk lane itself (pk in the
                # SELECT list), everything else a value slot
                parts = [img.pk_dev if s < 0 else img.vals_dev[s]
                         for s in self._slots]
                self._cols = (jnp.stack(parts) if parts
                              else img.vals_dev[:0, :])
                self._mask = img.vals_dev[self._mask_slot]
                self.n = img.count
                self.nbytes = int((len(self._slots) + 2) * 8 * img.cap)
                self._img = img
            return (self.n, self._keys, self._cols, self._mask)

    def _program(self, bucket: int, cap: int):
        pkey = (bucket, cap)
        prog = self._batched.progs.get(pkey)
        if prog is not None:
            return prog
        with self._compile_mu:
            prog = self._batched.progs.get(pkey)
            if prog is not None:
                return prog
            lane = jax.ShapeDtypeStruct((bucket,), jnp.int64)
            scalar = jax.ShapeDtypeStruct((), jnp.int64)
            keys_s = jax.ShapeDtypeStruct((cap,), jnp.int64)
            cols_s = jax.ShapeDtypeStruct((len(self._slots), cap),
                                          jnp.int64)
            with stats.timed("serving.compile"):
                _tracing.set_tag(bucket=bucket)
                lowered = jax.jit(self._fn).lower(
                    lane, lane, lane, scalar, keys_s, cols_s, keys_s)
                prog = compile_via_vault(
                    lowered,
                    tables=(self.table,) if self.table else ())
            self._batched.progs[pkey] = prog
            return prog

    def compile_bucket(self, batch: int) -> bool:
        n, keys, _, _ = self._refresh()
        self._program(_pow2_at_least(max(int(batch), 1)),
                      int(keys.shape[0]))
        return True

    def serve(self, specs):
        """Uniform serving-queue entry point (see ServingScanRunner)."""
        los = np.asarray([s.lo for s in specs], np.int64)
        his = np.asarray([s.hi for s in specs], np.int64)
        lims = np.asarray(
            [self.window if s.limit is None
             else min(s.limit, self.window) for s in specs], np.int64)
        vals, valid, counts = self.run(los, his, lims)
        return [_prefix_payload(self.names, vals[i], valid[i],
                                int(counts[i]))
                for i in range(len(specs))]

    def prewarm_batch(self, batch: int) -> None:
        z = np.zeros(batch, dtype=np.int64)
        self.run(z, z, np.full(batch, self.window, dtype=np.int64))

    def run(self, los, his, lims):
        """Same contract as ServingScanRunner.run — (values, valid,
        counts) numpy arrays — over the CURRENT resident image."""
        n, keys, cols, mask = self._refresh()
        los = np.asarray(los, dtype=np.int64)
        his = np.asarray(his, dtype=np.int64)
        lims = np.asarray(lims, dtype=np.int64)
        b = len(los)
        if b == 0:
            c = len(self.names)
            return (np.zeros((b, c, self.window), np.int64),
                    np.zeros((b, c, self.window), bool),
                    np.zeros(b, np.int32))
        bucket = _pow2_at_least(b)
        if bucket > b:
            pad = np.zeros(bucket - b, dtype=np.int64)
            los = np.concatenate([los, pad])
            his = np.concatenate([his, pad])
            lims = np.concatenate([lims, pad])
        prog = self._program(bucket, int(keys.shape[0]))
        vals, valid, counts = jax.block_until_ready(
            prog(los, his, lims, np.int64(n), keys, cols, mask))
        return (np.asarray(vals)[:b], np.asarray(valid)[:b],
                np.asarray(counts)[:b])


def build_serving_runner(catalog, capacity: int, table: str, cols,
                         window: int) -> ServingScanRunner:
    """Snapshot `table`'s pk + projected INT columns (with validity
    lanes) out of the catalog's chunk stream into a ServingScanRunner.
    The caller keys the runner by the table's MVCC-versioned scan-cache
    key, so a stale image can never serve — any write rotates the key
    and the next batch builds fresh (same contract as the scan-image
    cache). Device-resident tables route to ResidentServingRunner
    instead: per-dispatch image refresh under a write-stable key."""
    rs = getattr(catalog, "resident_serving", None)
    if rs is not None:
        try:
            info = rs(table, cols)
        except Exception:  # noqa: BLE001 — never block the host build
            info = None
        if info is not None:
            return ResidentServingRunner(
                info["rt"], tuple(cols), info["slots"], info["bits"],
                info["mask_slot"], window, table=table)
    pks, columns, valids = _snapshot_columns(catalog, capacity, table,
                                             cols)
    return ServingScanRunner(pks, columns, valids, window, table=table)


def _snapshot_columns(catalog, capacity: int, table: str, cols):
    """Host-snapshot `table`'s pk + `cols` (with validity lanes) out of
    the catalog's chunk stream, pk-stable-sorted: the shared image build
    behind every frozen-snapshot serving runner. INT columns come out
    int64; VECTOR columns keep their decoded (rows, d) float32 shape —
    both exactly the arrays the per-statement scan feeds downstream, so
    batched kernels see bit-identical inputs."""
    pk = catalog.table_pk(table)[0]
    wanted = list(dict.fromkeys((pk,) + tuple(cols)))
    parts = list(catalog.table_chunks(table, capacity, wanted)())

    def _cast(arrs):
        a = np.concatenate(arrs) if len(arrs) > 1 else np.asarray(
            arrs[0])
        if a.ndim == 2:  # VECTOR(d) decodes to (rows, d) float32
            return np.asarray(a, np.float32)
        return np.asarray(a, np.int64)

    with stats.timed("serving.image_build"):
        if parts:
            pks = np.concatenate([np.asarray(p[pk], np.int64)
                                  for p in parts])
            columns = {}
            valids = {}
            for c in cols:
                columns[c] = _cast([p[c] for p in parts])
                if c + "__valid" in parts[0]:
                    valids[c] = np.concatenate(
                        [np.asarray(p[c + "__valid"], bool)
                         for p in parts])
                else:
                    valids[c] = np.ones(len(columns[c]), bool)
        else:
            pks = np.zeros(0, np.int64)
            columns = {c: np.zeros(0, np.int64) for c in cols}
            valids = {c: np.zeros(0, bool) for c in cols}
        if len(pks) > 1 and not np.all(pks[1:] >= pks[:-1]):
            order = np.argsort(pks, kind="stable")
            pks = pks[order]
            columns = {c: v[order] for c, v in columns.items()}
            valids = {c: v[order] for c, v in valids.items()}
        return pks, columns, valids


def _prefix_payload(names, vals, valid, count: int):
    """One member's collect()-shaped payload out of its batch lane: the
    first `count` window rows of every projected column (the prefix
    property, or post-sort row order for the top-K classes)."""
    payload = {}
    for ci, name in enumerate(names):
        payload[name] = np.array(vals[ci, :count])
        payload[name + "__valid"] = np.array(valid[ci, :count])
    return payload


class ServingAggRunner:
    """Batchable-aggregate runner: each vmap lane folds its own [lo, hi)
    pk range through the scalar-aggregate formulas of ops/agg.py's
    `_scalar_agg` — count(*)/count as int64 masked sums, sum in the
    column dtype (int64), avg as float32(sum)/float32(max(count, 1)),
    min/max as identity-filled reductions, each value paired with the
    same any-live validity. Integer reductions are order-independent, so
    a lane's fold is bit-identical to the streaming path's chunked fold
    over the same MVCC version (the per-class prefix-property argument:
    aggregates have no row order to preserve, only exact arithmetic).

    Snapshot-frozen like ServingScanRunner: the serving queue keys these
    runners by the table's MVCC-versioned scan-cache key, so any write
    rotates the group and the next batch rebuilds."""

    def __init__(self, pks, columns, valids, aggs, names, window: int,
                 table: Optional[str] = None):
        self.window = int(window)
        self.n = len(pks)
        self.aggs = tuple(aggs)      # ((func, col-or-None), ...)
        self.names = tuple(names)    # output field name per agg
        self.table = table
        in_cols = tuple(dict.fromkeys(
            c for _f, c in self.aggs if c is not None))
        self._in_cols = in_cols
        self.nbytes = int(np.asarray(pks).nbytes
                          + sum(columns[c].nbytes for c in in_cols)
                          + sum(valids[c].nbytes for c in in_cols))
        self._batched = _BucketPrograms()
        self._compile_mu = threading.Lock()
        if self.n == 0:
            return
        pks_np = np.asarray(pks, dtype=np.int64)
        self._keys = jnp.asarray(pks_np)
        if in_cols:
            self._cols = jnp.stack([jnp.asarray(np.asarray(
                columns[c], np.int64)) for c in in_cols])
            self._vals = jnp.stack([jnp.asarray(np.asarray(
                valids[c], bool)) for c in in_cols])
        else:  # pure count(*): the kernel still wants array operands
            self._cols = jnp.zeros((1, self.n), jnp.int64)
            self._vals = jnp.ones((1, self.n), bool)
        cidx_of = {c: i for i, c in enumerate(in_cols)}
        agg_plan = tuple((f, None if c is None else cidx_of[c])
                         for f, c in self.aggs)
        pk0 = (int(pks_np[0]) if np.array_equal(
            pks_np, pks_np[0] + np.arange(self.n)) else None)
        n = self.n
        lanes = jnp.arange(self.window)

        def one(lo, hi, keys, cols, vals):
            if pk0 is not None:
                start = jnp.clip(lo - pk0, 0, n)
            else:
                start = jnp.searchsorted(keys, lo)
            idx = start + lanes
            cidx = jnp.minimum(idx, n - 1)
            pk = keys[cidx]
            sel = (idx < n) & (pk >= lo) & (pk < hi)
            outs = []
            oks = []
            for func, ci in agg_plan:
                if func == "count_star":
                    outs.append(jnp.sum(sel.astype(jnp.int64)))
                    oks.append(jnp.ones((), bool))
                    continue
                v = cols[ci, cidx]
                live = sel & vals[ci, cidx]
                any_live = jnp.any(live)
                if func == "count":
                    outs.append(jnp.sum(live.astype(jnp.int64)))
                    oks.append(jnp.ones((), bool))
                elif func in ("sum", "avg"):
                    s = jnp.sum(jnp.where(live, v,
                                          jnp.zeros((), v.dtype)))
                    if func == "sum":
                        outs.append(s)
                    else:
                        cnt = jnp.maximum(
                            jnp.sum(live.astype(jnp.int64)), 1)
                        outs.append(s.astype(jnp.float32)
                                    / cnt.astype(jnp.float32))
                    oks.append(any_live)
                else:  # min / max
                    ident = _agg_identity(func, v.dtype)
                    filled = jnp.where(live, v, ident)
                    outs.append(jnp.min(filled) if func == "min"
                                else jnp.max(filled))
                    oks.append(any_live)
            return tuple(outs), tuple(oks)

        self._fn = jax.vmap(one, in_axes=(0, 0, None, None, None))

    def _program(self, bucket: int):
        prog = self._batched.progs.get(bucket)
        if prog is not None:
            return prog
        with self._compile_mu:
            prog = self._batched.progs.get(bucket)
            if prog is not None:
                return prog
            lane = jax.ShapeDtypeStruct((bucket,), jnp.int64)
            with stats.timed("serving.compile"):
                _tracing.set_tag(bucket=bucket)
                lowered = jax.jit(self._fn).lower(
                    lane, lane, self._keys, self._cols, self._vals)
                prog = compile_via_vault(
                    lowered,
                    tables=(self.table,) if self.table else ())
            self._batched.progs[bucket] = prog
            return prog

    def compile_bucket(self, batch: int) -> bool:
        if self.n == 0:
            return False
        self._program(_pow2_at_least(max(int(batch), 1)))
        return True

    def _empty_lane(self):
        """The formulas of `one` over an all-dead selection, host-side
        (an empty table never traces a kernel)."""
        out = []
        for func, _ci in self.aggs:
            if func in ("count_star", "count"):
                out.append((np.int64(0), True))
            elif func == "sum":
                out.append((np.int64(0), False))
            elif func == "avg":
                out.append((np.float32(0.0), False))
            elif func == "min":
                out.append((np.int64(np.iinfo(np.int64).max), False))
            else:  # max
                out.append((np.int64(np.iinfo(np.int64).min), False))
        return out

    def run(self, los, his):
        """(per-agg values, per-agg valids) — each a length-len(aggs)
        list of (B,) numpy arrays."""
        los = np.asarray(los, dtype=np.int64)
        his = np.asarray(his, dtype=np.int64)
        b = len(los)
        if self.n == 0 or b == 0:
            empty = self._empty_lane()
            return ([np.full(b, v, dtype=np.asarray(v).dtype)
                     for v, _ in empty],
                    [np.full(b, ok, dtype=bool) for _, ok in empty])
        bucket = _pow2_at_least(b)
        if bucket > b:
            pad = np.zeros(bucket - b, dtype=np.int64)
            los = np.concatenate([los, pad])
            his = np.concatenate([his, pad])
        prog = self._program(bucket)
        outs, oks = jax.block_until_ready(
            prog(los, his, self._keys, self._cols, self._vals))
        return ([np.asarray(o)[:b] for o in outs],
                [np.asarray(o)[:b] for o in oks])

    def serve(self, specs):
        los = np.asarray([s.lo for s in specs], np.int64)
        his = np.asarray([s.hi for s in specs], np.int64)
        outs, oks = self.run(los, his)
        payloads = []
        for i in range(len(specs)):
            p = {}
            for j, name in enumerate(self.names):
                p[name] = np.array([outs[j][i]])
                p[name + "__valid"] = np.array([oks[j][i]])
            payloads.append(p)
        return payloads

    def prewarm_batch(self, batch: int) -> None:
        z = np.zeros(batch, dtype=np.int64)
        self.run(z, z)


class ServingTopKRunner:
    """LIMIT + ORDER BY non-pk runner: each vmap lane gathers its pow2
    window of pk-range rows, then sorts them with exactly ops/sort.py's
    lexicographic key construction — value key (bitwise-NOT for DESC),
    NULLs via a leading validity rank (NULLS FIRST for ASC, LAST for
    DESC — the SQL/CRDB default), out-of-range lanes forced last — and
    jnp.lexsort's stable tie-break, which preserves window-lane order =
    pk order, the same total order the streaming TopKOp produces over
    the same rows. The first min(matched, k) sorted rows of a lane are
    therefore bit-identical to the per-statement result."""

    def __init__(self, pks, columns, valids, order_vals, order_valid,
                 descending: bool, window: int,
                 table: Optional[str] = None):
        self.window = int(window)
        self.n = len(pks)
        self.names = tuple(columns)
        self.descending = bool(descending)
        self.table = table
        self.nbytes = int(np.asarray(pks).nbytes
                          + sum(columns[c].nbytes for c in columns)
                          + sum(valids[c].nbytes for c in valids)
                          + np.asarray(order_vals).nbytes)
        self._batched = _BucketPrograms()
        self._compile_mu = threading.Lock()
        if self.n == 0:
            return
        pks_np = np.asarray(pks, dtype=np.int64)
        self._keys = jnp.asarray(pks_np)
        self._cols = jnp.stack([jnp.asarray(np.asarray(columns[c],
                                                       np.int64))
                                for c in self.names])
        self._vals = jnp.stack([jnp.asarray(np.asarray(valids[c],
                                                       bool))
                                for c in self.names])
        self._ovals = jnp.asarray(np.asarray(order_vals, np.int64))
        self._ovalid = jnp.asarray(np.asarray(order_valid, bool))
        pk0 = (int(pks_np[0]) if np.array_equal(
            pks_np, pks_np[0] + np.arange(self.n)) else None)
        n = self.n
        lanes = jnp.arange(self.window)
        desc = self.descending
        nulls_first = not desc  # ops/sort.py SortKey default

        def one(lo, hi, lim, keys, cols, vals, ovals, ovalid):
            if pk0 is not None:
                start = jnp.clip(lo - pk0, 0, n)
            else:
                start = jnp.searchsorted(keys, lo)
            idx = start + lanes
            cidx = jnp.minimum(idx, n - 1)
            pk = keys[cidx]
            ok = (idx < n) & (pk >= lo) & (pk < hi)
            kv = _sortable_int(ovals[cidx])
            if desc:
                kv = ~kv
            va = ovalid[cidx]
            null_rank = (jnp.where(va, 1, 0) if nulls_first
                         else jnp.where(va, 0, 1))
            # lexsort: LAST key is primary — dead lanes last, then the
            # null rank, then the (possibly flipped) value key; stable
            # ties keep window-lane order, i.e. pk order
            perm = jnp.lexsort((kv, null_rank, jnp.where(ok, 0, 1)))
            sidx = cidx[perm]
            count = jnp.minimum(ok.sum(), lim).astype(jnp.int32)
            return cols[:, sidx], vals[:, sidx], count

        self._fn = jax.vmap(
            one, in_axes=(0, 0, 0, None, None, None, None, None))

    def _program(self, bucket: int):
        prog = self._batched.progs.get(bucket)
        if prog is not None:
            return prog
        with self._compile_mu:
            prog = self._batched.progs.get(bucket)
            if prog is not None:
                return prog
            lane = jax.ShapeDtypeStruct((bucket,), jnp.int64)
            with stats.timed("serving.compile"):
                _tracing.set_tag(bucket=bucket)
                lowered = jax.jit(self._fn).lower(
                    lane, lane, lane, self._keys, self._cols,
                    self._vals, self._ovals, self._ovalid)
                prog = compile_via_vault(
                    lowered,
                    tables=(self.table,) if self.table else ())
            self._batched.progs[bucket] = prog
            return prog

    def compile_bucket(self, batch: int) -> bool:
        if self.n == 0:
            return False
        self._program(_pow2_at_least(max(int(batch), 1)))
        return True

    def run(self, los, his, lims):
        los = np.asarray(los, dtype=np.int64)
        his = np.asarray(his, dtype=np.int64)
        lims = np.asarray(lims, dtype=np.int64)
        b = len(los)
        if self.n == 0 or b == 0:
            c = len(self.names)
            return (np.zeros((b, c, self.window), np.int64),
                    np.zeros((b, c, self.window), bool),
                    np.zeros(b, np.int32))
        bucket = _pow2_at_least(b)
        if bucket > b:
            pad = np.zeros(bucket - b, dtype=np.int64)
            los = np.concatenate([los, pad])
            his = np.concatenate([his, pad])
            lims = np.concatenate([lims, pad])
        prog = self._program(bucket)
        vals, valid, counts = jax.block_until_ready(
            prog(los, his, lims, self._keys, self._cols, self._vals,
                 self._ovals, self._ovalid))
        return (np.asarray(vals)[:b], np.asarray(valid)[:b],
                np.asarray(counts)[:b])

    def serve(self, specs):
        los = np.asarray([s.lo for s in specs], np.int64)
        his = np.asarray([s.hi for s in specs], np.int64)
        lims = np.asarray(
            [self.window if s.limit is None
             else min(s.limit, self.window) for s in specs], np.int64)
        vals, valid, counts = self.run(los, his, lims)
        return [_prefix_payload(self.names, vals[i], valid[i],
                                int(counts[i]))
                for i in range(len(specs))]

    def prewarm_batch(self, batch: int) -> None:
        z = np.zeros(batch, dtype=np.int64)
        self.run(z, z, np.full(batch, self.window, dtype=np.int64))


class ServingVectorRunner:
    """Batched vector top-K: concurrent `ORDER BY vcol <-> $q LIMIT k`
    statements on the same (table, metric, k) coalesce into ONE vmapped
    multi-query distance + top-K dispatch — ops/vector.py's
    ExactSearcher shape reached from the serving queue. Each lane ranks
    ALL table rows by the same float32 distance_fn the per-statement
    VecDistance lowering uses, with the exact-path ordering contract:
    ascending distance, NULL embeddings last (SortKey nulls_first=False)
    ordered among themselves by their decoded raw-slot distance, stable
    ties in pk order. k is static (part of the compatibility key); the
    query vector rides the lane as data."""

    def __init__(self, pks, columns, valids, vecs, vec_valid,
                 metric: str, k: int, table: Optional[str] = None):
        self.k = int(k)
        self.window = self.k  # uniform runner attr (lane output rows)
        self.n = len(pks)
        self.names = tuple(columns)
        self.metric = metric
        self.table = table
        vecs = np.asarray(vecs, np.float32)
        self.dim = int(vecs.shape[1]) if vecs.ndim == 2 else 0
        self.nbytes = int(np.asarray(pks).nbytes + vecs.nbytes
                          + sum(columns[c].nbytes for c in columns))
        self._batched = _BucketPrograms()
        self._compile_mu = threading.Lock()
        if self.n == 0:
            return
        self._cols = jnp.stack([jnp.asarray(np.asarray(columns[c],
                                                       np.int64))
                                for c in self.names])
        self._vals = jnp.stack([jnp.asarray(np.asarray(valids[c],
                                                       bool))
                                for c in self.names])
        self._vecs = jnp.asarray(vecs)
        self._vvalid = jnp.asarray(np.asarray(vec_valid, bool))
        dist = distance_fn(metric)
        n, k_ = self.n, self.k

        def one(q, cols, vals, vecs_a, vvalid):
            d = dist(vecs_a, q)
            kv = _sortable_int(d)
            # the exact-path TopKOp sorts __vdist with
            # nulls_first=False: NULL embeddings last
            null_rank = jnp.where(vvalid, 0, 1)
            perm = jnp.lexsort((kv, null_rank))
            sidx = (perm[:k_] if n >= k_ else jnp.concatenate(
                [perm, jnp.zeros(k_ - n, perm.dtype)]))
            return cols[:, sidx], vals[:, sidx]

        self._fn = jax.vmap(one, in_axes=(0, None, None, None, None))

    def _program(self, bucket: int):
        prog = self._batched.progs.get(bucket)
        if prog is not None:
            return prog
        with self._compile_mu:
            prog = self._batched.progs.get(bucket)
            if prog is not None:
                return prog
            qs = jax.ShapeDtypeStruct((bucket, self.dim), jnp.float32)
            with stats.timed("serving.compile"):
                _tracing.set_tag(bucket=bucket)
                lowered = jax.jit(self._fn).lower(
                    qs, self._cols, self._vals, self._vecs,
                    self._vvalid)
                prog = compile_via_vault(
                    lowered,
                    tables=(self.table,) if self.table else ())
            self._batched.progs[bucket] = prog
            return prog

    def compile_bucket(self, batch: int) -> bool:
        if self.n == 0:
            return False
        self._program(_pow2_at_least(max(int(batch), 1)))
        return True

    def run(self, qs):
        """(m, d) query batch -> (values (m, C, k), valid, counts)."""
        qs = np.asarray(qs, dtype=np.float32)
        b = len(qs)
        if self.n == 0 or b == 0:
            c = len(self.names)
            return (np.zeros((b, c, self.k), np.int64),
                    np.zeros((b, c, self.k), bool),
                    np.zeros(b, np.int32))
        bucket = _pow2_at_least(b)
        if bucket > b:
            qs = np.concatenate(
                [qs, np.zeros((bucket - b, self.dim), np.float32)])
        prog = self._program(bucket)
        vals, valid = jax.block_until_ready(
            prog(qs, self._cols, self._vals, self._vecs, self._vvalid))
        counts = np.full(b, min(self.n, self.k), np.int32)
        return np.asarray(vals)[:b], np.asarray(valid)[:b], counts

    def serve(self, specs):
        qs = np.stack([np.asarray(s.qvec, np.float32) for s in specs])
        vals, valid, counts = self.run(qs)
        return [_prefix_payload(self.names, vals[i], valid[i],
                                int(counts[i]))
                for i in range(len(specs))]

    def prewarm_batch(self, batch: int) -> None:
        self.run(np.zeros((batch, max(self.dim, 1)), np.float32))


def build_serving_batch_runner(catalog, capacity: int, spec):
    """Runner for one serving BatchSpec (sql/serving.py), dispatched on
    its compatibility class. The scan class keeps its resident-table
    fast path (build_serving_runner); the other classes snapshot
    host-side under the table's MVCC-versioned key — device-resident
    tables still accelerate the snapshot itself, because table_chunks
    reads through the resident visibility kernel."""
    kind = getattr(spec, "kind", "scan")
    if kind == "scan":
        return build_serving_runner(catalog, capacity, spec.table,
                                    spec.cols, spec.window)
    if kind == "agg":
        need = tuple(dict.fromkeys(
            c for _f, c in spec.aggs if c is not None))
        pks, columns, valids = _snapshot_columns(catalog, capacity,
                                                 spec.table, need)
        return ServingAggRunner(pks, columns, valids, spec.aggs,
                                spec.names, spec.window,
                                table=spec.table)
    if kind == "topk":
        need = tuple(dict.fromkeys(spec.cols + (spec.order_col,)))
        pks, columns, valids = _snapshot_columns(catalog, capacity,
                                                 spec.table, need)
        return ServingTopKRunner(
            pks, {c: columns[c] for c in spec.cols},
            {c: valids[c] for c in spec.cols},
            columns[spec.order_col], valids[spec.order_col],
            spec.descending, spec.window, table=spec.table)
    if kind == "vector":
        need = tuple(dict.fromkeys(spec.cols + (spec.vcol,)))
        pks, columns, valids = _snapshot_columns(catalog, capacity,
                                                 spec.table, need)
        return ServingVectorRunner(
            pks, {c: columns[c] for c in spec.cols},
            {c: valids[c] for c in spec.cols},
            columns[spec.vcol], valids[spec.vcol], spec.metric,
            spec.limit, table=spec.table)
    raise ValueError(f"unknown serving batch class {kind!r}")
