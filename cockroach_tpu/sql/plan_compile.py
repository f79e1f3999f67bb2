"""Generic plan->jaxpr compilation + measured-cost operator placement.

"Query Processing on Tensor Computation Runtimes" (arXiv:2203.01877)
lowers arbitrary relational plans to tensor programs; this module is
that seam for ANY bound plan tree from sql/plan.py. The actual lowering
rules live where they always have — `build()` maps each plan node onto
an exec/ operator, and the fused tracer (exec/fused.py _Tracer) inlines
every operator's kernels (ops/) into ONE jitted program with
padded/pow2-bucketed intermediate shapes, warm under the plan vault and
the process-wide program cache. LOWERING_RULES below is the explicit
registry of those rules: one entry per plan-node kind naming the
operator it lowers to and the device kernels the fused program
composes. Correlated subqueries reach here already decorrelated into
join+agg (plan.decorrelate, the first normalize() pass).

On top of the lowering sits Tailwind-style (arXiv:2604.28079)
per-operator PLACEMENT (sql/cost.py): every operator is assigned a tier

  fused      inside the single whole-query device program
  streaming  chunked per-operator device kernels (the ladder's rung 2)
  host       the row-at-a-time datum engine / XLA-CPU backend

seeded from MEASURED per-fingerprint device-seconds in sqlstats when
the fingerprint is warm (sql.placement.measured_min_execs), static
cardinality estimates when cold. Decisions are cached per fingerprint
with an anti-thrash clamp (sql.placement.replan_every /
replan_min_execs); insights-flagged degradation marks the cached
placement dirty for an early re-plan.

Mixed tiers: when a host-only operator (RowMapOp's computed strings /
exact decimals) caps an otherwise-fusible subtree, the subtree is
wrapped in CompiledSubtreeOp so everything BELOW the host operator
still executes as one fused device program.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set

from cockroach_tpu.exec.operators import (
    Operator, ScanOp, walk_operators,
)
from cockroach_tpu.sql.cost import (
    HOST_ROWS_PER_S, TPU_ROWS_PER_S, OpCost, QueryPlacement,
    default_placement_cache, measured_route,
)
from cockroach_tpu.sql.plan import (
    Aggregate, Apply, Catalog, Distinct, Filter, IndexScan, Join, Limit,
    OrderBy, Plan, Project, Scan, Shrink, VectorTopK, Window, build,
    estimate_cardinality, normalize, _walk_plan,
)

# plan-node kind -> (display name, exec operator, device kernels the
# fused tracer composes for it). The registry is what EXPLAIN's tier
# rendering and the coverage bench read; build()/_Tracer implement it.
LOWERING_RULES: Dict[type, tuple] = {
    Scan: ("scan", "ScanOp", "packed stacked image + traceable unpack"),
    IndexScan: ("index scan", "ScanOp", "index-bounded chunk stream"),
    Filter: ("filter", "MapOp", "ops/expr.filter_mask"),
    Project: ("project", "MapOp", "ops/expr.eval_expr"),
    Shrink: ("shrink", "ShrinkOp", "compact-to-pow2 gather"),
    Join: ("join", "JoinOp", "ops/join.hash_join (inner/left/right/"
           "full/semi/anti)"),
    Aggregate: ("aggregate", "HashAggOp", "ops/agg: in place over "
                "grouped input, int-key sort, by slot, or hash"),
    Distinct: ("distinct", "DistinctOp", "hash aggregation on keys"),
    OrderBy: ("sort", "SortOp", "ops/sort bitonic/segmented sort"),
    Limit: ("limit", "LimitOp", "top-K when ordered, slice otherwise"),
    Window: ("window", "WindowOp", "ops/window segmented scans over "
             "the partition sort"),
    VectorTopK: ("vector top-k", "TopKOp", "ops/vector distances + "
                 "top-K"),
    Apply: ("apply", "JoinOp", "decorrelated to join+agg before "
            "lowering (plan.decorrelate)"),
}

# family key into sqlstats' per-operator measured device seconds
# (exec/stats.operator_device) for each plan-node kind
_FAMILY = {
    Scan: "scan", IndexScan: "scan", Join: "join", Apply: "join",
    Aggregate: "agg", Distinct: "agg", OrderBy: "sort", Limit: "sort",
    Window: "sort", VectorTopK: "vector", Filter: "fused",
    Project: "fused", Shrink: "fused",
}


@dataclass
class CompiledPlan:
    """compile_plan's output: the wired operator tree, the flow backend
    the placement chose, the per-operator tier table, and (when the
    whole tree fused) the root FusedRunner."""
    op: Operator
    backend: str
    placement: QueryPlacement
    runner: object = None


class CompiledSubtreeOp(Operator):
    """A fused-compiled subtree presented as an ordinary streaming
    operator: the device program below a host-only parent. batches()
    yields the runner's packed single-readback result; FlowRestart from
    a deferred overflow propagates to the outer flow driver, which
    widens and reruns the whole flow — the same contract every operator
    honors."""

    def __init__(self, runner, child: Operator):
        self.runner = runner
        self.child = child
        self.schema = child.schema

    def batches(self):
        yield from self.runner.batches()


def _unwrap(op: Operator) -> Operator:
    # invariant test builds interpose CheckedOp above every operator
    while type(op).__name__ == "CheckedOp":
        op = op.child
    return op


def _est_scan_rows(op: Operator) -> Optional[int]:
    """Sum of planner-stamped scan estimates — EXACTLY the quantity
    flow_backend() routes on, so static placement can never diverge
    from the pre-placement routing behavior."""
    est, known = 0, False
    for sub in walk_operators(op):
        sub = _unwrap(sub)
        if isinstance(sub, ScanOp):
            rows = getattr(sub, "est_rows", None)
            if rows is not None:
                est += rows
                known = True
    return est if known else None


def _stamp_estimate(op: Optional[Operator], rows: float) -> None:
    """Leave the planner's output-row estimate of a plan node on its
    operator as `est_rows`: what the distributed runner sizes a BY_HASH
    join's buckets from (parallel/dist_flow.py: the rows a side sends).
    A ScanOp keeps the table's row count that build() stamped: the
    device-or-host routing (_est_scan_rows, operators.flow_backend) sums
    the scans' figures and reads no other operator's."""
    inner = _unwrap(op) if op is not None else None
    if inner is not None and not isinstance(inner, ScanOp):
        inner.est_rows = int(rows)


def _wrap_mixed(root: Operator):
    """Root didn't fuse: find host-only operators (the row engine's
    RowMapOp) whose child subtree DOES fuse, and wrap that subtree in
    CompiledSubtreeOp — host above, one device program below. Returns
    the set of operator ids now running fused."""
    from cockroach_tpu.exec.fused import try_compile
    from cockroach_tpu.exec.rowexec import RowMapOp

    fused_ids: Set[int] = set()
    candidates = [op for op in walk_operators(root)
                  if isinstance(op, RowMapOp)
                  and not isinstance(op.child, CompiledSubtreeOp)
                  and not isinstance(_unwrap(op.child), ScanOp)]
    for op in candidates:
        r = try_compile(op.child)
        if r is None:
            continue
        for sub in walk_operators(op.child):
            fused_ids.add(id(sub))
        op.child = CompiledSubtreeOp(r, op.child)
    return fused_ids


def _node_tier(node: Plan, op: Optional[Operator], backend: str,
               whole_fused: bool, fused_ids: Set[int]):
    """-> (tier, reason) for one plan node's operator."""
    if backend == "cpu":
        return "host", "flow routed to the host backend"
    inner = _unwrap(op) if op is not None else None
    if inner is not None and type(inner).__name__ == "RowMapOp":
        return "host", "row-engine projection (computed strings / " \
                       "exact decimal semantics)"
    if inner is not None and type(inner).__name__ == "VectorANNOp":
        return "streaming", "IVF index probe runs as its own dispatch"
    if whole_fused:
        return "fused", "inside the single whole-query device program"
    if op is not None and id(op) in fused_ids:
        return "fused", "fused device subtree under a host operator"
    return "streaming", "outside the fusion grammar here: chunked " \
                        "device kernels"


def compile_plan(p: Plan, catalog: Catalog, capacity: int = 1 << 17,
                 sql: Optional[str] = None, setting: str = "auto",
                 record: bool = True,
                 _normalized: bool = False) -> CompiledPlan:
    """Compile ANY bound plan tree: normalize (incl. decorrelation),
    build the operator tree, run the placement pass, and attach the
    fused whole-query program when the tree admits one.

    `sql` keys the per-fingerprint placement cache; without it every
    call plans statically. `record=False` is the EXPLAIN read: no
    execution is counted against the re-plan clamp and nothing is
    stored."""
    from cockroach_tpu.exec.fused import try_compile
    from cockroach_tpu.sql.sqlstats import default_sqlstats, fingerprint

    norm = p if _normalized else normalize(p, catalog)
    node_map: Dict[int, Operator] = {}
    op = build(norm, catalog, capacity, _normalized=True,
               node_map=node_map)
    nodes = list(_walk_plan(norm))

    fp = fingerprint(sql) if sql else ""
    cache = default_placement_cache()
    cached: Optional[QueryPlacement] = None
    # a forced side (SET vectorize = tpu|cpu) neither reads nor seeds
    # the per-fingerprint cache: that holds the coster's own decisions
    routed = setting not in ("tpu", "cpu")
    if fp and routed:
        if not record:
            cached = cache.peek(fp)
        elif not cache.should_replan(fp):
            cached = cache.get(fp)
        if cached is not None and len(cached.ops) != len(nodes):
            cached = None  # plan shape changed under this fingerprint

    est = _est_scan_rows(op)
    stats_snap = None
    if cached is not None:
        backend, source = cached.backend, cached.source
        device_s, host_s = cached.est_device_s, cached.est_host_s
    else:
        stats_snap = default_sqlstats().get(fp) if fp else None
        backend, source, device_s, host_s = measured_route(
            est or 0, stats_snap, setting)

    # structural pass: does the whole tree fuse; if not, which subtrees
    runner = None
    fused_ids: Set[int] = set()
    whole_fused = False
    if backend != "cpu":
        runner = getattr(op, "_fused_runner", None) or try_compile(op)
        if runner is not None:
            op._fused_runner = runner
            whole_fused = True
        else:
            fused_ids = _wrap_mixed(op)

    placement = QueryPlacement(
        backend=backend, source=source, fingerprint=fp,
        est_scan_rows=est or 0, est_device_s=device_s,
        est_host_s=host_s)
    measured_ops = (stats_snap or {}).get("op_device") or {}
    execs = max((stats_snap or {}).get("count", 0), 1)
    for node in nodes:
        name, _opname, _kern = LOWERING_RULES.get(
            type(node), (type(node).__name__.lower(), "", ""))
        nop = node_map.get(id(node))
        tier, reason = _node_tier(node, nop, backend, whole_fused,
                                  fused_ids)
        try:
            rows = estimate_cardinality(node, catalog)
        except Exception:
            rows = 0.0
        else:
            _stamp_estimate(nop, rows)
        oc = OpCost(name=name, detail=_describe(node),
                    est_rows=rows,
                    device_s=rows / TPU_ROWS_PER_S,
                    host_s=rows / HOST_ROWS_PER_S,
                    tier=tier, source="static", reason=reason)
        fam = _FAMILY.get(type(node))
        if fam in measured_ops:
            # sqlstats accumulated this family's execution seconds for
            # this fingerprint: seed the operator's device cost with the
            # measured per-execution mean
            oc.device_s = measured_ops[fam] / execs
            oc.source = "measured"
        placement.ops.append(oc)

    if fp and routed and record and cached is None:
        cache.store(fp, placement)
    return CompiledPlan(op=op, backend=backend, placement=placement,
                        runner=runner)


def _describe(node: Plan) -> str:
    if isinstance(node, (Scan, IndexScan)):
        return node.table
    if isinstance(node, Join):
        return node.how + " " + ",".join(node.left_on) + (
            " +residual" if node.residual is not None else "")
    if isinstance(node, Aggregate):
        return ",".join(node.group_by) if node.group_by else "scalar"
    if isinstance(node, (OrderBy,)):
        return ",".join(k.col for k in node.keys)
    if isinstance(node, Window):
        return ",".join(s.func for s in node.specs)
    if isinstance(node, Project):
        return f"{len(node.outputs)} cols"
    return ""


def mark_degraded(fp: str) -> None:
    """Insights hook: flag a fingerprint's cached placement for an early
    (clamped) re-plan."""
    default_placement_cache().mark_degraded(fp)
