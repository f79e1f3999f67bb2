"""EXPLAIN / EXPLAIN ANALYZE + the statement executor entry point.

Reference: sql/instrumentation.go:72 (EXPLAIN ANALYZE assembly from
ComponentStats trailing metadata), opt/exec/explain. `execute`
is the conn_executor dispatch seam: one call takes SQL text and returns
either result columns or an explain rendering.
"""

from __future__ import annotations

import time
from typing import Dict, List, Tuple

from cockroach_tpu.sql import parser as P
from cockroach_tpu.sql.bind import Binder
from cockroach_tpu.sql.plan import (
    Aggregate, Catalog, Distinct, Filter, IndexScan, Join, Limit,
    OrderBy, Plan, Project, Scan, VectorTopK, Window, join_keeps,
    normalize,
)


def render_plan(p: Plan, catalog: Catalog) -> List[str]:
    """Normalized logical plan -> indented tree lines (EXPLAIN), with
    estimated row counts from ANALYZE stats where available (the
    coster's cardinalities, opt/xform/coster.go)."""
    lines: List[str] = []

    def _est(scan_node, predicate) -> str:
        from cockroach_tpu.sql.stats import estimate_rows

        try:
            stats = catalog.table_stats(scan_node.table)
            base = catalog.table_rows(scan_node.table)
        except Exception:
            return ""
        if stats is None and predicate is None:
            return ""
        filters = [predicate] if predicate is not None else []
        est = estimate_rows(stats, base, filters)
        return f" (~{int(est)} rows)"

    def describe(node: Plan) -> str:
        if isinstance(node, IndexScan):
            return (f"index scan {node.table}@{node.column} "
                    f"[{node.lo}, {node.hi}]{_est(node, None)}")
        if isinstance(node, Scan):
            cols = f" columns=({', '.join(node.columns)})" \
                if node.columns else ""
            return f"scan {node.table}{cols}{_est(node, None)}"
        if isinstance(node, Filter):
            inner = node.input
            if isinstance(inner, (Scan, IndexScan)):
                return f"filter {node.predicate!r}" \
                    + _est(inner, node.predicate)
            return f"filter {node.predicate!r}"
        if isinstance(node, Project):
            return f"project {', '.join(n for n, _ in node.outputs)}"
        if isinstance(node, Join):
            keys = ", ".join(f"{a}={b}"
                             for a, b in zip(node.left_on, node.right_on))
            # the share of its probe's rows the join keeps, by estimate:
            # what the join orderer ranked its build by (sql/bind.py) and
            # what sizes a Shrink above it
            keeps = join_keeps(node, catalog)
            # a decorrelated EXISTS / NOT EXISTS keeps what its subquery
            # compared beside the equality (sql/plan.Join.residual)
            residual = ("" if node.residual is None
                        else f" residual {node.residual!r}")
            return f"{node.how} join on {keys}{residual}" + (
                "" if keeps is None else f" (keeps ~{100 * keeps:.1f}%)")
        if isinstance(node, Aggregate):
            aggs = ", ".join(f"{a.func}({a.col or '*'}) as {a.out}"
                             for a in node.aggs)
            gb = (f" group by {', '.join(node.group_by)}"
                  if node.group_by else "")
            return f"aggregate {aggs}{gb}"
        if isinstance(node, OrderBy):
            keys = ", ".join(k.col + (" desc" if k.descending else "")
                             for k in node.keys)
            return f"sort {keys}"
        if isinstance(node, Limit):
            off = f" offset {node.offset}" if node.offset else ""
            return f"limit {node.n}{off}"
        if isinstance(node, Distinct):
            return "distinct" + (f" on ({', '.join(node.keys)})"
                                 if node.keys else "")
        if isinstance(node, VectorTopK):
            metric = {"l2": "<->", "cos": "<=>"}.get(node.metric,
                                                     node.metric)
            mode = (f"ann nprobe={node.nprobe}" if node.ann
                    else "exact")
            return (f"vector top-k [{mode}] {node.column} {metric} "
                    f"[{len(node.query)}-dim] k={node.k}")
        if isinstance(node, Window):
            fns = ", ".join(f"{s.func}({s.col or ''}) as {s.out}"
                            for s in node.specs)
            pb = (f" partition by {', '.join(node.partition_by)}"
                  if node.partition_by else "")
            ob = (" order by " + ", ".join(
                k.col + (" desc" if k.descending else "")
                for k in node.order_by) if node.order_by else "")
            return f"window {fns}{pb}{ob}"
        return type(node).__name__.lower()

    def walk(node: Plan, depth: int):
        lines.append("  " * depth + "-> " + describe(node)
                     if depth else describe(node))
        for k in node.inputs():
            walk(k, depth + 1)

    walk(p, 0)
    return lines


def _ranged_key_lines(op) -> List[str]:
    """One line an aggregate that lowers by slot BECAUSE the statistics
    bound a key (HashAggOp.key_domains): the slots, and each range it
    stands on (stale the moment a row is written outside it; the flow
    then restarts on the hash aggregate)."""
    import math

    from cockroach_tpu.exec.operators import HashAggOp, walk_operators

    return [
        f"aggregate by slot: group by {', '.join(agg.group_by)} in "
        f"{math.prod(agg._dense_sizes)} slots ("
        + ", ".join(f"{k} in [{lo}, {hi}]"
                    for k, (lo, hi) in agg.key_domains.items())
        + " by the statistics)"
        for agg in walk_operators(op)
        if isinstance(agg, HashAggOp) and agg.key_domains]


def _distribution_lines(op, mesh, catalog: Catalog) -> List[str]:
    """How the distributed runner would place this tree on `mesh`
    (DistFusedRunner.describe), from the catalog's row counts: no scan
    is walked and nothing moves for an EXPLAIN."""
    from cockroach_tpu.exec.operators import ScanOp, walk_operators
    from cockroach_tpu.parallel.dist_flow import DistFusedRunner

    chunks = {}
    for sc in walk_operators(op):
        if isinstance(sc, ScanOp):
            # an index feed names no table: one chunk
            rows = int(catalog.table_rows(sc.table)) if sc.table else 0
            chunks[id(sc)] = max(1, -(-rows // sc.capacity))
    return DistFusedRunner(op, mesh).describe(chunks)


def _device_lines(op, tier, lines: List[str]):
    """EXPLAIN ANALYZE (DEVICE): profile the whole-query runner that just
    served the statement (exec/device_profile.py) and append device time
    by plan operator to `lines`; -> the profile as a dict, or None where
    no such runner served it (the lines then say so, and no table)."""
    from cockroach_tpu.exec import device_profile

    runner = (device_profile.served_runner(op)
              if op is not None and tier in ("fused", "dist") else None)
    prof = runner.device_profile() if runner is not None else None
    lines.append("")
    if prof is None:
        # a runner that is there and has no program handed the statement
        # to the streaming runtime (fused.fallback_*)
        lines.append(f"device time by operator: no whole-query device "
                     f"program served this statement (tier="
                     f"{tier or 'n/a'}"
                     + (", which handed it to the streaming runtime)"
                        if tier in ("fused", "dist") else ")"))
        return None
    lines.extend(device_profile.render(op, prof))
    return device_profile.as_dict(op, prof)


def execute(sql: str, catalog: Catalog, capacity: int = 1 << 17,
            mesh=None) -> Tuple[str, object]:
    """-> ("rows", columns-dict) | ("explain", [lines]).

    EXPLAIN renders the normalized plan; EXPLAIN ANALYZE also runs the
    query with the stats collector + a trace span and appends the
    per-stage attribution (the ComponentStats -> EXPLAIN ANALYZE path).
    """
    kind, payload, _schema = execute_with_plan(sql, catalog, capacity,
                                               mesh)
    return kind, payload


def execute_with_plan(sql: str, catalog: Catalog, capacity: int = 1 << 17,
                      mesh=None, ast=None,
                      op_sink=None,
                      setting: str = "auto",
                      strict: bool = False,
                      params=None) -> Tuple[str, object, object]:
    """-> (kind, payload, output Schema or None) — the schema is the
    built operator tree's own, for exact result decoding. Pass `ast` to
    skip re-parsing (Session already parsed for dispatch). `op_sink` (a
    list) receives {"plan": bound plan, "op": built operator tree} for
    non-EXPLAIN statements — Session's prepared-statement cache. With a
    `mesh` the statement runs distributed (sql/plan.run) and EXPLAIN
    shows the distribution; `strict` is `distsql = always`. `params`
    (sql/params.BoundParams) are the values of the statement's `$n`
    placeholders: the plan is made at this binding and serves all, its
    programs take the values as arguments, and the sink also receives
    the statement's `slots`."""
    from cockroach_tpu.exec import stats
    from cockroach_tpu.ops.expr import bound_args
    from cockroach_tpu.sql import params as _params
    from cockroach_tpu.sql.plan import run
    from cockroach_tpu.util.tracing import tracer

    if ast is None:
        ast = P.parse(sql)
    is_explain = isinstance(ast, P.ExplainStmt)
    analyze = ast.analyze if is_explain else False
    stmt = ast.stmt if is_explain else ast
    if "crdb_internal." in sql:
        # virtual-schema statements bind and run against a per-statement
        # VirtualCatalog wrapper: crdb_internal.* names materialize from
        # the live registries, everything else delegates (sql/vtable.py)
        from cockroach_tpu.sql.vtable import VirtualCatalog

        catalog = VirtualCatalog(catalog)
    from cockroach_tpu.server.registry import default_query_registry

    qreg = default_query_registry()
    qreg.set_phase_current("compiling")
    with stats.timed("sql.bind"):
        binder = Binder(catalog,
                        params=None if params is None else params.values)
        plan = binder.bind(stmt)
        slots = binder.param_slots
        args = (None if params is None
                else _params.evaluate(slots, params.values))
    if not is_explain:
        qreg.set_phase_current("executing")
        sink = [] if op_sink is not None else None
        with bound_args(args):
            result, schema = run(plan, catalog, capacity, mesh=mesh,
                                 with_schema=True, op_sink=sink, sql=sql,
                                 setting=setting, strict=strict)
        if op_sink is not None:
            op_sink.append({"plan": plan,
                            "op": sink[0] if sink else None,
                            "slots": slots})
        return "rows", result, schema

    norm = normalize(plan, catalog)
    lines = render_plan(norm, catalog)
    # operator placement (sql/plan_compile.py): annotate every plan line
    # with its tier and the cost inputs that chose it — render_plan and
    # the placement pass walk the SAME pre-order, so lines and OpCosts
    # zip 1:1. record=False: an EXPLAIN read must not count against the
    # re-plan clamp.
    from cockroach_tpu.sql.cost import crossover_rows, est_tpu_seconds
    from cockroach_tpu.sql.plan_compile import compile_plan

    placement = explained = None
    try:
        explained = compile_plan(norm, catalog, capacity, sql=sql,
                                 setting=setting, record=False,
                                 _normalized=True)
        placement = explained.placement
    except Exception:
        pass  # placement is advisory; EXPLAIN still renders the plan
    if placement is not None:
        for i, oc in enumerate(placement.ops[:len(lines)]):
            lines[i] += (f"  [tier={oc.tier} est={int(oc.est_rows)} rows"
                         f" device={oc.device_s * 1e3:.1f}ms"
                         f" host={oc.host_s * 1e3:.1f}ms"
                         f" src={oc.source}]")
        lines.append(
            f"engine: {placement.backend} ({placement.source}; est "
            f"{placement.est_scan_rows} scan rows, device "
            f"{placement.est_device_s * 1e3:.0f}ms vs host "
            f"{placement.est_host_s * 1e3:.0f}ms, crossover "
            f"~{crossover_rows()} rows; tpu dispatch floor "
            f"{1000 * est_tpu_seconds(0):.0f}ms)")
    else:
        # placement unavailable (e.g. a catalog that cannot build):
        # fall back to the whole-flow static routing line
        from cockroach_tpu.sql.cost import est_host_seconds
        from cockroach_tpu.sql.plan import Scan as _Scan, _walk_plan

        est = sum(catalog.table_rows(s.table)
                  for s in _walk_plan(norm) if isinstance(s, _Scan))
        engine = ("cpu" if est_host_seconds(est) < est_tpu_seconds(est)
                  else "tpu")
        lines.append(f"engine: {engine} (est {est} scan rows, "
                     f"crossover ~{crossover_rows()} rows; tpu dispatch "
                     f"floor {1000 * est_tpu_seconds(0):.0f}ms)")
    if explained is not None:
        lines.extend(_ranged_key_lines(explained.op))
    if mesh is not None and explained is not None:
        lines.extend(_distribution_lines(explained.op, mesh, catalog))
    if slots:
        # one plan and one program for every binding: each slot is an
        # argument of the program; the estimates above were taken at the
        # binding this EXPLAIN was sent with
        lines.append("parameters: "
                     + ", ".join(s.describe() for s in slots))
        lines.append("estimates taken at: "
                     + _params.describe_binding(params.values, slots))
    if analyze:
        from cockroach_tpu.util.tracing import summarize

        st = stats.enable()
        try:
            # the statement was bound before this table began: what its
            # join orderer counted goes on the page with the order
            for steps in binder.join_ranks:
                st.add("sql.join_rank", rows=steps)
            with tracer().span("query", sql=sql[:60]) as sp:
                t0 = time.perf_counter()
                built: List[object] = []
                with bound_args(args):
                    res = run(norm, catalog, capacity, mesh=mesh, sql=sql,
                              strict=strict, op_sink=built)
                elapsed = time.perf_counter() - t0
            n = len(next(iter(res.values()))) if res else 0
            lines.append("")
            lines.append(f"execution: {elapsed * 1e3:.1f}ms, "
                         f"{n} result rows")
            rep = st.report()
            if rep:
                lines.extend(rep.splitlines())
            # the HOST stage timers grouped by the prefix of their name
            # (exec/stats.operator_breakdown), annotated with each
            # family's placement tier: a whole-query program is ONE row
            # here (`fused` or `dist`), and EXPLAIN ANALYZE (DEVICE)
            # splits it by plan operator. Host-tier operators get an
            # EXPLICIT tier=host row — the row engine spends no device
            # time, and a 0/missing device-ms line misreads as "free"
            # rather than "placed on the host".
            ops = stats.operator_breakdown(st)
            fam_tier: Dict[str, str] = {}
            host_ops: List[object] = []
            if placement is not None:
                from cockroach_tpu.sql.plan import _walk_plan as _wp
                from cockroach_tpu.sql.plan_compile import _FAMILY

                rank = {"fused": 0, "streaming": 1, "host": 2}
                for node, oc in zip(_wp(norm), placement.ops):
                    fam = ("host" if oc.tier == "host"
                           else _FAMILY.get(type(node), "fused"))
                    if rank[oc.tier] > rank.get(
                            fam_tier.get(fam, ""), -1):
                        fam_tier[fam] = oc.tier
                    if oc.tier == "host":
                        host_ops.append(oc)
            if ops or host_ops:
                lines.append("")
                lines.append("operators:")
            seen_host_fam = False
            for o in ops:
                tier = fam_tier.get(o["operator"])
                if tier == "host" or o["operator"] == "host":
                    # host family: the time is host milliseconds by
                    # construction — label it as such
                    seen_host_fam = True
                    row = (f"  {o['operator']:<12}"
                           f" {o['device_ms'] + o['other_ms']:9.1f}"
                           f" host-ms")
                else:
                    row = (f"  {o['operator']:<12}"
                           f" {o['device_ms']:9.1f} device-ms")
                    if o["other_ms"]:
                        row += f" (+{o['other_ms']:.1f} compile-ms)"
                if o["rows"]:
                    row += f" {o['rows']:12d} rows"
                if o["bytes"]:
                    row += f" {o['bytes'] / 1e6:9.1f} MB"
                if tier is not None:
                    row += f"  tier={tier}"
                lines.append(row)
                if f"{o['operator']}.exec" in st.stages and \
                        o["operator"] in ("fused", "dist"):
                    lines.append("    one device program: EXPLAIN ANALYZE "
                                 "(DEVICE) splits it by operator")
            if host_ops and not seen_host_fam:
                # nothing in the stage table covered the host work (the
                # row engine records under the "host" family only while
                # it runs): still attribute it explicitly
                for oc in {(oc.name, oc.reason): oc
                           for oc in host_ops}.values():
                    lines.append(f"  {oc.name:<12}       0.0 host-ms"
                                 f"  tier=host ({oc.reason})")
            lines.append("")
            lines.extend(sp.render().splitlines())
            # resilience digest: what the span tree says happened to the
            # query on its way down the ladder (one line, greppable)
            summ = summarize(sp)
            lines.append("")
            lines.append(
                f"resilience: tier={summ['tier'] or 'n/a'} "
                f"retries={summ['retries']} "
                f"degradations={summ['degradations']} "
                f"restarts={summ['restarts']}")
            device = None
            if ast.device:
                device = _device_lines(built[0] if built else None,
                                       summ["tier"], lines)
            if getattr(ast, "debug", False):
                # EXPLAIN ANALYZE (DEBUG): persist the statement bundle
                # (plan + span tree + operator times + digest) and tell
                # the operator where it landed, like the reference's
                # "Statement diagnostics bundle generated" line
                import os
                import tempfile

                from cockroach_tpu.server.debugzip import (
                    write_statement_bundle,
                )

                path = os.path.join(
                    tempfile.gettempdir(),
                    f"stmt-bundle-{sp.trace_id:x}.zip")
                write_statement_bundle(path, sql, lines, span=sp,
                                       operators=ops, digest=summ,
                                       device=device)
                lines.append("")
                lines.append(f"statement bundle: {path}")
        finally:
            stats.disable()
    return "explain", lines, None
