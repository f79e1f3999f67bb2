"""Diagnostics bundles: `debug zip` + per-statement bundles.

Reference: pkg/cli/zip — `cockroach debug zip` walks every node's
status APIs and packs vars, in-flight traces, jobs, hot ranges,
settings, and recent logs into one archive a support engineer can read
offline; and sql/instrumentation.go's EXPLAIN ANALYZE (DEBUG), which
writes a per-statement bundle (plan, trace, environment).

Two collection modes, mirroring the reference's in-process vs RPC
split:

- `write_debug_zip` reads THROUGH the in-process status plane
  (server/nodestatus.py): every gossiped NodeStatus becomes a
  `debug/nodes/<id>/` section, and the collecting node contributes its
  full local registries (Prometheus vars, insights, jobs, TSDB dump,
  recent logs) — the parts gossip deliberately compacts away.
- `collect_http` scrapes a live StatusServer's endpoints over HTTP,
  for an operator pointing the CLI at a running node.
"""

from __future__ import annotations

import json
import time
import zipfile
from typing import Optional

_metrics_cache = None


def _metrics():
    global _metrics_cache
    if _metrics_cache is None:
        from cockroach_tpu.util.metric import default_registry

        reg = default_registry()
        _metrics_cache = {
            "zips": reg.counter(
                "debug_zip_writes_total",
                "debug-zip archives written"),
            "bundles": reg.counter(
                "stmt_bundles_written_total",
                "EXPLAIN ANALYZE (DEBUG) statement bundles written"),
        }
    return _metrics_cache


def _write_json(zf: zipfile.ZipFile, name: str, payload) -> None:
    zf.writestr(name, json.dumps(payload, sort_keys=True, indent=1,
                                 default=str))


def _settings_dump() -> dict:
    """Registered cluster settings with live values, plus whatever the
    gossiped `setting:` namespace carries (the propagated overrides)."""
    from cockroach_tpu.util.settings import Settings

    live = Settings()
    out = {}
    for name, s in sorted(Settings.all().items()):
        try:
            value = live.get(name)
        except Exception:
            value = s.default
        out[name] = {"value": value, "default": s.default,
                     "description": s.description}
    return out


def _tsdb_dump(tsdb) -> dict:
    """Every series the TSDB knows, downsampled at storage resolution."""
    out = {}
    for name in sorted(tsdb._names.values()):
        pts = tsdb.query(name, 0, 1 << 62)
        out[name] = [{"start_ns": b, "avg": avg, "min": mn, "max": mx}
                     for b, avg, mn, mx in pts]
    return out


def write_debug_zip(out_path: str, plane=None, cluster=None, tsdb=None,
                    jobs_registry=None, matviews=None) -> str:
    """Pack cluster-wide diagnostics into `out_path`.

    Layout (the reference's debug-zip tree, flattened to what this
    rebuild records):

        debug/cluster/nodes.json        per-node liveness + digest
        debug/cluster/hot_ranges.json   load-ranked replica rows
        debug/cluster/settings.json     registered settings + values
        debug/nodes/<id>/status.json    the node's gossiped NodeStatus
        debug/nodes/<id>/queries.json   ...and its per-field sections
        debug/nodes/<id>/traces.json    (sessions, hot_ranges,
        debug/nodes/<id>/insights.json   insights, jobs likewise)
        debug/nodes/<id>/vars.txt       gossiped metrics snapshot
        debug/nodes/<id>/vars_full.txt  collector only: live Prometheus
        debug/nodes/<id>/ts.json        collector only (TSDB attached)
        debug/nodes/<id>/logs.json      collector only: recent-log ring
    """
    from cockroach_tpu.server.nodestatus import default_status_node
    from cockroach_tpu.util.log import get_logger
    from cockroach_tpu.util.metric import default_registry

    plane = plane or default_status_node()
    if plane is not None and cluster is None:
        cluster = plane.cluster
    statuses = plane.statuses() if plane is not None else {}
    local_id = plane.node_id if plane is not None else 0
    if not statuses:
        # no plane installed: a single-node process still produces a
        # useful bundle from its local registries
        statuses = {local_id: {"node_id": local_id, "metrics": {}}}
    with zipfile.ZipFile(out_path, "w",
                         compression=zipfile.ZIP_DEFLATED) as zf:
        _write_json(zf, "debug/cluster/collected.json", {
            "collected_at": round(time.time(), 3),
            "collector_node_id": local_id,
            "nodes": sorted(statuses),
        })
        if plane is not None:
            _write_json(zf, "debug/cluster/nodes.json",
                        plane.nodes_report())
        if cluster is not None:
            _write_json(zf, "debug/cluster/hot_ranges.json",
                        cluster.hot_ranges())
        _write_json(zf, "debug/cluster/settings.json", _settings_dump())
        for nid in sorted(statuses):
            st = statuses[nid]
            base = f"debug/nodes/{nid}/"
            _write_json(zf, base + "status.json", st)
            for field in ("queries", "sessions", "traces",
                          "hot_ranges"):
                _write_json(zf, base + field + ".json",
                            st.get(field, []))
            if nid != local_id:
                # remote nodes: the gossiped digests; the collector
                # writes its full local versions below instead
                _write_json(zf, base + "insights.json",
                            st.get("insights", []))
                _write_json(zf, base + "jobs.json", st.get("jobs", []))
            # the gossiped metrics snapshot, rendered scrape-style so
            # the same grep works on every node's section
            zf.writestr(base + "vars.txt", "".join(
                f"{k} {v}\n"
                for k, v in sorted(st.get("metrics", {}).items())))
        # collecting node: full local registries (what gossip compacts)
        base = f"debug/nodes/{local_id}/"
        zf.writestr(base + "vars_full.txt",
                    default_registry().export_prometheus())
        from cockroach_tpu.sql.insights import default_insights

        _write_json(zf, base + "insights.json",
                    [dict(r) for r in default_insights().insights()])
        if jobs_registry is None and plane is not None:
            jobs_registry = plane.jobs
        _write_json(zf, base + "jobs.json", [] if jobs_registry is None
                    else [
            {"id": rec.id, "kind": rec.kind, "state": rec.state,
             "progress": rec.progress, "error": rec.error}
            for rec in jobs_registry.list_jobs()])
        if matviews is not None:
            _write_json(zf, base + "matviews.json", matviews.report())
        if tsdb is not None:
            _write_json(zf, base + "ts.json", _tsdb_dump(tsdb))
        _write_json(zf, base + "logs.json", get_logger().recent())
    _metrics()["zips"].inc()
    return out_path


# HTTP endpoints collect_http scrapes from a live StatusServer, mapped
# to their archive entry (the CLI's remote mode)
HTTP_SECTIONS = [
    ("/health", "debug/health.json"),
    ("/_status/vars", "debug/vars.txt"),
    ("/_status/nodes", "debug/nodes.json"),
    ("/_status/hotranges", "debug/hot_ranges.json"),
    ("/_status/statements", "debug/statements.json"),
    ("/_status/traces", "debug/traces.json"),
    ("/_status/queries", "debug/queries.json"),
    ("/_status/insights", "debug/insights.json"),
    ("/_status/jobs", "debug/jobs.json"),
]


def collect_http(base_url: str, out_path: str) -> str:
    """Scrape a running StatusServer into a debug zip. Endpoints a
    given deployment lacks (404: no TSDB, no cluster) are skipped, not
    fatal — a partial bundle beats none (the reference's zip does the
    same per-node best-effort collection)."""
    from urllib.error import URLError
    from urllib.request import urlopen

    base = base_url.rstrip("/")
    with zipfile.ZipFile(out_path, "w",
                         compression=zipfile.ZIP_DEFLATED) as zf:
        collected = []
        for path, entry in HTTP_SECTIONS:
            try:
                with urlopen(base + path, timeout=10) as resp:
                    zf.writestr(entry, resp.read())
                collected.append(path)
            except (URLError, OSError):
                continue
        _write_json(zf, "debug/collected.json", {
            "collected_at": round(time.time(), 3),
            "base_url": base, "sections": collected})
    _metrics()["zips"].inc()
    return out_path


def write_statement_bundle(out_path: str, sql: str, plan_lines,
                           span=None, operators=None,
                           digest: Optional[dict] = None,
                           device: Optional[dict] = None) -> str:
    """EXPLAIN ANALYZE (DEBUG)'s per-statement bundle: the plan, the
    full span tree (structured + rendered), the host stage seconds by
    family, the resilience digest and, asked with (DEBUG, DEVICE), the
    device time by plan operator (exec/device_profile.as_dict)."""
    with zipfile.ZipFile(out_path, "w",
                         compression=zipfile.ZIP_DEFLATED) as zf:
        zf.writestr("stmt.sql", sql + "\n")
        zf.writestr("plan.txt", "\n".join(plan_lines) + "\n")
        if span is not None:
            _write_json(zf, "trace.json", span.as_dict())
            zf.writestr("trace.txt", span.render() + "\n")
        if operators is not None:
            _write_json(zf, "operators.json", operators)
        if digest is not None:
            _write_json(zf, "digest.json", digest)
        if device is not None:
            _write_json(zf, "device_profile.json", device)
    _metrics()["bundles"].inc()
    return out_path
