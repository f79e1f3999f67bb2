"""Per-stage execution statistics — the ComponentStats analog.

Reference: every vectorized operator is wrapped by a
vectorizedStatsCollector (pkg/sql/colflow/stats.go:239) emitting
ComponentStats protos (execinfrapb/component_stats.proto:64) that flow
back as trailing metadata and render in EXPLAIN ANALYZE
(sql/instrumentation.go:72).

What this collector records is HOST stage seconds by name: pack time,
transfer dispatch time, program dispatch and wait, forced syncs
(readbacks), and row/byte counts. A whole-query program (exec/fused.py,
parallel/dist_flow.py) is one dispatch and one wait here, whatever its
plan; its device time by plan operator comes from inside the program:
every operator's lowering carries a `crdb.op<N>.<Kind>` scope, and
exec/device_profile.py reads a profile of the program's own executions by
those scopes (EXPLAIN ANALYZE (DEVICE)).

`timed(name)` is the one way to open a stage, and a stage is a span is
an annotation: besides feeding the collections it attaches a child span
to the thread's active trace (util/tracing.py) and opens the program's
profiler annotation of that name, so the timeline EXPLAIN ANALYZE and
/_status/traces render, the stage table, and a profile of the node all
come from one call site.

Zero overhead when disabled (module flag checked per call site).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass
from typing import Dict, Optional

from cockroach_tpu.util import tracing as _tracing

_tracer = _tracing.tracer()


@dataclass
class ComponentStats:
    """One stage's counters (component_stats.proto:64 analog)."""

    name: str
    events: int = 0
    seconds: float = 0.0
    rows: int = 0
    bytes: int = 0

    def line(self) -> str:
        parts = [f"{self.name:<28} {self.seconds * 1000:9.1f} ms"
                 f" {self.events:6d} ev"]
        if self.rows:
            parts.append(f"{self.rows:12d} rows")
        if self.bytes:
            parts.append(f"{self.bytes / 1e6:9.1f} MB")
        return "  ".join(parts)


class StatsCollection:
    """Thread-safe per-flow stats registry (prefetch threads report in)."""

    def __init__(self):
        self._mu = threading.Lock()
        self.stages: Dict[str, ComponentStats] = {}

    def stage(self, name: str) -> ComponentStats:
        with self._mu:
            s = self.stages.get(name)
            if s is None:
                s = self.stages[name] = ComponentStats(name)
            return s

    def add(self, name: str, seconds: float = 0.0, rows: int = 0,
            bytes: int = 0, events: int = 1) -> None:
        with self._mu:  # stage(), inlined: one lock an event
            s = self.stages.get(name)
            if s is None:
                s = self.stages[name] = ComponentStats(name)
            s.events += events
            s.seconds += seconds
            s.rows += rows
            s.bytes += bytes

    def report(self) -> str:
        with self._mu:
            stages = sorted(self.stages.values(),
                            key=lambda s: -s.seconds)
        return "\n".join(s.line() for s in stages)

    def as_dict(self) -> Dict[str, Dict[str, float]]:
        """JSON-ready stage table (chip_smoke.py and the scripts/check_*
        gates print it)."""
        with self._mu:
            return {
                s.name: {"seconds": round(s.seconds, 4),
                         "events": s.events, "rows": s.rows,
                         "bytes": s.bytes}
                for s in sorted(self.stages.values(),
                                key=lambda s: -s.seconds)
            }


# The one counter for "a query the fused tier could express ran on the
# streaming runtime because its data or program did not fit device
# memory" (exec/fused.py HBMExceeded, and a device OOM while executing):
# the right executor for that volume, but never a silent one.
STREAM_HBM = "fused.stream_hbm"

# module-level switch: None = disabled (the common, zero-overhead case)
_active: Optional[StatsCollection] = None

# per-query overlay: a thread-local collection installed by the session
# for the duration of one statement (query_stats below). The module-level
# _active stays the EXPLAIN ANALYZE / bench switch — visible to prefetch
# threads — while the overlay gives every statement its own attribution
# without turning the global on. Producer threads (scan prefetch) carry
# no overlay, so streaming-tier pack/transfer time attributes to the
# global collection only; the driving thread's dispatch/readback stages
# are what the per-query breakdown covers.
_tls = threading.local()


def enable() -> StatsCollection:
    """Start collecting into a fresh collection (EXPLAIN ANALYZE mode)."""
    global _active
    _active = StatsCollection()
    return _active


def disable() -> None:
    global _active
    _active = None


def active() -> Optional[StatsCollection]:
    return _active


@contextmanager
def query_stats():
    """Install a fresh per-query StatsCollection on this thread for the
    statement's duration; yields the collection (read it AFTER the body
    for the statement's operator breakdown). Nests, restoring the outer
    overlay."""
    col = StatsCollection()
    prev = getattr(_tls, "col", None)
    _tls.col = col
    try:
        yield col
    finally:
        _tls.col = prev


def add(name: str, **kw) -> None:
    a = _active
    if a is not None:
        a.add(name, **kw)
    q = getattr(_tls, "col", None)
    if q is not None and q is not a:
        q.add(name, **kw)


def stage_seconds(col: Optional[StatsCollection], name: str) -> float:
    """Seconds `col` has under stage `name` (0.0 when it has none)."""
    s = col.stages.get(name) if col is not None else None
    return s.seconds if s is not None else 0.0


class _Stage:
    """One open stage (what `timed` returns when anything listens):
    the profiler annotation, the child span when the thread has an
    active trace, one clock read on either side, and on exit one add()
    to each collection, also when the body raised."""

    __slots__ = ("name", "rows", "bytes", "a", "q", "traced", "span",
                 "ann", "t0")

    def __init__(self, name, rows, bytes, a, q, traced):
        self.name = name
        self.rows = rows
        self.bytes = bytes
        self.a = a
        self.q = q
        self.traced = traced

    def __enter__(self):
        self.ann = _tracing.annotation(self.name)
        self.ann.__enter__()
        if self.traced:
            self.span = _tracer.start_span(self.name)
            self.t0 = self.span.start
        else:
            self.span = None
            self.t0 = time.perf_counter()
        return self.span

    def __exit__(self, *exc):
        end = time.perf_counter()
        dt = end - self.t0
        span = self.span
        if span is not None:
            span.end = end
            if self.rows:
                span.tags["rows"] = self.rows
            if self.bytes:
                span.tags["bytes"] = self.bytes
            _tracer.finish_span(span)
        self.ann.__exit__(*exc)
        a, q = self.a, self.q
        if a is not None:
            a.add(self.name, seconds=dt, rows=self.rows, bytes=self.bytes)
        if q is not None and q is not a:
            q.add(self.name, seconds=dt, rows=self.rows, bytes=self.bytes)
        return False


_NOT_LISTENING = nullcontext()


def timed(name: str, rows: int = 0, bytes: int = 0):
    """Open stage `name` (a context manager). Call it as `stats.timed`,
    looked up on the module at call time, with `name, rows, bytes` only
    (tags go on the span with tracing.set_tag): a harness that wraps
    this function from outside relies on both. With no collection on
    and no trace active on this thread it is a no-op of one branch."""
    a = _active
    q = getattr(_tls, "col", None)
    traced = _tracer.current() is not None
    if a is None and q is None and not traced:
        return _NOT_LISTENING
    return _Stage(name, rows, bytes, a, q, traced)


# ------------------------------------------------- per-operator breakdown

# stage prefixes that represent query execution work (device dispatch
# and wait as the HOST clock sees them, readback, host fold): the
# `device-ms` column of EXPLAIN ANALYZE's stage-family table and the
# device_seconds rolled into sqlstats. Compile and background stages are
# excluded: they are amortized, not per-query execution cost.
_EXEC_PREFIXES = ("scan", "agg", "join", "sort", "fused", "serving",
                  "dist", "vector", "spill", "sql")
_NON_EXEC_STAGES = ("compile", "vault", "image_build", "prime",
                    "prewarm")
# stages that split or surround one already counted (fused.exec holds
# fused.dispatch and fused.wait, dist.exec its own two, dist.dispatch the
# placing of the bound values: dist.args), or that are host work of the
# session, or set-up (dist.ingest: a scan's first placement)
_NOT_EXEC = frozenset(("fused.dispatch", "fused.wait", "fused.prepare",
                       "fused.unpack", "dist.dispatch", "dist.wait",
                       "dist.args", "dist.prepare", "dist.unpack",
                       "dist.ingest",
                       "sql.lookup", "sql.parse", "sql.bind", "sql.plan"))


def _is_exec_stage(name: str) -> bool:
    head = name.split(".", 1)[0]
    if head not in _EXEC_PREFIXES or name in _NOT_EXEC:
        return False
    return not any(t in name for t in _NON_EXEC_STAGES)


def operator_breakdown(col: Optional[StatsCollection]) -> list:
    """HOST stage seconds by the prefix of the stage's name (the part
    before the first '.') -> [{operator, device_ms, rows, bytes, events}],
    sorted by device_ms desc. `device_ms` holds the family's execution
    stages (_is_exec_stage: for a whole-query program `fused.exec` +
    `fused.readback`, one row whatever the plan); compile/prewarm stages
    are listed under other_ms so the rendering stays honest about total
    time. Device time by PLAN operator is exec/device_profile.py's."""
    if col is None:
        return []
    with col._mu:
        stages = list(col.stages.values())
    groups: Dict[str, Dict[str, float]] = {}
    for s in stages:
        fam = s.name.split(".", 1)[0]
        g = groups.setdefault(fam, {"operator": fam, "device_ms": 0.0,
                                    "other_ms": 0.0, "rows": 0,
                                    "bytes": 0, "events": 0})
        if _is_exec_stage(s.name):
            g["device_ms"] += s.seconds * 1e3
        else:
            g["other_ms"] += s.seconds * 1e3
        g["rows"] += s.rows
        g["bytes"] += s.bytes
        g["events"] += s.events
    out = sorted(groups.values(),
                 key=lambda g: (-g["device_ms"], -g["other_ms"]))
    for g in out:
        g["device_ms"] = round(g["device_ms"], 3)
        g["other_ms"] = round(g["other_ms"], 3)
    return out


def operator_device(col: Optional[StatsCollection]) -> Dict[str, float]:
    """Per-operator-family execution seconds (the measured-cost signal
    sqlstats accumulates per fingerprint and the placement pass reads:
    sql/cost.py measured_route)."""
    if col is None:
        return {}
    out: Dict[str, float] = {}
    with col._mu:
        for s in col.stages.values():
            if not _is_exec_stage(s.name):
                continue
            fam = s.name.split(".", 1)[0]
            out[fam] = out.get(fam, 0.0) + s.seconds
    return out


def device_seconds(col: Optional[StatsCollection]) -> float:
    """Total execution-stage seconds in a collection (the sqlstats
    device-time roll-up)."""
    if col is None:
        return 0.0
    with col._mu:
        return sum(s.seconds for s in col.stages.values()
                   if _is_exec_stage(s.name))


def bytes_scanned(col: Optional[StatsCollection]) -> int:
    """Total bytes moved by scan stages (the sqlstats cost substrate)."""
    if col is None:
        return 0
    with col._mu:
        return sum(s.bytes for s in col.stages.values()
                   if s.name.startswith("scan."))


def degradations_seen(col: Optional[StatsCollection]) -> bool:
    """Did the resilience ladder degrade during this collection's scope?
    (insight signal)"""
    if col is None:
        return False
    with col._mu:
        return any(s.name.startswith("resilience.degrade")
                   for s in col.stages.values())
