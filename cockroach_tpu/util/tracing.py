"""Tracing: hierarchical spans with structured payloads + propagation.

Reference: pkg/util/tracing (tracer.go:300 Span, crdbspan.go) — always-on
lightweight spans, context propagation through every layer and across RPC
via interceptors (SetupFlowRequest.TraceInfo), recordings rendered by
EXPLAIN ANALYZE / inflight-trace registry.

This implementation keeps the same surface at the scale this runtime
needs: a thread-local span stack (context propagation within a flow),
`carrier()`/`from_carrier()` for crossing process/RPC boundaries (the
TraceInfo analog), structured events, and a tree rendering.

One seam with exec/stats.py: a stage is a span is an annotation.
pgwire opens the root of a served statement (`statement_span`), the
session the query span under it (`query_span`, the root when there is no
wire), and every `stats.timed(name)` stage attaches a child span here
and opens `annotation(name)`, a `jax.profiler.TraceAnnotation` under
ANNOTATION_PREFIX, so a profile of the node shows the program's own
spans beside the device ops. `child_span`/`record`/`set_tag` are no-ops
when no root is active — the cost posture matches exec/stats.py's
disabled path.

A finished statement leaves nothing behind unless it was slow: at least
SLOW_FACTOR times its fingerprint's usual time (sql/insights.py's
baseline, handed over by the session with `note_usual`). Its tree then
goes to a ring of FINISHED_RING trees served beside the inflight spans
(`Tracer.inflight_summaries`), and its excess over the usual time is
split between `sql_slow_stmt_wait_seconds` (spent in `fused.wait`, or
`dist.wait` on the distributed tier, over that stage's usual time: the
device, or the queue before it) and `sql_slow_stmt_host_seconds` (the
rest: the host).
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional, Tuple

from cockroach_tpu.util.settings import Settings

TRACE_ENABLED = Settings.register(
    "sql.trace.enabled",
    True,
    "open a root span per query (EXPLAIN ANALYZE always traces)",
)

# Bound per-span recording memory (the reference's maxRecordedBytes
# posture): past the cap events are counted, not stored, and the
# rendering carries a truncation marker.
MAX_EVENTS_PER_SPAN = 128

# every annotation the program opens in the profiler's trace starts so
ANNOTATION_PREFIX = "crdb."
# a served statement is kept when it took this many times its
# fingerprint's usual time. Fixed, and not insights' sigma rule: a
# statement that repeats to 0.03% would flag its own jitter at 3 sigma
SLOW_FACTOR = 2.0
FINISHED_RING = 64  # slow statements' finished trees kept
# what of a statement is not the host's: the device call and its
# readback, of the single-chip runner or of the distributed one
_DEVICE_STAGES = frozenset(("fused.exec", "fused.readback",
                            "dist.exec", "dist.readback"))
_WAIT_STAGES = frozenset(("fused.wait", "dist.wait"))

_dropped_counter = None


def _dropped_metric():
    global _dropped_counter
    if _dropped_counter is None:
        from cockroach_tpu.util.metric import default_registry

        _dropped_counter = default_registry().counter(
            "trace_dropped_events_total",
            "span events discarded past the per-span recording cap")
    return _dropped_counter


def enabled() -> bool:
    return bool(Settings().get(TRACE_ENABLED))


_annotation_cls = None


def annotation(name: str):
    """The program's own span in the profiler's trace: a
    jax.profiler.TraceAnnotation named ANNOTATION_PREFIX + name (a
    context manager; half a microsecond while no profile is running)."""
    global _annotation_cls
    if _annotation_cls is None:
        from jax.profiler import TraceAnnotation

        _annotation_cls = TraceAnnotation
    return _annotation_cls(ANNOTATION_PREFIX + name)


class Span:
    """One timed interval of a trace. A plain slotted class: the stage
    seam (exec/stats.timed) allocates one per stage of every traced
    statement."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "start",
                 "end", "tags", "events", "children", "dropped", "query",
                 "usual")

    def __init__(self, name: str, trace_id: int, span_id: int,
                 parent_id: Optional[int] = None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start = time.perf_counter()
        self.end: Optional[float] = None
        self.tags: Dict[str, object] = {}
        self.events: List = []  # (dt, message, tags)
        self.children: List["Span"] = []
        self.dropped = 0  # events discarded past MAX_EVENTS_PER_SPAN
        self.query = False  # opened by query_span: tag_root tags it too
        # (usual seconds of the statement, of its fused.wait), set on a
        # statement's root by note_usual
        self.usual: Optional[Tuple[float, float]] = None

    @property
    def duration(self) -> float:
        return (self.end or time.perf_counter()) - self.start

    @property
    def self_time(self) -> float:
        """Duration minus what the child spans cover."""
        return max(self.duration
                   - sum(c.duration for c in list(self.children)), 0.0)

    def record(self, message: str, **tags):
        if len(self.events) >= MAX_EVENTS_PER_SPAN:
            self.dropped += 1
            _dropped_metric().inc()
            return
        self.events.append((time.perf_counter() - self.start, message,
                            tags))

    def set_tag(self, key: str, value):
        self.tags[key] = value

    def finish(self):
        if self.end is None:
            self.end = time.perf_counter()

    # -- rendering --------------------------------------------------------

    def render(self, indent: int = 0) -> str:
        pad = "  " * indent
        tag_s = (" " + " ".join(f"{k}={v}" for k, v in self.tags.items())
                 if self.tags else "")
        lines = [f"{pad}{self.name}: {self.duration * 1e3:.2f}ms{tag_s}"]
        for dt, msg, tags in self.events:
            t = (" " + " ".join(f"{k}={v}" for k, v in tags.items())
                 if tags else "")
            lines.append(f"{pad}  @{dt * 1e3:.2f}ms {msg}{t}")
        if self.dropped:
            lines.append(f"{pad}  (+{self.dropped} events dropped)")
        for c in self.children:
            lines.append(c.render(indent + 1))
        return "\n".join(lines)

    def as_dict(self) -> Dict[str, object]:
        d: Dict[str, object] = {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "duration_ms": round(self.duration * 1e3, 3),
            "finished": self.end is not None,
        }
        if self.tags:
            d["tags"] = dict(self.tags)
        if self.events:
            d["events"] = [
                {"at_ms": round(dt * 1e3, 3), "msg": msg,
                 **({"tags": tags} if tags else {})}
                for dt, msg, tags in list(self.events)
            ]
        if self.dropped:
            d["dropped_events"] = self.dropped
        if self.children:
            d["children"] = [c.as_dict() for c in list(self.children)]
        return d

    def walk(self) -> Iterator["Span"]:
        yield self
        for c in list(self.children):
            yield from c.walk()


def _covered(root: Span, names) -> float:
    """Seconds of `root`'s descendants named in `names` (a span that
    counts is not descended into: fused.exec holds fused.wait)."""
    total, stack = 0.0, list(root.children)
    while stack:
        s = stack.pop()
        if s.name in names:
            total += s.duration
        else:
            stack.extend(s.children)
    return total


class Tracer:
    """Span factory + thread-local active-span propagation, the inflight
    registry, and the ring of slow statements' finished trees."""

    def __init__(self):
        from cockroach_tpu.util.metric import default_registry

        self._tls = threading.local()
        self._next_id = itertools.count(1)
        self.inflight: Dict[int, Span] = {}  # inflight-trace registry
        self.finished: deque = deque(maxlen=FINISHED_RING)
        reg = default_registry()
        self._slow_wait = reg.histogram(
            "sql_slow_stmt_wait_seconds",
            "of each slow statement's excess over its fingerprint's "
            "usual time, the part spent in fused.wait (dist.wait) over "
            "that stage's usual time (the device, or the queue before "
            "it)")
        self._slow_host = reg.histogram(
            "sql_slow_stmt_host_seconds",
            "of each slow statement's excess over its fingerprint's "
            "usual time, the part not spent waiting for the device: "
            "a stall of the host")

    def _ids(self) -> int:
        return next(self._next_id)

    def _stack(self) -> List[Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current(self) -> Optional[Span]:
        st = getattr(self._tls, "stack", None)
        return st[-1] if st else None

    def root(self) -> Optional[Span]:
        st = getattr(self._tls, "stack", None)
        return st[0] if st else None

    def start_span(self, name: str) -> Span:
        """Open a span under this thread's active one (a root when there
        is none) and make it the active span; pair with finish_span().
        The context-manager-free form for exec/stats.timed."""
        st = self._stack()
        sid = next(self._next_id)
        if st:
            parent = st[-1]
            s = Span(name, parent.trace_id, sid, parent.span_id)
            parent.children.append(s)
        else:
            s = Span(name, sid, sid)
        self.inflight[sid] = s
        st.append(s)
        return s

    def finish_span(self, s: Span) -> None:
        self._stack().pop()
        s.finish()
        self.inflight.pop(s.span_id, None)

    @contextmanager
    def span(self, name: str, **tags):
        s = self.start_span(name)
        s.tags.update(tags)
        try:
            yield s
        finally:
            self.finish_span(s)

    # -- cross-boundary propagation (TraceInfo analog) --------------------

    def carrier(self) -> Optional[Dict[str, int]]:
        cur = self.current()
        if cur is None:
            return None
        return {"trace_id": cur.trace_id, "span_id": cur.span_id}

    @contextmanager
    def from_carrier(self, carrier: Optional[Dict[str, int]], name: str,
                     **tags):
        """Open a span that continues a remote trace (the receiving side
        of SetupFlowRequest.TraceInfo). When the parent span is inflight
        in this process (worker-thread hop rather than a true RPC), the
        child is grafted onto the live tree so one recording covers both
        sides; otherwise ids alone link the recordings."""
        sid = self._ids()
        s = Span(name,
                 trace_id=(carrier or {}).get("trace_id", sid),
                 span_id=sid,
                 parent_id=(carrier or {}).get("span_id"))
        s.tags.update(tags)
        parent = (self.inflight.get(s.parent_id)
                  if s.parent_id is not None else None)
        if parent is not None and parent.trace_id == s.trace_id:
            parent.children.append(s)
        self.inflight[sid] = s
        self._stack().append(s)
        try:
            yield s
        finally:
            self._stack().pop()
            s.finish()
            self.inflight.pop(sid, None)

    def start_remote(self, carrier: Optional[Dict[str, int]], name: str,
                     **tags) -> Optional[Span]:
        """Non-context form of from_carrier for STREAMING code (chunk
        generators) that cannot scope a with-block around a remote hop:
        creates the child span, grafts it onto the live parent when the
        parent is inflight in-process, registers it inflight, and does
        NOT touch the thread-local stack — interleaved generators (a
        join consuming two chunk streams) therefore cannot corrupt span
        nesting. The caller must pair it with finish_remote(). Returns
        None (a no-op handle) when there is no carrier to continue."""
        if carrier is None:
            return None
        sid = self._ids()
        s = Span(name, trace_id=carrier.get("trace_id", sid),
                 span_id=sid, parent_id=carrier.get("span_id"))
        s.tags.update(tags)
        parent = (self.inflight.get(s.parent_id)
                  if s.parent_id is not None else None)
        if parent is not None and parent.trace_id == s.trace_id:
            parent.children.append(s)
        self.inflight[sid] = s
        return s

    def finish_remote(self, s: Optional[Span]) -> None:
        if s is None:
            return
        s.finish()
        self.inflight.pop(s.span_id, None)

    # -- finished statements ----------------------------------------------

    def finish_statement(self, root: Span) -> None:
        """A served statement's root has finished. Its host time (its
        duration minus the device call and the readback) goes to the
        stage `<root>.host` of the process-wide stats collection, when
        one is on. A statement that took SLOW_FACTOR times its usual
        time keeps its tree in the ring, and its excess is split between
        the two sql_slow_stmt_* histograms; any other leaves nothing."""
        # exec/stats imports this module at its top
        from cockroach_tpu.exec import stats

        dur = root.duration
        if stats.active() is not None:
            stats.add(root.name + ".host",
                      seconds=max(dur - _covered(root, _DEVICE_STAGES),
                                  0.0))
        usual = root.usual
        if usual is None or dur < SLOW_FACTOR * usual[0]:
            return
        excess = dur - usual[0]
        waited = _covered(root, _WAIT_STAGES)
        wait_x = min(max(waited - usual[1], 0.0), excess)
        self._slow_wait.observe(wait_x)
        self._slow_host.observe(excess - wait_x)
        root.tags.update(usual_ms=round(usual[0] * 1e3, 3),
                         wait_excess_ms=round(wait_x * 1e3, 3),
                         host_excess_ms=round((excess - wait_x) * 1e3, 3))
        self.finished.append(root)

    def inflight_summaries(self) -> List[Dict[str, object]]:
        """Shallow /_status/traces payload: one row per live span, then
        one per span of every kept slow statement (`finished` true).
        `node_id` is the span's node tag (remote KV hops are stamped
        with the serving node) or None for untagged local spans;
        `start_ms` is the span's start after its trace's root, where the
        root is among the rows."""
        spans = list(self.inflight.values())
        spans += [s for root in list(self.finished) for s in root.walk()]
        t0 = {s.trace_id: s.start for s in spans if s.parent_id is None}
        rows = []
        for s in spans:
            tags = dict(s.tags)
            nid = tags.get("node_id")
            base = t0.get(s.trace_id)
            rows.append({
                "name": s.name,
                "trace_id": s.trace_id,
                "span_id": s.span_id,
                "parent_id": s.parent_id,
                "node_id": int(nid) if nid is not None else None,
                "start_ms": (None if base is None
                             else round((s.start - base) * 1e3, 3)),
                "elapsed_ms": round(s.duration * 1e3, 3),
                "finished": s.end is not None,
                "tags": {k: str(v) for k, v in tags.items()},
                "events": len(s.events) + s.dropped,
            })
        # live rows first: a consumer that caps the list (nodestatus)
        # must not lose them behind the kept trees
        rows.sort(key=lambda r: (r["finished"], r["trace_id"],
                                 r["span_id"]))
        return rows


_tracer = Tracer()


def tracer() -> Tracer:
    return _tracer


def record(message: str, **tags) -> None:
    """Attach an event to the active span, if any (zero-cost when not
    tracing)."""
    cur = _tracer.current()
    if cur is not None:
        cur.record(message, **tags)


def set_tag(**tags) -> None:
    """Tag the active span, if any: how a `stats.timed` stage's span
    gets what the stage's name does not say (a bucket, a table)."""
    cur = _tracer.current()
    if cur is not None:
        cur.tags.update(tags)


def tag_root(**tags) -> None:
    """Tag this thread's root span (e.g. the tier a query finished on),
    and the innermost query span under it: under pgwire the root is the
    wire's, and the tier belongs on `session.execute` too."""
    st = _tracer._stack()
    if not st:
        return
    st[0].tags.update(tags)
    for s in reversed(st):
        if s.query:
            if s is not st[0]:
                s.tags.update(tags)
            return


def note_usual(seconds: float, wait_seconds: float) -> None:
    """Hand this thread's root span the statement's usual time and its
    fused.wait's (the fingerprint's baseline BEFORE this execution is
    folded in): what finish_statement judges the statement against."""
    root = _tracer.root()
    if root is not None:
        root.usual = (seconds, wait_seconds)


@contextmanager
def _gated_span(name: str, tags, query: bool = False,
                statement: bool = False):
    """A span with the program's annotation around it, gated on
    `sql.trace.enabled`: yields None (one settings lookup) when off."""
    if not enabled():
        yield None
        return
    with annotation(name):
        s = _tracer.start_span(name)
        s.query = query
        s.tags.update(tags)
        try:
            yield s
        finally:
            _tracer.finish_span(s)
            if statement:
                _tracer.finish_statement(s)


def statement_span(name: str, **tags):
    """Root span of one served statement, from its message complete in
    the buffer to the flush. On exit the tracer judges the finished
    tree (Tracer.finish_statement)."""
    return _gated_span(name, tags, statement=True)


def query_span(name: str, **tags):
    """Span of one query: the root, or the child of a served
    statement's root; `tag_root` tags it beside the root."""
    return _gated_span(name, tags, query=True)


@contextmanager
def child_span(name: str, **tags):
    """Child span attached to the active span; a no-op yielding None when
    nothing is tracing. For a span that is not a stage (`flow.<tier>`);
    a stage opens its span itself (exec/stats.timed)."""
    if _tracer.current() is None:
        yield None
        return
    with _tracer.span(name, **tags) as s:
        yield s


def summarize(span: Optional[Span]) -> Optional[Dict[str, object]]:
    """Compact per-query trace digest for EXPLAIN ANALYZE:
    stage durations, retry count, tier reached, event volume."""
    if span is None:
        return None
    stages: Dict[str, float] = {}
    retries = 0
    degradations = 0
    restarts = 0
    events = 0
    dropped = 0
    tier = span.tags.get("tier")
    for s in span.walk():
        if s is not span:
            stages[s.name] = stages.get(s.name, 0.0) + s.duration * 1e3
        if s.name.startswith("flow."):
            # the LAST flow.<tier> span entered is the rung the query
            # finished on (degraded rungs appear earlier in the walk)
            tier = s.name[len("flow."):]
        events += len(s.events)
        dropped += s.dropped
        for _, msg, _tags in list(s.events):
            if msg == "retry":
                retries += 1
            elif msg.startswith("degrade"):
                degradations += 1
            elif msg.startswith("flow.restart"):
                restarts += 1
    return {
        "duration_ms": round(span.duration * 1e3, 3),
        "stages": {k: round(v, 3) for k, v in sorted(stages.items())},
        "retries": retries,
        "degradations": degradations,
        "restarts": restarts,
        "tier": tier,
        "events": events,
        "dropped_events": dropped,
    }
