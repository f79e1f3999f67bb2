"""What the benchmark reads from the program, set from outside it.

Always on (counters; cheap, the same on both sides of a comparison):
  - JAX's own compile events (jax.monitoring): backend compiles and
    persistent-cache loads, so that a compile inside the window is seen;
  - the process-wide exec/stats collection;
  - the `tier` tag of every finished root span: `tracing.query_span` is
    looked up at call time by Session.execute, so a recording wrapper here
    sees the program's own span and nothing in the served path changes
    (the tracer itself keeps no finished span; copied from
    chip_smoke.Observer).

Only in a traced run (`annotate=True`):
  - every `stats.timed(name)` stage, every root span and the pgwire
    message handlers also open a `jax.profiler.TraceAnnotation` named
    `bench.<name>`, so that the profiler's trace carries host spans on the
    device's clock (trace_reduce attributes idle gaps to them);
  - the seconds of every stage event are kept one by one (the program's
    collection keeps sums only), for medians.
"""

from __future__ import annotations

import contextlib
import threading

# pgwire message handlers wrapped in a traced run: (method, span name)
_WIRE_SPANS = (("_msg_parse", "wire.parse"), ("_msg_bind", "wire.bind"),
               ("_msg_execute", "wire.execute"),
               ("simple_query", "wire.simple_query"),
               ("_render", "wire.render"), ("_data_rows", "wire.encode"),
               ("_flush", "wire.flush"))
# what still answers when the device program did not run (copied from
# chip_smoke.check_counters)
_FAULT_PREFIXES = ("resilience.degrade.", "resilience.skip.",
                   "resilience.shrink.", "resilience.forced.",
                   "fused.fallback", "fused.stream_hbm", "dist.fallback")
_FAULT_NAMES = ("route.cpu", "scan.resident_fallback",
                "compile.vault_store_error")


class Observer:
    def __init__(self, annotate: bool):
        import jax.monitoring

        from cockroach_tpu.exec import stats
        from cockroach_tpu.util import tracing

        self.annotate = annotate
        self.compiles = 0
        self.cache_loads = 0
        self.tiers = {}            # tier tag -> finished root spans
        self.events = {}           # stage -> [seconds per event], traced
        self._mu = threading.Lock()
        self.collection = stats.enable()

        def on_duration(name, _secs, **_kw):
            if name.endswith("backend_compile_duration"):
                self.compiles += 1
            elif name.endswith("cache_retrieval_time_sec"):
                self.cache_loads += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)

        orig_span = tracing.query_span
        tiers, mu = self.tiers, self._mu

        @contextlib.contextmanager
        def recording_query_span(name, **tags):
            with self._annotation(name), orig_span(name, **tags) as span:
                try:
                    yield span
                finally:
                    if span is not None:
                        tier = str(span.tags.get("tier"))
                        with mu:
                            tiers[(name, tier)] = tiers.get((name, tier),
                                                            0) + 1

        tracing.query_span = recording_query_span
        if annotate:
            self._wrap_stages(stats)
            self._wrap_wire()

    def _annotation(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax.profiler

        return jax.profiler.TraceAnnotation("bench." + name)

    def _wrap_stages(self, stats) -> None:
        orig_timed = stats.timed
        orig_add = stats.StatsCollection.add
        observer = self

        @contextlib.contextmanager
        def timed(name, rows=0, bytes=0):
            with observer._annotation(name), \
                    orig_timed(name, rows=rows, bytes=bytes):
                yield

        def add(col, name, seconds=0.0, rows=0, bytes=0, events=1):
            if seconds and col is observer.collection:
                with observer._mu:
                    observer.events.setdefault(name, []).append(seconds)
            return orig_add(col, name, seconds=seconds, rows=rows,
                            bytes=bytes, events=events)

        stats.timed = timed
        stats.StatsCollection.add = add

    def _wrap_wire(self) -> None:
        from cockroach_tpu.sql import pgwire

        conn = getattr(pgwire, "_Conn", None)
        if conn is None:
            return
        for method, span in _WIRE_SPANS:
            orig = getattr(conn, method, None)
            if orig is not None:
                setattr(conn, method, self._annotated(orig, span))

    def _annotated(self, fn, span: str):
        observer = self

        def wrapper(*a, **kw):
            with observer._annotation(span):
                return fn(*a, **kw)

        wrapper.__name__ = getattr(fn, "__name__", span)
        return wrapper

    # -- reading -----------------------------------------------------------

    def snapshot(self) -> dict:
        """Everything that counts, at one instant: stage table, every
        counter and histogram (count, sum) of the program's metric
        registry, compile events. Whatever a cell's `expect` or a layer
        metric names is found here without an edit of this file."""
        from cockroach_tpu.util import metric

        with self.collection._mu:  # as_dict() rounds seconds to 1e-4
            stages = {s.name: {"seconds": s.seconds, "events": s.events,
                               "rows": s.rows, "bytes": s.bytes}
                      for s in self.collection.stages.values()}
        counters, histograms = {}, {}
        for name, m in metric.default_registry().metrics():
            if isinstance(m, metric.Counter):
                counters[name] = m.value()
            elif isinstance(m, metric.Histogram):
                h = m.snapshot()
                histograms[name] = {"count": h["count"], "sum": h["sum"]}
        with self._mu:
            tiers = dict(self.tiers)
            n_events = {k: len(v) for k, v in self.events.items()}
        return {"stages": stages, "counters": counters,
                "histograms": histograms, "compiles": self.compiles,
                "cache_loads": self.cache_loads,
                "tiers": tiers, "n_events": n_events}

    def events_between(self, before: dict, after: dict) -> dict:
        """{stage: [seconds]} of the events recorded between two
        snapshots (traced runs only; empty otherwise)."""
        out = {}
        with self._mu:
            for name, secs in self.events.items():
                a = before["n_events"].get(name, 0)
                b = after["n_events"].get(name, len(secs))
                if b > a:
                    out[name] = secs[a:b]
        return out


def delta(before: dict, after: dict) -> dict:
    """after - before for the additive parts of two snapshots."""
    stages = {}
    for name, s in after["stages"].items():
        b = before["stages"].get(name, {})
        d = {k: s[k] - b.get(k, 0) for k in ("seconds", "events", "rows",
                                             "bytes")}
        if any(d.values()):
            stages[name] = d
    tiers = {k: v - before["tiers"].get(k, 0)
             for k, v in after["tiers"].items()
             if v - before["tiers"].get(k, 0)}
    return {"stages": stages,
            "counters": {k: v - before["counters"].get(k, 0)
                         for k, v in after["counters"].items()},
            "histograms": {
                k: {f: h[f] - before["histograms"].get(k, {}).get(f, 0)
                    for f in ("count", "sum")}
                for k, h in after["histograms"].items()},
            "compiles": after["compiles"] - before["compiles"],
            "cache_loads": after["cache_loads"] - before["cache_loads"],
            "tiers": tiers}


def faults(stages: dict) -> list:
    """Names of counted stages that mean the device program did not serve
    the statement (a CPU route, a ladder step, a skipped tier, a fused
    runner that streamed)."""
    return sorted(n for n, s in stages.items()
                  if s.get("events", 0) > 0
                  and (n.startswith(_FAULT_PREFIXES) or n in _FAULT_NAMES))
