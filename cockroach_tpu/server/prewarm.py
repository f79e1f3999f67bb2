"""Background plan pre-warm: checkpointable compile jobs off the query path.

The plan vault (util/plan_vault.py) makes a compiled program reusable
across restarts; this module makes sure the compile itself never happens
on a foreground statement's clock. PREPARE time, CREATE TABLE time, and
server warm-up all funnel into ONE job kind — "plan_prewarm" — in the
existing server/jobs.py registry, so pre-warm work inherits the jobs
contract for free: records persist in the MVCC system keyspace (a
restarted node re-adopts unfinished warm-up), progress checkpoints after
every task (resume skips completed work), cancel/pause fence the running
holder via the lease epoch, and /_status/jobs shows it all.

A job's payload is a task list; each task is independently re-runnable:

  {"kind": "prepared", "sql": ..., "capacity": N, "extra_buckets": K}
      plan + AOT-compile the statement's pow2 chunk-bucket ladder
      (FusedRunner.aot_compile) and install the prepared entry in the
      catalog's shared cache, so the first foreground execution is a
      warm dispatch.
  {"kind": "serving", "table": ..., "cols": [...], "window": W,
   "buckets": [...]}
      build/install the ServingQueue runner for one batch shape and
      compile its pow2 batch-bucket programs (vault-first).

The PrewarmService runs adoption on a daemon thread: enqueue() returns
immediately, foreground statements never wait. Compilation happens under
each runner's own lock, so the only statement that can ever block on a
pre-warm compile is one racing to compile the exact same program — which
it would have paid for alone anyway.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

from cockroach_tpu.exec import stats
from cockroach_tpu.server.jobs import JobRecord, Registry, States
from cockroach_tpu.util import tracing as _tracing
from cockroach_tpu.util.metric import default_registry
from cockroach_tpu.util.settings import Settings

JOB_KIND = "plan_prewarm"

PREWARM_ENABLED = Settings.register(
    "sql.prewarm.enabled",
    False,
    "enqueue background plan_prewarm jobs at PREPARE / warm-up time "
    "(compile-at-prepare off the query path); off by default — "
    "pgwire server start and the bench/chaos harnesses turn it on",
)
PREWARM_EXTRA_BUCKETS = Settings.register(
    "sql.prewarm.extra_buckets",
    1,
    "chunk-bucket doublings above the current data size to AOT-compile "
    "per prepared plan (the pow2 ladder headroom for table growth)",
)


def enabled() -> bool:
    return bool(Settings().get(PREWARM_ENABLED))


class PrewarmService:
    """Per-catalog pre-warm driver: owns a jobs.Registry resumer for
    plan_prewarm and a daemon adoption thread. One service per
    SessionCatalog (attached to it), sharing the catalog's store so job
    records live next to the data they warm."""

    POLL_S = 0.25

    def __init__(self, catalog, capacity: int = 1 << 14,
                 registry: Optional[Registry] = None):
        self.catalog = catalog
        self.capacity = int(capacity)
        self.registry = registry or Registry(catalog.store)
        self.registry.register_resumer(JOB_KIND, self._resume)
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._mu = threading.Lock()
        self._noted: set = set()  # sql already enqueued (dedupe)
        reg = default_registry()
        self.jobs_total = reg.counter(
            "prewarm.jobs_total", "plan_prewarm jobs enqueued")
        self.tasks_total = reg.counter(
            "prewarm.tasks_total", "pre-warm tasks completed")

    # -------------------------------------------------------- lifecycle --

    def start(self) -> None:
        """Start the background adoption thread (idempotent)."""
        with self._mu:
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="plan-prewarm", daemon=True)
            self._thread.start()

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        self._wake.set()
        t = self._thread
        if t is not None:
            t.join(timeout)

    def _loop(self) -> None:
        while not self._stop.is_set():
            self._wake.wait(self.POLL_S)
            self._wake.clear()
            if self._stop.is_set():
                return
            try:
                with stats.timed("prewarm.poll"):
                    self.registry.adopt_and_run()
            except Exception as e:  # noqa: BLE001 — the warm-up loop
                # must outlive any one bad job (this thread never has a
                # span to record on: the log is where an operator looks)
                from cockroach_tpu.util import log as _log

                _log.get_logger().warning(
                    _log.Channel.OPS,
                    f"prewarm loop: {type(e).__name__}: {str(e)[:120]}")

    def run_pending(self, max_jobs: int = 16) -> List[int]:
        """Synchronously adopt+run runnable prewarm jobs — the
        deterministic drain for tests, gates, and bench setup."""
        return self.registry.adopt_and_run(max_jobs)

    # -------------------------------------------------------- enqueueing --

    def enqueue(self, tasks: List[dict]) -> Optional[int]:
        """Persist one plan_prewarm job and wake the worker. Returns the
        job id (None for an empty task list)."""
        tasks = [t for t in tasks if t]
        if not tasks:
            return None
        job_id = self.registry.create(JOB_KIND, {"tasks": tasks})
        self.jobs_total.inc()
        stats.add("prewarm.job_enqueued", events=1)
        _tracing.record("prewarm.enqueued", job=job_id, tasks=len(tasks))
        self._wake.set()
        return job_id

    def note_prepared(self, sql: str, capacity: Optional[int] = None) -> \
            Optional[int]:
        """PREPARE-time hook (Session._prepared_store): enqueue the
        statement's ladder compile once per SQL text."""
        if not enabled():
            return None
        with self._mu:
            if sql in self._noted:
                return None
            self._noted.add(sql)
        return self.enqueue([{
            "kind": "prepared",
            "sql": sql,
            "capacity": int(capacity or self.capacity),
            "extra_buckets": int(Settings().get(PREWARM_EXTRA_BUCKETS)),
        }])

    def forget(self, sql: Optional[str] = None) -> None:
        """Drop enqueue dedupe state (DDL changed the world)."""
        with self._mu:
            if sql is None:
                self._noted.clear()
            else:
                self._noted.discard(sql)

    # ---------------------------------------------------------- resumer --

    def _resume(self, registry: Registry, rec: JobRecord) -> None:
        """Run one plan_prewarm job from its checkpoint. Tasks already
        counted in progress["done"] are skipped — the resume-from-
        checkpoint contract a mid-prewarm kill relies on. StaleLease from
        checkpoint() aborts cleanly (cancel/pause bumped the epoch)."""
        tasks = list(rec.payload.get("tasks", ()))
        done = int(rec.progress.get("done", 0))
        epoch = rec.lease_epoch
        for i in range(done, len(tasks)):
            with _tracing.child_span("prewarm.task",
                                     kind=tasks[i].get("kind", "?")):
                try:
                    self._run_task(tasks[i])
                except Exception as e:  # noqa: BLE001 — one bad task
                    # must not void the rest of the ladder
                    stats.add("prewarm.task_failed")
                    _tracing.record("prewarm.task_failed",
                                    kind=tasks[i].get("kind", "?"),
                                    detail=str(e)[:120])
            self.tasks_total.inc()
            # checkpoint AFTER each task: a kill here resumes at i+1
            registry.checkpoint(rec.id, epoch,
                                {"done": i + 1, "total": len(tasks)})

    def _run_task(self, task: Dict) -> None:
        kind = task.get("kind")
        if kind == "prepared":
            self._warm_prepared(task)
        elif kind == "serving":
            self._warm_serving(task)
        else:
            raise ValueError(f"unknown prewarm task kind {kind!r}")

    def _warm_prepared(self, task: Dict) -> None:
        """Plan the statement, AOT-compile its bucket ladder, and
        install the shared prepared entry — off the query path. Uses a
        throwaway Session over the shared catalog so the entry lands in
        the cross-session cache exactly as a foreground PREPARE would."""
        from cockroach_tpu.exec import fused as _fused
        from cockroach_tpu.sql import parser as P
        from cockroach_tpu.sql.bind import Binder
        from cockroach_tpu.sql.plan import build
        from cockroach_tpu.sql.session import Session

        sql = task["sql"]
        capacity = int(task.get("capacity", self.capacity))
        extra = int(task.get("extra_buckets", 1))
        # already prepared in this process (the common PREPARE-time
        # case): ladder-compile on the LIVE runner — its base bucket is
        # a program-cache hit, so only the headroom rungs cost anything
        shared = getattr(self.catalog, "shared_prepared", None)
        if shared is not None:
            with shared[1]:
                prep = shared[0].get(sql)
            runner = (getattr(prep.op, "_fused_runner", None)
                      if prep is not None else None)
            if runner is not None:
                runner.aot_compile(extra_buckets=extra)
                stats.add("prewarm.prepared", events=1)
                return
        ast = P.parse(sql)
        if not isinstance(ast, P.SelectStmt):
            return
        plan = Binder(self.catalog).bind(ast)
        op = build(plan, self.catalog, capacity)
        runner = _fused.try_compile(op)
        if runner is None:
            return
        op._fused_runner = runner
        n = runner.aot_compile(extra_buckets=extra)
        if n == 0:
            return
        stats.add("prewarm.prepared", events=1)
        sess = Session(self.catalog, capacity)
        sess._prepared_store(sql, {"plan": plan, "op": op}, ast)

    def _warm_serving(self, task: Dict) -> None:
        from cockroach_tpu.sql import serving as _serving

        n = _serving.serving_queue().prewarm_shape(
            self.catalog, int(task.get("capacity", self.capacity)),
            task["table"], tuple(task.get("cols", ())),
            int(task["window"]),
            [int(b) for b in task.get("buckets", (1,))],
            # class-family fields; tasks persisted before the class
            # split carry none of these and warm as scan shapes
            cls=task.get("class", "scan"),
            order_col=task.get("order_col"),
            descending=bool(task.get("descending", False)),
            aggs=task.get("aggs"), names=task.get("names"),
            vcol=task.get("vcol"), metric=task.get("metric"))
        stats.add("prewarm.serving", events=n)


def service_for(catalog, capacity: int = 1 << 14) -> \
        Optional[PrewarmService]:
    """The catalog's pre-warm service (created on first use); None for
    catalogs without a store (nothing to persist jobs into)."""
    if getattr(catalog, "store", None) is None:
        return None
    svc = getattr(catalog, "_prewarm_service", None)
    if svc is None:
        svc = PrewarmService(catalog, capacity)
        catalog._prewarm_service = svc
    return svc


def note_prepared(catalog, sql: str, capacity: int) -> Optional[int]:
    """Session._prepared_store's seam: fire-and-forget ladder compile
    for a newly prepared statement. No-ops unless sql.prewarm.enabled."""
    if not enabled():
        return None
    try:
        svc = service_for(catalog, capacity)
    except Exception:  # noqa: BLE001 — prewarm must never fail PREPARE
        return None
    if svc is None:
        return None
    svc.start()
    return svc.note_prepared(sql, capacity)
