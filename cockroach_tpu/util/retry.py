"""Retry with exponential backoff + the execution-error classifier.

Reference: pkg/util/retry (retry.go Options/Retry) — every KV and DistSQL
client loop runs under one Options shape: initial backoff, multiplier,
jitter, max backoff, max retries. This module is the TPU pipeline's
analog, plus the piece the reference spreads across pgerror/colexecerror:
a classifier that splits transient faults (injected faults, transfer
hiccups, flow-restart exhaustion — the "retry me" family) from resource
exhaustion (degrade to a cheaper tier: device OOM, budget trips) and
terminal errors (user/logic errors — fail fast).

The classifier verdict drives the degradation ladder in
exec/operators.py:run_flow: RETRYABLE errors are retried in place under
Options backoff, RESOURCE errors step the ladder down a tier
(fused-distributed -> fused -> streaming -> grace-spill), TERMINAL errors
propagate unchanged.
"""

from __future__ import annotations

import random
import time
from dataclasses import dataclass, field
from typing import Callable, Optional, TypeVar

from cockroach_tpu.util import cancel
from cockroach_tpu.util.settings import Settings

# -------------------------------------------------------------- settings

RESILIENCE_MAX_RETRIES = Settings.register(
    "sql.resilience.max_retries",
    6,
    "in-place retries of a transient fault before degrading/failing",
)
RESILIENCE_INITIAL_BACKOFF = Settings.register(
    "sql.resilience.initial_backoff_s",
    0.01,
    "first retry backoff in seconds (doubles per attempt up to the max)",
)
RESILIENCE_MAX_BACKOFF = Settings.register(
    "sql.resilience.max_backoff_s",
    1.0,
    "backoff ceiling in seconds",
)
RESILIENCE_BACKOFF_MULTIPLIER = Settings.register(
    "sql.resilience.backoff_multiplier",
    2.0,
    "backoff growth factor per retry",
)
RESILIENCE_JITTER = Settings.register(
    "sql.resilience.jitter",
    0.25,
    "backoff jitter fraction (sleep in [b*(1-j), b*(1+j)])",
)

# ------------------------------------------------------- classification

RETRYABLE = "retryable"   # transient: retry in place under backoff
RESOURCE = "resource"     # capacity: step the degradation ladder down
TERMINAL = "terminal"     # user/logic error: propagate unchanged

# jaxlib.XlaRuntimeError carries the gRPC-style status name in its
# message; match on text so the classifier needs no jaxlib import (and
# covers test doubles that mimic the message).
_OOM_TOKENS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")
_TRANSIENT_TOKENS = ("UNAVAILABLE", "ABORTED", "DATA_LOSS",
                     "transfer failed", "DEADLINE_EXCEEDED")


class CompileRefused(RuntimeError):
    """The device's compiler (XLA, Mosaic) or the lowering refused a
    program: scoped vmem, an unsupported op, a kernel it cannot tile. A
    defect of the program, not a capacity condition — no lower tier may
    answer in its place, whatever words the message holds. Raised where
    programs are lowered and compiled (exec/fused.py)."""


def classify(exc: BaseException) -> str:
    """One verdict per exception: RETRYABLE / RESOURCE / TERMINAL."""
    from cockroach_tpu.util.cancel import QueryCancelled
    from cockroach_tpu.util.fault import InjectedFault
    from cockroach_tpu.util.mon import BudgetExceededError

    if isinstance(exc, CompileRefused):
        # before the token matchers: "Ran out of memory in memory space
        # vmem" reads as an OOM, and must not step the ladder down
        return TERMINAL
    if isinstance(exc, QueryCancelled):
        # checked before the token matchers: the cancellation reason may
        # mention "timeout", which must not read as a transient fault —
        # a cancelled statement is dead, not retryable
        return TERMINAL
    if isinstance(exc, InjectedFault):
        return RETRYABLE
    if isinstance(exc, BudgetExceededError) or isinstance(exc, MemoryError):
        return RESOURCE
    from cockroach_tpu.parallel.mesh import DeviceLost

    if isinstance(exc, DeviceLost):
        # a chip dropped out of the mesh: retrying the same program on
        # the same placement cannot succeed — step the ladder down (the
        # dist tier's next rung recompiles on the surviving pow2
        # sub-mesh, parallel/dist_flow.collect_distributed)
        return RESOURCE
    from cockroach_tpu.exec.operators import FlowRestart

    if isinstance(exc, FlowRestart):
        # surfaced only after max_restarts widening attempts: the client
        # may retry the whole statement (maps to pgcode 40001), but the
        # ladder does not chew on it further
        return RETRYABLE
    from cockroach_tpu.kv.kvserver import NotLeaseholder
    from cockroach_tpu.parallel.spans import StaleLeaseholder

    if isinstance(exc, (NotLeaseholder, StaleLeaseholder)):
        # lease moved (node death, transfer): the scan plane resumes the
        # remaining span in place; if that budget is exhausted the
        # gateway re-plans from fresh leases — transient either way
        return RETRYABLE
    msg = str(exc)
    if any(tok in msg for tok in _OOM_TOKENS):
        return RESOURCE
    if isinstance(exc, (ConnectionError, TimeoutError)):
        return RETRYABLE
    if any(tok in msg for tok in _TRANSIENT_TOKENS):
        return RETRYABLE
    return TERMINAL


class RetriesExhausted(RuntimeError):
    """The retry budget ran out; `last` holds the final attempt's error."""

    def __init__(self, name: str, attempts: int, last: BaseException):
        super().__init__(
            f"{name}: {attempts} attempts exhausted; last: "
            f"{type(last).__name__}: {last}")
        self.name = name
        self.attempts = attempts
        self.last = last


# ------------------------------------------------------------- Options

@dataclass
class Options:
    """Backoff policy (reference: retry.Options, pkg/util/retry/retry.go).
    `sleep` is injectable so tests and the chaos harness run clockless."""

    initial_backoff: float = 0.05
    max_backoff: float = 1.0
    multiplier: float = 2.0
    jitter: float = 0.15
    max_retries: int = 5          # attempts = max_retries + 1
    sleep: Callable[[float], None] = time.sleep
    rng: random.Random = field(default_factory=lambda: random.Random(0x5eed))

    def backoffs(self):
        """The jittered sleep for each retry, in order (len = max_retries)."""
        b = self.initial_backoff
        for _ in range(self.max_retries):
            j = self.jitter
            yield max(0.0, b * (1 + self.rng.uniform(-j, j)))
            b = min(b * self.multiplier, self.max_backoff)


def options_from_settings() -> Options:
    """The process-wide `sql.resilience.*` policy."""
    s = Settings()
    return Options(
        initial_backoff=float(s.get(RESILIENCE_INITIAL_BACKOFF)),
        max_backoff=float(s.get(RESILIENCE_MAX_BACKOFF)),
        multiplier=float(s.get(RESILIENCE_BACKOFF_MULTIPLIER)),
        jitter=float(s.get(RESILIENCE_JITTER)),
        max_retries=int(s.get(RESILIENCE_MAX_RETRIES)),
    )


T = TypeVar("T")


def with_retry(fn: Callable[[], T], opts: Optional[Options] = None,
               name: str = "op") -> T:
    """Run `fn`, retrying RETRYABLE failures under `opts` backoff. RESOURCE
    and TERMINAL errors propagate immediately (the ladder, not the local
    loop, decides what a capacity error means). On budget exhaustion the
    LAST error is re-raised (not wrapped): an injected fault at a seam
    must stay recognizable to the ladder above.

    Use at idempotent pipeline seams only — the fault points fire BEFORE
    any state mutation so a retried call observes a clean slate."""
    if opts is None:
        opts = options_from_settings()
    backoffs = opts.backoffs()
    attempts = 0
    while True:
        attempts += 1
        try:
            return fn()
        except BaseException as e:  # noqa: BLE001 — classifier decides
            if classify(e) != RETRYABLE:
                raise
            # next(it, None) — a raw next() here would turn budget
            # exhaustion into StopIteration, which is both the wrong
            # error and fatal inside generators (PEP 479)
            pause = next(backoffs, None)
            if pause is None:
                raise  # retry budget exhausted: surface the last error
            # a cancel/deadline must not sit out a backoff sleep: poll
            # before committing to the pause (QueryCancelled is TERMINAL
            # so it propagates out of the loop, not back into it)
            cancel.checkpoint()
            record_retry(name, pause)
            opts.sleep(pause)


def record_retry(name: str, pause: float) -> None:
    """Count one retry in the metric registry, per-query stats, and the
    active trace span (if a query is being traced)."""
    from cockroach_tpu.exec import stats
    from cockroach_tpu.util import tracing
    from cockroach_tpu.util.metric import default_registry

    reg = default_registry()
    reg.counter("sql_resilience_retries_total",
                "in-place retries of transient faults").inc()
    reg.histogram(
        "sql_resilience_retry_backoff_seconds",
        "backoff slept before each retry",
        buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0),
    ).observe(pause)
    stats.add(f"resilience.retry.{name}")
    tracing.record("retry", name=name, backoff_s=round(pause, 4))
