"""TPU-aware cost model: measured device coefficients + engine routing.

Reference: pkg/sql/opt/xform/coster.go:70,526 — the coster charges
per-row CPU costs and sequencing overheads. On an accelerator the
dominant SMALL-QUERY term is nothing like a per-row cost: every query
pays a flat dispatch+readback round trip, which a small scan+top-K can
beat on the host. The coster therefore routes whole queries:

    est_tpu  = DISPATCH_FLOOR + rows / TPU_ROWS_PER_S
    est_host = rows / HOST_ROWS_PER_S

and the engine with the lower estimate wins (SET vectorize=tpu|cpu
forces a side; the default `auto` costs it). The host engine is the
SAME XLA program compiled for the local CPU backend — one engine, two
placements, so routing can never change semantics. This is also the
fix for YCSB-E's 0.007x (VERDICT r4 weak #10): point-ish scans ride the
host; multi-M-row analytics ride the accelerator.

The coefficients below are NOT this machine's: the floor and the H2D rate
were measured before PR 1 on a v5e behind a link that no longer exists.
chip_smoke.py measures both on the attached chip and prints them next to
these constants on every run (CHANGES.md, PR 22, has the first readings).
Re-deriving them — and the crossover they imply — is ROADMAP S1; until
then `auto` sends any scan under ~2.5M rows to the host, and
SET vectorize = tpu forces the device.
"""

from __future__ import annotations

from typing import Optional

# stale: measured before PR 1 on another attachment (see above, and S1)
DISPATCH_FLOOR_S = 0.107      # flat per dispatch+readback round trip
TPU_ROWS_PER_S = 40e6         # fused whole-query pipeline, warm
HOST_ROWS_PER_S = 15e6        # XLA-CPU single-thread columnar
H2D_GBPS = 0.1                # host->device bandwidth
ROW_GATHER_ROWS_PER_S = 130e6  # HBM random row gathers (latency-bound)


def est_tpu_seconds(rows: int) -> float:
    return DISPATCH_FLOOR_S + rows / TPU_ROWS_PER_S


def est_host_seconds(rows: int) -> float:
    return rows / HOST_ROWS_PER_S


def route_backend(est_rows: Optional[int], setting: str = "auto") -> str:
    """-> "tpu" | "cpu" for a flow whose scans cover ~est_rows rows."""
    if setting in ("tpu", "cpu"):
        return setting
    if est_rows is None:
        return "tpu"
    return ("cpu" if est_host_seconds(est_rows) < est_tpu_seconds(est_rows)
            else "tpu")


def crossover_rows() -> int:
    """Row count where the accelerator starts winning (EXPLAIN info)."""
    return int(DISPATCH_FLOOR_S / (1.0 / HOST_ROWS_PER_S
                                   - 1.0 / TPU_ROWS_PER_S))


# ------------------------------------------------------------- placement --
#
# Tailwind-style (arXiv:2604.28079) per-operator placement: every
# operator in a compiled plan gets a TIER —
#
#   fused     one whole-query jitted device program (exec/fused.py)
#   streaming chunked per-operator device kernels (exec/operators.py)
#   host      the row-at-a-time datum engine / XLA-CPU backend
#
# — decided from MEASURED per-fingerprint device-seconds in sqlstats
# when the fingerprint is warm enough, falling back to the static
# cardinality model above on cold fingerprints. Re-planning is clamped
# (satellite: cold fingerprints must not thrash) and insights-flagged
# degradation marks the cached placement dirty for an early re-plan.

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List

from cockroach_tpu.util.settings import Settings

PLACEMENT_REPLAN_EVERY = Settings.register(
    "sql.placement.replan_every",
    64,
    "re-run the operator placement pass for a fingerprint every N "
    "executions (cost drift tracking without per-execution planning)",
)
PLACEMENT_REPLAN_MIN_EXECS = Settings.register(
    "sql.placement.replan_min_execs",
    8,
    "minimum executions between placements for one fingerprint, even "
    "when insights flag it degraded — the anti-thrash clamp",
)
PLACEMENT_MEASURED_MIN_EXECS = Settings.register(
    "sql.placement.measured_min_execs",
    3,
    "executions of a fingerprint before its measured timings override "
    "the static cardinality estimates in placement",
)
PLACEMENT_CACHE_CAP = 512


@dataclass
class OpCost:
    """One operator's placement decision + the cost inputs that made it
    (EXPLAIN's per-operator tier/cost rendering)."""
    name: str                  # plan-node kind ("scan", "hash join", ...)
    detail: str = ""           # table / keys / agg list for display
    est_rows: float = 0.0      # static cardinality estimate
    device_s: float = 0.0      # est or measured device seconds
    host_s: float = 0.0        # est or measured host seconds
    tier: str = "fused"        # "fused" | "streaming" | "host"
    source: str = "static"     # "static" | "measured" | "forced"
    reason: str = ""           # one-liner: why this tier

    def as_dict(self) -> dict:
        return {"name": self.name, "detail": self.detail,
                "est_rows": int(self.est_rows),
                "device_s": round(self.device_s, 4),
                "host_s": round(self.host_s, 4),
                "tier": self.tier, "source": self.source,
                "reason": self.reason}


@dataclass
class QueryPlacement:
    """The placement pass's output for one plan: a backend decision for
    the whole flow plus per-operator tiers in pre-order plan-walk
    order."""
    backend: str = "tpu"          # "tpu" | "cpu" (flow_backend setting)
    source: str = "static"        # what seeded the backend choice
    fingerprint: str = ""
    est_scan_rows: int = 0
    est_device_s: float = 0.0
    est_host_s: float = 0.0
    ops: List[OpCost] = field(default_factory=list)

    def as_dict(self) -> dict:
        return {"backend": self.backend, "source": self.source,
                "fingerprint": self.fingerprint,
                "est_scan_rows": self.est_scan_rows,
                "est_device_s": round(self.est_device_s, 4),
                "est_host_s": round(self.est_host_s, 4),
                "ops": [o.as_dict() for o in self.ops]}

    def tier_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for o in self.ops:
            out[o.tier] = out.get(o.tier, 0) + 1
        return out


class _Entry:
    __slots__ = ("placement", "execs_since_plan", "dirty")

    def __init__(self, placement: QueryPlacement):
        self.placement = placement
        self.execs_since_plan = 0
        self.dirty = False


class PlacementCache:
    """Per-fingerprint placement memo with the anti-thrash clamp.

    should_replan() is True when (a) the fingerprint has no cached
    placement, (b) REPLAN_EVERY executions have elapsed since the last
    plan, or (c) insights marked it degraded AND at least
    REPLAN_MIN_EXECS executions have elapsed (the clamp: a burst of
    degraded insights cannot force per-execution planning)."""

    def __init__(self):
        import threading

        self._mu = threading.Lock()
        self._entries: "OrderedDict[str, _Entry]" = OrderedDict()

    def should_replan(self, fp: str) -> bool:
        if not fp:
            return True
        every = max(int(Settings().get(PLACEMENT_REPLAN_EVERY)), 1)
        min_execs = max(int(Settings().get(PLACEMENT_REPLAN_MIN_EXECS)),
                        0)
        with self._mu:
            e = self._entries.get(fp)
            if e is None:
                return True
            if e.execs_since_plan >= every:
                return True
            return e.dirty and e.execs_since_plan >= min_execs

    def get(self, fp: str) -> "QueryPlacement | None":
        with self._mu:
            e = self._entries.get(fp)
            if e is None:
                return None
            e.execs_since_plan += 1
            self._entries.move_to_end(fp)
            return e.placement

    def peek(self, fp: str) -> "QueryPlacement | None":
        """get() without counting an execution (EXPLAIN reads)."""
        with self._mu:
            e = self._entries.get(fp)
            return e.placement if e is not None else None

    def store(self, fp: str, placement: QueryPlacement) -> None:
        if not fp:
            return
        with self._mu:
            self._entries[fp] = _Entry(placement)
            self._entries.move_to_end(fp)
            while len(self._entries) > PLACEMENT_CACHE_CAP:
                self._entries.popitem(last=False)

    def mark_degraded(self, fp: str) -> None:
        """Insights hook: a degraded/slow fingerprint re-plans early
        (subject to the REPLAN_MIN_EXECS clamp)."""
        with self._mu:
            e = self._entries.get(fp)
            if e is not None:
                e.dirty = True

    def reset(self) -> None:
        with self._mu:
            self._entries.clear()


_default_cache = PlacementCache()


def default_placement_cache() -> PlacementCache:
    return _default_cache


def measured_route(est_rows: int, stats: "dict | None",
                   setting: str = "auto"):
    """-> (backend, source, device_s, host_s): the static estimates with
    the MEASURED side substituted when the fingerprint is warm enough.

    sqlstats tells us what the query actually cost on the side it has
    been running on (device_frac decides which side that was); the other
    side keeps its static estimate. When measured reality diverges from
    the static model — a 'cheap' query that actually burns device
    seconds, or vice versa — argmin flips the backend and the
    fingerprint migrates tiers."""
    device_s = est_tpu_seconds(est_rows)
    host_s = est_host_seconds(est_rows)
    if setting in ("tpu", "cpu"):
        return setting, "forced", device_s, host_s
    min_execs = max(int(Settings().get(PLACEMENT_MEASURED_MIN_EXECS)), 1)
    source = "static"
    if stats and stats.get("count", 0) >= min_execs:
        mean_s = stats.get("mean_seconds", 0.0)
        if mean_s > 0.0:
            dev_frac = (stats.get("device_seconds", 0.0)
                        / max(stats.get("total_seconds", mean_s), 1e-9))
            if dev_frac > 0.5:
                device_s = mean_s
            else:
                host_s = mean_s
            source = "measured"
    backend = "cpu" if host_s < device_s else "tpu"
    return backend, source, device_s, host_s
