"""Cold-start microbench: first-execution latency under three regimes.

A node that just restarted pays trace + lower + XLA-compile before its
first row; the two persistence layers each shave a different slice:

  cold       — no caches at all: full trace + lower + backend compile.
  xla_warm   — persistent XLA compilation cache only (the
               util/compile_cache.py layer): trace + lower still run,
               the backend compile is a disk hit.
  vault_warm — plan vault (util/plan_vault.py): trace + lower still
               run, the compiled executable deserializes from disk —
               no XLA involvement at all.

Each measurement is the FIRST execution of the statement on a fresh
catalog + store + session (fresh FusedRunner, nothing shared in
process), so the number is the honest "first query after restart"
latency, minus process boot. scripts/check_cold_start.py crosses real
process boundaries for the correctness half of this story; this module
produces the latency table for bench.py's JSON.
"""

from __future__ import annotations

import os
import shutil
import time
from typing import Callable, Dict, Optional

N_ROWS = 3000
QUERIES = {
    "agg": ("select a, sum(b) as sb, count(*) as n from t "
            "group by a order by a"),
    "topk": "select a, b from t where b > 50 order by b desc limit 20",
}


def _fresh_session(capacity: int = 256):
    from cockroach_tpu.sql.session import Session, SessionCatalog
    from cockroach_tpu.storage.engine import PyEngine
    from cockroach_tpu.storage.mvcc import MVCCStore
    from cockroach_tpu.util.hlc import HLC, ManualClock

    store = MVCCStore(engine=PyEngine(), clock=HLC(ManualClock(1000)))
    sess = Session(SessionCatalog(store), capacity=capacity)
    sess.execute("create table t (a int, b int)")
    vals = ", ".join(f"({i % 11}, {i * 7 % 1000})" for i in range(N_ROWS))
    sess.execute(f"insert into t values {vals}")
    return sess


def _first_exec_times(vault_dir: str = "") -> Dict[str, float]:
    """First-ever execution wall time per query on a fresh session.

    The vault (when used) is mounted only after the schema is rebuilt: a
    real restart re-opens persistent storage without replaying DDL, and
    the replayed CREATE TABLE would otherwise (correctly) garbage-collect
    the artifacts tagged with the table."""
    from cockroach_tpu.util import plan_vault as pv
    from cockroach_tpu.util.settings import Settings

    Settings().set(pv.PLAN_VAULT_DIR, "")
    sess = _fresh_session()
    Settings().set(pv.PLAN_VAULT_DIR, vault_dir)
    out = {}
    for name, sql in QUERIES.items():
        t0 = time.perf_counter()
        sess.execute(sql)
        out[name] = time.perf_counter() - t0
    return out


def run(log: Optional[Callable[[str], None]] = None) -> dict:
    """The bench.py "coldstart" block. The cold and vault regimes run with
    the persistent XLA cache switched off (util/compile_cache.py — the
    cache directory itself is never re-pointed); the xla_warm regime uses
    the process's own cache, populated by a first pass. The plan vault
    lives in a fixed, emptied sub-directory of the checkout; its setting
    is restored on exit."""
    from cockroach_tpu.util import plan_vault as pv
    from cockroach_tpu.util.compile_cache import (
        CHECKOUT, persistent_cache_disabled,
    )
    from cockroach_tpu.util.settings import Settings

    log = log or (lambda m: None)
    old_vault = Settings().get(pv.PLAN_VAULT_DIR)
    vault_dir = os.path.join(CHECKOUT, ".jax_cache", "coldstart_vault")
    shutil.rmtree(vault_dir, ignore_errors=True)
    os.makedirs(vault_dir)

    try:
        # -- regime 1: cold (no caches anywhere)
        with persistent_cache_disabled():
            cold = _first_exec_times()
        log(f"coldstart: cold {({k: round(v, 3) for k, v in cold.items()})}")

        # -- regime 2: persistent XLA cache, warm (populate, re-measure)
        _first_exec_times()  # populate
        xla_warm = _first_exec_times()
        log(f"coldstart: xla_warm "
            f"{({k: round(v, 3) for k, v in xla_warm.items()})}")

        # -- regime 3: plan vault, warm (populate, re-measure). The XLA
        # cache must be OFF while populating: a cache-hit executable
        # doesn't re-serialize (store would refuse, see plan_vault.py).
        with persistent_cache_disabled():
            _first_exec_times(vault_dir)  # populate
            vault_warm = _first_exec_times(vault_dir)
        log(f"coldstart: vault_warm "
            f"{({k: round(v, 3) for k, v in vault_warm.items()})}")

        return {"queries": {
            name: {
                "cold_s": round(cold[name], 4),
                "xla_warm_s": round(xla_warm[name], 4),
                "vault_warm_s": round(vault_warm[name], 4),
                "vault_speedup": round(
                    cold[name] / max(vault_warm[name], 1e-9), 2),
            } for name in QUERIES
        }}
    finally:
        Settings().set(pv.PLAN_VAULT_DIR, old_vault)
        shutil.rmtree(vault_dir, ignore_errors=True)
