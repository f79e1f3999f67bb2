"""chip_smoke.py — the served SQL path, once, on the attached TPU.

One process. SQL text goes in over pgwire (PgServer on an ephemeral port,
a wire client on this thread), through Session, compile_plan and the
fused runner, and rows come back; every answer is compared with a plain
numpy reference and every statement's counters are read back so that a
CPU route, a lower tier or a warm recompile fails the run.

    python chip_smoke.py [--seed N]        one chip: SF1 TPC-H Q1/Q6/Q3,
                                           a YCSB-E range scan, a write
    python chip_smoke.py --chips 4         DistSQL Q3 on a four-chip mesh
                                           vs one chip vs the oracle, only
    python chip_smoke.py --rehearse-sf 0.01   CPU rehearsal at a tiny scale

Without a TPU (and without --rehearse-sf) it exits non-zero before any
data is loaded. The last line of stdout is
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}} with
the device as JAX reports it, so a rehearsal can never pass for a chip
run. Earlier lines are one JSON object each (also appended to
chiprun_out/chip_smoke.jsonl).
"""

from __future__ import annotations

import argparse
import contextlib
import datetime
import json
import os
import statistics
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

Q1_SQL = """
select l_returnflag, l_linestatus,
       sum(l_quantity) as sum_qty,
       sum(l_extendedprice) as sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
       avg(l_quantity) as avg_qty,
       avg(l_extendedprice) as avg_price,
       avg(l_discount) as avg_disc,
       count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""

Q3_SQL = """
select l_orderkey,
       sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING'
  and c_custkey = o_custkey
  and l_orderkey = o_orderkey
  and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate
limit 10
"""

Q6_SQL = """
select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= date '1994-01-01'
  and l_shipdate < date '1994-01-01' + interval '1' year
  and l_discount between 0.05 and 0.07
  and l_quantity < 24
"""

TPCH_TABLES = ("lineitem", "orders", "customer", "part", "supplier",
               "partsupp", "nation", "region")
CAPACITY = 1 << 17          # rows per scan chunk: SF1 lineitem = 64 chunks
YCSB_FIELDS = 10            # the YCSB record: a key and ten value fields
YCSB_ROWS = 200_000
YCSB_SCAN_LEN = 50
H2D_BYTES = 256 << 20

_out_file = None


def emit(obj: dict) -> None:
    line = json.dumps(obj, sort_keys=True, default=str)
    print(line, flush=True)
    if _out_file is not None:
        _out_file.write(line + "\n")
        _out_file.flush()


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------- observation --

class Observer:
    """Reads what the program already keeps, per statement: the global
    exec/stats collection, the statement's root span (tier), and JAX's
    own compile events."""

    def __init__(self):
        import jax.monitoring

        from cockroach_tpu.util import tracing

        self.compiles = 0      # backend compiles (XLA ran)
        self.cache_loads = 0   # executables read from the persistent cache
        self.roots = []        # finished root spans, in order

        def on_duration(name, _secs, **_kw):
            if name.endswith("backend_compile_duration"):
                self.compiles += 1
            elif name.endswith("cache_retrieval_time_sec"):
                self.cache_loads += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)

        # the tracer keeps no finished spans; Session.execute opens its
        # root through tracing.query_span, looked up at call time, so a
        # recording wrapper here sees the program's own span and nothing
        # in the served path changes
        orig = tracing.query_span
        roots = self.roots

        @contextlib.contextmanager
        def recording_query_span(name, **tags):
            with orig(name, **tags) as span:
                try:
                    yield span
                finally:
                    if span is not None:
                        roots.append(span)

        tracing.query_span = recording_query_span

    @staticmethod
    def _restarts() -> int:
        from cockroach_tpu.util.metric import default_registry

        return default_registry().counter(
            "sql_flow_restarts_total",
            "deferred-flag flow restarts").value()

    @contextlib.contextmanager
    def statement(self):
        """-> dict filled after the body: seconds, counters, compiles,
        tier of the LAST root span the body finished."""
        from cockroach_tpu.exec import stats

        col = stats.enable()
        c0, l0, r0 = self.compiles, self.cache_loads, len(self.roots)
        f0 = self._restarts()
        seen: dict = {}
        t0 = time.perf_counter()
        try:
            yield seen
        finally:
            seen["seconds"] = time.perf_counter() - t0
            stats.disable()
            stages = col.as_dict()
            seen["stages"] = stages
            seen["compiles"] = self.compiles - c0
            seen["cache_loads"] = self.cache_loads - l0
            roots = self.roots[r0:]
            seen["tier"] = roots[-1].tags.get("tier") if roots else None
            # a deferred capacity overflow reruns (and recompiles) the
            # whole flow: the answer is right, the cold time doubles
            seen["flow_restarts"] = self._restarts() - f0


def events(stages: dict, name: str) -> int:
    return int(stages.get(name, {}).get("events", 0))


_KEEP = ("route.", "resilience.", "fused.", "dist.", "scan.", "serving.",
         "compile.")


def counters_of(stages: dict) -> dict:
    return {k: int(v["events"]) for k, v in sorted(stages.items())
            if k.startswith(_KEEP)}


def seconds_of(stages: dict) -> dict:
    """Host-clock seconds of the timed stages (exec/stats.timed)."""
    return {k: round(v["seconds"], 4) for k, v in sorted(stages.items())
            if k.startswith(_KEEP) and v["seconds"] > 0}


def statement_line(seen: dict, **extra) -> dict:
    return {"seconds": seen["seconds"], "tier": seen["tier"],
            "compiles": seen["compiles"],
            "cache_loads": seen["cache_loads"],
            "flow_restarts": seen["flow_restarts"],
            "bytes_to_device": bytes_to_device(seen["stages"]),
            "counters": counters_of(seen["stages"]),
            "stage_seconds": seconds_of(seen["stages"]), **extra}


def check_counters(seen: dict, what: str, want_tier=None,
                   want_stage=None) -> None:
    """The faults that still answer: a CPU route, a ladder step, a
    skipped tier, a fused runner that streamed."""
    st = seen["stages"]
    check(events(st, "route.cpu") == 0, f"{what}: route.cpu > 0")
    for name in st:
        check(not name.startswith(("resilience.degrade.",
                                   "resilience.skip.",
                                   "resilience.shrink.",
                                   "resilience.forced.")),
              f"{what}: {name} counted")
        check(not name.startswith(("fused.fallback", "fused.stream_hbm",
                                   "dist.fallback")),
              f"{what}: {name} counted (the streaming tree answered)")
    for name in ("scan.resident_fallback", "compile.vault_store_error"):
        check(events(st, name) == 0, f"{what}: {name} counted")
    if want_tier is not None:
        check(seen["tier"] == want_tier,
              f"{what}: root span tier {seen['tier']!r}, "
              f"want {want_tier!r}")
    if want_stage is not None:
        check(events(st, want_stage) >= 1,
              f"{what}: no {want_stage} event — the device program "
              f"did not run")


def bytes_to_device(stages: dict) -> int:
    return sum(int(v.get("bytes", 0)) for k, v in stages.items()
               if k in ("scan.transfer", "dist.ingest_shard",
                        "resident.h2d", "serving.image_build"))


# -------------------------------------------------------------- decoding --

_EPOCH = datetime.date(1970, 1, 1)


def scaled(text: str) -> int:
    """A DECIMAL as pgwire prints it -> its scaled integer ('12.34' ->
    1234): the oracles work in scaled int64."""
    return int(text.replace(".", ""))


def days(text: str) -> int:
    return (datetime.date.fromisoformat(text) - _EPOCH).days


def check_q1(rows, gen, Q) -> None:
    want = Q.q1_oracle(gen)
    sch = gen.schema("lineitem")
    rf = {str(s): i for i, s in enumerate(sch.dicts["l_returnflag"])}
    ls = {str(s): i for i, s in enumerate(sch.dicts["l_linestatus"])}
    check(len(rows) == len(want), f"q1: {len(rows)} groups, "
                                  f"want {len(want)}")
    for r in rows:
        w = want[(rf[r[0]], ls[r[1]])]
        got = (scaled(r[2]), scaled(r[3]), scaled(r[4]), scaled(r[5]))
        check(got == tuple(w[:4]), f"q1 sums {r[:2]}: {got} != {w[:4]}")
        np.testing.assert_allclose(float(r[6]), w[4], rtol=1e-4)
        np.testing.assert_allclose(float(r[7]), w[5], rtol=1e-4)
        np.testing.assert_allclose(float(r[8]), w[6], rtol=1e-3)
        check(int(r[9]) == w[7], f"q1 count {r[:2]}")


def check_q6(rows, gen, Q) -> None:
    check(len(rows) == 1 and scaled(rows[0][0]) == Q.q6_oracle(gen),
          f"q6: {rows} != {Q.q6_oracle(gen)}")


def q3_rows(rows):
    return [(int(r[0]), scaled(r[1]), days(r[2])) for r in rows]


def check_q3(rows, gen, Q) -> None:
    want = Q.q3_oracle(gen)
    check(q3_rows(rows) == want, f"q3: {q3_rows(rows)[:3]} != {want[:3]}")


# --------------------------------------------------------- introspection --

def fused_programs(pg, sql: str):
    """Compiled whole-query programs + their device-resident arguments
    for `sql`, from the prepared entry of any live connection."""
    out = []
    for conn in list(pg._conns):
        prep = conn.session._prepared.get(sql)
        runner = getattr(getattr(prep, "op", None), "_fused_runner", None)
        if runner is None:
            continue
        progs = [p[0] for p in runner._progs.values() if p is not None]
        args = [a for a, _chunks in runner._exec_cache.values()]
        out.append((progs, args))
    return out


def check_on_device(pg, sql: str, what: str, device) -> bool:
    """Every array the statement's program ran on, and everything it
    returns, lives on `device`. -> whether the program text holds a
    Pallas kernel (tpu_custom_call)."""
    found = fused_programs(pg, sql)
    check(bool(found), f"{what}: no compiled fused program reachable")
    custom_call = False
    for progs, argsets in found:
        check(bool(progs), f"{what}: runner holds no program")
        for compiled in progs:
            custom_call |= "tpu_custom_call" in compiled.as_text()
            devs = set()
            for sh in _leaves(compiled.output_shardings):
                devs |= set(sh.device_set)
            check(devs == {device},
                  f"{what}: program output on {devs}, want {device}")
        for args in argsets:
            for arr in _leaves(args):
                check(arr.devices() == {device},
                      f"{what}: argument on {arr.devices()}, "
                      f"want {device}")
    return custom_call


def _leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


# ------------------------------------------------------------ the phases --

def phase_device(jax, rehearse: bool) -> float:
    """The two constants sql/cost.py hard-codes, measured: one round trip
    of a trivial jitted program with its readback, and host->device
    bandwidth for one large buffer. -> the round trip's median seconds."""
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), jnp.int32)
    np.asarray(f(x))  # compile
    trips = []
    for _ in range(20):
        t0 = time.perf_counter()
        np.asarray(f(x))
        trips.append(time.perf_counter() - t0)
    nbytes = H2D_BYTES >> (4 if rehearse else 0)
    host = np.ones((nbytes,), np.uint8)
    rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        d = jax.block_until_ready(jax.device_put(host))
        rates.append(nbytes / (time.perf_counter() - t0) / 1e9)
        check(d.devices() == {jax.devices()[0]}, "device_put off device")
        d.delete()
    from cockroach_tpu.sql import cost

    emit({"phase": "device",
          "dispatch_roundtrip_s_median20": statistics.median(trips),
          "dispatch_roundtrip_s_min": min(trips),
          "h2d_gbps_median3": statistics.median(rates),
          "h2d_bytes": nbytes,
          "cost_py_DISPATCH_FLOOR_S": cost.DISPATCH_FLOOR_S,
          "cost_py_H2D_GBPS": cost.H2D_GBPS})
    return statistics.median(trips)


def make_store():
    """The store `python -m cockroach_tpu start` builds (cli.cmd_start):
    MVCCStore on the default — native — engine."""
    from cockroach_tpu.storage.engine import NativeEngine
    from cockroach_tpu.storage.mvcc import MVCCStore

    store = MVCCStore()
    check(isinstance(store.engine, NativeEngine),
          f"engine is {type(store.engine).__name__}, not native")
    return store


def place(ask, sql: str, coster_stale: bool) -> bool:
    """Leave `sql` to the coster (vectorize = auto, what a default
    session runs) unless it would route it to the host backend. It does
    that for any scan under ~2.5M rows while sql/cost.py holds a dispatch
    floor two orders above the round trip phase_device measures; only
    then (`coster_stale`) is the device forced — a stop-gap that goes
    with ROADMAP S1/D3. Once the constants follow the measurement a host
    route fails the run instead. `ask(text)` sends one statement and
    returns its lines. -> whether the device was forced."""
    ask("set vectorize = auto")
    engine = [ln for ln in ask("explain " + sql)
              if ln.startswith("engine:")]
    check(bool(engine), f"explain printed no engine line for {sql[:40]!r}")
    forced = any(ln.startswith("engine: cpu") for ln in engine)
    emit({"phase": "coster", "sql": " ".join(sql.split())[:60],
          "auto_would_choose": engine, "forced_to_device": forced})
    if forced:
        check(coster_stale,
              f"auto routes {sql[:40]!r} to the host although cost.py's "
              f"dispatch floor is within 10x of the measured round trip: "
              f"{engine}")
        ask("set vectorize = tpu")
    return forced


def wire_ask(client):
    def ask(text: str):
        rows, code = client.query(text)
        check(code is None, f"{text[:40]!r}: sqlstate {code}")
        return [r[0] for r in rows]
    return ask


def phase_tpch(obs, store, gen, device, coster_stale: bool) -> None:
    from cockroach_tpu.sql.pgwire import PgServer
    from cockroach_tpu.workload import tpch_queries as Q
    from cockroach_tpu.workload.servebench import WireClient

    t0 = time.perf_counter()
    catalog = gen.mvcc_load(store, TPCH_TABLES)
    emit({"phase": "load", "what": "tpch", "sf": gen.sf,
          "tables": len(TPCH_TABLES),
          "lineitem_rows": gen.num_rows("lineitem"),
          "seconds": time.perf_counter() - t0,
          "engine": type(store.engine).__name__})
    pg = PgServer(catalog, capacity=CAPACITY).start()
    try:
        client = WireClient(pg.addr, timeout=1100.0)
        for name, sql, verify in (("q1", Q1_SQL, check_q1),
                                  ("q6", Q6_SQL, check_q6),
                                  ("q3", Q3_SQL, check_q3)):
            forced = place(wire_ask(client), sql, coster_stale)
            for run in ("cold", "warm"):
                with obs.statement() as seen:
                    rows, code = client.query(sql)
                check(code is None, f"{name} {run}: sqlstate {code}")
                verify(rows, gen, Q)
                check_counters(seen, f"{name} {run}", want_tier="fused",
                               want_stage="fused.exec")
                custom = check_on_device(pg, sql, f"{name} {run}", device)
                if run == "warm":
                    check(seen["compiles"] == 0
                          and seen["cache_loads"] == 0
                          and events(seen["stages"], "fused.compile") == 0,
                          f"{name} warm compiled: {seen['compiles']} "
                          f"compiles, {seen['cache_loads']} cache loads")
                emit(statement_line(seen, phase=name, run=run,
                                    rows=len(rows), tpu_custom_call=custom,
                                    forced_to_device=forced,
                                    matches_oracle=True))
        client.close()
    finally:
        pg.close()


def phase_ycsb_and_write(obs, store, seed: int, n_rows: int,
                         coster_stale: bool) -> None:
    """YCSB-E's statement (a short range scan from a key) through
    Parse/Bind/Execute, then an acknowledged INSERT read back on a second
    connection and through an aggregate. DDL/DML need the SessionCatalog
    (cli.cmd_start's catalog); the TPC-H tables above sit behind the
    read-only MVCCCatalog mvcc_load returns, so this phase has its own
    PgServer over the same store."""
    from cockroach_tpu.sql.pgwire import PgServer
    from cockroach_tpu.sql.session import SessionCatalog
    from cockroach_tpu.workload.servebench import WireClient

    catalog = SessionCatalog(store)
    pg = PgServer(catalog, capacity=CAPACITY).start()
    try:
        a = WireClient(pg.addr, timeout=1100.0)
        b = WireClient(pg.addr, timeout=1100.0)
        fields = [f"field{i}" for i in range(YCSB_FIELDS)]
        rows, code = a.query(
            "create table usertable (ycsb_key int primary key, "
            + ", ".join(f"{f} int" for f in fields) + ")")
        check(code is None, f"create usertable: sqlstate {code}")
        # bulk ingest (the AddSSTable path mvcc_load uses), not INSERT:
        # the descriptor's value slots in order, then the NULL bitmap
        rng = np.random.default_rng(seed)
        pks = np.arange(n_rows, dtype=np.int64) * 3  # gaps between keys
        data = rng.integers(0, 1 << 40, (YCSB_FIELDS, n_rows),
                            dtype=np.int64)
        t0 = time.perf_counter()
        desc = catalog.desc("usertable")
        cols = {f: data[i] for i, f in enumerate(fields)}
        cols["__nulls"] = np.zeros(n_rows, np.int64)
        store.ingest_table(desc.table_id, pks, cols)
        rows, code = a.query("analyze usertable")
        check(code is None, f"analyze usertable: sqlstate {code}")
        emit({"phase": "load", "what": "usertable", "rows": n_rows,
              "seconds": time.perf_counter() - t0})

        # -- YCSB-E: select ... where key >= $1 order by key limit 50
        scan_sql = ("select ycsb_key, " + ", ".join(fields)
                    + " from usertable where ycsb_key >= $1 "
                    "order by ycsb_key limit %d" % YCSB_SCAN_LEN)
        # the same bind twice. Since PR 31 `$1` stays a value: the binder
        # types it from `ycsb_key`, the prepared entry is keyed on the
        # parameterised text, and the start key is an argument of the
        # statement's one program (Session.bind_params, counter
        # sql_bind_params_total), so a new start key is no longer another
        # cold run (it was: 70-85 s on the chip, CHANGES.md PR 22). The
        # workload's own `LIMIT n` with n drawn per request is still
        # outside that scope (`LIMIT $2` binds as text, counter
        # sql_bind_textual_total; ROADMAP R2 waits for it and for M5)
        start = int(rng.integers(0, int(pks[-1]) - 3 * YCSB_SCAN_LEN))
        forced = place(wire_ask(a), scan_sql.replace("$1", str(start)),
                       coster_stale)
        for run in ("cold", "warm"):
            with obs.statement() as seen:
                rows, code = a.query_extended(scan_sql, (start,))
            check(code is None, f"ycsb_e {run}: sqlstate {code}")
            lo = int(np.searchsorted(pks, start))
            want = [tuple([int(pks[i])] + data[:, i].tolist())
                    for i in range(lo, lo + YCSB_SCAN_LEN)]
            got = [tuple(int(v) for v in r) for r in rows]
            check(got == want, f"ycsb_e {run}: rows differ from the "
                               f"sorted-array reference at key {start}")
            check_counters(seen, f"ycsb_e {run}", want_tier="fused",
                           want_stage="fused.exec")
            if run == "warm":
                check(seen["compiles"] == 0 and seen["cache_loads"] == 0,
                      f"ycsb_e warm compiled ({seen['compiles']})")
            emit(statement_line(seen, phase="ycsb_e", run=run,
                                start_key=start, rows=len(rows),
                                forced_to_device=forced,
                                matches_reference=True))

        # -- write path: INSERT on a, read back on b, then an aggregate
        agg_sql = ("select sum(field0) as s, count(*) as n from usertable "
                   "where field1 >= %d and field1 < %d and field2 < %d"
                   % (1 << 37, 1 << 39, 1 << 39))

        def agg_ref(f0, f1, f2):
            m = (f1 >= 1 << 37) & (f1 < 1 << 39) & (f2 < 1 << 39)
            return int(f0[m].sum()), int(m.sum())

        agg_forced = place(wire_ask(b), agg_sql, coster_stale)
        with obs.statement() as seen:
            rows, code = b.query(agg_sql)
        check(code is None, f"agg before: sqlstate {code}")
        before = agg_ref(data[0], data[1], data[2])
        check((int(rows[0][0]), int(rows[0][1])) == before,
              f"agg before insert: {rows} != {before}")
        check_counters(seen, "agg before")
        emit(statement_line(seen, phase="write.agg_before",
                            forced_to_device=agg_forced))

        base = int(pks[-1]) + 1
        new = rng.integers(1 << 37, 1 << 38, (4, YCSB_FIELDS),
                           dtype=np.int64)  # all pass the filter
        with obs.statement() as seen:
            rows, code = a.query(
                "insert into usertable values " + ", ".join(
                    "(" + ", ".join(str(v) for v in [base + i]
                                    + new[i].tolist()) + ")"
                    for i in range(len(new))))
        check(code is None, f"insert: sqlstate {code}")
        emit({"phase": "write.insert", "rows": len(new),
              "seconds": seen["seconds"], "acknowledged": True})

        # a plain filter, not the scan statement: that one compiles for
        # over a minute per start key, and this phase is about the write
        back_sql = ("select ycsb_key, " + ", ".join(fields)
                    + " from usertable where ycsb_key >= %d" % base)
        forced = place(wire_ask(b), back_sql, coster_stale)
        with obs.statement() as seen:
            rows, code = b.query(back_sql)
        check(code is None, f"read back: sqlstate {code}")
        got = sorted(tuple(int(v) for v in r) for r in rows)
        want = [tuple([base + i] + new[i].tolist())
                for i in range(len(new))]
        check(got == want, f"read back on the second connection: "
                           f"{got} != {want}")
        check_counters(seen, "read back")
        emit(statement_line(seen, phase="write.read_back", rows=len(rows),
                            forced_to_device=forced,
                            matches_reference=True))

        agg_forced = place(wire_ask(b), agg_sql, coster_stale)
        with obs.statement() as seen:
            rows, code = b.query(agg_sql)
        check(code is None, f"agg after: sqlstate {code}")
        after = agg_ref(np.concatenate([data[0], new[:, 0]]),
                        np.concatenate([data[1], new[:, 1]]),
                        np.concatenate([data[2], new[:, 2]]))
        check(after[1] == before[1] + len(new), "reference is off")
        check((int(rows[0][0]), int(rows[0][1])) == after,
              f"agg after insert does not see the rows: "
              f"{rows} != {after}")
        check_counters(seen, "agg after")
        emit(statement_line(seen, phase="write.agg_after",
                            forced_to_device=agg_forced,
                            sees_inserted_rows=True))
        a.close()
        b.close()
    finally:
        pg.close()


def phase_four_chips(obs, store, gen, coster_stale: bool) -> None:
    """DistSQL across chips: Q3's text through a Session with `SET
    distsql = always` over a catalog whose mesh is make_mesh(4), against
    the same text on one chip in this process and against the oracle."""
    from cockroach_tpu.parallel import dist_flow, ingest, make_mesh
    from cockroach_tpu.sql.session import Session
    from cockroach_tpu.workload import tpch_queries as Q

    t0 = time.perf_counter()
    catalog = gen.mvcc_load(store, ("lineitem", "orders", "customer"))
    emit({"phase": "load", "what": "tpch q3 tables", "sf": gen.sf,
          "lineitem_rows": gen.num_rows("lineitem"),
          "seconds": time.perf_counter() - t0,
          "engine": type(store.engine).__name__})
    catalog.with_mesh(make_mesh(4))
    # nothing is forced: at SF1 the default sql.distsql.broadcast_limit_rows
    # equals customer's padded rows, so customer stays MIRROR and
    # lineitem x (orders x customer) goes BY_HASH through the all_to_all
    # (checked on the compiled text below); at a rehearsal's scale every
    # build is under the limit and no exchange is compiled
    want = Q.q3_oracle(gen)
    dist = Session(catalog, capacity=CAPACITY)
    dist.execute("set distsql = always")

    def rows_of(res):
        return [(int(res["l_orderkey"][i]), int(res["revenue"][i]),
                 int(res["o_orderdate"][i]))
                for i in range(len(res["l_orderkey"]))]

    results = {}
    for run in ("cold", "warm"):
        with obs.statement() as seen:
            _kind, res, _schema = dist.execute(Q3_SQL)
        results["dist"] = rows_of(res)
        check(results["dist"] == want, f"q3 on four chips ({run}) != "
              f"oracle: {results['dist'][:3]} != {want[:3]}")
        check_counters(seen, f"q3 dist {run}", want_tier="dist",
                       want_stage="dist.exec")
        if run == "warm":
            check(seen["compiles"] == 0 and seen["cache_loads"] == 0,
                  f"q3 dist warm compiled ({seen['compiles']})")
        emit(statement_line(seen, phase="q3_dist4", run=run,
                            rows=len(results["dist"]),
                            matches_oracle=True))

    progs = [e for e in dist_flow._PROGS.values() if e is not None]
    check(bool(progs), "no distributed program was compiled")
    by_hash = any(e.a2a_bytes for e in progs)
    check(by_hash or coster_stale,
          "no join of Q3 at SF1 went BY_HASH: nothing crossed the ICI")
    check(not by_hash
          or any("all-to-all" in e.compiled.as_text() for e in progs),
          "no all-to-all in the compiled distributed program")
    shard_devs = {}
    for img in ingest._CACHE.values():
        if img.role == ingest.SHARDED:
            names = tuple(f.name for f in img.schema)
            shard_devs[names] = sorted(
                str(s.device) for s in img.bufs.addressable_shards)
    li = [d for names, d in shard_devs.items()
          if any(n.startswith("l_") for n in names)]
    check(bool(li), f"no sharded lineitem image: {shard_devs}")
    for d in li:
        check(len(set(d)) == 4, f"lineitem shards sit on {d}, "
                                f"not on four distinct devices")
    emit({"phase": "q3_dist4.placement", "all_to_all": by_hash,
          "lineitem_shard_devices": li[0],
          "sharded_images": len(shard_devs)})

    sess = Session(catalog, capacity=CAPACITY)
    forced = place(lambda text: sess.execute(text)[1] or [], Q3_SQL,
                   coster_stale)
    for run in ("cold", "warm"):
        with obs.statement() as seen:
            _kind, res, _schema = sess.execute(Q3_SQL)
        results["one"] = rows_of(res)
        check_counters(seen, f"q3 one chip {run}", want_tier="fused",
                       want_stage="fused.exec")
        emit(statement_line(seen, phase="q3_one_chip", run=run,
                            forced_to_device=forced,
                            rows=len(results["one"])))
    check(results["one"] == results["dist"] == want,
          "q3: four chips, one chip and the oracle do not agree")
    emit({"phase": "q3_dist4.agree", "four_chips_eq_one_chip_eq_oracle":
          True})


# ------------------------------------------------------------------ main --

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse-sf", type=float, default=None,
                    metavar="SF", help="CPU rehearsal at this tiny TPC-H "
                    "scale; the last line still names the real platform")
    args = ap.parse_args(argv)
    rehearse = args.rehearse_sf is not None

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" and not rehearse:
        print(f"chip_smoke: no TPU (jax.devices()[0].platform = "
              f"{dev.platform!r}); nothing run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX reports {len(devs)}", file=sys.stderr)
        return 2

    sys.path.insert(0, HERE)
    import cockroach_tpu  # noqa: F401 — x64 + the compile-cache resolver

    global _out_file
    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    _out_file = open(os.path.join(out_dir, "chip_smoke.jsonl"), "a")

    from cockroach_tpu.workload.tpch import TPCH

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    sf = args.rehearse_sf if rehearse else 1.0
    emit({"phase": "start", "device": device, "sf": sf,
          "seed": args.seed, "chips": args.chips, "rehearsal": rehearse,
          "jax": jax.__version__,
          "compile_cache_dir": jax.config.jax_compilation_cache_dir})
    t_all = time.perf_counter()
    obs = Observer()
    gen = TPCH(sf=sf, seed=args.seed)
    try:
        store = make_store()
        # a tiny rehearsal scale belongs on the host by any coster, so a
        # rehearsal may force the device; a chip run may only where the
        # coster's floor is far off the round trip measured here
        if args.chips == 4:
            phase_four_chips(obs, store, gen, coster_stale=rehearse)
        else:
            from cockroach_tpu.sql import cost

            trip = phase_device(jax, rehearse)
            stale = rehearse or cost.DISPATCH_FLOOR_S > 10 * trip
            phase_tpch(obs, store, gen, dev, stale)
            phase_ycsb_and_write(
                obs, store, args.seed,
                max(2000, int(YCSB_ROWS * min(1.0, sf))), stale)
    except (SmokeFailure, AssertionError) as e:
        emit({"phase": "failed", "error": f"{type(e).__name__}: {e}"[:2000],
              "seconds": time.perf_counter() - t_all})
        return 1
    emit({"phase": "done", "seconds": time.perf_counter() - t_all})
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except BaseException:  # noqa: BLE001 — report, then exit non-zero
        import traceback

        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads (pgwire accept loops, prewarm) must not hold the
    # process — or the chip — past the last line
    os._exit(rc)
