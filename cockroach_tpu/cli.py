"""CLI — the operator surface (SURVEY.md L9; reference: pkg/cli cobra
commands `cockroach sql|demo|workload|...`, pkg/workload generators).

    python -m cockroach_tpu sql [--sf X] [-e SQL ...]
    python -m cockroach_tpu demo [-e SQL ...]
    python -m cockroach_tpu workload tpch|ycsb [...]

`sql` opens an interactive shell over the TPC-H catalog (generated
data); `demo` boots an in-process 3-node replicated cluster, loads a
sample table through the DistSender, and opens the shell over the MVCC
catalog — the `cockroach demo` analog. Both support EXPLAIN [ANALYZE].
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import List, Optional

import numpy as np


# ------------------------------------------------------------- rendering --

def decimal_text(v: int, scale: int) -> str:
    """Exact scaled-int64 -> decimal text (no float round trip)."""
    if scale == 0:
        return str(v)
    sign = "-" if v < 0 else ""
    q, r = divmod(abs(int(v)), 10 ** scale)
    return f"{sign}{q}.{r:0{scale}d}"


def decode_column(vals, valid, ty, dictionary) -> List[Optional[str]]:
    """One result column -> text values (None = SQL NULL). The single
    decode used by the CLI table renderer and the pgwire data rows."""
    import datetime as _dt

    from cockroach_tpu.coldata.batch import Kind

    # fast path for the overwhelmingly common shape — a plain integer
    # column with no dictionary and no special rendering: tolist()
    # converts to Python ints in C, so the per-element cost is one str()
    # instead of an isinstance chain over np scalars (this is the pgwire
    # serving path's per-row hot loop)
    a = np.asarray(vals) if not isinstance(vals, np.ndarray) else vals
    if (dictionary is None and a.ndim == 1 and a.dtype.kind in "iu"
            and (ty is None or ty.kind not in (Kind.DECIMAL, Kind.DATE,
                                               Kind.VECTOR))):
        out = [str(x) for x in a.tolist()]
        if valid is not None and len(valid) == len(out):
            vv = np.asarray(valid)
            if not vv.all():
                for i in np.nonzero(~vv)[0].tolist():
                    out[i] = None
        return out

    epoch = _dt.date(1970, 1, 1)
    out: List[Optional[str]] = []
    for i in range(len(vals)):
        if valid is not None and len(valid) == len(vals) \
                and not bool(valid[i]):
            out.append(None)
        elif dictionary is not None:
            code = int(vals[i])
            out.append(str(dictionary[code])
                       if 0 <= code < len(dictionary) else f"?{code}")
        elif ty is not None and ty.kind is Kind.DECIMAL:
            out.append(decimal_text(int(vals[i]), ty.scale))
        elif ty is not None and ty.kind is Kind.DATE:
            out.append(str(epoch + _dt.timedelta(days=int(vals[i]))))
        elif (ty is not None and ty.kind is Kind.VECTOR) \
                or isinstance(vals[i], np.ndarray):
            # pgvector text format: '[1,2.5,...]'
            out.append("[" + ",".join(
                f"{float(x):g}" for x in np.asarray(vals[i]).ravel()) + "]")
        elif isinstance(vals[i], (np.floating, float)):
            out.append(f"{float(vals[i]):.4f}")
        else:
            out.append(str(vals[i]))
    return out


def format_rows(result: dict, schema, limit: int = 25) -> List[str]:
    """Columns dict -> aligned text table (dictionary strings decoded)."""
    names = [n for n in result if not n.endswith("__valid")]
    if not names:
        return ["(no columns)"]
    decoded = {}
    for n in names:
        vals = result[n]
        valid = result.get(n + "__valid")
        d = None
        ty = None
        if schema is not None:
            try:
                ty = schema.field(n).type
                d = schema.dictionary(n)
            except KeyError:
                pass
        col = decode_column(vals, valid, ty, d)
        decoded[n] = [("NULL" if v is None else v) for v in col]
    n_rows = len(decoded[names[0]])
    shown = min(n_rows, limit)
    widths = {n: max(len(n), *(len(decoded[n][i]) for i in range(shown))
                     if shown else [len(n)]) for n in names}
    sep = "+".join("-" * (widths[n] + 2) for n in names)
    lines = [" | ".join(n.ljust(widths[n]) for n in names), sep]
    for i in range(shown):
        lines.append(" | ".join(decoded[n][i].ljust(widths[n])
                                for n in names))
    if n_rows > shown:
        lines.append(f"... ({n_rows} rows total)")
    else:
        lines.append(f"({n_rows} row{'s' if n_rows != 1 else ''})")
    return lines


def split_statements(buf: str):
    """Split buffered input on ';' outside string literals ('' escapes).
    -> (complete statements, remaining buffer)."""
    stmts = []
    cur = []
    in_str = False
    i = 0
    while i < len(buf):
        ch = buf[i]
        if ch == "'":
            in_str = not in_str
            cur.append(ch)
        elif ch == ";" and not in_str:
            s = "".join(cur).strip()
            if s:
                stmts.append(s)
            cur = []
        else:
            cur.append(ch)
        i += 1
    return stmts, "".join(cur)


# ----------------------------------------------------------------- shell --

def run_statement(sql: str, catalog, capacity: int,
                  session=None) -> List[str]:
    from cockroach_tpu.sql.bind import BindError
    from cockroach_tpu.sql.explain import execute_with_plan
    from cockroach_tpu.sql.parser import ParseError

    t0 = time.perf_counter()
    try:
        if session is not None:
            kind, payload, schema = session.execute(sql)
        else:
            kind, payload, schema = execute_with_plan(sql, catalog,
                                                      capacity)
    except (ParseError, BindError) as e:
        return [f"error: {e}"]
    except Exception as e:  # engine errors must not kill the shell
        return [f"error: {type(e).__name__}: {e}"]
    elapsed = time.perf_counter() - t0
    if kind == "explain":
        return list(payload)
    if kind == "ok":
        return [str(payload), f"time: {elapsed * 1e3:.0f}ms"]
    lines = format_rows(payload, schema)
    lines.append(f"time: {elapsed * 1e3:.0f}ms")
    return lines


def shell(catalog, capacity: int, statements: Optional[List[str]] = None,
          tables: Optional[List[str]] = None, session=None):
    if statements:
        for s in statements:
            for line in run_statement(s, catalog, capacity, session):
                print(line)
        return
    print("cockroach_tpu SQL shell — \\q quits, \\d lists tables, "
          "EXPLAIN [ANALYZE] supported; end statements with ;")
    buf = ""
    while True:
        try:
            prompt = "> " if not buf else "… "
            line = input(prompt)
        except (EOFError, KeyboardInterrupt):
            print()
            return
        if line.strip() in ("\\q", "exit", "quit"):
            return
        if line.strip() == "\\d":
            for t in (tables or []):
                print(" ", t)
            continue
        buf += line + "\n"
        stmts, buf = split_statements(buf)
        for stmt in stmts:
            for out in run_statement(stmt, catalog, capacity, session):
                print(out)


# -------------------------------------------------------------- commands --

def cmd_sql(args):
    from cockroach_tpu.sql import TPCHCatalog
    from cockroach_tpu.workload.tpch import TPCH

    gen = TPCH(sf=args.sf)
    shell(TPCHCatalog(gen), args.capacity, args.execute,
          tables=["lineitem", "orders", "customer", "part", "partsupp",
                  "supplier", "nation", "region"])


def cmd_demo(args):
    import struct

    from cockroach_tpu.kv import Cluster, DistSender
    from cockroach_tpu.sql.session import (
        Session, SessionCatalog, TableDescriptor,
    )
    from cockroach_tpu.storage.mvcc import MVCCStore

    print("starting in-process 3-node replicated cluster ...")
    cluster = Cluster(3, seed=0)
    cluster.await_leases()
    ds = DistSender(cluster)
    n = args.rows
    rng = np.random.default_rng(0)
    vals = rng.integers(0, 1000, n)
    for i in range(n):
        key = struct.pack(">HQ", 1, i)
        row = struct.pack("<qq", int(i), int(vals[i]))
        ds.write([("put", key, row)])
    cluster.pump(30)
    node = cluster.nodes[1]
    store = MVCCStore(engine=node.engine, clock=node.clock)
    catalog = SessionCatalog(store)
    catalog.save(TableDescriptor(
        1, "kv", [("id", "int"), ("val", "int")], None,
        next_rowid=n + 1))
    session = Session(catalog, capacity=args.capacity)
    print(f"demo table 'kv' ({n} rows) replicated across 3 nodes; "
          "SQL (incl. CREATE TABLE / INSERT / UPDATE / DELETE) runs "
          "over node 1's MVCC store")
    shell(catalog, args.capacity, args.execute, tables=["kv"],
          session=session)


def cmd_workload(args):
    if args.generator == "tpch":
        from cockroach_tpu.exec import collect
        from cockroach_tpu.workload.tpch import TPCH
        from cockroach_tpu.workload import tpch_queries as Q

        gen = TPCH(sf=args.sf)
        queries = [int(q) for q in args.queries.split(",")]
        for qn in queries:
            flow = Q.QUERIES[qn](gen, args.capacity)
            t0 = time.perf_counter()
            collect(flow)
            cold = time.perf_counter() - t0
            times = []
            for _ in range(args.runs):
                flow = Q.QUERIES[qn](gen, args.capacity)
                t0 = time.perf_counter()
                collect(flow)
                times.append(time.perf_counter() - t0)
            best = min(times) if times else cold
            print(f"q{qn}: cold {cold * 1e3:.0f}ms, "
                  f"best-of-{args.runs} {best * 1e3:.0f}ms")
    elif args.generator == "tpcc":
        from cockroach_tpu.kv.txn import DB
        from cockroach_tpu.storage import MVCCStore
        from cockroach_tpu.util.hlc import HLC, ManualClock
        from cockroach_tpu.workload import tpcc

        store = MVCCStore(clock=HLC(ManualClock(1000)))
        t0 = time.perf_counter()
        tpcc.load(store, n_warehouses=1)
        print(f"loaded 1 warehouse in {time.perf_counter() - t0:.2f}s")
        mix = tpcc.TPCC(DB(store))
        t0 = time.perf_counter()
        out = mix.run_mix(args.ops)
        dt = time.perf_counter() - t0
        tpcc.check_consistency(store)
        print(f"tpcc: {out['new_orders']} new orders, "
              f"{out['payments']} payments in {dt:.2f}s "
              f"({out['new_orders'] / dt * 60:,.0f} tpmC-ish); "
              f"consistency checks PASSED")
    else:  # ycsb
        from cockroach_tpu.storage import MVCCStore
        from cockroach_tpu.util.hlc import HLC, ManualClock
        from cockroach_tpu.workload import ycsb

        rng = np.random.default_rng(0)
        store = MVCCStore(clock=HLC(ManualClock(1000)))
        t0 = time.perf_counter()
        ycsb.load(store, args.records, rng)
        print(f"loaded {args.records} records in "
              f"{time.perf_counter() - t0:.2f}s")
        ops_per_sec, rows = ycsb.run_e(store, args.ops, args.records, rng)
        print(f"ycsb-e: {ops_per_sec:,.0f} ops/s "
              f"({rows} rows scanned over {args.ops} ops)")


def cmd_start(args):
    """`cockroach start-single-node` analog: pgwire + HTTP status over
    a storage-backed session catalog; blocks until interrupted."""
    from cockroach_tpu.server.status import StatusServer
    from cockroach_tpu.sql.pgwire import PgServer
    from cockroach_tpu.sql.session import SessionCatalog
    from cockroach_tpu.storage.mvcc import MVCCStore

    store = MVCCStore()
    catalog = SessionCatalog(store)
    pg = PgServer(catalog, capacity=args.capacity,
                  port=args.pg_port).start()
    # pgwire startup attaches a prewarm service when the plan vault is
    # configured; surface its job progress at /_status/jobs
    prewarm_svc = getattr(catalog, "_prewarm_service", None)
    status = StatusServer(
        port=args.http_port,
        jobs_registry=prewarm_svc.registry if prewarm_svc else None,
    ).start()
    print(f"pgwire listening on {pg.addr[0]}:{pg.addr[1]}")
    print(f"status HTTP on http://{status.addr[0]}:{status.addr[1]} "
          "(/health, /_status/vars, /_status/statements, /_status/jobs)")
    print("ready — connect with any PostgreSQL v3 client; ^C stops")
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        print("\nshutting down")
    finally:
        pg.close()
        status.close()


def cmd_debug(args):
    """`cockroach debug zip` analog: scrape a running node's status
    endpoints into one diagnostics archive."""
    from cockroach_tpu.server.debugzip import collect_http

    if args.verb != "zip":
        raise SystemExit(f"unknown debug verb {args.verb!r}")
    out = collect_http(args.url, args.out)
    print(f"wrote {out}")


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="cockroach_tpu",
        description="TPU-native distributed SQL engine CLI")
    sub = ap.add_subparsers(dest="command", required=True)

    sp = sub.add_parser("sql", help="SQL shell over generated TPC-H data")
    sp.add_argument("--sf", type=float, default=0.01)
    sp.add_argument("--capacity", type=int, default=1 << 14)
    sp.add_argument("-e", "--execute", action="append",
                    help="execute statement and exit (repeatable)")
    sp.set_defaults(fn=cmd_sql)

    dp = sub.add_parser("demo", help="in-process replicated cluster demo")
    dp.add_argument("--rows", type=int, default=1000)
    dp.add_argument("--capacity", type=int, default=1 << 12)
    dp.add_argument("-e", "--execute", action="append")
    dp.set_defaults(fn=cmd_demo)

    wp = sub.add_parser("workload", help="run a load generator")
    wp.add_argument("generator", choices=["tpch", "ycsb", "tpcc"])
    wp.add_argument("--sf", type=float, default=0.01)
    wp.add_argument("--capacity", type=int, default=1 << 14)
    wp.add_argument("--queries", default="1,3,6,9,18")
    wp.add_argument("--runs", type=int, default=3)
    wp.add_argument("--records", type=int, default=100000)
    wp.add_argument("--ops", type=int, default=1000)
    wp.set_defaults(fn=cmd_workload)

    st = sub.add_parser("start",
                        help="single-node server: pgwire + status HTTP")
    st.add_argument("--pg-port", type=int, default=26257)
    st.add_argument("--http-port", type=int, default=8080)
    st.add_argument("--capacity", type=int, default=1 << 14)
    st.set_defaults(fn=cmd_start)

    dz = sub.add_parser("debug",
                        help="diagnostics: `debug zip` collects a "
                             "node's status APIs into one archive")
    dz.add_argument("verb", choices=["zip"])
    dz.add_argument("--url", default="http://127.0.0.1:8080",
                    help="status HTTP base URL of a running node")
    dz.add_argument("--out", default="debug.zip",
                    help="output archive path")
    dz.set_defaults(fn=cmd_debug)

    args = ap.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
