"""Traffic kind `closed_loop`: N clients, each sending its next statement
only when the previous answer has arrived (TPC-H streams; `workload run
ycsb --concurrency N`).

One thread drives all connections through a selector, so the load
generator costs the same few CPU cycles whatever N is and no client
thread waits for another's interpreter lock. Runs in the client child
process: stdlib + numpy only, never JAX.

Cell parameters (benchmark/workloads/<cell>.json, "traffic_params"):
  clients           number of closed-loop connections
  warmup_per_client statements each client sends, untimed, before the window

Every client sends statement `i % len(statements)` of the cell (client i),
so a cell with one statement class sends one text. A statement's
parameters come from its "params" stream (benchmark/paramgen/<kind>.py),
seeded per client from (--seed, client index): the same seed gives the
same keys in the same order on every connection.

run(job, wait_go) -> result dict (see benchmark/client.py for the job).
"""

from __future__ import annotations

import importlib
import selectors
import time

import numpy as np

from benchmark import wire

_DRAW = 2048  # parameters drawn per refill


class _Client:
    __slots__ = ("idx", "stmt_idx", "stmt", "sock", "buf", "scan", "rng",
                 "gen", "state", "pending", "t_send", "cur", "sent")

    def __init__(self, idx, stmt_idx, stmt, sock, rng, gen, state):
        self.idx, self.stmt_idx, self.stmt, self.sock = (idx, stmt_idx,
                                                         stmt, sock)
        self.buf = bytearray()
        self.scan = 0
        self.rng, self.gen, self.state = rng, gen, state
        self.pending = []
        self.t_send = 0.0
        self.cur = None
        self.sent = 0

    def next_message(self):
        if self.gen is None:
            self.cur = ()
        else:
            if not self.pending:
                self.pending = self.gen.draw(self.stmt["params"], self.rng,
                                             _DRAW, self.state)[::-1]
            self.cur = self.pending.pop()
        if self.stmt.get("protocol", "simple") == "extended":
            return wire.extended_query(self.stmt["sql"], self.cur)
        return wire.simple_query(self.stmt["sql"])


def key_streams(job: dict, count: int):
    """The first `count` parameter tuples of every client, without a
    server: what the determinism test compares."""
    clients = _make_clients(job, connect=False)
    out = []
    for c in clients:
        seq = []
        for _ in range(count):
            c.next_message()
            seq.append(c.cur)
        out.append(seq)
    return out


def _make_clients(job: dict, connect: bool = True):
    stmts = job["statements"]
    n = int(job["traffic_params"]["clients"])
    states = {}
    clients = []
    for i in range(n):
        si = i % len(stmts)
        stmt = stmts[si]
        gen = state = None
        if stmt.get("params"):
            kind = stmt["params"]["kind"]
            gen = importlib.import_module(f"benchmark.paramgen.{kind}")
            if si not in states:
                states[si] = gen.prepare(stmt["params"])
            state = states[si]
        rng = np.random.default_rng([int(job["seed"]), i])
        sock = None
        if connect:
            # handshake and session set-up through the blocking client,
            # whose socket the loop then drives itself
            opened = wire.WireClient(job["addr"], timeout=job["timeout_s"])
            for text in job.get("session_setup", ()):
                _rows, code = opened.query(text)
                if code is not None:
                    raise RuntimeError(f"{text!r}: sqlstate {code}")
            sock = opened.s
        clients.append(_Client(i, si, stmt, sock, rng, gen, state))
    return clients


def _loop(clients, sel, stop_at, limit_each, timeout_s, record):
    """Closed loop until `stop_at` (monotonic) or until every client has
    sent `limit_each` statements; answers in flight at the end are drained
    and recorded like the others (the caller cuts them by time)."""
    active = 0
    for c in clients:
        c.sent = 0
        c.sock.setblocking(False)
        msg = c.next_message()
        c.t_send = time.perf_counter()
        c.sock.sendall(msg)
        c.sent = 1
        active += 1
    last_progress = time.perf_counter()
    while active:
        events = sel.select(timeout=1.0)
        now = time.perf_counter()
        if not events:
            if now - last_progress > timeout_s:
                raise TimeoutError(f"no answer for {timeout_s} s")
            continue
        last_progress = now
        for key, _mask in events:
            c = key.data
            try:
                chunk = c.sock.recv(1 << 16)
            except BlockingIOError:
                continue
            if not chunk:
                raise ConnectionError(f"client {c.idx}: server closed")
            c.buf += chunk
            end, c.scan = wire.response_end(c.buf, c.scan)
            if end < 0:
                continue  # the walk resumes at c.scan, a message boundary
            t_done = time.perf_counter()
            raw = bytes(c.buf[:end])
            del c.buf[:end]
            c.scan = 0
            record(c, c.t_send, t_done, raw)
            more = (t_done < stop_at if limit_each is None
                    else c.sent < limit_each)
            if more:
                msg = c.next_message()
                c.t_send = time.perf_counter()
                c.sock.sendall(msg)
                c.sent += 1
            else:
                active -= 1


def run(job: dict, wait_go) -> dict:
    tp = job["traffic_params"]
    clients = _make_clients(job)
    sel = selectors.DefaultSelector()
    for c in clients:
        sel.register(c.sock, selectors.EVENT_READ, c)
    try:
        # -- warm-up: the same connections, the same statements, untimed
        warm = []
        _loop(clients, sel, None, int(tp["warmup_per_client"]),
              job["timeout_s"],
              lambda c, t0, t1, raw: warm.append((t1 - t0, raw)))
        warm_errors = sum(1 for _dt, raw in warm
                          if wire.parse_response(raw)[1] is not None)
        wait_go({"warmup_statements": len(warm),
                 "warmup_errors": warm_errors,
                 "warmup_last_s": [dt for dt, _ in warm[-len(clients):]]})
        # -- the window
        records = []       # (client, stmt_idx, t_send, t_done, response id)
        responses = {}     # (stmt_idx, params, raw) -> id
        ordered = []

        def record(c, t0, t1, raw):
            key = (c.stmt_idx, c.cur, raw)
            rid = responses.get(key)
            if rid is None:
                rid = responses[key] = len(ordered)
                ordered.append(key)
            records.append((c.idx, c.stmt_idx, t0, t1, rid))

        wall0 = time.time()
        t_begin = time.perf_counter()
        _loop(clients, sel, t_begin + float(job["seconds"]), None,
              job["timeout_s"], record)
        t_end = time.perf_counter()
    finally:
        sel.close()
        for c in clients:
            try:
                c.sock.close()
            except OSError:
                pass
    parsed = []
    for stmt_idx, params, raw in ordered:
        rows, code = wire.parse_response(raw)
        parsed.append({"stmt": stmt_idx, "params": list(params),
                       "rows": rows, "code": code})
    return {"window_wall_start": wall0, "t_begin": t_begin,
            "drained_s": t_end - t_begin,
            "records": records, "responses": parsed}
