"""Device time by plan operator, for the `op_*_ms`, `program_gaps_ms` and
`op_unattributed_pct` readers beside this file (not a metric itself).

The program measures this itself (cockroach_tpu/exec/device_profile.py):
every plan operator's lowering carries a `crdb.op<N>.<Kind>` scope in the
compiled executable, and `profile_prepared(catalog)` profiles, for every
prepared statement a whole-query runner has served, five SERIAL executions
of the statement's own program at its last binding, and books each device
instruction's self time to the operator whose scope it carries (`named`),
or that both its nearest scoped producers and consumers name
(`inferred`), or to nobody (`unattributed`).

On first use in a traced run this calls it once, AFTER the window: the
server is closed, the catalog, its images and its programs are live, and
the checks and the client's numbers are taken, so nothing the run judges
moves. It prints one line
  {"phase": "device_profile", "seconds": ..., "statements": [{"fingerprint",
   "executions", "chips", "scoped", "busy_ms", "gaps_ms", "launch_ms",
   "drain_ms", "lane_offset_ms", "operators": [{"n", "kind", "part",
   "label", "device_ms", "named_ms", "inferred_ms", "largest_chip_ms"}],
   "result_ms", "unattributed_ms", "unattributed_ops": [[instruction, ms,
   "nearest scoped producers -> consumers"]], "top_ops": [[instruction,
   ms, scope, how]]}]}
before the run's last line and keeps the result for the other readers. A
program without the facility (a parent of the PR that brought it) has
nothing to read: every reader returns None, nothing is printed and
nothing raises."""

import json
import time

_UNREAD = object()
_statements = _UNREAD


def statements(ctx):
    """The profiles of the run's prepared statements (a list of dicts),
    or None where the program cannot give them."""
    global _statements
    if _statements is not _UNREAD:
        return _statements
    _statements = None
    try:
        from cockroach_tpu.exec import device_profile
    except ImportError:
        return None
    t0 = time.perf_counter()
    line = {"phase": "device_profile"}
    try:
        got = device_profile.profile_prepared(ctx["load"]["catalog"],
                                              repeats=5)
        _statements = [dict(prof, fingerprint=fp) for fp, prof in got.items()]
        line["statements"] = _statements
    except Exception as e:  # noqa: BLE001: a reading, never the run's fate
        line["error"] = f"{type(e).__name__}: {e}"[:500]
    line["seconds"] = time.perf_counter() - t0
    print(json.dumps(line, sort_keys=True, default=str), flush=True)
    return _statements


def statement(ctx):
    """The profile of the cell's one statement: the only one there is,
    or the one that was busiest (a warm-up step may have prepared
    another text)."""
    profs = statements(ctx)
    if not profs:
        return None
    return max(profs, key=lambda p: p["busy_ms"])


def family_ms(ctx, kinds, parts=("",)):
    """Device self milliseconds a statement, summed over the operators
    whose class is in `kinds` (None: any) and whose part (""; "exchange"
    or "merge": what the distributed tracer adds to an operator) is in
    `parts`. None where the program has no such operator."""
    prof = statement(ctx)
    if prof is None:
        return None
    rows = [r for r in prof["operators"]
            if (kinds is None or r["kind"] in kinds) and r["part"] in parts]
    if not rows:
        return None
    return sum(r["device_ms"] for r in rows)
