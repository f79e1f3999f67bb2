"""Layer: host runtime. `stall_host_ms` (the window's delta of the sum of
histogram `sql_slow_stmt_host_seconds`, in ms: see that reader) under a
name of its own for a cell that does not report `stmts_per_s`, the metric
that one moves: a per-layer metric lists only cells that report what it
moves. On `tpch-sf1-qgen.q6-2streams` it is the reading that tells a run in
the host's slow mode from a fast one (63 against 521 ms in the ledger's
PR 47 line). Source: program counter."""

from benchmark.layer_metrics.stall_host_ms import read  # noqa: F401
