"""Layer: host runtime. `stall_wait_ms` (the window's delta of the sum of
histogram `sql_slow_stmt_wait_seconds`, in ms: see that reader) under a
name of its own for a cell that does not report `stmts_per_s`, the metric
that one moves: a per-layer metric lists only cells that report what it
moves. Source: program counter."""

from benchmark.layer_metrics.stall_wait_ms import read  # noqa: F401
