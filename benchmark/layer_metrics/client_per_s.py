"""Layer: wire + session. Statements answered correctly inside the window,
all clients, over its seconds (the number `e2e_metrics/stmts_per_s`
reads), for a cell whose rate is no verdict: on
`tpch-sf1-qgen.q6-2streams` two closed-loop clients answer 2 / (mean
latency) a second, the mean holds the tail, and the rate spreads 1.1%
between runs of one tree against a bound of 1% (PERF.md section 2, PR 48).
Read here in the traced run, so that the ledger keeps the cell's
throughput; a window with no correct statement has nothing to read.
Source: host clock at the client."""

from benchmark.e2e_metrics.stmts_per_s import read  # noqa: F401
