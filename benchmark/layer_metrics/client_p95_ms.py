"""Layer: wire + session. The 95th percentile of the statement latencies
at the wire client over the window (the number `e2e_metrics/stmt_p95_ms`
reads), for a cell whose tail is no verdict: on
`tpch-sf1-qgen.q6-2streams` a statement is a third host path under two
sessions, the machine's host runs fast or slow for minutes at a time, and
the tail spreads 4% from run to run against a bound of 1% (PERF.md
section 2, PR 48). Read here in the traced run, so that the ledger keeps a
reading of the two-session tail of the prepared path; a window with no
correct statement has nothing to read. Source: host clock at the client."""

from benchmark.e2e_metrics.stmt_p95_ms import read  # noqa: F401
