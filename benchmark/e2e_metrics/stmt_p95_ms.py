"""95th percentile of the same latencies as stmt_p50_ms (linear
interpolation between closest ranks; the sample count is on the "window"
line)."""


def read(ctx):
    return ctx["client"].get("p95_ms")
