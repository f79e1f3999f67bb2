"""Layer: wire + session. Client p50 minus the median seconds of the same
window's `fused.exec` stage events: that stage is one synchronous device
call (dispatch to block_until_ready), so what is left is wire, parse/bind,
session and row encoding. A cell whose statements do not run through
`fused.exec` (a batched serving cell, whose `serving.exec` stage is itself
mostly host and lock wait: PERF.md, PR 24) has nothing to read here and
brings a metric of its own.
Source: host clock at the client, program span seconds (traced run)."""

import statistics


def read(ctx):
    secs = ctx["events"].get("fused.exec")
    if not secs or "p50_ms" not in ctx["client"]:
        return None
    return ctx["client"]["p50_ms"] - statistics.median(secs) * 1e3
