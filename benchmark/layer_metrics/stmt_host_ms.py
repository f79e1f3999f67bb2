"""Layer: wire + session. Median seconds of the window's
`wire.statement.host` stage events, in ms: what the program itself books,
at the finish of every served statement's root span, as the statement's
time on the host (the root `wire.statement`, message complete in the
buffer to the flush, minus what its `fused.exec` and `fused.readback`
spans cover). The inside twin of `host_path_ms`, on one clock; it leaves
out the kernel's socket path and the client's own time, which that one
includes. A program without the stage has nothing to read here.
Source: program span seconds (traced run)."""

import statistics


def read(ctx):
    secs = ctx["events"].get("wire.statement.host")
    if not secs:
        return None
    return statistics.median(secs) * 1e3
