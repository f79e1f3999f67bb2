"""Layer: distributed runner. Median seconds of the window's
`dist.readback` stage events (device to host copy of the packed result
window, replicated on every chip, read from one) plus the median of its
`dist.unpack` events (the packed window into columns; 0 s where the
program has no such stage), in ms: `readback_ms`'s twin on the mesh.
Source: program span seconds (traced run)."""

import statistics


def read(ctx):
    back = ctx["events"].get("dist.readback")
    if not back:
        return None
    unpack = ctx["events"].get("dist.unpack")
    return (statistics.median(back)
            + (statistics.median(unpack) if unpack else 0.0)) * 1e3
