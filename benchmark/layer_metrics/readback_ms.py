"""Layer: fused runner. Median seconds of the window's `fused.readback`
stage events (device to host copy of the packed result window) plus the
median of its `fused.unpack` events (the packed window into columns; 0 s
where the program has no such stage), in ms.
Source: program span seconds (traced run)."""

import statistics


def read(ctx):
    back = ctx["events"].get("fused.readback")
    if not back:
        return None
    unpack = ctx["events"].get("fused.unpack")
    return (statistics.median(back)
            + (statistics.median(unpack) if unpack else 0.0)) * 1e3
