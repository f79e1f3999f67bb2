"""Layer: fused runner. Median seconds of the window's `fused.wait` stage
events, in ms: inside `fused.exec`, the `block_until_ready` of the
dispatched program (the device runs it, after whatever another session
enqueued before it). A program that does not split `fused.exec` has
nothing to read here. Source: program span seconds (traced run)."""

import statistics


def read(ctx):
    secs = ctx["events"].get("fused.wait")
    if not secs:
        return None
    return statistics.median(secs) * 1e3
