"""Layer: fused runner. Median seconds of the window's `fused.exec` stage
events (dispatch to block_until_ready of the whole-query program), in ms."""

import statistics


def read(ctx):
    secs = ctx["events"].get("fused.exec")
    if not secs:
        return None
    return statistics.median(secs) * 1e3
