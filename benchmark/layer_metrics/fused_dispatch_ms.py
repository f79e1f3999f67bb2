"""Layer: fused runner. Median seconds of the window's `fused.dispatch`
stage events, in ms: inside `fused.exec`, from the call of the compiled
program until it returns (the host enqueues; the device may not have
started). A program that does not split `fused.exec` has nothing to read
here. Source: program span seconds (traced run)."""

import statistics


def read(ctx):
    secs = ctx["events"].get("fused.dispatch")
    if not secs:
        return None
    return statistics.median(secs) * 1e3
