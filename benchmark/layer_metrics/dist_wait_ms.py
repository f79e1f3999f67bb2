"""Layer: distributed runner. Median seconds of the window's `dist.wait`
stage events, in ms: inside `dist.exec`, the `block_until_ready` of the
dispatched program (the chips run it, exchanges included). The dispatch
is `dist_exec_ms` less this. A program that does not split `dist.exec`
has nothing to read here. Source: program span seconds (traced run)."""

import statistics


def read(ctx):
    secs = ctx["events"].get("dist.wait")
    if not secs:
        return None
    return statistics.median(secs) * 1e3
