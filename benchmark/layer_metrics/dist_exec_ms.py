"""Layer: distributed runner. Median seconds of the window's `dist.exec`
stage events (dispatch to block_until_ready of the one shard_map program
that is the whole statement on every chip), in ms: `fused_exec_ms`'s twin
on the mesh. A program without the stage has nothing to read here.
Source: program span seconds (traced run)."""

import statistics


def read(ctx):
    secs = ctx["events"].get("dist.exec")
    if not secs:
        return None
    return statistics.median(secs) * 1e3
