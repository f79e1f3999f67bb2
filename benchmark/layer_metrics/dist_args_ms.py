"""Layer: distributed runner. Median seconds of the window's `dist.args`
events, in ms: the host time ONE dispatch of a distributed program spends
placing the statement's bound values on the mesh, replicated (one
`device_put` to `NamedSharding(mesh, P())` an argument: the packed int64
vector of the scalar slots, and one boolean table over a dictionary for
each bound LIKE pattern; Q9's is 262,144 entries). The stage is opened
inside `dist.dispatch`, so the time is part of `dist_exec_ms`, named apart
here because it grows with the arguments and the chips and not with the
statement's rows; its `bytes` are what is placed (once, not a chip) and its
`rows` the number of arguments. A statement without bound values on the
mesh, or a program without the stage, has nothing to read here.
Source: program span seconds (traced run)."""

import statistics


def read(ctx):
    events = ctx["events"].get("dist.args")
    if not events:
        return None
    return statistics.median(events) * 1e3
