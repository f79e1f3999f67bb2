"""Layer: distributed runner. MB ONE chip sends through the exchanges of
the aggregates one statement merges BY_HASH: the window's `dist.agg_route`
bytes over its events (one event a dispatch). The program reckons the bytes from the traced shapes when it
compiles: (chips - 1) x bucket rows x the partial's row width (the group
key, every accumulator column with its validity lane, the selection
lane); lanes that carry no group are sent like the others. `a2a_mb` holds
these bytes and the joins' beside them. 0 where no aggregate is routed
(a one-device mesh); a program without the stage (a parent of the PR that
brought it) has nothing to read here.
Source: program counter (the stage's bytes and events)."""


def read(ctx):
    stage = ctx["window"]["stages"].get("dist.agg_route")
    if not stage or not stage.get("events"):
        return None
    return stage["bytes"] / stage["events"] / 1e6
