"""Layer: fused runner. Millions of lanes ONE statement's device program
passes through 64-bit scans inside its joins: the window's `rows` of
stage `fused.join_scan64_lanes` over its events (one event a dispatch).
The chip has no 64-bit lanes, so a scan over a 64-bit operand runs as
pairs of u32 and costs several times a 32-bit one (a flat s64 cummax of
8,650,752 lanes 15.7 ms on a v5e, a blocked s32 one 1.2). The program
reckons the lanes from the traced shapes when it compiles: probe plus
build capacity for every such scan a materialized join still runs, none
for a join that compacts under its Shrink, two for a resorting carry
join, one for a join on the hashed key. It is to the joins' scans what
`sort_lanes_m` is to their sorts: a change of lowering moves it before it
moves a millisecond. A program without the stage has nothing to read
here.
Source: program counter (the stage's rows and events)."""


def read(ctx):
    stage = ctx["window"]["stages"].get("fused.join_scan64_lanes")
    if not stage or not stage.get("events"):
        return None
    return stage["rows"] / stage["events"] / 1e6
