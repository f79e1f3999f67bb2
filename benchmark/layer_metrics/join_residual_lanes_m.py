"""Layer: fused runner. Millions of lanes ONE statement's device program
passes through its semi and anti joins that carry a RESIDUAL, a predicate
beside the key equality (a decorrelated `EXISTS (.. b.k = a.k AND b.x <>
a.x)`, TPC-H Q21's two): the window's `rows` of stage
`fused.join_residual_lanes` over its events (one event a dispatch). The
program reckons the lanes from the traced shapes when it compiles: probe
plus build capacity of every such join. They say where the subquery joins
stand in the plan: above the Shrink of the most selective join they probe
its lanes (Q21 at SF1: 2 x (262,144 + 2,097,152)), below it the fact
table's (2 x (8,388,608 + 2,097,152)), and a change of join order moves
this before it moves a millisecond. A program without the stage (the
parent's, and every statement without a correlated subquery reads 0
there) has nothing to read here.
Source: program counter (the stage's rows and events)."""


def read(ctx):
    stage = ctx["window"]["stages"].get("fused.join_residual_lanes")
    if not stage or not stage.get("events"):
        return None
    return stage["rows"] / stage["events"] / 1e6
