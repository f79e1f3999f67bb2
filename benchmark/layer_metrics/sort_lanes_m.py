"""Layer: fused runner. Millions of lanes ONE statement's device program
passes through key sorts: the window's `rows` of stage `fused.sort_lanes`
over its events (one event a dispatch). The program reckons the lanes
from the traced shapes when it compiles: for every materialized join the
probe's plus the build's capacity, for an aggregate lowered through one
sort (the int-key aggregate, the group join) its input's capacity; lanes
that carry no row are sorted like the others. It is to the one-chip join
programs what `a2a_mb` is to the mesh: a plan change (a Shrink sized from
the estimate, a join order, one scan where the text has two) moves it
before it moves a millisecond. A program without the stage has nothing to
read here.
Source: program counter (the stage's rows and events)."""


def read(ctx):
    stage = ctx["window"]["stages"].get("fused.sort_lanes")
    if not stage or not stage.get("events"):
        return None
    return stage["rows"] / stage["events"] / 1e6
