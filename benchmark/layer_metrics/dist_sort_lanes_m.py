"""Layer: distributed runner. Millions of lanes ONE chip passes through
key sorts in one statement's distributed program: the window's `rows` of
stage `dist.sort_lanes` over its events (one event a dispatch). The
program reckons the lanes from the traced shapes when it compiles, as
`fused.sort_lanes` does on one chip (`sort_lanes_m`): for every
materialized join the probe's plus the build's capacity AS THE CHIP SEES
THEM (a shard's lanes, or n_dev x bucket behind an exchange), for an
aggregate lowered through one sort its input's capacity; and, which the
one-chip stage has no twin of, the routers' destination sorts: every lane
of a side that is routed BY_HASH is sorted by destination once before the
all_to_all. Lanes that carry no row are sorted like the others. It is to
the mesh's joins what `a2a_mb` is to its exchanges: a layout change (a
Shrink below a router, a bucket sized from the estimate, a build that
turns MIRROR) moves it before it moves a millisecond. A program without
the stage has nothing to read here.
Source: program counter (the stage's rows and events)."""


def read(ctx):
    stage = ctx["window"]["stages"].get("dist.sort_lanes")
    if not stage or not stage.get("events"):
        return None
    return stage["rows"] / stage["events"] / 1e6
