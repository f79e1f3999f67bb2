"""Layer: distributed runner. MB ONE chip sends through the BY_HASH
exchanges (`lax.all_to_all`) of one statement: the window's `dist.a2a`
bytes over its events (one event a dispatch). The program reckons the
bytes from the traced shapes when it compiles: for every repartitioned
join, (chips - 1) x bucket rows x row width, the build once and the probe
once per routed batch; lanes that carry no row are sent like the others.
0 for a statement with no repartitioned join or a one-device mesh. A
program without the stage has nothing to read here.
Source: program counter (the stage's bytes and events)."""


def read(ctx):
    stage = ctx["window"]["stages"].get("dist.a2a")
    if not stage or not stage.get("events"):
        return None
    return stage["bytes"] / stage["events"] / 1e6
