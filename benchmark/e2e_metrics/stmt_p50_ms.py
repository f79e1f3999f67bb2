"""Median statement latency at the wire client, send to the last byte of
the answer, over the statements answered correctly inside the window."""


def read(ctx):
    return ctx["client"].get("p50_ms")
