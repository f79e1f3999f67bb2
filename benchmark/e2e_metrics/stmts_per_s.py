"""Statements answered correctly inside the window, all clients, over the
seconds of the window."""


def read(ctx):
    return ctx["client"].get("per_s")
