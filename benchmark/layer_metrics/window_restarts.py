"""Layer: fused runner. The window's delta of counter
`sql_flow_restarts_total`: flow restarts (a capacity that one binding's
rows overflowed, widened and compiled again) inside the measured window.
A parameterised statement's one program is sized from the binding its plan
was made at and run on every other; the warm-up visits the corners of the
domain so that this reads 0, and a capacity tightened past a neighbouring
binding shows here (and as a compile in the window). A program that has
not registered the counter has nothing to read here.
Source: program counter."""


def read(ctx):
    n = ctx["window"]["counters"].get("sql_flow_restarts_total")
    return None if n is None else float(n)
