"""Layer: device. 100 * (1 - union of device-operation intervals over the
traced window); the same busy_s and window_s go into the last line's
`device`."""


def read(ctx):
    if not ctx["trace"]:
        return None
    return ctx["trace"]["idle_pct"]
