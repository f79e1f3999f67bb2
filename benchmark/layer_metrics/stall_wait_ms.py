"""Layer: host runtime. Of the window's slow statements (each took at
least twice its fingerprint's usual time), the milliseconds of excess
spent in `fused.wait` over that stage's usual time (blocked on the
device, or behind another session's program): the window's delta of the
sum of histogram `sql_slow_stmt_wait_seconds`. 0 when no statement was
slow; a program without the histogram has nothing to read here.
Source: program counter."""


def read(ctx):
    h = ctx["window"]["histograms"].get("sql_slow_stmt_wait_seconds")
    return None if h is None else h["sum"] * 1e3
