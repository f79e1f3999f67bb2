"""Layer: host runtime. Of the window's slow statements (each took at
least twice its fingerprint's usual time), the milliseconds of excess NOT
spent waiting for the device: the window's delta of the sum of histogram
`sql_slow_stmt_host_seconds` (util/tracing.py: finish_statement). 0 when
no statement was slow; a program without the histogram has nothing to read
here. Source: program counter."""


def read(ctx):
    h = ctx["window"]["histograms"].get("sql_slow_stmt_host_seconds")
    return None if h is None else h["sum"] * 1e3
