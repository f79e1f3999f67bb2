"""Layer: host runtime. Milliseconds the window spent in collections of
the Python runtime: the window's delta of the sum of histogram
`runtime_gc_pause_seconds`, which the program observes from a gc callback
(every thread of the server waits for a collection). 0 when none ran; a
program without the histogram has nothing to read here. How many of them
were full collections is counter `runtime_gc_full_total` on the run's
`window` line. Source: program counter."""


def read(ctx):
    h = ctx["window"]["histograms"].get("runtime_gc_pause_seconds")
    return None if h is None else h["sum"] * 1e3
