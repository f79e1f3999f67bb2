"""Layer: distributed runner. Milliseconds of device self time a statement
(mean over the chips) under every `.exchange` scope (what the distributed
tracer adds to a BY_HASH join: the router's destination sort, the bucket
slices, `all_to_all`) and every `.merge` scope (the `all_gather` and the
merging aggregate or top-K): the exchange told apart from the operators
behind it. A collective's time is wait plus wire. A mesh program in
which the profile finds no such scope reads 0 (its `device_profile` line
says `scoped: false` where the executable came from a tree without
scopes). From the program's own profile of five serial executions after
the window (`_device_profile.py`).
Source: device trace (the program's profile of its own executable)."""

from benchmark.layer_metrics import _device_profile


def read(ctx):
    ms = _device_profile.family_ms(ctx, None, ("exchange", "merge"))
    if ms is None and _device_profile.statement(ctx) is not None:
        return 0.0
    return ms
