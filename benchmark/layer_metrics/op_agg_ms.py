"""Layer: kernels. Milliseconds of device self time a statement under the
scopes of its `HashAggOp`s and `DistinctOp`s (`crdb.op<N>.HashAggOp`):
the aggregate's sort, scans and reductions, whichever lowering it took;
on the mesh WITHOUT the all_gather merge (`op_exchange_ms`). From the
program's own profile of five serial executions after the window
(`_device_profile.py`).
Source: device trace (the program's profile of its own executable)."""

from benchmark.layer_metrics import _device_profile


def read(ctx):
    return _device_profile.family_ms(ctx, ("HashAggOp", "DistinctOp"))
