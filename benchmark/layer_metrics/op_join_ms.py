"""Layer: kernels. Milliseconds of device self time a statement under the
scopes of its `JoinOp`s (`crdb.op<N>.JoinOp`): key sorts, scans, the
compaction and the row gathers of every join, a join lowered as one step
with the Shrink above it included; on the mesh WITHOUT what the exchange
adds (`op_exchange_ms`). From the program's own profile of five serial
executions after the window (`_device_profile.py`).
Source: device trace (the program's profile of its own executable)."""

from benchmark.layer_metrics import _device_profile


def read(ctx):
    return _device_profile.family_ms(ctx, ("JoinOp",))
