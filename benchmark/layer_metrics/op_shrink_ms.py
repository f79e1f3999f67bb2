"""Layer: kernels. Milliseconds of device self time a statement under the
scopes of its `ShrinkOp`s standing alone (`crdb.op<N>.ShrinkOp`): the
`pred` argsort and the gathers of `shrink_traceable`. A Shrink lowered as
one step with the join under it leaves nothing here (the step is the
join's: `op_join_ms`), so 0 is a reading: every Shrink of the plan
compacts with its join. From the program's own profile of five serial
executions after the window (`_device_profile.py`).
Source: device trace (the program's profile of its own executable)."""

from benchmark.layer_metrics import _device_profile


def read(ctx):
    return _device_profile.family_ms(ctx, ("ShrinkOp",))
