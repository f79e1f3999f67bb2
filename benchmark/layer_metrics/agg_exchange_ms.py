"""Layer: distributed runner. Milliseconds of device self time a statement
(mean over the chips) under the `.exchange` scopes of its `HashAggOp`s
(`crdb.op<N>.HashAggOp.exchange`): what the distributed tracer adds to an
aggregate that merges BY_HASH, the router's destination sort over the
shard's partial, the bucket slices and the `all_to_all` on the group key.
The final stage behind it (the merge of what arrived) is the operator's
own scope and reads in `op_agg_ms`; an aggregate merged by `all_gather`
has a `.merge` scope and none of this. `op_exchange_ms` holds this number
and the joins' exchanges and every merge beside it. A collective's time
is wait plus wire. A program in which the profile finds no such scope
(no aggregate is routed: the one-device rehearsal) reads 0, as
`op_exchange_ms` does; a program without the profile has nothing to read
here. From the program's own profile of five serial executions after the
window (`_device_profile.py`).
Source: device trace (the program's profile of its own executable)."""

from benchmark.layer_metrics import _device_profile


def read(ctx):
    ms = _device_profile.family_ms(ctx, ("HashAggOp",), ("exchange",))
    if ms is None and _device_profile.statement(ctx) is not None:
        return 0.0
    return ms
