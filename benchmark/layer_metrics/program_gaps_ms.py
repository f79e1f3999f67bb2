"""Layer: fused runner (the distributed runner on the mesh). Milliseconds
a statement in which the device is idle INSIDE its program: first device
instruction to last, less the union of the instructions (mean over the
chips): the pauses between a thousand small operations, which
`fused_wait_ms` / `dist_wait_ms` hold but no instruction does. From the
program's own profile of five serial executions after the window
(`_device_profile.py`).
Source: device trace (the program's profile of its own executable)."""

from benchmark.layer_metrics import _device_profile


def read(ctx):
    prof = _device_profile.statement(ctx)
    return None if prof is None else prof["gaps_ms"]
