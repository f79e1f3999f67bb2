"""Layer: scan images + storage. Milliseconds of device self time a
statement under the scopes of its `ScanOp`s (`crdb.op<N>.ScanOp`): the
flat unpack of the stacked scan images (slices off the byte image, the
relayout `copy`, bytes assembled into words) as far as the compiled
program's instructions name it or sit between instructions that do; what
XLA made of the unpack and shares with the first filter counts where its
fusion's name puts it. From the program's own profile of five serial
executions after the window (`_device_profile.py`).
Source: device trace (the program's profile of its own executable)."""

from benchmark.layer_metrics import _device_profile


def read(ctx):
    return _device_profile.family_ms(ctx, ("ScanOp",))
