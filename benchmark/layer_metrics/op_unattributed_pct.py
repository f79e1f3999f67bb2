"""Layer: kernels. Percent of a statement's device-busy time that the
program's profile books to NO plan operator: instructions that carry no
`crdb.` scope and whose nearest scoped producers and consumers do not
together name one operator (XLA's own copies and expansions on the
boundary between two operators). 100 for an executable compiled by a
tree without scopes. The profile never guesses by shape, by schedule
position or by one side alone, so this is what the `op_*_ms` metrics
leave out. From the program's own profile of five serial executions
after the window (`_device_profile.py`).
Source: device trace (the program's profile of its own executable)."""

from benchmark.layer_metrics import _device_profile


def read(ctx):
    prof = _device_profile.statement(ctx)
    if prof is None or prof["busy_ms"] <= 0:
        return None
    return 100.0 * prof["unattributed_ms"] / prof["busy_ms"]
