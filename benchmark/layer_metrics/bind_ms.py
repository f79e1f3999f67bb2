"""Layer: wire + session. Median seconds of the window's `wire.parse`
events plus median seconds of its `wire.bind` events, in ms: the host
time a statement sent by the extended protocol spends before its Execute
message. Both stages are opened outside the root span `wire.statement`,
so `stmt_host_ms` cannot see them. `wire.bind` holds the serving queue's
match on the bound text and, inside stage `sql.bind_params`, the typing of
the values against the statement's prepared entry, the dictionary lookup
of a string and the host folding of parameter arithmetic. A cell sent by
the simple protocol, or a program without the stages, has nothing to read
here. Source: program span seconds (traced run)."""

import statistics


def read(ctx):
    parse = ctx["events"].get("wire.parse")
    bind = ctx["events"].get("wire.bind")
    if not parse or not bind:
        return None
    return (statistics.median(parse) + statistics.median(bind)) * 1e3
