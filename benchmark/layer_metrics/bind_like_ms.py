"""Layer: wire + session. Median seconds of the window's `sql.bind_like`
events, in ms: the host time ONE Bind spends turning a bound LIKE pattern
into the program's argument, a boolean table over the column's dictionary
(sql/params.py matches the pattern against every dictionary entry that
holds its longest literal; the stage's `rows` are the dictionary's
entries). The stage is opened inside `sql.bind_params`, inside `wire.bind`:
the time is part of `bind_ms`, named apart here because it grows with the
dictionary and not with the statement. A cell without a bound pattern, or a
program without the stage, has nothing to read here.
Source: program span seconds (traced run)."""

import statistics


def read(ctx):
    events = ctx["events"].get("sql.bind_like")
    if not events:
        return None
    return statistics.median(events) * 1e3
