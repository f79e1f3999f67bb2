"""Layer: wire + session. `sql.prepared_hit` events over statements
attempted in the window, in percent. A statement that enters through the
EXECUTE seam (Session.execute_spec) never consults the prepared cache, so
a cell of such statements has nothing to read here."""


def read(ctx):
    hits = ctx["window"]["stages"].get("sql.prepared_hit", {}).get("events")
    spans = sum(c for (name, _tier), c in ctx["window"]["tiers"].items()
                if name == "session.execute")
    if not hits or not spans:
        return None
    return 100.0 * hits / spans
