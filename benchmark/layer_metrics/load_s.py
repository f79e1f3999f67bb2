"""Layer: scan images + storage. Seconds of the bulk ingest of the cell's
tables (generation from the seed included)."""


def read(ctx):
    return ctx["load"]["load_s"]
