"""Layer: scan images + storage. Seconds the whole run spent in
`storage.ingest` (`MVCCStore.ingest_table`: the engine's bulk ingest and
what follows it in the store): the program's part of `load_s`, whose rest
is the benchmark's own generation of the data from the seed. A program
that does not time its ingest has nothing to read here.
Source: program span seconds, whole run."""


def read(ctx):
    stage = ctx["whole"]["stages"].get("storage.ingest")
    return None if stage is None else stage["seconds"]
