"""Layer: distributed runner. Bytes the whole run placed on the mesh's
devices as ingest-sharded and replicated scan images (stage `dist.ingest`:
a sharded image counts each shard once, a replicated one once per
device), in MB. `prime_mb` reads `scan.transfer`, which a sharded ingest
never counts. A program without the stage has nothing to read here.
Source: program counter (the stage's bytes), whole run."""


def read(ctx):
    stage = ctx["whole"]["stages"].get("dist.ingest")
    return None if stage is None else stage["bytes"] / 1e6
