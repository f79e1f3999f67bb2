"""Layer: distributed runner. Seconds the whole run spent in
`dist.compile` (trace, lower, and compile or load from the persistent
cache, of every distributed program): the part of `first_exec_s` that is
program load on the mesh; a cold compile of Q3's sharded program is
minutes, a cached load seconds. A program without the stage has nothing
to read here. Source: program span seconds, whole run."""


def read(ctx):
    stage = ctx["whole"]["stages"].get("dist.compile")
    return None if stage is None else stage["seconds"]
