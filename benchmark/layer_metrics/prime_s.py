"""Layer: scan images + storage. Seconds the whole run spent in
`fused.prime` (scan walk, pack, stack and transfer of the statement's
scan images to the device; `prime_mb` counts its bytes).
Source: program span seconds, whole run."""


def read(ctx):
    stage = ctx["whole"]["stages"].get("fused.prime")
    return None if stage is None else stage["seconds"]
