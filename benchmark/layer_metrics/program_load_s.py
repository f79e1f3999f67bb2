"""Layer: plan + compile. Seconds the whole run spent in `fused.compile`
(trace, lower, and compile or load from the persistent cache, of every
whole-query program) plus `fused.aot_compile` (the pre-warm ladder; 0 s
where none ran): the part of `first_exec_s` that is program load.
Source: program span seconds, whole run."""


def read(ctx):
    stages = ctx["whole"]["stages"]
    if "fused.compile" not in stages:
        return None
    return (stages["fused.compile"]["seconds"]
            + stages.get("fused.aot_compile", {}).get("seconds", 0.0))
