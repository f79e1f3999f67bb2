"""Parameter streams: benchmark/paramgen/<kind>.py, found by the "kind" of
a statement's "params" in the cell file. Each has prepare(spec) -> state
and draw(spec, rng, size, state) -> list of parameter tuples."""

import importlib

import numpy as np


def first(statement: dict, seed: int) -> tuple:
    """One parameter tuple for a statement's first execution."""
    spec = statement.get("params")
    if not spec:
        return ()
    gen = importlib.import_module(f"benchmark.paramgen.{spec['kind']}")
    rng = np.random.default_rng([int(seed), 1 << 20])
    return gen.draw(spec, rng, 1, gen.prepare(spec))[0]
