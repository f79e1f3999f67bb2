"""Parameter stream `zipf_scrambled`: YCSB's scrambled-zipfian key chooser.

Copied from cockroach_tpu/workload/ycsb.py (`Zipf`, `fnv_scramble`; PR 24):
inverse-CDF sampling of a rank in [0, n) with P(rank) ~ 1/(rank+1)**theta
(Gray et al., the YCSB generator), then an FNV-style scramble that spreads
the hot head across the key space. Spec fields: n, theta.

draw(spec, rng, size, state) -> list of 1-tuples of int keys in [0, n).
"""

from __future__ import annotations

import numpy as np


def fnv_scramble(ranks: np.ndarray, n: int) -> np.ndarray:
    h = ranks.astype(np.uint64) * np.uint64(0x100000001B3)
    h ^= h >> np.uint64(29)
    return (h % np.uint64(n)).astype(np.int64)


def prepare(spec: dict):
    """The zeta table, built once per process and shared by all clients."""
    n = int(spec["n"])
    ranks = np.arange(1, n + 1, dtype=np.float64)
    cdf = np.cumsum(1.0 / np.power(ranks, float(spec["theta"])))
    cdf /= cdf[-1]
    return {"n": n, "cdf": cdf}


def draw(spec: dict, rng: np.random.Generator, size: int, state):
    ranks = np.searchsorted(state["cdf"], rng.random(size)).astype(np.int64)
    ranks = np.minimum(ranks, state["n"] - 1)
    return [(int(k),) for k in fnv_scramble(ranks, state["n"])]
