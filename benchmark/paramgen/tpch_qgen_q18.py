"""Parameter stream `tpch_qgen_q18`: QGEN's substitution parameter of
TPC-H Q18, drawn afresh for every execution (specification rev. 3, clause
2.4.18.3; clause number and range from memory).

  (QUANTITY,): a whole number uniform in 312 .. 315, as the text a driver
  would bind ("312"): four bindings.

Spec fields: `quantity`: [lo, hi], optional. A configuration's `rehearse`
block sets it for the CPU rehearsal alone (SF 0.01 has no order over 312;
250 .. 253 leaves rows to compare); a cell's own file never does.

The harness seeds `rng` from (--seed, client). Runs in the client child:
numpy and the standard library only.

draw(spec, rng, size, state) -> list of 1-tuples of str;
corners(spec) -> the two ends of the range, for the warm-up step
(benchmark/warmup/qgen_domain.py).
"""

from __future__ import annotations

import numpy as np

QUANTITY = (312, 315)


def prepare(spec: dict):
    lo, hi = (int(v) for v in spec.get("quantity", QUANTITY))
    if lo > hi:
        raise ValueError(f"tpch_qgen_q18: empty range {lo}..{hi}")
    return lo, hi


def draw(spec: dict, rng: np.random.Generator, size: int, state):
    lo, hi = state
    return [(str(int(q)),) for q in rng.integers(lo, hi + 1, size)]


def corners(spec: dict):
    lo, hi = prepare(spec)
    return [(str(lo),), (str(hi),)]
