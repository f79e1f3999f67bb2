"""Parameter stream `tpch_qgen_q21`: QGEN's substitution parameter of
TPC-H Q21, drawn afresh for every execution (specification rev. 3, clause
2.4.21.3; clause number and domain from memory).

  (NATION,): one of the 25 N_NAME values of clause 4.2.3, uniform, as the
  text a driver would bind ("SAUDI ARABIA"): 25 bindings.

Spec fields: none. A configuration's `rehearse` block narrows nothing: all
25 nations have suppliers with waiting orders at SF 0.01.

The harness seeds `rng` from (--seed, client). Runs in the client child:
numpy and the standard library only (the loader's NATIONS cannot be
imported here, it pulls in JAX; tests/test_q21.py holds the two lists
equal).

draw(spec, rng, size, state) -> list of 1-tuples of str;
corners(spec) -> every nation: the domain has no order and no ends, and a
deployment that has been up for a day has seen all 25 (the warm-up step,
benchmark/warmup/qgen_domain.py: 25 statements of set-up).
"""

from __future__ import annotations

import numpy as np

NATIONS = (
    "ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
    "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ", "JAPAN",
    "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU", "CHINA", "ROMANIA",
    "SAUDI ARABIA", "VIETNAM", "RUSSIA", "UNITED KINGDOM", "UNITED STATES",
)


def prepare(spec: dict):
    return NATIONS


def draw(spec: dict, rng: np.random.Generator, size: int, state):
    return [(state[int(i)],) for i in rng.integers(0, len(state), size)]


def corners(spec: dict):
    return [(n,) for n in prepare(spec)]
