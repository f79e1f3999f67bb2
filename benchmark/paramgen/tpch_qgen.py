"""Parameter stream `tpch_qgen`: QGEN's substitution parameters, drawn
afresh for every execution (TPC-H specification rev. 3, clauses 2.4.3.3
and 2.4.6.3; clause numbers from memory).

Spec fields: query = "q3" | "q6".
  q3 -> (SEGMENT, DATE): SEGMENT uniform over the five market segments,
        DATE uniform over 1995-03-01 .. 1995-03-31 (155 bindings);
  q6 -> (DATE, DISCOUNT, QUANTITY): DATE the first of January of a year
        uniform in 1993 .. 1997, DISCOUNT uniform in 0.02 .. 0.09 by 0.01,
        QUANTITY uniform in 24 .. 25 (80 bindings).
Values are the text a driver would bind: dates as YYYY-MM-DD, the
discount with two decimals, the quantity a whole number.

The harness seeds `rng` from (--seed, client). Runs in the client child:
numpy and the standard library only (the loader's SEGMENTS cannot be
imported here, it pulls in JAX; tests/test_params.py holds the two lists
equal).

draw(spec, rng, size, state) -> list of tuples of str;
corners(spec) -> the bindings at the corners of the domain, for the
warm-up step (benchmark/warmup/qgen_domain.py).
"""

from __future__ import annotations

import datetime

import numpy as np

SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
_Q3_FIRST = datetime.date(1995, 3, 1)
_Q3_DAYS = 31
_Q6_YEARS = (1993, 1997)
_Q6_DISCOUNTS = (2, 9)       # hundredths
_Q6_QUANTITIES = (24, 25)


def _q3(segment: int, day: int):
    return (SEGMENTS[segment],
            (_Q3_FIRST + datetime.timedelta(days=int(day))).isoformat())


def _q6(year: int, disc: int, qty: int):
    return (f"{int(year)}-01-01", f"0.{int(disc):02d}", str(int(qty)))


def prepare(spec: dict):
    if spec["query"] not in ("q3", "q6"):
        raise ValueError(f"tpch_qgen: no query {spec['query']!r}")
    return spec["query"]


def draw(spec: dict, rng: np.random.Generator, size: int, state):
    if state == "q3":
        seg = rng.integers(0, len(SEGMENTS), size)
        day = rng.integers(0, _Q3_DAYS, size)
        return [_q3(s, d) for s, d in zip(seg, day)]
    year = rng.integers(_Q6_YEARS[0], _Q6_YEARS[1] + 1, size)
    disc = rng.integers(_Q6_DISCOUNTS[0], _Q6_DISCOUNTS[1] + 1, size)
    qty = rng.integers(_Q6_QUANTITIES[0], _Q6_QUANTITIES[1] + 1, size)
    return [_q6(y, d, q) for y, d, q in zip(year, disc, qty)]


def corners(spec: dict):
    if prepare(spec) == "q3":
        return [_q3(s, d) for s in range(len(SEGMENTS))
                for d in (0, _Q3_DAYS - 1)]
    return [_q6(y, d, q) for y in _Q6_YEARS for d in _Q6_DISCOUNTS
            for q in _Q6_QUANTITIES]
