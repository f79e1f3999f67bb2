"""Plain reference for TPC-H Q6 (forecasting revenue change) with QGEN's
substitution parameters (clause 2.4.6.3): params = (DATE, DISCOUNT,
QUANTITY) as the client sent them, e.g. ("1994-01-01", "0.06", "24").
numpy over the seeded arrays; imports nothing of the program.

    select sum(l_extendedprice * l_discount) as revenue from lineitem
    where l_shipdate >= DATE and l_shipdate < DATE + 1 year
      and l_discount between DISCOUNT - 0.01 and DISCOUNT + 0.01
      and l_quantity < QUANTITY

Decimals are scaled integers (scale 2): the discount bounds are
round(DISCOUNT * 100) -+ 1, exact, and the quantity bound QUANTITY * 100.
The answer is one row with one decimal of scale 4 (price s2 * discount
s2), summed exactly in int64 and compared digit for digit (limit 0) at the
binding of each response; a binding that selects no row answers NULL.
Nothing here is shared between bindings but the four columns widened to
int64 once: a binding is three masks over the lines, about 0.05 s at SF1.

`control="float32"` accumulates the sum in float32, the nearest precision
below the exact arithmetic the configuration states: a sum of about 1e12
(scaled) does not fit 24 bits, so the comparison must fail.
"""

from __future__ import annotations

import datetime
from decimal import Decimal

import numpy as np

_EPOCH = datetime.date(1970, 1, 1)
LIMITS = {"rows_missing_or_extra": 0, "cells_mismatched": 0}


class Reference:
    def __init__(self, data: dict, dicts: dict, statement: dict):
        t = data["lineitem"]
        self.ship = np.asarray(t["l_shipdate"]).astype(np.int64)
        self.disc = np.asarray(t["l_discount"]).astype(np.int64)
        self.qty = np.asarray(t["l_quantity"]).astype(np.int64)
        self.px = np.asarray(t["l_extendedprice"]).astype(np.int64)
        self._answers = {}

    def answer(self, params, control=None):
        """The revenue as a scaled integer (scale 4), or None."""
        key = (tuple(params), control)
        if key in self._answers:
            return self._answers[key]
        first = datetime.date.fromisoformat(params[0])
        lo = (first - _EPOCH).days
        hi = (first.replace(year=first.year + 1) - _EPOCH).days
        disc = int(Decimal(params[1]).scaleb(2))
        qty = int(Decimal(params[2]).scaleb(2))
        keep = ((self.ship >= lo) & (self.ship < hi)
                & (self.disc >= disc - 1) & (self.disc <= disc + 1)
                & (self.qty < qty))
        terms = self.px[keep] * self.disc[keep]
        if not len(terms):
            out = None
        elif control is None:
            out = int(terms.sum())
        elif control == "float32":
            out = int(np.cumsum(terms.astype(np.float32),
                                dtype=np.float32)[-1])
        else:
            raise ValueError(f"tpch_q6: no control {control!r}")
        self._answers[key] = out
        return out

    def control_rows(self, params, control: str):
        v = self.answer(params, control)
        return [(None if v is None else str(Decimal(v).scaleb(-4)),)]

    def check(self, responses):
        oks, worst = [], {k: 0 for k in LIMITS}
        for params, rows in responses:
            want = self.answer(params)
            bad_rows = abs(len(rows) - 1)
            bad_cells = 0
            if rows and len(rows[0]) == 1:
                got = rows[0][0]
                try:
                    same = ((got is None and want is None)
                            or (got is not None and want is not None
                                and Decimal(got)
                                == Decimal(want).scaleb(-4)))
                except ArithmeticError:
                    same = False
                bad_cells += not same
            elif rows:
                bad_rows += 1
            got = {"rows_missing_or_extra": bad_rows,
                   "cells_mismatched": bad_cells}
            oks.append(all(got[k] <= LIMITS[k] for k in LIMITS))
            for k in worst:
                worst[k] = max(worst[k], got[k])
        compared = [{"name": k, "value": worst[k], "limit": LIMITS[k],
                     "ok": worst[k] <= LIMITS[k]} for k in LIMITS]
        return oks, compared
