"""Plain reference for TPC-H Q3 (shipping priority), numpy over the seeded
arrays. Imports nothing of the program.

Validation parameters: SEGMENT = BUILDING, DATE = 1995-03-15. Revenue is a
scaled integer (scale 4: extendedprice scale 2 times (1 - discount) scale
2), summed exactly in int64. The answer is the ten rows with the largest
revenue, ties by o_orderdate; all four columns are compared exactly, in
order (limit 0).

`control="float32"` sums revenue in float32, the nearest precision below
the exact arithmetic the configuration states; revenues of about 4e9
(scaled) do not fit 24 bits, so the comparison must fail.
"""

from __future__ import annotations

import datetime
from decimal import Decimal

import numpy as np

_EPOCH = datetime.date(1970, 1, 1)
DATE = (datetime.date(1995, 3, 15) - _EPOCH).days
SEGMENT = "BUILDING"
LIMITS = {"rows_missing_or_extra": 0, "cells_mismatched": 0}


class Reference:
    def __init__(self, data: dict, dicts: dict, statement: dict):
        self.data = data
        self.seg_code = dicts["c_mktsegment"].index(SEGMENT)
        self._answers = {}

    def answer(self, control=None):
        """[(l_orderkey, revenue s4, o_orderdate days, o_shippriority)]."""
        if control in self._answers:
            return self._answers[control]
        c, o, l = (self.data["customer"], self.data["orders"],
                   self.data["lineitem"])
        bcust = c["c_custkey"][c["c_mktsegment"] == self.seg_code]
        okeep = (o["o_orderdate"] < DATE) & np.isin(o["o_custkey"], bcust)
        okey = o["o_orderkey"][okeep].astype(np.int64)
        order = np.argsort(okey, kind="stable")
        okey = okey[order]
        odate = o["o_orderdate"][okeep][order].astype(np.int64)
        oprio = o["o_shippriority"][okeep][order].astype(np.int64)
        lkeep = l["l_shipdate"] > DATE
        lkey = l["l_orderkey"][lkeep].astype(np.int64)
        pos = np.minimum(np.searchsorted(okey, lkey), max(len(okey) - 1, 0))
        m = (okey[pos] == lkey) if len(okey) else np.zeros(len(lkey), bool)
        rev = (l["l_extendedprice"][lkeep][m].astype(np.int64)
               * (100 - l["l_discount"][lkeep][m].astype(np.int64)))
        grp = pos[m]
        by = np.argsort(grp, kind="stable")
        grp, rev = grp[by], rev[by]
        if len(grp) == 0:
            self._answers[control] = []
            return []
        starts = np.flatnonzero(np.r_[True, grp[1:] != grp[:-1]])
        if control is None:
            sums = np.add.reduceat(rev, starts)
        elif control == "float32":
            sums = np.add.reduceat(rev.astype(np.float32),
                                   starts).astype(np.int64)
        else:
            raise ValueError(f"tpch_q3: no control {control!r}")
        g = grp[starts]
        top = np.lexsort((odate[g], -sums))[:10]
        out = [(int(okey[g[i]]), int(sums[i]), int(odate[g[i]]),
                int(oprio[g[i]])) for i in top]
        self._answers[control] = out
        return out

    def control_rows(self, params, control: str):
        return [(str(k), str(Decimal(r).scaleb(-4)),
                 (_EPOCH + datetime.timedelta(days=d)).isoformat(), str(p))
                for k, r, d, p in self.answer(control)]

    def check(self, responses):
        want = self.answer()
        oks, worst = [], {k: 0 for k in LIMITS}
        for _params, rows in responses:
            bad_rows = abs(len(rows) - len(want))
            bad_cells = 0
            for r, w in zip(rows, want):
                if len(r) != 4:
                    bad_rows += 1
                    continue
                try:
                    got = (int(r[0]), Decimal(r[1]),
                           (datetime.date.fromisoformat(r[2]) - _EPOCH).days,
                           int(r[3]))
                except (ValueError, ArithmeticError):
                    bad_cells += 4
                    continue
                exp = (w[0], Decimal(w[1]).scaleb(-4), w[2], w[3])
                bad_cells += sum(a != b for a, b in zip(got, exp))
            got = {"rows_missing_or_extra": bad_rows,
                   "cells_mismatched": bad_cells}
            oks.append(all(got[k] <= LIMITS[k] for k in LIMITS))
            for k in worst:
                worst[k] = max(worst[k], got[k])
        compared = [{"name": k, "value": worst[k], "limit": LIMITS[k],
                     "ok": worst[k] <= LIMITS[k]} for k in LIMITS]
        return oks, compared
