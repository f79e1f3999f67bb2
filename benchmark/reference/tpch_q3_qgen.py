"""Plain reference for TPC-H Q3 with QGEN's substitution parameters
(clause 2.4.3.3): params = (SEGMENT, DATE) as the client sent them, e.g.
("BUILDING", "1995-03-15"). numpy over the seeded arrays; imports nothing
of the program.

A window holds up to 155 distinct bindings, so the work that no
parameter touches is done once, in __init__, and SHARED by every binding:
  - orders -> the segment code of the order's customer (searchsorted of
    o_custkey in the sorted c_custkey);
  - lineitem -> the position of its order among the orders sorted by key
    (searchsorted), then the lines sorted by that position, with the
    start of every order's run of lines, each line's scaled revenue
    extendedprice * (100 - discount) and its ship date beside it.
A binding then costs two masked reduceat passes over the lines (revenue
and count of the lines shipped after DATE, per order), one mask over the
orders, and a lexsort of the orders that are left: about 0.1 s at SF1.

Revenue is a scaled integer (scale 4) summed exactly in int64. The answer
is the ten rows with the largest revenue, ties by o_orderdate; all four
columns are compared exactly, in order (limit 0), at the binding of each
response.

`control="float32"` sums revenue in float32, the nearest precision below
the exact arithmetic the configuration states; revenues of about 4e9
(scaled) do not fit 24 bits, so the comparison must fail.
"""

from __future__ import annotations

import datetime
from decimal import Decimal

import numpy as np

_EPOCH = datetime.date(1970, 1, 1)
LIMITS = {"rows_missing_or_extra": 0, "cells_mismatched": 0}


def _days(text: str) -> int:
    return (datetime.date.fromisoformat(text) - _EPOCH).days


class Reference:
    def __init__(self, data: dict, dicts: dict, statement: dict):
        c, o, l = data["customer"], data["orders"], data["lineitem"]
        self.segments = list(dicts["c_mktsegment"])
        by_cust = np.argsort(c["c_custkey"], kind="stable")
        ckey = np.asarray(c["c_custkey"])[by_cust].astype(np.int64)
        cseg = np.asarray(c["c_mktsegment"])[by_cust].astype(np.int64)
        by_key = np.argsort(o["o_orderkey"], kind="stable")
        self.okey = np.asarray(o["o_orderkey"])[by_key].astype(np.int64)
        self.odate = np.asarray(o["o_orderdate"])[by_key].astype(np.int64)
        self.oprio = np.asarray(o["o_shippriority"])[by_key].astype(np.int64)
        ocust = np.asarray(o["o_custkey"])[by_key].astype(np.int64)
        at = np.minimum(np.searchsorted(ckey, ocust), len(ckey) - 1)
        # an order whose customer is unknown joins nothing
        self.oseg = np.where(ckey[at] == ocust, cseg[at], -1)
        lkey = np.asarray(l["l_orderkey"]).astype(np.int64)
        pos = np.minimum(np.searchsorted(self.okey, lkey),
                         len(self.okey) - 1)
        joined = self.okey[pos] == lkey
        order = np.argsort(pos[joined], kind="stable")
        self.lpos = pos[joined][order]
        self.lship = np.asarray(l["l_shipdate"])[joined][order].astype(
            np.int64)
        self.lrev = (np.asarray(l["l_extendedprice"])[joined][order].astype(
            np.int64) * (100 - np.asarray(l["l_discount"])[joined][
                order].astype(np.int64)))
        self.starts = np.flatnonzero(
            np.r_[True, self.lpos[1:] != self.lpos[:-1]])
        self.group_pos = self.lpos[self.starts]   # order position per run
        self._answers = {}

    def answer(self, params, control=None):
        """[(l_orderkey, revenue s4, o_orderdate days, o_shippriority)]."""
        key = (tuple(params), control)
        if key in self._answers:
            return self._answers[key]
        segment, date = params[0], _days(params[1])
        seg = (self.segments.index(segment)
               if segment in self.segments else -2)
        if not len(self.starts):
            self._answers[key] = []
            return []
        shipped = self.lship > date
        counts = np.add.reduceat(shipped.astype(np.int64), self.starts)
        weights = np.where(shipped, self.lrev, 0)
        if control is None:
            sums = np.add.reduceat(weights, self.starts)
        elif control == "float32":
            sums = np.add.reduceat(weights.astype(np.float32),
                                   self.starts).astype(np.int64)
        else:
            raise ValueError(f"tpch_q3_qgen: no control {control!r}")
        g = self.group_pos
        keep = ((counts > 0) & (self.oseg[g] == seg)
                & (self.odate[g] < date))
        g, sums = g[keep], sums[keep]
        top = np.lexsort((self.odate[g], -sums))[:10]
        out = [(int(self.okey[g[i]]), int(sums[i]), int(self.odate[g[i]]),
                int(self.oprio[g[i]])) for i in top]
        self._answers[key] = out
        return out

    def control_rows(self, params, control: str):
        return [(str(k), str(Decimal(r).scaleb(-4)),
                 (_EPOCH + datetime.timedelta(days=d)).isoformat(), str(p))
                for k, r, d, p in self.answer(params, control)]

    def check(self, responses):
        oks, worst = [], {k: 0 for k in LIMITS}
        for params, rows in responses:
            want = self.answer(params)
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
                except (ValueError, ArithmeticError, TypeError):
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
