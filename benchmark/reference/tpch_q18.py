"""Plain reference for TPC-H Q18 (large volume customer) with QGEN's
substitution parameter (clause 2.4.18.3): params = (QUANTITY,) as the
client sent it, e.g. ("312",). numpy over the seeded arrays; imports
nothing of the program.

    select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
           sum(l_quantity)
    from customer, orders, lineitem
    where o_orderkey in (select l_orderkey from lineitem
                         group by l_orderkey
                         having sum(l_quantity) > [QUANTITY])
      and c_custkey = o_custkey and o_orderkey = l_orderkey
    group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    order by o_totalprice desc, o_orderdate  limit 100

No parameter touches the aggregation, so it is done once, in __init__:
l_quantity (scale 2) summed by order key in int64 (np.add.at over the
order's position among the sorted order keys), and each order's customer
found by searchsorted. A binding is then a mask (sum > QUANTITY * 100), a
lexsort of the orders that pass by (o_totalprice descending, o_orderdate),
and the first 100.

What is compared, at the binding of EACH response (limits 0):
  - the number of rows: min(100, orders that pass);
  - every row, found by its o_orderkey among the orders that pass: c_name
    as the STRING the wire sent ("Customer#" and nine digits), the other
    five columns digit for digit;
  - the order of the rows: row i must carry the sort key (o_totalprice,
    o_orderdate) of the reference's row i, and no order may come twice.
Ties: SQL leaves the order of rows that tie on BOTH sort keys open, and at
the cut (row 100) it leaves open WHICH of the tied rows are returned. The
comparison therefore treats the rows that tie on (o_totalprice,
o_orderdate) as a set: any of them may stand at any position that carries
that sort key, inside the answer and at the cut alike. Everything else is
fixed by the data.

`control="float32"` holds o_totalprice and the quantity sums in float32,
the nearest precision below the exact arithmetic the configuration
states. It must come out wrong, and it does by o_totalprice: a total
price in cents (about 1e7 .. 6e7 for the orders Q18 returns) passes 2^24,
so float32 rounds it to a multiple of 2 or 4 cents and the printed column
differs. sum(l_quantity) does NOT show it: at most 7 lines of at most 50
units, 35,000 scaled, is exact in float32; a control that lowered only the
sum's precision would pass, and is not what this file offers.
"""

from __future__ import annotations

import datetime
from decimal import Decimal

import numpy as np

_EPOCH = datetime.date(1970, 1, 1)
_LIMIT = 100
LIMITS = {"rows_missing_or_extra": 0, "cells_mismatched": 0}


def _cents(text: str) -> int:
    """A printed decimal of scale <= 2 as scaled integer; anything finer
    raises (it could not equal a stored value)."""
    d = Decimal(text).scaleb(2)
    if d != d.to_integral_value():
        raise ValueError(text)
    return int(d)


class Reference:
    def __init__(self, data: dict, dicts: dict, statement: dict):
        c, o, l = data["customer"], data["orders"], data["lineitem"]
        names = dicts["c_name"]
        by_key = np.argsort(o["o_orderkey"], kind="stable")
        self.okey = np.asarray(o["o_orderkey"])[by_key].astype(np.int64)
        self.odate = np.asarray(o["o_orderdate"])[by_key].astype(np.int64)
        self.oprice = np.asarray(o["o_totalprice"])[by_key].astype(np.int64)
        ocust = np.asarray(o["o_custkey"])[by_key].astype(np.int64)
        # orders -> their customer (an order without one joins nothing)
        by_cust = np.argsort(c["c_custkey"], kind="stable")
        ckey = np.asarray(c["c_custkey"])[by_cust].astype(np.int64)
        ccode = np.asarray(c["c_name"])[by_cust].astype(np.int64)
        at = np.minimum(np.searchsorted(ckey, ocust), len(ckey) - 1)
        self.has_cust = ckey[at] == ocust
        self.ocust = ocust
        self.oname = [names[i] for i in ccode]   # by customer position
        self.cust_at = at
        # lineitem -> sum(l_quantity) per order, exact in int64
        lkey = np.asarray(l["l_orderkey"]).astype(np.int64)
        pos = np.minimum(np.searchsorted(self.okey, lkey),
                         len(self.okey) - 1)
        joined = self.okey[pos] == lkey
        self.qty = np.zeros(len(self.okey), np.int64)
        np.add.at(self.qty, pos[joined],
                  np.asarray(l["l_quantity"])[joined].astype(np.int64))
        self.lines = np.bincount(pos[joined], minlength=len(self.okey))
        self._answers = {}

    def answer(self, params, control=None):
        """[(c_name, c_custkey, o_orderkey, o_orderdate days,
        o_totalprice cents, sum_qty s2)] in the statement's order (ties on
        both sort keys by o_orderkey), and every row that passes."""
        key = (tuple(params), control)
        if key in self._answers:
            return self._answers[key]
        threshold = int(params[0]) * 100
        qty, price = self.qty, self.oprice
        if control == "float32":
            qty = qty.astype(np.float32).astype(np.int64)
            price = price.astype(np.float32).astype(np.int64)
        elif control is not None:
            raise ValueError(f"tpch_q18: no control {control!r}")
        keep = np.flatnonzero((qty > threshold) & (self.lines > 0)
                              & self.has_cust)
        order = keep[np.lexsort((self.okey[keep], self.odate[keep],
                                 -price[keep]))]
        rows = [(self.oname[self.cust_at[i]], int(self.ocust[i]),
                 int(self.okey[i]), int(self.odate[i]), int(price[i]),
                 int(qty[i])) for i in order]
        self._answers[key] = rows
        return rows

    def control_rows(self, params, control: str):
        return [(n, str(ck), str(ok),
                 (_EPOCH + datetime.timedelta(days=d)).isoformat(),
                 str(Decimal(p).scaleb(-2)), str(Decimal(q).scaleb(-2)))
                for n, ck, ok, d, p, q in
                self.answer(params, control)[:_LIMIT]]

    def check(self, responses):
        oks, worst = [], {k: 0 for k in LIMITS}
        for params, rows in responses:
            passing = self.answer(params)
            want = passing[:_LIMIT]
            by_order = {w[2]: w for w in passing}
            bad_rows = abs(len(rows) - len(want))
            bad_cells = 0
            seen = set()
            for r, w in zip(rows, want):
                if len(r) != 6:
                    bad_rows += 1
                    continue
                try:
                    got = (r[0], int(r[1]), int(r[2]),
                           (datetime.date.fromisoformat(r[3])
                            - _EPOCH).days, _cents(r[4]), _cents(r[5]))
                except (ValueError, ArithmeticError, TypeError):
                    bad_cells += 6
                    continue
                exp = by_order.get(got[2])
                if exp is None or got[2] in seen:
                    bad_rows += 1      # no such order, or the order twice
                    continue
                seen.add(got[2])
                bad_cells += sum(a != b for a, b in zip(got, exp))
                # rows that tie on both sort keys are a set: the position
                # is right when it carries the reference's sort key
                bad_cells += (exp[4], exp[3]) != (w[4], w[3])
            got = {"rows_missing_or_extra": bad_rows,
                   "cells_mismatched": bad_cells}
            oks.append(all(got[k] <= LIMITS[k] for k in LIMITS))
            for k in worst:
                worst[k] = max(worst[k], got[k])
        compared = [{"name": k, "value": worst[k], "limit": LIMITS[k],
                     "ok": worst[k] <= LIMITS[k]} for k in LIMITS]
        return oks, compared
