"""Plain reference for TPC-H Q21 (suppliers who kept orders waiting) with
QGEN's substitution parameter (clause 2.4.21.3): params = (NATION,) as the
client sent it, e.g. ("SAUDI ARABIA",). numpy over the seeded arrays;
imports nothing of the program.

    select s_name, count(*) as numwait
    from supplier, lineitem l1, orders, nation
    where s_suppkey = l1.l_suppkey and o_orderkey = l1.l_orderkey
      and o_orderstatus = 'F' and l1.l_receiptdate > l1.l_commitdate
      and exists (select * from lineitem l2
                  where l2.l_orderkey = l1.l_orderkey
                    and l2.l_suppkey <> l1.l_suppkey)
      and not exists (select * from lineitem l3
                      where l3.l_orderkey = l1.l_orderkey
                        and l3.l_suppkey <> l1.l_suppkey
                        and l3.l_receiptdate > l3.l_commitdate)
      and s_nationkey = n_nationkey and n_name = [NATION]
    group by s_name order by numwait desc, s_name  limit 100

Written from the text: a subquery is what it says, a question about the
SET of suppliers on the line's order (`exists_other`): the distinct
(order, supplier) pairs are sorted once and counted by order, over all
lines for the EXISTS and over the late lines for the NOT EXISTS. No
parameter touches that, so it is done once, in __init__: every line that
waits (late, on an 'F' order, another supplier on the order, no OTHER late
supplier on it) is counted to its supplier. A binding is then the
suppliers of one nation with a count, sorted by (numwait descending,
s_name), and the first 100.

What is compared, at the binding of EACH response (limits 0):
  - the number of rows: min(100, suppliers of the nation that wait);
  - row i against the reference's row i: s_name as the STRING the wire
    sent ("Supplier#" and nine digits), numwait digit for digit. S_NAME
    is unique, so the statement's order is total and nothing ties.

`control="half_width"` holds every column the statement reads in half its
stored width, the nearest integer precision below the one the
configuration states: the 4-byte keys in 16 bits, the 2-byte dates in 8.
It must come out wrong, and it does by ONE of the two limits, which one
by the scale: days fold onto 256 values, so `l_receiptdate >
l_commitdate` changes sides for about half the lines at any scale (SF
0.01, where the order keys still fit 16 bits: the same suppliers come
back with other counts, cells_mismatched alone); at SF 1 the 1.5M sparse
order keys fold onto 65,536 values besides, every order then has late
lines of many suppliers, NO line waits alone and the control's answer is
empty (rows_missing_or_extra 100 at every binding, cells_mismatched 0: my
chip run, PR 50). (float32, the control of the money queries, is exact
here: keys under 2^24, counts under 100.)
"""

from __future__ import annotations

import numpy as np

_LIMIT = 100
LIMITS = {"rows_missing_or_extra": 0, "cells_mismatched": 0}


def exists_other(key, x, x_valid=None, among=None):
    """For every row i: is there a row j `among` the rows, of the same
    `key`, whose `x` differs from row i's? SQL's three values: a NULL x on
    either side (`x_valid` false) compares to unknown and is no such row;
    rows of one key with one and the same x, however many, are none
    either. -> bool per row. By the SET of x on each key: its size, and
    whether the row's own x is a member."""
    key = np.asarray(key, dtype=np.int64)
    x = np.asarray(x, dtype=np.int64)
    n = len(key)
    valid = (np.ones(n, bool) if x_valid is None
             else np.asarray(x_valid, dtype=bool))
    member = valid if among is None else valid & np.asarray(among, bool)
    # the distinct (key, x) pairs of the member rows, in key order
    order = np.lexsort((x[member], key[member]))
    pk, px = key[member][order], x[member][order]
    first = np.ones(len(pk), bool)
    first[1:] = (pk[1:] != pk[:-1]) | (px[1:] != px[:-1])
    pk, px = pk[first], px[first]
    keys, size = np.unique(pk, return_counts=True)   # |set| of each key
    at = np.minimum(np.searchsorted(keys, key), max(len(keys) - 1, 0))
    has = (keys[at] == key) if len(keys) else np.zeros(n, bool)
    others = np.where(has, size[at] if len(keys) else 0, 0)
    # is the row's own (key, x) one of the pairs? (pairs sort as one
    # number: x's offset and span keep the order)
    if len(pk):
        lo = min(int(px.min()), int(x.min()))
        span = max(int(px.max()), int(x.max())) - lo + 1
        code = pk * span + (px - lo)
        mine = key * span + (x - lo)
        j = np.minimum(np.searchsorted(code, mine), len(code) - 1)
        own = code[j] == mine
    else:
        own = np.zeros(n, bool)
    return valid & (others - own.astype(np.int64) >= 1)


class Reference:
    def __init__(self, data: dict, dicts: dict, statement: dict):
        s, l, o, n = (data["supplier"], data["lineitem"], data["orders"],
                      data["nation"])
        # NATION as the client binds it -> n_nationkey
        self.nations = {dicts["n_name"][int(c)]: int(k) for c, k in
                        zip(np.asarray(n["n_name"]).tolist(),
                            np.asarray(n["n_nationkey"]).tolist())}
        self.skey = np.asarray(s["s_suppkey"]).astype(np.int64)
        self.snation = np.asarray(s["s_nationkey"]).astype(np.int64)
        self.sname = [dicts["s_name"][c]
                      for c in np.asarray(s["s_name"]).tolist()]
        self.lkey = np.asarray(l["l_orderkey"]).astype(np.int64)
        self.lsupp = np.asarray(l["l_suppkey"]).astype(np.int64)
        self.commit = np.asarray(l["l_commitdate"]).astype(np.int64)
        self.receipt = np.asarray(l["l_receiptdate"]).astype(np.int64)
        status = [dicts["o_orderstatus"][c]
                  for c in range(len(dicts["o_orderstatus"]))]
        self.okey_f = np.asarray(o["o_orderkey"]).astype(np.int64)[
            np.asarray(o["o_orderstatus"]) == status.index("F")]
        self._numwait = {}
        self._answers = {}

    def _waits(self, control):
        """numwait of every supplier row (0: none), whatever the nation."""
        if control not in self._numwait:
            held = {"lkey": self.lkey, "okey": self.okey_f,
                    "lsupp": self.lsupp, "commit": self.commit,
                    "receipt": self.receipt}
            if control == "half_width":
                for name, v in held.items():
                    half = np.int8 if name in ("commit", "receipt") \
                        else np.int16
                    held[name] = v.astype(half).astype(np.int64)
            elif control is not None:
                raise ValueError(f"tpch_q21: no control {control!r}")
            lkey, lsupp = held["lkey"], held["lsupp"]
            late = held["receipt"] > held["commit"]
            waits = (late & np.isin(lkey, held["okey"])
                     & exists_other(lkey, lsupp)
                     & ~exists_other(lkey, lsupp, among=late))
            by_supp = np.argsort(self.skey, kind="stable")
            at = np.minimum(np.searchsorted(self.skey[by_supp],
                                            lsupp[waits]),
                            len(by_supp) - 1)
            joined = self.skey[by_supp][at] == lsupp[waits]
            self._numwait[control] = np.bincount(
                by_supp[at[joined]], minlength=len(self.skey))
        return self._numwait[control]

    def answer(self, params, control=None):
        """[(s_name, numwait)] in the statement's order, first 100."""
        key = (tuple(params), control)
        if key not in self._answers:
            nation = self.nations.get(params[0], -1)  # unknown: no row
            numwait = self._waits(control)
            keep = np.flatnonzero((numwait > 0) & (self.snation == nation))
            rows = sorted(((self.sname[i], int(numwait[i])) for i in keep),
                          key=lambda r: (-r[1], r[0]))
            self._answers[key] = rows[:_LIMIT]
        return self._answers[key]

    def control_rows(self, params, control: str):
        return [(name, str(n)) for name, n in self.answer(params, control)]

    def check(self, responses):
        oks, worst = [], {k: 0 for k in LIMITS}
        for params, rows in responses:
            want = self.answer(params)
            bad_rows = abs(len(rows) - len(want))
            bad_cells = 0
            for r, w in zip(rows, want):
                if len(r) != 2:
                    bad_rows += 1
                    continue
                try:
                    got = (r[0], int(r[1]))
                except (ValueError, TypeError):
                    bad_cells += 2
                    continue
                bad_cells += sum(a != b for a, b in zip(got, w))
            got = {"rows_missing_or_extra": bad_rows,
                   "cells_mismatched": bad_cells}
            oks.append(all(got[k] <= LIMITS[k] for k in LIMITS))
            for k in worst:
                worst[k] = max(worst[k], got[k])
        compared = [{"name": k, "value": worst[k], "limit": LIMITS[k],
                     "ok": worst[k] <= LIMITS[k]} for k in LIMITS]
        return oks, compared
