"""Plain reference for TPC-H Q9 (product type profit measure) with QGEN's
substitution parameter (clause 2.4.9.3): params = (pattern,) as the client
sent it, e.g. ("%green%",). numpy over the seeded arrays; imports nothing
of the program.

    select nation, o_year, sum(amount) as sum_profit
    from (select n_name as nation,
                 extract(year from o_orderdate) as o_year,
                 l_extendedprice * (1 - l_discount)
                     - ps_supplycost * l_quantity as amount
          from part, supplier, lineitem, partsupp, orders, nation
          where s_suppkey = l_suppkey and ps_suppkey = l_suppkey
            and ps_partkey = l_partkey and p_partkey = l_partkey
            and o_orderkey = l_orderkey and s_nationkey = n_nationkey
            and p_name like [pattern]) as profit
    group by nation, o_year  order by nation, o_year desc

Only the part filter reads the parameter, so everything else is done once,
in __init__, for every lineitem row: its supplier's nation by key, its
partsupp row's cost by the pair (ps_partkey * (max suppkey + 1) +
ps_suppkey through searchsorted), its order's year from days since 1970 by
datetime64, and amount = l_extendedprice * (100 - l_discount) -
ps_supplycost * l_quantity in int64 at scale 4 (both products are of two
scale-2 values). A row whose supplier, partsupp row, order or nation is
missing joins nothing; nor does one whose part is missing (its part row
is found once, by key, as the others are). A binding is then the pattern
applied to the p_name dictionary, each lineitem row's part looked up in
that table of names that pass, and an exact int64 sum by (nation, year).

Patterns covered, by Python's own string operations: `%` alone (every
name), `%word%` (`word in name`), `word%` (startswith), `%word` (endswith)
and a pattern with no wildcard (equality), `word` holding neither `%` nor
`_`. Any other shape raises: this file has no general LIKE, and a cell
whose parameter stream sends one needs another reference. A NULL binding
(None) matches no row.

What is compared, at the binding of EACH response (limits 0):
  - the number of rows: the (nation, year) groups with a row that passes,
    0 for a pattern nothing matches;
  - every row at its position: `nation` as the STRING the wire sent,
    `o_year` as an integer, `sum_profit` digit for digit as a decimal of
    scale 4. The statement's two sort keys are the group's key, so the
    order has no ties: row i must be the reference's row i.

`control="float32"` holds every amount and every sum in float32, the
nearest precision below the exact arithmetic the configuration states. It
must come out wrong, and it does by sum_profit: a group's profit at SF1
is about 4e11 ten-thousandths (4e9 at the rehearsal's SF 0.01), five (three)
orders of magnitude past the 2^24 a float32 holds exactly, so the printed
digits differ in every group. The number of rows, the nations and the
years are the exact ones: a control that compared only those would pass.
"""

from __future__ import annotations

from decimal import Decimal

import numpy as np

LIMITS = {"rows_missing_or_extra": 0, "cells_mismatched": 0}
_SCALE = 4


def _scaled(text: str) -> int:
    """A printed decimal of scale <= 4 as scaled integer; anything finer
    raises (it could not equal an exact sum)."""
    d = Decimal(text).scaleb(_SCALE)
    if d != d.to_integral_value():
        raise ValueError(text)
    return int(d)


def matcher(pattern):
    """The pattern as a predicate over one name, for the shapes the
    docstring lists."""
    if pattern is None:
        return lambda name: False
    body = pattern.strip("%")
    if "%" in body or "_" in body:
        raise ValueError(f"tpch_q9: pattern {pattern!r} is not one of "
                         f"%, %word%, word%, %word, word")
    head = pattern.startswith("%")
    tail = pattern.endswith("%") and len(pattern) > 1
    if not body:
        if pattern == "":
            return lambda name: name == ""
        return lambda name: True
    if head and tail:
        return lambda name: body in name
    if tail:
        return lambda name: name.startswith(body)
    if head:
        return lambda name: name.endswith(body)
    return lambda name: name == body


def _lookup(keys: np.ndarray, wanted: np.ndarray):
    """-> (position of each wanted key among `keys`, found) for unique
    `keys` in any order."""
    order = np.argsort(keys, kind="stable")
    sorted_keys = keys[order]
    at = np.minimum(np.searchsorted(sorted_keys, wanted),
                    len(sorted_keys) - 1)
    return order[at], sorted_keys[at] == wanted


class Reference:
    def __init__(self, data: dict, dicts: dict, statement: dict):
        p, s, l = data["part"], data["supplier"], data["lineitem"]
        ps, o, n = data["partsupp"], data["orders"], data["nation"]
        i64 = lambda a: np.asarray(a).astype(np.int64)
        self.names = [str(x) for x in dicts["p_name"]]
        self.nations = [str(x) for x in dicts["n_name"]]
        l_part, l_supp = i64(l["l_partkey"]), i64(l["l_suppkey"])
        # lineitem -> part -> the code of its name
        at, ok = _lookup(i64(p["p_partkey"]), l_part)
        self.l_name = i64(p["p_name"])[at]
        # lineitem -> supplier -> nation
        at, found = _lookup(i64(s["s_suppkey"]), l_supp)
        ok &= found
        s_nation = i64(s["s_nationkey"])[at]
        nat, nat_ok = _lookup(i64(n["n_nationkey"]), s_nation)
        ok &= nat_ok
        self.l_nation = i64(n["n_name"])[nat]    # the name's code
        # lineitem -> partsupp by the pair
        width = max(int(i64(ps["ps_suppkey"]).max()),
                    int(l_supp.max())) + 1
        at, found = _lookup(
            i64(ps["ps_partkey"]) * width + i64(ps["ps_suppkey"]),
            l_part * width + l_supp)
        ok &= found
        cost = i64(ps["ps_supplycost"])[at]
        # lineitem -> orders -> year
        at, found = _lookup(i64(o["o_orderkey"]), i64(l["l_orderkey"]))
        ok &= found
        days = i64(o["o_orderdate"])[at]
        self.l_year = days.astype("datetime64[D]").astype(
            "datetime64[Y]").astype(np.int64) + 1970
        self.l_amount = (i64(l["l_extendedprice"])
                         * (100 - i64(l["l_discount"]))
                         - cost * i64(l["l_quantity"]))
        self.l_ok = ok
        self._answers = {}

    def answer(self, params, control=None):
        """[(nation, o_year, sum_profit at scale 4)] in the statement's
        order: nation ascending, year descending."""
        key = (tuple(params), control)
        if key in self._answers:
            return self._answers[key]
        if control not in (None, "float32"):
            raise ValueError(f"tpch_q9: no control {control!r}")
        passes = matcher(params[0])
        name_ok = np.fromiter((passes(x) for x in self.names), np.bool_,
                              len(self.names))
        keep = np.flatnonzero(self.l_ok & name_ok[self.l_name])
        group = self.l_nation[keep] * 10000 + self.l_year[keep]
        groups, inverse = np.unique(group, return_inverse=True)
        if control == "float32":
            sums = np.zeros(len(groups), np.float32)
            np.add.at(sums, inverse, self.l_amount[keep].astype(np.float32))
            sums = sums.astype(np.int64)
        else:
            sums = np.zeros(len(groups), np.int64)
            np.add.at(sums, inverse, self.l_amount[keep])
        rows = [(self.nations[int(g) // 10000], int(g) % 10000, int(v))
                for g, v in zip(groups, sums)]
        rows.sort(key=lambda r: (r[0], -r[1]))
        self._answers[key] = rows
        return rows

    def control_rows(self, params, control: str):
        return [(nation, str(year), str(Decimal(v).scaleb(-_SCALE)))
                for nation, year, v in self.answer(params, control)]

    def check(self, responses):
        oks, worst = [], {k: 0 for k in LIMITS}
        for params, rows in responses:
            want = self.answer(params)
            bad_rows = abs(len(rows) - len(want))
            bad_cells = 0
            for r, w in zip(rows, want):
                if len(r) != 3:
                    bad_rows += 1
                    continue
                try:
                    got = (r[0], int(r[1]), _scaled(r[2]))
                except (ValueError, ArithmeticError, TypeError):
                    bad_cells += 3
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
