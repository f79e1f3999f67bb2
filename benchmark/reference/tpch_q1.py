"""Plain reference for TPC-H Q1 (pricing summary report), numpy over the
seeded arrays. Imports nothing of the program.

Validation parameters: DELTA = 90 days, so l_shipdate <= 1998-09-02.
Decimals are scaled integers (scale 2); the products carry scale 4 and 6.
Integer and decimal answers are compared exactly (limit 0). The three
averages come back as float32 printed with four decimals, so each is
compared with exact-integer-sum / count in float64 in units of what that
format can resolve: |got - exact| / (0.5e-4 + |exact| * 2**-24), half a
unit of the last printed decimal plus half a float32 ulp. A single correct
rounding reads at most 1. The limit and the readings it was set from are in
LIMITS below and in PERF.md section 2.

`control="float32"` accumulates every sum in float32, the nearest
precision below the exact 64-bit integer arithmetic the configuration
states: the comparison must then fail (benchmark/test_benchmark.py, and
--control 1 on the chip).
"""

from __future__ import annotations

import datetime
from decimal import Decimal

import numpy as np

CUTOFF = (datetime.date(1998, 12, 1) - datetime.date(1970, 1, 1)).days - 90
COLUMNS = ("l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
           "l_discount", "l_tax", "l_shipdate")
# avg_err_units, from chip readings at SF1 (PERF.md section 2): sound runs
# read 0.93 to 2.83 over thirteen seeds (the program's float32 average carries
# two or three roundings, not one); the float32 control reads 7,253 or more.
# The limit sits 3.5 times above the sound runs' largest.
LIMITS = {"groups_missing_or_extra": 0, "exact_columns_mismatched": 0,
          "avg_err_units": 10.0}


def avg_units(got: float, exact: float) -> float:
    return abs(got - exact) / (0.5e-4 + abs(exact) * 2.0 ** -24)


def _fmt(scaled: int, scale: int) -> str:
    return str(Decimal(int(scaled)).scaleb(-scale))


class Reference:
    def __init__(self, data: dict, dicts: dict, statement: dict):
        t = data["lineitem"]
        self.rf_pool = dicts["l_returnflag"]
        self.ls_pool = dicts["l_linestatus"]
        keep = t["l_shipdate"] <= CUTOFF
        self.rf = t["l_returnflag"][keep].astype(np.int64)
        self.ls = t["l_linestatus"][keep].astype(np.int64)
        self.qty = t["l_quantity"][keep].astype(np.int64)
        self.px = t["l_extendedprice"][keep].astype(np.int64)
        self.disc = t["l_discount"][keep].astype(np.int64)
        self.tax = t["l_tax"][keep].astype(np.int64)
        self._answers = {}

    def answer(self, control=None) -> dict:
        """{(returnflag, linestatus): (sum_qty s2, sum_base_price s2,
        sum_disc_price s4, sum_charge s6, avg_qty, avg_price, avg_disc,
        count)} with the strings of the dictionary pools as keys."""
        if control in self._answers:
            return self._answers[control]
        disc_price = self.px * (100 - self.disc)
        charge = disc_price * (100 + self.tax)
        code = self.rf * len(self.ls_pool) + self.ls
        out = {}
        for c in np.unique(code):
            m = code == c
            n = int(m.sum())
            if control is None:
                tot = lambda a: int(a[m].sum())
            elif control == "float32":
                tot = lambda a: int(np.cumsum(a[m].astype(np.float32),
                                              dtype=np.float32)[-1])
            else:
                raise ValueError(f"tpch_q1: no control {control!r}")
            s_qty, s_px, s_dp, s_ch, s_disc = (
                tot(self.qty), tot(self.px), tot(disc_price), tot(charge),
                tot(self.disc))
            key = (self.rf_pool[int(c) // len(self.ls_pool)],
                   self.ls_pool[int(c) % len(self.ls_pool)])
            out[key] = (s_qty, s_px, s_dp, s_ch, s_qty / n / 100,
                        s_px / n / 100, s_disc / n / 100, n)
        self._answers[control] = out
        return out

    def control_rows(self, params, control: str):
        """The rows the wire would carry if the sums were accumulated in
        `control` precision."""
        rows = []
        for (rf, ls), w in sorted(self.answer(control).items()):
            rows.append((rf, ls, _fmt(w[0], 2), _fmt(w[1], 2), _fmt(w[2], 4),
                         _fmt(w[3], 6), f"{np.float32(w[4]):.4f}",
                         f"{np.float32(w[5]):.4f}",
                         f"{np.float32(w[6]):.4f}", str(w[7])))
        return rows

    def check(self, responses):
        """responses: [(params, rows)] -> ([ok per response], [compared])."""
        want = self.answer()
        oks, worst = [], {k: 0 for k in LIMITS}
        for _params, rows in responses:
            bad_groups = abs(len(rows) - len(want))
            bad_exact = 0
            units = 0.0
            for r in rows:
                w = want.get((r[0], r[1]))
                if w is None or len(r) != 10:
                    bad_groups += 1
                    continue
                for got, exp, scale in ((r[2], w[0], 2), (r[3], w[1], 2),
                                        (r[4], w[2], 4), (r[5], w[3], 6),
                                        (r[9], w[7], 0)):
                    if Decimal(got) != Decimal(exp).scaleb(-scale):
                        bad_exact += 1
                for got, exp in ((r[6], w[4]), (r[7], w[5]), (r[8], w[6])):
                    units = max(units, avg_units(float(got), exp))
            got = {"groups_missing_or_extra": bad_groups,
                   "exact_columns_mismatched": bad_exact,
                   "avg_err_units": units}
            oks.append(all(got[k] <= LIMITS[k] for k in LIMITS))
            for k in worst:
                worst[k] = max(worst[k], got[k])
        compared = [{"name": k, "value": worst[k], "limit": LIMITS[k],
                     "ok": worst[k] <= LIMITS[k]} for k in LIMITS]
        return oks, compared
