"""Loader `tpch_pname`: the `tpch` loader's path with PART.P_NAME as
clause 4.2.3 writes it.

Everything but one column is `loaders/tpch.py`'s: the same generator
(tpch_dbgen.TPCH, subclassed here, not edited), the same bulk ingest
(`MVCCStore.ingest_table`), the same read-only `MVCCCatalog`, the same
seed use, so every other column of every table is the value a `tpch-sf1`
cell loads from the same seed.

P_NAME is five DISTINCT words of the specification's colour list, joined
by single spaces ("lace spring maroon dim navajo"; rule from memory of
clause 4.2.3), a pure function of (seed, row). WORDS is the list as
`tpch_dbgen.COLORS` has it, less that list's one entry of two words ("hot
pink": the specification's list has "hot" and "pink", and 92 words). The
program stores a STRING column as dictionary codes, so the column is a
dictionary of the DISTINCT names in string order (at SF1 all but a handful
of the 200,000 parts: 92 * 91 * 90 * 89 * 88 ordered draws) and a 4-byte
code in the scan image where the pooled column had 2 ("i4" where
tpch_dbgen._WIRES has "i2": 200,000 codes do not fit 16 bits). The other
pooled columns (addresses, phones, comments) stay codes into the
4096-entry pool; no statement of a cell may read them.

load() and stored_width() are the contract of benchmark/README.md.
"""

from __future__ import annotations

import numpy as np

from benchmark.loaders import tpch, tpch_cname, tpch_dbgen

WORDS = tuple(c for c in tpch_dbgen.COLORS if " " not in c)
WORDS_PER_NAME = 5


def name_words(rows: np.ndarray, seed: int) -> np.ndarray:
    """(len(rows), 5) indices into WORDS, distinct within a row: draw k
    takes one of the 92 - k words not yet taken (the draw counts along the
    words left, in list order)."""
    n_words = len(WORDS)
    tag = tpch_dbgen._T["part"] * 100 + 20
    picks = np.empty((len(rows), WORDS_PER_NAME), np.int64)
    for k in range(WORDS_PER_NAME):
        idx = tpch_dbgen._uniform_int(rows, seed, tag + k, 0,
                                      n_words - k - 1)
        # step over the words already taken, smallest first
        taken = np.sort(picks[:, :k], axis=1)
        for j in range(k):
            idx = idx + (idx >= taken[:, j])
        picks[:, k] = idx
    return picks


class TPCHPName(tpch_dbgen.TPCH):
    """tpch_dbgen.TPCH with part.p_name = five distinct colour words."""

    _WIRES = dict(tpch_dbgen.TPCH._WIRES, p_name="i4")

    def _names(self):
        """(dictionary: the distinct names in string order, code of every
        part row), made once for the whole table."""
        made = getattr(self, "_pname", None)
        if made is None:
            picks = name_words(np.arange(self.n_part, dtype=np.int64),
                               self.seed)
            words = np.asarray(WORDS, dtype=object)
            names = words[picks[:, 0]]
            for k in range(1, WORDS_PER_NAME):
                names = names + " " + words[picks[:, k]]
            distinct, codes = np.unique(names.astype(str),
                                        return_inverse=True)
            made = self._pname = (distinct.astype(object),
                                  codes.astype(np.int32))
        return made

    def schema(self, table: str):
        schema = super().schema(table)
        if table != "part":
            return schema
        return type(schema)(schema.fields,
                            dict(schema.dicts, p_name=self._names()[0]))

    def rows(self, name: str, lo: int, hi: int):
        out = super().rows(name, lo, hi)
        if name == "part":
            out["p_name"] = self._names()[1][lo:hi]
        return out


def stored_width(table: str, column: str) -> int:
    """Bytes one value of `column` takes in its scan image on the device:
    TPCHPName._WIRES (p_name 4; every other column as loaders/tpch.py)."""
    return tpch.WIRE_BYTES[TPCHPName._WIRES.get(column)]


def load(store, args: dict, tables, seed: int) -> dict:
    return tpch_cname.load_from(
        TPCHPName(sf=float(args["sf"]), seed=int(seed)), store, tables)
