"""Loader `tpch_sname`: the `tpch` loader's path with SUPPLIER.S_NAME as
clause 4.2.3 writes it.

Everything but one column is `loaders/tpch.py`'s: the same generator
(tpch_dbgen.TPCH, subclassed here, not edited), the same bulk ingest
(`MVCCStore.ingest_table`), the same read-only `MVCCCatalog`, the same
seed use, so every other column of every table is the value a `tpch-sf1`
cell loads from the same seed.

S_NAME is the text "Supplier#" followed by the supplier key as nine
digits (S_NAME = "Supplier#000000001" for S_SUPPKEY 1; format from memory
of clause 4.2.3). The program stores a STRING column as dictionary codes,
so the column is a dictionary of SF * 10,000 distinct strings in key
order (the code of a row is s_suppkey - 1): 10,000 codes at SF 1 still
fit the 2-byte code the pooled column had in the scan image, so `_WIRES`
is the generator's own. The other pooled columns (addresses, phones,
comments) stay codes into the 4096-entry pool; no statement of a cell may
read them.

load() and stored_width() are the contract of benchmark/README.md.
"""

from __future__ import annotations

import numpy as np

from benchmark.loaders import tpch, tpch_cname, tpch_dbgen


class TPCHSName(tpch_dbgen.TPCH):
    """tpch_dbgen.TPCH with supplier.s_name = Supplier#%09d of the key."""

    def schema(self, table: str):
        schema = super().schema(table)
        if table != "supplier":
            return schema
        if self.n_supplier > 1 << 15:
            raise ValueError("tpch_sname: s_name's 2-byte code holds "
                             "32,768 suppliers (SF 3.2)")
        names = getattr(self, "_sname", None)
        if names is None:   # made once: every schema() call shares them
            names = self._sname = np.asarray(
                [f"Supplier#{k:09d}"
                 for k in range(1, self.n_supplier + 1)], dtype=object)
        return type(schema)(schema.fields, dict(schema.dicts, s_name=names))

    def rows(self, name: str, lo: int, hi: int):
        out = super().rows(name, lo, hi)
        if name == "supplier":
            out["s_name"] = (out["s_suppkey"] - 1).astype(np.int32)
        return out


def stored_width(table: str, column: str) -> int:
    """Bytes one value of `column` takes in its scan image on the device:
    the generator's own _WIRES (s_name 2, as the pooled column)."""
    return tpch.WIRE_BYTES[TPCHSName._WIRES.get(column)]


def load(store, args: dict, tables, seed: int) -> dict:
    return tpch_cname.load_from(
        TPCHSName(sf=float(args["sf"]), seed=int(seed)), store, tables)
