"""Loader `tpch_cname`: the `tpch` loader's path with CUSTOMER.C_NAME as
clause 4.2.3 writes it.

Everything but one column is `loaders/tpch.py`'s: the same generator
(tpch_dbgen.TPCH, subclassed here, not edited), the same bulk ingest
(`MVCCStore.ingest_table`), the same read-only `MVCCCatalog`, the same
seed use, so every other column of every table is the value a `tpch-sf1`
cell loads from the same seed.

C_NAME is the text "Customer#" followed by the customer key as nine
digits (C_NAME = "Customer#000000001" for C_CUSTKEY 1; format from memory
of clause 4.2.3). The program stores a STRING column as dictionary codes,
so the column is a dictionary of SF * 150,000 distinct strings in key
order (the code of a row is c_custkey - 1) and a 4-byte code in the scan
image where the pooled column had 2 ("i4" where tpch_dbgen._WIRES has
"i2": 150,000 codes do not fit 16 bits). The other pooled columns
(addresses, phones, comments) stay codes into the 4096-entry pool; no
statement of a cell may read them.

load() and stored_width() are the contract of benchmark/README.md.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.loaders import tpch, tpch_dbgen


class TPCHCName(tpch_dbgen.TPCH):
    """tpch_dbgen.TPCH with customer.c_name = Customer#%09d of the key."""

    _WIRES = dict(tpch_dbgen.TPCH._WIRES, c_name="i4")

    def schema(self, table: str):
        schema = super().schema(table)
        if table != "customer":
            return schema
        names = np.asarray([f"Customer#{k:09d}"
                            for k in range(1, self.n_customer + 1)],
                           dtype=object)
        return type(schema)(schema.fields, dict(schema.dicts, c_name=names))

    def rows(self, name: str, lo: int, hi: int):
        out = super().rows(name, lo, hi)
        if name == "customer":
            out["c_name"] = (out["c_custkey"] - 1).astype(np.int32)
        return out


def stored_width(table: str, column: str) -> int:
    """Bytes one value of `column` takes in its scan image on the device:
    TPCHCName._WIRES (c_name 4; every other column as loaders/tpch.py)."""
    return tpch.WIRE_BYTES[TPCHCName._WIRES.get(column)]


def load(store, args: dict, tables, seed: int) -> dict:
    return load_from(TPCHCName(sf=float(args["sf"]), seed=int(seed)),
                     store, tables)


def load_from(gen: tpch_dbgen.TPCH, store, tables) -> dict:
    """loaders/tpch.load over the generator it is given (that file names
    its generator in the body of load(), so the body is repeated here
    line for line; tests/test_q18.py passes a generator with tied sort
    keys)."""
    from cockroach_tpu.sql.plan import _TPCH_PKS, MVCCCatalog
    from cockroach_tpu.sql.stats import sample_stats

    t0 = time.perf_counter()
    names = [t for t in tpch.TABLE_ORDER if t in set(tables)]
    unknown = set(tables) - set(names)
    if unknown:
        raise ValueError(f"tpch_cname loader: unknown tables "
                         f"{sorted(unknown)}")
    mapping, rows, stats, data, dicts = {}, {}, {}, {}, {}
    for name in names:
        tid = 10 + tpch.TABLE_ORDER.index(name)
        schema = gen.schema(name)
        cols = gen.table(name)
        ordered = {f.name: np.asarray(cols[f.name], dtype=np.int64)
                   for f in schema}
        n = gen.num_rows(name)
        store.ingest_table(tid, np.arange(n, dtype=np.int64), ordered)
        mapping[name] = (tid, schema)
        rows[name] = n
        stats[name] = sample_stats([ordered], schema)
        stats[name].row_count = n
        data[name] = cols
        for col, pool in schema.dicts.items():
            dicts[col] = [str(s) for s in pool]
    gen._money = None   # 0.3 GB at SF1, of use only while tables are made
    catalog = MVCCCatalog(store, mapping, rows=rows,
                          pks={t: _TPCH_PKS[t] for t in names
                               if t in _TPCH_PKS},
                          stats=stats)
    return {"store": store, "catalog": catalog, "data": data,
            "dicts": dicts, "rows": rows,
            "load_s": time.perf_counter() - t0}
