"""Loader `tpch`: TPC-H tables from the seed into an MVCCStore.

Data comes from the benchmark's own dbgen copy (tpch_dbgen.py); the ingest
is the program's bulk path, `MVCCStore.ingest_table` (the AddSSTable path
`TPCH.mvcc_load` uses), and the catalog is the read-only `MVCCCatalog` that
path returns. Only the tables the cell's statements name are generated and
loaded, always in the order of TABLE_ORDER so that table ids do not depend
on the cell.

load() -> dict with
  store, catalog      what PgServer serves
  data                {table: {column: numpy array}}, the reference's input
  dicts               {column: list of strings} for dictionary-coded columns
  rows                {table: row count}
  load_s              seconds of the bulk ingest (generation included)
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.loaders import tpch_dbgen

TABLE_ORDER = ("lineitem", "orders", "customer", "part", "supplier",
               "partsupp", "nation", "region")
WIRE_BYTES = {"i1": 1, "i2": 2, "i4": 4, None: 8}


def stored_width(table: str, column: str) -> int:
    """Bytes one value of `column` takes in its scan image on the device
    (the narrow transport dtype of tpch_dbgen._WIRES; 8 where none is
    declared)."""
    return WIRE_BYTES[tpch_dbgen.TPCH._WIRES.get(column)]


def load(store, args: dict, tables, seed: int) -> dict:
    from cockroach_tpu.sql.plan import _TPCH_PKS, MVCCCatalog
    from cockroach_tpu.sql.stats import sample_stats

    t0 = time.perf_counter()
    gen = tpch_dbgen.TPCH(sf=float(args["sf"]), seed=int(seed))
    names = [t for t in TABLE_ORDER if t in set(tables)]
    unknown = set(tables) - set(names)
    if unknown:
        raise ValueError(f"tpch loader: unknown tables {sorted(unknown)}")
    mapping, rows, stats, data, dicts = {}, {}, {}, {}, {}
    for name in names:
        tid = 10 + TABLE_ORDER.index(name)
        schema = gen.schema(name)
        cols = gen.table(name)
        ordered = {f.name: np.asarray(cols[f.name], dtype=np.int64)
                   for f in schema}
        n = gen.num_rows(name)
        store.ingest_table(tid, np.arange(n, dtype=np.int64), ordered)
        mapping[name] = (tid, schema)
        rows[name] = n
        # the arrays are in hand, so ANALYZE is free at load time (as in
        # TPCH.mvcc_load)
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
