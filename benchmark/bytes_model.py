"""Bytes a statement has to read from device memory: the yardstick behind
stmt_program_roofline.

A statement reads every row of every column it references, once, at the
column's stored width in its scan image:

    bytes = sum over tables, columns of rows(table) * stored_width(column)

The column list is the cell file's statements[].reads ({table: [columns]});
the stored width comes from the loader (`stored_width(table, column)`:
tpch_dbgen._WIRES for TPC-H). A later PR that changes
what a statement reads (a narrower image, a pruned column, an index that
skips rows) re-reckons here: the list in the cell file, the width in the
loader.

TPC-H SF1 (6,001,215 lineitem rows at dbgen's seed; +-0.1% by seed):
  Q1  lineitem: l_returnflag 1, l_linestatus 1, l_quantity 2,
      l_extendedprice 4, l_discount 1, l_tax 1, l_shipdate 2 = 12 B/row,
      about 72 MB (the smoke's first execution moved 72.4 MB, PR 22)
  Q3  lineitem: l_orderkey 4, l_extendedprice 4, l_discount 1,
      l_shipdate 2 = 11 B/row; orders: o_orderkey 4, o_custkey 4,
      o_orderdate 2, o_shippriority 1 = 11 B/row; customer: c_custkey 4,
      c_mktsegment 1 = 5 B/row; about 83 MB (84.9 MB moved, PR 22)
A statement with no `reads` (a point read: one row, whose roofline share
would be noise) counts 0 bytes and the metric is left out.
"""

from __future__ import annotations


def statement_bytes(statement: dict, loader, rows: dict) -> int:
    total = 0
    for table, columns in statement.get("reads", {}).items():
        for col in columns:
            total += rows[table] * loader.stored_width(table, col)
    return total


def cell_bytes(cell: dict, loader, rows: dict) -> float:
    """Mean bytes per statement of the cell's mix (clients take the
    statements round robin, so the mix is uniform)."""
    per = [statement_bytes(s, loader, rows) for s in cell["statements"]]
    return sum(per) / len(per) if per else 0.0
