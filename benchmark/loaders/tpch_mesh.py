"""Loader `tpch_mesh`: the `tpch` loader's tables, on a node with a mesh.

The data is `loaders/tpch.py`'s, unchanged: the same seed gives the same
rows as in a `tpch-sf1` cell, so a cell of this configuration and the
one-chip cell of the same statement ask for the same answer. What this
loader adds is the deployment: the catalog it returns carries a device
mesh of `args["chips"]` devices (`parallel/mesh.make_mesh`; the number
comes from the configuration's file, not from what the host happens to
show), which is where a session with `distsql = on | always` finds it
(`Catalog.mesh`). `PgServer(catalog, capacity=)` serves it as it serves
any catalog.

A program without `SET distsql` takes the attribute and ignores it; its
run ends at the configuration's `session_setup`, before any statement.
"""

from __future__ import annotations

from benchmark.loaders import tpch

stored_width = tpch.stored_width


def load(store, args: dict, tables, seed: int) -> dict:
    from cockroach_tpu.parallel import make_mesh

    loaded = tpch.load(store, {"sf": args["sf"]}, tables, seed)
    loaded["catalog"].mesh = make_mesh(int(args["chips"]))
    return loaded
