"""Loader `tpch_mesh_cname`: the `tpch_cname` loader's tables, on a node
with a mesh.

The data is `loaders/tpch_cname.py`'s, unchanged: the same seed gives the
same rows as in a `tpch-sf1-q18` cell (C_NAME as clause 4.2.3 writes it, a
dictionary of 150,000 strings at SF 1), so a cell of this configuration
and the one-chip cell of the same statement ask for the same answer at the
same binding. What this loader adds is `loaders/tpch_mesh.py`'s two lines
over the other loader: the catalog it returns carries a device mesh of
`args["chips"]` devices (`parallel/mesh.make_mesh`; the number comes from
the configuration's file, not from what the host happens to show), which
is where a session with `distsql = on | always` finds it (`Catalog.mesh`).

A program without `SET distsql` takes the attribute and ignores it; its
run ends at the configuration's `session_setup`, before any statement. A
program whose distributed runner does not take Q18 (a repartitioned join
nested inside a build, until the builds that a ShrinkOp keeps small were
gathered) answers the first execution with sqlstate 0A000, and the run
ends there.

load() and stored_width() are the contract of benchmark/README.md.
"""

from __future__ import annotations

from benchmark.loaders import tpch_cname

stored_width = tpch_cname.stored_width


def load(store, args: dict, tables, seed: int) -> dict:
    from cockroach_tpu.parallel import make_mesh

    loaded = tpch_cname.load(store, {"sf": args["sf"]}, tables, seed)
    loaded["catalog"].mesh = make_mesh(int(args["chips"]))
    return loaded
