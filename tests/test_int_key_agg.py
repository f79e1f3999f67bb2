"""The int-key aggregate (ops/agg.int_key_aggregate) against a per-row
Python oracle, compacted and as the run-ends view, with a NULL key group;
and the round trip of the packing its inputs ride the sort in
(ops/bitpack.py)."""

import jax.numpy as jnp
import numpy as np
import pytest

from cockroach_tpu.coldata.batch import Batch, Column
from cockroach_tpu.ops.agg import AggSpec, int_key_aggregate
from cockroach_tpu.ops.bitpack import (
    pack_lanes, plan_pack, unpack_lanes,
)


def _batch(cols, sel=None):
    cap = len(next(iter(cols.values()))[0] if isinstance(
        next(iter(cols.values())), tuple) else next(iter(cols.values())))
    out = {}
    for n, v in cols.items():
        if isinstance(v, tuple):
            vals, valid = v
            out[n] = Column(jnp.asarray(vals), jnp.asarray(valid))
        else:
            out[n] = Column(jnp.asarray(v), None)
    sel = (jnp.ones(cap, bool) if sel is None else jnp.asarray(sel))
    return Batch(out, sel, jnp.sum(sel).astype(jnp.int32))


def test_bitpack_roundtrip():
    rng = np.random.default_rng(0)
    b = _batch({
        "a": rng.integers(-500, 10_000, 64),
        "b": (rng.integers(0, 7, 64),
              rng.random(64) > 0.3),
        "c": rng.random(64).astype(np.float32),
        "d": rng.random(64) > 0.5,
    })
    plan = plan_pack(b, ["a", "b", "c", "d"])
    packed = pack_lanes(b, plan)
    cols = unpack_lanes(packed, plan, b)
    np.testing.assert_array_equal(cols["a"].values, b.col("a").values)
    valid = np.asarray(b.col("b").validity)
    np.testing.assert_array_equal(
        np.asarray(cols["b"].values)[valid],
        np.asarray(b.col("b").values)[valid])
    np.testing.assert_array_equal(cols["b"].validity, b.col("b").validity)
    np.testing.assert_array_equal(cols["c"].values, b.col("c").values)
    np.testing.assert_array_equal(cols["d"].values, b.col("d").values)


@pytest.mark.parametrize("out_cap", [0, 128])
def test_int_key_aggregate_vs_oracle(out_cap):
    rng = np.random.default_rng(4)
    n = 200
    k = rng.integers(-40, 40, n)
    v = rng.integers(-100, 100, n)
    sel = rng.random(n) > 0.15
    b = _batch({"k": k, "v": v}, sel=sel)
    res = int_key_aggregate(
        b, "k", [AggSpec("sum", "v", "s"),
                 AggSpec("count_star", None, "n")],
        out_capacity=out_cap)
    assert not bool(res.fallback)
    assert not bool(res.overflow)
    want = {}
    for i in range(n):
        if sel[i]:
            s, c = want.get(int(k[i]), (0, 0))
            want[int(k[i])] = (s + int(v[i]), c + 1)
    got = {}
    bt = res.batch
    smask = np.asarray(bt.sel)
    for i in range(bt.capacity):
        if smask[i]:
            got[int(bt.col("k").values[i])] = (
                int(bt.col("s").values[i]), int(bt.col("n").values[i]))
    assert got == want


def test_int_key_aggregate_null_key_group():
    b = _batch({"k": ([1, 1, 5, 2, 9], [True, True, False, False, True]),
                "v": [10, 20, 30, 40, 50]})
    res = int_key_aggregate(b, "k", [AggSpec("sum", "v", "s")],
                            out_capacity=8)
    bt = res.batch
    smask = np.asarray(bt.sel)
    kvalid = np.asarray(bt.col("k").validity)
    rows = {}
    for i in range(bt.capacity):
        if smask[i]:
            key = int(bt.col("k").values[i]) if kvalid[i] else None
            rows[key] = int(bt.col("s").values[i])
    # NULL keys (rows 5, 2 -> v 30+40) form ONE group
    assert rows == {1: 30, 9: 50, None: 70}
