"""M1 kernel library tests (ops/).

Modeled on the reference's operator-level harness
(colexectestutils.RunTests, utils.go:320): fixed tuple fixtures checked
against an oracle — here numpy/python recomputation — plus randomized
inputs with NULLs and sparse selection masks (the analog of running with
random selection vectors).
"""

import numpy as np
import jax.numpy as jnp
import pytest

import cockroach_tpu as ct
from cockroach_tpu.coldata.batch import Batch, Column, Schema, Field, INT, FLOAT, STRING, DECIMAL, DATE
from cockroach_tpu.ops import (
    hash_columns, group_assignment, AggSpec, hash_aggregate,
    SortKey, sort_batch, top_k_batch, hash_join, distinct,
)
from cockroach_tpu.ops import expr as E


def make_batch(cols, sel=None):
    """cols: {name: (np_values, np_validity_or_None)}"""
    out = {}
    cap = None
    for n, (v, val) in cols.items():
        v = np.asarray(v)
        cap = len(v)
        out[n] = Column(jnp.asarray(v),
                        None if val is None else jnp.asarray(np.asarray(val)))
    if sel is None:
        sel = np.ones(cap, dtype=bool)
    sel = jnp.asarray(np.asarray(sel))
    return Batch(out, sel, jnp.sum(sel).astype(jnp.int32))


# ---------------------------------------------------------------- hashing --

def test_hash_columns_deterministic_and_seeded():
    b = make_batch({"k": (np.array([1, 2, 1, 3], dtype=np.int64), None)})
    h1 = np.asarray(hash_columns(b, ["k"]))
    h2 = np.asarray(hash_columns(b, ["k"]))
    np.testing.assert_array_equal(h1, h2)
    assert h1[0] == h1[2] and h1[0] != h1[1]
    h3 = np.asarray(hash_columns(b, ["k"], seed=7))
    assert not np.array_equal(h1, h3)  # Grace recursion needs fresh bits


def test_hash_deselected_lanes_zero():
    sel = np.array([True, False, True, False])
    b = make_batch({"k": (np.arange(4, dtype=np.int64), None)}, sel=sel)
    h = np.asarray(hash_columns(b, ["k"]))
    assert h[1] == 0 and h[3] == 0 and h[0] != 0


# ---------------------------------------------------------- group assign --

def test_group_assignment_basic():
    keys = np.array([5, 7, 5, 9, 7, 5], dtype=np.int64)
    b = make_batch({"k": (keys, None)})
    ga = group_assignment(b, ["k"])
    gid = np.asarray(ga.group_id)
    assert int(ga.num_groups) == 3
    # first-occurrence order: 5 -> 0, 7 -> 1, 9 -> 2
    np.testing.assert_array_equal(gid, [0, 1, 0, 2, 1, 0])
    np.testing.assert_array_equal(np.asarray(ga.leader_row)[:3], [0, 1, 3])


def test_group_assignment_nulls_group_together():
    keys = np.array([1, 1, 2, 1], dtype=np.int64)
    validity = np.array([True, False, True, False])
    b = make_batch({"k": (keys, validity)})
    ga = group_assignment(b, ["k"])
    gid = np.asarray(ga.group_id)
    assert int(ga.num_groups) == 3
    assert gid[1] == gid[3]          # the two NULLs are one group
    assert gid[0] != gid[1]


def test_group_assignment_respects_sel():
    keys = np.array([1, 2, 1, 2], dtype=np.int64)
    b = make_batch({"k": (keys, None)}, sel=[True, False, True, False])
    ga = group_assignment(b, ["k"])
    assert int(ga.num_groups) == 1
    gid = np.asarray(ga.group_id)
    assert gid[1] == -1 and gid[3] == -1


def test_group_assignment_multicol_random():
    rng = np.random.default_rng(1)
    n = 512
    a = rng.integers(0, 13, n).astype(np.int64)
    c = rng.integers(0, 7, n).astype(np.int64)
    b = make_batch({"a": (a, None), "c": (c, None)})
    ga = group_assignment(b, ["a", "c"])
    gid = np.asarray(ga.group_id)
    oracle = {}
    for i in range(n):
        key = (a[i], c[i])
        if key not in oracle:
            oracle[key] = gid[i]
        assert gid[i] == oracle[key]
    assert int(ga.num_groups) == len(oracle)


# ----------------------------------------------------------------- aggs ---

def test_hash_aggregate_sums_counts():
    k = np.array([1, 2, 1, 2, 1], dtype=np.int64)
    v = np.array([10, 20, 30, 40, 50], dtype=np.int64)
    validity = np.array([True, True, False, True, True])
    b = make_batch({"k": (k, None), "v": (v, validity)})
    out = hash_aggregate(b, ["k"], [
        AggSpec("sum", "v", "s"), AggSpec("count", "v", "c"),
        AggSpec("count_star", None, "n"), AggSpec("min", "v", "mn"),
        AggSpec("max", "v", "mx"), AggSpec("avg", "v", "a"),
    ])
    ng = int(out.length)
    assert ng == 2
    kk = np.asarray(out.col("k").values)[:ng]
    s = np.asarray(out.col("s").values)[:ng]
    c = np.asarray(out.col("c").values)[:ng]
    n = np.asarray(out.col("n").values)[:ng]
    mn = np.asarray(out.col("mn").values)[:ng]
    mx = np.asarray(out.col("mx").values)[:ng]
    a = np.asarray(out.col("a").values)[:ng]
    i1 = int(np.nonzero(kk == 1)[0][0])
    i2 = int(np.nonzero(kk == 2)[0][0])
    assert s[i1] == 60 and s[i2] == 60          # NULL v at row 2 skipped
    assert c[i1] == 2 and c[i2] == 2
    assert n[i1] == 3 and n[i2] == 2
    assert mn[i1] == 10 and mx[i1] == 50
    assert abs(a[i1] - 30.0) < 1e-5


def test_scalar_aggregate_no_groups():
    v = np.array([1.0, 2.0, 3.0], dtype=np.float32)
    b = make_batch({"v": (v, None)})
    out = hash_aggregate(b, [], [AggSpec("sum", "v", "s"),
                                 AggSpec("count_star", None, "n")])
    assert int(out.length) == 1
    assert abs(float(out.col("s").values[0]) - 6.0) < 1e-6
    assert int(out.col("n").values[0]) == 3


def test_aggregate_all_null_group_yields_null():
    k = np.array([1, 1], dtype=np.int64)
    v = np.array([5, 6], dtype=np.int64)
    validity = np.array([False, False])
    b = make_batch({"k": (k, None), "v": (v, validity)})
    out = hash_aggregate(b, ["k"], [AggSpec("sum", "v", "s")])
    assert int(out.length) == 1
    assert not bool(out.col("s").validity[0])


# ----------------------------------------------------------------- sort ---

def test_sort_multi_key_desc_nulls():
    a = np.array([3, 1, 2, 1, 9], dtype=np.int64)
    validity = np.array([True, True, True, True, False])
    f = np.array([0.5, 2.5, 1.5, 0.5, 9.9], dtype=np.float32)
    b = make_batch({"a": (a, validity), "f": (f, None)})
    out = sort_batch(b, [SortKey("a"), SortKey("f", descending=True)])
    av = np.asarray(out.col("a").values)
    aval = np.asarray(out.col("a").validity)
    fv = np.asarray(out.col("f").values)
    # NULL first (ASC default), then 1,1 (f desc: 2.5 then 0.5), 2, 3
    assert not aval[0]
    np.testing.assert_array_equal(av[1:], [1, 1, 2, 3])
    np.testing.assert_allclose(fv[1:3], [2.5, 0.5])


def test_sort_pushes_deselected_last():
    a = np.array([4, 3, 2, 1], dtype=np.int64)
    b = make_batch({"a": (a, None)}, sel=[True, False, True, False])
    out = sort_batch(b, [SortKey("a")])
    av = np.asarray(out.col("a").values)
    np.testing.assert_array_equal(av[:2], [2, 4])
    assert int(out.length) == 2
    np.testing.assert_array_equal(np.asarray(out.sel), [True, True, False, False])


def test_top_k():
    a = np.array([5, 1, 4, 2, 3], dtype=np.int64)
    b = make_batch({"a": (a, None)})
    out = top_k_batch(b, [SortKey("a")], k=3)
    np.testing.assert_array_equal(np.asarray(out.col("a").values), [1, 2, 3])
    out2 = top_k_batch(b, [SortKey("a", descending=True)], k=2)
    np.testing.assert_array_equal(np.asarray(out2.col("a").values), [5, 4])


def test_top_k_larger_than_input():
    a = np.array([2, 1], dtype=np.int64)
    b = make_batch({"a": (a, None)})
    out = top_k_batch(b, [SortKey("a")], k=5)
    assert int(out.length) == 2
    np.testing.assert_array_equal(np.asarray(out.sel),
                                  [True, True, False, False, False])


def test_sort_float_negatives():
    f = np.array([0.0, -1.5, 2.0, -0.0, -3.0], dtype=np.float32)
    b = make_batch({"f": (f, None)})
    out = sort_batch(b, [SortKey("f")])
    fv = np.asarray(out.col("f").values)
    np.testing.assert_allclose(fv, [-3.0, -1.5, 0.0, -0.0, 2.0])


# ----------------------------------------------------------------- join ---

def _join_oracle(lk, rk, how):
    pairs = []
    lmatched = set()
    rmatched = set()
    for i, a in enumerate(lk):
        for j, c in enumerate(rk):
            if a is not None and c is not None and a == c:
                pairs.append((i, j))
                lmatched.add(i)
                rmatched.add(j)
    if how == "inner":
        return pairs
    if how == "left":
        return pairs + [(i, None) for i in range(len(lk)) if i not in lmatched]
    if how == "right":
        return pairs + [(None, j) for j in range(len(rk)) if j not in rmatched]
    if how == "outer":
        return (pairs + [(i, None) for i in range(len(lk)) if i not in lmatched]
                + [(None, j) for j in range(len(rk)) if j not in rmatched])
    if how == "semi":
        return sorted(lmatched)
    if how == "anti":
        return [i for i in range(len(lk)) if i not in lmatched]


@pytest.mark.parametrize("how", ["inner", "left", "right", "outer", "semi", "anti"])
def test_hash_join_types(how):
    lk = np.array([1, 2, 3, 2, 7], dtype=np.int64)
    lv = np.array([10, 20, 30, 21, 70], dtype=np.int64)
    rk = np.array([2, 2, 3, 5], dtype=np.int64)
    rv = np.array([200, 201, 300, 500], dtype=np.int64)
    left = make_batch({"lk": (lk, None), "lv": (lv, None)})
    right = make_batch({"rk": (rk, None), "rv": (rv, None)})
    res = hash_join(left, right, ["lk"], ["rk"], how=how, out_capacity=32)
    assert not bool(res.overflow)
    out = res.batch
    sel = np.asarray(out.sel)
    oracle = _join_oracle(list(lk), list(rk), how)

    if how in ("semi", "anti"):
        got_rows = [int(v) for v in np.asarray(out.col("lk").values)[sel]]
        want = sorted(int(lk[i]) for i in oracle)
        assert sorted(got_rows) == want
        return

    got = []
    lkv = np.asarray(out.col("lk").values)
    lkval = out.col("lk").validity
    lkval = np.ones(len(sel), bool) if lkval is None else np.asarray(lkval)
    rkv = np.asarray(out.col("rk").values)
    rkval = out.col("rk").validity
    rkval = np.ones(len(sel), bool) if rkval is None else np.asarray(rkval)
    lvv = np.asarray(out.col("lv").values)
    rvv = np.asarray(out.col("rv").values)
    for i in np.nonzero(sel)[0]:
        lside = int(lvv[i]) if lkval[i] else None
        rside = int(rvv[i]) if rkval[i] else None
        got.append((lside, rside))
    want = []
    for i, j in oracle:
        want.append((int(lv[i]) if i is not None else None,
                     int(rv[j]) if j is not None else None))
    assert sorted(got, key=str) == sorted(want, key=str)
    assert int(out.length) == len(want)


def test_join_null_keys_never_match():
    lk = np.array([1, 2], dtype=np.int64)
    lval = np.array([True, False])
    rk = np.array([2, 1], dtype=np.int64)
    rval = np.array([False, True])
    left = make_batch({"lk": (lk, lval), "lv": (np.array([1, 2], np.int64), None)})
    right = make_batch({"rk": (rk, rval), "rv": (np.array([3, 4], np.int64), None)})
    res = hash_join(left, right, ["lk"], ["rk"], how="inner", out_capacity=8)
    out = res.batch
    sel = np.asarray(out.sel)
    assert int(out.length) == 1  # only 1==1 (both non-NULL)
    i = np.nonzero(sel)[0][0]
    assert int(out.col("lk").values[i]) == 1


def test_join_overflow_flag():
    lk = np.zeros(8, dtype=np.int64)
    rk = np.zeros(8, dtype=np.int64)
    left = make_batch({"lk": (lk, None)})
    right = make_batch({"rk": (rk, None)})
    res = hash_join(left, right, ["lk"], ["rk"], how="semi", out_capacity=16)
    assert bool(res.overflow)  # 64 pairs > 16


def test_join_random_against_oracle():
    rng = np.random.default_rng(3)
    lk = rng.integers(0, 20, 200).astype(np.int64)
    rk = rng.integers(0, 20, 100).astype(np.int64)
    left = make_batch({"lk": (lk, None)})
    right = make_batch({"rk": (rk, None)})
    res = hash_join(left, right, ["lk"], ["rk"], how="inner",
                    out_capacity=4096)
    assert not bool(res.overflow)
    want = sum(1 for a in lk for b_ in rk if a == b_)
    assert int(res.batch.length) == want


# -------------------------------------------------------------- distinct --

def test_distinct():
    k = np.array([1, 2, 1, 3, 2], dtype=np.int64)
    b = make_batch({"k": (k, None)})
    out = distinct(b, ["k"])
    sel = np.asarray(out.sel)
    np.testing.assert_array_equal(sel, [True, True, False, True, False])


# ----------------------------------------------------------------- expr ---

def _schema_with_dict():
    d = np.array(["AIR", "MAIL", "SHIP", "TRUCK"])
    return Schema(
        [Field("qty", INT), Field("price", DECIMAL(2)),
         Field("disc", DECIMAL(2)), Field("mode", STRING, dict_ref="m"),
         Field("d", DATE)],
        dicts={"m": d},
    )


def _expr_batch():
    return make_batch({
        "qty": (np.array([5, 30, 17, 40], dtype=np.int64), None),
        "price": (np.array([10050, 20000, 99, 500], dtype=np.int64), None),   # 100.50 etc
        "disc": (np.array([5, 10, 0, 7], dtype=np.int64), None),              # 0.05 ...
        "mode": (np.array([0, 2, 1, 3], dtype=np.int32), None),
        "d": (np.array([9500, 9600, 9700, 9800], dtype=np.int32), None),
    })


def test_expr_filter_and_arith():
    sch = _schema_with_dict()
    b = _expr_batch()
    mask = E.filter_mask(E.Col("qty") < 24, b, sch)
    np.testing.assert_array_equal(np.asarray(mask), [True, False, True, False])

    # disc_price = price * (1 - disc): decimal mul scales 2+2 -> 4
    e = E.BinOp("*", E.Col("price"),
                E.BinOp("-", E.Lit(1.0, DECIMAL(2)), E.Col("disc")))
    c = E.eval_expr(e, b, sch)
    # row0: 100.50 * 0.95 = 95.475 -> scaled 1e4 => 954750
    assert int(c.values[0]) == 10050 * 95
    assert e.type(sch).scale == 4


def test_expr_string_predicates():
    sch = _schema_with_dict()
    b = _expr_batch()
    eq = E.filter_mask(E.Cmp("==", E.Col("mode"), E.Lit("SHIP")), b, sch)
    np.testing.assert_array_equal(np.asarray(eq), [False, True, False, False])
    inl = E.filter_mask(E.InList(E.Col("mode"), ("AIR", "TRUCK")), b, sch)
    np.testing.assert_array_equal(np.asarray(inl), [True, False, False, True])
    like = E.filter_mask(E.Like(E.Col("mode"), "%AI%"), b, sch)
    np.testing.assert_array_equal(np.asarray(like), [True, False, True, False])


def test_expr_case_and_extract():
    sch = _schema_with_dict()
    b = _expr_batch()
    e = E.Case(((E.Cmp("==", E.Col("mode"), E.Lit("SHIP")), E.Col("qty")),),
               otherwise=E.Lit(0))
    c = E.eval_expr(e, b, sch)
    np.testing.assert_array_equal(np.asarray(c.values), [0, 30, 0, 0])

    y = E.eval_expr(E.Extract("year", E.Col("d")), b, sch)
    import datetime
    for i, days in enumerate([9500, 9600, 9700, 9800]):
        want = (datetime.date(1970, 1, 1) + datetime.timedelta(days=days)).year
        assert int(y.values[i]) == want


def test_expr_three_valued_logic():
    sch = Schema([Field("a", INT), Field("b", INT)])
    b = make_batch({
        "a": (np.array([1, 1, 0], np.int64), np.array([True, False, True])),
        "b": (np.array([1, 1, 1], np.int64), None),
    })
    # a == b: row1 NULL -> dropped by filter
    m = E.filter_mask(E.Cmp("==", E.Col("a"), E.Col("b")), b, sch)
    np.testing.assert_array_equal(np.asarray(m), [True, False, False])
    # NULL OR TRUE = TRUE
    m2 = E.filter_mask(
        E.BoolOp("or", (E.Cmp("==", E.Col("a"), E.Col("b")),
                        E.Cmp("==", E.Col("b"), E.Col("b")))), b, sch)
    np.testing.assert_array_equal(np.asarray(m2), [True, True, True])


def test_expr_isnull():
    sch = Schema([Field("a", INT)])
    b = make_batch({"a": (np.array([1, 2], np.int64),
                          np.array([True, False]))})
    m = E.filter_mask(E.IsNull(E.Col("a")), b, sch)
    np.testing.assert_array_equal(np.asarray(m), [False, True])


def test_expr_int_literal_decimal_typed():
    sch = _schema_with_dict()
    b = _expr_batch()
    # price == 200 with the literal typed DECIMAL(2): must scale to 20000
    m = E.filter_mask(
        E.Cmp("==", E.Col("price"), E.Lit(200, DECIMAL(2))), b, sch)
    np.testing.assert_array_equal(np.asarray(m), [False, True, False, False])


def test_expr_string_col_vs_col_ordering():
    d = np.array(["zebra", "apple", "mango"])
    sch = Schema([Field("a", STRING, dict_ref="s"),
                  Field("b", STRING, dict_ref="s")], dicts={"s": d})
    b = make_batch({"a": (np.array([0, 1], np.int32), None),
                    "b": (np.array([1, 2], np.int32), None)})
    # "zebra" < "apple" is False; "apple" < "mango" is True — must compare
    # lexicographically, not by first-occurrence dictionary code
    m = E.filter_mask(E.Cmp("<", E.Col("a"), E.Col("b")), b, sch)
    np.testing.assert_array_equal(np.asarray(m), [False, True])
    eqm = E.filter_mask(E.Cmp("==", E.Col("a"), E.Col("b")), b, sch)
    np.testing.assert_array_equal(np.asarray(eqm), [False, False])


def test_blocked_cumsum_matches_numpy(rng):
    from cockroach_tpu.ops.prefix import blocked_cumsum
    import jax

    for n in [1, 7, 512, 513, 5000]:
        x = rng.integers(-(1 << 40), 1 << 40, n)
        got = np.asarray(jax.jit(lambda v: blocked_cumsum(v, block=64))(
            jnp.asarray(x)))
        np.testing.assert_array_equal(got, np.cumsum(x))


def test_blocked_assoc_scan_segmented(rng):
    from cockroach_tpu.ops.prefix import blocked_assoc_scan
    import jax

    n = 3000
    vals = rng.integers(-1000, 1000, n)
    boundary = rng.random(n) < 0.05
    boundary[0] = True

    def combine(x, y):
        a, f1 = x
        b, f2 = y
        return jnp.where(f2, b, jnp.minimum(a, b)), f1 | f2

    got, _ = jax.jit(lambda v, b: blocked_assoc_scan(
        combine, (v, b), block=64))(jnp.asarray(vals), jnp.asarray(boundary))
    # reference: per-segment running min
    exp = np.zeros(n, dtype=vals.dtype)
    cur = None
    for i in range(n):
        cur = vals[i] if boundary[i] or cur is None else min(cur, vals[i])
        exp[i] = cur
    np.testing.assert_array_equal(np.asarray(got), exp)


def test_ordered_aggregate_matches_hash(rng):
    from cockroach_tpu.ops.agg import hash_aggregate, ordered_aggregate

    cap = 64
    keys = np.sort(rng.integers(0, 10, cap))
    vals = rng.integers(-100, 100, cap)
    b = Batch({"k": Column(jnp.asarray(keys)),
               "v": Column(jnp.asarray(vals))},
              jnp.arange(cap) < 50, jnp.int32(50))
    aggs = [AggSpec("sum", "v", "s"), AggSpec("count_star", None, "n"),
            AggSpec("min", "v", "mn")]
    oa = ordered_aggregate(b, ["k"], aggs)
    ha = hash_aggregate(b, ["k"], aggs)
    assert int(oa.length) == int(ha.length)
    n = int(oa.length)

    def rows(out):
        return sorted(
            (int(out.col("k").values[i]), int(out.col("s").values[i]),
             int(out.col("n").values[i]), int(out.col("mn").values[i]))
            for i in range(n))

    assert rows(oa) == rows(ha)


_EVERY_AGG = [AggSpec("sum", "v", "s"), AggSpec("sum", "w", "sw"),
              AggSpec("count", "v", "c"), AggSpec("count_star", None, "n"),
              AggSpec("min", "v", "mn"), AggSpec("max", "f", "mx"),
              AggSpec("sum_hi32", "v", "hi"), AggSpec("sum_lo32", "v", "lo"),
              AggSpec("bool_and", "bl", "ba"), AggSpec("bool_or", "bl", "bo"),
              AggSpec("any_not_null", "v", "an"), AggSpec("sum", "f", "sf"),
              # avg's parts, as HashAggOp decomposes it
              AggSpec("sum", "v", "avs"), AggSpec("count", "v", "avc")]


def _grouped_batch(rng, cap, n_keys, sel, null_key=None):
    """Keys (k1 nullable where `null_key` names a value, k2 a function of
    k1) in contiguous runs over ALL lanes; every input type with NULLs."""
    k1 = np.sort(rng.integers(0, n_keys, cap))
    v = rng.integers(-(1 << 40), 1 << 40, cap)
    null = rng.random(cap) < 0.3
    return make_batch({
        "k1": (k1, None if null_key is None else k1 != null_key),
        "k2": ((k1 * 3) % 2, None),
        "v": (v, ~null),
        "w": ((v % 1000).astype(np.int32), None),
        "f": (rng.normal(size=cap).astype(np.float32), None),
        "bl": (rng.random(cap) < 0.5, ~null)}, sel)


def _agg_rows(out, names):
    rows = []
    for i in np.nonzero(np.asarray(out.sel))[0]:
        row = []
        for n in names:
            c = out.col(n)
            ok = c.validity is None or bool(c.validity[i])
            row.append((ok, float(c.values[i]) if ok else 0.0))
        rows.append(tuple(row))
    return sorted(rows)


_RUN_ENDS_CASES = {
    # name: (capacity, distinct keys, selection, NULL key value)
    "dense_prefix": (600, 40, lambda r, c: np.arange(c) < 417, None),
    "every_lane_live": (64, 9, lambda r, c: np.ones(c, bool), None),
    "runs_of_one": (64, 1 << 30, lambda r, c: np.arange(c) < 50, None),
    "one_run_the_whole_batch": (700, 1, lambda r, c: np.ones(c, bool), None),
    "one_lane": (1, 1, lambda r, c: np.ones(c, bool), None),
    "dead_tail": (1500, 30, lambda r, c: np.arange(c) < 3, None),
    "all_dead": (600, 7, lambda r, c: np.zeros(c, bool), None),
    "null_key_run": (600, 12, lambda r, c: np.arange(c) < 500, 1),
    "holes_in_runs": (600, 9, lambda r, c: r.random(c) < 0.6, None),
    "sparse_holes": (1500, 200, lambda r, c: r.random(c) < 0.1, 3),
    # every run's FIRST lane dead, and every run's last
    "first_lane_of_every_run_dead": (600, 15, "first", None),
    "last_lane_of_every_run_dead": (600, 15, "last", None),
}


@pytest.mark.parametrize("case", sorted(_RUN_ENDS_CASES))
def test_run_ends_aggregate_matches_lex(case, rng):
    """The in-place grouped aggregate against the exact lexsort path:
    every supported function with NULL inputs, the same groups and the
    same values, each group once at its run's last live lane; `dense`
    (the caller's word that live lanes come first) changes nothing where
    it is true; ordered_aggregate is its compacted form."""
    from cockroach_tpu.ops.agg import ordered_aggregate, run_ends_aggregate

    cap, n_keys, sel_of, null_key = _RUN_ENDS_CASES[case]
    b = _grouped_batch(rng, cap, n_keys, np.ones(cap, bool), null_key)
    k1 = np.asarray(b.col("k1").values)
    if sel_of in ("first", "last"):
        edge = np.r_[True, k1[1:] != k1[:-1]]
        sel = ~(edge if sel_of == "first" else np.r_[edge[1:], True])
    else:
        sel = sel_of(rng, cap)
    b = Batch(b.columns, jnp.asarray(sel), jnp.int32(sel.sum()))
    gb = ["k1", "k2"]
    exact = gb + [a.out for a in _EVERY_AGG if a.out not in ("sf", "an")]
    want = hash_aggregate(b, gb, _EVERY_AGG, method="lex")
    live_first = not sel.any() or sel[:sel.sum()].all()
    for dense in ([False, True] if live_first else [False]):
        got = run_ends_aggregate(b, gb, _EVERY_AGG, dense=dense)
        assert int(got.length) == int(want.length) == int(
            np.asarray(got.sel).sum())
        assert _agg_rows(got, exact) == _agg_rows(want, exact)
        # one lane a group, and it is a live lane of the input
        assert not (np.asarray(got.sel) & ~sel).any()
        for (gk, gs), (wk, ws) in zip(_agg_rows(got, ["k1", "sf"]),
                                      _agg_rows(want, ["k1", "sf"])):
            assert gk == wk and gs[0] == ws[0]
            assert abs(gs[1] - ws[1]) < 1e-3
        # any_not_null: SOME non-NULL input of the group
        vs = np.asarray(b.col("v").values)
        for (k, an) in _agg_rows(got, ["k1", "an"]):
            live = sel & np.asarray(b.col("v").validity) & (
                (k1 == k[1]) if k[0] else (k1 == null_key))
            assert an[0] == bool(live.any())
            assert not an[0] or an[1] in vs[live].astype(float)
    compacted = ordered_aggregate(b, gb, _EVERY_AGG)
    assert _agg_rows(compacted, exact) == _agg_rows(want, exact)
    assert (np.asarray(compacted.sel)
            == (np.arange(cap) < int(want.length))).all()


def test_blocked_cummax_matches_numpy(rng):
    import jax
    from cockroach_tpu.ops.prefix import blocked_cummax

    for n in (1, 5, 64, 65, 3000, 70001):
        for dt in (np.int64, np.int32, np.float32):
            x = rng.integers(-(1 << 30), 1 << 30, n).astype(dt)
            got = np.asarray(jax.jit(lambda v: blocked_cummax(v, block=64))(
                jnp.asarray(x)))
            np.testing.assert_array_equal(got, np.maximum.accumulate(x))


def test_ordered_agg_op_streaming(rng):
    from cockroach_tpu.exec import collect
    from cockroach_tpu.exec.operators import OrderedAggOp, ScanOp
    from cockroach_tpu.coldata.batch import Field, INT, Schema

    # sorted keys split across chunks: straddling runs must re-merge
    n = 100
    keys = np.sort(rng.integers(0, 12, n))
    vals = rng.integers(0, 50, n)
    schema = Schema([Field("k", INT), Field("v", INT)])

    def chunks():
        yield {"k": keys, "v": vals}

    scan = ScanOp(schema, chunks, 16)
    agg = OrderedAggOp(scan, ["k"], [AggSpec("sum", "v", "s")])
    res = collect(agg, fuse=False)
    got = dict(zip(res["k"].tolist(), res["s"].tolist()))
    exp = {int(k): int(vals[keys == k].sum()) for k in np.unique(keys)}
    assert got == exp


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_merge_join_matches_hash_join(rng, how):
    from cockroach_tpu.ops.join import hash_join, merge_join

    lcap, rcap = 48, 32
    lk = rng.integers(0, 20, lcap)
    rk = np.sort(rng.integers(0, 20, rcap))  # build pre-sorted
    left = Batch({"lk": Column(jnp.asarray(lk)),
                  "lv": Column(jnp.asarray(np.arange(lcap)))},
                 jnp.arange(lcap) < 40, jnp.int32(40))
    right = Batch({"rk": Column(jnp.asarray(rk)),
                   "rv": Column(jnp.asarray(np.arange(rcap)))},
                 jnp.arange(rcap) < 28, jnp.int32(28))
    mj = merge_join(left, right, ["lk"], ["rk"], how=how, out_capacity=256)
    hj = hash_join(left, right, ["lk"], ["rk"], how=how, out_capacity=256)
    assert not bool(mj.overflow) and not bool(hj.overflow)

    def rows(res):
        b = res.batch
        sel = np.asarray(b.sel)
        names = sorted(b.columns)
        return sorted(
            tuple(int(np.asarray(b.col(c).values)[i]) for c in names)
            for i in np.nonzero(sel)[0])

    assert rows(mj) == rows(hj)


def test_wide_sum_exact_beyond_int64(rng):
    """SF100-scale exactness (VERDICT r3 item 6): group sums that exceed
    int64 must come out exact via the two-lane (hi/lo) decomposition."""
    from cockroach_tpu.exec import collect
    from cockroach_tpu.exec.operators import HashAggOp, ScanOp
    from cockroach_tpu.coldata.batch import Field, INT, Schema

    n = 64
    # charge-like magnitudes ~2^61: a 16-row group sums to ~2^65 > int64
    vals = rng.integers(1 << 60, 1 << 61, n)
    keys = np.repeat(np.arange(4, dtype=np.int64), n // 4)
    schema = Schema([Field("k", INT), Field("v", INT)])

    def chunks():
        yield {"k": keys, "v": vals}

    for fuse in (True, False):
        scan = ScanOp(schema, chunks, 16)
        agg = HashAggOp(scan, ["k"],
                        [AggSpec("sum", "v", "s", wide=True),
                         AggSpec("count_star", None, "n")])
        res = collect(agg, fuse=fuse)
        # collect recombines the halves into exact python-int columns
        got = dict(zip((int(k) for k in res["k"]),
                       (int(v) for v in res["s"])))
        exp = {g: sum(int(v) for v in vals[keys == g]) for g in range(4)}
        assert got == exp, f"fuse={fuse}"
        assert max(exp.values()) > (1 << 63)  # the point of the test
