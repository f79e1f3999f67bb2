"""Differential tests: unique sort-join (ops/sortjoin.py) vs the general
ragged-expansion join (ops/join.py) and numpy oracles.

Mirrors the reference's operator harness posture
(colexectestutils.RunTests, utils.go:320): same fixtures through both
implementations, unordered comparison.
"""

import numpy as np
import jax.numpy as jnp
import pytest

import cockroach_tpu  # noqa: F401  (x64 config)
from cockroach_tpu.coldata.batch import Batch, Column
from cockroach_tpu.ops import bitpack
from cockroach_tpu.ops.join import hash_join


def _batch(cols, sel=None):
    out = {}
    for n, v in cols.items():
        if isinstance(v, tuple):
            vals, valid = v
            out[n] = Column(jnp.asarray(vals), jnp.asarray(valid))
        else:
            out[n] = Column(jnp.asarray(v))
    b = Batch.from_columns(out)
    if sel is not None:
        b = b.with_sel(jnp.asarray(sel))
    return b


def _rows(res, names):
    """Set-of-tuples view of selected rows (None for NULL)."""
    return _batch_rows(res.batch, names)


def _batch_rows(batch, names):
    sel = np.asarray(batch.sel)
    out = []
    for i in range(len(sel)):
        if not sel[i]:
            continue
        row = []
        for n in names:
            c = batch.col(n)
            valid = (np.asarray(c.validity)[i]
                     if c.validity is not None else True)
            row.append(int(np.asarray(c.values)[i]) if valid else None)
        out.append(tuple(row))
    return sorted(out, key=str)


HOWS = ["inner", "left", "semi", "anti", "right", "outer"]


@pytest.mark.parametrize("how", HOWS)
def test_unique_matches_expand_int_keys(how):
    rng = np.random.default_rng(3)
    n, m = 257, 101
    probe = _batch({
        "pk": rng.integers(0, 150, n).astype(np.int64),
        "pv": np.arange(n, dtype=np.int64)})
    build = _batch({
        "bk": rng.permutation(150)[:m].astype(np.int64),
        "bv": (np.arange(m, dtype=np.int64) * 10,
               rng.integers(0, 2, m).astype(bool))})
    names = ["pk", "pv"] if how in ("semi", "anti") else \
        ["pk", "pv", "bk", "bv"]
    got = hash_join(probe, build, ("pk",), ("bk",), how=how, mode="unique")
    assert not bool(got.overflow)
    want = hash_join(probe, build, ("pk",), ("bk",), how=how,
                     out_capacity=4 * n, mode="expand")
    assert _rows(got, names) == _rows(want, names)


@pytest.mark.parametrize("how", HOWS)
def test_unique_matches_expand_hash_keys(how):
    """Composite (int, int) key -> hash kind with carried-key verify."""
    rng = np.random.default_rng(5)
    n, m = 200, 64
    probe = _batch({
        "pa": rng.integers(0, 12, n).astype(np.int64),
        "pb": rng.integers(0, 12, n).astype(np.int64),
        "pv": np.arange(n, dtype=np.int64)})
    pairs = rng.permutation(144)[:m]
    build = _batch({
        "ba": (pairs // 12).astype(np.int64),
        "bb": (pairs % 12).astype(np.int64),
        "bv": np.arange(m, dtype=np.int64)})
    names = ["pa", "pb", "pv"] if how in ("semi", "anti") else \
        ["pa", "pb", "pv", "ba", "bb", "bv"]
    got = hash_join(probe, build, ("pa", "pb"), ("ba", "bb"), how=how,
                    mode="unique")
    assert not bool(got.overflow)
    want = hash_join(probe, build, ("pa", "pb"), ("ba", "bb"), how=how,
                     out_capacity=4 * n, mode="expand")
    assert _rows(got, names) == _rows(want, names)


def test_duplicate_build_keys_raise_fallback_flag():
    probe = _batch({"pk": np.array([1, 2, 3], dtype=np.int64)})
    build = _batch({"bk": np.array([2, 2, 3], dtype=np.int64),
                    "bv": np.array([7, 8, 9], dtype=np.int64)})
    res = hash_join(probe, build, ("pk",), ("bk",), how="inner",
                    mode="unique")
    assert bool(res.overflow)


def test_null_keys_never_match_and_never_fallback():
    # two NULL build keys are NOT duplicate keys; NULL probe keys match
    # nothing (left join keeps them with a NULL build side)
    probe = _batch({"pk": (np.array([1, 2, 0], dtype=np.int64),
                           np.array([True, True, False]))})
    build = _batch({"bk": (np.array([1, 0, 0], dtype=np.int64),
                           np.array([True, False, False])),
                    "bv": np.array([10, 20, 30], dtype=np.int64)})
    res = hash_join(probe, build, ("pk",), ("bk",), how="left",
                    mode="unique")
    assert not bool(res.overflow)
    assert _rows(res, ["pk", "bv"]) == sorted(
        [(1, 10), (2, None), (None, None)], key=str)


def test_dead_lanes_ignored():
    probe = _batch({"pk": np.array([1, 2, 3, 4], dtype=np.int64)},
                   sel=[True, False, True, False])
    build = _batch({"bk": np.array([3, 2], dtype=np.int64),
                    "bv": np.array([30, 20], dtype=np.int64)},
                   sel=[True, False])
    res = hash_join(probe, build, ("pk",), ("bk",), how="inner",
                    mode="unique")
    assert not bool(res.overflow)
    assert _rows(res, ["pk", "bv"]) == [(3, 30)]


def test_int_key_out_of_range_flags_fallback():
    big = np.int64(1) << np.int64(62)
    probe = _batch({"pk": np.array([1, big], dtype=np.int64)})
    build = _batch({"bk": np.array([1, 5], dtype=np.int64),
                    "bv": np.array([10, 50], dtype=np.int64)})
    res = hash_join(probe, build, ("pk",), ("bk",), how="inner",
                    mode="unique")
    assert bool(res.overflow)


def test_negative_int_keys():
    probe = _batch({"pk": np.array([-5, 0, 7, -5], dtype=np.int64)})
    build = _batch({"bk": np.array([-5, 7, 9], dtype=np.int64),
                    "bv": np.array([1, 2, 3], dtype=np.int64)})
    # the u32 carry fast path only covers keys in [0, 2^30): negatives
    # raise the deferred flag and the restart ladder's next mode
    # (row-matrix unique) answers exactly
    res = hash_join(probe, build, ("pk",), ("bk",), how="inner",
                    mode="unique")
    assert bool(res.overflow)
    res2 = hash_join(probe, build, ("pk",), ("bk",), how="inner",
                     mode="unique-mat")
    assert not bool(res2.overflow)
    assert _rows(res2, ["pk", "bv"]) == sorted(
        [(-5, 1), (-5, 1), (7, 2)], key=str)


def test_float_keys_use_hash_kind():
    probe = _batch({"pk": np.array([1.5, 2.5, np.nan], dtype=np.float64)})
    build = _batch({"bk": np.array([2.5, np.nan, 9.0], dtype=np.float64),
                    "bv": np.array([25, 99, 90], dtype=np.int64)})
    res = hash_join(probe, build, ("pk",), ("bk",), how="inner",
                    mode="unique")
    assert not bool(res.overflow)
    # NaN == NaN under the engine's total order (matches expand path)
    want = hash_join(probe, build, ("pk",), ("bk",), how="inner",
                     out_capacity=16, mode="expand")
    got_rows = _rows(res, ["bv"])
    assert got_rows == _rows(want, ["bv"])


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_streaming_joinop_unique_fallback_to_expand(how):
    """A JoinOp over a duplicate-key build must transparently restart from
    the unique fast path into expand mode via the FlowRestart contract."""
    from cockroach_tpu.exec.operators import JoinOp, collect
    from tests.test_exec import _source

    probe = _source({"pk": np.array([1, 2, 2, 5], dtype=np.int64)},
                    capacity=2, nchunks=2)
    build = _source({"bk": np.array([2, 2, 3], dtype=np.int64),
                     "bv": np.array([20, 21, 30], dtype=np.int64)},
                    capacity=3)
    j = JoinOp(probe, build, ["pk"], ["bk"], how=how)
    assert j.build_mode == "unique"
    got = collect(j)
    n = len(got["pk"])
    rows = sorted(
        (int(got["pk"][i]),
         (int(got["bv"][i]) if got["bv__valid"][i] else None)
         if "bv" in got else 0)
        for i in range(n))
    assert j.build_mode == "expand"  # the restart downgraded the mode
    if how == "inner":
        assert rows == sorted([(2, 20), (2, 21), (2, 20), (2, 21)])
    elif how == "left":
        assert rows == sorted([(1, None), (2, 20), (2, 21), (2, 20),
                               (2, 21), (5, None)], key=str)
    elif how == "semi":
        assert [r[0] for r in rows] == [2, 2]
    elif how == "anti":
        assert [r[0] for r in rows] == [1, 5]


# -- the compacting probe (a ShrinkOp directly above the join) -------------

def _compact_cases():
    rng = np.random.default_rng(11)
    n, m = 300, 64
    keys = rng.permutation(200)[:m].astype(np.int64)
    plain_probe = {"pk": rng.integers(0, 200, n).astype(np.int64),
                   "pv": np.arange(n, dtype=np.int64),
                   "pw": np.arange(n, dtype=np.int64) * 3}
    plain_build = {"bk": keys, "bv": np.arange(m, dtype=np.int64) * 10}
    big = np.int64(1) << np.int64(62)
    return {
        # name: (probe cols, probe sel, build cols, build sel, C,
        #        fallback, overflow)
        "plain": (plain_probe, None, plain_build, None, 512, False, False),
        "dead_lanes": (plain_probe, rng.random(n) > 0.3, plain_build,
                       rng.random(m) > 0.3, 512, False, False),
        "null_keys": (
            dict(plain_probe, pk=(plain_probe["pk"], rng.random(n) > 0.2)),
            rng.random(n) > 0.1,
            {"bk": (keys, rng.random(m) > 0.2),
             "bv": (plain_build["bv"], rng.random(m) > 0.5)},
            None, 512, False, False),
        "negative_keys": (
            {"pk": np.array([-5, 0, 7, -5], dtype=np.int64),
             "pv": np.arange(4, dtype=np.int64)}, None,
            {"bk": np.array([-5, 7, 9], dtype=np.int64),
             "bv": np.array([1, 2, 3], dtype=np.int64)}, None,
            8, True, False),
        "out_of_range_keys": (
            {"pk": np.array([1, big], dtype=np.int64),
             "pv": np.arange(2, dtype=np.int64)}, None,
            {"bk": np.array([1, 5], dtype=np.int64),
             "bv": np.array([10, 50], dtype=np.int64)}, None,
            8, True, False),
        "duplicate_build_keys": (
            {"pk": np.array([1, 2, 3], dtype=np.int64),
             "pv": np.arange(3, dtype=np.int64)}, None,
            {"bk": np.array([2, 2, 3], dtype=np.int64),
             "bv": np.array([7, 8, 9], dtype=np.int64)}, None,
            8, True, False),
        "more_matches_than_c": (plain_probe, None, plain_build, None, 16,
                                False, True),
        "c_over_all_lanes": (plain_probe, None, plain_build, None, 1024,
                             False, False),
        "zero_matches": (
            plain_probe, None,
            {"bk": keys + 1000, "bv": plain_build["bv"]}, None,
            64, False, False),
        "payload_over_31_bits": (
            plain_probe, None,
            {"bk": keys,
             "bv": (rng.integers(0, 1 << 40, m).astype(np.int64),
                    rng.random(m) > 0.2),
             "bw": rng.integers(-(1 << 15), 1 << 15, m).astype(np.int64)},
            rng.random(m) > 0.1, 512, False, False),
        # a build whose columns no u64 holds compacts all the same: they
        # do not ride the sort (the row index does); only the resorting
        # form (_two_step's probe_unique on "unique") flags the width
        "payload_over_62_bits": (
            plain_probe, None,
            {"bk": keys,
             "bv": rng.integers(0, 1 << 40, m).astype(np.int64),
             "bw": rng.integers(0, 1 << 40, m).astype(np.int64)}, None,
            512, False, False),
        "three_wide_columns": (
            plain_probe, rng.random(n) > 0.1,
            {"bk": keys,
             "bv": rng.integers(-(1 << 50), 1 << 50, m).astype(np.int64),
             "bw": (rng.integers(0, 1 << 45, m).astype(np.int64),
                    rng.random(m) > 0.3),
             "bx": rng.integers(0, 1 << 61, m).astype(np.int64)},
            rng.random(m) > 0.1, 512, False, False),
        "wide_columns_and_a_float32": (
            plain_probe, None,
            {"bk": (keys, rng.random(m) > 0.1),
             "bv": rng.integers(0, 1 << 40, m).astype(np.int64),
             "bw": rng.integers(0, 1 << 40, m).astype(np.int64),
             "bf": (rng.integers(-999, 999, m).astype(np.float32),
                    rng.random(m) > 0.2),
             "bb": rng.random(m) > 0.5}, None,
            512, False, False),
        "wide_columns_more_matches_than_c": (
            plain_probe, None,
            {"bk": keys,
             "bv": rng.integers(0, 1 << 40, m).astype(np.int64),
             "bw": rng.integers(0, 1 << 40, m).astype(np.int64)}, None,
            16, False, True),
        "wide_columns_duplicate_build_keys": (
            {"pk": np.array([1, 2, 3], dtype=np.int64),
             "pv": np.arange(3, dtype=np.int64)}, None,
            {"bk": np.array([2, 2, 3], dtype=np.int64),
             "bv": np.array([7, 8, 9], dtype=np.int64) << 40,
             "bw": np.array([7, 8, 9], dtype=np.int64) << 41}, None,
            8, True, False),
    }


_COMPACT_CASES = _compact_cases()


def _two_step(probe, build, how, capacity):
    """probe_unique, then the ShrinkOp's own compaction. A build the
    carry join's one u64 cannot hold takes the restart ladder's next
    rung, the row-matrix join, as JoinOp.widen would after the flag."""
    from cockroach_tpu.exec.operators import ShrinkOp
    from tests.test_exec import _source

    res = hash_join(probe, build, ("pk",), ("bk",), how=how, mode="unique")
    wide = [c for c in build.columns if c != "bk"]
    if wide and int(bitpack.plan_pack(build, wide).total_bits) > 62:
        assert bool(res.overflow)
        res = hash_join(probe, build, ("pk",), ("bk",), how=how,
                        mode="unique-mat")
    shrink = ShrinkOp(_source({"x": np.zeros(1, np.int64)}, capacity=1),
                      capacity)
    out, overflow = shrink.shrink_traceable(res.batch)
    return out, bool(res.overflow), bool(overflow)


@pytest.mark.parametrize("how", ["inner", "semi"])
@pytest.mark.parametrize("case", sorted(_COMPACT_CASES))
def test_compacting_probe_matches_probe_then_shrink(case, how):
    """probe_unique_compact == probe_unique + ShrinkOp.shrink_traceable:
    the same row multiset, the same fallback and overflow flags."""
    from cockroach_tpu.ops.join import prepare_build
    from cockroach_tpu.ops.sortjoin import compacts, probe_unique_compact

    pcols, psel, bcols, bsel, C, fallback, overflow = _COMPACT_CASES[case]
    probe, build = _batch(pcols, psel), _batch(bcols, bsel)
    ub = prepare_build(build, ("bk",), mode="unique")
    assert compacts(ub, probe.capacity, how)
    got = probe_unique_compact(probe, ub, ("pk",), how, C)
    want, want_fallback, want_overflow = _two_step(probe, build, how, C)
    assert (bool(got.fallback), bool(got.overflow)) == (fallback, overflow)
    assert (want_fallback, want_overflow) == (fallback, overflow)
    assert got.batch.capacity == C == want.capacity
    assert sorted(got.batch.columns) == sorted(want.columns)
    if fallback:
        return  # the restart ladder reruns the join in its next mode
    assert int(got.batch.length) == int(want.length)
    names = sorted(want.columns)
    if not overflow:
        assert _batch_rows(got.batch, names) == _batch_rows(want, names)
        return
    # first restart: ShrinkOp.widen() grows the capacity 16x
    from cockroach_tpu.exec.operators import ShrinkOp
    wide = C * ShrinkOp.GROWTH
    got = probe_unique_compact(probe, ub, ("pk",), how, wide)
    want, _f, want_overflow = _two_step(probe, build, how, wide)
    assert not bool(got.overflow) and not want_overflow
    assert _batch_rows(got.batch, names) == _batch_rows(want, names)


@pytest.mark.parametrize("case", sorted(
    c for c, v in _COMPACT_CASES.items() if not v[5]))
def test_compacting_inner_join_leaves_its_lanes_in_key_order(case):
    """The guarantee an aggregate above relies on (ISSUE 36): an INNER
    join's compacted result has its live rows first, ascending in the
    join key (equal keys adjacent), the build's key equal to the probe's
    on every live lane; a cut at C lanes (overflow) is a prefix of that
    order. A semi join's is in probe-lane order."""
    from cockroach_tpu.ops.join import prepare_build
    from cockroach_tpu.ops.sortjoin import probe_unique_compact

    pcols, psel, bcols, bsel, C, _fallback, _overflow = _COMPACT_CASES[case]
    probe, build = _batch(pcols, psel), _batch(bcols, bsel)
    ub = prepare_build(build, ("bk",), mode="unique")
    got = probe_unique_compact(probe, ub, ("pk",), "inner", C).batch
    n = int(got.length)
    sel = np.asarray(got.sel)
    assert sel[:n].all() and not sel[n:].any()
    pk = np.asarray(got.col("pk").values)[:n]
    assert (np.diff(pk) >= 0).all()
    assert (np.asarray(got.col("bk").values)[:n] == pk).all()
    semi = probe_unique_compact(probe, ub, ("pk",), "semi", C).batch
    pv = np.asarray(semi.col("pv").values)[:int(semi.length)]
    assert (np.diff(pv) > 0).all()      # pv is the probe's lane index


@pytest.mark.parametrize("how", ["left", "anti", "right", "outer"])
def test_compacting_probe_refuses_other_join_types(how):
    from cockroach_tpu.ops.join import prepare_build
    from cockroach_tpu.ops.sortjoin import probe_unique_compact

    pcols, _ps, bcols, _bs, C, _f, _o = _COMPACT_CASES["plain"]
    ub = prepare_build(_batch(bcols), ("bk",), mode="unique")
    with pytest.raises(ValueError):
        probe_unique_compact(_batch(pcols), ub, ("pk",), how, C)


@pytest.mark.parametrize("build_cols,mode,takes", [
    ("plain", "unique", True),
    ("payload_over_62_bits", "unique", True),
    # the row-matrix rung sorts u64 keys and has no narrow packing
    ("plain", "unique-mat", False),
    # a hash-kind key (two columns, or a float) needs its verification
    # gather at every probe lane: the row-matrix form
    ("two_key_columns", "unique", False),
    ("float_key", "unique", False),
])
def test_compacting_probe_needs_a_carry_build(build_cols, mode, takes):
    """`compacts` asks for an int-kind key in the narrow u32 packing and
    nothing of the build's other columns; where it is false the tracer
    takes two steps and probe_unique_compact refuses."""
    from cockroach_tpu.ops.join import prepare_build
    from cockroach_tpu.ops.sortjoin import (
        carries, compacts, probe_unique_compact,
    )

    pcols, _ps, plain, _bs, C, _f, _o = _COMPACT_CASES["plain"]
    probe, on, probe_on = _batch(pcols), ("bk",), ("pk",)
    if build_cols == "two_key_columns":
        bcols, on, probe_on = (dict(plain, bk2=plain["bk"]), ("bk", "bk2"),
                               ("pk", "pv"))
    elif build_cols == "float_key":
        bcols = dict(plain, bk=plain["bk"].astype(np.float64))
    else:
        bcols = _COMPACT_CASES[build_cols][2]
    ub = prepare_build(_batch(bcols), on, mode=mode)
    assert compacts(ub, probe.capacity, "inner") == takes
    assert compacts(ub, probe.capacity, "semi") == takes
    if takes:
        # the resorting form still wants the packed payload
        assert carries(ub, probe.capacity, "inner")
        return
    with pytest.raises(ValueError):
        probe_unique_compact(probe, ub, probe_on, "inner", C)
