"""The compacting join's run heads (ISSUE 49): one 32-bit scan tells every
sorted lane where its run starts and whether a build lane stands there
(ops/sortjoin._carry_sort(rows=True), ops/prefix.blocked_cummax), and no
scan over a 64-bit operand is left under a Shrink.

(a) probe_unique_compact against probe_unique + the ShrinkOp's own
    compaction over shapes that stress the scan's two levels (rows of 512
    lanes): rows, the inner result's lane order and both flags;
(b) no cumulative operation of its jaxpr has a 64-bit operand, those of
    the resorting and the row-matrix joins (not ISSUE 49's) still do;
(c) the tracer's reckoning of the lanes that still pass a 64-bit scan
    (stage fused.join_scan64_lanes): none for a compacting join, two a
    lane for a resorting carry join, one for a join on the hashed key.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cockroach_tpu  # noqa: F401  (x64 config)
from cockroach_tpu.exec import fused, stats
from cockroach_tpu.exec.operators import (
    JoinOp, ScanOp, ShrinkOp, walk_operators,
)
from cockroach_tpu.ops import prefix, sortjoin
from cockroach_tpu.ops.join import prepare_build
from tests.test_fused import _eqns, _int_scan, collect
from tests.test_sortjoin import _batch, _batch_rows, _two_step

BLOCK = prefix._BLOCK


def _lanes(*runs):
    """Keys of consecutive lanes: (key, count) pairs."""
    return np.concatenate([np.full(c, k, np.int64) for k, c in runs])


def _head_cases():
    rng = np.random.default_rng(49)
    cases = {}

    def case(name, pk, bk, C, psel=None, bsel=None, pvalid=None,
             bvalid=None, fallback=False, overflow=False, shuffle=True):
        pk, bk = np.asarray(pk, np.int64), np.asarray(bk, np.int64)
        if shuffle:   # the sort, not the input, makes the runs
            pk = rng.permutation(pk)
        probe = {"pk": pk if pvalid is None else (pk, pvalid),
                 "pv": np.arange(len(pk), dtype=np.int64)}
        build = {"bk": bk if bvalid is None else (bk, bvalid),
                 "bv": bk * 10 + 1}
        cases[name] = (probe, psel, build, bsel, C, fallback, overflow)

    # 1,251 lanes: two full rows of the scan and a part of a third. A
    # probe-only run first, a run with a build that crosses both row
    # edges, a probe-only run between two runs with a build, a build
    # lane no probe asks for (10), a probe-only run last
    case("run_across_two_block_edges",
         _lanes((3, 50), (7, 1100), (8, 30), (9, 40), (11, 25)),
         [5, 7, 9, 10, 12, 2], 2048)
    # sorted position of key 1000's build lane: `pre` single-lane
    # probe-only runs stand before it, so it is a row's last lane (511),
    # a row's first (512) or the lane after
    for pre in (BLOCK - 1, BLOCK, BLOCK + 1):
        case(f"build_lane_at_position_{pre}",
             np.concatenate([np.arange(pre), _lanes((1000, 3)),
                             2000 + np.arange(90)]),
             [1000, 1500], 512)
    # a probe-only run ends a row and the next row starts with a probe
    # lane of a run whose build lane ended the row before it
    case("probe_lanes_carry_the_head_over_an_edge",
         np.concatenate([np.arange(BLOCK - 2), _lanes((700, 2 * BLOCK))]),
         [700], 2048)
    n, m = 1900, 333
    keys = rng.permutation(5000)[:m]
    pk = rng.integers(0, 5000, n)
    case("dead_and_null_lanes_on_both_sides", pk, keys, 2048,
         psel=rng.random(n) > 0.25, bsel=rng.random(m) > 0.25,
         pvalid=rng.random(n) > 0.2, bvalid=rng.random(m) > 0.2)
    # a dead build lane's key leaves its probe lanes a probe-only run
    case("every_build_lane_dead", pk, keys, 512,
         bsel=np.zeros(m, bool))
    case("every_probe_matches", keys[rng.integers(0, m, 600)],
         keys[:77], 1024)
    case("no_probe_matches", pk, keys + 10000, 512)
    case("more_matches_than_c", keys[rng.integers(0, m, 1500)], keys,
         BLOCK, overflow=True)
    case("duplicate_build_key", pk,
         np.concatenate([keys, keys[40:41]]), 2048, fallback=True)
    # a multiple of the row, and fewer lanes than one row (the flat scan)
    case("whole_rows_exactly", keys[rng.integers(0, m, 2 * BLOCK - 100)],
         keys[:100], 2048)
    case("one_row", pk[:200], keys[:56], 256)
    return cases


_HEAD_CASES = _head_cases()


def test_the_cases_stress_both_levels_of_the_scan():
    sizes = {name: len(np.asarray(c[0]["pv"])) + len(np.asarray(
        c[2]["bv"])) for name, c in _HEAD_CASES.items()}
    assert sizes["run_across_two_block_edges"] == 1251 > 2 * BLOCK
    assert sizes["run_across_two_block_edges"] % BLOCK
    assert sizes["whole_rows_exactly"] == 2 * BLOCK
    assert sizes["one_row"] < BLOCK


@pytest.mark.parametrize("how", ["inner", "semi"])
@pytest.mark.parametrize("case", sorted(_HEAD_CASES))
def test_head_scan_matches_probe_then_shrink(case, how):
    """Rows, lane order and flags of probe_unique_compact are those of
    probe_unique followed by the Shrink: a semi join's lanes in the
    probe's order, an inner join's ascending in the key and, within a
    key, in the probe's order (what the parent's 64-bit broadcast gave,
    lane for lane)."""
    pcols, psel, bcols, bsel, C, fallback, overflow = _HEAD_CASES[case]
    probe, build = _batch(pcols, psel), _batch(bcols, bsel)
    ub = prepare_build(build, ("bk",), mode="unique")
    assert sortjoin.compacts(ub, probe.capacity, how)
    got = sortjoin.probe_unique_compact(probe, ub, ("pk",), how, C)
    want, want_fallback, want_overflow = _two_step(probe, build, how, C)
    assert (bool(got.fallback), bool(got.overflow)) == (fallback, overflow)
    assert (want_fallback, want_overflow) == (fallback, overflow)
    if fallback:
        return  # the answer is discarded; the ladder reruns the join
    assert int(got.batch.length) == int(want.length)
    if overflow:
        # the Shrink's restart: 16x the capacity holds every match
        C *= ShrinkOp.GROWTH
        got = sortjoin.probe_unique_compact(probe, ub, ("pk",), how, C)
        want, _f, want_overflow = _two_step(probe, build, how, C)
        assert not bool(got.overflow) and not want_overflow
    names = sorted(want.columns)
    assert sorted(got.batch.columns) == names
    rows = _batch_rows(want, names)
    assert _batch_rows(got.batch, names) == rows
    # the lane order, live rows first
    n = int(got.batch.length)
    sel = np.asarray(got.batch.sel)
    assert sel[:n].all() and not sel[n:].any()
    pv = np.asarray(got.batch.col("pv").values)[:n].tolist()
    want_pv = np.asarray(want.col("pv").values)[np.asarray(want.sel)]
    if how == "semi":
        assert pv == want_pv.tolist()
        return
    pk = np.asarray(got.batch.col("pk").values)[:n].tolist()
    want_pk = np.asarray(want.col("pk").values)[np.asarray(want.sel)]
    order = np.lexsort((want_pv, want_pk))
    assert (pk, pv) == (want_pk[order].tolist(), want_pv[order].tolist())
    assert np.asarray(got.batch.col("bk").values)[:n].tolist() == pk


# -- (b) the operands of the scans ------------------------------------------

_SCANS = ("cumsum", "cummax", "cummin", "cumprod", "cumlogsumexp",
          "reduce_window", "reduce_window_sum", "reduce_window_max",
          "reduce_window_min")


def _scans64(jaxpr):
    """(primitive, lanes) of every cumulative operation of `jaxpr` that
    has an operand of 64 bits."""
    return [(eqn.primitive.name, int(np.prod(v.aval.shape)))
            for eqn in _eqns(jaxpr) if eqn.primitive.name in _SCANS
            for v in eqn.invars[:1] if v.aval.dtype.itemsize == 8]


def _join_jaxpr(fn, lcap, rcap, build_cols=("bv",)):
    """jaxpr of fn(probe, build) over int32 columns of lcap and rcap
    lanes (Q3's join at SF1: 8,388,608 + 262,144; nothing runs)."""
    def prog(pk, pv, psel, bk, bsel, *bcols):
        probe = _batch({"pk": pk, "pv": pv}).with_sel(psel)
        build = _batch(dict({"bk": bk}, **dict(zip(build_cols, bcols)))
                       ).with_sel(bsel)
        out = fn(probe, build)
        return jax.tree_util.tree_leaves(out)

    def sds(n, dt=jnp.int32):
        return jax.ShapeDtypeStruct((n,), dt)

    return jax.make_jaxpr(prog)(
        sds(lcap), sds(lcap), sds(lcap, jnp.bool_), sds(rcap),
        sds(rcap, jnp.bool_), *[sds(rcap) for _ in build_cols]).jaxpr


Q3_LANES = (8388608, 262144)


@pytest.mark.parametrize("how", ["inner", "semi"])
def test_no_scan_of_the_compacting_join_has_a_64_bit_operand(how):
    def compact(probe, build):
        ub = sortjoin.prepare_unique(build, ("bk",))
        r = sortjoin.probe_unique_compact(probe, ub, ("pk",), how, 262144)
        return r.batch, r.fallback, r.overflow

    jaxpr = _join_jaxpr(compact, *Q3_LANES)
    assert _scans64(jaxpr) == []
    # ... and the one scan there is: s32, in rows of 512 lanes
    n = sum(Q3_LANES)
    assert n % BLOCK == 0
    heads = [eqn for eqn in _eqns(jaxpr) if eqn.primitive.name == "cummax"
             and int(np.prod(eqn.invars[0].aval.shape)) == n]
    assert [str(e.invars[0].aval.dtype) for e in heads] == ["int32"]
    assert heads[0].invars[0].aval.shape == (n // BLOCK, BLOCK)


@pytest.mark.parametrize("form,scans", [
    # the resorting carry join broadcasts its 62-bit payload in two halves
    ("carry", [("cummax", sum(Q3_LANES))] * 2),
    # the row-matrix join (here: the hashed two-column key) its build row
    ("hashed", [("cummax", sum(Q3_LANES))]),
])
def test_the_other_joins_keep_their_64_bit_scans(form, scans):
    """_probe_carry's split cummax and probe_unique's run broadcast are
    not ISSUE 49's: the payload is wanted at every probe lane there."""
    on = (("pk",), ("bk",)) if form == "carry" else (("pk", "pv"),
                                                     ("bk", "bv"))

    def join(probe, build):
        ub = sortjoin.prepare_unique(build, on[1])
        assert sortjoin.carries(ub, probe.capacity, "inner") == (
            form == "carry")
        r = sortjoin.probe_unique(probe, ub, on[0], "inner")
        return r.batch, r.overflow

    assert _scans64(_join_jaxpr(join, *Q3_LANES)) == scans


# -- (c) what the tracer reckons --------------------------------------------

def _plan(form):
    """-> (root, lanes that pass a 64-bit scan): 256 probe lanes against
    64 build lanes, one chunk each."""
    rng = np.random.default_rng(5)
    pk = rng.integers(0, 400, 256)
    bk = rng.permutation(400)[:64]
    probe = _int_scan({"fk": pk, "v": np.arange(256)}, 256)
    build = _int_scan({"k": bk, "d": bk * 7}, 64)
    n = 256 + 64
    if form == "compacting":
        join = JoinOp(probe, build, ["fk"], ["k"], how="inner")
        return ShrinkOp(join, 512), 0
    if form == "resorting":
        return JoinOp(probe, build, ["fk"], ["k"], how="inner"), 2 * n
    if form == "hashed":
        return JoinOp(probe, build, ["fk", "v"], ["k", "d"],
                      how="inner"), n
    if form == "expanding":   # its int64 match counts, a probe lane each
        return JoinOp(probe, build, ["fk"], ["k"], how="inner",
                      build_mode="expand"), 256
    assert form == "compacting_over_resorting"
    inner = JoinOp(probe, build, ["fk"], ["k"], how="inner")
    dim = _int_scan({"k2": bk[:32], "e": bk[:32] + 1}, 32)
    return ShrinkOp(JoinOp(inner, dim, ["fk"], ["k2"], how="semi"),
                    512), 2 * n


@pytest.mark.parametrize("form", ["compacting", "resorting", "hashed",
                                  "expanding",
                                  "compacting_over_resorting"])
def test_tracer_counts_the_lanes_that_still_pass_a_64_bit_scan(form):
    root, lanes = _plan(form)
    col = stats.enable()
    try:
        collect(root, fuse=True)
    finally:
        stats.disable()
    assert col.stages.get("fused.fallback_unsupported") is None
    stage = col.stages["fused.join_scan64_lanes"]
    assert stage.events == col.stages["fused.sort_lanes"].events >= 1
    assert stage.rows == lanes * stage.events
    compacts = col.stages.get("fused.join_compact")
    assert (compacts.events if compacts else 0) == (
        1 if form.startswith("compacting") else 0)
    # the program holds what the counter says: its 64-bit scans at the
    # joins' widths
    runner = fused.try_compile(root)
    scans = [id(sc) for sc in walk_operators(root)
             if isinstance(sc, ScanOp)]
    prog, box = runner._make_prog(scans)
    (_entry, args) = runner._prepare()
    jaxpr = jax.make_jaxpr(prog)(*args).jaxpr
    assert box["join_scan64_lanes"] == lanes
    assert sum(n for _p, n in _scans64(jaxpr) if n >= 256) == lanes
