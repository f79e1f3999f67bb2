"""No sort of the served programs pays for a stability its key already
gives (ISSUE 43).

Two halves. (1) Every rewritten sort site against the STABLE form it
replaces, kept here as the plain reference (`_until_pr43`: inside it every
`lax.sort` of the operators is `num_keys=1, is_stable=True` again, and
`first_selected` is `argsort(~sel, stable=True)` again): the same arrays,
lane for lane, on adversarial inputs. The router's site has its plain
reference in tests/test_parallel.py (`_numpy_router`, cases
`one_destination_overflows`, `empty_range`, ...). (2) The jaxpr of every
cell's program at test scale holds no stable sort at 1,024 lanes or more
outside the ORDER BY / top-K lowering.
"""

from contextlib import contextmanager
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cockroach_tpu  # noqa: F401  (x64 config)
from cockroach_tpu.coldata import batch as batch_mod
from cockroach_tpu.coldata.batch import Batch, Column, first_selected
from cockroach_tpu.exec import operators as operators_mod
from cockroach_tpu.ops import agg as agg_mod
from cockroach_tpu.ops import sortjoin
from cockroach_tpu.ops.agg import AggSpec, hash_aggregate, int_key_aggregate


def _argsort_selected(sel, C):
    """first_selected as it was spelled until PR 43."""
    order = jnp.argsort(~sel, stable=True).astype(jnp.int32)
    n = sel.shape[0]
    return order[:C] if n >= C else jnp.concatenate(
        [order, jnp.zeros((C - n,), jnp.int32)])


@contextmanager
def _until_pr43():
    """The stable forms: every `lax.sort` the operators issue keys on its
    first operand alone and is stable (JAX's defaults, which every site
    took), and the compactions are the `(pred, i32)` argsort."""
    real = jax.lax.sort

    def stable(operand, dimension=-1, is_stable=True, num_keys=1):
        return real(operand, dimension=dimension, is_stable=True, num_keys=1)

    with mock.patch.object(jax.lax, "sort", stable), \
            mock.patch.object(batch_mod, "first_selected",
                              _argsort_selected), \
            mock.patch.object(agg_mod, "first_selected",
                              _argsort_selected), \
            mock.patch.object(operators_mod, "first_selected",
                              _argsort_selected):
        yield


def _same(got, want):
    """Two pytrees of arrays: the same leaves, bit for bit."""
    gl, gt = jax.tree_util.tree_flatten(got)
    wl, wt = jax.tree_util.tree_flatten(want)
    assert gt == wt
    for g, w in zip(gl, wl):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g, w)


def _batch(cols, sel=None):
    out = {n: (Column(jnp.asarray(v[0]), jnp.asarray(v[1]))
               if isinstance(v, tuple) else Column(jnp.asarray(v)))
           for n, v in cols.items()}
    b = Batch.from_columns(out)
    return b if sel is None else b.with_sel(jnp.asarray(sel))


# ------------------------------------------------------- the join's sides ---

def _join_sides(case):
    """(probe, build): 640 probe lanes whose keys come in runs of 1..64
    equal keys, scattered over the lanes, against a 96-lane build."""
    rng = np.random.default_rng(43)
    runs = np.concatenate([np.full(r, k) for k, r in enumerate(
        [1, 2, 3, 5, 8, 13, 21, 34, 55, 64, 64, 64, 64, 64, 64, 64, 50])])
    pk = rng.permutation(runs).astype(np.int64)
    n, m = len(pk), 96
    bk = rng.permutation(m).astype(np.int64)       # keys 0..95: 17 match
    psel = bsel = None
    pvalid = np.ones(n, bool)
    bvalid = np.ones(m, bool)
    if case in ("dead_and_null", "duplicate_build"):
        psel = rng.random(n) > 0.2
        bsel = rng.random(m) > 0.2
        pvalid = rng.random(n) > 0.1
        bvalid = rng.random(m) > 0.1
    if case == "duplicate_build":
        bk[:8] = bk[8:16]
    probe = _batch({"pk": (pk, pvalid),
                    "pv": np.arange(n, dtype=np.int64) * 3,
                    "pf": rng.random(n).astype(np.float32)}, psel)
    build = _batch({"bk": (bk, bvalid),
                    "bv": (rng.integers(0, 1 << 20, m).astype(np.int64),
                           rng.random(m) > 0.3),
                    "bw": rng.integers(0, 100, m).astype(np.int32)}, bsel)
    return probe, build


CASES = ["runs_of_1_to_64", "dead_and_null", "duplicate_build"]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("how,capacity", [
    ("inner", 1024), ("semi", 1024), ("inner", 64), ("semi", 64)])
def test_compacting_join_is_the_stable_forms_lane_for_lane(case, how,
                                                          capacity):
    """_carry_sort(rows=True), unstable. The inner join's lane order is
    a guarantee (key order, equal keys in probe-lane order: what the
    stable key sort gave), so it sorts (packed, row-or-lane) as TWO
    keys; the semi join's compaction carries the lane index, so one key
    will do. A capacity under the matches overflows on both."""
    probe, build = _join_sides(case)
    ub = sortjoin.prepare_unique(build, ("bk",))
    assert sortjoin.compacts(ub, probe.capacity, how)
    got = sortjoin.probe_unique_compact(probe, ub, ("pk",), how, capacity)
    with _until_pr43():
        want = sortjoin.probe_unique_compact(probe, ub, ("pk",), how,
                                             capacity)
    assert bool(got.fallback) == bool(want.fallback) == (
        case == "duplicate_build")
    assert bool(got.overflow) == bool(want.overflow) == (capacity == 64)
    _same(got, want)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_resorting_carry_join_is_the_stable_forms_lane_for_lane(case, how):
    """_carry_sort(rows=False) and the resort by destination, both
    unstable with one key: ties are probe lanes of one key, each with its
    own destination. Duplicate build keys raise `fallback` on both (the
    result is discarded; the stable form's broadcast mixed the duplicate
    rows' payload halves too)."""
    probe, build = _join_sides(case)
    ub = sortjoin.prepare_unique(build, ("bk",))
    assert sortjoin.carries(ub, probe.capacity, how)
    got = sortjoin.probe_unique(probe, ub, ("pk",), how)
    with _until_pr43():
        want = sortjoin.probe_unique(probe, ub, ("pk",), how)
    assert bool(got.overflow) == bool(want.overflow) == (
        case == "duplicate_build")
    _same(got, want)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti", "outer"])
def test_hashed_key_join_is_the_stable_forms_lane_for_lane(case, how):
    """probe_unique's row-matrix form under the hashed u64 key (two
    columns): (packed, position) unstable on the key alone (a probe lane
    carries its own position), and the resort by a permutation."""
    probe, build = _join_sides(case)
    probe = probe.with_column("p2", Column(probe.col("pv").values % 2))
    build = build.with_column("b2", Column(build.col("bw").values
                                           .astype(jnp.int64) % 2))
    ub = sortjoin.prepare_unique(build, ("bk", "b2"))
    assert ub.key_kind == "hash"
    got = sortjoin.probe_unique(probe, ub, ("pk", "p2"), how)
    with _until_pr43():
        want = sortjoin.probe_unique(probe, ub, ("pk", "p2"), how)
    assert bool(got.overflow) == bool(want.overflow)
    _same(got, want)


# ----------------------------------------------------------- aggregates ---

def _agg_input(case):
    probe, _build = _join_sides("runs_of_1_to_64" if case == "all_live"
                                else "dead_and_null")
    return probe


AGGS = [AggSpec("sum", "pv", "s"), AggSpec("count", "pv", "c"),
        AggSpec("count_star", None, "n")]


@pytest.mark.parametrize("case", ["all_live", "dead_and_null"])
@pytest.mark.parametrize("out_capacity", [0, 8, 16, 32, 1024])
def test_int_key_aggregate_is_the_stable_forms_lane_for_lane(case,
                                                             out_capacity):
    """(key, packed inputs) unstable: integer sums and counts read at run
    ends never see the order of a group's lanes. The compaction of the
    run ends is first_selected, where it was a (u32, i32) stable sort;
    17 groups (18 with the NULL key's): capacities under, at and over
    them, over the lanes too, and the uncompacted view."""
    b = _agg_input(case)
    got = int_key_aggregate(b, "pk", AGGS, out_capacity=out_capacity)
    with _until_pr43():
        want = int_key_aggregate(b, "pk", AGGS, out_capacity=out_capacity)
    assert bool(got.overflow) == bool(want.overflow) == (
        out_capacity in (8, 16))
    _same(got, want)


@pytest.mark.parametrize("case", ["all_live", "dead_and_null"])
@pytest.mark.parametrize("keys", [("pk",), ("pk", "p2")])
def test_hash_aggregate_is_the_stable_forms_lane_for_lane(case, keys):
    """(hash, position) as two keys, unstable: the order of equal hashes
    is their lane order, as the stable sort's."""
    b = _agg_input(case)
    b = b.with_column("p2", Column(b.col("pv").values % 3))
    aggs = AGGS + [AggSpec("min", "pf", "lo"), AggSpec("sum", "pf", "fs")]
    got = hash_aggregate(b, keys, aggs, method="hash")
    with _until_pr43():
        want = hash_aggregate(b, keys, aggs, method="hash")
    _same(got, want)


# --------------------------------------------------- Shrink and compact ---

def _selection(case, n=640):
    rng = np.random.default_rng(7)
    return {"all": np.ones(n, bool), "none": np.zeros(n, bool),
            "one": np.arange(n) == 417,
            "last_lane": np.arange(n) == n - 1,
            "some": rng.random(n) > 0.7}[case]


SELECTIONS = ["all", "none", "one", "last_lane", "some"]


@pytest.mark.parametrize("case", SELECTIONS)
@pytest.mark.parametrize("C", [1, 64, 640, 4096])
def test_first_selected_is_the_stable_argsort(case, C):
    sel = jnp.asarray(_selection(case))
    got = first_selected(sel, C)
    want = _argsort_selected(sel, C)
    assert got.dtype == want.dtype == jnp.int32
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("case", SELECTIONS)
@pytest.mark.parametrize("C", [64, 640, 4096])
def test_shrink_is_the_stable_forms_lane_for_lane(case, C):
    """ShrinkOp.shrink_traceable: capacity under the selected rows
    (overflow), at the lanes, and over them (cap < C)."""
    probe, _build = _join_sides("runs_of_1_to_64")
    m = probe.with_sel(jnp.asarray(_selection(case)))
    op = operators_mod.ShrinkOp.__new__(operators_mod.ShrinkOp)
    op.capacity = C
    got = op.shrink_traceable(m)
    with _until_pr43():
        want = op.shrink_traceable(m)
    assert bool(got[1]) == bool(want[1]) == (int(m.length) > C)
    assert got[0].capacity == C
    _same(got, want)


@pytest.mark.parametrize("case", SELECTIONS)
def test_compact_is_the_stable_forms_lane_for_lane(case):
    probe, _build = _join_sides("dead_and_null")
    m = probe.with_sel(jnp.asarray(_selection(case)))
    got = m.compact()
    with _until_pr43():
        want = m.compact()
    _same(got, want)
    assert np.array_equal(np.asarray(got.sel),
                          np.arange(m.capacity) < int(m.length))


# ------------------------------------ no stable sort in a cell's program ---

FLOOR = 1024
ORDER_BY = {"SortOp", "TopKOp"}


def _stable_sorts(jaxpr):
    """Every sort of `jaxpr` issued with is_stable=True at FLOOR lanes or
    more outside the ORDER BY / top-K lowering (multi-key order is their
    meaning): (lanes, operand dtypes, innermost operator scope)."""
    import re

    from tests.test_fused import _scoped_eqns

    found = []
    for stack, eqn in _scoped_eqns(jaxpr):
        if eqn.primitive.name != "sort" or not eqn.params["is_stable"]:
            continue
        lanes = eqn.invars[0].aval.shape[0]
        owner = re.findall(r"crdb\.op\d+\.(\w+)", stack)[-1:]
        if lanes >= FLOOR and not (owner and owner[0] in ORDER_BY):
            found.append((lanes, [str(v.aval.dtype) for v in eqn.invars],
                          owner))
    return found


@pytest.fixture
def traced_programs(monkeypatch):
    """The jaxpr of every whole-query program exec/fused or
    parallel/dist_flow lowers during the test (one trace: the lowering
    is the trace's own)."""
    from cockroach_tpu.exec import fused
    from cockroach_tpu.parallel import dist_flow

    jaxprs = []

    def recording(fn, args):
        traced = jax.jit(fn).trace(*args)
        jaxprs.append(traced.jaxpr)
        return traced.lower()

    monkeypatch.setattr(fused, "lower_program", recording)
    monkeypatch.setattr(dist_flow, "lower_program", recording)
    return jaxprs


def _q18_program():
    from benchmark.loaders import tpch_cname
    from tests import test_q18 as T

    loaded = T._serve(tpch_cname.TPCHCName(sf=0.01, seed=7))
    try:
        sess = T._session(loaded)
        sess._prepared = type(sess._prepared)()
        bound, text = sess.bind_params(T.Q18, ("312",))
        sess.execute(text, params=bound)
    finally:
        loaded["pg"].close()


def _q9_program():
    from benchmark.loaders import tpch_pname
    from tests import test_q9 as T

    loaded = T._serve(tpch_pname.TPCHPName(sf=0.01, seed=7))
    try:
        T._run(T._session(loaded, own_cache=True), T.Q9, ("%green%",))
    finally:
        loaded["pg"].close()


def _q9_mesh_program():
    from benchmark.loaders import tpch_pname
    from cockroach_tpu.parallel import dist_flow, make_mesh
    from cockroach_tpu.storage.mvcc import MVCCStore
    from cockroach_tpu.util.settings import Settings
    from tests import test_session_distsql as T

    if len(jax.devices()) < T.N_DEV:
        pytest.skip("needs four virtual CPU devices")
    loaded = tpch_pname.load(MVCCStore(), {"sf": 0.01},
                             T.Q9_STMT["tables"], T.SEED)
    s = Settings()
    old = s.get(dist_flow.BROADCAST_LIMIT)
    s.set(dist_flow.BROADCAST_LIMIT, 2 * T.Q9_CAP)
    cat = loaded["catalog"].with_mesh(make_mesh(T.N_DEV))
    progs = dict(dist_flow._PROGS)
    dist_flow._PROGS.clear()    # another test's entry would skip the trace
    try:
        sess = T._session9(cat, "set distsql = always")
        _payload, root = T._bound(sess, T.Q9, ("%green%",))
        assert root.children[0].tags["tier"] == "dist"
    finally:
        dist_flow._PROGS.update(progs)
        cat.with_mesh(None)
        s.set(dist_flow.BROADCAST_LIMIT, old)


@pytest.mark.parametrize("program", [
    "q3_compact", "q3_two_step", "q3_mesh", "q3_mesh_lanes", "q18", "q9",
    "q9_mesh"])
def test_no_cell_program_sorts_stably(program, monkeypatch,
                                      traced_programs):
    """The programs the six sort cells run, as the suite builds them at
    SF 0.01: Q3's one-chip program (`compact`; `two_step` is the ladder's
    other lowering, with the standing Shrinks), Q3 on four shards with
    and without the planner's estimates, Q18's, Q9's on one chip and on
    four shards. None issues a stable sort at 1,024 lanes or more but the
    ORDER BY / top-K lowering: XLA's tie-break operand is paid for
    nowhere (ISSUE 43)."""
    if program.startswith("q3_"):
        from tests.test_fused import _q3_program

        jaxprs = [_q3_program(program[3:], monkeypatch)[0].jaxpr]
    else:
        {"q18": _q18_program, "q9": _q9_program,
         "q9_mesh": _q9_mesh_program}[program]()
        jaxprs = [j.jaxpr for j in traced_programs]
    from tests.test_fused import _sorts

    assert jaxprs
    # the programs do sort at these lanes: the test reads the right thing
    assert any(s[0] >= FLOOR for j in jaxprs for s in _sorts(j))
    assert [s for j in jaxprs for s in _stable_sorts(j)] == []
