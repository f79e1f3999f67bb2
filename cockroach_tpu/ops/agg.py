"""Hash and ordered aggregation kernels — segmented-scan based.

Reference: pkg/sql/colexec/hash_aggregator.go:62 (hashAggregator),
colexecagg/*_tmpl.go (per-func x per-type kernels, ~31K generated LoC).

TPU strategy (see hashtable.py for why not scatter-based tables): group
rows into contiguous runs by sorting on the key columns (`sorted_groups`),
then evaluate every aggregate as a **prefix operation over the sorted
view**, reading each run's result at its last position:

- sum/count:    cumsum, then difference at run ends;
- min/max/bool: segmented associative scan (reset at run boundaries);
- any_not_null: segmented "first live value" scan.

Beside it: `dense_aggregate` (every key has a small static domain: group
g at slot g, no sort), `run_ends_aggregate` (input already grouped: in
place) and `int_key_aggregate` (one integer key, sums and counts: the key
and the packed inputs ride ONE sort).

No scatter appears anywhere on this path; XLA lowers sorts + scans +
gathers to fast vector code. Group ids come out key-sorted, which also
makes a downstream ORDER BY on the group keys a no-op.

Precision: sums over INT/DECIMAL accumulate in int64 of the already-
scaled values; when n_rows * max_scaled_value can approach 2^63 (TPC-H
Q1's charge column crosses it around SF~50) the planner marks the sum
`wide=True` (AggSpec) and it decomposes into exact sum_hi32/sum_lo32
halves recombined host-side in arbitrary precision — the device-native
answer to the reference's datum-backed decimal fallback (col/coldataext).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from cockroach_tpu.coldata.batch import (
    Batch, Column, first_selected, mask_padding,
)
from cockroach_tpu.ops.bitpack import pack_lanes, plan_pack
from cockroach_tpu.ops.hashtable import SortedGroups, sorted_groups
from cockroach_tpu.ops.prefix import (
    blocked_assoc_scan, blocked_cummax, blocked_cumsum,
)


def _shift1(x):
    """x shifted right by one lane (lane 0 keeps its own value) — a
    concatenate+slice, NOT x[maximum(iota-1, 0)]: XLA lowers the latter
    as a full random gather (~140 ms per 6M-lane column on v5e, profiled
    r4) while the concat is effectively free."""
    return jnp.concatenate([x[:1], x[:-1]])

SUPPORTED = ("sum", "count", "count_star", "min", "max", "avg",
             "bool_and", "bool_or", "any_not_null",
             # two-lane wide-sum halves: planner-decomposed exact int128
             # accumulation for sums that can exceed int64 (SF100 Q1
             # charge; the reference answers with datum-backed decimals,
             # col/coldataext — here the split stays on-device and the
             # halves recombine host-side in arbitrary precision)
             "sum_hi32", "sum_lo32")


@dataclass(frozen=True)
class AggSpec:
    """One aggregate: func over input column `col`, output named `out`.

    `wide=True` (sum only) requests exact accumulation beyond int64: the
    flow layer decomposes it into sum_hi32/sum_lo32 halves whose host
    recombination `hi * 2**32 + lo` is exact for any row count < 2^31.
    """

    func: str
    col: Optional[str]  # None for count_star
    out: str
    wide: bool = False

    def __post_init__(self):
        if self.func not in SUPPORTED:
            raise ValueError(f"unsupported aggregate {self.func}")
        if self.col is None and self.func != "count_star":
            raise ValueError(f"{self.func} needs an input column")
        if self.wide and self.func != "sum":
            raise ValueError("wide accumulation applies to sum only")


def _identity(func: str, dtype):
    if func in ("min", "bool_and"):
        if dtype == jnp.bool_:
            return jnp.array(True)
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.array(jnp.inf, dtype)
        return jnp.array(jnp.iinfo(dtype).max, dtype)
    if func in ("max", "bool_or"):
        if dtype == jnp.bool_:
            return jnp.array(False)
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.array(-jnp.inf, dtype)
        return jnp.array(jnp.iinfo(dtype).min, dtype)
    raise AssertionError(func)


def _seg_scan(op, vals, boundary):
    """Segmented inclusive scan: combine resets at run boundaries.
    combine((a,f1),(b,f2)) = (f2 ? b : op(a,b), f1|f2) — associative."""

    def combine(x, y):
        a, f1 = x
        b, f2 = y
        return jnp.where(f2, b, op(a, b)), f1 | f2

    out, _ = blocked_assoc_scan(combine, (vals, boundary))
    return out


def _seg_first_live(vals, live, boundary):
    """Per run: first value where live is True (value, found)."""

    def combine(x, y):
        av, ah, f1 = x
        bv, bh, f2 = y
        # within a run (no reset): keep a if it has a value, else b
        nv = jnp.where(ah, av, bv)
        nh = ah | bh
        return (jnp.where(f2, bv, nv), jnp.where(f2, bh, nh), f1 | f2)

    v, h, _ = blocked_assoc_scan(combine, (vals, live, boundary))
    return v, h


class _SortedView:
    """Precomputed per-(batch, group_by) state shared by all aggregates.

    method="hash": ONE multi-operand `lax.sort` keyed on the 64-bit key
    hash carries sel + every referenced column (and validity) through the
    sort network as payloads. Random-access gathers at 1M lanes cost
    ~25 ms each on v5e (HBM random access) while payload movement inside
    the bitonic network is sequential — the payload sort replaces ~2
    gathers per column plus the argsort. Boundaries come from adjacent
    comparison of the sorted payloads themselves (a shift, not a gather),
    and collisions are detected exactly as in sorted_groups.

    method="lex": the exact multi-key lexsort path (sorted_groups) with
    per-column gathers — kept for non-hot callers and as the differential
    reference.
    """

    def __init__(self, batch: Batch, group_by: Sequence[str],
                 seed: int = 0, method: str = "lex"):
        from cockroach_tpu.ops.search import counts_at_most

        cap = batch.capacity
        self.cap = cap
        self._sorted: dict = {}

        if method == "hash":
            from cockroach_tpu.ops.hash import hash_columns

            group_by = list(group_by)
            h = hash_columns(batch, group_by, seed=seed)
            h = jnp.where(batch.sel, h, jnp.uint64(0xFFFFFFFFFFFFFFFF))
            # TWO-operand sort (compile cost on TPU scales ~linearly with
            # sort operand count, ~30s each at 1M) + ONE row-gather of all
            # referenced columns stacked into an int64 matrix (a (cap, C)
            # row gather costs what a single 1-D gather costs; C separate
            # gathers cost C times that). (hash, position) is a total key
            # and IS the stable order, so both operands are keys and the
            # sort is unstable: the default's tie-break would be a third
            # operand (scripts/price_sort_operands.py)
            h_sorted, perm = lax.sort(
                (h, jnp.arange(cap, dtype=jnp.int32)), num_keys=2,
                is_stable=False)
            self.perm = perm

            from cockroach_tpu.ops.rowmat import pack_rows, unpack_rows

            mat, plan = pack_rows(batch)
            cols_sorted, self.sel_sorted = unpack_rows(mat[perm], plan)
            for n, c in cols_sorted.items():
                self._sorted[n] = (c.values, c.validity)

            idx = jnp.arange(cap)
            prev_ok = idx > 0
            same = jnp.ones(cap, dtype=jnp.bool_)
            for n in group_by:
                v, valid = self._sorted[n]
                pv = _shift1(v)
                col_eq = v == pv
                if jnp.issubdtype(v.dtype, jnp.floating):
                    col_eq = col_eq | (jnp.isnan(v) & jnp.isnan(pv))
                if valid is not None:
                    pvalid = _shift1(valid)
                    col_eq = jnp.where(valid & pvalid, col_eq,
                                       valid == pvalid)
                same = same & col_eq
            same = same & prev_ok
            first_live = self.sel_sorted & (jnp.cumsum(self.sel_sorted) == 1)
            boundary = self.sel_sorted & (first_live | ~same)
            boundary = boundary.at[0].set(self.sel_sorted[0])
            prev_live = _shift1(self.sel_sorted) & prev_ok
            h_prev = _shift1(h_sorted)
            collision = jnp.any(self.sel_sorted & prev_live
                                & (h_sorted == h_prev) & ~same)
            gid_sorted = jnp.cumsum(boundary.astype(jnp.int32)) - 1
            num_groups = jnp.sum(boundary).astype(jnp.int32)
            gid_sorted = jnp.where(self.sel_sorted, gid_sorted, cap)
            self.sg = SortedGroups(perm, None, boundary, gid_sorted,
                                   num_groups, collision)
        else:
            sg = sorted_groups(batch, group_by, seed=seed, method=method)
            self.sg = sg
            self.perm = sg.perm
            self.sel_sorted = batch.sel[sg.perm]

        self._init_extents(cap)

    def _init_extents(self, cap: int):
        from cockroach_tpu.ops.search import counts_at_most

        g = jnp.arange(cap)
        # group extents from a histogram prefix (gid_sorted is
        # non-decreasing): starts[g] = #{gid < g}, ends[g] = #{gid <= g}-1
        cam = counts_at_most(self.sg.gid_sorted, cap)
        self.starts = jnp.minimum(
            jnp.concatenate([jnp.zeros(1, jnp.int32), cam[:-1]]), cap - 1)
        self.ends = jnp.minimum(cam - 1, cap - 1).astype(jnp.int32)
        self.out_sel = g < self.sg.num_groups

    def sorted_col(self, batch: Batch, name: str):
        if name in self._sorted:
            v, valid = self._sorted[name]
            live = (self.sel_sorted if valid is None
                    else (self.sel_sorted & valid))
            return v, live
        c = batch.col(name)
        v = c.values[self.perm]
        live = self.sel_sorted if c.validity is None else (
            self.sel_sorted & c.validity[self.perm])
        return v, live

    def leader_col(self, batch: Batch, name: str):
        """Group-key column at each group's first sorted row."""
        if name in self._sorted:
            v, valid = self._sorted[name]
            return Column(v[self.starts],
                          None if valid is None else valid[self.starts])
        c = batch.col(name)
        leader = self.perm[self.starts]
        return Column(c.values[leader],
                      None if c.validity is None else c.validity[leader])

    def run_diff(self, prefix):
        """Per-group total from an inclusive prefix sum."""
        at_end = prefix[self.ends]
        before = jnp.where(
            self.starts > 0, prefix[jnp.maximum(self.starts - 1, 0)],
            jnp.zeros((), prefix.dtype))
        return at_end - before

    def run_end(self, scanned):
        return scanned[self.ends]

    def lane(self, arr, counts: bool = False):
        """-> (`arr` as an int64 lane of the (cap, L) matrix that
        `readers` row-gathers, how to decode it)."""
        dt = arr.dtype
        if jnp.issubdtype(dt, jnp.floating):
            return (arr.astype(jnp.float32).view(jnp.uint32)
                    .astype(jnp.int64)), "f32"
        if dt == jnp.bool_:
            return arr.astype(jnp.int64), "bool"
        return arr.astype(jnp.int64), "i64" if dt != jnp.int32 else "i32"

    def readers(self, lanes):
        """-> (at_end(i), diff(i)): lane i's value at each group's last
        row, and that less its value before the group's first, per group
        lane g. ONE batched row gather at the run ends; the prefix row
        BEFORE each group needs no second one: runs are contiguous among
        live lanes (dead lanes contribute zero to every masked prefix),
        so prefix-before-group-g IS end_rows[g-1], a shift."""
        dec = [d for _a, d in lanes]
        P = jnp.stack([a for a, _d in lanes], axis=1)     # (cap, L) int64
        end_rows = P[self.ends]
        prev_rows = jnp.concatenate(
            [jnp.zeros((1, P.shape[1]), P.dtype), end_rows[:-1]], axis=0)
        has_prev = self.starts > 0

        def at_end(i):
            v = end_rows[:, i]
            if dec[i] == "f32":
                return v.astype(jnp.uint32).view(jnp.float32)
            if dec[i] == "bool":
                return v != 0
            return v.astype(jnp.int32) if dec[i] == "i32" else v

        def diff(i):
            e, b = at_end(i), prev_rows[:, i]
            if dec[i] == "f32":
                b = b.astype(jnp.uint32).view(jnp.float32)
            elif dec[i] == "i32":
                b = b.astype(jnp.int32)
            return e - jnp.where(has_prev, b, jnp.zeros((), e.dtype))

        return at_end, diff


def _to_words(x) -> list:
    """`x` as uint32 words, high first (one, or two for a 64-bit dtype):
    what rides a 64-bit running maximum under a lane count."""
    if x.dtype == jnp.bool_ or x.dtype.itemsize < 4:
        x = x.astype(jnp.int32)
    if x.dtype.itemsize == 4:
        return [lax.bitcast_convert_type(x, jnp.uint32)]
    u = lax.bitcast_convert_type(x, jnp.uint64)
    return [(u >> np.uint64(32)).astype(jnp.uint32), u.astype(jnp.uint32)]


def _from_words(words: Sequence, dtype):
    """_to_words back."""
    if len(words) == 2:
        hi, lo = (w.astype(jnp.uint64) for w in words)
        return lax.bitcast_convert_type((hi << np.uint64(32)) | lo, dtype)
    if dtype == jnp.bool_ or jnp.dtype(dtype).itemsize < 4:
        return lax.bitcast_convert_type(words[0], jnp.int32).astype(dtype)
    return lax.bitcast_convert_type(words[0], dtype)


def _at_last_marked(mark, vals: Sequence):
    """-> (each of `vals` at the nearest lane at or before a lane where
    `mark` holds (zeros before the first), whether there is one). No
    gather and no generic scan (whose compile time the TPU's compiler
    does not bound at millions of lanes): the marks counted so far ride
    above bit 32 of a running maximum, so the latest marked lane's word
    wins over every earlier lane's; one blocked cummax a 32-bit word."""
    count = blocked_cumsum(mark.astype(jnp.int32))
    above = count.astype(jnp.int64) << np.int64(32)

    def last(word):
        enc = above | jnp.where(mark, word.astype(jnp.int64), 0)
        return (blocked_cummax(enc) & np.int64(0xFFFFFFFF)).astype(
            jnp.uint32)

    return ([_from_words([last(w) for w in _to_words(v)], v.dtype)
             for v in vals], count > 0)


def _next_live(vals: Sequence, live):
    """-> (each of `vals` at the nearest live lane AFTER a lane, whether
    there is one): what a lane with dead neighbours is compared with."""
    def after(x):  # lane i takes the reversed lanes' lane i + 1
        return jnp.concatenate([x[::-1][1:], jnp.zeros((1,), x.dtype)])

    got, has = _at_last_marked(live[::-1], [v[::-1] for v in vals])
    return [after(v) for v in got], after(has)


class _RunEndsView:
    """What _eval_aggs reads of a batch whose equal group keys are ALREADY
    adjacent among its live lanes (run_ends_aggregate), in place: no
    hash, no sort, no permutation, and nothing gathered or scattered.

    A live lane ENDS its run when the next live lane's keys differ (or
    none follows), NULLs equal to each other and NaN to NaN as the sorted
    views have it; the lane after an end opens the next run, so dead
    lanes inside a run, before its first live lane or after its last
    belong to no group and add nothing (every prefix is masked by
    liveness). `dense`: the caller vouches that no dead lane lies
    between two live ones (a compacted batch: live lanes first), and the
    next live lane is the next lane; otherwise its keys are carried back
    over the dead lanes (_next_live: one running maximum a 32-bit word of
    the keys), so a filter that punched holes into runs, a run's first
    lane among them, groups exactly."""

    perm = None  # rows stand where they stood

    def __init__(self, batch: Batch, group_by: Sequence[str], dense: bool):
        live = batch.sel
        self.sel_sorted = live
        keys = [batch.col(n) for n in group_by]
        mine = [x for c in keys for x in (c.values, c.validity)
                if x is not None]
        if dense:
            theirs = [jnp.concatenate([x[1:], x[-1:]]) for x in mine]
            follows = jnp.concatenate([live[1:], jnp.zeros((1,), jnp.bool_)])
        else:
            theirs, follows = _next_live(mine, live)
        theirs = iter(theirs)
        same = jnp.ones(batch.capacity, dtype=jnp.bool_)
        for c in keys:
            v, nv = c.values, next(theirs)
            col_eq = v == nv
            if jnp.issubdtype(v.dtype, jnp.floating):
                col_eq = col_eq | (jnp.isnan(v) & jnp.isnan(nv))
            if c.validity is not None:
                nvalid = next(theirs)
                col_eq = jnp.where(c.validity & nvalid, col_eq,
                                   c.validity == nvalid)
            same = same & col_eq
        self.out_sel = live & ~(follows & same)   # one lane a group
        is_end = self.out_sel
        opens = jnp.concatenate([jnp.ones((1,), jnp.bool_), is_end[:-1]])
        num_groups = jnp.sum(is_end).astype(jnp.int32)
        self.sg = SortedGroups(None, None, opens, None, num_groups,
                               jnp.bool_(False))

    def sorted_col(self, batch: Batch, name: str):
        c = batch.col(name)
        return c.values, (self.sel_sorted if c.validity is None
                          else self.sel_sorted & c.validity)

    def lane(self, arr, counts: bool = False):
        if jnp.issubdtype(arr.dtype, jnp.floating):
            arr = arr.astype(jnp.float32)  # as the sorted views' lanes
        return arr, counts

    def _before_run(self, x, counts: bool):
        """Running sum `x` at the end of the PREVIOUS run (0 before the
        first), at every lane of a run."""
        if counts:
            # a count never falls: the last end's is the largest so far
            last = blocked_cummax(jnp.where(self.out_sel, x,
                                            jnp.zeros((), x.dtype)))
        else:
            # a signed or float sum rises and falls: _at_last_marked
            (last,), _any = _at_last_marked(self.out_sel, [x])
        return jnp.concatenate([jnp.zeros((1,), x.dtype), last[:-1]])

    def readers(self, lanes):
        """-> (at_end(i), diff(i)) as _SortedView.readers, every group at
        its run's last live lane (other lanes hold no answer: out_sel)."""
        before: dict = {}

        def at_end(i):
            return lanes[i][0]

        def diff(i):
            if i not in before:
                before[i] = self._before_run(*lanes[i])
            return lanes[i][0] - before[i]

        return at_end, diff


def _eval_aggs(aggs: Sequence[AggSpec], batch: Batch,
               view,
               group_keys: Sequence[str] = ()) -> dict:
    """Evaluate EVERY aggregate AND the group-key output columns over a
    view of the batch in which a group's rows are one contiguous run.

    Phase 1 builds the per-agg prefix arrays (cumsums / segmented scans —
    sequential-access, cheap) plus one lane per group-key column (its
    sorted values: the value at a run's END equals the value at its
    leader). Phase 2 is the view's (`readers`): each lane's value at the
    run ends, and for the running sums that value less the one before the
    run. A _SortedView stacks the lanes into one (cap, L) int64 matrix and
    gathers whole rows at run ends, group g to lane g — a 1-D gather
    moves ~0.2 GB/s on v5e while the (cap, L) row gather moves every lane
    for the same cost (profiled r4: per-column gathers dominated Q3's
    device time). A _RunEndsView leaves every group at its run's last
    live lane and gathers nothing."""
    if not aggs and not group_keys:
        return {}  # DISTINCT with no keys: nothing to emit
    lanes: list = []

    def add_lane(arr, counts: bool = False) -> int:
        lanes.append(view.lane(arr, counts))
        return len(lanes) - 1

    cnt_lane: dict = {}  # col name (or None=sel) -> live-count lane index

    def count_lane_of(col: Optional[str]) -> int:
        if col not in cnt_lane:
            live = (view.sel_sorted if col is None
                    else view.sorted_col(batch, col)[1])
            cnt_lane[col] = add_lane(blocked_cumsum(live.astype(jnp.int64)),
                                     counts=True)
        return cnt_lane[col]

    specs = []  # (agg, kind, lane indices...)
    for a in aggs:
        if a.func == "count_star":
            specs.append((a, "diff", count_lane_of(None)))
            continue
        v, live = view.sorted_col(batch, a.col)
        ci = count_lane_of(a.col)
        if a.func == "count":
            specs.append((a, "diff", ci))
        elif a.func in ("sum_hi32", "sum_lo32"):
            half = _wide_half(a.func, v)
            i = add_lane(blocked_cumsum(jnp.where(live, half, jnp.int64(0))))
            specs.append((a, "diff_valid", i, ci))
        elif a.func in ("sum", "avg"):
            acc = (v.dtype if jnp.issubdtype(v.dtype, jnp.integer)
                   else jnp.float32)
            i = add_lane(blocked_cumsum(
                jnp.where(live, v, jnp.zeros((), v.dtype)).astype(acc)))
            specs.append((a, "sum" if a.func == "sum" else "avg", i, ci))
        elif a.func in ("min", "max"):
            ident = _identity(a.func, v.dtype)
            op = jnp.minimum if a.func == "min" else jnp.maximum
            i = add_lane(_seg_scan(op, jnp.where(live, v, ident),
                                   view.sg.boundary))
            specs.append((a, "end_valid", i, ci))
        elif a.func in ("bool_and", "bool_or"):
            ident = a.func == "bool_and"
            op = jnp.minimum if a.func == "bool_and" else jnp.maximum
            i = add_lane(_seg_scan(
                op, jnp.where(live, v, ident).astype(jnp.int32),
                view.sg.boundary))
            specs.append((a, "end_bool", i, ci))
        elif a.func == "any_not_null":
            sv, sh = _seg_first_live(v, live, view.sg.boundary)
            i = add_lane(sv)
            j = add_lane(sh)
            specs.append((a, "first_live", i, j, ci))
        else:
            raise AssertionError(a.func)

    key_specs = []  # (name, value lane, validity lane or None)
    for name in group_keys:
        v, _live = view.sorted_col(batch, name)
        vi = add_lane(v)
        c = batch.col(name)
        if c.validity is not None:
            valid_sorted = (c.validity if view.perm is None
                            else c.validity[view.perm])
            key_specs.append((name, vi, add_lane(valid_sorted)))
        else:
            key_specs.append((name, vi, None))

    at_end, diff = view.readers(lanes)

    out: dict = {}
    for name, vi, validi in key_specs:
        out[name] = Column(at_end(vi),
                           None if validi is None else at_end(validi))
    for spec in specs:
        a, kind = spec[0], spec[1]
        if kind == "diff":
            out[a.out] = Column(diff(spec[2]))
            continue
        cnt = diff(spec[-1])
        any_live = cnt > 0
        if kind == "diff_valid":
            out[a.out] = Column(diff(spec[2]), any_live)
        elif kind == "sum":
            out[a.out] = Column(diff(spec[2]), any_live)
        elif kind == "avg":
            s = diff(spec[2]).astype(jnp.float32)
            out[a.out] = Column(
                s / jnp.maximum(cnt, 1).astype(jnp.float32), any_live)
        elif kind == "end_valid":
            out[a.out] = Column(at_end(spec[2]), any_live)
        elif kind == "end_bool":
            out[a.out] = Column(at_end(spec[2]) > 0, any_live)
        elif kind == "first_live":
            found = at_end(spec[3])
            found = found if found.dtype == jnp.bool_ else found != 0
            out[a.out] = Column(at_end(spec[2]), found & any_live)
        else:
            raise AssertionError(kind)
    return out


def _wide_half(func: str, v):
    """Exact two's-complement split: v == (v >> 32) * 2**32 + (v & mask)
    with arithmetic shift, for any signed int64 v."""
    v = v.astype(jnp.int64)
    if func == "sum_hi32":
        return v >> jnp.int64(32)
    return v & jnp.int64(0xFFFFFFFF)


def _scalar_agg(agg: AggSpec, batch: Batch) -> Column:
    """Aggregation without GROUP BY: plain masked reductions, one lane."""
    sel = batch.sel
    if agg.func == "count_star":
        return Column(jnp.sum(sel.astype(jnp.int64))[None])
    c = batch.col(agg.col)
    live = sel if c.validity is None else (sel & c.validity)
    v = c.values
    any_live = jnp.any(live)[None]
    if agg.func == "count":
        return Column(jnp.sum(live.astype(jnp.int64))[None])
    if agg.func in ("sum_hi32", "sum_lo32"):
        half = _wide_half(agg.func, v)
        return Column(jnp.sum(jnp.where(live, half, jnp.int64(0)))[None],
                      any_live)
    if agg.func in ("sum", "avg"):
        acc_dtype = v.dtype if jnp.issubdtype(v.dtype, jnp.integer) else jnp.float32
        s = jnp.sum(jnp.where(live, v, jnp.zeros((), v.dtype)).astype(acc_dtype))
        if agg.func == "sum":
            return Column(s[None], any_live)
        cnt = jnp.maximum(jnp.sum(live.astype(jnp.int64)), 1)
        return Column((s.astype(jnp.float32) / cnt.astype(jnp.float32))[None],
                      any_live)
    if agg.func in ("min", "max"):
        ident = _identity(agg.func, v.dtype)
        filled = jnp.where(live, v, ident)
        r = jnp.min(filled) if agg.func == "min" else jnp.max(filled)
        return Column(r[None], any_live)
    if agg.func in ("bool_and", "bool_or"):
        ident = agg.func == "bool_and"
        filled = jnp.where(live, v, ident)
        r = jnp.all(filled) if agg.func == "bool_and" else jnp.any(filled)
        return Column(r[None], any_live)
    if agg.func == "any_not_null":
        first = jnp.argmax(live)  # first True (0 if none — masked by validity)
        return Column(v[first][None], any_live)
    raise AssertionError(agg.func)


def hash_aggregate(batch: Batch, group_by: Sequence[str],
                   aggs: Sequence[AggSpec], seed: int = 0,
                   method: str = "lex", with_flag: bool = False):
    """GROUP BY group_by. Output: group g at lane g (key-sorted order),
    live lanes [0, num_groups). Scalar aggregation (group_by=[]) emits one
    row even over zero input rows (SQL scalar-agg semantics).

    method="hash" (see sorted_groups) sorts on one 64-bit key hash —
    drastically cheaper to compile on TPU than a multi-operand lexsort —
    and reports possible hash collisions via the second return value when
    `with_flag` is set; the flow runtime answers a raised flag with a
    re-seeded rerun (exact semantics, probabilistically-free fast path).
    """
    if not group_by:
        out_cols = {a.out: _scalar_agg(a, batch) for a in aggs}
        out = Batch(out_cols, jnp.ones(1, dtype=jnp.bool_), jnp.int32(1))
        return (out, jnp.bool_(False)) if with_flag else out

    view = _SortedView(batch, group_by, seed=seed, method=method)
    out_cols = dict(_eval_aggs(aggs, batch, view, group_keys=group_by))
    out_cols = mask_padding(out_cols, view.out_sel)
    out = Batch(out_cols, view.out_sel, view.sg.num_groups)
    return (out, view.sg.collision) if with_flag else out


# ---------------------------------------------------------------------------
# Dense (sort-free) aggregation for low-cardinality keys.
#
# When every GROUP BY column has a statically known small domain (dictionary
# codes, bools, or an integer or date key whose range [lo, hi] the planner
# proved from the table statistics: the year of o_orderdate, 1992..1998),
# the group space is a fixed D = prod(sizes) lanes and every
# aggregate is a masked reduction over a (cap, D) broadcast — no sort, no
# scatter, no data-dependent shapes. A ranged key's slot is `value - lo`;
# statistics go stale, so a live value outside [lo, hi] raises a deferred
# flag (dense_aggregate's with_flag) and the flow restarts without the
# range: never a dropped or clamped row. Two wins on TPU: the kernel is pure
# VPU-friendly elementwise+reduce (a 1M-row batch aggregates in ~HBM-read
# time), and the compiled program contains NO sort HLO — big sorts are what
# makes a whole-query program slow to compile for the TPU (Q1 at SF1: 5 s;
# Q3, which sorts: 149 s — scripts/rehearse_tpu_compile.py), so Q1-style
# queries would otherwise pay minutes of compile for milliseconds of work.
# Reference analog: hash_aggregator.go's distinct-first optimization;
# the merge step is lane-aligned elementwise combine (partials share the
# same static key space), replacing the concat+re-aggregate merge.


DENSE_MAX_GROUPS = 256  # (cap x D) broadcast traffic bound


def dense_key_sizes(schema, group_by: Sequence[str], domains=None):
    """Per-key domain sizes (incl. a NULL slot) if every group column has a
    statically known small domain; None otherwise. `domains` (name ->
    (lo, hi), inclusive: the planner's, sql/plan.key_domains) gives an
    INT or DATE key its range; a key of those kinds without one has no
    static domain."""
    from cockroach_tpu.coldata.batch import Kind as _Kind

    domains = domains or {}
    sizes = []
    for n in group_by:
        f = schema.field(n)
        if f.type.kind is _Kind.STRING:
            d = schema.dictionary(n)
            if d is None:
                return None
            sizes.append(len(d) + 1)  # +1 = NULL slot
        elif f.type.kind is _Kind.BOOL:
            sizes.append(3)  # false, true, NULL
        elif f.type.kind in (_Kind.INT, _Kind.DATE) and n in domains:
            lo, hi = domains[n]
            if hi < lo:
                return None
            # the NULL slot always, as a dictionary key has it: an outer
            # join NULL-extends a column its table calls NOT NULL
            sizes.append(hi - lo + 2)
        else:
            return None
    prod = 1
    for s in sizes:
        prod *= s
    if not sizes or prod > DENSE_MAX_GROUPS:
        return None
    return sizes


def _dense_packed(batch: Batch, group_by: Sequence[str],
                  sizes: Sequence[int], domains=None):
    """(cap,) packed group code in [0, D); D for dead lanes. NULL keys
    take the last slot of their column's domain. A key in `domains`
    (ranged, see dense_key_sizes) takes slot `value - lo`; the third
    result is whether a live row's key lies outside its range (False
    without ranged keys)."""
    domains = domains or {}
    D = 1
    for s in sizes:
        D *= s
    packed = jnp.zeros(batch.capacity, dtype=jnp.int32)
    outside = jnp.bool_(False)
    for n, size in zip(group_by, sizes):
        c = batch.col(n)
        if n in domains:
            lo, hi = domains[n]
            v = c.values.astype(jnp.int64)
            inside = (v >= lo) & (v <= hi)
            outside |= jnp.any(batch.sel & c.valid_mask() & ~inside)
            code = jnp.where(inside, v - lo, 0).astype(jnp.int32)
        else:
            code = c.values.astype(jnp.int32)
        if c.validity is not None:
            code = jnp.where(c.validity, code, jnp.int32(size - 1))
        packed = packed * size + code
    return jnp.where(batch.sel, packed, jnp.int32(D)), D, outside


def dense_aggregate(batch: Batch, group_by: Sequence[str],
                    aggs: Sequence[AggSpec], sizes: Sequence[int],
                    domains=None, with_flag: bool = False):
    """GROUP BY over the dense key space. Output: capacity D, group with
    packed code g at LANE g (a fixed global layout — partials from
    different batches merge lane-wise with dense_merge). sel marks groups
    with >= 1 selected row. With ranged keys (`domains`, as
    dense_key_sizes took them) the caller asks `with_flag` and gets
    (Batch, a live key lay outside its range): the statistics were stale,
    the batch is not the answer, and the flow runtime restarts without
    the range (HashAggOp.widen).

    Two lowering paths: the Pallas MXU kernel (ops/pallas_kernels.py)
    computes all integer sum/count aggregates in ONE pass via byte-limb
    matmuls when sql.tpu.pallas enables it; everything else (and the
    fallback) uses per-aggregate masked broadcasts."""
    group_by, domains = list(group_by), domains or {}
    assert with_flag or not domains, "a ranged key needs its flag read"
    packed, D, outside = _dense_packed(batch, group_by, sizes, domains)

    interp = _pallas_mode()
    kernel_cols: dict = {}
    rest = list(aggs)
    counts = None
    if interp is not None:
        counts, kernel_cols, rest = _dense_kernel_sums(
            batch, aggs, packed, D, interp)
    mask = None
    lanes = jnp.arange(D, dtype=jnp.int32)
    if rest or counts is None:
        mask = packed[:, None] == lanes[None, :]      # (cap, D)
        if counts is None:
            counts = jnp.sum(mask, axis=0, dtype=jnp.int64)

    out_cols: dict = {}
    # decode lane -> per-column codes; NULL slot clears validity
    rem = lanes
    codes = []
    for size in reversed(sizes):
        codes.append(rem % size)
        rem = rem // size
    codes.reverse()
    for n, size, code in zip(group_by, sizes, codes):
        c = batch.col(n)
        is_null = code == (size - 1) if c.validity is not None else None
        if n in domains:
            code = code.astype(jnp.int64) + domains[n][0]
        if c.validity is None:
            out_cols[n] = Column(code.astype(c.values.dtype))
        else:
            out_cols[n] = Column(
                jnp.where(is_null, 0, code).astype(c.values.dtype), ~is_null)

    for a in rest:
        out_cols[a.out] = _dense_one(a, batch, mask, counts)
    out_cols.update(kernel_cols)
    sel = counts > 0
    out_cols = mask_padding(out_cols, sel)
    out = Batch(out_cols, sel, jnp.sum(sel).astype(jnp.int32))
    return (out, outside) if with_flag else out


def _pallas_mode():
    """-> None (kernel off) or the `interpret` flag for pallas_call."""
    from cockroach_tpu.util.settings import PALLAS, Settings

    mode = Settings().get(PALLAS)
    if mode == "off":
        return None
    if mode == "interpret":
        return True
    if mode == "on":
        return False
    import jax

    return False if jax.default_backend() == "tpu" else None


def _dense_kernel_sums(batch: Batch, aggs, packed, D, interp):
    """Route integer sum/count aggregates through the Pallas limb-matmul
    kernel (one fused pass). Returns (counts, {out: Column}, leftover
    aggregates for the broadcast path); (None, {}, aggs) if nothing
    qualifies."""
    from cockroach_tpu.ops import pallas_kernels as pk

    if batch.capacity > pk.MAX_ROWS:
        return None, {}, list(aggs)
    ones = jnp.ones(batch.capacity, dtype=jnp.int64)
    cols = [(ones, None)]  # index 0: rows-per-group (count_star/counts)
    index: dict = {}

    def add(values, live, key):
        if key in index:
            return index[key]
        cols.append((values, live))
        index[key] = len(cols) - 1
        return index[key]

    plan = []
    rest = []
    for a in aggs:
        if a.func == "count_star":
            plan.append((a, "count_star", 0, 0))
            continue
        if a.func not in ("count", "sum", "sum_hi32", "sum_lo32"):
            rest.append(a)
            continue
        c = batch.col(a.col)
        if a.func != "count" and c.values.dtype != jnp.int64:
            # float sums stay on the f32 broadcast path; narrower int
            # columns keep the fallback's own-dtype wrap semantics
            rest.append(a)
            continue
        live = c.validity
        cnt_idx = (0 if live is None
                   else add(ones, live, ("cnt", a.col)))
        if a.func == "count":
            plan.append((a, "count", cnt_idx, cnt_idx))
            continue
        v = c.values.astype(jnp.int64)
        if a.func in ("sum_hi32", "sum_lo32"):
            v = _wide_half(a.func, v)
        vi = add(v, live, (a.func, a.col))
        plan.append((a, "sum", vi, cnt_idx))
    if not plan:
        return None, {}, list(aggs)

    sums = pk.dense_sums_via_pallas(packed, cols, D, interp)
    counts = sums[0]
    out = {}
    for a, kind, i, cnt_idx in plan:
        if kind == "count_star":
            out[a.out] = Column(counts)
        elif kind == "count":
            out[a.out] = Column(sums[i])
        else:
            n_live = sums[cnt_idx]
            out[a.out] = Column(sums[i], n_live > 0)
    return counts, out, rest


def _dense_one(agg: AggSpec, batch: Batch, mask, counts) -> Column:
    if agg.func == "count_star":
        return Column(counts)
    c = batch.col(agg.col)
    v = c.values
    live = mask if c.validity is None else (mask & c.validity[:, None])
    n_live = jnp.sum(live, axis=0, dtype=jnp.int64)
    any_live = n_live > 0
    if agg.func == "count":
        return Column(n_live)
    if agg.func in ("sum_hi32", "sum_lo32"):
        half = _wide_half(agg.func, v)
        s = jnp.sum(jnp.where(live, half[:, None], jnp.int64(0)), axis=0)
        return Column(s, any_live)
    if agg.func in ("sum", "avg"):
        acc_dtype = (v.dtype if jnp.issubdtype(v.dtype, jnp.integer)
                     else jnp.float32)
        s = jnp.sum(jnp.where(live, v[:, None],
                              jnp.zeros((), v.dtype)).astype(acc_dtype),
                    axis=0)
        if agg.func == "sum":
            return Column(s, any_live)
        mean = s.astype(jnp.float32) / jnp.maximum(n_live, 1).astype(jnp.float32)
        return Column(mean, any_live)
    if agg.func in ("min", "max"):
        ident = _identity(agg.func, v.dtype)
        filled = jnp.where(live, v[:, None], ident)
        r = (jnp.min(filled, axis=0) if agg.func == "min"
             else jnp.max(filled, axis=0))
        return Column(r, any_live)
    if agg.func in ("bool_and", "bool_or"):
        ident = agg.func == "bool_and"
        filled = jnp.where(live, v[:, None], ident)
        r = (jnp.all(filled, axis=0) if agg.func == "bool_and"
             else jnp.any(filled, axis=0))
        return Column(r, any_live)
    if agg.func == "any_not_null":
        first = jnp.argmax(live, axis=0)
        return Column(v[first], any_live)
    raise AssertionError(agg.func)


_DENSE_MERGE = {
    "sum": "sum", "count": "sum", "count_star": "sum",
    "sum_hi32": "sum", "sum_lo32": "sum",
    "min": "min", "max": "max", "bool_and": "bool_and",
    "bool_or": "bool_or", "any_not_null": "any_not_null",
}


def dense_merge(a: Batch, b: Batch, group_by: Sequence[str],
                aggs: Sequence[AggSpec]) -> Batch:
    """Lane-aligned merge of two dense_aggregate outputs (same key space):
    pure elementwise combines, no sort, no concat."""
    sel = a.sel | b.sel
    out_cols: dict = {}
    for n in group_by:
        ca, cb = a.col(n), b.col(n)
        # the per-lane key decode is identical in both partials, but
        # mask_padding ZEROES key values on lanes dead in that partial —
        # a lane live only in b must take b's values (a partial may
        # miss a group entirely)
        if ca.validity is None:
            out_cols[n] = Column(jnp.where(a.sel, ca.values, cb.values))
        else:
            out_cols[n] = Column(jnp.where(a.sel, ca.values, cb.values),
                                 jnp.where(a.sel, ca.validity, cb.validity))
    for spec in aggs:
        f = _DENSE_MERGE[spec.func]
        ca, cb = a.col(spec.out), b.col(spec.out)
        va = ca.valid_mask() if ca.validity is not None else a.sel
        vb = cb.valid_mask() if cb.validity is not None else b.sel
        if f == "sum":
            if ca.validity is None and cb.validity is None:
                out_cols[spec.out] = Column(ca.values + cb.values)
            else:
                z = jnp.zeros((), ca.values.dtype)
                out_cols[spec.out] = Column(
                    jnp.where(va, ca.values, z) + jnp.where(vb, cb.values, z),
                    va | vb)
        elif f in ("min", "max"):
            ident = _identity(f, ca.values.dtype)
            xa = jnp.where(va, ca.values, ident)
            xb = jnp.where(vb, cb.values, ident)
            op = jnp.minimum if f == "min" else jnp.maximum
            out_cols[spec.out] = Column(op(xa, xb), va | vb)
        elif f in ("bool_and", "bool_or"):
            ident = f == "bool_and"
            xa = jnp.where(va, ca.values, ident)
            xb = jnp.where(vb, cb.values, ident)
            out_cols[spec.out] = Column(
                xa & xb if f == "bool_and" else xa | xb, va | vb)
        elif f == "any_not_null":
            out_cols[spec.out] = Column(
                jnp.where(va, ca.values, cb.values), va | vb)
        else:
            raise AssertionError(f)
    out_cols = mask_padding(out_cols, sel)
    return Batch(out_cols, sel, jnp.sum(sel).astype(jnp.int32))


def run_ends_aggregate(batch: Batch, group_by: Sequence[str],
                       aggs: Sequence[AggSpec],
                       dense: bool = False) -> Batch:
    """GROUP BY over input ALREADY grouped in contiguous runs, in place
    (reference orderedAggregator, colexec/ordered_aggregator.go): no hash
    (so no collision flag), no sort, no gather and no scatter over the
    batch's lanes. Run boundaries come from comparing each live lane's
    keys with the next live lane's, and every aggregate of `SUPPORTED`
    from the prefix arrays hash_aggregate builds (_eval_aggs: the same
    code, so the same semantics: NULL inputs, the wide sum's halves,
    avg's parts, an all-dead batch).

    Output: the UNCOMPACTED run-ends view, the form
    int_key_aggregate emits with out_capacity=0: a batch at
    the input's capacity with each group ONCE, at its run's last live
    lane (`sel` marks those lanes, `length` counts them), groups in input
    run order. top_k_batch, ShrinkOp, MapOp and a join's build take a
    sparse `sel`; Batch.compact() gives group g at lane g.

    Precondition (the caller's to prove): equal group keys are adjacent
    among the live lanes. Dead lanes may lie anywhere, inside runs too,
    unless the caller passes `dense` (live lanes first, as a compacting
    join leaves them: exec/fused._Tracer._ordered_input), which saves
    the scans that look past them."""
    view = _RunEndsView(batch, group_by, dense)
    out_cols = mask_padding(
        dict(_eval_aggs(aggs, batch, view, group_keys=group_by)),
        view.out_sel)
    return Batch(out_cols, view.out_sel, view.sg.num_groups)


def ordered_aggregate(batch: Batch, group_by: Sequence[str],
                      aggs: Sequence[AggSpec]) -> Batch:
    """run_ends_aggregate with hash_aggregate's output contract (group g
    at lane g, live lanes [0, num_groups)), groups in input run order:
    what the streaming OrderedAggOp folds, chunk by chunk.

    Precondition: equal group keys are adjacent among selected rows
    (SortOp output, PK-ordered scans), dead lanes anywhere. A caller
    whose input is only PARTIALLY grouped still gets correct results from
    the flow layer's merge fold — split runs re-merge by key there."""
    return run_ends_aggregate(batch, group_by, aggs).compact()


# What int_key_aggregate evaluates: int64 sums and counts over the packed
# inputs' bits, and their extremes: all commute exactly (its sort is unstable).
INT_KEY_AGG_FUNCS = ("sum", "count", "count_star", "min", "max")


class IntKeyAggResult(NamedTuple):
    batch: Batch           # group rows: compacted, or the run-ends view
    fallback: jnp.ndarray  # bool: rerun with key64, then by hash
    overflow: jnp.ndarray  # bool: more groups than out_capacity


def int_key_aggregate(
    batch: Batch, key_col: str, aggs: Sequence[AggSpec],
    out_capacity: int = 0, key64: bool = False,
) -> IntKeyAggResult:
    """GROUP BY a single integer column without hashing, permutation
    gathers, or an inverse sort: sort (biased key, packed agg inputs)
    directly, then segmented sums as cumsum differences.

    The general path (ops/hashtable.sorted_groups + hash_aggregate) pays
    argsort(hash) + argsort(perm), two full random key gathers and one
    gather per aggregate input. Here the key and the inputs (packed into
    one u64 operand, ops/bitpack.py) RIDE the one sort; a key range or
    packed inputs too wide for the operands raise `fallback`.

    out_capacity == 0 returns the UNCOMPACTED run-ends view
    (run_ends_aggregate's output form): a batch at input capacity whose
    sel marks one lane per group — the right shape when a selective
    filter/shrink follows (Q18's HAVING). Per-group totals use that
    cumsums of bias-packed (non-negative) inputs are non-decreasing: the
    previous group end's running value arrives via one cummax + lane
    shift. A NULL key forms its own single group (SQL GROUP BY
    semantics). A run's MIN or MAX of an input rides one running maximum
    with the run's number above bit 32 (no earlier run's value outlasts a
    run's first lane), over the input's packed bits: 32 hold them or
    `fallback` is raised."""
    cap = batch.capacity
    c = batch.col(key_col)
    live = batch.sel
    k = c.values.astype(jnp.int64)
    valid_live = live if c.validity is None else (live & c.validity)
    null_live = live & ~valid_live

    big = np.int64((1 << 62) - 1)
    klo = jnp.min(jnp.where(valid_live, k, big))
    khi = jnp.max(jnp.where(valid_live, k, -big - 1))
    anyv = jnp.any(valid_live)
    klo = jnp.where(anyv, klo, 0)
    key_budget = 62 if key64 else 30
    key_flag = anyv & ((khi - klo) >= (jnp.int64(1) << key_budget))

    kdt = jnp.uint64 if key64 else jnp.uint32
    TOP = kdt(1) << (np.uint32(63) if key64 else np.uint32(31))
    kb = jax.lax.bitcast_convert_type(
        jnp.clip(k - klo, 0, jnp.int64(1) << key_budget),
        jnp.uint64).astype(kdt)
    # live NULL keys share ONE sentinel (one NULL group); dead lanes a
    # different one — runs never mix liveness classes
    gk = jnp.where(valid_live, kb, jnp.where(null_live, TOP, TOP | kdt(2)))

    agg_cols: List[str] = []
    for a in aggs:
        if a.col is not None and a.col not in agg_cols:
            agg_cols.append(a.col)
    aplan = plan_pack(batch, agg_cols)
    apayv = pack_lanes(batch, aplan)
    agg_flag = aplan.total_bits > jnp.int32(63)

    # unstable: ties are the lanes of one group (or dead lanes), and
    # their order reaches no output. INT_KEY_AGG_FUNCS are sums and counts in
    # int64 over the packed inputs' bits, which commute exactly, read at
    # run ENDS only (every other lane of every output column is zeroed
    # and deselected below); the stable sort's tie-break would be a
    # third operand at the input's lanes (7.4 ms of 29.7 at 8,388,608
    # lanes on a v5e: scripts/price_sort_operands.py)
    sgk, sgv = jax.lax.sort((gk, apayv), num_keys=1, is_stable=False)
    prev = jnp.concatenate([~sgk[:1], sgk[:-1]])
    newrun = sgk != prev
    newrun = newrun.at[0].set(True)
    live_s = sgk != (TOP | kdt(2))
    nxt = jnp.concatenate([newrun[1:], jnp.ones((1,), jnp.bool_)])
    is_end = nxt & live_s

    def extract(a: AggSpec):
        """(values i64 biased, valid bool) per sorted lane."""
        i = aplan.names.index(a.col)
        off = aplan.offsets[i].astype(jnp.uint64)
        raw = sgv >> off
        avalid = live_s
        if aplan.nullable[i]:
            avalid = live_s & ((raw & np.uint64(1)) != 0)
            raw = raw >> np.uint64(1)
        mask = jnp.where(
            aplan.widths[i] >= 64, np.uint64(0xFFFFFFFFFFFFFFFF),
            (jnp.uint64(1) << aplan.widths[i].astype(jnp.uint64))
            - np.uint64(1))
        return jax.lax.bitcast_convert_type(raw & mask, jnp.int64), avalid

    def seg_total(cum):
        """Per-run totals at end lanes (uncompacted): cum is
        NON-DECREASING, so the previous end's running value is
        shift1(cummax(cum at ends))."""
        t = jnp.where(is_end, cum, 0)
        carry = jax.lax.cummax(t)
        prev_end = jnp.concatenate([jnp.zeros((1,), cum.dtype),
                                    carry[:-1]])
        return jnp.where(is_end, cum - prev_end, 0)

    cnt_all = jnp.cumsum(live_s.astype(jnp.int64))
    cols: Dict[str, Column] = {}
    kv = sgk.astype(jnp.int64) + klo  # un-bias (no tag bit here)
    kv = jnp.where(live_s & (sgk < TOP), kv, 0)
    key_validity = None
    if c.validity is not None:
        key_validity = is_end & (sgk < TOP)
    cols[key_col] = Column(
        jnp.where(is_end, kv, 0).astype(c.values.dtype), key_validity)

    M32 = np.int64(0xFFFFFFFF)
    # the run's number above bit 32: min and max only
    runid = (jnp.cumsum(newrun.astype(jnp.int32)).astype(jnp.int64)
             << np.int64(32)
             if any(a.func in ("min", "max") for a in aggs) else None)

    def run_extreme(v, avalid, func):
        """The run's min or max of the biased values `v` (< 2^32) over its
        valid lanes so far, at every lane: the answer at run ends. NULLs
        and, for min, the reversed value leave the low word 0, the least
        there is."""
        low = jnp.where(avalid, v if func == "max" else M32 - v, 0)
        got = jax.lax.cummax(runid | low) & M32
        return got if func == "max" else M32 - got

    sums = []
    for a in aggs:
        if a.func == "count_star":
            sums.append((a, seg_total(cnt_all), None, None))
        else:
            v, avalid = extract(a)
            # non-nullable inputs: valid-count cumsum == cnt_all
            i_n = aplan.names.index(a.col)
            cum_valid = (jnp.cumsum(avalid.astype(jnp.int64))
                         if aplan.nullable[i_n] else cnt_all)
            nv = seg_total(cum_valid)
            if a.func == "count":
                sums.append((a, nv, None, None))
            elif a.func in ("min", "max"):
                agg_flag = agg_flag | (aplan.widths[i_n] > jnp.int32(32))
                ext = run_extreme(v, avalid, a.func) + aplan.los[i_n]
                sums.append((a, jnp.where(is_end, ext, 0), nv, None))
            else:
                i = aplan.names.index(a.col)
                s = seg_total(jnp.cumsum(jnp.where(avalid, v, 0)))
                sums.append((a, s + nv * aplan.los[i], nv, None))
    for a, tot, nv, _ in sums:
        if a.func == "sum":
            cols[a.out] = Column(jnp.where(nv > 0, tot, 0), nv > 0)
        elif a.func in ("min", "max"):
            # the input's own type, as the other aggregates give it
            cols[a.out] = Column(
                jnp.where(nv > 0, tot, 0).astype(
                    batch.col(a.col).values.dtype), nv > 0)
        else:
            cols[a.out] = Column(tot, None)

    n_groups = jnp.sum(is_end)
    fallback = key_flag | agg_flag
    if not out_capacity:
        out = Batch(cols, is_end, n_groups.astype(jnp.int32))
        return IntKeyAggResult(out, fallback, jnp.bool_(False))
    # compacted variant: one single-operand u32 sort + tiny gathers
    C = out_capacity
    top = first_selected(is_end, C)
    valid = jnp.arange(C) < n_groups
    ccols = {}
    for nme, col in cols.items():
        v = jnp.where(valid, col.values[top], jnp.zeros((),
                                                        col.values.dtype))
        ccols[nme] = Column(v, None if col.validity is None
                            else (col.validity[top] & valid))
    out = Batch(ccols, valid, jnp.minimum(n_groups, C).astype(jnp.int32))
    return IntKeyAggResult(out, fallback, n_groups > C)
