"""Equi-join kernels: inner / left / right / full outer / semi / anti.

Reference: pkg/sql/colexec/colexecjoin/hashjoiner.go:166 (hashJoiner over
the chained colexechash.HashTable) — ~131K generated LoC of per-type
specializations. The chained-bucket probe is a data-dependent pointer walk;
on TPU we instead express the join as **hash-sort + binary-search probe +
static ragged expansion**, which is branch-free and entirely MXU/VPU
friendly:

1. hash build-side keys to u64, argsort build rows by hash (XLA bitonic);
2. per probe row, `searchsorted` gives the [lo, hi) candidate range;
3. expand candidate pairs into a *static* `out_capacity`-sized pair list
   with the cumsum/searchsorted ragged-expand trick;
4. verify true key equality per pair (kills hash collisions; SQL join
   semantics: NULL keys never match, unlike GROUP BY);
5. outer variants append unmatched-row regions with NULL-padded far side.

If total matches exceed `out_capacity` the result's `overflow` flag is set
and the flow runtime retries with a larger capacity or Grace-partitions the
inputs (the analog of the reference's disk spiller, disk_spiller.go:208).
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax.numpy as jnp

from cockroach_tpu.coldata.batch import Batch, Column
from cockroach_tpu.ops.hash import hash_columns
from cockroach_tpu.ops.prefix import blocked_cumsum

JOIN_TYPES = ("inner", "left", "right", "outer", "semi", "anti")


class JoinResult(NamedTuple):
    batch: Batch
    overflow: jnp.ndarray       # bool scalar: matches exceeded out_capacity
    # (rcap,) bool: build rows matched by THIS probe batch. Streaming
    # right/full-outer joins OR these across probe batches and emit
    # unmatched build rows once at end-of-stream (exec/operators.py).
    matched_build: jnp.ndarray = None


class BuildTable(NamedTuple):
    """A hash-prepared build side: batch + hash-sorted order + per-position
    run extents. Preparing once and probing many times keeps the build-side
    sort out of the per-probe-batch loop — the analog of the reference
    hashJoiner's separate build phase (hashjoiner.go:166 hjBuilding vs
    hjProbing states). The probe MUST hash with the same `seed`
    (hash_join_prepared reads it from here, so a mismatch cannot happen by
    API construction)."""

    batch: Batch
    order: jnp.ndarray       # int32 (rcap,): build rows by ascending hash
    hash_sorted: jnp.ndarray  # uint64 (rcap,): sorted build-key hashes
    run_end: jnp.ndarray     # int32 (rcap,): last index of the equal-hash
    #                          run at each sorted position (probe uses it
    #                          to turn ONE left-search into [lo, hi))
    seed: int = 0


def effective_build_mode(mode: str, build_names: Sequence[str],
                         build_on: Sequence[str]) -> str:
    """Static downgrade of the unique fast paths. Modes (the restart
    ladder JoinOp.widen descends): "unique" = payload-carry sort join
    (build columns ride the sorts bit-packed; directly under a Shrink
    the build's row index rides instead and no width binds);
    "unique-mat" = sort join with a row-matrix gather (the r4 path —
    the fallback when the resorting form's payload exceeds 62 bits at
    run time, or a key leaves [0, 2^30)); "expand" = general
    many-to-many. The row matrix's packed-boolean lane holds at most 64
    bits — worst case 1 (sel) + 2 per column, so 31 columns is the safe
    bound; wider build sides go straight to expand."""
    if mode not in ("unique", "unique-mat"):
        return mode
    if len(set(build_names) | set(build_on)) > 31:
        return "expand"
    return mode


def prepare_build(right: Batch, right_on: Sequence[str],
                  seed: int = 0, mode: str = "expand"):
    """Prepare the build side for probing.

    mode="unique" -> the sort-join fast path (ops/sortjoin.py): assumes
    build keys are unique (every FK->PK join); duplicate keys surface as
    the deferred fallback flag and the flow driver restarts in "expand".
    mode="expand" -> the general many-to-many hash-sort + ragged
    expansion path (this module)."""
    if mode in ("unique", "unique-mat"):
        from cockroach_tpu.ops.sortjoin import prepare_unique

        return prepare_unique(right, right_on, seed=seed,
                              carry=(mode == "unique"))
    from cockroach_tpu.ops.search import run_ends

    sentinel = jnp.uint64(0xFFFFFFFFFFFFFFFF)
    hr = hash_columns(right, right_on, seed=seed)
    hr = jnp.where(right.sel, hr, sentinel)
    order = jnp.argsort(hr).astype(jnp.int32)
    hr_sorted = hr[order]
    return BuildTable(right, order, hr_sorted, run_ends(hr_sorted), seed)


def _null_columns(batch: Batch, rows, valid_mask) -> dict:
    """Gather columns at `rows` but mark validity by `valid_mask` (used to
    NULL-out the far side of outer-join regions)."""
    out = {}
    for n, c in batch.columns.items():
        vals = jnp.where(valid_mask, c.values[rows], jnp.zeros((), c.values.dtype))
        base = c.valid_mask()[rows] if c.validity is not None else jnp.ones_like(valid_mask)
        out[n] = Column(vals, base & valid_mask)
    return out


def hash_join(left: Batch, right: Batch, left_on: Sequence[str],
              right_on: Sequence[str], how: str = "inner",
              out_capacity: int | None = None, seed: int = 0,
              mode: str = "expand") -> JoinResult:
    """Join left (probe) with right (build). Column names must be disjoint
    except for semi/anti (which emit only left columns)."""
    return hash_join_prepared(left,
                              prepare_build(right, right_on, seed, mode),
                              left_on, right_on, how=how,
                              out_capacity=out_capacity)


def merge_join(left: Batch, right: Batch, left_on: Sequence[str],
               right_on: Sequence[str], how: str = "inner",
               out_capacity: int | None = None) -> JoinResult:
    """Equi-join when the BUILD side is already sorted on its single join
    key (reference NewMergeJoinOp, colexecjoin/mergejoiner.go:302). The
    hash join's build phase exists only to make equal keys adjacent — a
    key-sorted build already is, so this skips hashing AND the build sort:
    probe positions come from one co-sort search on the raw key values,
    run extents from adjacency. Multi-column keys or floats degrade to
    hash_join (the reference's merge joiner similarly restricts its fast
    cases and falls back per type).

    Precondition: right's selected rows are sorted ascending (NULLs
    anywhere — they never match). left need not be sorted.
    """
    if how not in JOIN_TYPES:
        raise ValueError(f"unknown join type {how}")
    lc = left.col(left_on[0]) if len(left_on) == 1 else None
    rc = right.col(right_on[0]) if len(right_on) == 1 else None
    if (lc is None or rc is None
            or jnp.issubdtype(lc.values.dtype, jnp.floating)
            or jnp.issubdtype(rc.values.dtype, jnp.floating)):
        return hash_join(left, right, left_on, right_on, how=how,
                         out_capacity=out_capacity)
    from cockroach_tpu.ops.search import run_ends

    sentinel = jnp.iinfo(jnp.int64).max
    rkey = rc.values.astype(jnp.int64)
    rkey = jnp.where(right.sel & rc.valid_mask(), rkey, sentinel)
    # live build rows are pre-sorted (the precondition); dead/NULL lanes
    # may interleave, so one defensive argsort restores a clean layout —
    # on pre-sorted data the bitonic network is cheap and this stays
    # strictly lighter than hash_join (no hashing of either side)
    order = jnp.argsort(rkey).astype(jnp.int32)
    rkey_sorted = rkey[order]
    lkey = lc.values.astype(jnp.int64)
    lkey = jnp.where(left.sel & lc.valid_mask(), lkey, sentinel - 1)
    return _probe_sorted(left, right, order, rkey_sorted,
                         run_ends(rkey_sorted), lkey, left_on, right_on,
                         how, out_capacity)


def hash_join_prepared(left: Batch, build: BuildTable,
                       left_on: Sequence[str], right_on: Sequence[str],
                       how: str = "inner",
                       out_capacity: int | None = None,
                       track_build: bool = False) -> JoinResult:
    """Probe a prepared build side. The probe hash seed comes from the
    BuildTable itself, so build and probe can never disagree.
    `track_build` forces the matched_build flags even for join types that
    do not need them per-batch (streaming right/full-outer joins consume
    them at end-of-stream)."""
    if how not in JOIN_TYPES:
        raise ValueError(f"unknown join type {how}")
    from cockroach_tpu.ops.sortjoin import UniqueBuild, probe_unique

    if isinstance(build, UniqueBuild):
        return probe_unique(left, build, tuple(left_on), how=how,
                            track_build=track_build)
    hl = hash_columns(left, left_on, seed=build.seed)
    return _probe_sorted(left, build.batch, build.order, build.hash_sorted,
                         build.run_end, hl, left_on, right_on, how,
                         out_capacity, track_build)


def scan64_lanes(build: BuildTable, probe_capacity: int, how: str) -> int:
    """Lanes hash_join_prepared passes through scans over a 64-bit
    operand (pairs of u32 on the chip) for this build: a unique build's
    run broadcasts (ops/sortjoin.scan64_lanes), else the expansion's
    int64 sum of match counts over the probe's lanes (_probe_sorted).
    What exec/fused counts as stage fused.join_scan64_lanes."""
    from cockroach_tpu.ops import sortjoin

    if isinstance(build, sortjoin.UniqueBuild):
        return sortjoin.scan64_lanes(build, probe_capacity, how)
    return probe_capacity


def _probe_sorted(left: Batch, right: Batch, order, key_sorted, run_end,
                  lq, left_on, right_on, how: str,
                  out_capacity: int | None,
                  track_build: bool = False) -> JoinResult:
    """Shared probe core: `key_sorted` is the build rows' comparable key
    (hash for hash_join, raw value for merge_join) in ascending order via
    permutation `order`; `lq` is each probe row's key in the same space.
    True-key equality verification downstream makes the key space only a
    candidate filter, never a correctness dependency."""
    lcap, rcap = left.capacity, right.capacity
    if out_capacity is None:
        out_capacity = max(lcap, rcap)

    from cockroach_tpu.ops.search import (
        counts_at_most, searchsorted_left_via_sort,
    )

    # ONE co-sort search gives lo; the prepared run extents give hi
    lo = searchsorted_left_via_sort(key_sorted, lq)
    at = jnp.minimum(lo, rcap - 1)
    found = key_sorted[at] == lq
    hi = jnp.where(found, run_end[at] + 1, lo)
    # int64 counters: a skewed many-to-many join can exceed 2^31 candidate
    # pairs; int32 would wrap, silently corrupting the ragged expansion and
    # masking the overflow flag
    counts = jnp.where(left.sel, (hi - lo).astype(jnp.int64), jnp.int64(0))

    cum = blocked_cumsum(counts)                   # inclusive
    total = cum[-1]

    out_rows = jnp.arange(out_capacity, dtype=jnp.int64)
    probe_of_out = counts_at_most(cum, out_capacity)
    probe_safe = jnp.minimum(probe_of_out, lcap - 1)
    prev_cum = jnp.where(probe_safe > 0, cum[jnp.maximum(probe_safe - 1, 0)], 0)
    j = out_rows - prev_cum
    in_range = out_rows < total
    build_pos = jnp.where(in_range, lo[probe_safe] + j.astype(jnp.int32), 0)
    build_row = order[jnp.minimum(build_pos, rcap - 1)]

    overflow = total > out_capacity

    # gather whole candidate rows ONCE per side (ops/rowmat.py cost
    # model: one (out,W) row gather ~= one 1-D gather; the per-column
    # formulation paid ~65 ms per column at 2M on v5e), then verify key
    # equality from the gathered values — no further gathers
    from cockroach_tpu.ops.rowmat import pack_rows, unpack_rows

    lmat, lplan = pack_rows(left)
    rmat, rplan = pack_rows(right)
    lrows = lmat[probe_safe]
    rrows = rmat[build_row]
    lcols_raw, lsel = unpack_rows(lrows, lplan)
    rcols_raw, rsel = unpack_rows(rrows, rplan)

    eq = jnp.ones(out_capacity, dtype=jnp.bool_)
    for ln, rn in zip(left_on, right_on):
        lc, rc = lcols_raw[ln], rcols_raw[rn]
        col_eq = lc.values == rc.values
        if jnp.issubdtype(lc.values.dtype, jnp.floating):
            col_eq |= jnp.isnan(lc.values) & jnp.isnan(rc.values)
        if lc.validity is not None:
            col_eq &= lc.validity
        if rc.validity is not None:
            col_eq &= rc.validity
        eq &= col_eq
    match = in_range & eq & lsel & rsel

    # per-probe/build matched flags (a scatter each) only where a join
    # type consumes them — inner joins skip both
    need_l = how in ("semi", "anti", "left", "outer")
    need_r = track_build or how in ("right", "outer")
    matched_l = matched_r = None
    if need_l:
        matched_l = jnp.zeros((lcap,), dtype=jnp.bool_)
        matched_l = matched_l.at[jnp.where(match, probe_safe, lcap)].max(
            True, mode="drop")
    if need_r:
        matched_r = jnp.zeros((rcap,), dtype=jnp.bool_)
        matched_r = matched_r.at[jnp.where(match, build_row, rcap)].max(
            True, mode="drop")

    if how == "semi":
        return JoinResult(left.filter(matched_l), overflow, matched_r)
    if how == "anti":
        return JoinResult(left.filter(left.sel & ~matched_l), overflow,
                          matched_r)

    def masked(cols_raw):
        return {n: Column(
            jnp.where(match, c.values, jnp.zeros((), c.values.dtype)),
            match if c.validity is None else (c.validity & match))
            for n, c in cols_raw.items()}

    cols = {}
    cols.update(masked(lcols_raw))
    cols.update(masked(rcols_raw))
    sel = match
    length = jnp.sum(match).astype(jnp.int32)
    pieces = [Batch(cols, sel, length)]

    if how in ("left", "outer"):
        unmatched = left.sel & ~matched_l
        rows = jnp.arange(lcap, dtype=jnp.int32)
        cols_l = {}
        cols_l.update(_null_columns(left, rows, unmatched))
        cols_l.update(_null_columns(right, jnp.zeros((lcap,), jnp.int32),
                                    jnp.zeros((lcap,), jnp.bool_)))
        pieces.append(Batch(cols_l, unmatched,
                            jnp.sum(unmatched).astype(jnp.int32)))

    if how in ("right", "outer"):
        unmatched = right.sel & ~matched_r
        rows = jnp.arange(rcap, dtype=jnp.int32)
        cols_r = {}
        cols_r.update(_null_columns(left, jnp.zeros((rcap,), jnp.int32),
                                    jnp.zeros((rcap,), jnp.bool_)))
        cols_r.update(_null_columns(right, rows, unmatched))
        pieces.append(Batch(cols_r, unmatched,
                            jnp.sum(unmatched).astype(jnp.int32)))

    if len(pieces) == 1:
        return JoinResult(pieces[0], overflow, matched_r)
    from cockroach_tpu.coldata.batch import concat_batches
    return JoinResult(concat_batches(pieces), overflow, matched_r)
