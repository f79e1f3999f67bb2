"""Fused group-join: ONE sort performs an FK->PK equi-join AND the
GROUP BY that keys on the join column.

The flagship TPC-H shapes (Q3, Q18) aggregate the probe side GROUPED BY
the join key (plus build columns, which a unique build makes
functionally dependent on it). The round-4 engine ran join and
aggregation as separate sort pipelines — two key sorts, a destination
resort, a row-matrix gather, then the aggregation's own sort. But after
the join's [build ++ probe] key sort, lanes of one group are ALREADY
adjacent: the aggregation happens right there as segmented-cumsum
differences at run ends. Measured on v5e: Q3 SF1 warm 1.14s -> 0.22s
(0.19x -> 0.99x numpy); SF10 Q3 2.2-3.0x, Q1 via the sibling
int_key_aggregate 31x.

Pipeline (all native cum-ops; no scatters, no probe-side row gathers):
  1. pack (key - min_key) << 1 | side into ONE u32 (u64 on retry) sort
     key; dead/NULL-key lanes get top-region sentinels tagged as probe
     so they can never look like duplicate build keys;
  2. lax.sort [(key, value)], unstable (integer sums and counts never
     read the order of a run's probe lanes, so XLA's tie-break operand
     is not paid for) — build lanes carry their ROW INDEX as the
     value, probe lanes their packed aggregate inputs (disjoint lane
     sets share the operand; ops/bitpack.py);
  3. runid = cumsum(new-run); ONE narrow cummax broadcasts (has_build,
     build row index) to each run — a row index always fits 31 bits,
     so no payload-width ladder exists;
  4. per aggregate: extract input bits, segmented sums via cumsum;
  5. one single-operand u32 sort (coldata/batch.first_selected: the
     miss bit above the lane index) compacts matched run-END lanes to
     the group capacity; adjacent-end cumsum differences yield exact
     group sums/counts (between two matched ends every contribution is
     zero), and build GROUP COLUMNS gather from the build batch at just
     those <= out_capacity ends.

Deferred flags (the optimistic/general pairing, disk_spiller.go:208):
duplicate build keys / key or aggregate-input width overflows -> rerun
wide, then down the general JoinOp+HashAggOp path; group-capacity
overflow -> rerun with a doubled capacity. Reference:
colexecjoin/hashjoiner.go:166 + hash_aggregator.go:62 collapsed into
one kernel — a TPU-only fusion the CPU engine has no analog for.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cockroach_tpu.coldata.batch import Batch, Column, first_selected
from cockroach_tpu.ops.agg import AggSpec
from cockroach_tpu.ops.bitpack import pack_lanes, plan_pack

GJ_FUNCS = ("sum", "count", "count_star")


class GroupJoinResult(NamedTuple):
    batch: Batch           # group rows at `out_capacity` lanes
    fallback: jnp.ndarray  # bool: rerun via the general join+agg path
    overflow: jnp.ndarray  # bool: rerun with a larger out_capacity


def _key_i64(batch: Batch, col: str):
    c = batch.col(col)
    live = batch.sel
    if c.validity is not None:
        live = live & c.validity
    return c.values.astype(jnp.int64), live


def _shift1(x):
    return jnp.concatenate([x[:1], x[:-1]])


def int_key_aggregate(
    batch: Batch, key_col: str, aggs: Sequence[AggSpec],
    out_capacity: int = 0, key64: bool = False,
) -> GroupJoinResult:
    """GROUP BY a single integer column without hashing, permutation
    gathers, or an inverse sort: sort (biased key, packed agg inputs)
    directly, then segmented sums as cumsum differences.

    The general path (ops/hashtable.sorted_groups + ops/agg) pays
    argsort(hash) + argsort(perm) + TWO full random key gathers + one
    gather per aggregate input — ~400ms for Q18's 6M-row first
    aggregation on v5e. Here the key and inputs RIDE the one sort.

    out_capacity == 0 returns the UNCOMPACTED run-ends view: a batch at
    input capacity whose sel marks one lane per group — the right shape
    when a selective filter/shrink follows (Q18's HAVING). Per-group
    totals use that cumsums of bias-packed (non-negative) inputs are
    non-decreasing: the previous group end's running value arrives via
    one cummax + lane shift. A NULL key forms its own single group
    (SQL GROUP BY semantics)."""
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
    # their order reaches no output. GJ_FUNCS are sums and counts in
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
            else:
                i = aplan.names.index(a.col)
                s = seg_total(jnp.cumsum(jnp.where(avalid, v, 0)))
                sums.append((a, s + nv * aplan.los[i], nv, None))
    for a, tot, nv, _ in sums:
        if a.func == "sum":
            cols[a.out] = Column(jnp.where(nv > 0, tot, 0), nv > 0)
        else:
            cols[a.out] = Column(tot, None)

    n_groups = jnp.sum(is_end)
    fallback = key_flag | agg_flag
    if not out_capacity:
        out = Batch(cols, is_end, n_groups.astype(jnp.int32))
        return GroupJoinResult(out, fallback, jnp.bool_(False))
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
    return GroupJoinResult(out, fallback, n_groups > C)


def group_join_aggregate(
    probe: Batch, build: Batch,
    probe_on: str, build_on: str,
    key_out: str, key_dtype,
    build_cols: Sequence[str],
    aggs: Sequence[AggSpec],
    out_capacity: int,
    key64: bool = False,
    wide_payload: bool = False,
    payload_ops: int = 1,
) -> GroupJoinResult:
    """Inner-join `probe` with unique-keyed `build` on single integer
    columns and aggregate probe rows grouped by the key (+`build_cols`).
    `aggs` are internal specs (sum/count/count_star over probe columns).

    Build lanes carry their ROW INDEX as the sort's value operand (not
    packed column bits): the output is only `out_capacity` compacted
    group rows, so build columns gather from the build batch at the run
    ENDS (<= out_capacity tiny gathers) instead of riding the multi-M
    lane sort — the r5.1 simplification that removed the payload-width
    ladder (one narrow cummax broadcasts the row index; wide mode is
    only ever needed for the KEY and for >31-bit aggregate inputs)."""
    lcap, rcap = probe.capacity, build.capacity
    n = lcap + rcap
    bk, blive = _key_i64(build, build_on)
    pk, plive = _key_i64(probe, probe_on)

    # ---- dynamic key bias + static-width check -------------------------
    big = np.int64((1 << 62) - 1)
    klo = jnp.minimum(jnp.min(jnp.where(blive, bk, big)),
                      jnp.min(jnp.where(plive, pk, big)))
    khi = jnp.maximum(jnp.max(jnp.where(blive, bk, -big - 1)),
                      jnp.max(jnp.where(plive, pk, -big - 1)))
    any_live = jnp.any(blive) | jnp.any(plive)
    klo = jnp.where(any_live, klo, 0)
    key_budget = 62 if key64 else 30
    key_flag = any_live & ((khi - klo) >= (jnp.int64(1) << key_budget))

    kdt = jnp.uint64 if key64 else jnp.uint32
    TOP = kdt(1) << (np.uint32(63) if key64 else np.uint32(31))
    bb = jax.lax.bitcast_convert_type(
        jnp.clip(bk - klo, 0, jnp.int64(1) << key_budget), jnp.uint64)
    pb = jax.lax.bitcast_convert_type(
        jnp.clip(pk - klo, 0, jnp.int64(1) << key_budget), jnp.uint64)
    sent = TOP | kdt(1)
    gk_b = jnp.where(blive, (bb.astype(kdt) << kdt(1)), sent)
    gk_p = jnp.where(plive, (pb.astype(kdt) << kdt(1)) | kdt(1), sent)

    # ---- value operand: build row index | packed aggregate inputs ------
    # (disjoint lane sets share one operand; wide mode widens it for
    # >31-bit agg inputs)
    agg_cols: List[str] = []
    for a in aggs:
        if a.col is not None and a.col not in agg_cols:
            agg_cols.append(a.col)
    aplan = plan_pack(probe, agg_cols)
    apayv = pack_lanes(probe, aplan)
    agg_budget = 62 if wide_payload else 31
    agg_flag = aplan.total_bits > jnp.int32(agg_budget)
    pay_flag = jnp.bool_(False)  # row-index payload: no width hazard

    vdt = jnp.uint64 if wide_payload else jnp.uint32
    gk = jnp.concatenate([gk_b, gk_p])
    gv = jnp.concatenate([jnp.arange(rcap, dtype=jnp.uint32).astype(vdt),
                          apayv.astype(vdt)])
    # unstable: the tag bit, part of the key, leads each run with its
    # build lane; ties are probe lanes of one key (int64 sums and
    # counts read at run ends: their order reaches no output), dead
    # lanes, or duplicate build keys (`dup_flag`: the result is discarded)
    sgk, sgv = jax.lax.sort((gk, gv), num_keys=1, is_stable=False)
    sgv = sgv.astype(jnp.uint64)

    # ---- runs + broadcast of the build ROW INDEX ----------------------
    prev = jnp.concatenate([sgk[:1] | kdt(1), sgk[:-1]])
    newrun = (sgk >> kdt(1)) != (prev >> kdt(1))
    newrun = newrun.at[0].set(True)
    live_lane = sgk < TOP
    is_b = ((sgk & kdt(1)) == 0) & live_lane
    dup_flag = jnp.any(is_b & ~newrun)
    runid = jnp.cumsum(newrun.astype(jnp.int32)).astype(jnp.int64)
    M32 = np.int64(0xFFFFFFFF)
    # one narrow cummax ALWAYS suffices: the payload is a row index
    # (< 2^31 by construction), never packed column bits
    enc = (runid << np.int64(32)) | jnp.where(
        is_b, jax.lax.bitcast_convert_type(sgv, jnp.int64) + 1, 0)
    m = jax.lax.cummax(enc)
    low = m & M32
    has_b = low > 0
    brow = low - 1  # build row per run (valid where has_b)
    matched = has_b & ~is_b & live_lane

    # ---- segmented aggregation via cumsum ------------------------------
    cums: List[jnp.ndarray] = []   # one per agg, in spec order
    cnt_all = jnp.cumsum(matched.astype(jnp.int64))
    for a in aggs:
        if a.func == "count_star":
            cums.append(cnt_all)
            continue
        i = aplan.names.index(a.col)
        off = aplan.offsets[i].astype(jnp.uint64)
        raw = sgv >> off
        avalid = matched
        if aplan.nullable[i]:
            avalid = matched & ((raw & np.uint64(1)) != 0)
            raw = raw >> np.uint64(1)
        mask = jnp.where(
            aplan.widths[i] >= 64, np.uint64(0xFFFFFFFFFFFFFFFF),
            (jnp.uint64(1) << aplan.widths[i].astype(jnp.uint64))
            - np.uint64(1))
        v = jax.lax.bitcast_convert_type(raw & mask, jnp.int64)
        # non-nullable inputs: the valid-count cumsum IS cnt_all —
        # reuse it (one ~67M-lane cumsum saved per aggregate)
        cnt_cum = (jnp.cumsum(avalid.astype(jnp.int64))
                   if aplan.nullable[i] else cnt_all)
        if a.func == "count":
            cums.append(cnt_cum)
        else:  # sum of biased values + bias * count afterwards
            cums.append(jnp.stack([
                jnp.cumsum(jnp.where(avalid, v, 0)), cnt_cum], axis=0))

    # ---- compact matched run-END lanes ---------------------------------
    nxt = jnp.concatenate([newrun[1:], jnp.ones((1,), jnp.bool_)])
    is_end = nxt & matched
    C = out_capacity
    top = first_selected(is_end, C)
    n_ends = jnp.sum(is_end)
    valid = jnp.arange(C) < n_ends
    overflow = n_ends > C

    e_key = ((sgk[top] >> kdt(1)).astype(jnp.int64) + klo)

    def ends_diff(c):
        e = c[top]
        p = jnp.concatenate([jnp.zeros((1,), c.dtype), e[:-1]])
        return jnp.where(valid, e - p, 0)

    cols: Dict[str, Column] = {}
    kv = e_key.astype(key_dtype)
    kv = jnp.where(valid, kv, jnp.zeros((), key_dtype))
    cols[key_out] = Column(kv, None)
    # build columns: <= out_capacity tiny gathers from the build batch
    # (the row-index payload made carrying them through the sort
    # unnecessary)
    e_brow = jnp.clip(jnp.where(valid, brow[top], 0), 0, rcap - 1) \
        .astype(jnp.int32)
    for nme in build_cols:
        c = build.col(nme)
        v = jnp.where(valid, c.values[e_brow],
                      jnp.zeros((), c.values.dtype))
        vy = valid if c.validity is None else (c.validity[e_brow] & valid)
        cols[nme] = Column(v, vy)
    for a, c in zip(aggs, cums):
        if a.func in ("count", "count_star"):
            cols[a.out] = Column(ends_diff(c), None)
        else:
            i = aplan.names.index(a.col)
            s = ends_diff(c[0])
            cnt = ends_diff(c[1])
            sv = s + cnt * aplan.los[i]
            # SQL: SUM over zero non-NULL inputs is NULL
            cols[a.out] = Column(jnp.where(cnt > 0, sv, 0), cnt > 0)

    out = Batch(cols, valid, jnp.minimum(n_ends, C).astype(jnp.int32))
    fallback = key_flag | pay_flag | agg_flag | dup_flag
    return GroupJoinResult(out, fallback, overflow)
