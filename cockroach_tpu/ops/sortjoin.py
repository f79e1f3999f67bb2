"""Unique-build equi-join: two NARROW sorts + one segmented scan + one
row-matrix gather.

Reference: pkg/sql/colexec/colexecjoin/hashjoiner.go:166 — the CPU hash
join's build/probe phases over a chained hash table. Round 3 replaced the
pointer-chasing probe with a co-sort binary search + ragged expansion
(ops/join.py) — correct, but the measured primitive costs on v5e are
upside-down for that plan: a 4M-lane random GATHER costs ~30 ms and a
SCATTER ~37 ms while a full 4M-lane sort costs ~9 ms, and — the real
killer — XLA compile time grows ~30-60 s per extra sort OPERAND at
multi-M lanes (the round-3 4M join microbench never finished compiling).
This module therefore keeps every sort as narrow as possible (one u64
key + one i32 iota) and moves whole rows exactly once:

  1. pack each row's join key and a build/probe tag bit into ONE uint64
     sort operand (raw biased value for single integer keys — exact, no
     collisions; 62-bit hash otherwise);
  2. lax.sort [build ++ probe] keyed on packed, carrying one value
     operand (a build lane's payload or row index, a probe lane's lane
     index). Equal keys become adjacent with the build row FIRST: the
     tag bit is part of the key, so the sort need not be stable for it,
     and none here is (PR 43: XLA's stable sort carries a tie-break
     operand of its own; where the order of a key's probe lanes is read,
     the value operand is the second key);
  3. one 3-leaf segmented scan broadcasts each run head's (is_build,
     source index) to the run ("take right if right starts a run" — the
     carry resets at every head, so no segment ids are needed). A probe
     lane is matched iff its run head is a build lane;
  4. a build lane that is NOT a run head means duplicate build keys (or
     a 62-bit hash collision): the deferred `fallback` flag tells the
     flow driver to restart the join in the general many-to-many mode
     (ops/join.py) — the same optimistic-fast-path/general-slow-path
     pairing as the reference's disk spiller (disk_spiller.go:208);
  5. back out of the sorted (key) domain, in one of two forms. The
     consumer decides which, and the plan shows the consumer:
     a. RESORT by each lane's DESTINATION index (probe lanes -> their own
        probe position; a permutation, so no ties), carrying
        (matched-build-row << 1 | match) as one i32 — lanes [0:lcap] land
        in probe order, probe columns never move. For every consumer
        that reads the probe's lane layout;
     b. COMPACT the matched probe lanes where they stand, in key order
        (probe_unique_compact): one single-operand u32 sort of lcap +
        rcap lanes puts the matches first (first_matches), and C-row
        gathers fetch their probe lane index and build ROW INDEX and
        (step 6) the rows of both sides. Step 3's broadcast is not
        needed here: the tag puts a run's build lane at its head, so one
        32-bit running maximum over the heads' positions tells a lane
        whether its run has one and where its row index stands
        (_carry_sort, PR 49). For an inner or semi join
        whose only reader is a ShrinkOp of capacity C
        (exec/fused._Tracer._mat_join): a selective join keeps a sliver
        of its probe, and the second full-width sort of (a) would
        restore an order that the Shrink discards one operator later
        (Q3 at SF1: 37.6 of 211 ms a statement, PERF.md section 6,
        PR 26). Here a build lane carries its row index, not its
        columns, through the key sort: they are wanted at C lanes, not
        at lcap, so their number and width ask nothing of the sort
        (Q18's last join builds on five columns, 91 bits: PR 34);
  6. ONE (lcap, W) row gather pulls each matched build row's columns
     from the build side's pre-packed row matrix (rowmat.pack_rows at
     prepare time) — a row gather costs the same as a 1-D gather. (The
     payload-carry build of round 5 needs none under form (a): its
     columns ride the sorts. Form (b) gathers C rows of the probe's
     columns and, for an inner join, C rows of the build's.)

Unique-build covers every FK->PK join TPC-H runs (the build side of
every flagship-query join is its primary key). Output capacity == probe
capacity: each probe row has at most one match, so there is no
expansion, no overflow, and downstream operators keep the probe's lane
layout. (Form 5b trades that layout for capacity C and an overflow flag,
which are the ShrinkOp's own.)
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from cockroach_tpu.coldata.batch import (
    Batch, Column, first_matches, first_selected,
)
from cockroach_tpu.ops.prefix import blocked_cummax
from cockroach_tpu.ops.rowmat import RowPlan, pack_rows, unpack_rows

# numpy scalars, NOT jnp: a module-level jax.Array closure constant gets
# hoisted to AOT const_args by jit, and the fused runner's direct
# Compiled.call then fails ("compiled for N inputs but called with M").
# numpy scalars embed as plain HLO constants.
_TOP = np.uint64(1 << 63)      # sentinel region (dead/NULL keys)
_BIAS = np.int64(1 << 61)      # int-key bias: [-2^61, 2^61) -> u62
_MASK62 = np.uint64((1 << 62) - 1)


class UniqueBuild(NamedTuple):
    """A build side prepared for the unique-key sort join.

    Round 5: int-keyed builds whose non-key columns bit-pack (ops/
    bitpack.py) carry them as ONE u64 sort value operand (`payv`/
    `pay_plan`) instead of a row matrix — the resorting join then moves
    build data exclusively through its two sorts and the row-matrix
    gather (the single largest device cost of r4 joins, ~30ms per 4M
    rows) disappears, while 62 bits hold the payload (`fallback`
    otherwise). The compacting join reads `batch` and the narrow
    `packed` keys of the same build and neither `payv` nor `pay_plan`.
    `mat` stays for the hash-kind/verification and
    matched-build-tracking paths."""

    batch: Batch
    packed: jnp.ndarray       # uint64 (rcap,): sortable packed key, tag=0
    mat: object               # (rcap, W) int64 row matrix, or None (carry)
    key_kind: str             # "int" (exact) | "hash" (verify via key cols)
    range_flag: jnp.ndarray   # bool: an int key fell outside [-2^61, 2^61)
    build_on: tuple           # key column names (hash-kind verification)
    plan: object              # static RowPlan layout, or None (carry)
    seed: int
    payv: object              # uint64 (rcap,) packed non-key payload | None
    pay_plan: object          # DynPack | None


# key_kind/build_on/plan/seed are STATIC metadata (they select trace-time
# code paths), so jitted functions can return a UniqueBuild: only
# batch/packed/mat/range_flag/payv/pay_plan are array leaves (DynPack is
# itself a pytree with its own static aux).
jax.tree_util.register_pytree_node(
    UniqueBuild,
    lambda ub: ((ub.batch, ub.packed, ub.mat, ub.range_flag, ub.payv,
                 ub.pay_plan),
                (ub.key_kind, ub.build_on, ub.plan, ub.seed)),
    lambda aux, children: UniqueBuild(
        children[0], children[1], children[2], aux[0], children[3],
        aux[1], aux[2], aux[3], children[4], children[5]))


def _int_key_col(batch: Batch, on: Sequence[str]):
    """The single integer key column, or None if keys need hashing."""
    if len(on) != 1:
        return None
    c = batch.col(on[0])
    if jnp.issubdtype(c.values.dtype, jnp.integer):
        return c
    return None


def _key_live(batch: Batch, on: Sequence[str]):
    """Live lanes whose key has no NULL: only these can ever match."""
    live = batch.sel
    for n in on:
        c = batch.col(n)
        if c.validity is not None:
            live = live & c.validity
    return live


def _pack_keys(batch: Batch, on: Sequence[str], tag: int, seed: int,
               kind: str, narrow: bool = False):
    """-> (packed keys, range_flag). Sentinel lanes (dead/NULL key) get
    per-lane keys in the top region: a dead probe lane can only pair with
    the same-index dead build lane, and the key-liveness guard kills that
    match downstream; distinct per-lane build sentinels can never look
    like duplicate build keys.

    `narrow` (carry path): pack into u32 — keys must sit in [0, 2^30)
    (every TPC-H key through SF100 does; violations raise range_flag and
    the restart ladder reverts to the u64 row-matrix path). A u32 key
    operand halves the dominant sort's bytes (r5 measured: the 8M join
    microbench sort is bandwidth-bound)."""
    cap = batch.capacity
    live = _key_live(batch, on)
    if kind == "int":
        kc = _int_key_col(batch, on)
        if kc is None:
            # build keyed "int" but this side's key is not a single
            # integer column: the hash path would not match such pairs
            # either (hash.py bitcasts floats, so int 2 and float 2.0
            # hash apart) — emit sentinels only, i.e. no matches
            live = jnp.zeros((cap,), jnp.bool_)
            v = jnp.zeros((cap,), jnp.int64)
        else:
            v = kc.values.astype(jnp.int64)
        if narrow:
            in_range = (v >= 0) & (v < np.int64(1 << 30))
            range_flag = jnp.any(live & ~in_range)
            u32 = jnp.clip(v, 0, (1 << 30) - 1).astype(jnp.uint32)
            packed = (u32 << np.uint32(1)) | np.uint32(tag)
            lane = jnp.arange(cap, dtype=jnp.uint32)
            sentinel = (np.uint32(1 << 31)
                        | (lane << np.uint32(1)) | np.uint32(tag))
            return jnp.where(live, packed, sentinel), range_flag
        in_range = (v >= -_BIAS) & (v < _BIAS)
        range_flag = jnp.any(live & ~in_range)
        u = jax.lax.bitcast_convert_type(v + _BIAS, jnp.uint64)
        packed = (u << np.uint64(1)) | np.uint64(tag)
    else:
        from cockroach_tpu.ops.hash import hash_columns

        h = hash_columns(batch, on, seed=seed)
        packed = ((h & _MASK62) << np.uint64(1)) | np.uint64(tag)
        range_flag = jnp.bool_(False)
    lane = jnp.arange(cap, dtype=jnp.uint32).astype(jnp.uint64)
    sentinel = _TOP | (lane << np.uint64(1)) | np.uint64(tag)
    return jnp.where(live, packed, sentinel), range_flag


def prepare_unique(build: Batch, build_on: Sequence[str],
                   seed: int = 0, carry: bool = True) -> UniqueBuild:
    from cockroach_tpu.ops import bitpack

    kind = "int" if _int_key_col(build, build_on) is not None else "hash"
    packed, range_flag = _pack_keys(build, build_on, 0, seed, kind)
    noncore = [n for n in build.columns if n not in build_on]
    if carry and kind == "int" and bitpack.packable(build, noncore):
        # payload-carry: key columns are synthesized from the probe key
        # on match, so only non-key columns ride the payload
        pay_plan = bitpack.plan_pack(build, noncore)
        payv = bitpack.pack_lanes(build, pay_plan)
        if build.capacity < (1 << 29):
            # u32 keys for the carry sorts (range-flagged; the ladder
            # reverts to unique-mat when keys exceed [0, 2^30))
            packed, range_flag = _pack_keys(build, build_on, 0, seed,
                                            kind, narrow=True)
        return UniqueBuild(build, packed, None, kind, range_flag,
                           tuple(build_on), None, seed, payv, pay_plan)
    mat, plan = pack_rows(build)
    return UniqueBuild(build, packed, mat, kind, range_flag,
                       tuple(build_on), plan, seed, None, None)


def _run_build_broadcast(newrun, is_build, perm):
    """-> (has_build, build_perm) per sorted lane: whether this lane's
    run contains a build lane, and that build lane's `perm` value.

    Implemented with NATIVE cumulative ops only: XLA compiles
    lax.cumsum/cummax to reduce-window in seconds, while a generic
    lax.associative_scan with a custom combine takes tens of MINUTES at
    multi-M lanes on TPU (measured round 4; it was the dominant compile
    cost of the round-3 engine). Encoding: runid is non-decreasing, so
    cummax of (runid << 32 | build_perm+1) can never leak a value across
    run boundaries — a later run's lanes dominate via the high bits."""
    runid = jnp.cumsum(newrun.astype(jnp.int32))
    enc = (runid.astype(jnp.int64) << np.int64(32)) | jnp.where(
        is_build, (perm + 1).astype(jnp.int64), np.int64(0))
    m = jax.lax.cummax(enc, axis=0)
    low = (m & np.int64(0xFFFFFFFF)).astype(jnp.int32)
    return low > 0, low - 1


class _CarrySorted(NamedTuple):
    """The carry join after its key sort and run broadcast, still in the
    sorted (key) domain: what both last steps (resort to probe order /
    compact the matches) start from."""

    s_packed: jnp.ndarray      # sorted packed keys, build ++ probe
    s_val: jnp.ndarray         # build lanes: payload; probe lanes: lane idx
    is_build: jnp.ndarray
    match_sorted: jnp.ndarray  # probe lane whose run starts with a build
    bpay: jnp.ndarray          # the run's build payload: uint64 packed
    #                            columns, or (rows=True) the int32 sorted
    #                            position of the run's head, whose s_val
    #                            is the build row where match_sorted
    fallback: jnp.ndarray


def _carry_sort(probe: Batch, ub: UniqueBuild, probe_on: Sequence[str],
                rows: bool = False, ordered: bool = False) -> _CarrySorted:
    """Steps 1-4 of the carry join: probe key packing, key sort, run
    detection, the deferred `fallback` flag and what each lane learns
    of its run's build lane (ONE copy, whatever step 5 does with it).
    What a build lane carries beside its key is one of two things:

    - its non-key columns bit-packed into one u64 (`ub.payv`): the form
      the resort needs, where every probe lane receives the columns. 62
      bits hold it or `fallback` is raised, and the broadcast is the
      split cummax (two 31-bit halves under the run id);
    - `rows`: its own ROW INDEX as a u32 (< rcap < 2^30, whatever the
      build's columns): the form the compaction takes, which fetches
      the columns from `ub.batch` at the C surviving lanes only. No
      width to overflow, and nothing to broadcast: ONE 32-bit running
      maximum of (position << 1 | is_build) over the run heads gives
      every lane its run's head, where the build lane stands if the run
      has one, and the caller reads the row there (`s_val` of the head)
      at its C lanes. A semi join reads the match alone.

    `ordered`: the caller reads the sorted domain's lane ORDER (the
    compacting inner join, whose result is in it), so lanes of equal
    keys must stand as a stable sort leaves them."""
    lcap, rcap = probe.capacity, ub.batch.capacity
    n = lcap + rcap
    p_packed, p_range = _pack_keys(
        probe, probe_on, 1, ub.seed, ub.key_kind,
        narrow=(ub.packed.dtype == jnp.uint32))
    packed = jnp.concatenate([ub.packed, p_packed])
    # value operand: build lanes carry their payload, probe lanes their
    # own lane index (the destination for the resort)
    lane = jnp.arange(lcap, dtype=jnp.uint32)
    if rows:
        val = jnp.concatenate([jnp.arange(rcap, dtype=jnp.uint32), lane])
    else:
        val = jnp.concatenate([ub.payv, lane.astype(jnp.uint64)])
    # The sort is unstable: XLA's stable sort carries a tie-break operand
    # of its own (7.8 ms of 22.9 at 8,388,608 lanes on a v5e:
    # scripts/price_sort_operands.py, PR 43), and nothing here needs it.
    # Ties are probe lanes of one key (or duplicate build keys:
    # `fallback`); the tag, part of the key, puts a key's build lane
    # first whatever the sort does with them. Their order reaches an
    # output only where the caller reads the sorted domain's lane order
    # (`ordered`): there (packed, val) is sorted as TWO keys, a total
    # key that IS the stable order (val rises with the row among build
    # lanes and with the lane among probe lanes), for 1.3-2.3 ms more
    # than one key. Otherwise every lane of a run receives the run's one
    # build payload (a cummax over ALL its build lanes) and each probe
    # lane carries its own lane index, so any order of a run will do.
    assert rows or not ordered  # a packed payload is no tie-break
    s_packed, s_val = jax.lax.sort(
        (packed, val), num_keys=2 if ordered else 1, is_stable=False)

    one = s_packed.dtype.type(1)  # u32 (narrow carry keys) or u64
    pos = jnp.arange(n, dtype=jnp.int32)
    prev_packed = jnp.concatenate([s_packed[:1], s_packed[:-1]])
    same_key = (s_packed >> one) == (prev_packed >> one)
    newrun = (pos == 0) | ~same_key
    is_build = (s_packed & one) == s_packed.dtype.type(0)
    dup = jnp.any(is_build & ~newrun)
    fallback = dup | ub.range_flag | p_range
    if rows:
        # the tag is the key's low bit, so a run that has a build lane
        # has it at its HEAD (a second one raises `dup`): each lane needs
        # its run head's position and tag, and position << 1 | tag rises
        # over the heads, so one 32-bit running maximum carries both
        # (n < 2^30 by `compacts`). The chip has no 64-bit lanes: the
        # (runid << 32 | row + 1) broadcast cost 15.7 ms at 8,650,752
        # lanes on a v5e where this costs 1.2 (PERF.md section 6, PR 49)
        head = blocked_cummax(jnp.where(
            newrun, (pos << 1) | is_build.astype(jnp.int32), 0))
        has_b = (head & 1) != 0
        return _CarrySorted(s_packed, s_val, is_build, ~is_build & has_b,
                            head >> 1, fallback)
    fallback = fallback | (ub.pay_plan.total_bits > jnp.int32(62))

    # broadcast the build payload to its run: split-cummax (62-bit
    # payload in two 31-bit halves; runid rides the high 32 bits so a
    # later run always dominates)
    runid = jnp.cumsum(newrun.astype(jnp.int32)).astype(jnp.int64)
    M31 = np.uint64(0x7FFFFFFF)
    M32 = np.int64(0xFFFFFFFF)
    lo31 = (s_val & M31).astype(jnp.int64)
    hi31 = (s_val >> np.uint64(31)).astype(jnp.int64)
    m1 = jax.lax.cummax((runid << np.int64(32))
                        | jnp.where(is_build, lo31 + 1, 0))
    m2 = jax.lax.cummax((runid << np.int64(32))
                        | jnp.where(is_build, hi31, 0))
    low1 = m1 & M32
    has_b = low1 > 0
    bpay = (jax.lax.bitcast_convert_type(low1 - 1, jnp.uint64)
            & M31) | (jax.lax.bitcast_convert_type(m2 & M32, jnp.uint64)
                      << np.uint64(31))
    return _CarrySorted(s_packed, s_val, is_build, ~is_build & has_b,
                        bpay, fallback)


def _probe_carry(probe: Batch, ub: UniqueBuild, probe_on: Sequence[str],
                 how: str):
    """Payload-carry probe: build columns ride the two sorts as one
    bit-packed u64 operand; NO row-matrix gather happens. Applies to
    int-keyed unique builds for inner/left/semi/anti without
    matched-build tracking."""
    from cockroach_tpu.ops import bitpack
    from cockroach_tpu.ops.join import JoinResult

    build = ub.batch
    lcap = probe.capacity
    cs = _carry_sort(probe, ub, probe_on)
    fallback = cs.fallback

    # resort by destination: probe lanes -> their own probe position,
    # build lanes -> past the probe span; payload rides as (bpay<<1|match)
    pos = jnp.arange(lcap + build.capacity, dtype=jnp.int32)
    dest = jnp.where(cs.is_build, jnp.int32(lcap) + pos,
                     cs.s_val.astype(jnp.int32))
    res = (cs.bpay << np.uint64(1)) | cs.match_sorted.astype(jnp.uint64)
    # dest is a permutation: no ties, so no stable tie-break to pay for
    _d, o_res = jax.lax.sort((dest, res), num_keys=1, is_stable=False)
    o_match = (o_res[:lcap] & np.uint64(1)) != 0
    o_bpay = o_res[:lcap] >> np.uint64(1)

    key_live = _key_live(probe, probe_on)
    match = o_match & key_live

    if how == "semi":
        return JoinResult(probe.with_sel(probe.sel & match), fallback,
                          None)
    if how == "anti":
        return JoinResult(probe.with_sel(probe.sel & ~match), fallback,
                          None)
    bcols = bitpack.unpack_lanes(o_bpay, ub.pay_plan, build,
                                 valid_and=match)
    # the build key equals the probe key on every matched lane: the
    # payload holds non-key columns only
    for pn, bn in zip(probe_on, ub.build_on):
        bdt = build.col(bn).values.dtype
        v = jnp.where(match, probe.col(pn).values.astype(bdt),
                      jnp.zeros((), bdt))
        bcols[bn] = Column(v, match)
    cols = dict(probe.columns)
    cols.update(bcols)
    sel = probe.sel if how == "left" else (probe.sel & match)
    return JoinResult(Batch(cols, sel, jnp.sum(sel).astype(jnp.int32)),
                      fallback, None)


class CompactJoin(NamedTuple):
    batch: Batch                # capacity C, matched rows first
    fallback: jnp.ndarray       # as JoinResult.overflow of probe_unique
    overflow: jnp.ndarray       # bool scalar: more matches than C


def carries(ub: UniqueBuild, probe_capacity: int, how: str,
            track_build: bool = False) -> bool:
    """Does probe_unique take the payload-carry path for this probe?"""
    return (ub.pay_plan is not None
            and how in ("inner", "left", "semi", "anti")
            and not track_build
            and probe_capacity + ub.batch.capacity < (1 << 30))


def compacts(ub: UniqueBuild, probe_capacity: int, how: str) -> bool:
    """Does probe_unique_compact take this probe? An inner or semi join
    over an int-kind key in the narrow u32 packing (prepare_unique's
    carry form). Nothing is asked of the build's other columns: they do
    not ride the sort."""
    return (ub.key_kind == "int" and ub.packed.dtype == jnp.uint32
            and how in ("inner", "semi")
            and probe_capacity + ub.batch.capacity < (1 << 30))


def scan64_lanes(ub: UniqueBuild, probe_capacity: int, how: str) -> int:
    """Lanes probe_unique passes through scans over a 64-bit operand
    (pairs of u32 on the chip: PERF.md section 6, PR 49): the resorting
    carry join broadcasts its payload with two s64 cummaxes
    (_carry_sort), the row-matrix join its build row with one
    (_run_build_broadcast), each over probe + build lanes. The
    compacting join (probe_unique_compact) runs none."""
    n = probe_capacity + ub.batch.capacity
    return 2 * n if carries(ub, probe_capacity, how) else n


def probe_unique_compact(probe: Batch, ub: UniqueBuild,
                         probe_on: Sequence[str], how: str,
                         capacity: int) -> CompactJoin:
    """probe_unique followed by a compaction to `capacity` lanes (what a
    ShrinkOp over the join computes), as ONE step that never restores
    probe order: the matched probe lanes are known in the sorted domain,
    so the destination resort is replaced by the compaction's own sort
    there (first_matches), one C-row gather of each match's probe lane
    index and run head, one C-lane gather of the build row index that
    stands at the head, and one C-row gather a side of the columns
    themselves. No scan of it has a 64-bit operand (the run heads come
    from one s32 running maximum: _carry_sort), and the build's columns
    never ride a sort, so their
    number and width are free. A semi join emits nothing
    of the build: its compaction carries the probe lane index itself and
    gathers probe rows only. Inner and semi joins over a build that
    `compacts` only.

    The lane order of an INNER join's result is a guarantee: live rows
    first (`sel` = lane < `length`), ascending in the join key, so rows
    of equal keys are adjacent. The matches are taken from the key
    sort's own domain in position order (first_matches over its
    positions), and `compacts` admits only the narrow packing (`key << 1
    | tag`: exact and monotone in the key; a key outside [0, 2^30)
    raises `fallback`). An aggregate grouped by the key (and columns of
    the unique build, functions of it) reads its groups off this order
    with no sort (exec/fused._Tracer._ordered_input,
    ops/agg.run_ends_aggregate). A raised `fallback` or `overflow`
    discards the result with the whole program's answer; the guarantee
    is asked of the rerun's join anew. A SEMI join's result is in
    probe-lane order (its compaction carries the lane index) and
    guarantees no key order."""
    from cockroach_tpu.coldata.batch import mask_padding

    if not compacts(ub, probe.capacity, how):
        raise ValueError(f"no compacting probe for a {how} join of this "
                         f"build")
    C = capacity
    cs = _carry_sort(probe, ub, probe_on, rows=True, ordered=how == "inner")
    # a sentinel probe lane (dead lane or NULL key: top bit) pairs with
    # the same-index build sentinel and is no match: the key-liveness
    # guard of the resorting form, taken in the sorted domain
    kdt = cs.s_packed.dtype
    top = kdt.type(1 << (kdt.itemsize * 8 - 1))
    match = cs.match_sorted & ((cs.s_packed & top) == kdt.type(0))
    n_match = jnp.sum(match).astype(jnp.int32)
    length = jnp.minimum(n_match, C).astype(jnp.int32)
    sel = jnp.arange(C) < length
    if how == "semi":
        lane = first_matches(match, cs.s_val, C)  # lane index < 2^30
    else:
        # one (C, 2) row gather: two 1-D gathers cost twice it
        kidx = first_selected(match, C)
        sv = cs.s_val.astype(jnp.int32)
        got = jnp.stack([sv, cs.bpay], axis=1)[kidx]
        lane = got[:, 0]
        # a build lane's value IS its row index: a match's build row is
        # the value at its run's head, read at the C lanes only
        brow = sv[got[:, 1]]
    cols = dict(probe.gather(lane, sel=sel, length=length).columns)
    if how == "inner":
        # the build's columns, its key among them, from its own lanes
        # (a matched lane is live, and its key the probe's), under the
        # join's NULL-padding contract
        for name, c in ub.batch.gather(brow).columns.items():
            valid = sel if c.validity is None else c.validity & sel
            cols[name] = Column(
                jnp.where(valid, c.values, jnp.zeros((), c.values.dtype)),
                valid)
    return CompactJoin(Batch(mask_padding(cols, sel), sel, length),
                       cs.fallback, n_match > C)


def probe_unique(probe: Batch, ub: UniqueBuild, probe_on: Sequence[str],
                 how: str = "inner", track_build: bool = False):
    """Join `probe` against a prepared unique build. Returns JoinResult
    (ops/join.py) whose batch capacity == probe.capacity. The overflow
    flag doubles as the fallback signal (duplicate build keys / hash
    collision / int key out of range / too-wide carry payload): the flow
    driver restarts the join through the general sort-expansion path."""
    from cockroach_tpu.ops.join import JoinResult

    build = ub.batch
    if carries(ub, probe.capacity, how, track_build):
        return _probe_carry(probe, ub, probe_on, how)
    if ub.mat is None:
        # carry-prepared build reached a path that needs the row matrix
        # (matched-build tracking, right/outer): build it here — inside
        # a fused program this costs the same as at prepare time. The
        # carry prep packs u32 keys; this path sorts u64, so repack.
        mat, plan = pack_rows(build)
        packed64, rflag = _pack_keys(build, ub.build_on, 0, ub.seed,
                                     ub.key_kind)
        ub = ub._replace(mat=mat, plan=plan, packed=packed64,
                         range_flag=rflag)
    lcap, rcap = probe.capacity, build.capacity
    n = lcap + rcap
    p_packed, p_range = _pack_keys(probe, probe_on, 1, ub.seed, ub.key_kind)

    packed = jnp.concatenate([ub.packed, p_packed])
    iota = jnp.arange(n, dtype=jnp.int32)
    # unstable, as _carry_sort's: ties are probe lanes of one key (a
    # second build lane of it raises `fallback`), each carrying its own
    # position, and every lane of a run receives the run's one build row
    s_packed, perm = jax.lax.sort((packed, iota), num_keys=1,
                                  is_stable=False)

    pos = iota
    prev_packed = jnp.concatenate([s_packed[:1], s_packed[:-1]])
    same_key = (s_packed >> np.uint64(1)) == (prev_packed >> np.uint64(1))
    newrun = (pos == 0) | ~same_key
    is_build = (s_packed & np.uint64(1)) == np.uint64(0)
    # a build lane that does not start a run follows an equal key: either
    # a duplicate build key or (hash kind) a 62-bit collision
    dup = jnp.any(is_build & ~newrun)
    fallback = dup | ub.range_flag | p_range

    has_build, build_perm = _run_build_broadcast(newrun, is_build, perm)
    match_sorted = ~is_build & has_build

    # destination: probe lanes -> their probe position [0, lcap), build
    # lanes -> lcap + row; carry (matched build row << 1 | match) as one
    # i32 payload so the resort needs no extra operands
    dest = jnp.where(perm < rcap, perm + jnp.int32(lcap),
                     perm - jnp.int32(rcap))
    brow_sorted = jnp.clip(build_perm, 0, rcap - 1)
    res_payload = (brow_sorted << jnp.int32(1)) | match_sorted.astype(
        jnp.int32)
    # dest is a permutation: no ties
    _d, o_payload = jax.lax.sort((dest, res_payload), num_keys=1,
                                 is_stable=False)
    o_match = (o_payload[:lcap] & jnp.int32(1)).astype(jnp.bool_)
    o_brow = o_payload[:lcap] >> jnp.int32(1)

    # hash kind: gather + compare the build key columns (collision ->
    # verified miss, which is exact: if the probe key WERE in the build,
    # the collision would have been two build lanes in one run -> dup)
    key_live = _key_live(probe, probe_on)
    match = o_match & key_live

    emit_build = how in ("inner", "left", "right", "outer")
    bcols = None
    if emit_build or ub.key_kind == "hash":
        rows = jnp.where(match, o_brow, 0)
        bcols, _bsel = unpack_rows(ub.mat[rows], ub.plan, valid_and=match)

    if ub.key_kind == "hash":
        verified = match
        for pn, bn in zip(probe_on, ub.build_on):
            pc = probe.col(pn)
            bc = bcols[bn]
            pvals, bvals = pc.values, bc.values
            if jnp.issubdtype(pvals.dtype, jnp.floating):
                # compare in float32 on BOTH sides: the row matrix
                # carries floats as f32 bits (rowmat.pack_rows), and the
                # expand path compares f32-roundtripped values of both
                # sides — full-precision probe vs narrowed build would
                # silently drop matches the expand path finds
                pvals = pvals.astype(jnp.float32)
                bvals = bvals.astype(jnp.float32)
                col_eq = (pvals == bvals) | (jnp.isnan(pvals)
                                             & jnp.isnan(bvals))
            else:
                if bvals.dtype != pvals.dtype:
                    bvals = bvals.astype(pvals.dtype)
                col_eq = pvals == bvals
            verified = verified & col_eq
        match = verified
        if emit_build and bcols is not None:
            # re-mask the gathered build columns by the verified match
            bcols = {
                nm: Column(
                    jnp.where(match, c.values, jnp.zeros((), c.values.dtype)),
                    match if c.validity is None else (c.validity & match))
                for nm, c in bcols.items()}

    matched_build = None
    if track_build or how in ("right", "outer"):
        brow = jnp.where(match, o_brow, jnp.int32(rcap))
        matched_build = jnp.zeros((rcap,), jnp.bool_).at[brow].max(
            True, mode="drop")

    if how == "semi":
        return JoinResult(probe.with_sel(probe.sel & match),
                          fallback, matched_build)
    if how == "anti":
        return JoinResult(probe.with_sel(probe.sel & ~match),
                          fallback, matched_build)

    if how in ("right", "outer"):
        # single-batch full semantics: lanes [0:lcap] carry the probe-side
        # output, lanes [lcap:] the unmatched build rows (NULL probe side).
        # Streaming right/outer never reaches here — the runtime probes
        # with the inner/left leg and emits unmatched build rows at EOS
        # from `matched_build`.
        cols = {}
        zb = jnp.zeros((rcap,), jnp.bool_)
        for nm, c in probe.columns.items():
            vals = jnp.concatenate(
                [c.values, jnp.zeros((rcap,), c.values.dtype)])
            valid = jnp.concatenate([c.valid_mask(), zb])
            cols[nm] = Column(vals, valid)
        tail_sel = build.sel & ~matched_build
        for nm, c in build.columns.items():
            mc = bcols[nm]
            vals = jnp.concatenate([mc.values, c.values])
            valid = jnp.concatenate(
                [mc.valid_mask(), c.valid_mask() & tail_sel])
            cols[nm] = Column(vals, valid)
        head_sel = probe.sel if how == "outer" else (probe.sel & match)
        sel = jnp.concatenate([head_sel, tail_sel])
        return JoinResult(
            Batch(cols, sel, jnp.sum(sel).astype(jnp.int32)),
            fallback, matched_build)

    cols = dict(probe.columns)
    cols.update(bcols)
    sel = probe.sel if how == "left" else (probe.sel & match)
    return JoinResult(Batch(cols, sel, jnp.sum(sel).astype(jnp.int32)),
                      fallback, matched_build)
