"""Dynamic lane bit-packing: carry several narrow columns as ONE sort
operand.

Why: on v5e the dominant cost of a sort-based join is not the sort but
moving the build side's PAYLOAD to matched probe lanes — the round-4
engine did it with a (rows, W) row-matrix gather (~30 ms per 4M rows,
latency-bound). If the payload columns fit in 63 bits they can instead
ride the join's existing sorts as the value operand: the sort moves them
at sequential-bandwidth cost and no gather ever happens.

Packing is DYNAMIC: per-column [lo, hi] are computed on device (cheap
reductions), widths are ceil(log2(span+1)) plus a validity bit for
nullable columns, and offsets are exclusive-summed — all traced values,
applied with variable-shift ops. Nothing depends on table statistics and
stale-stats hazards cannot exist; instead `total_bits > 63` raises a
DEFERRED flag and the flow driver reruns down the general path (the
optimistic/general pairing of disk_spiller.go:208).

Exactness: integers/dates/dict codes ride biased by their live minimum;
float32 rides as its raw 32 bits; bool as one bit. Every round trip is
bit-exact. The reference has no analog (CPU columnar stays columnar);
this is purely a TPU memory-system adaptation.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cockroach_tpu.coldata.batch import Batch, Column


class DynPack(NamedTuple):
    """Traced packing plan for a fixed (static) column list."""

    names: Tuple[str, ...]       # static: packed column order
    kinds: Tuple[str, ...]       # static: "int" | "f32" | "bool"
    nullable: Tuple[bool, ...]   # static: carries a validity bit
    los: jnp.ndarray             # (C,) int64 live minima (0 for f32/bool)
    widths: jnp.ndarray          # (C,) int32 value bit widths
    offsets: jnp.ndarray         # (C,) int32 exclusive bit offsets
    total_bits: jnp.ndarray      # scalar int32 (incl. validity bits)


jax.tree_util.register_pytree_node(
    DynPack,
    lambda p: ((p.los, p.widths, p.offsets, p.total_bits),
               (p.names, p.kinds, p.nullable)),
    lambda aux, ch: DynPack(aux[0], aux[1], aux[2], ch[0], ch[1], ch[2],
                            ch[3]))


def _col_kind(c: Column) -> str:
    dt = c.values.dtype
    if dt == jnp.bool_:
        return "bool"
    if jnp.issubdtype(dt, jnp.floating):
        return "f32" if dt.itemsize <= 4 else "wide"
    if jnp.issubdtype(dt, jnp.integer):
        return "int"
    return "wide"


def packable(batch: Batch, cols: Sequence[str]) -> bool:
    """Static check: every column's dtype can ride a packed lane."""
    return all(_col_kind(batch.col(n)) != "wide" for n in cols)


def plan_pack(batch: Batch, cols: Sequence[str]) -> DynPack:
    """Build the traced packing plan over `batch`'s LIVE lanes."""
    names, kinds, nullable = [], [], []
    los, widths = [], []
    live = batch.sel
    n_live = jnp.sum(live)
    for n in cols:
        c = batch.col(n)
        kind = _col_kind(c)
        assert kind != "wide", f"column {n} not packable"
        names.append(n)
        kinds.append(kind)
        nullable.append(c.validity is not None)
        if kind == "bool":
            los.append(jnp.int64(0))
            widths.append(jnp.int32(1))
        elif kind == "f32":
            los.append(jnp.int64(0))
            widths.append(jnp.int32(32))
        else:
            v = c.values.astype(jnp.int64)
            ok = live if c.validity is None else (live & c.validity)
            big = np.int64((1 << 62) - 1)
            lo = jnp.min(jnp.where(ok, v, big))
            hi = jnp.max(jnp.where(ok, v, -big - 1))
            any_ok = jnp.any(ok)
            lo = jnp.where(any_ok, lo, 0)
            hi = jnp.where(any_ok, hi, 0)
            span = (hi - lo).astype(jnp.uint64)
            # width = bits needed for span (0 when all-equal)
            w = jnp.where(span == 0, 0,
                          64 - jax.lax.clz(span).astype(jnp.int32))
            los.append(lo)
            widths.append(w.astype(jnp.int32))
    if not names:  # zero-column payload (e.g. COUNT(*)-only aggregates)
        z32 = jnp.zeros((0,), jnp.int32)
        return DynPack((), (), (), jnp.zeros((0,), jnp.int64), z32, z32,
                       jnp.int32(0))
    wid = jnp.stack(widths) + jnp.asarray(
        [1 if nb else 0 for nb in nullable], jnp.int32)
    offsets = jnp.cumsum(wid) - wid
    return DynPack(tuple(names), tuple(kinds), tuple(nullable),
                   jnp.stack(los), jnp.stack(widths), offsets,
                   jnp.sum(wid))


def pack_lanes(batch: Batch, plan: DynPack) -> jnp.ndarray:
    """(cap,) uint64 packed payload of the planned columns. Lanes whose
    value is NULL pack a 0 value + cleared validity bit; dead lanes pack
    garbage the consumer must mask via its own liveness."""
    cap = batch.capacity
    out = jnp.zeros((cap,), jnp.uint64)
    for i, (n, kind) in enumerate(zip(plan.names, plan.kinds)):
        c = batch.col(n)
        off = plan.offsets[i].astype(jnp.uint64)
        if kind == "bool":
            raw = c.values.astype(jnp.uint64)
        elif kind == "f32":
            raw = c.values.astype(jnp.float32).view(jnp.uint32) \
                .astype(jnp.uint64)
        else:
            biased = c.values.astype(jnp.int64) - plan.los[i]
            raw = jax.lax.bitcast_convert_type(biased, jnp.uint64)
            # mask to the allotted width: values outside [lo, hi] only
            # occur on dead/NULL lanes (or when the plan came from a
            # DIFFERENT batch, which overflow_flag covers)
            mask = jnp.where(
                plan.widths[i] >= 64, np.uint64(0xFFFFFFFFFFFFFFFF),
                (jnp.uint64(1) << plan.widths[i].astype(jnp.uint64))
                - np.uint64(1))
            raw = raw & mask
        if plan.nullable[i]:
            valid = c.validity.astype(jnp.uint64)
            raw = (raw << np.uint64(1)) | valid
        out = out | (raw << off)
    return out


def unpack_lanes(packed: jnp.ndarray, plan: DynPack, ref: Batch,
                 valid_and=None) -> Dict[str, Column]:
    """Columns back out of packed payloads. `ref` supplies the output
    dtypes. `valid_and` (bool mask) gates validity AND zeroes values on
    dead rows (the join NULL-padding contract)."""
    cols: Dict[str, Column] = {}
    for i, (n, kind) in enumerate(zip(plan.names, plan.kinds)):
        off = plan.offsets[i].astype(jnp.uint64)
        raw = packed >> off
        validity = None
        if plan.nullable[i]:
            validity = (raw & np.uint64(1)) != 0
            raw = raw >> np.uint64(1)
        mask = jnp.where(
            plan.widths[i] >= 64, np.uint64(0xFFFFFFFFFFFFFFFF),
            (jnp.uint64(1) << plan.widths[i].astype(jnp.uint64))
            - np.uint64(1))
        raw = raw & mask
        dt = ref.col(n).values.dtype
        if kind == "bool":
            v = raw != 0
        elif kind == "f32":
            v = raw.astype(jnp.uint32).view(jnp.float32).astype(dt)
        else:
            v = (jax.lax.bitcast_convert_type(raw, jnp.int64)
                 + plan.los[i]).astype(dt)
        if valid_and is not None:
            v = jnp.where(valid_and, v, jnp.zeros((), dt))
            validity = (valid_and if validity is None
                        else (validity & valid_and))
        cols[n] = Column(v, validity)
    return cols


def overflow_flag(plan: DynPack, budget: int = 63) -> jnp.ndarray:
    """Deferred flag: the packed payload does not fit `budget` bits."""
    return plan.total_bits > jnp.int32(budget)


# ------------------------------------------------------- HLC timestamps --
#
# The host-side Timestamp.pack() ((wall << 32) | logical) exceeds int64
# for real wall clocks (~2^60 ns shifted by 32), so device-resident MVCC
# version timestamps (storage/resident.py) ride a base-relative pack:
# wall biased by the table's base wall in the high bits, logical in the
# low TS_LOGICAL_BITS — the same bias-by-live-minimum trick DynPack uses
# for int lanes, statically sized so one int64 comparison is the full
# lexicographic (wall, logical) order.

TS_LOGICAL_BITS = 20
TS_WALL_BITS = 62 - TS_LOGICAL_BITS     # packed stays < 2^62 (int64-safe)
_TS_LOGICAL_MAX = (1 << TS_LOGICAL_BITS) - 1
_TS_WALL_SPAN = 1 << TS_WALL_BITS       # ~73 min of ns-resolution wall


class TsOverflow(Exception):
    """A version timestamp does not fit the base-relative pack (wall
    outside [base, base + 2^TS_WALL_BITS) or logical >= 2^TS_LOGICAL_BITS).
    The resident layer degrades to the host-walk tier on this."""


def ts_base(min_wall: int) -> int:
    """The pack base for a table whose smallest version wall is
    `min_wall`: biased low by half the representable span so moderately
    earlier explicit timestamps (tests, imports) still pack."""
    return max(0, int(min_wall) - (_TS_WALL_SPAN >> 1))


def pack_ts(wall: int, logical: int, base: int) -> int:
    """Exact int64 encoding of a VERSION timestamp relative to `base`;
    order-isomorphic to (wall, logical) for every in-range pair. Raises
    TsOverflow out of range."""
    delta = int(wall) - int(base)
    if not (0 <= delta < _TS_WALL_SPAN) or not (
            0 <= int(logical) <= _TS_LOGICAL_MAX):
        raise TsOverflow(
            f"timestamp ({wall},{logical}) outside base={base} pack range")
    return (delta << TS_LOGICAL_BITS) | int(logical)


def pack_ts_read(wall: int, logical: int, base: int) -> int:
    """Encode a READ timestamp for `<=` comparison against packed
    versions. Out-of-range reads clamp to sentinels that preserve the
    comparison outcome exactly, PROVIDED every version packed without
    overflow: a read below the base sees nothing (-1 < every packed
    version), a read past the span sees everything, and a clamped
    logical is >= every in-range logical at the same wall."""
    delta = int(wall) - int(base)
    if delta < 0:
        return -1
    if delta >= _TS_WALL_SPAN:
        return 1 << 62
    return (delta << TS_LOGICAL_BITS) | min(int(logical), _TS_LOGICAL_MAX)


def pack_ts_arrays(walls: np.ndarray, logicals: np.ndarray,
                   base: int) -> np.ndarray:
    """Vectorized pack_ts over version-timestamp arrays (delta ingest
    batches); raises TsOverflow when ANY element is out of range."""
    walls = np.asarray(walls, dtype=np.int64)
    logicals = np.asarray(logicals, dtype=np.int64)
    deltas = walls - np.int64(base)
    if len(walls) and (
            int(deltas.min()) < 0 or int(deltas.max()) >= _TS_WALL_SPAN
            or int(logicals.min()) < 0
            or int(logicals.max()) > _TS_LOGICAL_MAX):
        raise TsOverflow(f"timestamp batch outside base={base} pack range")
    return (deltas << np.int64(TS_LOGICAL_BITS)) | logicals
