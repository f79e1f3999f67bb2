"""Columnar batch format — the data currency of the execution engine.

Reference: pkg/col/coldata (batch.go:24 `Batch`, vec.go:44 `Vec`,
nulls.go:35 `Nulls`, bytes.go flat `Bytes`). The reference Batch is a slice
of typed vectors + a length + an optional selection vector, sized 1024 rows
(max 4096). This rebuild re-designs it TPU-first:

- A Batch is a **pytree of fixed-shape device arrays**: every column is a
  (capacity,) array, and instead of a selection *vector* (data-dependent
  length — hostile to XLA) we carry a boolean **selection mask** plus a
  dynamic `length` scalar. Kernels compute over all `capacity` lanes and
  mask; compaction happens only at shuffle boundaries (joins, collectives).
- Nulls are a boolean validity array per column (True = valid), matching
  Arrow semantics so host<->device interchange is zero-copy-shaped.
- Strings are dictionary codes (int32) on device; the dictionary itself
  lives host-side in the static Schema (reference analog: the fetch spec
  shipped inside scan requests, catalog/fetchpb).
- Decimals are int64-scaled integers (exact, TPU-friendly); dates are int32
  days since epoch. No float64 ever reaches the TPU.

Default capacity is 1<<16 rows: the reference tuned 1024 for CPU cache
(batch.go:81-85 cites MonetDB/X100); TPU batches amortize kernel dispatch
and want the VPU's 8x128 lanes saturated, so 16-64x larger (SURVEY.md
Appendix A).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Kind(enum.Enum):
    """Canonical type families (reference: col/typeconv)."""

    BOOL = "bool"
    INT = "int"          # int64
    FLOAT = "float"      # float32 on device
    DECIMAL = "decimal"  # int64 scaled by 10^scale
    DATE = "date"        # int32 days since unix epoch
    STRING = "string"    # int32 dictionary code
    TIMESTAMP = "timestamp"  # int64 nanos
    VECTOR = "vector"    # (capacity, d) float32 embedding


_DEVICE_DTYPES = {
    Kind.BOOL: jnp.bool_,
    Kind.INT: jnp.int64,
    Kind.FLOAT: jnp.float32,
    Kind.DECIMAL: jnp.int64,
    Kind.DATE: jnp.int32,
    Kind.STRING: jnp.int32,
    Kind.TIMESTAMP: jnp.int64,
    Kind.VECTOR: jnp.float32,
}


@dataclass(frozen=True)
class ColType:
    """A column's logical type. Hashable => usable in static (traced) context."""

    kind: Kind
    # DECIMAL: digits after the point; VECTOR: the dimension d. Reusing
    # one int field keeps ColType a two-slot frozen (hashable) dataclass.
    scale: int = 0

    @property
    def dtype(self):
        return _DEVICE_DTYPES[self.kind]

    @property
    def dim(self) -> int:
        """VECTOR dimension (the `d` of vector(d))."""
        return self.scale

    def lanes(self) -> int:
        """Device lanes per row: d for VECTOR columns, 1 otherwise."""
        return self.scale if self.kind is Kind.VECTOR else 1

    def __repr__(self):
        if self.kind is Kind.DECIMAL:
            return f"decimal(:{self.scale})"
        if self.kind is Kind.VECTOR:
            return f"vector({self.scale})"
        return self.kind.value


BOOL = ColType(Kind.BOOL)
INT = ColType(Kind.INT)
FLOAT = ColType(Kind.FLOAT)
DATE = ColType(Kind.DATE)
STRING = ColType(Kind.STRING)
TIMESTAMP = ColType(Kind.TIMESTAMP)


def DECIMAL(scale: int = 2) -> ColType:
    return ColType(Kind.DECIMAL, scale)


def VECTOR(dim: int) -> ColType:
    return ColType(Kind.VECTOR, dim)


@dataclass(frozen=True)
class Field:
    name: str
    type: ColType
    # For STRING columns: identity token of the host-side dictionary. Two
    # columns with the same dict_ref share a dictionary => their codes are
    # directly comparable (join/group on codes without re-encoding).
    dict_ref: Optional[str] = None
    # Optional narrow transport dtype (numpy dtype string, e.g. "i2"): the
    # host->device wire format when the producer guarantees all values fit.
    # The device unpack widens to the canonical device dtype. A cold scan
    # is bound by the host->device link, so wire width IS its rate — the
    # reference's analog is colserde choosing compact Arrow encodings for
    # FlowStream payloads (colserde/arrowbatchconverter.go:130).
    wire: Optional[str] = None
    # Nullable columns get a validity byte-lane in the packed wire format
    # (chunk key "<name>__valid") and a device-side validity mask — the
    # Arrow validity-bitmap analog (pkg/col/coldata/nulls.go).
    nullable: bool = False


class Schema:
    """Static (host-side) description of a Batch. Hashable for jit caching.

    The reference ships this as the fetch spec / ProcessorSpec column types
    (execinfrapb); here it also owns string dictionaries, keyed by dict_ref.
    """

    def __init__(self, fields: Sequence[Field], dicts: Optional[Dict[str, np.ndarray]] = None):
        self.fields: Tuple[Field, ...] = tuple(fields)
        self._by_name = {f.name: i for i, f in enumerate(self.fields)}
        # dict_ref -> numpy array of python str (the decode table)
        self.dicts: Dict[str, np.ndarray] = dicts or {}

    def __hash__(self):
        return hash(self.fields)

    def __eq__(self, other):
        return isinstance(other, Schema) and self.fields == other.fields

    def __iter__(self):
        return iter(self.fields)

    def __len__(self):
        return len(self.fields)

    def field(self, name: str) -> Field:
        return self.fields[self._by_name[name]]

    def index(self, name: str) -> int:
        return self._by_name[name]

    def names(self):
        return [f.name for f in self.fields]

    def dictionary(self, name: str) -> Optional[np.ndarray]:
        ref = self.field(name).dict_ref
        return self.dicts.get(ref) if ref else None

    def project(self, names: Sequence[str]) -> "Schema":
        fields = [self.field(n) for n in names]
        dicts = {f.dict_ref: self.dicts[f.dict_ref]
                 for f in fields if f.dict_ref and f.dict_ref in self.dicts}
        return Schema(fields, dicts)

    def extend(self, fields: Sequence[Field], dicts: Optional[Dict[str, np.ndarray]] = None) -> "Schema":
        d = dict(self.dicts)
        if dicts:
            d.update(dicts)
        return Schema(list(self.fields) + list(fields), d)

    def __repr__(self):
        return "Schema(" + ", ".join(f"{f.name}:{f.type}" for f in self.fields) + ")"


@jax.tree_util.register_pytree_node_class
class Column:
    """One typed device vector + validity (reference coldata.Vec, vec.go:44).

    validity is None when the column has no NULLs (the common case — mirrors
    the reference's `Nulls.MaybeHasNulls` fast path, nulls.go:35).
    """

    def __init__(self, values, validity=None):
        self.values = values
        self.validity = validity

    def tree_flatten(self):
        return (self.values, self.validity), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.values.shape[0]

    def valid_mask(self):
        if self.validity is None:
            return jnp.ones(self.values.shape[0], dtype=jnp.bool_)
        return self.validity

    def gather(self, idx) -> "Column":
        v = self.validity if self.validity is None else self.validity[idx]
        return Column(self.values[idx], v)

    def __repr__(self):
        n = "" if self.validity is None else ", nulls"
        return f"Column({self.values.dtype}[{self.values.shape[0]}]{n})"


@jax.tree_util.register_pytree_node_class
class Batch:
    """A pytree of columns + a selection mask (reference coldata.Batch).

    `sel` is a boolean mask over [0, capacity); `length` is the number of
    logical rows (== sel.sum() when all live rows are a prefix, but sel may
    be sparse after filters). Kernels must treat rows with sel==False as
    absent. The reference's int selection vector (batch.go Selection) trades
    exactly this: it compacts eagerly; we compact lazily at shuffle points
    to keep shapes static under jit.
    """

    def __init__(self, columns: Dict[str, Column], sel, length):
        self.columns = dict(columns)
        self.sel = sel
        self.length = length  # int32 scalar (dynamic under jit)

    def tree_flatten(self):
        names = tuple(self.columns.keys())
        children = tuple(self.columns[n] for n in names) + (self.sel, self.length)
        return children, names

    @classmethod
    def tree_unflatten(cls, names, children):
        cols = dict(zip(names, children[: len(names)]))
        sel, length = children[len(names):]
        return cls(cols, sel, length)

    # -- construction ------------------------------------------------------

    @staticmethod
    def from_columns(columns: Dict[str, Column]) -> "Batch":
        cap = next(iter(columns.values())).capacity
        return Batch(columns, jnp.ones(cap, dtype=jnp.bool_), jnp.int32(cap))

    # -- shape info --------------------------------------------------------

    @property
    def capacity(self) -> int:
        if self.columns:
            return next(iter(self.columns.values())).capacity
        return self.sel.shape[0]

    def names(self):
        return list(self.columns.keys())

    def col(self, name: str) -> Column:
        return self.columns[name]

    # -- transforms (all jit-safe) ----------------------------------------

    def with_sel(self, sel, length=None) -> "Batch":
        if length is None:
            length = jnp.sum(sel).astype(jnp.int32)
        return Batch(self.columns, sel, length)

    def filter(self, mask) -> "Batch":
        """Narrow the selection by an additional boolean mask."""
        sel = jnp.logical_and(self.sel, mask)
        return Batch(self.columns, sel, jnp.sum(sel).astype(jnp.int32))

    def project(self, names: Sequence[str]) -> "Batch":
        return Batch({n: self.columns[n] for n in names}, self.sel, self.length)

    def with_column(self, name: str, col: Column) -> "Batch":
        cols = dict(self.columns)
        cols[name] = col
        return Batch(cols, self.sel, self.length)

    def compact(self) -> "Batch":
        """Pack selected rows to the front (stable); rows past `length` are
        zero-filled and deselected. The shuffle-boundary materialization the
        reference does eagerly per-op via selection vectors."""
        cap = self.capacity
        out = self.gather(first_selected(self.sel, cap))
        new_sel = jnp.arange(cap) < self.length
        return Batch(mask_padding(out.columns, new_sel), new_sel,
                     self.length)

    def gather(self, idx, sel=None, length=None) -> "Batch":
        """Move whole rows to `idx` order. Multi-column batches route
        through ONE (rows, W) row-matrix gather (ops/rowmat.py): on v5e a
        1-D gather moves ~0.2 GB/s while a row gather moves the whole
        row set for the same cost — per-column gathers were the single
        largest device cost of round-3 queries (profiled r4)."""
        lossless = all(
            not (jnp.issubdtype(c.values.dtype, jnp.floating)
                 and c.values.dtype.itemsize > 4)
            and c.values.ndim == 1  # VECTOR (cap, d) columns: per-column
            for c in self.columns.values())
        # rowmat's packed-boolean lane holds <=64 bits (1 sel + up to 2
        # per column); very wide batches fall back to per-column gathers
        # (ADVICE r4: the assert used to hard-fail ~31+ column batches)
        bool_bits = 1 + sum(
            (2 if c.values.dtype == jnp.bool_ else
             (1 if c.validity is not None else 0))
            for c in self.columns.values())
        if len(self.columns) >= 2 and lossless and bool_bits <= 64:
            from cockroach_tpu.ops.rowmat import pack_rows, unpack_rows

            mat, plan = pack_rows(self)
            cols, gsel = unpack_rows(mat[idx], plan)
        else:
            cols = {n: c.gather(idx) for n, c in self.columns.items()}
            gsel = None
        if sel is None:
            sel = self.sel[idx] if gsel is None else gsel
        if length is None:
            length = jnp.sum(sel).astype(jnp.int32)
        return Batch(cols, sel, length)

    def __repr__(self):
        inner = ", ".join(f"{n}: {c!r}" for n, c in self.columns.items())
        return f"Batch[cap={self.capacity}]({inner})"


def full_sel(capacity: int):
    return jnp.ones(capacity, dtype=jnp.bool_)


_TOP32 = np.uint32(1 << 31)


def first_matches(match, carry, C: int):
    """-> (C,) int32: `carry` (uint32 under 2^31) of the first C lanes
    where `match`, in lane order, then of unmatched lanes in lane order
    (zeros past the lanes there are). ONE sort of a single u32 operand:
    the miss bit rides above each lane's carry, so no key needs a value
    operand beside it. Where `carry` rises with the lane (the lane index
    itself, a position) the key is unique and the result is what
    `argsort(~match, stable=True)[:C]` gathers, lane for lane, from one
    operand where that sort moves two and a tie-break: on v5e at
    8,388,608 lanes the argsort costs 26.4 ms standing alone and this
    sort 8.7 (scripts/price_sort_operands.py; PERF.md section 6). Every
    compaction of the served programs is this function: the compacting
    joins (ops/sortjoin.py), the int-key aggregate's run ends (ops/agg.py),
    ShrinkOp and Batch.compact."""
    key = jax.lax.sort(jnp.where(match, carry, carry | _TOP32),
                       is_stable=False)
    n = key.shape[0]
    key = key[:C] if n >= C else jnp.concatenate(
        [key, jnp.full((C - n,), _TOP32)])
    return (key & ~_TOP32).astype(jnp.int32)


def first_selected(sel, C: int):
    """-> (C,) int32: the lanes where `sel`, in lane order, then the
    others in lane order: `argsort(~sel, stable=True)[:C]` (lanes under
    2^31, which every batch has)."""
    return first_matches(sel, jnp.arange(sel.shape[0], dtype=jnp.uint32), C)


def mask_padding(columns: Dict[str, Column], sel) -> Dict[str, Column]:
    """Zero-fill values and clear validity on dead lanes so padding never
    leaks garbage into downstream hashes/collectives. The single source of
    the padding-hygiene invariant (used by compact(), agg, top-K)."""
    def _mask(c: Column) -> Column:
        # VECTOR columns are (capacity, d): broadcast sel over the lanes
        s = sel if c.values.ndim == 1 else sel[:, None]
        return Column(
            jnp.where(s, c.values, jnp.zeros((), c.values.dtype)),
            None if c.validity is None else jnp.logical_and(c.validity, sel),
        )

    return {n: _mask(c) for n, c in columns.items()}


def batch_shardings(batch: Batch, mesh, row_axis: str):
    """Pytree of shardings for `jax.device_put(batch, ...)`: row-sharded
    columns/sel along `row_axis`, replicated scalar `length`.

    Needed because Batch mixes rank-1 leaves with the rank-0 length — a
    single PartitionSpec can't cover both. This is the P1/P2 data layout
    (SURVEY.md §2.9): each device holds a contiguous row shard.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    rows = NamedSharding(mesh, PartitionSpec(row_axis))
    repl = NamedSharding(mesh, PartitionSpec())
    return jax.tree_util.tree_map(
        lambda leaf: repl if jnp.ndim(leaf) == 0 else rows, batch
    )


def concat_batches(batches: Sequence[Batch], schemas: Optional[Sequence["Schema"]] = None) -> Batch:
    """Concatenate along rows.

    All batches must share column names/dtypes AND, for STRING columns,
    the same dictionary — codes are merged verbatim, so concatenating
    columns encoded against different dictionaries silently corrupts
    data. Pass `schemas` to have this checked (dict_refs must match);
    inside a single flow all batches of a stream share one Schema, so
    internal callers satisfy this by construction.
    """
    if schemas is not None:
        first = schemas[0]
        for s in schemas[1:]:
            for f0, f1 in zip(first.fields, s.fields):
                if f0.dict_ref != f1.dict_ref or (
                    f0.dict_ref and s.dicts.get(f1.dict_ref) is not first.dicts.get(f0.dict_ref)
                ):
                    raise ValueError(
                        f"concat_batches: column {f0.name!r} encoded against "
                        f"different dictionaries; re-encode before concat"
                    )
    names = batches[0].names()
    cols = {}
    for n in names:
        vals = jnp.concatenate([b.columns[n].values for b in batches])
        vs = [b.columns[n].validity for b in batches]
        if all(v is None for v in vs):
            validity = None
        else:
            validity = jnp.concatenate([
                b.columns[n].valid_mask() for b in batches
            ])
        cols[n] = Column(vals, validity)
    sel = jnp.concatenate([b.sel for b in batches])
    length = sum((b.length for b in batches), start=jnp.int32(0))
    return Batch(cols, sel, length)
