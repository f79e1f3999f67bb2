"""Arrow <-> device-batch conversion.

Reference: pkg/col/colserde (arrowbatchconverter.go:48 `ArrowBatchConverter`,
`BatchToArrow` :130, `ArrowToBatch` :409). Arrow is the host<->host and
host<->device interchange format, exactly as in the reference where every
remote flow stream carries Arrow IPC record batches (colrpc/outbox.go:59-99).

The TPU twist: strings are dictionary-encoded at conversion time (pyarrow
does the heavy lifting) so only int32 codes ship to the device; dictionaries
stay in the Schema. Decimal128 narrows to int64-scaled (reference coldataext
falls back to slow datum vecs for decimals — we instead bound precision to
what int64 holds, which covers TPC-H and exactly matches its semantics).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

import jax.numpy as jnp

from cockroach_tpu.coldata.batch import (
    Batch,
    ColType,
    Column,
    Field,
    Kind,
    Schema,
)


def _coltype_of_arrow(t: pa.DataType) -> ColType:
    if pa.types.is_dictionary(t):
        # Only string dictionaries keep their codes; other dictionary
        # value types are decoded to plain arrays by the caller.
        if pa.types.is_string(t.value_type) or pa.types.is_large_string(t.value_type):
            return ColType(Kind.STRING)
        return _coltype_of_arrow(t.value_type)
    if pa.types.is_boolean(t):
        return ColType(Kind.BOOL)
    if pa.types.is_integer(t):
        return ColType(Kind.INT)
    if pa.types.is_floating(t):
        return ColType(Kind.FLOAT)
    if pa.types.is_decimal(t):
        return ColType(Kind.DECIMAL, t.scale)
    if pa.types.is_date(t):
        return ColType(Kind.DATE)
    if pa.types.is_timestamp(t):
        return ColType(Kind.TIMESTAMP)
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        return ColType(Kind.STRING)
    raise NotImplementedError(f"arrow type {t} not supported")


def _np_dtype(ct: ColType):
    # Single source of truth: the device dtype table in batch.py (jnp
    # dtypes are numpy dtypes under x64 mode).
    from cockroach_tpu.coldata.batch import _DEVICE_DTYPES

    return np.dtype(_DEVICE_DTYPES[ct.kind])


def _decimal_to_int64(arr: pa.Array, scale: int) -> np.ndarray:
    """Vectorized decimal128 -> int64-scaled decode.

    Reads the low 8 bytes of each 16-byte little-endian decimal128 word —
    exact whenever the scaled value fits int64, which our ColType contract
    guarantees (values beyond int64 raise at the cast below). Avoids the
    per-row Python Decimal loop on the ingest hot path.
    """
    if (not pa.types.is_decimal128(arr.type) or arr.type.scale != scale
            or arr.type.precision < 38):
        # normalizes decimal256 too; the cast raises on true int64 overflow
        arr = arr.cast(pa.decimal128(38, scale))
    buf = arr.buffers()[1]
    words = np.frombuffer(buf, dtype="<i8")
    lo = words[arr.offset * 2 : (arr.offset + len(arr)) * 2 : 2]
    hi = words[arr.offset * 2 + 1 : (arr.offset + len(arr)) * 2 + 1 : 2]
    # values fitting int64 have hi == sign-extension of lo
    valid_mask = ~arr.is_null().to_numpy(zero_copy_only=False)
    if not np.array_equal(hi[valid_mask], (lo >> 63)[valid_mask]):
        raise OverflowError("decimal value exceeds int64-scaled range")
    return np.where(valid_mask, lo, 0).astype(np.int64)


def _pad(arr: np.ndarray, capacity: int) -> np.ndarray:
    n = arr.shape[0]
    if n == capacity:
        return arr
    out = np.zeros((capacity,) + arr.shape[1:], dtype=arr.dtype)
    out[:n] = arr
    return out


def arrow_to_batch(
    rb: pa.RecordBatch,
    capacity: Optional[int] = None,
    dict_prefix: str = "",
):
    """Convert a pyarrow RecordBatch into a device Batch + Schema.

    Rows beyond rb.num_rows (up to `capacity`) are zero-padded and masked
    out via the selection mask — the static-shape analog of the reference's
    variable batch length.
    """
    n = rb.num_rows
    capacity = capacity or n
    assert capacity >= n, (capacity, n)

    fields = []
    dicts: Dict[str, np.ndarray] = {}
    cols: Dict[str, Column] = {}

    for i, f in enumerate(rb.schema):
        arr = rb.column(i)
        ct = _coltype_of_arrow(f.type)
        dict_ref = None

        if pa.types.is_dictionary(arr.type) and ct.kind is not Kind.STRING:
            arr = arr.cast(arr.type.value_type)  # decode non-string dicts

        if ct.kind is Kind.STRING:
            if not pa.types.is_dictionary(arr.type):
                arr = arr.dictionary_encode()
            dict_ref = dict_prefix + f.name
            dicts[dict_ref] = np.asarray(arr.dictionary.to_pylist(), dtype=object)
            indices = arr.indices
            null_mask = indices.is_null().to_numpy(zero_copy_only=False)
            if null_mask.any():
                indices = indices.fill_null(0)
            np_vals = indices.to_numpy(zero_copy_only=False).astype(np.int32)
        elif ct.kind is Kind.DECIMAL:
            null_mask = arr.is_null().to_numpy(zero_copy_only=False)
            np_vals = _decimal_to_int64(arr, ct.scale)
        else:
            null_mask = arr.is_null().to_numpy(zero_copy_only=False)
            if null_mask.any():
                zero = False if pa.types.is_boolean(arr.type) else 0
                arr = arr.fill_null(pa.scalar(zero, type=arr.type))
            np_vals = arr.to_numpy(zero_copy_only=False).astype(_np_dtype(ct))

        values = jnp.asarray(_pad(np_vals, capacity))
        validity = None
        if null_mask.any():
            validity = jnp.asarray(_pad(~null_mask, capacity))
        cols[f.name] = Column(values, validity)
        fields.append(Field(f.name, ct, dict_ref))

    sel = jnp.arange(capacity) < n
    batch = Batch(cols, sel, jnp.int32(n))
    return batch, Schema(fields, dicts)


def batch_to_arrow(batch: Batch, schema: Schema) -> pa.RecordBatch:
    """Convert a device Batch back to a (compacted) pyarrow RecordBatch."""
    sel = np.asarray(batch.sel)
    arrays = []
    names = []
    for f in schema:
        if f.name not in batch.columns:
            continue
        col = batch.columns[f.name]
        vals = np.asarray(col.values)[sel]
        valid = None if col.validity is None else np.asarray(col.validity)[sel]
        mask = None if valid is None else ~valid

        if f.type.kind is Kind.STRING:
            d = schema.dicts.get(f.dict_ref) if f.dict_ref else None
            if d is not None:
                decoded = pa.DictionaryArray.from_arrays(
                    pa.array(vals, type=pa.int32(), mask=mask),
                    pa.array(list(d), type=pa.string()),
                )
                arrays.append(decoded.cast(pa.string()))
            else:
                arrays.append(pa.array(vals, type=pa.int32(), mask=mask))
        elif f.type.kind is Kind.DECIMAL:
            # Emit the exact scaled-int64 representation; the SQL result
            # encoder re-applies the scale when rendering to clients.
            arrays.append(pa.array(vals, type=pa.int64(), mask=mask))
        else:
            pa_type = {
                Kind.BOOL: pa.bool_(),
                Kind.INT: pa.int64(),
                Kind.FLOAT: pa.float32(),
                Kind.DATE: pa.date32(),
                Kind.TIMESTAMP: pa.timestamp("ns"),
            }[f.type.kind]
            arrays.append(pa.array(vals, type=pa_type, mask=mask))
        names.append(f.name)
    return pa.RecordBatch.from_arrays(arrays, names=names)


def numpy_to_batch(
    data: Dict[str, np.ndarray],
    schema: Schema,
    capacity: Optional[int] = None,
):
    """Build a Batch from host numpy columns (test/workload convenience)."""
    # zero-COLUMN batches are legal (COUNT(*) needs no inputs): they have
    # zero rows unless a capacity says otherwise
    n = len(next(iter(data.values()))) if data else 0
    capacity = capacity or n
    cols = {}
    for f in schema:
        arr = np.asarray(data[f.name]).astype(_np_dtype(f.type))
        cols[f.name] = Column(jnp.asarray(_pad(arr, capacity)), None)
    sel = jnp.arange(capacity) < n
    return Batch(cols, sel, jnp.int32(n))


# --- packed ingest: one transfer per chunk + jitted on-device unpack -------
#
# Per-column jnp.asarray calls pay a transfer's fixed latency per column;
# packing every column into ONE uint8 buffer amortizes it and runs at the
# link's large-transfer bandwidth (chip_smoke.py prints it: `h2d_gbps`).
# The reference analog is the Arrow IPC RecordBatch body (colserde
# record_batch.go): contiguous buffers + a static layout header.

def pack_layout(schema: Schema, capacity: int):
    """[(name, np_dtype, offset, nbytes)] with 8-byte aligned offsets.
    Uses each field's narrow `wire` dtype when declared (batch.py Field).
    Nullable fields get an extra uint8 validity lane named
    "<name>__valid" (the Arrow validity-bitmap analog)."""
    layout = []
    off = 0
    for f in schema:
        dt = np.dtype(f.wire) if f.wire else _np_dtype(f.type)
        # VECTOR(d) columns ride d float32 lanes per row; the unpackers
        # recover d from nbytes // (capacity * itemsize)
        lanes = f.type.dim if f.type.kind is Kind.VECTOR else 1
        nbytes = capacity * lanes * dt.itemsize
        layout.append((f.name, dt, off, nbytes))
        off += (nbytes + 7) & ~7
        if getattr(f, "nullable", False):
            layout.append((f.name + "__valid", np.dtype(np.uint8), off,
                           capacity))
            off += (capacity + 7) & ~7
    return layout, off


def pack_chunk(chunk: Dict[str, np.ndarray], schema: Schema,
               capacity: int) -> Tuple[np.ndarray, int]:
    """Host-side: copy columns (cast + zero-pad) into one uint8 buffer.
    Validity lanes missing from the chunk default to all-valid."""
    layout, total = pack_layout(schema, capacity)
    buf = np.zeros(total, dtype=np.uint8)
    n = len(next(iter(chunk.values())))
    for name, dt, off, nbytes in layout:
        src = chunk.get(name)
        if src is None and name.endswith("__valid"):
            src = np.ones(n, dtype=np.uint8)
        arr = np.asarray(src).astype(dt, copy=False)[:capacity]
        flat = arr.reshape(-1)  # VECTOR rows flatten row-major
        view = buf[off:off + flat.shape[0] * dt.itemsize].view(dt)
        view[:] = flat
    return buf, n


def make_flat_unpack(schema: Schema, capacity: int):
    """Traceable (bufs (N, nbytes) u8, ms (N,) i32) -> one FLAT Batch of
    capacity N*cap — the fused tracer's materialization path. Each
    column lives at one byte range per chunk, so the flat column is a
    2-D slice + bitcast + reshape (XLA fuses it into consumers) instead
    of N per-chunk unpacks + an N-way concat (~400ms of HBM copies per
    60-chunk scan at SF10)."""
    import jax.numpy as jnp
    from jax import lax

    layout, _total = pack_layout(schema, capacity)
    device_dt = {f.name: _np_dtype(f.type) for f in schema}

    def unpack(bufs, ms):
        n = bufs.shape[0]
        cols = {}
        valids = {}
        for name, dt, off, nbytes in layout:
            raw = lax.slice(bufs, (0, off), (n, off + nbytes))
            jdt = jnp.dtype(dt)
            if name.endswith("__valid"):
                valids[name[:-len("__valid")]] = \
                    raw.reshape(-1) != 0
                continue
            lanes = nbytes // (capacity * jdt.itemsize)
            if jdt == jnp.bool_:
                vals = raw.reshape(-1).astype(jnp.bool_)
            elif jdt.itemsize == 1:
                vals = lax.bitcast_convert_type(raw, jdt).reshape(-1)
            elif lanes > 1:  # VECTOR: (N*cap, d)
                vals = lax.bitcast_convert_type(
                    raw.reshape(n, capacity * lanes, jdt.itemsize),
                    jdt).reshape(-1, lanes)
            else:
                vals = lax.bitcast_convert_type(
                    raw.reshape(n, capacity, jdt.itemsize),
                    jdt).reshape(-1)
            want = jnp.dtype(device_dt[name])
            if vals.dtype != want:
                vals = vals.astype(want)
            cols[name] = Column(vals)
        lane = jnp.arange(capacity, dtype=jnp.int32)
        sel = (lane[None, :] < ms[:, None]).reshape(-1)
        for name, v in valids.items():
            cols[name] = Column(cols[name].values, v & sel)
        length = jnp.sum(ms).astype(jnp.int32)
        return Batch(cols, sel, length)

    return unpack


def make_unpack(schema: Schema, capacity: int):
    """Traceable (buf: uint8[total], n: int32) -> Batch. Wire dtypes are
    widened to the canonical device dtype after the bitcast."""
    import jax.numpy as jnp
    from jax import lax

    layout, _total = pack_layout(schema, capacity)
    device_dt = {f.name: _np_dtype(f.type) for f in schema}

    def unpack(buf, n):
        cols = {}
        valids = {}
        for name, dt, off, nbytes in layout:
            raw = lax.dynamic_slice(buf, (off,), (nbytes,))
            jdt = jnp.dtype(dt)
            if name.endswith("__valid"):
                valids[name[:-len("__valid")]] = raw != 0
                continue
            lanes = nbytes // (capacity * jdt.itemsize)
            if jdt == jnp.bool_:
                vals = raw.astype(jnp.bool_)
            elif jdt.itemsize == 1:
                vals = lax.bitcast_convert_type(raw, jdt)
            elif lanes > 1:  # VECTOR: (capacity, d)
                vals = lax.bitcast_convert_type(
                    raw.reshape(capacity * lanes, jdt.itemsize),
                    jdt).reshape(capacity, lanes)
            else:
                vals = lax.bitcast_convert_type(
                    raw.reshape(capacity, jdt.itemsize), jdt)
            want = jnp.dtype(device_dt[name])
            if vals.dtype != want:
                vals = vals.astype(want)
            cols[name] = Column(vals)
        sel = jnp.arange(capacity) < n
        for name, v in valids.items():
            cols[name] = Column(cols[name].values, v & sel)
        return Batch(cols, sel, jnp.asarray(n, jnp.int32))

    return unpack
