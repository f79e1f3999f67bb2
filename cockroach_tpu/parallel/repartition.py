"""Collective repartitioning (shard_map kernels) and mesh placement.

Reference mapping (SURVEY.md §2.9):
- P3 BY_HASH repartition (colflow/routers.go:442 HashRouter -> outbox ->
  gRPC FlowStream -> inbox) ==> `hash_repartition_local`: one on-chip
  sort by destination that carries every lane as an operand, each
  destination's bucket cut out of the sorted lanes as ONE slice (no
  gather, no scatter), then `lax.all_to_all` over ICI, once per lane per
  batch round. parallel/dist_flow.py routes both sides of a BY_HASH join
  through it, so that each chip joins one partition.
- P1/P2 placement: `shard_batch`, `put_sharded_blocks` (ingest-time, one
  host-link crossing a replica), `put_replicated` (the P4 MIRROR side).

Buckets are fixed-capacity (static shapes); overflow is detected and
psum-reduced so the host can retry with a bigger factor — the collective
analog of the join overflow retry (SURVEY.md §7.4 item 5: skew handling).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
import inspect as _inspect

try:
    from jax import shard_map as _shard_map
except ImportError:  # older jax
    from jax.experimental.shard_map import shard_map as _shard_map

# replication checking kwarg was renamed check_rep -> check_vma in jax 0.8
_CHECK_KW = ("check_vma" if "check_vma" in
             _inspect.signature(_shard_map).parameters else "check_rep")


def shard_map(f, **kw):
    kw[_CHECK_KW] = kw.pop("check_rep", False)
    return _shard_map(f, **kw)

from cockroach_tpu.coldata.batch import Batch
from cockroach_tpu.ops.hash import hash_columns


def _batch_pspecs(batch: Batch, axis: Optional[str]):
    """Pytree of PartitionSpecs for a Batch: rows sharded on `axis`
    (or replicated if axis is None), scalar length replicated."""
    row = P(axis) if axis else P()
    repl = P()
    return jax.tree_util.tree_map(
        lambda leaf: repl if jnp.ndim(leaf) == 0 else row, batch)


def shard_batch(batch: Batch, mesh: Mesh, axis: str = "x") -> Batch:
    """Place a host/global Batch row-sharded over the mesh (P1/P2 layout)."""
    specs = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), _batch_pspecs(batch, axis))
    return jax.device_put(batch, specs)


# -------------------------------------------------- ingest-time placement --

def axis_devices(mesh: Mesh, axis: str):
    """Device grid reorganized as (n_dev_along_axis, n_other): row d is
    every device holding the d-th block of a P(axis)-sharded array (one
    device per row on a flat mesh; the replica set across the other axes
    on a multi-axis mesh)."""
    ax = tuple(mesh.axis_names).index(axis)
    grid = np.moveaxis(mesh.devices, ax, 0)
    return grid.reshape(grid.shape[0], -1)


def put_sharded_blocks(blocks, mesh: Mesh, axis: str):
    """Assemble per-shard host blocks into ONE global array sharded
    `P(axis)` on its leading dim — the ingest-time placement: each block
    is device_put straight to its owning device(s), so the bytes cross
    the host link exactly once per replica instead of landing whole on
    device 0 and being scattered (SPMD ingest sharding, P2).

    `blocks` is a length-n_dev list of equal-shape numpy arrays; returns
    (global jax.Array, per-device single-shard arrays for incremental
    reassembly via `reassemble_sharded`)."""
    grid = axis_devices(mesh, axis)
    n_dev = grid.shape[0]
    assert len(blocks) == n_dev, (len(blocks), n_dev)
    per_dev = []
    for d in range(n_dev):
        block = np.ascontiguousarray(blocks[d])
        for dev in grid[d]:
            per_dev.append(jax.device_put(block, dev))
    global_shape = (n_dev * blocks[0].shape[0],) + tuple(blocks[0].shape[1:])
    arr = jax.make_array_from_single_device_arrays(
        global_shape, NamedSharding(mesh, P(axis)), per_dev)
    return arr, per_dev


def reassemble_sharded(per_dev, mesh: Mesh, axis: str):
    """Rebuild the global P(axis) array from (possibly partially
    replaced) per-device shard arrays — the zero-copy path for a
    per-shard refresh: untouched shards keep their device buffers."""
    grid = axis_devices(mesh, axis)
    n_dev = grid.shape[0]
    shard = per_dev[0].shape
    global_shape = (n_dev * shard[0],) + tuple(shard[1:])
    return jax.make_array_from_single_device_arrays(
        global_shape, NamedSharding(mesh, P(axis)), list(per_dev))


def put_replicated(host, mesh: Mesh):
    """Place one host array fully replicated over the mesh (the P4
    MIRROR broadcast side): every device gets its own copy."""
    return jax.device_put(host, NamedSharding(mesh, P()))


def hash_repartition_local(batch: Batch, key_names: Sequence[str],
                           axis_name: str, n_dev: int,
                           bucket_cap: int, seed: int = 0
                           ) -> Tuple[Batch, jnp.ndarray]:
    """Runs INSIDE shard_map. Routes each selected row to device
    `hash(keys) % n_dev`: destination sort, bucket slices, all_to_all
    (the BY_HASH router, P3).

    Returns (received batch of capacity n_dev*bucket_cap, overflow flag).
    Overflow (some bucket exceeded bucket_cap) must be psum-checked by the
    caller across the axis.
    """
    # high hash bits pick the device so the low bits stay independent for
    # the local hash table / join probe (reference re-seeds per Grace level)
    h = hash_columns(batch, key_names, seed=seed)
    dest = ((h >> jnp.uint64(42)) % jnp.uint64(n_dev)).astype(jnp.int32)
    return _route_and_exchange(batch, dest, axis_name, n_dev, bucket_cap)


def range_repartition_local(batch: Batch, key_name: str,
                            boundaries: jnp.ndarray, axis_name: str,
                            n_dev: int, bucket_cap: int
                            ) -> Tuple[Batch, jnp.ndarray]:
    """BY_RANGE router (P5, OutputRouterSpec_BY_RANGE data.proto:160 —
    the bulk-ingest routing strategy): rows route to the device owning
    their key range. `boundaries` are the n_dev-1 sorted split points;
    device d owns keys in [boundaries[d-1], boundaries[d])."""
    vals = batch.col(key_name).values.astype(jnp.int64)
    dest = jnp.searchsorted(boundaries.astype(jnp.int64), vals,
                            side="right").astype(jnp.int32)
    return _route_and_exchange(batch, dest, axis_name, n_dev, bucket_cap)


def exchange_bucket(rows: int, n_dev: int) -> int:
    """Rows of one destination's bucket when a device sends `rows` rows
    through `hash_repartition_local`: an even spread with twice the room
    for skew, a power of two, 64 at least. `rows` is the caller's to
    choose: the rows it expects the side to SEND (the planner's estimate,
    a shard's share of it; parallel/dist_flow.py) or, where nothing
    better is known, the side's lanes, which no count of rows can pass.
    A fuller bucket drops nothing silently: it raises the router's
    overflow flag, and the flow restarts (from an estimate, on the
    bucket the lanes give; dist_flow._BucketGuard)."""
    return 1 << (max(64, rows // n_dev * 2) - 1).bit_length()


def exchange_bytes(batch: Batch, n_dev: int, bucket_cap: int) -> int:
    """Bytes ONE device sends over the axis in one `_route_and_exchange`
    of `batch`, from its static shapes: of the n_dev buckets of
    bucket_cap rows it fills, one stays at home; a row is every column's
    values, its validity lane where it has one, and the selection
    lane."""
    row = jnp.dtype(jnp.bool_).itemsize
    for c in batch.columns.values():
        row += c.values.dtype.itemsize * int(
            np.prod(c.values.shape[1:], dtype=np.int64))
        if c.validity is not None:
            row += c.validity.dtype.itemsize
    return (n_dev - 1) * bucket_cap * row


def _route_and_exchange(batch: Batch, dest: jnp.ndarray, axis_name: str,
                        n_dev: int, bucket_cap: int
                        ) -> Tuple[Batch, jnp.ndarray]:
    """Shared router tail: ONE sort by destination carries every lane
    along as an operand; each destination's rows are then a contiguous
    run in lane order, cut out as one slice of bucket_cap rows; an
    all_to_all over ICI for each lane. No gather and no scatter: on a
    v5e the sort costs a fourteenth of gathering the lanes by an argsort
    and a seventieth of placing them element by element (PERF.md
    section 6, PR 28). The sort's key is `dest * cap + lane` as one u32:
    unique, so the sort is unstable and XLA adds no tie-break operand
    to the columns it carries, and a run keeps its lane order (an
    overflowing run its first bucket_cap rows) as the stable sort by
    `dest` alone kept it. A shard too long for that key,
    (n_dev + 1) * cap >= 2^32, sorts by `dest`, stable."""
    dest = jnp.where(batch.sel, dest, n_dev)          # dead rows sort last
    cap = batch.capacity

    # every column's values, and its validity where it has one (a tuple:
    # a dict would come back in sorted order, not the batch's)
    lanes, columns = jax.tree_util.tree_flatten(
        tuple(batch.columns.values()))
    cuts = jnp.arange(n_dev + 1)
    if (n_dev + 1) * cap < (1 << 32):
        key = (dest.astype(jnp.uint32) * np.uint32(cap)
               + jnp.arange(cap, dtype=jnp.uint32))
        sorted_dest, *lanes = lax.sort((key, *lanes), num_keys=1,
                                       is_stable=False)
        cuts = cuts.astype(jnp.uint32) * np.uint32(cap)
    else:
        sorted_dest, *lanes = lax.sort((dest, *lanes), num_keys=1,
                                       is_stable=True)
    starts = jnp.searchsorted(sorted_dest, cuts).astype(jnp.int32)
    count = starts[1:] - starts[:-1]                   # rows per destination
    overflow = jnp.any(count > bucket_cap)
    # row j of bucket d is the j-th row of d's run: live while the run
    # lasts, zero after it (an overflowing run keeps its first bucket_cap)
    live = (jnp.arange(bucket_cap, dtype=jnp.int32)[None, :]
            < jnp.minimum(count, bucket_cap)[:, None]).reshape(-1)

    # exchange: chunk d of my buffer -> device d (ICI all-to-all)
    a2a = lambda x: lax.all_to_all(x, axis_name, split_axis=0,
                                   concat_axis=0, tiled=True)

    def send(lane):
        # bucket_cap rows of padding: a run that starts near the end is
        # still cut at its own start (dynamic_slice clamps a start whose
        # slice would not fit, and would send another destination's rows)
        lane = jnp.concatenate(
            [lane, jnp.zeros((bucket_cap,), lane.dtype)])
        out = jnp.concatenate(
            [lax.dynamic_slice(lane, (starts[d],), (bucket_cap,))
             for d in range(n_dev)])
        return a2a(jnp.where(live, out, jnp.zeros((), lane.dtype)))

    cols = dict(zip(batch.columns, jax.tree_util.tree_unflatten(
        columns, [send(v) for v in lanes])))
    sel = a2a(live)
    out = Batch(cols, sel, jnp.sum(sel).astype(jnp.int32))
    return out, overflow
