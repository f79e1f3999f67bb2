"""Blocked prefix sums.

XLA lowers a flat `jnp.cumsum` to reduce-window chains whose scoped-VMEM
footprint grows with array length; for int64 inputs on TPU (emulated as
u32 hi/lo pairs) a multi-million-lane cumsum exceeds the v5e scoped-VMEM
limit at compile time ("Ran out of memory in memory space vmem ...
reduce-window"). The standard fix is the two-level scan decomposition:
cumsum within fixed-size blocks, cumsum the block totals, add the offsets
back. Every window XLA sees is then <= `block` lanes regardless of input
size. Exactness is unaffected — it is the same integer addition tree.

Reference analog: none needed on CPU (colexecagg accumulates scalar-at-a-
time); this is a TPU-lowering concern, handled once here for every
consumer (agg kernels, join ragged expansion).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_BLOCK = 512


def blocked_cumsum(x, block: int = _BLOCK):
    """Inclusive 1-D cumsum with bounded scan windows. Same dtype/semantics
    as jnp.cumsum(x) for any integer/float dtype."""
    n = x.shape[0]
    if n <= block:
        return jnp.cumsum(x)
    pad = (-n) % block
    xp = jnp.pad(x, (0, pad)) if pad else x
    rows = xp.reshape(-1, block)
    within = jnp.cumsum(rows, axis=1)
    totals = within[:, -1]
    offsets = blocked_cumsum(totals, block) - totals
    out = (within + offsets[:, None]).reshape(-1)
    return out[:n]


def blocked_cummax(x, block: int = _BLOCK):
    """Inclusive 1-D running maximum with bounded scan windows: the same
    values as lax.cummax(x). A flat 64-bit cummax of 262,144 lanes takes
    the TPU's compiler 185 s (v5e, compiled for a described chip; 5 s at
    32 bits), the two-level form 0.5 s."""
    n = x.shape[0]
    if n <= block:
        return lax.cummax(x)
    pad = (-n) % block
    rows = (jnp.pad(x, (0, pad)) if pad else x).reshape(-1, block)
    within = lax.cummax(rows, axis=1)
    upto = blocked_cummax(within[:, -1:].reshape(-1), block)  # rows 0..r
    out = jnp.concatenate(
        [within[:1],
         jnp.maximum(within[1:], upto[:-1].reshape(-1, 1))], axis=0)
    return out.reshape(-1)[:n]


def blocked_assoc_scan(combine, xs, block: int = _BLOCK):
    """Inclusive 1-D `lax.associative_scan` over a pytree `xs`, decomposed
    into bounded-window scans (same two-level scheme as blocked_cumsum).

    `combine(a, b)` must be associative and elementwise-broadcasting (all
    the segmented-scan combines in ops/agg.py are). End-padding is
    arbitrary (zeros): a forward inclusive scan never feeds padded lanes
    back into real outputs."""
    tm = jax.tree_util.tree_map
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    if n <= block:
        return lax.associative_scan(combine, xs)
    pad = (-n) % block

    def prep(a):
        return (jnp.pad(a, (0, pad)) if pad else a).reshape(-1, block)

    rows = tm(prep, xs)
    within = lax.associative_scan(combine, rows, axis=1)
    summaries = tm(lambda w: w[:, -1], within)
    # inclusive scan of per-row summaries (recursively blocked)
    summ_scan = blocked_assoc_scan(combine, summaries, block)
    carry = tm(lambda s: s[:-1, None], summ_scan)   # prefix for rows 1..R-1
    tail = tm(lambda w: w[1:], within)
    combined_tail = combine(carry, tail)
    first = tm(lambda w: w[0], within)
    return tm(
        lambda f, ct: jnp.concatenate([f[None], ct], axis=0).reshape(-1)[:n],
        first, combined_tail)
