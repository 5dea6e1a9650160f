"""Vectorized per-segment reductions in arrival (row) order.

CloudSim's space-shared queues are "first K entities whose cumulative core
demand fits" (paper Figure 4a/4c).  Tensorized, that is an *exclusive prefix
sum of demand within each segment, in row order*: entity i runs iff
``prefix(i) + demand(i) <= capacity(segment(i))``.

Implemented with one stable argsort + associative_scan, O(N log N), no
host<->device sync, fully vmappable.

``lookup`` reads small per-row tables at per-row indices.  Under the batch
step's ``vmap`` a plain ``table[idx]`` becomes a batched gather, which on a
TPU costs about 11 ns an index however small the table; a table of at most
``ONE_HOT_MAX`` entries is read instead as an exact one-hot select, a
compare and a reduction that fuse with their neighbours.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array, lax

# Largest table (entries per row) read by the one-hot form; larger ones are
# gathered.  On one TPU v5e the one-hot form read five tables of 2,048
# entries 5.5 and 6 times faster than five gathers, at 256 rows of 500
# indices and at 12 rows of 1,052; the two cost the same near 11,000
# entries (PERF.md §6; scripts/lookup_crossover.py).
ONE_HOT_MAX = 2048


def _one_hot(idx: Array, n: int) -> Array:
    """[n, *idx.shape] bool: entry ``j`` of the table axis marks ``idx == j``."""
    return jnp.arange(n, dtype=idx.dtype).reshape((n,) + (1,) * idx.ndim) == idx


def _select(hit: Array, x: Array, axis: int) -> Array:
    """The entry of ``x`` that ``hit`` marks along ``axis`` (at most one),
    bit for bit.  Floats are summed as their bit patterns, so ``-0.0``,
    infinities, NaN payloads and subnormals (which TPU float arithmetic
    flushes) come back unchanged; an axis with no mark gives zero bits."""
    if x.dtype == jnp.bool_:
        return jnp.any(hit & x, axis=axis)
    if jnp.issubdtype(x.dtype, jnp.floating):
        bits = lax.bitcast_convert_type(x, jnp.dtype(f"uint{8 * x.dtype.itemsize}"))
        return lax.bitcast_convert_type(_select(hit, bits, axis), x.dtype)
    return jnp.sum(jnp.where(hit, x, 0), axis=axis, dtype=x.dtype)


def lookup(tables, idx: Array):
    """``table[idx]`` for each 1-D table of ``tables`` (one array or a
    pytree of them, all of one static length n); ``idx`` in ``[0, n)``,
    which callers clip.

    A table of at most ``ONE_HOT_MAX`` entries is read by one compare mask
    ``[n, *idx.shape]`` shared by every table, each then reduced over the
    table axis: sibling reductions fuse into one kernel.  Larger tables
    keep the gather.  The result is the same bits either way.
    """
    n = jax.tree.leaves(tables)[0].shape[0]
    if n > ONE_HOT_MAX:
        return jax.tree.map(lambda t: t[idx], tables)
    hit = _one_hot(idx, n)
    pad = (1,) * idx.ndim
    return jax.tree.map(lambda t: _select(hit, t.reshape((n,) + pad), 0), tables)


def segment_prefix_sum(values: Array, segment_ids: Array, num_segments: int) -> Array:
    """Exclusive prefix sum of ``values`` within each segment, in index order.

    ``segment_ids`` entries >= num_segments (or negative mapped there by the
    caller) contribute nothing and receive garbage prefixes — callers mask.
    """
    seg = jnp.clip(segment_ids, 0, num_segments)  # clip strays into a junk segment
    order = jnp.argsort(seg, stable=True)         # stable => row order inside segs
    v_sorted, seg_sorted = lookup((values, seg), order)
    incl = jnp.cumsum(v_sorted)
    excl = incl - v_sorted
    # Subtract each segment's starting offset: forward-fill the exclusive sum
    # observed at the first row of each segment. cumsum is non-decreasing for
    # non-negative values, so a running max implements the forward fill.
    is_first = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), seg_sorted[1:] != seg_sorted[:-1]]
    )
    base = jnp.where(is_first, excl, -jnp.inf)
    base = jax.lax.associative_scan(jnp.maximum, base)
    prefix_sorted = (excl - base).astype(values.dtype)
    n = values.shape[0]
    if n > ONE_HOT_MAX:
        return jnp.zeros_like(values).at[order].set(prefix_sorted)
    # ``order`` is a permutation, so row j takes the one i with order[i] == j
    return _select(_one_hot(order, n), prefix_sorted, 1)


def segment_sum(values: Array, segment_ids: Array, num_segments: int) -> Array:
    """Sum of ``values`` per segment -> [num_segments]."""
    seg = jnp.clip(segment_ids, 0, num_segments)
    return jnp.zeros((num_segments + 1,), values.dtype).at[seg].add(values)[:-1]


def segment_all(values: Array, segment_ids: Array, num_segments: int) -> Array:
    """Logical AND of ``values`` per segment (vacuously True) -> [num_segments]."""
    neg = segment_sum((~values).astype(jnp.int32), segment_ids, num_segments)
    return neg == 0


def segment_min(values: Array, segment_ids: Array, num_segments: int, fill) -> Array:
    seg = jnp.clip(segment_ids, 0, num_segments)
    out = jnp.full((num_segments + 1,), fill, values.dtype)
    return out.at[seg].min(values)[:-1]
