"""Top-k selection utilities (the paper's TS phase, XLA path).

  * ``topk_smallest`` — thin lax.top_k wrapper over (dists, ids).
  * ``smallest_k``    — lax.top_k with k clamped to the axis and padded.
  * ``merge_topk``    — merge two sorted top-k candidate lists
                        (per-shard results -> global winners).

The fused scan kernel selects inside Pallas instead
(``kernels/pq_scan._select_topk``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("TS")
def topk_smallest(dists: jax.Array, ids: jax.Array, k: int):
    """k smallest along last axis. Returns (dists (..., k), ids (..., k))."""
    neg, idx = jax.lax.top_k(-dists, k)
    return -neg, jnp.take_along_axis(ids, idx, axis=-1)


def smallest_k(dists: jax.Array, k: int):
    """k smallest along the last axis -> (values, indices), ascending.

    ``k`` may exceed the axis (nprobe > nlist after clusters merge):
    ``lax.top_k`` is asked for at most the axis length and the missing
    slots come back as (+inf, -1)."""
    kk = min(k, dists.shape[-1])
    neg, idx = jax.lax.top_k(-dists, kk)
    vals, idx = -neg, idx.astype(jnp.int32)
    if kk < k:
        pad = [(0, 0)] * (dists.ndim - 1) + [(0, k - kk)]
        vals = jnp.pad(vals, pad, constant_values=jnp.inf)
        idx = jnp.pad(idx, pad, constant_values=-1)
    return vals, idx


def merge_topk(d1, i1, d2, i2, k: int):
    """Merge two (…, k') candidate lists -> k smallest."""
    d = jnp.concatenate([d1, d2], axis=-1)
    i = jnp.concatenate([i1, i2], axis=-1)
    return topk_smallest(d, i, k)
