"""Exact top-k by blocked brute force over the raw corpus, on the device.

The benchmark's own copy of an exact-search oracle (the program has one
in ``core/search.py``; it is not imported, so that no change to the
program can move the yardstick).  Distances are squared L2 by the
expansion ``|q|^2 - 2 q.x + |x|^2``:

- ``uint8`` corpora: queries and rows are integers in [0, 255], which
  bfloat16 holds exactly, and the products and sums stay below 2**24, so
  one bf16 pass with float32 accumulation gives every distance exactly.
- ``float32`` corpora: the dot products run at ``HIGHEST`` precision.

Rows are visited in blocks, and a running top-k is merged per block, so
the working set is one (Q, block) distance matrix.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _dots(q, x):
    if x.dtype == jnp.uint8:
        return jax.lax.dot_general(
            q.astype(jnp.bfloat16), x.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return jnp.matmul(q.astype(jnp.float32), x.astype(jnp.float32).T,
                      precision=HIGHEST)


def _sqnorm(x):
    x = x.astype(jnp.float32)
    return jnp.sum(x * x, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "block"))
def exact_topk(corpus: jax.Array, queries: jax.Array, k: int,
               block: int = 262144):
    """(Q, k) squared distances and row ids of each query's k nearest rows,
    ascending; ties go to the lower row id."""
    n = corpus.shape[0]
    block = min(block, n)
    nb = -(-n // block)
    qq = _sqnorm(queries)[:, None]

    def body(i, carry):
        best_d, best_i = carry
        start = jnp.minimum(i * block, n - block)   # last block overlaps
        rows = jax.lax.dynamic_slice_in_dim(corpus, start, block, 0)
        d = qq + _sqnorm(rows)[None, :] - 2.0 * _dots(queries, rows)
        ids = start + jnp.arange(block, dtype=jnp.int32)
        # rows the previous block already saw (overlap) are masked out
        d = jnp.where(ids[None, :] >= i * block, d, jnp.inf)
        cd = jnp.concatenate([best_d, d], axis=1)
        ci = jnp.concatenate([best_i, jnp.broadcast_to(ids, d.shape)], axis=1)
        neg, pos = jax.lax.top_k(-cd, k)
        return -neg, jnp.take_along_axis(ci, pos, axis=1)

    q = queries.shape[0]
    init = (jnp.full((q, k), jnp.inf, jnp.float32),
            jnp.full((q, k), -1, jnp.int32))
    return jax.lax.fori_loop(0, nb, body, init)


def recall_at_k(served_ids, true_ids) -> float:
    """Mean over queries of |served top-k ∩ exact top-k| / k."""
    import numpy as np
    served = np.asarray(served_ids)
    true = np.asarray(true_ids)
    k = true.shape[1]
    hits = [len(set(s[s >= 0].tolist()) & set(t.tolist()))
            for s, t in zip(served, true)]
    return float(np.sum(hits)) / (k * len(true))
