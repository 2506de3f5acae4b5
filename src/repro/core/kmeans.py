"""Batched Lloyd k-means in JAX — coarse quantizer + PQ sub-codebook training.

Used for (a) the IVF coarse quantizer (``nlist`` centroids over the corpus)
and (b) the per-subspace PQ codebooks (vmapped over the M subspaces).

Design notes
------------
* Pure-functional, jit-compiled update step; the iteration loop is a
  ``lax.fori_loop`` so the whole training run is one XLA program.
* Empty clusters are re-seeded from the points with the largest distance to
  their assigned centroid (the standard Faiss "split largest" fallback,
  simplified to "steal farthest point" which is what matters at our scale).
* Assignment is chunked over points so the (N, K) distance matrix never
  materializes for large N — keeps peak memory at ``chunk × K``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

#: f32 GEMMs whose results rank or sum distances run at full precision
#: (the TPU default is one bf16 pass; the CPU ignores the setting).
HIGHEST = jax.lax.Precision.HIGHEST


class KMeansState(NamedTuple):
    centroids: jax.Array  # (K, D) f32
    assign: jax.Array     # (N,) i32
    obj: jax.Array        # () f32 — mean squared distance (inertia / N)


def l2_sq(x: jax.Array, y: jax.Array) -> jax.Array:
    """Pairwise squared L2 between rows of x (n, d) and y (m, d) -> (n, m).

    Uses the expansion ||x||^2 - 2 x.y + ||y||^2 (one GEMM — MXU-friendly);
    clamped at 0 against catastrophic cancellation.  The GEMM runs at
    HIGHEST precision: a TPU's default single bf16 pass leaves errors
    the size of the neighbor gaps, and the clamped ties then send whole
    blobs to the lowest-index centroid (a 735k-point cluster out of 10M
    in a chip run).
    """
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    xx = jnp.sum(x * x, axis=-1, keepdims=True)          # (n, 1)
    yy = jnp.sum(y * y, axis=-1, keepdims=True).T        # (1, m)
    d = xx + yy - 2.0 * jnp.matmul(x, y.T, precision=HIGHEST)
    return jnp.maximum(d, 0.0)


def assign_chunked(points: jax.Array, centroids: jax.Array, chunk: int = 16384):
    """argmin_k ||p - c_k||^2 for every point, chunked. -> (assign, mindist)."""
    n = points.shape[0]
    pad = (-n) % chunk
    pts = jnp.pad(points, ((0, pad), (0, 0)))
    nchunks = pts.shape[0] // chunk

    def body(carry, pchunk):
        d = l2_sq(pchunk, centroids)
        return carry, (jnp.argmin(d, axis=1).astype(jnp.int32), jnp.min(d, axis=1))

    _, (assign, mind) = jax.lax.scan(
        body, None, pts.reshape(nchunks, chunk, -1))
    return assign.reshape(-1)[:n], mind.reshape(-1)[:n]


def _update_step(points: jax.Array, state: KMeansState, chunk: int) -> KMeansState:
    k = state.centroids.shape[0]
    assign, mind = assign_chunked(points, state.centroids, chunk)
    # new centroids = segment mean
    sums = jax.ops.segment_sum(points.astype(jnp.float32), assign, num_segments=k)
    counts = jax.ops.segment_sum(jnp.ones((points.shape[0],), jnp.float32),
                                 assign, num_segments=k)
    new_c = sums / jnp.maximum(counts, 1.0)[:, None]
    # empty-cluster reseed: steal the globally farthest points, one per empty
    # slot (ranked), so distinct empties get distinct points.
    empty = counts < 0.5                                   # (K,)
    order = jnp.argsort(-mind)                             # farthest-first point ids
    empty_rank = jnp.cumsum(empty.astype(jnp.int32)) - 1   # rank among empties
    steal = points[order[jnp.clip(empty_rank, 0, points.shape[0] - 1)]]
    new_c = jnp.where(empty[:, None], steal.astype(jnp.float32), new_c)
    return KMeansState(new_c, assign, jnp.mean(mind))


@functools.partial(jax.jit, static_argnames=("k", "iters", "chunk"))
def kmeans(key: jax.Array, points: jax.Array, k: int, iters: int = 12,
           chunk: int = 16384) -> KMeansState:
    """Lloyd k-means. points (N, D) any real dtype -> KMeansState (f32)."""
    n = points.shape[0]
    init_idx = jax.random.choice(key, n, shape=(k,), replace=n < k)
    state = KMeansState(points[init_idx].astype(jnp.float32),
                        jnp.zeros((n,), jnp.int32), jnp.inf)

    def body(_, st):
        return _update_step(points, st, chunk)

    state = jax.lax.fori_loop(0, iters, body, state)
    # final assignment against the final centroids
    assign, mind = assign_chunked(points, state.centroids, chunk)
    return KMeansState(state.centroids, assign, jnp.mean(mind))


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def kmeans_multi(key: jax.Array, points: jax.Array, k: int, iters: int = 12
                 ) -> KMeansState:
    """vmapped k-means over a leading axis: points (M, N, d) -> (M, k, d).

    Used for PQ sub-codebooks (one k-means per subspace, shared iteration
    count, independent seeds)."""
    m = points.shape[0]
    keys = jax.random.split(key, m)
    return jax.vmap(lambda kk, p: kmeans(kk, p, k=k, iters=iters,
                                         chunk=min(16384, p.shape[0])))(keys, points)
