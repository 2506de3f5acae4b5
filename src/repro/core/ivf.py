"""IVF-PQ index construction and the padded cluster layout.

Build pipeline (matches Faiss IVFPQ / the paper's engine):
  1. coarse k-means over the training set (a sample, or the corpus)
     -> nlist centroids
  2. PQ-train (or OPQ) on the training set's residuals
  3. assign + residual + encode every point, in chunks (``assign_encode``)
  4. group codes by cluster

JAX wants static shapes, so the grouped layout pads every cluster to
``cmax`` (95th-percentile-or-max size by default) with a size array for
masking — the same structure a DPU's MRAM region holds in the paper.  The
layout optimizer (core/layout.py) later *re*-groups instances (split /
duplicated clusters) into per-shard arrays of exactly this shape.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kmeans import kmeans, assign_chunked, l2_sq
from repro.core.pq import (PQCodebook, OPQCodebook, train_pq, train_opq,
                           encode_pq)


class IVFPQIndex(NamedTuple):
    """Flat (CSR-ish) index: codes sorted by cluster id."""
    centroids: jax.Array        # (nlist, D) f32
    codebook: PQCodebook
    codes: jax.Array            # (N, M) u8/u16 — sorted by cluster
    ids: jax.Array              # (N,) i32 — original point ids, same order
    offsets: jax.Array          # (nlist + 1,) i32 — CSR row offsets
    rotation: Optional[jax.Array] = None   # (D, D) if OPQ

    @property
    def nlist(self) -> int:
        return self.centroids.shape[0]

    @property
    def dim(self) -> int:
        return self.centroids.shape[1]

    @property
    def sizes(self) -> jax.Array:
        return self.offsets[1:] - self.offsets[:-1]


class PaddedClusters(NamedTuple):
    """Dense padded layout: what one shard (or the single device) scans."""
    codes: jax.Array     # (ncls, cmax, M) u8/u16
    ids: jax.Array       # (ncls, cmax) i32 — -1 in padding
    sizes: jax.Array     # (ncls,) i32

    @property
    def cmax(self) -> int:
        return self.codes.shape[1]


@jax.jit
def assign_encode(points: jax.Array, centroids: jax.Array,
                  codebook: PQCodebook, rotation: Optional[jax.Array] = None):
    """Coarse assignment + residual + PQ encoding, chunked over points so
    the f32 copy, residuals and distance blocks exist for one chunk at a
    time (what lets a 10M-point build fit in HBM).  Per-row math is that
    of ``assign_chunked`` then ``encode_pq``.
    -> (assign (N,) i32, codes (N, M))."""
    n, d = points.shape
    chunk = 16384                         # assign_chunked's default
    pad = (-n) % chunk
    pts = jnp.pad(points, ((0, pad), (0, 0))).reshape(-1, chunk, d)

    def body(_, pc):
        x = pc.astype(jnp.float32)
        a = jnp.argmin(l2_sq(x, centroids), axis=1).astype(jnp.int32)
        r = x - centroids[a]
        if rotation is not None:
            r = r @ rotation
        return None, (a, encode_pq(codebook, r))

    _, (assign, codes) = jax.lax.scan(body, None, pts)
    return assign.reshape(-1)[:n], codes.reshape(-1, codes.shape[-1])[:n]


def build_ivfpq(key: jax.Array, points: jax.Array, *, nlist: int, m: int,
                cb: int = 256, kmeans_iters: int = 12, pq_iters: int = 12,
                opq: bool = False, train_sample: Optional[int] = None
                ) -> IVFPQIndex:
    """Build an IVF-PQ(-OPQ) index over ``points`` (N, D).

    The quantizers (coarse k-means, then PQ/OPQ on the residuals) train
    on ``train_sample`` points drawn without replacement, or on every
    point when it is None; all points are then encoded in chunks."""
    n = points.shape[0]
    kc, kp, ks = jax.random.split(key, 3)
    train_pts = points
    if train_sample is not None and train_sample < n:
        sel = jax.random.choice(ks, n, shape=(train_sample,), replace=False)
        train_pts = points[sel]

    km = kmeans(kc, train_pts, k=nlist, iters=kmeans_iters)
    centroids = km.centroids
    train_f = train_pts.astype(jnp.float32)
    train_assign, _ = assign_chunked(train_f, centroids)
    residuals = train_f - centroids[train_assign]

    rotation = None
    if opq:
        opq_cb: OPQCodebook = train_opq(kp, residuals, m=m, cb=cb,
                                        pq_iters=pq_iters)
        rotation = opq_cb.rotation
        codebook = opq_cb.pq
    else:
        codebook = train_pq(kp, residuals, m=m, cb=cb, iters=pq_iters)
    del train_f, residuals

    assign, codes = assign_encode(points, centroids, codebook, rotation)

    # group by cluster: stable sort by assignment
    order = jnp.argsort(assign, stable=True)
    codes_sorted = codes[order]
    ids_sorted = order.astype(jnp.int32)
    sizes = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), assign,
                                num_segments=nlist)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(sizes)]).astype(jnp.int32)
    return IVFPQIndex(centroids, codebook, codes_sorted, ids_sorted, offsets,
                      rotation)


def pad_clusters(index: IVFPQIndex, cmax: Optional[int] = None,
                 pad_multiple: int = 8) -> PaddedClusters:
    """CSR -> dense padded (nlist, cmax, M). Done once offline (numpy ok)."""
    sizes = np.asarray(index.sizes)
    offsets = np.asarray(index.offsets)
    codes = np.asarray(index.codes)
    ids = np.asarray(index.ids)
    nlist, m = index.nlist, codes.shape[1]
    if cmax is None:
        cmax = int(sizes.max(initial=1))
    cmax = max(int(cmax), 1)
    cmax = -(-cmax // pad_multiple) * pad_multiple
    out_codes = np.zeros((nlist, cmax, m), dtype=codes.dtype)
    out_ids = np.full((nlist, cmax), -1, dtype=np.int32)
    for c in range(nlist):
        s = min(int(sizes[c]), cmax)
        out_codes[c, :s] = codes[offsets[c]:offsets[c] + s]
        out_ids[c, :s] = ids[offsets[c]:offsets[c] + s]
    return PaddedClusters(jnp.asarray(out_codes), jnp.asarray(out_ids),
                          jnp.asarray(np.minimum(sizes, cmax).astype(np.int32)))


def reconstruct(index: IVFPQIndex, point_rank: jax.Array) -> jax.Array:
    """Approximate reconstruction of the point stored at sorted rank r —
    centroid + decoded residual (un-rotated if OPQ). Used by tests."""
    from repro.core.pq import decode_pq
    # cluster of rank r = searchsorted over offsets
    cl = jnp.searchsorted(index.offsets, point_rank, side="right") - 1
    res = decode_pq(index.codebook, index.codes[point_rank][None])[0]
    if index.rotation is not None:
        res = res @ index.rotation.T
    return index.centroids[cl] + res
