"""Two-level coarse quantizer: CL over group metadata, not all centroids.

Flat CL (``core.search.cluster_locate``) prices every query against all
``nlist`` centroids — Eq. 1's ``Q x N x D`` term.  At tiered/billion
scale ``nlist`` grows with the corpus and that GEMM (and the centroid
metadata it streams) becomes the router's wall.  The classic fix is a
second k-means level over the centroids themselves (IVF's IMI cousin,
UpANNS's routing tier): queries first rank ``n_groups`` L1 centroids,
then score only the clusters belonging to the top ``nprobe1`` groups.

Cost: ``Q x (G + nprobe1 * gmax) x D`` instead of ``Q x nlist x D`` —
with ``G ~ sqrt(nlist)`` routing touches ``O(sqrt(nlist))`` centroid
rows per query.  With ``nprobe1 == n_groups`` the candidate set is every
cluster, so the probe *set* equals flat CL's (the parity anchor tests
pin); smaller ``nprobe1`` trades recall for routing cost exactly like
``nprobe`` trades recall for scan cost.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kmeans import kmeans
from repro.core.topk import smallest_k


class Coarse2(NamedTuple):
    """Group-level routing metadata over an index's cluster centroids."""
    l1_centroids: jax.Array    # (G, D) f32 — level-1 (group) centroids
    members: jax.Array         # (G, gmax) i32 cluster ids, -1 pad
    member_centroids: jax.Array  # (G, gmax, D) f32 — gathered L2 rows

    @property
    def n_groups(self) -> int:
        return self.l1_centroids.shape[0]

    @property
    def gmax(self) -> int:
        return self.members.shape[1]


def build_coarse2(key, centroids, n_groups: Optional[int] = None,
                  iters: int = 8) -> Coarse2:
    """k-means over the cluster centroids -> grouped routing metadata.

    ``n_groups`` defaults to ``ceil(sqrt(nlist))`` (balances the two
    levels' GEMM costs).  Member lists are padded to the largest group.
    """
    cents = np.asarray(centroids, np.float32)
    nlist, d = cents.shape
    if n_groups is None:
        n_groups = max(int(math.ceil(math.sqrt(nlist))), 1)
    n_groups = min(int(n_groups), nlist)
    if n_groups < 1:
        raise ValueError(f"n_groups must be >= 1, got {n_groups}")
    km = kmeans(key, jnp.asarray(cents), k=n_groups, iters=iters)
    l1 = np.asarray(km.centroids, np.float32)
    assign = np.asarray(km.assign, np.int64)
    gmax = max(int(np.bincount(assign, minlength=n_groups).max()), 1)
    members = np.full((n_groups, gmax), -1, np.int32)
    cursor = np.zeros(n_groups, np.int64)
    for c in range(nlist):
        g = int(assign[c])
        members[g, cursor[g]] = c
        cursor[g] += 1
    # gathered member centroid rows (pad rows read centroid 0; their
    # distances are masked to +inf in locate, so the value is arbitrary)
    member_cents = cents[np.clip(members, 0, None)]
    member_cents = np.where(members[..., None] >= 0, member_cents, 0.0)
    return Coarse2(jnp.asarray(l1), jnp.asarray(members),
                   jnp.asarray(member_cents, jnp.float32))


@functools.partial(jax.jit, static_argnames=("nprobe", "nprobe1"))
@jax.named_scope("CL")
def coarse2_locate(coarse: Coarse2, queries: jax.Array, *, nprobe: int,
                   nprobe1: int):
    """Two-level CL: (Q, D) -> probe ids (Q, nprobe) + centroid dists.

    Same contract as :func:`repro.core.search.cluster_locate`; only the
    top ``nprobe1`` groups' member centroids are scored.  Distances use
    the same ``||q||^2 - 2 q.c + ||c||^2`` expansion (clamped at 0) as
    ``kmeans.l2_sq``, so at ``nprobe1 == n_groups`` the ranked candidate
    set matches flat CL's up to ties.
    """
    q = queries.astype(jnp.float32)
    nprobe1 = min(nprobe1, coarse.n_groups)
    # level 1: rank groups
    qq = jnp.sum(q * q, axis=-1, keepdims=True)              # (Q, 1)
    l1 = coarse.l1_centroids
    d1 = qq + jnp.sum(l1 * l1, axis=-1)[None, :] - 2.0 * (q @ l1.T)
    _, groups = jax.lax.top_k(-d1, nprobe1)                  # (Q, G1)
    # level 2: score only the selected groups' members
    cand = coarse.members[groups]                            # (Q, G1, gmax)
    cand = cand.reshape(q.shape[0], -1)                      # (Q, S)
    cc = coarse.member_centroids[groups]                     # (Q, G1, gmax, D)
    cc = cc.reshape(q.shape[0], -1, q.shape[1])              # (Q, S, D)
    d2 = (qq + jnp.sum(cc * cc, axis=-1)
          - 2.0 * jnp.einsum("qd,qsd->qs", q, cc))
    d2 = jnp.maximum(d2, 0.0)
    d2 = jnp.where(cand >= 0, d2, jnp.inf)                   # mask pads
    d, idx = smallest_k(d2, nprobe)          # (+inf, -1) past the candidates
    probes = jnp.take_along_axis(cand, jnp.maximum(idx, 0), axis=1)
    return jnp.where(idx >= 0, probes, -1).astype(jnp.int32), d


def routing_rows_touched(nlist: int, n_groups: int, gmax: int,
                         nprobe1: int) -> int:
    """Centroid-metadata rows one query's CL reads: flat = ``nlist``;
    two-level = ``n_groups + nprobe1 * gmax`` (the model term the docs
    and perf accounting quote)."""
    del nlist
    return int(n_groups) + int(nprobe1) * int(gmax)
