"""Single-device five-phase cluster-based ANNS pipeline (paper Fig. 1).

    CL  cluster locating      q x centroids GEMM + top-nprobe
    RC  residual computation  q - centroid[probe]
    LC  LUT construction      build_lut (or the Pallas lut_build kernel)
    DC  distance calculation  adc scan (or the Pallas pq_scan kernel)
    TS  top-k sorting         lax.top_k merge

The distributed engine (sharded_search.py) runs the same phases with
LC/DC/TS per shard and a final cross-shard merge.

Each phase's function carries its ``jax.named_scope`` where it is
defined (``CL``: ``cluster_locate``, ``cluster_locate_masked``,
``coarse2_locate``; ``RC``: ``residuals``; ``LC``: ``build_lut_batch``,
``quantize_lut``, the ``lut_build`` kernels; ``DC``: ``gather_probed``,
``adc_distances``, ``adc_distances_quantized``, the scan kernels; ``TS``:
``topk_smallest``), so every jit that calls them names its ops by phase
in the HLO ``op_name`` path, and a profiler trace can be split by phase.
Where one scope nests inside another (``merge_topk`` inside a fused scan,
say), the outermost phase in the ``op_name`` path owns the op.  The
scopes are metadata only: the compiled code is the same without them.

``use_kernels=True`` routes LC/DC through the Pallas kernels: interpreted
on the CPU backend, compiled by Mosaic elsewhere, where only
``strategy="onehot"`` compiles (the kernels ran on a TPU v5e in
``chip_smoke.py``).  The served default stays the jnp path
(``use_kernels=False``, ``strategy="gather"``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.kmeans import l2_sq
from repro.core.ivf import IVFPQIndex, PaddedClusters
from repro.core.adc import (build_lut_batch, adc_distances,
                            adc_distances_quantized, quantize_lut)
from repro.core.topk import smallest_k, topk_smallest

# The phase scopes live only in HLO metadata, which JAX's persistent
# compilation cache leaves out of its key by default: an executable built
# from the same computation without the scopes would then be handed back,
# and a profile of it would name no phase.  Keep metadata in the key, with
# source paths cut to their file names so that where a checkout lies does
# not change it.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
jax.config.update("jax_hlo_source_file_canonicalization_regex", r".*/")


class SearchParams(NamedTuple):
    nprobe: int
    k: int
    strategy: str = "gather"        # "gather" | "onehot" for the DC phase
    query_chunk: int = 256          # queries per scan step
    use_kernels: bool = False       # route LC/DC through Pallas kernels
    lut_dtype: str = "f32"          # "f32" | "uint8" quantized-LUT fast path


@jax.named_scope("CL")
def cluster_locate(queries: jax.Array, centroids: jax.Array, nprobe: int):
    """CL: (Q, D) x (nlist, D) -> probe ids (Q, nprobe) + centroid dists.
    With nprobe > nlist the surplus probes are -1 (distance +inf) and
    scan nothing (``gather_probed``)."""
    d, idx = smallest_k(l2_sq(queries, centroids), nprobe)
    return idx, d


@jax.named_scope("CL")
def cluster_locate_masked(queries: jax.Array, centroids: jax.Array,
                          nprobe: int, allowed: jax.Array):
    """CL over a per-query cluster mask (tenant namespaces, PR 10).

    ``allowed`` (Q, nlist) bool — disallowed centroids rank ``+inf`` so
    a tenant's probes land on its member clusters first; allowed
    clusters keep their exact distances AND their relative tie order, so
    the ranking matches a dedicated index holding only those clusters.
    When nprobe exceeds a tenant's member count the surplus probes fall
    on disallowed clusters, whose rows the scope mask strikes anyway.
    """
    d = l2_sq(queries, centroids)
    d = jnp.where(allowed, d, jnp.inf)
    d, idx = smallest_k(d, nprobe)
    return idx, d


@jax.named_scope("RC")
def residuals(queries: jax.Array, centroids: jax.Array, rotation,
              probes: jax.Array) -> jax.Array:
    """RC: (Q, D) queries and (Q, P) probes -> (Q*P, D) residuals
    ``query - centroid[probe]``, rotated where the index has a rotation."""
    residual = queries[:, None, :] - centroids[probes]           # (Q, P, D)
    if rotation is not None:
        residual = residual @ rotation
    return residual.reshape(probes.shape[0] * probes.shape[1], -1)


@jax.named_scope("DC")
def gather_probed(clusters: PaddedClusters, flat_probes: jax.Array):
    """Codes (T, C, M), ids (T, C) and sizes (T,) of the probed clusters.
    A -1 probe (nprobe > nlist) reads cluster 0 but gets size 0 and ids
    -1, so it scans nothing."""
    ok = flat_probes >= 0
    p = jnp.where(ok, flat_probes, 0)
    return (clusters.codes[p], jnp.where(ok[:, None], clusters.ids[p], -1),
            jnp.where(ok, clusters.sizes[p], 0))


def _search_chunk(queries, centroids, codebook, clusters: PaddedClusters,
                  rotation, params: SearchParams):
    q = queries.astype(jnp.float32)
    probes, _ = cluster_locate(q, centroids, params.nprobe)       # (Qc, P)
    qc, p = probes.shape
    flat_res = residuals(q, centroids, rotation, probes)          # (Qc*P, D)
    codes, ids, sizes = gather_probed(clusters, probes.reshape(-1))
    quantized = params.lut_dtype == "uint8"
    if params.use_kernels:
        from repro.kernels import ops as kops
        if quantized:                     # LC with fused quantize epilogue
            lut = kops.lut_build_q(flat_res, codebook.codebooks,
                                   codebook.sqnorms)
        else:
            lut = kops.lut_build(flat_res, codebook.codebooks,
                                 codebook.sqnorms)                # (QcP, M, CB)
        dists = kops.pq_scan_dc(lut, codes, sizes,
                                strategy=params.strategy)
    else:
        lut = build_lut_batch(codebook, flat_res)
        strat = "gather" if params.strategy == "gather" else "onehot"
        if quantized:
            dists = adc_distances_quantized(quantize_lut(lut), codes, sizes,
                                            strat)
        else:
            dists = adc_distances(lut, codes, sizes, strat)
    # TS: per query over all probed candidates
    cand_d = dists.reshape(qc, p * clusters.cmax)
    cand_i = ids.reshape(qc, p * clusters.cmax)
    best_d, best_i = topk_smallest(cand_d, cand_i, params.k)
    return best_d, best_i


@functools.partial(jax.jit, static_argnames=("params",))
def search_ivfpq(index: IVFPQIndex, clusters: PaddedClusters,
                 queries: jax.Array, params: SearchParams):
    """Full pipeline over (Q, D) queries, chunked with lax.map to bound the
    (Q*P, cmax) DC working set. Returns (dists (Q, k), ids (Q, k))."""
    n = queries.shape[0]
    chunk = min(params.query_chunk, n)
    pad = (-n) % chunk
    qpad = jnp.pad(queries, ((0, pad), (0, 0)))
    batches = qpad.reshape(-1, chunk, queries.shape[1])

    fn = functools.partial(_search_chunk, centroids=index.centroids,
                           codebook=index.codebook, clusters=clusters,
                           rotation=index.rotation, params=params)
    best_d, best_i = jax.lax.map(lambda qb: fn(qb), batches)
    best_d = best_d.reshape(-1, params.k)[:n]
    best_i = best_i.reshape(-1, params.k)[:n]
    return best_d, best_i


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def exact_search(points: jax.Array, queries: jax.Array, k: int,
                 chunk: int = 1024):
    """Brute-force oracle for recall measurement (chunked over queries)."""
    n = queries.shape[0]
    pad = (-n) % chunk
    qpad = jnp.pad(queries, ((0, pad), (0, 0)))

    def body(_, qb):
        d = l2_sq(qb, points)
        nd, idx = jax.lax.top_k(-d, k)
        return None, (-nd, idx.astype(jnp.int32))

    _, (dd, ii) = jax.lax.scan(body, None,
                               qpad.reshape(-1, chunk, queries.shape[1]))
    return dd.reshape(-1, k)[:n], ii.reshape(-1, k)[:n]


def recall_at_k(found_ids: jax.Array, true_ids: jax.Array) -> jax.Array:
    """recall@k: |found ∩ true| / k averaged over queries (paper metric,
    recall@10 >= 0.8 constraint)."""
    hits = (found_ids[:, :, None] == true_ids[:, None, :]).any(axis=2)
    # padding ids are -1 -> never match true ids (>=0)
    return jnp.mean(jnp.sum(hits, axis=1) / true_ids.shape[1])
