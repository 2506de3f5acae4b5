"""Online serving runtime: micro-batched streaming search over the engine.

Glues three pieces together:

  * :mod:`repro.runtime.batching` — coalesces single-query requests into
    fixed-shape padded micro-batches (bucketed so jit compiles once per
    bucket), flushing on deadline or on a full batch;
  * an engine behind the :class:`SearchEngine` protocol — either the
    single-device pipeline (:class:`LocalEngine` around
    ``core.search.search_ivfpq``) or the distributed one
    (:class:`ShardedEngine` around ``core.sharded_search``), both
    optionally backed by the hot-cluster LUT cache
    (:mod:`repro.runtime.cache`) that skips redundant LC work on skewed
    streams;
  * :class:`ServingRuntime` — submit/step online API plus a
    virtual-clock stream simulator with latency/throughput
    instrumentation (p50/p99, queue depth, batch occupancy, cache hit
    rate).

Units and shapes: timestamps and latencies are seconds on the caller's
clock (the simulator uses a virtual clock and charges real measured
engine time); queries are (D,) f32 per request, batched to (bucket, D);
results per request are ((k,) f32 distances, (k,) i32 ids).

Invariants:
  * every engine op is row-wise per query, so a request's result is
    independent of which micro-batch it rode in — de-padded served
    results match a direct batched ``search()`` call exactly (asserted
    in tests and ``examples/rag_serving.py``), including with the LUT
    cache enabled at exact granularity;
  * padding rows (``row >= n_valid``) never reach the LUT cache or the
    sharded engine's heat estimator — occupancy metrics and admission
    see only real traffic;
  * ``warmup`` compiles every bucket shape (and the sharded engine's
    per-bucket task-table shapes) without polluting cache entries, cache
    stats, or heat counts.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
import warnings
from typing import List, Optional, Protocol, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.adc import (QuantizedLUT, adc_distances,
                            adc_distances_quantized, build_lut_batch,
                            quantize_lut)
from repro.core.coarse2 import Coarse2, coarse2_locate
from repro.core.filter import NO_TAG, VectorMeta, mask_scoped_distances
from repro.core.ivf import IVFPQIndex, PaddedClusters
from repro.core.search import (SearchParams, cluster_locate,
                               cluster_locate_masked, gather_probed,
                               residuals, search_ivfpq)
from repro.core.topk import topk_smallest
from repro.runtime.batching import (BucketPolicy, MicroBatch, MicroBatcher,
                                    Request)
from repro.runtime.cache import (HotClusterLUTCache, lut_fill_misses,
                                 lut_miss_scan, precompile_lut_shapes,
                                 stack_lut_bank)


# ---------------------------------------------------------------------------
# Deprecation shims: direct construction of the engine adapters and the
# runtime still works but the supported front door is the service layer
# (repro.service.AnnService built from a ServiceSpec).  Each class warns
# once per process; the service layer builds inside
# ``service_construction()`` and never warns.
# ---------------------------------------------------------------------------

_DEPRECATION_WARNED: set = set()
_SUPPRESS_DEPRECATION = threading.local()


@contextlib.contextmanager
def service_construction():
    """Mark constructions issued by the service layer (no deprecation
    warning).  Re-entrant and thread-local."""
    prev = getattr(_SUPPRESS_DEPRECATION, "on", False)
    _SUPPRESS_DEPRECATION.on = True
    try:
        yield
    finally:
        _SUPPRESS_DEPRECATION.on = prev


def _warn_direct_use(name: str) -> None:
    if getattr(_SUPPRESS_DEPRECATION, "on", False):
        return
    if name in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(name)
    warnings.warn(
        f"Direct {name}(...) construction is deprecated; build through "
        f"repro.service.AnnService (AnnService.build(ServiceSpec(...))), "
        f"which owns the engine/runtime lifecycle. The old constructor "
        f"keeps working.", DeprecationWarning, stacklevel=3)


class SearchEngine(Protocol):
    """What the runtime needs from an engine: fixed k, batched search."""

    k: int

    def search_batch(self, queries: np.ndarray,
                     n_valid: Optional[int] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """(B, D) f32 -> ((B, k) dists, (B, k) ids), row-wise per query.

        ``n_valid``: rows >= n_valid are batch padding — engines may
        skip caching/accounting for them (results for those rows are
        discarded by the caller)."""
        ...


# ---------------------------------------------------------------------------
# Engine adapters
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("nprobe",))
def _cl_rc(queries, centroids, rotation, *, nprobe: int):
    """CL + RC for the cached path: (Q, D) -> probes (Q, P), flat residuals
    (Q*P, D).  Jitted per bucket shape like the main pipeline."""
    probes, _ = cluster_locate(queries, centroids, nprobe)
    return probes, residuals(queries, centroids, rotation, probes)


@functools.partial(jax.jit, static_argnames=("k", "strategy", "nprobe"))
def _dc_ts(lut, flat_probes, clusters: PaddedClusters, *, k: int,
           strategy: str, nprobe: int):
    """DC + TS over cache-assembled LUTs: (Q*P, M, CB) f32 — or a
    (Q*P,)-batched QuantizedLUT on the uint8 path — -> (Q, k) x2."""
    codes, ids, sizes = gather_probed(clusters, flat_probes)
    strat = "gather" if strategy == "gather" else "onehot"
    if isinstance(lut, QuantizedLUT):
        dists = adc_distances_quantized(lut, codes, sizes, strat)
        n_rows = lut.lut_q.shape[0]
    else:
        dists = adc_distances(lut, codes, sizes, strat)
        n_rows = lut.shape[0]
    nq = n_rows // nprobe
    cand_d = dists.reshape(nq, nprobe * clusters.cmax)
    cand_i = ids.reshape(nq, nprobe * clusters.cmax)
    return topk_smallest(cand_d, cand_i, k)


@jax.jit
def _rc_from_probes(queries, centroids, rotation, probes):
    """RC for externally-routed probes (two-level CL): (Q, D) + (Q, P)
    -> flat residuals (Q*P, D)."""
    return residuals(queries, centroids, rotation, probes)


@jax.jit
def _lc_tasks(codebook, flat_res):
    """Jitted LC for the task path: (T, D) residuals -> (T, M, CB) f32.

    ``build_lut_batch`` is an eager vmap — fine inside the fused
    ``search_ivfpq`` jit, but called op-by-op from ``_search_tasks`` its
    dispatch overhead dominated the whole batch (several ms against a
    sub-ms scan), which pushed the scoped/tiered paths past the
    PIM-paced service model under replica contention."""
    return build_lut_batch(codebook, flat_res)


@jax.jit
def _lc_tasks_u8(codebook, flat_res):
    """`_lc_tasks` fused with uint8 LUT quantization."""
    return quantize_lut(build_lut_batch(codebook, flat_res))


@functools.partial(jax.jit, static_argnames=("k", "strategy", "nprobe"))
def _dc_ts_tasks(lut, codes, ids, sizes, *, k: int, strategy: str,
                 nprobe: int):
    """DC + TS over *pre-gathered* task tensors — the tiered fetch path.

    Identical math to :func:`_dc_ts`, but the (Q*P, cmax, M) codes /
    (Q*P, cmax) ids / (Q*P,) sizes arrive from the host (TieredStore
    resident-slab rows + mmap cold reads) instead of being gathered from
    a device-resident ``PaddedClusters`` — the engine never materializes
    the full code tensor.  Because the tier's per-cluster capacity equals
    ``pad_clusters``'s cmax and sizes mask the scan the same way, results
    are bit-identical to the all-resident gather."""
    strat = "gather" if strategy == "gather" else "onehot"
    if isinstance(lut, QuantizedLUT):
        dists = adc_distances_quantized(lut, codes, sizes, strat)
        n_rows = lut.lut_q.shape[0]
    else:
        dists = adc_distances(lut, codes, sizes, strat)
        n_rows = lut.shape[0]
    nq = n_rows // nprobe
    cmax = codes.shape[1]
    cand_d = dists.reshape(nq, nprobe * cmax)
    cand_i = ids.reshape(nq, nprobe * cmax)
    return topk_smallest(cand_d, cand_i, k)


@functools.partial(jax.jit, static_argnames=("k", "strategy", "nprobe"))
def _dc_ts_scoped(lut, flat_probes, clusters: PaddedClusters, meta_tenant,
                  meta_tags, q_tenants, q_terms, *, k: int, strategy: str,
                  nprobe: int):
    """Scoped :func:`_dc_ts` (PR 10): same DC math, then the tenant /
    predicate mask strikes out-of-scope candidate rows to ``+inf`` (and
    id -1) before TS — the same discipline the sizes mask uses, so
    filtered top-k is exact over the matching rows."""
    codes, ids, sizes = gather_probed(clusters, flat_probes)
    strat = "gather" if strategy == "gather" else "onehot"
    if isinstance(lut, QuantizedLUT):
        dists = adc_distances_quantized(lut, codes, sizes, strat)
        n_rows = lut.lut_q.shape[0]
    else:
        dists = adc_distances(lut, codes, sizes, strat)
        n_rows = lut.shape[0]
    nq = n_rows // nprobe
    cand_d = dists.reshape(nq, nprobe * clusters.cmax)
    cand_i = ids.reshape(nq, nprobe * clusters.cmax)
    cand_d = mask_scoped_distances(cand_d, cand_i, meta_tenant, meta_tags,
                                   q_tenants, q_terms)
    bd, bi = topk_smallest(cand_d, cand_i, k)
    return bd, jnp.where(jnp.isfinite(bd), bi, -1)


@functools.partial(jax.jit, static_argnames=("k", "strategy", "nprobe"))
def _dc_ts_tasks_scoped(lut, codes, ids, sizes, meta_tenant, meta_tags,
                        q_tenants, q_terms, *, k: int, strategy: str,
                        nprobe: int):
    """Scoped :func:`_dc_ts_tasks` — the tiered fetch path with the
    tenant/predicate mask applied before TS (see ``_dc_ts_scoped``)."""
    strat = "gather" if strategy == "gather" else "onehot"
    if isinstance(lut, QuantizedLUT):
        dists = adc_distances_quantized(lut, codes, sizes, strat)
        n_rows = lut.lut_q.shape[0]
    else:
        dists = adc_distances(lut, codes, sizes, strat)
        n_rows = lut.shape[0]
    nq = n_rows // nprobe
    cmax = codes.shape[1]
    cand_d = dists.reshape(nq, nprobe * cmax)
    cand_i = ids.reshape(nq, nprobe * cmax)
    cand_d = mask_scoped_distances(cand_d, cand_i, meta_tenant, meta_tags,
                                   q_tenants, q_terms)
    bd, bi = topk_smallest(cand_d, cand_i, k)
    return bd, jnp.where(jnp.isfinite(bd), bi, -1)


@functools.partial(jax.jit,
                   static_argnames=("k", "strategy", "nprobe", "lut_u8"))
def _scoped_search_fused(queries, centroids, rotation, codebook,
                         clusters: PaddedClusters, allowed, meta_tenant,
                         meta_tags, q_tenants, q_terms, *, k: int,
                         strategy: str, nprobe: int, lut_u8: bool):
    """The whole scoped five-phase pipeline in one jit (PR 10).

    Running the scoped phases as separate jits (masked CL, RC, LC,
    DC/TS) plus the host roundtrips between them cost several ms of
    dispatch per batch — more than the Eq. 15 modeled service time, so
    paced scoped serving was compute-bound where unscoped serving was
    model-bound.  The all-resident no-cache scoped path fuses to one
    dispatch here; the tiered / LUT-cached scoped paths keep the staged
    ``_search_tasks`` route (their host-side fetch is the point).  Same
    ops in the same order as the staged path: masked CL, RC, LC, DC,
    scope mask, TS, id epilogue.
    """
    probes, _ = cluster_locate_masked(queries, centroids, nprobe, allowed)
    flat_res = residuals(queries, centroids, rotation, probes)
    lut = build_lut_batch(codebook, flat_res)
    if lut_u8:
        lut = quantize_lut(lut)
    flat_probes = probes.reshape(-1)
    codes, ids, sizes = gather_probed(clusters, flat_probes)
    strat = "gather" if strategy == "gather" else "onehot"
    if lut_u8:
        dists = adc_distances_quantized(lut, codes, sizes, strat)
    else:
        dists = adc_distances(lut, codes, sizes, strat)
    nq = queries.shape[0]
    cand_d = dists.reshape(nq, nprobe * clusters.cmax)
    cand_i = ids.reshape(nq, nprobe * clusters.cmax)
    cand_d = mask_scoped_distances(cand_d, cand_i, meta_tenant, meta_tags,
                                   q_tenants, q_terms)
    bd, bi = topk_smallest(cand_d, cand_i, k)
    return bd, jnp.where(jnp.isfinite(bd), bi, -1)


def _fetch(x) -> np.ndarray:
    """One device->host sync of a served path (an ``ann.fetch`` span)."""
    with jax.profiler.TraceAnnotation("ann.fetch"):
        return np.asarray(x)


class LocalEngine:
    """Single-device five-phase pipeline behind the serving protocol.

    With ``lut_cache`` set, the LC phase consults the hot-cluster LUT
    cache per (query, probed cluster) pair and only computes LUTs for
    misses (one batched ``build_lut_batch`` over the miss rows); RC/DC/TS
    are unchanged, so at exact granularity results are bit-identical to
    the uncached path.

    Live-index support: ``(index, clusters)`` live in one ``_view`` tuple
    read exactly once per batch, and ``install`` swaps the whole tuple —
    a single atomic attribute store — so a mutation landing mid-batch
    can never mix old centroids with new codes.  ``install`` with a new
    *index* (a generation swap: centroids/codebooks changed) also bumps
    the view generation that salts every LUT-cache bucket, so a stale
    in-flight batch cannot poison the cache for the new generation.
    """

    def __init__(self, index: IVFPQIndex, clusters: Optional[PaddedClusters],
                 params: SearchParams,
                 lut_cache: Optional[HotClusterLUTCache] = None,
                 tiered_store=None,
                 coarse: Optional[Coarse2] = None,
                 coarse_nprobe1: int = 0,
                 meta: Optional[VectorMeta] = None):
        _warn_direct_use("LocalEngine")
        if (lut_cache is not None
                and getattr(lut_cache, "lut_dtype", "f32")
                != params.lut_dtype):
            raise ValueError(
                f"lut_cache.lut_dtype={lut_cache.lut_dtype!r} disagrees "
                f"with SearchParams.lut_dtype={params.lut_dtype!r}; cached "
                f"and uncached scans must run the same dtype")
        if clusters is None and tiered_store is None:
            raise ValueError("clusters may be omitted only with a "
                             "tiered_store (codes then live in the tier)")
        self._view = (index, clusters, 0)
        self.params = params
        self.lut_cache = lut_cache
        # tiered storage (repro.storage.TieredStore): CL routes as usual,
        # then codes/ids/sizes for the probed clusters are fetched from
        # the RAM-resident slab or the mmap spill file — the engine holds
        # no full PaddedClusters, which is the beyond-memory point
        self.tiered_store = tiered_store
        # two-level coarse quantizer: when set, CL ranks only the top
        # coarse_nprobe1 groups' member centroids instead of all nlist
        self.coarse = coarse
        self.coarse_nprobe1 = (int(coarse_nprobe1) if coarse_nprobe1
                               else (coarse.n_groups if coarse is not None
                                     else 0))
        self.k = params.k
        # per-vector metadata for tenant-scoped / predicate-filtered
        # search (PR 10); None = the legacy single-tenant engine
        self.meta = meta
        # per-batch degrade report, re-stamped by every search_batch call;
        # the serving runtime reads it to flag requests as degraded
        self.last_batch_info: dict = {"degraded": False, "dropped_probes": 0}

    # the (index, clusters) pair is one atomic view; the split properties
    # keep the long-standing attribute surface working
    @property
    def index(self) -> IVFPQIndex:
        return self._view[0]

    @index.setter
    def index(self, index: IVFPQIndex) -> None:
        self.install(index=index)

    @property
    def clusters(self) -> PaddedClusters:
        return self._view[1]

    @clusters.setter
    def clusters(self, clusters: PaddedClusters) -> None:
        self.install(clusters=clusters)

    @property
    def view_generation(self) -> int:
        return self._view[2]

    def install(self, index: Optional[IVFPQIndex] = None,
                clusters: Optional[PaddedClusters] = None) -> None:
        """Atomically swap the engine onto new index tensors.

        ``clusters``-only installs are plain data mutations (upserts /
        deletes): LUTs depend only on (query, centroid, codebook), so
        cached entries stay valid.  Passing ``index`` means the
        quantizers changed (a maintenance generation) — the view
        generation is bumped so cache keys from older views can never be
        hit again, even by a batch that was in flight across the swap."""
        cur_index, cur_clusters, gen = self._view
        self._view = (index if index is not None else cur_index,
                      clusters if clusters is not None else cur_clusters,
                      gen + 1 if index is not None else gen)

    def search_batch(self, queries: np.ndarray,
                     n_valid: Optional[int] = None,
                     budget_s: Optional[float] = None,
                     tenants: Optional[np.ndarray] = None,
                     terms: Optional[np.ndarray] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
        index, clusters, _ = self._view
        self.last_batch_info = {"degraded": False, "dropped_probes": 0}
        scope = self._make_scope(tenants, terms)
        staged = self.tiered_store is not None or self.lut_cache is not None
        if scope is not None:
            path = "tasks" if staged else "scoped"
        elif self.tiered_store is not None or self.coarse is not None:
            path = "tasks"
        else:
            path = "cached" if self.lut_cache is not None else "fused"
        with jax.profiler.TraceAnnotation("ann.engine", path=path):
            if path == "scoped":
                # all-resident, no cache: one fused dispatch (an
                # all-true row of ``allowed`` reduces masked CL to
                # plain CL exactly, so unscoped tenants in a mixed
                # batch rank identically to the fast path)
                p = self.params
                allowed = self.meta.allowed_for(
                    scope[4], index.centroids.shape[0])
                bd, bi = _scoped_search_fused(
                    jnp.asarray(queries, jnp.float32), index.centroids,
                    index.rotation, index.codebook, clusters,
                    jnp.asarray(allowed), scope[0], scope[1], scope[2],
                    scope[3], k=p.k, strategy=p.strategy,
                    nprobe=p.nprobe, lut_u8=p.lut_dtype == "uint8")
                return _fetch(bd), _fetch(bi)
            if path == "tasks":
                # tiered and two-level routing, and scoped traffic on a
                # tier or LUT cache: the staged task path (same LC/DC
                # math; scoped rows masked before TS)
                return self._search_tasks(np.asarray(queries, np.float32),
                                          n_valid, budget_s, scope=scope)
            if path == "fused":
                d, i = search_ivfpq(index, clusters,
                                    jnp.asarray(queries, jnp.float32),
                                    self.params)
                return _fetch(d), _fetch(i)
            return self._search_cached(np.asarray(queries, np.float32),
                                       n_valid)

    def _make_scope(self, tenants, terms):
        """Package per-query scope arrays (PR 10 tenant namespaces and
        predicate filters) for the scoped scan variants.

        Returns None when the batch carries no scope at all, so legacy
        traffic stays on the exact pre-tenancy code paths (bit-compat).
        The scope tuple is ``(meta_tenant, meta_tags, q_tenants_dev,
        q_terms_dev, q_tenants_host)`` — device tables are
        version-cached on the VectorMeta so a steady state re-transfers
        nothing."""
        if tenants is None and terms is None:
            return None
        if self.meta is None:
            raise ValueError(
                "tenant/filtered search needs an engine built with "
                "per-vector metadata (ServiceSpec tenants / tagged "
                "upserts); this engine has meta=None")
        if self.coarse is not None:
            raise ValueError("scoped search is not supported with the "
                             "two-level coarse router (spec validation "
                             "rejects tenants + coarse_groups)")
        if tenants is None:
            tenants = np.full(len(terms), -1, np.int32)
        tenants = np.asarray(tenants, np.int32)
        if terms is None:
            terms = np.full((tenants.shape[0], self.meta.tag_fields),
                            NO_TAG, np.uint32)
        terms = np.asarray(terms, np.uint32)
        mt, mg = self.meta.device_tables()
        return (mt, mg, jnp.asarray(tenants), jnp.asarray(terms), tenants)

    def serving_info(self) -> dict:
        """Engine-side metrics block (tier residency, routing mode)."""
        out: dict = {"engine": "local"}
        if self.coarse is not None:
            out["coarse"] = {"n_groups": self.coarse.n_groups,
                             "nprobe1": self.coarse_nprobe1}
        if self.tiered_store is not None:
            out["tier"] = self.tiered_store.serving_info()
        return out

    def precompile_lc(self, max_rows: int) -> None:
        """Compile the cached path's miss-batch LC shapes (pow2 up to
        ``max_rows``) ahead of traffic — a first-seen miss count would
        otherwise pay its XLA compile mid-stream."""
        precompile_lut_shapes(self.index.codebook, max_rows,
                              lut_dtype=self.params.lut_dtype)

    def _search_cached(self, queries: np.ndarray,
                       n_valid: Optional[int] = None):
        """CL/RC and DC/TS jitted (once per bucket shape); LC goes through
        the cache host-side (``cache.lut_miss_scan``/``lut_fill_misses``),
        batching LUT construction over miss rows.  Padding rows
        (>= n_valid) bypass the cache entirely — they must not occupy LRU
        slots or distort hit-rate accounting."""
        p = self.params
        index, clusters, vgen = self._view    # one atomic read per batch
        probes, flat_res = _cl_rc(jnp.asarray(queries), index.centroids,
                                  index.rotation, nprobe=p.nprobe)
        probes_np = _fetch(probes)                         # (Q, P)
        nq, npr = probes_np.shape
        flat_probes = probes_np.reshape(-1)
        n_valid_q = n_valid if n_valid is not None else nq
        # one hash per (valid) query, reused across its nprobe cache
        # keys; the view generation salts the bucket so entries from a
        # superseded generation (older centroids/codebooks) can never hit
        buckets = [(vgen, self.lut_cache.bucket_of(queries[qi]))
                   for qi in range(n_valid_q)]
        luts, miss_rows = lut_miss_scan(self.lut_cache, flat_probes,
                                        buckets, npr, nq * npr)
        if miss_rows:
            flat_res_np = _fetch(flat_res)
            lut_fill_misses(self.lut_cache, index.codebook, luts,
                            miss_rows, flat_probes, buckets, npr,
                            flat_res_np[miss_rows])
        lut = stack_lut_bank(luts)            # (QP, M, CB) or QuantizedLUT
        bd, bi = _dc_ts(lut, jnp.asarray(flat_probes), clusters,
                        k=p.k, strategy=p.strategy, nprobe=npr)
        return _fetch(bd), _fetch(bi)

    def _route(self, queries_j, index):
        """CL + RC, flat or two-level: -> (probes (Q, P), flat residuals).

        With a :class:`~repro.core.coarse2.Coarse2` installed, routing
        scores ``n_groups + nprobe1 * gmax`` centroid rows instead of all
        ``nlist`` — at ``nprobe1 == n_groups`` the probe set matches flat
        CL (the parity default when ``coarse_nprobe1`` is unset)."""
        p = self.params
        if self.coarse is None:
            return _cl_rc(queries_j, index.centroids, index.rotation,
                          nprobe=p.nprobe)
        probes, _ = coarse2_locate(self.coarse, queries_j,
                                   nprobe=p.nprobe,
                                   nprobe1=self.coarse_nprobe1)
        flat_res = _rc_from_probes(queries_j, index.centroids,
                                   index.rotation, probes)
        return probes, flat_res

    def _search_tasks(self, queries: np.ndarray,
                      n_valid: Optional[int] = None,
                      budget_s: Optional[float] = None,
                      scope=None):
        """Tiered / two-level path: route, fetch task tensors through the
        tier (resident slab hit or batched mmap cold read), scan.

        Probe heat from valid rows feeds the tier's residency controller
        *before* the fetch, so a sustained shift promotes clusters ahead
        of — not after — the reads that want them.  Cold reads within the
        batch are deduplicated and fetched in one memmap gather
        (``TieredStore.gather``), i.e. per-probe misses batch per flush.

        Fail-operational: the fetch runs through
        ``TieredStore.gather_degraded`` — probes the tier cannot serve
        (cold-read IOError, quarantined clusters, or *all* cold probes
        when ``budget_s`` says the predicted cold-read cost would blow
        the deadline) come back with ``size == 0`` and the scan's
        n_valid masking yields a result exact over what was scanned.
        The batch is then reported degraded via ``last_batch_info``.
        """
        p = self.params
        index, clusters, vgen = self._view    # one atomic read per batch
        queries_j = jnp.asarray(queries)
        if scope is not None and (scope[4] >= 0).any():
            # tenant namespaces: CL ranks only the tenant's member
            # clusters (per-tenant cluster bitmap), so nprobe probes land
            # where that tenant's rows actually live
            allowed = self.meta.allowed_for(scope[4],
                                            index.centroids.shape[0])
            probes, _ = cluster_locate_masked(queries_j, index.centroids,
                                              p.nprobe,
                                              jnp.asarray(allowed))
            flat_res = _rc_from_probes(queries_j, index.centroids,
                                       index.rotation, probes)
        else:
            probes, flat_res = self._route(queries_j, index)
        probes_np = _fetch(probes)                         # (Q, P)
        nq, npr = probes_np.shape
        flat_probes = probes_np.reshape(-1)
        n_valid_q = n_valid if n_valid is not None else nq
        tier = self.tiered_store
        if tier is not None and n_valid_q > 0:
            tier.observe(probes_np[:n_valid_q])
        if self.lut_cache is not None:
            buckets = [(vgen, self.lut_cache.bucket_of(queries[qi]))
                       for qi in range(n_valid_q)]
            luts, miss_rows = lut_miss_scan(self.lut_cache, flat_probes,
                                            buckets, npr, nq * npr)
            if miss_rows:
                flat_res_np = _fetch(flat_res)
                lut_fill_misses(self.lut_cache, index.codebook, luts,
                                miss_rows, flat_probes, buckets, npr,
                                flat_res_np[miss_rows])
            lut = stack_lut_bank(luts)
        else:
            lut = (_lc_tasks_u8(index.codebook, flat_res)
                   if p.lut_dtype == "uint8"
                   else _lc_tasks(index.codebook, flat_res))
        if tier is not None:
            # deadline-at-risk check: if the predicted cold-fetch cost
            # (online EWMA of measured mmap reads) would overrun the
            # remaining budget, drop cold probes and serve resident-only
            resident_only = False
            if budget_s is not None:
                cold_ids = flat_probes[~tier.resident_mask[flat_probes]]
                n_cold = int(np.unique(cold_ids).size)
                if n_cold and (budget_s <= 0 or
                               tier.estimate_cold_seconds(n_cold)
                               > budget_s):
                    resident_only = True
            codes, ids, sizes, dropped = tier.gather_degraded(
                flat_probes, resident_only=resident_only)
            n_dropped = int(dropped[:n_valid_q * npr].sum())
            if n_dropped:
                self.last_batch_info = {"degraded": True,
                                        "dropped_probes": n_dropped}
            if scope is not None:
                bd, bi = _dc_ts_tasks_scoped(
                    lut, jnp.asarray(codes), jnp.asarray(ids),
                    jnp.asarray(sizes), scope[0], scope[1], scope[2],
                    scope[3], k=p.k, strategy=p.strategy, nprobe=npr)
            else:
                bd, bi = _dc_ts_tasks(lut, jnp.asarray(codes),
                                      jnp.asarray(ids), jnp.asarray(sizes),
                                      k=p.k, strategy=p.strategy,
                                      nprobe=npr)
        elif scope is not None:
            bd, bi = _dc_ts_scoped(lut, jnp.asarray(flat_probes), clusters,
                                   scope[0], scope[1], scope[2], scope[3],
                                   k=p.k, strategy=p.strategy, nprobe=npr)
        else:
            bd, bi = _dc_ts(lut, jnp.asarray(flat_probes), clusters,
                            k=p.k, strategy=p.strategy, nprobe=npr)
        return _fetch(bd), _fetch(bi)


class ShardedEngine:
    """``core.sharded_search.DistributedEngine`` behind the protocol.

    ``search(flush=True)`` drains deferred tasks, so each batch returns
    complete results; per-query merge makes rows independent of batch
    composition, which is what the de-padding invariant needs.

    The serving-v2 collaborators live on the wrapped engine; this adapter
    only forwards them (``lut_cache`` as a settable property so warmup's
    throwaway-cache swap reaches the engine, ``n_valid`` so padding rows
    stay out of the cache and the heat estimator).
    """

    def __init__(self, engine):
        _warn_direct_use("ShardedEngine")
        self.engine = engine
        self.k = engine.cfg.k

    @property
    def lut_cache(self):
        return self.engine.lut_cache

    @lut_cache.setter
    def lut_cache(self, cache):
        self.engine.lut_cache = cache

    @property
    def nprobe(self) -> int:
        return self.engine.cfg.nprobe

    def precompile_lc(self, max_rows: int) -> None:
        self.engine.precompile_lc(max_rows)

    def serving_info(self) -> dict:
        return self.engine.serving_info()

    @property
    def last_batch_info(self) -> dict:
        return getattr(self.engine, "last_batch_info",
                       {"degraded": False, "dropped_probes": 0})

    @property
    def meta(self):
        return getattr(self.engine, "meta", None)

    def search_batch(self, queries: np.ndarray,
                     n_valid: Optional[int] = None,
                     budget_s: Optional[float] = None,
                     tenants: Optional[np.ndarray] = None,
                     terms: Optional[np.ndarray] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
        kw: dict = {}
        if tenants is not None or terms is not None:
            kw["tenants"], kw["terms"] = tenants, terms
        d, i, _info = self.engine.search(jnp.asarray(queries, jnp.float32),
                                         n_valid=n_valid,
                                         budget_s=budget_s, **kw)
        return np.asarray(d), np.asarray(i)


class PimPacedEngine:
    """Pace an engine's service time to a modeled DRAM-PIM latency.

    The dev box running this repro is not the target hardware: XLA-on-CPU
    timings say nothing about a PIM fleet's capacity, and on a small
    host one replica's compute can saturate every core, hiding the
    fleet-scaling behavior the service tier exists to deliver.  This
    wrapper is the hardware-in-the-loop answer: the inner engine computes
    the *exact* results, then the wrapper sleeps out the remainder of the
    batch's modeled service time (Eq. 15 per-task latency on the UPMEM
    profile, ``ceil(n_valid * nprobe / ranks)`` serial task waves over
    the replica's ``ranks`` DPU ranks).  Sleeping holds no lock and burns
    no CPU, so N paced replicas overlap on any host exactly as N real
    PIM-rank fleets would — wall-clock serving experiments (executor
    overlap, autoscaling, routing) become deterministic-ish and
    reproducible anywhere.

    Results are bit-identical to the inner engine; only timing changes.
    Warmup batches (``n_valid=0``) are never paced.
    """

    def __init__(self, engine: "SearchEngine", nprobe: int, ranks: int,
                 task_latency_s: float):
        if ranks < 1:
            raise ValueError(f"ranks must be >= 1, got {ranks}")
        if task_latency_s <= 0:
            raise ValueError(f"task_latency_s must be positive, "
                             f"got {task_latency_s}")
        self.engine = engine
        self.k = engine.k
        self.nprobe = int(nprobe)
        self.ranks = int(ranks)
        self.task_latency_s = float(task_latency_s)
        self.paced_batches = 0

    def batch_latency_s(self, n_valid: int) -> float:
        """Modeled service time for a batch of ``n_valid`` queries."""
        tasks = n_valid * self.nprobe
        waves = -(-tasks // self.ranks)
        return waves * self.task_latency_s

    # the serving runtime's optional engine hooks forward to the inner
    # engine (lut_cache as a real property so warmup's throwaway-cache
    # swap reaches the engine that actually consults it)
    @property
    def lut_cache(self):
        return getattr(self.engine, "lut_cache", None)

    @lut_cache.setter
    def lut_cache(self, cache):
        self.engine.lut_cache = cache

    def __getattr__(self, name):
        if name == "engine":        # guard: never recurse pre-__init__
            raise AttributeError(name)
        return getattr(self.engine, name)

    def search_batch(self, queries: np.ndarray,
                     n_valid: Optional[int] = None,
                     budget_s: Optional[float] = None,
                     tenants: Optional[np.ndarray] = None,
                     terms: Optional[np.ndarray] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
        t0 = time.perf_counter()
        kw = {k: v for k, v in (("budget_s", budget_s),
                                ("tenants", tenants),
                                ("terms", terms)) if v is not None}
        d, i = self.engine.search_batch(queries, n_valid=n_valid, **kw)
        n = n_valid if n_valid is not None else len(queries)
        if n > 0:
            remaining = self.batch_latency_s(n) - (time.perf_counter() - t0)
            if remaining > 0:
                time.sleep(remaining)
            self.paced_batches += 1
        return d, i


# ---------------------------------------------------------------------------
# Instrumentation
# ---------------------------------------------------------------------------

def _percentile(xs: Sequence[float], pct: float) -> float:
    if not xs:
        return float("nan")
    return float(np.percentile(np.asarray(xs, np.float64), pct))


@dataclasses.dataclass
class BatchRecord:
    bucket: int
    n_valid: int
    reason: str
    service_s: float
    t_flush: float


class ServingStats:
    """Per-request latency + per-batch occupancy/service accounting.

    Thread-safe: arrivals are recorded on the submitting (router) thread
    while batch/done records come from the replica's executor worker, so
    one lock guards the lists and ``summary()`` reads a consistent
    snapshot."""

    def __init__(self):
        self.latencies_s: List[float] = []
        self.batches: List[BatchRecord] = []
        self.queue_depths: List[int] = []
        self.t_first_arrival: Optional[float] = None
        self.t_last_done: Optional[float] = None
        self.degraded_requests = 0
        self.deadline_missed = 0
        # per-tenant latency rollups (PR 10): tenant id -> latency list;
        # unscoped requests (tenant -1) stay out of the breakdown
        self.tenant_latencies: dict = {}
        self._lock = threading.Lock()

    def record_arrival(self, req: Request, depth: int) -> None:
        with self._lock:
            if (self.t_first_arrival is None
                    or req.t_arrival < self.t_first_arrival):
                self.t_first_arrival = req.t_arrival
            self.queue_depths.append(depth)

    def record_batch(self, batch: MicroBatch, service_s: float) -> None:
        with self._lock:
            self.batches.append(BatchRecord(batch.bucket, batch.n_valid,
                                            batch.reason, service_s,
                                            batch.t_flush))

    def record_done(self, req: Request) -> None:
        with self._lock:
            self.latencies_s.append(req.latency_s)
            if req.tenant >= 0:
                self.tenant_latencies.setdefault(req.tenant,
                                                 []).append(req.latency_s)
            if req.degraded:
                self.degraded_requests += 1
            if req.deadline_missed:
                self.deadline_missed += 1
            if self.t_last_done is None or req.t_done > self.t_last_done:
                self.t_last_done = req.t_done

    def recent_latencies(self, n: int = 64) -> List[float]:
        """Last ``n`` served latencies (the autoscaler's p99 window)."""
        with self._lock:
            return self.latencies_s[-n:]

    def summary(self) -> dict:
        with self._lock:
            n = len(self.latencies_s)
            span = ((self.t_last_done - self.t_first_arrival)
                    if n and self.t_last_done is not None else 0.0)
            slots = sum(b.bucket for b in self.batches)
            valid = sum(b.n_valid for b in self.batches)
            reasons = {"full": 0, "deadline": 0, "drain": 0}
            for b in self.batches:
                reasons[b.reason] += 1
            return self._summary_locked(n, span, slots, valid, reasons)

    def _summary_locked(self, n, span, slots, valid, reasons) -> dict:
        tenants = {
            int(t): {
                "requests": len(ls),
                "p50_ms": _percentile(ls, 50) * 1e3,
                "p99_ms": _percentile(ls, 99) * 1e3,
                "qps": len(ls) / span if span > 0 else float("nan"),
            } for t, ls in sorted(self.tenant_latencies.items())}
        return {
            **({"tenants": tenants} if tenants else {}),
            "requests": n,
            "batches": len(self.batches),
            "p50_ms": _percentile(self.latencies_s, 50) * 1e3,
            "p99_ms": _percentile(self.latencies_s, 99) * 1e3,
            "mean_ms": (float(np.mean(self.latencies_s)) * 1e3
                        if n else float("nan")),
            "qps": n / span if span > 0 else float("nan"),
            "avg_batch_occupancy": valid / slots if slots else float("nan"),
            "pad_fraction": (slots - valid) / slots if slots else 0.0,
            "mean_queue_depth": (float(np.mean(self.queue_depths))
                                 if self.queue_depths else 0.0),
            "max_queue_depth": (max(self.queue_depths)
                                if self.queue_depths else 0),
            "flushes": reasons,
            "degraded_requests": self.degraded_requests,
            "deadline_missed": self.deadline_missed,
        }


# ---------------------------------------------------------------------------
# Runtime
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServingConfig:
    """Bucket-policy and flush knobs (see README §serving).

    ``deadline_s`` > 0 arms deadline-bounded serving: each batch's
    budget is ``oldest arrival + deadline_s - service start``, passed to
    the engine so it can degrade (drop cold disk probes) rather than
    blow the deadline, and every served request is stamped
    ``deadline_missed`` when its completion still ran past the budget.
    """
    buckets: Tuple[int, ...] = (1, 2, 4, 8, 16, 32)
    max_wait_s: float = 2e-3          # deadline flush bound
    max_batch: Optional[int] = None   # default: largest bucket
    deadline_s: float = 0.0           # 0 = no per-request deadline
    filter_width: int = 4             # predicate terms per query (PR 10)

    def make_batcher(self) -> MicroBatcher:
        return MicroBatcher(BucketPolicy(self.buckets),
                            max_wait_s=self.max_wait_s,
                            max_batch=self.max_batch)


class BatchServeError(RuntimeError):
    """An engine raised mid-batch.  Carries the flushed batch so the
    caller (the replica executor) can fail or retry exactly the requests
    that rode in it — no other in-flight request is affected."""

    def __init__(self, batch: MicroBatch, cause: BaseException):
        super().__init__(f"engine failed serving a {batch.bucket}-slot "
                         f"batch ({batch.n_valid} live requests): {cause!r}")
        self.batch = batch
        self.cause = cause


class ServingRuntime:
    """Single-server online loop: submit -> micro-batch -> engine -> depad.

    Two usage modes:
      * online:  ``submit(q, now)`` + ``step(now)`` under a caller clock;
      * offline: ``run_stream([(t, q), ...])`` replays a timestamped
        arrival trace on a virtual clock, charging each batch its real
        measured engine service time — honest p50/p99 vs offered load.
    """

    def __init__(self, engine: SearchEngine,
                 config: Optional[ServingConfig] = None):
        _warn_direct_use("ServingRuntime")
        self.engine = engine
        self.config = config or ServingConfig()
        self.batcher = self.config.make_batcher()
        self.stats = ServingStats()
        # chaos hooks (repro.runtime.faults): the service stamps these
        # when an injector is armed; None costs one attribute load
        self.faults = None
        self.replica_idx: Optional[int] = None

    def warmup(self, d: int) -> None:
        """Compile every bucket shape once (zero queries) so the first
        real batch per bucket isn't charged jit time.  Warmup batches are
        all-padding (``n_valid=0``) so they never touch the cache or the
        heat estimator; a throwaway LUT cache additionally stands in for
        the real one so engines that ignore ``n_valid`` still can't
        pollute entries or stats."""
        cache = getattr(self.engine, "lut_cache", None)
        if cache is not None:
            # same granularity AND lut_dtype as the real cache, so warmup
            # compiles the exact bank dtype/shape set traffic will use
            self.engine.lut_cache = HotClusterLUTCache(
                capacity=len(self.batcher.policy.buckets) * 64,
                granularity=cache.granularity,
                lut_dtype=getattr(cache, "lut_dtype", "f32"))
        try:
            for b in self.batcher.policy.buckets:
                self.engine.search_batch(np.zeros((b, d), np.float32),
                                         n_valid=0)
            if getattr(self.engine, "meta", None) is not None:
                # scoped traffic runs distinct jit signatures (masked CL
                # + scoped DC/TS); compile those per bucket too, with a
                # tenant id present so the masked-CL branch is exercised
                w = self.config.filter_width
                for b in self.batcher.policy.buckets:
                    self.engine.search_batch(
                        np.zeros((b, d), np.float32), n_valid=0,
                        tenants=np.zeros(b, np.int32),
                        terms=np.full((b, w), NO_TAG, np.uint32))
            precompile = getattr(self.engine, "precompile_lc", None)
            if cache is not None and precompile is not None:
                nprobe = (getattr(self.engine, "nprobe", None)
                          or getattr(getattr(self.engine, "params", None),
                                     "nprobe", 1))
                precompile(self.batcher.policy.max_batch * nprobe)
        finally:
            if cache is not None:
                self.engine.lut_cache = cache

    # -- online API --------------------------------------------------------
    def submit(self, query: np.ndarray, now: float,
               attach=None, tenant: int = -1,
               terms: Tuple[int, ...] = ()) -> Request:
        """Queue one request; ``attach(req)`` binds a future under the
        batcher lock (see ``MicroBatcher.submit``).  ``tenant`` >= 0
        scopes the search to that tenant's namespace; ``terms`` are
        predicate tags (OR semantics) filtered inside the scan mask."""
        req = self.batcher.submit(query, now, attach=attach,
                                  tenant=tenant, terms=terms)
        self.stats.record_arrival(req, self.batcher.depth)
        return req

    def step(self, now: float, drain: bool = False) -> List[Request]:
        """Flush + serve every batch the policy releases at time ``now``."""
        done: List[Request] = []
        while True:
            batch = self.batcher.poll(now, drain=drain)
            if batch is None:
                return done
            done.extend(self._serve(batch, t_start=now))

    def serve_flushed(self, batch: MicroBatch,
                      t_start: float) -> List[Request]:
        """Serve an already-flushed batch at virtual time ``t_start``.

        Public hook for external stream drivers (the multi-replica router
        in :mod:`repro.service` replays one arrival trace across several
        runtimes, each with its own server-free clock)."""
        return self._serve(batch, t_start=t_start)

    def _serve(self, batch: MicroBatch, t_start: float) -> List[Request]:
        kwargs: dict = {}
        slept = 0.0
        if self.faults is not None:          # chaos sites (armed only)
            rule = self.faults.fire("engine.straggler",
                                    replica=self.replica_idx)
            if rule is not None and rule.delay_s > 0:
                time.sleep(rule.delay_s)
                slept = rule.delay_s
            rule = self.faults.fire("engine.batch",
                                    replica=self.replica_idx)
            if rule is not None:
                from repro.runtime.faults import InjectedFault
                err = InjectedFault("engine.batch",
                                    f"replica {self.replica_idx}")
                raise BatchServeError(batch, err) from err
        # deadline budget: remaining seconds (on the driving clock) until
        # the batch's OLDEST request blows its deadline — the engine uses
        # it to degrade (resident-only probes) instead of running long.
        # Computed AFTER the chaos straggler sleep and charged the slept
        # time, so the degrade decision sees the true remaining budget
        # instead of overcommitting to a cold fetch that must miss
        if self.config.deadline_s > 0 and batch.requests:
            deadline = (min(r.t_arrival for r in batch.requests)
                        + self.config.deadline_s)
            kwargs["budget_s"] = deadline - (t_start + slept)
        # scoped batches carry per-row tenant/term arrays; unscoped
        # batches pass nothing so the engine stays on the legacy path
        if batch.scoped:
            kwargs["tenants"], kwargs["terms"] = batch.scope_arrays(
                self.config.filter_width)
        t0 = time.perf_counter()
        try:
            d, i = self.engine.search_batch(batch.queries,
                                            n_valid=batch.n_valid,
                                            **kwargs)
        except Exception as e:
            # fail only this batch's requests; the caller decides whether
            # to retry them elsewhere (service tier) or propagate
            raise BatchServeError(batch, e) from e
        service_s = time.perf_counter() - t0
        self.stats.record_batch(batch, service_s)
        t_done = t_start + service_s
        # engines that can degrade report it per batch (set fresh on
        # every search_batch call, so a stale read is impossible)
        info = getattr(self.engine, "last_batch_info", None)
        degraded = bool(info and info.get("degraded"))
        for row, req in enumerate(batch.requests):   # de-pad: rows [0, n)
            req.dists = np.asarray(d[row])
            req.ids = np.asarray(i[row])
            req.t_flush = batch.t_flush
            req.t_service_start = t_start
            req.t_done = t_done
            req.degraded = degraded
            if self.config.deadline_s > 0:
                req.deadline_missed = (
                    t_done > req.t_arrival + self.config.deadline_s)
            self.stats.record_done(req)
            if req.future is not None:
                req.future._resolve(req)
        return batch.requests

    # -- offline simulation ------------------------------------------------
    def run_stream(self, arrivals: Sequence[Tuple[float, np.ndarray]]
                   ) -> List[Request]:
        """Replay (t_arrival, query) pairs; returns requests in order.

        Single-server discrete-event model: a batch flushed at t starts
        service at max(t, server_free) and occupies the server for its
        measured wall-clock engine time, so queueing delay shows up in
        the latency percentiles as offered load approaches capacity.
        """
        reqs: List[Request] = []
        server_free = 0.0

        def serve_at(batch: MicroBatch) -> None:
            nonlocal server_free
            start = max(batch.t_flush, server_free)
            served = self._serve(batch, t_start=start)
            server_free = served[0].t_done
        for t, query in sorted(arrivals, key=lambda a: a[0]):
            while True:   # fire deadline flushes that precede this arrival
                ddl = self.batcher.next_deadline()
                if ddl is None or ddl > t:
                    break
                batch = self.batcher.poll(ddl)
                if batch is None:
                    break
                serve_at(batch)
            reqs.append(self.submit(query, now=t))
            batch = self.batcher.poll(t)             # flush-on-full
            if batch is not None:
                serve_at(batch)
        while self.batcher.depth:                    # end-of-stream drain
            ddl = self.batcher.next_deadline()
            batch = self.batcher.poll(ddl, drain=True)
            serve_at(batch)
        return reqs

    # -- metrics -----------------------------------------------------------
    def metrics(self) -> dict:
        out = self.stats.summary()
        cache = getattr(self.engine, "lut_cache", None)
        if cache is not None:
            out["lut_cache"] = dict(cache.stats.as_dict(),
                                    entries=len(cache),
                                    granularity=cache.granularity)
        info = getattr(self.engine, "serving_info", None)
        if info is not None:
            out["engine"] = info()
        return out
