"""AnnService: the one front door to the DRIM-ANN serving stack.

Everything between "I have vectors" and "I get neighbors under latency
metrics" lives behind this facade:

    spec = ServiceSpec(engine="sharded", replicas=3, router="cache_aware",
                       cache_capacity=4096, nprobe=8, k=10)
    svc = AnnService.build(spec, points)        # index + engines + runtimes
    svc.warmup()                                # compile every bucket shape
    d, i = svc.search(queries)                  # synchronous batch
    fut = svc.submit_async(q)                   # futures-based lifecycle
    d1, i1 = fut.result(timeout=1.0)            #   (executor-backed)
    reqs = svc.stream(trace)                    # virtual-clock replay
    reqs = svc.stream(trace, clock="wall")      # real executor overlap
    svc.stats()                                 # per-replica + aggregate
    svc.shutdown()

Internally the service owns N identical replicas — each an engine
(``LocalEngine`` over ``search_ivfpq`` or ``ShardedEngine`` over the
UPMEM-style ``DistributedEngine``) with its *own* hot-cluster LUT cache
and heat estimator, behind its own ``ServingRuntime`` micro-batcher —
and a :class:`~repro.service.router.Router` that assigns every incoming
query to one replica.  Replicas share the index (and, for the local
engine, the padded cluster tensors), so results are routing-independent.

Request lifecycle (async API v2): ``submit_async`` routes the query,
enqueues it on the chosen replica's micro-batcher, and returns a
:class:`~repro.service.executor.SearchFuture`; the replica's
:class:`~repro.service.executor.ReplicaExecutor` worker flushes on
deadline/full, serves on the wall clock, and resolves the future with
the per-request queue/batch/engine timing breakdown.  N executors
genuinely overlap — that is the paper's many-ranks-busy throughput
argument restated at the service tier.  A replica failing mid-batch
fails only that batch's futures, and each affected request is retried
once on another healthy replica (``runtime.fault_tolerance.
ReplicaHealth`` tracks who is trustworthy).

``stream`` replays one arrival trace through either driver —
``clock="virtual"`` (discrete-event simulation, deterministic,
measured service time charged onto a virtual timeline) or
``clock="wall"`` (the executor path in real time) — through one shared
submit loop, so both clocks exercise the same routing and batching
code.  With ``ServiceSpec.replicas_max`` set, an
:class:`~repro.service.autoscale.Autoscaler` grows/shrinks the live
fleet between batches from queue-depth/p99 signals; scale events never
change results (replicas are identical by construction).

Invariants (pinned in tests/test_service.py, tests/test_async_service.py):
  * 1 replica, local engine, no cache: ``search`` is exactly
    ``search_ivfpq`` (same call, bit-identical);
  * per-query neighbor sets are identical across replica counts,
    router policies, stream clocks, and autoscale events;
  * serving-batch padding rows never reach the router's heat estimators
    (the router routes *requests*; padding is created downstream).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.filter import VectorMeta, pad_terms
from repro.core.mutable_index import Index
from repro.core.search import SearchParams, cluster_locate
from repro.core.sharded_search import DistributedEngine, EngineConfig
from repro.runtime.batching import MicroBatch, Request
from repro.runtime.cache import (HeatAwareAdmission, HotClusterLUTCache,
                                 OnlineHeatEstimator)
from repro.runtime.fault_tolerance import ReplicaHealth
from repro.runtime.serving import (LocalEngine, PimPacedEngine,
                                   ServingConfig, ServingRuntime,
                                   ShardedEngine, _percentile,
                                   service_construction)
from repro.service.autoscale import Autoscaler, ScaleSignals
from repro.service.executor import ReplicaExecutor, SearchFuture
from repro.service.router import Router, make_policy
from repro.service.spec import ServiceSpec
from repro.service.tenancy import TenantRegistry, WFQScheduler


class ServiceOverloaded(RuntimeError):
    """Raised by the submit path when ``spec.queue_bound`` in-flight
    requests are already queued: under overload the service degrades to
    *fast rejection* (the caller can shed or retry elsewhere) instead of
    letting the queue — and every queued request's latency — grow
    without bound."""


class TenantThrottled(ServiceOverloaded):
    """Raised by the submit path when a tenant's token bucket is out of
    tokens (``ServiceSpec.tenants`` rate_qps/burst): per-tenant
    admission control sheds *that tenant's* excess instead of letting it
    queue ahead of everyone else.  Subclasses :class:`ServiceOverloaded`
    so overload-aware callers need no new handler."""


@dataclasses.dataclass
class Replica:
    """One engine + runtime lane of the service."""
    runtime: ServingRuntime
    engine: object                     # LocalEngine | ShardedEngine adapter
    core: object                       # LocalEngine | DistributedEngine
    cache: Optional[HotClusterLUTCache]
    heat_estimator: Optional[OnlineHeatEstimator]

    @property
    def queue_depth(self) -> int:
        return self.runtime.batcher.depth


class AnnService:
    """Facade over index + replicas + router + serving runtimes.

    Build with :meth:`build`; the constructor itself is wiring-only and
    takes already-constructed parts.
    """

    def __init__(self, spec: ServiceSpec, index: Index,
                 replicas: Sequence[Replica], router: Router):
        self.spec = spec
        self.index = index                 # the unified Index handle
        self.replicas: List[Replica] = list(replicas)
        self.router = router
        self.health = ReplicaHealth(
            len(self.replicas),
            max_consecutive=spec.breaker_threshold,
            half_open_after_s=spec.breaker_half_open_s)
        self.autoscaler: Optional[Autoscaler] = None
        if spec.replicas_max:
            self.autoscaler = Autoscaler(
                spec.replicas, spec.replicas_max,
                queue_high=spec.autoscale_queue_high,
                queue_low=spec.autoscale_queue_low,
                p99_budget_s=(spec.autoscale_p99_budget_ms * 1e-3
                              if spec.autoscale_p99_budget_ms else None),
                cooldown=spec.autoscale_cooldown)
        self._live = len(self.replicas)
        self._executors: List[ReplicaExecutor] = []
        self._batch_rr = 0
        self._retries = 0
        self._shed = 0                 # submits rejected by queue_bound
        # seeded jitter for retry backoff: deterministic given the spec,
        # uncorrelated across retries (decorrelates replica thundering)
        self._retry_rng = np.random.default_rng(spec.index.seed + 0x5EED)
        # chaos: build(fault_injector=...) arms the whole stack through
        # _arm_faults; None = every site hook is a dead branch
        self.faults = None
        # serializes retry-target selection (worker threads) against
        # live-set updates (scale_to on the driver thread): a retry can
        # never be routed to a replica the autoscaler is draining —
        # either it sees the shrunken _live, or its enqueue lands before
        # the tail executor's drain starts (which then serves it)
        self._scale_lock = threading.Lock()
        self._warmed = False
        self._closed = False
        self._virtual_used = False   # clock-domain latch (see _check_*_ok)
        # scale-out context, stashed by build(); scale_to() rebuilds
        # replicas lazily from these when the fleet grows past the
        # originally constructed set (cluster tensors come straight off
        # the Index handle — always the current generation's)
        self._sample_probes = None
        self._sample_queries = None
        self._serving_cfg = ServingConfig(
            buckets=tuple(spec.buckets), max_wait_s=spec.max_wait_s,
            deadline_s=spec.deadline_ms * 1e-3,
            filter_width=spec.filter_width)
        # multi-tenant QoS (PR 10): name<->id registry + token buckets,
        # and (qos_wfq) weighted fair queueing on the executor path
        self.tenancy: Optional[TenantRegistry] = (
            TenantRegistry(spec.tenants) if spec.tenants else None)
        self.wfq: Optional[WFQScheduler] = None
        if spec.qos_wfq:
            window = spec.qos_window or (
                len(self.replicas) * max(spec.buckets))
            self.wfq = WFQScheduler(self.tenancy, window)
        # sticky WFQ dispatch anchor: (replica, remaining chunk) — see
        # _dispatch_executor
        self._wfq_anchor = (-1, 0)
        # mutation coordinator (wired by build() when spec.mutable)
        self.mutator = None
        for i, rep in enumerate(self.replicas):
            rep.runtime.replica_idx = i

    # -- construction ------------------------------------------------------
    @classmethod
    def build(cls, spec: ServiceSpec, points=None, *,
              index=None, sample_queries=None,
              tenants=None, tags=None,
              fault_injector=None) -> "AnnService":
        """Stand up the whole service from a validated spec.

        Either ``points`` (index built per ``spec.index``) or a prebuilt
        ``index`` must be given — an :class:`~repro.core.mutable_index.
        Index` handle, or a raw ``IVFPQIndex`` (wrapped transparently).
        With ``spec.mutable`` the service is built over a *mutable*
        handle (needs ``points``, or an already-mutable handle) and
        ``upsert``/``delete``/``run_maintenance`` come alive.
        ``sample_queries`` seeds the sharded engine's heat estimate
        (falls back to a slice of the corpus).

        ``tenants`` (per-vector owning tenant ids, (N,) int, -1 =
        unscoped) and ``tags`` (per-vector predicate tags, (N, <=
        ``spec.filter_width``) u32) attach a :class:`~repro.core.filter.
        VectorMeta` to the index handle; with ``spec.tenants`` set the
        meta is attached even when both are None (tenant rows then
        arrive via scoped ``upsert``).  ``fault_injector``
        (a :class:`~repro.runtime.faults.FaultInjector`) arms the
        whole stack's chaos hooks — engines, tier, maintenance — for
        fault-injection tests; None (production) leaves every hook a
        dead branch."""
        spec.validate()
        storage_kw = dict(storage=spec.storage, storage_dir=spec.storage_dir,
                          storage_budget_bytes=spec.storage_budget_bytes,
                          storage_promote_margin=spec.storage_promote_margin,
                          storage_checksum=spec.checksum)
        if spec.storage == "tiered" and spec.storage_dir is None:
            # fresh spill dir per build; lives as long as the process
            import tempfile
            storage_kw["storage_dir"] = tempfile.mkdtemp(prefix="ann_tier_")
        if index is None:
            if points is None:
                raise ValueError("AnnService.build needs points or index")
            handle = spec.index.build(points, mutable=spec.mutable,
                                      **storage_kw)
        elif isinstance(index, Index):
            handle = index
            if spec.mutable and not handle.mutable:
                raise ValueError(
                    "spec.mutable=True needs a mutable Index handle — "
                    "build one with IndexSpec.build(points, mutable=True)")
            if handle.storage != spec.storage:
                raise ValueError(
                    f"spec.storage={spec.storage!r} but the prebuilt Index "
                    f"handle was built storage={handle.storage!r} — build "
                    f"it with IndexSpec.build(points, storage=...) to "
                    f"match")
        else:
            # raw IVFPQIndex: wrap (identity-preserving for the static
            # case; with spec.mutable the raw points must come along so
            # maintenance can re-encode)
            handle = Index(index, points=points, mutable=spec.mutable,
                           **storage_kw)

        if spec.tenants or tenants is not None or tags is not None:
            cls._attach_meta(spec, handle, tenants, tags)

        sample_probes = None
        sample_np = None
        if spec.engine == "sharded":
            sample = sample_queries
            if sample is None:
                if points is None:
                    raise ValueError("sharded engine needs sample_queries "
                                     "(or points to fall back on) for the "
                                     "heat estimate")
                sample = np.asarray(points)[:min(256, len(points))]
            sample_np = np.asarray(sample, np.float32)
            probes, _ = cluster_locate(
                jnp.asarray(sample_np), handle.centroids, spec.nprobe)
            sample_probes = np.asarray(probes)

        serving_cfg = ServingConfig(buckets=tuple(spec.buckets),
                                    max_wait_s=spec.max_wait_s,
                                    deadline_s=spec.deadline_ms * 1e-3,
                                    filter_width=spec.filter_width)
        replicas: List[Replica] = []
        with service_construction():
            for _ in range(spec.replicas):
                replicas.append(cls._build_replica(
                    spec, handle, sample_probes, serving_cfg))

        policy = make_policy(
            spec.router, nlist=handle.nlist, n_replicas=spec.replicas,
            halflife_batches=spec.router_halflife_batches)

        def probe_fn(q: np.ndarray) -> np.ndarray:
            # read centroids through the handle so routing follows the
            # live generation (maintenance may split/merge clusters)
            p, _ = cluster_locate(
                jnp.asarray(np.asarray(q, np.float32)[None]),
                handle.centroids, spec.nprobe)
            p = np.asarray(p)[0]
            return p[p >= 0]            # -1: nprobe > nlist after merges

        svc = cls.__new__(cls)
        router = Router(policy, spec.replicas,
                        depth_fn=lambda r: svc.replicas[r].queue_depth,
                        probe_fn=probe_fn)
        cls.__init__(svc, spec, handle, replicas, router)
        svc._sample_probes = sample_probes
        svc._sample_queries = sample_np
        svc._serving_cfg = serving_cfg
        if spec.mutable:
            from repro.service.mutation import MutationCoordinator
            svc.mutator = MutationCoordinator(svc)
        if fault_injector is not None:
            svc._arm_faults(fault_injector)
        return svc

    @staticmethod
    def _attach_meta(spec: ServiceSpec, handle: Index,
                     tenants, tags) -> VectorMeta:
        """Build the id-keyed :class:`VectorMeta` tables for the handle:
        per-vector tenant/tags from the caller's arrays (row i = vector
        id i, the build's id assignment), cluster_of from the handle's
        live layout (padded clusters, or the tier's per-cluster id rows
        — meta stays RAM-resident either way)."""
        meta = VectorMeta(tag_fields=spec.filter_width)
        n = None
        if tenants is not None:
            tenants = np.asarray(tenants, np.int32).reshape(-1)
            n = tenants.size
        if tags is not None:
            tags = np.asarray(tags, np.uint32)
            if tags.ndim == 1:
                tags = tags[:, None]
            if n is not None and len(tags) != n:
                raise ValueError(
                    f"tenants ({n}) and tags ({len(tags)}) must describe "
                    f"the same vectors")
            n = len(tags)
        if n:
            meta.set(np.arange(n), tenant=tenants, tags=tags)
        tier = handle.tiered_store
        if tier is not None:
            for c in range(handle.nlist):
                _, ids_c = tier.peek(c)
                row = np.asarray(ids_c)[:int(tier.sizes[c])]
                row = row[row >= 0]
                if row.size:
                    meta.set(row, cluster=c)
        else:
            cl = handle.clusters
            meta.rebuild_clusters(np.asarray(cl.ids), np.asarray(cl.sizes))
        handle.meta = meta
        return meta

    def _arm_faults(self, injector) -> None:
        """Attach one FaultInjector to every chaos hook in the stack."""
        self.faults = injector
        for rep in self.replicas:
            rep.runtime.faults = injector
        if self.index.tiered_store is not None:
            self.index.tiered_store.faults = injector
        if self.mutator is not None:
            self.mutator.faults = injector

    @staticmethod
    def _build_replica(spec: ServiceSpec, index: Index,
                       sample_probes, serving_cfg: ServingConfig) -> Replica:
        def make_cache(admission=None):
            if not spec.cache_enabled:
                return None
            return HotClusterLUTCache(
                capacity=spec.cache_capacity or None,
                capacity_bytes=spec.cache_capacity_bytes or None,
                granularity=spec.cache_granularity,
                lut_dtype=spec.lut_dtype,
                admission=admission)

        def pace(engine):
            """PIM-paced serving: wrap the engine so batches take their
            Eq. 15 modeled time on a ``pim_paced_ranks``-rank fleet
            (results unchanged; see runtime.serving.PimPacedEngine).
            With tiered storage the per-task latency also carries the
            disk tier's expected cold-probe cost (Eq. 15 + seek/bw), at
            the steady-state cold prior 1 - budget/total."""
            if not spec.pim_paced_ranks:
                return engine
            from repro.core.perf_model import (IndexParams, UPMEM_PROFILE,
                                               lut_width_bytes,
                                               make_task_latency_model)
            sizes = np.asarray(index.sizes)
            ixp = IndexParams(n_total=int(sizes.sum()), nlist=index.nlist,
                              q=1, d=index.dim, k=spec.k, p=spec.nprobe,
                              m=index.codebook.m, cb=index.codebook.cb,
                              b_lut=lut_width_bytes(spec.lut_dtype))
            model = make_task_latency_model(ixp, UPMEM_PROFILE)
            task_s = model.task_latency(float(sizes.mean()))
            if index.tiered_store is not None:
                from repro.core.perf_model import (NVME_PROFILE,
                                                   cold_probe_seconds)
                tier = index.tiered_store
                cold_prior = max(
                    0.0, 1.0 - tier.budget_bytes / max(tier.total_bytes, 1))
                task_s += cold_prior * cold_probe_seconds(ixp, NVME_PROFILE)
            return PimPacedEngine(
                engine, nprobe=spec.nprobe, ranks=spec.pim_paced_ranks,
                task_latency_s=task_s)

        if spec.engine == "local":
            cache = make_cache()
            coarse = None
            if spec.coarse_groups:
                # one Coarse2 per handle (replicas share it; routing is
                # deterministic in the index seed)
                coarse = getattr(index, "_coarse2_cache", None)
                if coarse is None:
                    import jax

                    from repro.core.coarse2 import build_coarse2
                    coarse = build_coarse2(
                        jax.random.PRNGKey(spec.index.seed),
                        index.centroids, n_groups=spec.coarse_groups)
                    index._coarse2_cache = coarse
            # search_view: for a static handle, the wrapped IVFPQIndex
            # itself (bit-exact identity with direct search_ivfpq); for a
            # mutable one, a lean view whose jit shapes are independent
            # of N so mutations/generations never force recompiles.
            # Tiered handles hold no resident clusters — the engine
            # fetches probed rows through the tier instead.
            tier = index.tiered_store
            clusters = None if tier is not None else index.clusters
            core = LocalEngine(index.search_view, clusters,
                               SearchParams(nprobe=spec.nprobe, k=spec.k,
                                            strategy=spec.strategy,
                                            lut_dtype=spec.lut_dtype),
                               lut_cache=cache, tiered_store=tier,
                               coarse=coarse,
                               coarse_nprobe1=spec.coarse_nprobe1,
                               meta=index.meta)
            return Replica(ServingRuntime(pace(core), serving_cfg), core,
                           core, cache, None)
        est = None
        if spec.heat_aware_admission or spec.relayout_every > 0:
            from repro.core.layout import estimate_heat
            est = OnlineHeatEstimator(
                index.nlist, seed=estimate_heat(sample_probes, index.nlist))
        cache = make_cache(HeatAwareAdmission(est)
                           if spec.heat_aware_admission else None)
        cfg_kwargs = dict(n_shards=spec.n_shards, nprobe=spec.nprobe,
                          k=spec.k, split_max=spec.split_max,
                          dup_budget_bytes=spec.dup_budget_bytes,
                          tasks_per_shard=spec.tasks_per_shard,
                          strategy=spec.strategy,
                          lut_dtype=spec.lut_dtype,
                          relayout_every=spec.relayout_every)
        cfg_kwargs.update(dict(spec.engine_overrides or {}))
        core = DistributedEngine(index.to_ivfpq(), EngineConfig(**cfg_kwargs),
                                 sample_probes, lut_cache=cache,
                                 heat_estimator=est,
                                 tiered_store=index.tiered_store,
                                 meta=index.meta)
        if spec.tune_tasks_per_shard:
            core.tasks_controller = core.make_tasks_controller()
        adapter = ShardedEngine(core)
        return Replica(ServingRuntime(pace(adapter), serving_cfg), adapter,
                       core, cache, est)

    # -- lifecycle ---------------------------------------------------------
    @property
    def n_replicas(self) -> int:
        """Live replica count (the autoscaler moves this inside
        ``[spec.replicas, spec.replicas_max]``)."""
        return self._live

    @property
    def live_replicas(self) -> List[Replica]:
        return self.replicas[:self._live]

    def core_engine(self, replica: int = 0):
        """The underlying engine (LocalEngine / DistributedEngine) of one
        replica — for layout stats, scheduler inspection, ablations."""
        return self.replicas[replica].core

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError("AnnService is shut down")

    def _check_virtual_ok(self, what: str) -> None:
        """Virtual-clock APIs simulate time over the replica batchers;
        once executor workers are live they poll those same batchers on
        the wall clock, so mixing the two would race (and mix clock
        domains in the stats).  Fail loudly instead."""
        if any(ex.running for ex in self._executors):
            raise RuntimeError(
                f"{what} uses the virtual clock, but executor workers "
                f"are live (submit_async / stream(clock='wall') started "
                f"them); use clock='wall', or a service that has not "
                f"gone async")
        self._virtual_used = True

    def _check_wall_ok(self, what: str) -> None:
        """The mirror guard: wall-clock timestamps (time.monotonic)
        must not land in stats that already hold virtual-clock times —
        spans like t_last_done - t_first_arrival would be garbage."""
        if self._virtual_used:
            raise RuntimeError(
                f"{what} stamps wall-clock times, but this service "
                f"already served virtual-clock traffic (submit/step or "
                f"stream(clock='virtual')); its stats would mix clock "
                f"domains — use a fresh service for wall-clock serving")

    def warmup(self) -> None:
        """Compile every bucket shape on every replica (all-padding
        batches: no cache, heat, or router state is touched)."""
        self._check_open()
        for rep in self.replicas:
            rep.runtime.warmup(self.index.dim)
        self._warmed = True

    def shutdown(self) -> dict:
        """Drain the executors, close the service (subsequent calls
        raise) and return final stats.

        Fail-operational: a wedged worker (did not drain within
        ``spec.shutdown_timeout_s``) does not abort the shutdown of the
        rest of the fleet — it is counted in ``stats()['aggregate']
        ['wedged_workers']`` and the first wedge error is re-raised
        after every executor has been given its chance to drain."""
        if self.mutator is not None:
            self.mutator.close()
        first_err: Optional[BaseException] = None
        for ex in self._executors:
            try:
                ex.shutdown()
            except RuntimeError as err:       # wedged — keep draining rest
                if first_err is None:
                    first_err = err
        out = self.stats()
        self._closed = True
        if first_err is not None:
            raise first_err
        return out

    # -- mutation API --------------------------------------------------------
    def _require_mutable(self, what: str):
        if self.mutator is None:
            raise RuntimeError(
                f"AnnService.{what} needs a mutable service — build with "
                f"ServiceSpec(mutable=True) and the points array")
        return self.mutator

    def upsert(self, ids, vectors, *, tenant=None, tags=None) -> dict:
        """Insert or replace vectors in the live index: assign to the
        nearest centroid, encode with the live PQ codebooks, append to
        the per-cluster code arrays, and install the new tensors on
        every replica (centroids/codebooks unchanged, so LUT caches stay
        valid).  Visible to the next search batch.  Returns insert/
        replace counts (see :meth:`Index.upsert`).

        ``tenant`` (name or id) / ``tags`` scope the upserted vectors
        (needs a service built with per-vector metadata); omitting them
        stamps the rows unscoped — a recycled id never inherits its
        previous owner's scope."""
        self._check_open()
        mut = self._require_mutable("upsert")
        if tenant is None and tags is None:
            return mut.upsert(ids, vectors)
        return mut.upsert(ids, vectors,
                          tenant=self._resolve_tenant(tenant), tags=tags)

    def delete(self, ids) -> int:
        """Remove ids from the live index (swap-compacted out of the
        scan mask — a deleted id can never appear in a result) and
        install on every replica.  Returns how many ids were live."""
        self._check_open()
        return self._require_mutable("delete").delete(ids)

    def run_maintenance(self, force: bool = False, wait: bool = True
                        ) -> dict:
        """Run one cluster-maintenance cycle: split/merge clusters that
        drifted past the spec's size band and retrain PQ codebooks,
        building the next index generation on a background thread and
        installing it via each engine's prepare/swap — searches never
        block on the rebuild.  ``force=True`` rebuilds even when no
        cluster is out of band; ``wait=False`` returns immediately."""
        self._check_open()
        return self._require_mutable("run_maintenance").run_maintenance(
            force=force, wait=wait)

    # -- tenant scoping ------------------------------------------------------
    def _resolve_tenant(self, tenant) -> int:
        """Tenant name/int/None -> int id (-1 = unscoped)."""
        if self.tenancy is not None:
            return self.tenancy.resolve(tenant)
        if tenant is None:
            return -1
        if isinstance(tenant, str):
            raise KeyError(f"tenant names need ServiceSpec.tenants; got "
                           f"{tenant!r} on a spec without a tenants "
                           f"section (pass the int tenant id instead)")
        return int(tenant)

    # -- synchronous batch API ---------------------------------------------
    def search(self, queries, tenant=None,
               terms=()) -> Tuple[np.ndarray, np.ndarray]:
        """One batched search, bypassing the micro-batcher (offline /
        bulk callers).  Batches rotate over live replicas round-robin;
        results are replica-independent.  With 1 replica, a local
        engine, and no cache this is exactly ``search_ivfpq``.

        ``tenant`` (name or int id) scopes every query in the batch to
        that tenant's rows; ``terms`` (u32 tags, OR semantics) filters
        to rows carrying any of them.  Needs a service built with
        per-vector metadata.  Quotas do not apply on this offline path
        (admission control guards the *online* submit paths)."""
        self._check_open()
        r = self._batch_rr % self.n_replicas
        self._batch_rr += 1
        q = np.asarray(queries, np.float32)
        tid = self._resolve_tenant(tenant)
        if tid < 0 and not len(tuple(terms)):
            return self.replicas[r].engine.search_batch(q)
        tenants_arr = np.full(len(q), tid, np.int32)
        terms_arr = pad_terms([tuple(terms)] * len(q),
                              self.spec.filter_width)
        return self.replicas[r].engine.search_batch(
            q, tenants=tenants_arr, terms=terms_arr)

    # -- async request lifecycle --------------------------------------------
    def _route_and_submit(self, query, now: float, executor: bool,
                          tenant: int = -1, terms=()) -> SearchFuture:
        """The one submit path: route, enqueue, bind a future.  The
        future is attached under the batcher lock, so an executor worker
        can never serve the request before the future exists.

        On the executor path, a pick landing on an unhealthy replica
        (``ReplicaHealth``: too many consecutive batch failures) is
        steered to the healthiest shallowest alternative, so a
        permanently dying replica stops burning every routed request's
        single retry.  The router's pick counts record the policy's
        choice; ``stats()['health']`` shows who is being steered
        around.  With ``spec.breaker_half_open_s`` set the breaker
        itself re-admits a single probe batch after the cool-off
        (``ReplicaHealth.allow``), so a recovered replica rejoins the
        fleet without operator action; at the legacy default (0) an
        open breaker stays open until an autoscaler shrink parks the
        replica or an operator resets its health.

        With ``spec.queue_bound`` set the submit path is *admission
        controlled*: once that many requests are in flight fleet-wide,
        submits fail fast with :class:`ServiceOverloaded` instead of
        queueing without bound.

        Multi-tenant QoS (PR 10) layers in front: a scoped request
        first passes its tenant's token bucket (over quota ->
        :class:`TenantThrottled`, on both clock paths), and with
        ``spec.qos_wfq`` the executor path holds the request in the
        :class:`~repro.service.tenancy.WFQScheduler` — routing happens
        at *dispatch* time, so depth-aware policies see the fleet as it
        is when the request actually enters it."""
        q = np.asarray(query, np.float32)
        if tenant >= 0 and self.tenancy is not None \
                and not self.tenancy.admit(tenant, now):
            raise TenantThrottled(
                f"tenant {self.tenancy.name_of(tenant)!r} is over its "
                f"token-bucket quota; shedding")
        bound = self.spec.queue_bound
        if bound and executor:
            depth = sum(rep.queue_depth for rep in self.live_replicas)
            if depth >= bound:
                self._shed += 1
                raise ServiceOverloaded(
                    f"queue_bound={bound} in-flight requests already "
                    f"queued (depth={depth}); shedding")
        if executor and self.wfq is not None:
            fut = SearchFuture()
            fut.add_done_callback(self.wfq.on_complete)

            def dispatch(fut=fut, q=q, now=now, tenant=tenant,
                         terms=terms) -> None:
                try:
                    self._dispatch_executor(q, now, tenant, terms, fut)
                except BaseException as err:    # noqa: BLE001 — the done
                    fut._fail(err)              # callback frees the slot
            self.wfq.submit(tenant, dispatch)
            return fut
        r = self.router.route(q, tenant=tenant)
        if executor and not self.health.allow(r):
            with self._scale_lock:
                alt = self._retry_target(exclude=r)
            if alt is not None:
                r = alt
        cell: List[SearchFuture] = []

        def attach(req: Request, r=r) -> None:
            cell.append(SearchFuture(req, r))

        if executor:
            self._executors[r].submit(q, now=now, attach=attach,
                                      tenant=tenant, terms=terms)
        else:
            self.replicas[r].runtime.submit(q, now, attach=attach,
                                            tenant=tenant, terms=terms)
        return cell[0]

    def _dispatch_executor(self, q: np.ndarray, now: float, tenant: int,
                           terms, fut: SearchFuture) -> None:
        """WFQ dispatch: route (now, not at submit), steer around open
        breakers, bind the held future to the enqueued request.

        WFQ dispatches route by *chunked round-robin* instead of the
        spec's policy: the fair queue releases requests one per
        completion, and per-request depth-aware routing marches across
        the fleet with every pick (each pick deepens that replica's
        queue, so the next pick moves on), shredding the batches the
        micro-batcher wants to form — measured ~20% aggregate QPS loss
        under saturation.  A bucket's worth of consecutive dispatches
        goes to one replica (full batches), then the anchor advances to
        the next (even spread); tenant interleaving is already the fair
        queue's job, so the policy's per-request choice adds nothing
        here.  Health steering still applies and pick accounting stays
        complete (``Router.record``)."""
        r, left = self._wfq_anchor
        if not (0 <= r < self._live) or left <= 0:
            r = (r + 1) % self._live
            if not self.health.allow(r):
                with self._scale_lock:
                    alt = self._retry_target(exclude=r)
                if alt is not None:
                    r = alt
            left = max(self.spec.buckets)
        self.router.record(r, tenant=tenant)
        self._wfq_anchor = (r, left - 1)

        def attach(req: Request, r=r) -> None:
            fut._bind(req, r)

        self._executors[r].submit(q, now=now, attach=attach,
                                  tenant=tenant, terms=terms)

    def _ensure_executors(self, upto: Optional[int] = None) -> None:
        """Stand up (or top up, after growth) one executor per replica
        and start the first ``upto`` (default: the live set)."""
        while len(self._executors) < len(self.replicas):
            ridx = len(self._executors)
            self._executors.append(ReplicaExecutor(
                self.replicas[ridx].runtime, ridx,
                on_batch_failure=self._on_batch_failure,
                on_batch_success=self.health.record_success,
                join_timeout_s=self.spec.shutdown_timeout_s))
        for ex in self._executors[:self._live if upto is None else upto]:
            ex.start()

    def submit_async(self, query, now: Optional[float] = None, *,
                     tenant=None, terms=()) -> SearchFuture:
        """Route one query onto an executor-backed replica; returns a
        :class:`SearchFuture` (``result(timeout)``, ``done()``,
        ``timing()``).  First call starts the replica workers.
        ``tenant`` (name or id) / ``terms`` scope the request; a scoped
        submit may raise :class:`TenantThrottled` (quota) and, under
        ``spec.qos_wfq``, may be held by the fair queue before it
        reaches a replica."""
        self._check_open()
        self._check_wall_ok("submit_async()")
        self._ensure_executors()
        t = float(now) if now is not None else time.monotonic()
        return self._route_and_submit(query, t, executor=True,
                                      tenant=self._resolve_tenant(tenant),
                                      terms=tuple(terms))

    # -- old sync surface: thin wrappers over the same lifecycle -----------
    def submit(self, query, now: float, *, tenant=None,
               terms=()) -> Request:
        """Route one query and enqueue it on the chosen replica's
        micro-batcher under the caller's (virtual) clock.  Returns the
        live Request (stamped when served; its ``future`` resolves
        then too).  Thin wrapper over the async lifecycle — drive
        completion with :meth:`step`."""
        self._check_open()
        self._check_virtual_ok("submit()")
        return self._route_and_submit(
            query, now, executor=False,
            tenant=self._resolve_tenant(tenant),
            terms=tuple(terms)).request

    def step(self, now: float, drain: bool = False) -> List[Request]:
        """Advance every live replica's flush policy to time ``now``
        (virtual-clock counterpart of the executor workers)."""
        self._check_open()
        self._check_virtual_ok("step()")
        done: List[Request] = []
        for rep in self.live_replicas:
            done.extend(rep.runtime.step(now, drain=drain))
        return done

    # -- fault tolerance (executor path) ------------------------------------
    def _retry_target(self, exclude: int) -> Optional[int]:
        """Healthy live replica with the shallowest queue, never the one
        that just failed; None when the fleet has nowhere to go."""
        cands = [r for r in self.health.healthy()
                 if r < self._live and r != exclude]
        if not cands:
            return None
        return min(cands, key=lambda r: self.replicas[r].queue_depth)

    def _on_batch_failure(self, ridx: int, batch: MicroBatch,
                          cause: BaseException) -> None:
        """A replica died mid-batch: fail only that batch's requests,
        retrying each on another healthy replica (retry v2).

        Each request carries its own ``retries`` count; a request is
        retried at most ``spec.max_retries`` times, with exponential
        backoff ``backoff_base_ms * 2^attempt`` plus seeded jitter slept
        *once per failed batch* (on this worker thread, outside the
        scale lock — no router or retry is blocked by the wait)."""
        self.health.record_failure(ridx)
        live = [req for req in batch.requests if req.future is not None]
        retryable = [req for req in live
                     if req.retries < self.spec.max_retries]
        if retryable and self.spec.backoff_base_ms > 0:
            attempt = min(req.retries for req in retryable)
            delay = (self.spec.backoff_base_ms * 1e-3 * (2 ** attempt)
                     * (0.5 + 0.5 * float(self._retry_rng.random())))
            time.sleep(delay)
        for req in live:
            fut = req.future
            with self._scale_lock:
                target = (self._retry_target(exclude=ridx)
                          if req.retries < self.spec.max_retries else None)
                if target is None:
                    fut._fail(cause)
                    continue
                self._retries += 1

                def attach(new_req: Request, fut=fut, target=target,
                           n=req.retries + 1) -> None:
                    new_req.retries = n
                    fut._rebind(new_req, target)

                # keep the original arrival stamp: the caller has been
                # waiting since then, and stats/autoscaling must see the
                # failover's real latency (the stale deadline also makes
                # the retry flush immediately); scope rides along — a
                # retried tenant query must stay that tenant's
                self._executors[target].submit(req.query,
                                               now=req.t_arrival,
                                               attach=attach,
                                               tenant=req.tenant,
                                               terms=req.terms)

    # -- autoscaling ---------------------------------------------------------
    def scale_to(self, n: int) -> None:
        """Grow/shrink the live fleet to ``n`` replicas (LIFO).

        Growth reuses parked replicas when available, else builds fresh
        ones from the stashed spec context (warmed if the service was).
        Shrink drains the tail executors (queued requests are served
        before the worker parks) and drops their router heat.  Neighbor
        sets are invariant across scale events — replicas are identical
        by construction."""
        self._check_open()
        lo = self.spec.replicas
        hi = self.spec.replicas_max or max(len(self.replicas), lo)
        n = max(lo, min(int(n), hi))
        if n == self._live:
            return
        if n > self._live:
            with service_construction():
                while len(self.replicas) < n:
                    rep = self._build_replica(
                        self.spec, self.index,
                        self._sample_probes, self._serving_cfg)
                    rep.runtime.replica_idx = len(self.replicas)
                    rep.runtime.faults = self.faults
                    if self._warmed:
                        rep.runtime.warmup(self.index.dim)
                    self.replicas.append(rep)
            self.health.resize(len(self.replicas))
            if self._executors:
                # executors must exist and run before _live admits them
                # as retry targets (worker threads index _executors)
                self._ensure_executors(upto=n)
            with self._scale_lock:
                self._live = n
        else:
            with self._scale_lock:
                old_live = self._live
                self._live = n   # retries must not target the tail...
                tail = list(self._executors[n:old_live])
            for ex in tail:      # ...then drain it outside the lock (a
                ex.shutdown()    # failing worker may be waiting on it)
        self.router.resize(self._live)

    def _autoscale_tick(self) -> None:
        """One between-batches autoscaler evaluation (wall-clock stream
        driver); applies the decision immediately."""
        if self.autoscaler is None or not self._executors:
            return
        lat: List[float] = []
        for rep in self.live_replicas:
            lat.extend(rep.runtime.stats.recent_latencies(64))
        breaker = self.health.stats()["breaker"]
        signals = ScaleSignals(
            queue_depths=[rep.queue_depth for rep in self.live_replicas],
            p99_s=(_percentile(lat, 99) if lat else None),
            open_breakers=self.health.open_count(),
            open_mask=[i < len(breaker) and breaker[i] == "open"
                       for i in range(len(self.live_replicas))])
        target = self.autoscaler.decide(signals)
        if target != self._live:
            self.scale_to(target)

    # -- stream drivers ------------------------------------------------------
    def stream(self, arrivals: Sequence[Tuple],
               clock: str = "virtual") -> List[Request]:
        """Replay (t_arrival, query[, tenant]) arrivals across the fleet.

        One submit loop, two drivers:

          * ``clock="virtual"`` — multi-server discrete-event model:
            arrivals are routed in time order, each replica serves its
            own flushed batches on its own server-free clock (measured
            engine wall-clock charged onto the virtual timeline), and
            deadline flushes fire in global time order.  Deterministic;
            no threads.
          * ``clock="wall"`` — the executor path in real time: arrival
            gaps are slept, submits go through :meth:`submit_async`,
            replica workers overlap, and (with ``replicas_max`` set)
            the autoscaler moves the live fleet between batches.

        Arrivals may carry an optional third element — the tenant (name
        or int id), as produced by ``data.streams.make_query_stream(
        tenants=...)``.  A tenant over its token-bucket quota has that
        arrival *shed* (counted in ``stats()['tenants'][name]['shed']``,
        absent from the returned list) rather than aborting the replay —
        that is the quota doing its job under a hot-tenant burst.

        Returns served requests in arrival order (same neighbor sets
        under either clock — pinned in tests)."""
        self._check_open()
        if clock not in ("virtual", "wall"):
            raise ValueError(f"stream clock must be 'virtual' or 'wall', "
                             f"got {clock!r}")
        if clock == "virtual":
            self._check_virtual_ok("stream(clock='virtual')")
        else:
            self._check_wall_ok("stream(clock='wall')")
        arrivals = sorted(arrivals, key=lambda a: a[0])
        driver = (_WallStreamDriver(self) if clock == "wall"
                  else _VirtualStreamDriver(self))
        interval = self.spec.autoscale_interval
        for i, arrival in enumerate(arrivals):
            t, query = arrival[0], arrival[1]
            tenant = arrival[2] if len(arrival) > 2 else None
            driver.advance_to(t)
            try:
                driver.submit(query, t, tenant=tenant)
            except TenantThrottled:
                pass                    # shed: counted in tenancy stats
            if clock == "wall" and (i + 1) % interval == 0:
                self._autoscale_tick()
        return driver.finish()

    # -- metrics -------------------------------------------------------------
    def stats(self) -> dict:
        """Per-replica runtime metrics plus fleet-level rollup: aggregate
        p50/p99 over all served requests, QPS over the global span,
        summed LUT-cache hit rate, the router's pick counts, retry and
        replica-health counters, and the autoscaler's event log."""
        per = [rep.runtime.metrics() for rep in self.replicas]
        lat: List[float] = []
        t0s, t1s = [], []
        hits = lookups = 0
        for rep in self.replicas:
            s = rep.runtime.stats
            lat.extend(s.latencies_s)
            if s.t_first_arrival is not None:
                t0s.append(s.t_first_arrival)
            if s.t_last_done is not None:
                t1s.append(s.t_last_done)
            if rep.cache is not None:
                hits += rep.cache.stats.hits
                lookups += rep.cache.stats.lookups
        span = (max(t1s) - min(t0s)) if t0s and t1s else 0.0
        agg = {
            "requests": len(lat),
            "batches": sum(m["batches"] for m in per),
            "p50_ms": _percentile(lat, 50) * 1e3,
            "p99_ms": _percentile(lat, 99) * 1e3,
            "qps": len(lat) / span if span > 0 else float("nan"),
            "retries": self._retries,
            "shed": self._shed,
            "wedged_workers": sum(1 for ex in self._executors
                                  if ex.wedged),
            "degraded": sum(m.get("degraded_requests", 0) for m in per),
            "deadline_missed": sum(m.get("deadline_missed", 0)
                                   for m in per),
        }
        if lookups:
            agg["lut_hit_rate"] = hits / lookups
        out = {"aggregate": agg, "router": self.router.stats(),
               "health": self.health.stats(), "replicas": per}
        tenants = self._tenant_rollup(span)
        if tenants:
            out["tenants"] = tenants
        if self.wfq is not None:
            out["qos"] = self.wfq.stats()
        if self.faults is not None:
            out["faults"] = self.faults.stats()
        if self.index.tiered_store is not None:
            out["tier"] = self.index.tiered_store.serving_info()
        if self.autoscaler is not None:
            out["autoscaler"] = self.autoscaler.stats()
        if self.mutator is not None:
            out["mutation"] = self.mutator.stats()
        return out

    def _tenant_rollup(self, span: float) -> dict:
        """Fleet-wide per-tenant p50/p99/QPS/shed: merge every replica
        runtime's per-tenant latency lists, then overlay the registry's
        quota-shed counters (a registered tenant appears even if every
        one of its requests was shed)."""
        lat: dict = {}
        for rep in self.replicas:
            for tid, ls in rep.runtime.stats.tenant_latencies.items():
                lat.setdefault(int(tid), []).extend(ls)
        if not lat and self.tenancy is None:
            return {}
        name_of = (self.tenancy.name_of if self.tenancy is not None
                   else lambda t: str(t))
        out = {}
        for tid, ls in sorted(lat.items()):
            out[name_of(tid)] = {
                "id": tid,
                "requests": len(ls),
                "p50_ms": _percentile(ls, 50) * 1e3,
                "p99_ms": _percentile(ls, 99) * 1e3,
                "qps": len(ls) / span if span > 0 else float("nan"),
                "shed": 0,
            }
        if self.tenancy is not None:
            for name, info in self.tenancy.stats().items():
                row = out.setdefault(name, {
                    "id": info["id"], "requests": 0, "p50_ms": 0.0,
                    "p99_ms": 0.0, "qps": 0.0, "shed": 0})
                row["shed"] = info["shed"]
                row["weight"] = info["weight"]
        return out


# ---------------------------------------------------------------------------
# Stream drivers — one submit loop (in AnnService.stream), two clocks.
# ---------------------------------------------------------------------------

class _VirtualStreamDriver:
    """Deterministic multi-server discrete-event replay (no threads):
    per-replica server-free clocks, deadline flushes fired in global
    time order, measured engine time charged onto the virtual
    timeline."""

    def __init__(self, svc: AnnService):
        self.svc = svc
        self.free = [0.0] * svc.n_replicas
        self.reqs: List[Request] = []

    def _serve(self, r: int, batch: MicroBatch) -> None:
        start = max(batch.t_flush, self.free[r])
        served = self.svc.replicas[r].runtime.serve_flushed(batch,
                                                            t_start=start)
        self.free[r] = served[0].t_done

    def _fire_deadlines(self, until: Optional[float] = None) -> None:
        reps = self.svc.live_replicas
        while True:
            pend = [(rep.runtime.batcher.next_deadline(), ri)
                    for ri, rep in enumerate(reps)]
            pend = [(d, ri) for d, ri in pend if d is not None]
            if not pend:
                return
            ddl, ri = min(pend)
            if until is not None and ddl > until:
                return
            batch = reps[ri].runtime.batcher.poll(ddl)
            if batch is None:
                return
            self._serve(ri, batch)

    def advance_to(self, t: float) -> None:
        self._fire_deadlines(until=t)

    def submit(self, query, t: float, tenant=None) -> None:
        fut = self.svc._route_and_submit(
            query, t, executor=False,
            tenant=self.svc._resolve_tenant(tenant))
        req = fut.request
        self.reqs.append(req)
        r = req.replica
        batch = self.svc.replicas[r].runtime.batcher.poll(t)  # flush-on-full
        if batch is not None:
            self._serve(r, batch)

    def finish(self) -> List[Request]:
        for ri, rep in enumerate(self.svc.live_replicas):     # drain
            b = rep.runtime.batcher
            while b.depth:
                batch = b.poll(b.next_deadline(), drain=True)
                self._serve(ri, batch)
        return self.reqs


class _WallStreamDriver:
    """Real-time replay through the executor-backed replicas: arrival
    gaps are slept, workers overlap, futures gate completion."""

    def __init__(self, svc: AnnService):
        self.svc = svc
        svc._ensure_executors()
        self.t0 = time.monotonic()
        self.futures: List[SearchFuture] = []

    def advance_to(self, t: float) -> None:
        dt = (self.t0 + t) - time.monotonic()
        if dt > 0:
            time.sleep(dt)

    def submit(self, query, t: float, tenant=None) -> None:
        self.futures.append(self.svc.submit_async(query, tenant=tenant))

    def finish(self) -> List[Request]:
        svc = self.svc
        # WFQ holds a backlog outside the batchers: keep force-flushing
        # so completions keep pulling the queue until it runs dry
        while svc.wfq is not None and svc.wfq.pending:
            for ex in svc._executors[:svc._live]:
                ex.flush()
            time.sleep(0.002)
        for ex in svc._executors[:svc._live]:
            ex.flush()
        for fut in self.futures:
            fut.result(timeout=120.0)
        return [fut.request for fut in self.futures]
