"""Distributed DRIM-ANN engine: layout-sharded clusters + scheduled scans.

The UPMEM execution model maps onto the mesh as follows (DESIGN.md §2):

  DPU                      -> mesh device ("shards" axis)
  MRAM cluster residency   -> per-device shard of the padded instance arrays
  host->DPU query broadcast-> queries + centroids replicated (one broadcast)
  per-DPU (q, c) task list -> static-shape ShardSchedule tables (scheduler.py)
  DPU kernel (RC+LC+DC+TS) -> per-shard jnp/Pallas pipeline below
  host merge barrier       -> all tasks' top-k returned; per-query merge

Two execution paths around ONE per-shard function:
  * ``shard_map`` over a real mesh axis (production; exercised in tests via
    a subprocess with --xla_force_host_platform_device_count);
  * ``vmap`` simulation over the shard axis (single-device tests — identical
    numerics, no collectives).

The final per-query merge is host-side by default — faithful to UPMEM's
mandatory DPU->host synchronization (§II-B: DPUs cannot exchange results).
On TPU the merge could stay on-device; ``merge_on_device`` implements it
with a segment-top-k for moderate batch sizes and is used by the dry-run.

Serving-v2 additions (PR 2): the engine optionally takes

  * ``lut_cache`` — a :class:`repro.runtime.cache.HotClusterLUTCache`.
    LUTs are then assembled host-side once per (query, probed cluster)
    pair into a replicated bank of shape (Q*nprobe, M, CB) f32 and the
    shard step (``_shard_tasks_lut_fn``) runs DC+TS only, gathering each
    task's LUT by index.  Split parts and replicas of a cluster share
    one LUT (the uncached per-task path recomputes it per part), and
    cache hits skip LC entirely;
  * ``heat_estimator`` — an :class:`repro.runtime.cache.OnlineHeatEstimator`
    fed each batch's CL output; with ``cfg.relayout_every > 0`` the
    refreshed heat periodically re-drives ``build_layout`` (split /
    duplicate / allocate).  Re-layout is double-buffered:
    :meth:`DistributedEngine.prepare_layout` builds the next placement
    while the current one keeps serving, :meth:`swap_layout` installs it
    atomically between batches (:meth:`refresh_layout` = both in one);
  * ``tasks_controller`` — a
    :class:`repro.runtime.batching.TasksPerShardController` choosing the
    static task-table width per batch size instead of one global
    ``cfg.tasks_per_shard``.

Shapes and units throughout: queries (Q, D) f32; probes (Q, P) i32
cluster ids; task tables (S, T) i32 with -1 padding; candidate outputs
(S, T, k); heat is expected cluster accesses per query; all latencies
seconds.  Invariants: served results are independent of batch
composition (per-query merge), identical across the vmap and shard_map
paths, and — at exact cache granularity — bit-identical with the LUT
cache on or off (asserted in tests/test_serving_v2.py).
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


from repro.core.ivf import IVFPQIndex, PaddedClusters
from repro.core.pq import PQCodebook
from repro.core.adc import (QuantizedLUT, adc_distances,
                            adc_distances_quantized, build_lut_batch,
                            quantize_lut)
from repro.core.topk import topk_smallest
from repro.core.filter import mask_scoped_distances
from repro.util import next_pow2
from repro.core.layout import Layout, build_layout, estimate_heat
from repro.core.scheduler import ShardSchedule, schedule_batch
from repro.core.perf_model import TaskLatencyModel, make_task_latency_model


class ShardedIndex(NamedTuple):
    """Per-shard instance tensors, materialized from a Layout (offline)."""
    codes: jax.Array        # (S, slots, cpart, M) u8/u16
    ids: jax.Array          # (S, slots, cpart) i32, -1 pad
    sizes: jax.Array        # (S, slots) i32
    cluster_of: jax.Array   # (S, slots) i32 — original cluster id (-1 empty)
    start_of: jax.Array     # (S, slots) i32 — part row offset (diagnostics)
    slot_of_instance: np.ndarray   # (n_instances,) host-side
    centroids: jax.Array    # (nlist, D) f32 — replicated
    codebook: PQCodebook    # replicated
    rotation: Optional[jax.Array]

    @property
    def n_shards(self) -> int:
        return self.codes.shape[0]

    @property
    def slots(self) -> int:
        return self.codes.shape[1]

    @property
    def cpart(self) -> int:
        return self.codes.shape[2]


def materialize_shards(index: IVFPQIndex, layout: Layout,
                       pad_multiple: int = 8) -> ShardedIndex:
    """Offline: CSR index + layout -> dense per-shard tensors (numpy)."""
    codes_np = np.asarray(index.codes)
    ids_np = np.asarray(index.ids)
    offsets = np.asarray(index.offsets)
    m = codes_np.shape[1]
    s = layout.n_shards
    slots = max(int((layout.shard_of == sh).sum()) for sh in range(s))
    slots = max(slots, 1)
    cpart = max(i.size for i in layout.instances)
    cpart = max(-(-cpart // pad_multiple) * pad_multiple, pad_multiple)

    sh_codes = np.zeros((s, slots, cpart, m), dtype=codes_np.dtype)
    sh_ids = np.full((s, slots, cpart), -1, np.int32)
    sh_sizes = np.zeros((s, slots), np.int32)
    sh_cluster = np.full((s, slots), -1, np.int32)
    sh_start = np.zeros((s, slots), np.int32)
    slot_of = np.full(len(layout.instances), -1, np.int64)

    cursor = np.zeros(s, np.int64)
    for inst in layout.instances:
        sh = int(layout.shard_of[inst.instance_id])
        slot = int(cursor[sh])
        cursor[sh] += 1
        row0 = offsets[inst.cluster] + inst.start
        sz = int(inst.size)
        sh_codes[sh, slot, :sz] = codes_np[row0:row0 + sz]
        sh_ids[sh, slot, :sz] = ids_np[row0:row0 + sz]
        sh_sizes[sh, slot] = sz
        sh_cluster[sh, slot] = inst.cluster
        sh_start[sh, slot] = inst.start
        slot_of[inst.instance_id] = slot

    return ShardedIndex(jnp.asarray(sh_codes), jnp.asarray(sh_ids),
                        jnp.asarray(sh_sizes), jnp.asarray(sh_cluster),
                        jnp.asarray(sh_start), slot_of,
                        index.centroids, index.codebook, index.rotation)


def materialize_shards_tiered(index: IVFPQIndex, layout: Layout, tier,
                              pad_multiple: int = 8):
    """Tiered materialize: device tensors hold only RAM-resident clusters.

    ``index`` is a tiered handle's lean CSR view (real offsets, empty
    code arrays); rows come from the :class:`repro.storage.TieredStore`
    instead.  Instances of clusters cold at snapshot time get device
    ``sizes = 0`` — the shard step then yields inf/-1 candidates for
    them (ignored by the merge) and the engine scans those probes
    host-side through the tier's fetch path.  Returns ``(sindex,
    cold_mask)``; the mask is the snapshot the serving path routes by
    until the next re-layout (a cluster promoted mid-epoch still scans
    host-side — correct, just not yet device-accelerated).
    """
    m = index.codebook.m
    s = layout.n_shards
    slots = max(int((layout.shard_of == sh).sum()) for sh in range(s))
    slots = max(slots, 1)
    cpart = max(i.size for i in layout.instances)
    cpart = max(-(-cpart // pad_multiple) * pad_multiple, pad_multiple)

    resident = np.asarray(tier.resident_mask).copy()
    sh_codes = np.zeros((s, slots, cpart, m), np.uint8)
    sh_ids = np.full((s, slots, cpart), -1, np.int32)
    sh_sizes = np.zeros((s, slots), np.int32)
    sh_cluster = np.full((s, slots), -1, np.int32)
    sh_start = np.zeros((s, slots), np.int32)
    slot_of = np.full(len(layout.instances), -1, np.int64)

    cursor = np.zeros(s, np.int64)
    for inst in layout.instances:
        sh = int(layout.shard_of[inst.instance_id])
        slot = int(cursor[sh])
        cursor[sh] += 1
        sz = int(inst.size)
        if resident[inst.cluster]:
            codes_c, ids_c = tier.peek(inst.cluster)
            sh_codes[sh, slot, :sz] = codes_c[inst.start:inst.start + sz]
            sh_ids[sh, slot, :sz] = ids_c[inst.start:inst.start + sz]
            sh_sizes[sh, slot] = sz
        # cold: sizes stay 0 — the host-side tier scan owns this cluster
        sh_cluster[sh, slot] = inst.cluster
        sh_start[sh, slot] = inst.start
        slot_of[inst.instance_id] = slot

    sindex = ShardedIndex(jnp.asarray(sh_codes), jnp.asarray(sh_ids),
                          jnp.asarray(sh_sizes), jnp.asarray(sh_cluster),
                          jnp.asarray(sh_start), slot_of,
                          index.centroids, index.codebook, index.rotation)
    return sindex, ~resident


# ---------------------------------------------------------------------------
# Per-shard task pipeline — the "DPU kernel" (RC + LC + DC + TS).
# ---------------------------------------------------------------------------

def _shard_tasks_fn(codes, ids, sizes, cluster_of, qidx, sidx, queries,
                    centroids, codebook: PQCodebook, rotation, *, k: int,
                    strategy: str, use_kernels: bool,
                    fused_scan: bool = False, lut_dtype=None,
                    scan_block: int = 512, quantize: bool = False):
    """One shard's batch: static (T,) task table -> (T, k) candidates.

    codes (slots, cpart, M) ... qidx/sidx (T,) with -1 padding.

    ``fused_scan`` (§Perf, beyond-paper): stream the DC phase over C-blocks
    with a running top-k carried in the scan — the (T, C) distance matrix
    never reaches HBM (writeback drops from C to k floats/task), mirroring
    the fused Pallas kernel.  ``lut_dtype`` (e.g. bf16) halves LUT gather
    traffic (the paper's int-LUT spirit on TPU dtypes);
    ``lut_dtype="uint8"`` (or ``quantize=True``,
    ``EngineConfig.lut_dtype="uint8"``) is the full uint8 fast path on
    both the plain and fused-scan dataflows: LC gains the
    affine-quantize epilogue and DC scans uint8 entries with
    per-(task, subspace) scales.
    """
    t = qidx.shape[0]
    valid = qidx >= 0
    qi = jnp.clip(qidx, 0, queries.shape[0] - 1)
    si = jnp.clip(sidx, 0, codes.shape[0] - 1)

    q = queries[qi].astype(jnp.float32)                       # (T, D)
    cl = jnp.clip(cluster_of[si], 0, centroids.shape[0] - 1)
    residual = q - centroids[cl]                              # (T, D) -- RC
    if rotation is not None:
        residual = residual @ rotation
    task_codes = codes[si]                                    # (T, cpart, M)
    task_ids = ids[si]                                        # (T, cpart)
    task_sizes = jnp.where(valid, sizes[si], 0)               # invalid -> 0

    if use_kernels:
        from repro.kernels import ops as kops
        if quantize:
            lut = kops.lut_build_q(residual, codebook.codebooks,
                                   codebook.sqnorms)
        else:
            lut = kops.lut_build(residual, codebook.codebooks,
                                 codebook.sqnorms)
        bd, bi = kops.pq_scan_topk(lut, task_codes, task_ids, task_sizes, k,
                                   strategy=strategy)
    elif fused_scan:
        lut = build_lut_batch(codebook, residual)             # LC
        if quantize or lut_dtype == "uint8":
            # full uint8 fast path, fused: the affine-quantize epilogue
            # runs right after LC and the streaming DC scans u8 entries
            # with per-(task, subspace) scales — HBM traffic per block
            # drops 4x on top of the fused writeback saving
            lut = quantize_lut(lut)
        elif lut_dtype is not None:
            lut = lut.astype(lut_dtype)
        bd, bi = _fused_scan_topk(lut, task_codes, task_ids, task_sizes, k,
                                  block=scan_block)
    else:
        lut = build_lut_batch(codebook, residual)             # LC
        strat = "gather" if strategy == "gather" else "onehot"
        if quantize or lut_dtype == "uint8":
            d = adc_distances_quantized(quantize_lut(lut), task_codes,
                                        task_sizes, strat)    # DC (u8)
        else:
            if lut_dtype is not None:
                lut = lut.astype(lut_dtype)
            d = adc_distances(lut, task_codes, task_sizes, strat)   # DC
        bd, bi = topk_smallest(d, task_ids, k)                # TS
    bi = jnp.where(jnp.isfinite(bd), bi, -1)
    return bd, bi


def _shard_tasks_scoped_fn(codes, ids, sizes, cluster_of, qidx, sidx,
                           queries, centroids, codebook: PQCodebook,
                           rotation, meta_tenant, meta_tags, q_tenants,
                           q_terms, *, k: int, strategy: str,
                           quantize: bool = False):
    """Scoped ``_shard_tasks_fn`` (PR 10): RC+LC+DC as usual, then the
    tenant/predicate mask strikes out-of-scope candidate rows to ``+inf``
    before TS.  Each task inherits its query's scope via ``qidx`` (pad
    tasks gather query 0's scope harmlessly — their ``sizes == 0`` mask
    already invalidates every row).  The kernels/fused fast paths fuse TS
    into the scan and cannot interpose the mask, so scoped traffic always
    runs this jnp dataflow."""
    valid = qidx >= 0
    qi = jnp.clip(qidx, 0, queries.shape[0] - 1)
    si = jnp.clip(sidx, 0, codes.shape[0] - 1)

    q = queries[qi].astype(jnp.float32)                       # (T, D)
    cl = jnp.clip(cluster_of[si], 0, centroids.shape[0] - 1)
    residual = q - centroids[cl]                              # RC
    if rotation is not None:
        residual = residual @ rotation
    task_codes = codes[si]                                    # (T, cpart, M)
    task_ids = ids[si]                                        # (T, cpart)
    task_sizes = jnp.where(valid, sizes[si], 0)               # invalid -> 0

    lut = build_lut_batch(codebook, residual)                 # LC
    strat = "gather" if strategy == "gather" else "onehot"
    if quantize:
        d = adc_distances_quantized(quantize_lut(lut), task_codes,
                                    task_sizes, strat)        # DC (u8)
    else:
        d = adc_distances(lut, task_codes, task_sizes, strat)  # DC
    d = mask_scoped_distances(d, task_ids, meta_tenant, meta_tags,
                              q_tenants[qi], q_terms[qi])
    bd, bi = topk_smallest(d, task_ids, k)                    # TS
    return bd, jnp.where(jnp.isfinite(bd), bi, -1)


@functools.partial(jax.jit, static_argnames=("k", "strategy", "quantize"))
def run_shards_vmap_scoped(sindex: ShardedIndex, qidx: jax.Array,
                           sidx: jax.Array, queries: jax.Array,
                           meta_tenant: jax.Array, meta_tags: jax.Array,
                           q_tenants: jax.Array, q_terms: jax.Array, *,
                           k: int, strategy: str = "onehot",
                           quantize: bool = False):
    """Simulation path for scoped batches: vmap over the shard axis with
    the scope arrays replicated alongside queries (the same one
    host->PIM broadcast — per-query tenant/terms ride with the query)."""
    fn = functools.partial(_shard_tasks_scoped_fn, codebook=sindex.codebook,
                           rotation=sindex.rotation,
                           meta_tenant=meta_tenant, meta_tags=meta_tags,
                           q_tenants=q_tenants, q_terms=q_terms, k=k,
                           strategy=strategy, quantize=quantize)
    return jax.vmap(
        lambda c, i, sz, co, qq, ss: fn(c, i, sz, co, qq, ss, queries,
                                        sindex.centroids)
    )(sindex.codes, sindex.ids, sindex.sizes, sindex.cluster_of, qidx, sidx)


def _fused_scan_topk(lut, task_codes, task_ids, task_sizes, k: int,
                     block: int = 512):
    """Streaming DC+TS: scan over C-blocks, (T, k) running winners carried.

    jnp mirror of kernels/pq_scan.pq_scan_topk_pallas — same dataflow the
    fused kernel executes per VMEM block, expressed at XLA level so the
    dry-run's lowered artifact reflects the reduced HBM writeback.
    ``lut`` may be a (T,)-batched :class:`QuantizedLUT`, in which case
    each block runs the u8 gather-and-scale scan (the fused mirror of
    ``kernels/pq_scan.pq_scan_topk_q_pallas``).
    """
    from repro.core.adc import scan_codes, scan_codes_quantized
    scan_fn = (scan_codes_quantized if isinstance(lut, QuantizedLUT)
               else scan_codes)
    t, c, m = task_codes.shape
    pad = (-c) % block
    if pad:
        task_codes = jnp.pad(task_codes, ((0, 0), (0, pad), (0, 0)))
        task_ids = jnp.pad(task_ids, ((0, 0), (0, pad)),
                           constant_values=-1)
    nblk = (c + pad) // block
    codes_b = task_codes.reshape(t, nblk, block, m).swapaxes(0, 1)
    ids_b = task_ids.reshape(t, nblk, block).swapaxes(0, 1)

    def step(carry, inp):
        bd, bi = carry
        cb, ib, blk_i = inp
        d = jax.vmap(scan_fn)(lut, cb).astype(jnp.float32)     # (T, block)
        col = blk_i * block + jnp.arange(block)[None, :]
        d = jnp.where(col < task_sizes[:, None], d, jnp.inf)
        nd, ni = topk_smallest(jnp.concatenate([bd, d], axis=1),
                               jnp.concatenate([bi, ib], axis=1), k)
        return (nd, ni), None

    # derive the carry init from varying inputs so shard_map's manual-axes
    # tracking matches the scan body's outputs (full_like inherits vma)
    bd0 = jnp.full_like(task_ids[:, :k], 0).astype(jnp.float32) + jnp.inf
    bi0 = jnp.full_like(task_ids[:, :k], -1)
    (bd, bi), _ = jax.lax.scan(step, (bd0, bi0),
                               (codes_b, ids_b, jnp.arange(nblk)))
    return bd, bi


# ---------------------------------------------------------------------------
# Execution paths
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "strategy", "use_kernels",
                                             "quantize"))
def run_shards_vmap(sindex: ShardedIndex, qidx: jax.Array, sidx: jax.Array,
                    queries: jax.Array, *, k: int, strategy: str = "onehot",
                    use_kernels: bool = False, quantize: bool = False):
    """Simulation path: vmap over the shard axis on one device."""
    fn = functools.partial(_shard_tasks_fn, codebook=sindex.codebook,
                           rotation=sindex.rotation, k=k, strategy=strategy,
                           use_kernels=use_kernels, quantize=quantize)
    return jax.vmap(
        lambda c, i, sz, co, qq, ss: fn(c, i, sz, co, qq, ss, queries,
                                        sindex.centroids)
    )(sindex.codes, sindex.ids, sindex.sizes, sindex.cluster_of, qidx, sidx)


def make_sharded_step(mesh, sindex: ShardedIndex, *, k: int,
                      strategy: str = "onehot", use_kernels: bool = False,
                      quantize: bool = False, axis: str = "shards"):
    """Production path: shard_map over a real mesh axis.

    Returns a jitted step(codes, ids, sizes, cluster_of, qidx, sidx, queries,
    centroids) -> per-shard (T, k) candidates, with cluster data sharded and
    queries/centroids replicated (the one host->PIM broadcast per batch).
    """
    fn = functools.partial(_shard_tasks_fn, codebook=sindex.codebook,
                           rotation=sindex.rotation, k=k, strategy=strategy,
                           use_kernels=use_kernels, quantize=quantize)

    def per_shard(codes, ids, sizes, cluster_of, qidx, sidx, queries,
                  centroids):
        bd, bi = fn(codes[0], ids[0], sizes[0], cluster_of[0], qidx[0],
                    sidx[0], queries, centroids)
        return bd[None], bi[None]

    sharded = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(axis),
                  P(), P()),
        out_specs=(P(axis), P(axis)))
    return jax.jit(sharded)


@jax.jit
def miss_residuals(miss_queries: jax.Array, centroids: jax.Array,
                   crows: jax.Array, rotation: Optional[jax.Array]):
    """RC for cache-miss (query, cluster) pairs only: rotated residuals
    (R, D) f32 for ``miss_queries[r] - centroids[crows[r]]`` — the cached
    path's LC input.  Queries are gathered host-side and padded to a
    power of two, so the compiled shape depends only on the miss count
    (precompile_lc can warm every shape) and hit rows never pay the
    rotation matmul."""
    residual = miss_queries.astype(jnp.float32) - centroids[crows]
    if rotation is not None:
        residual = residual @ rotation
    return residual


def _shard_tasks_lut_fn(codes, ids, sizes, qidx, sidx, lidx, lut_bank, *,
                        k: int, strategy: str, use_kernels: bool):
    """One shard's batch with LUTs precomputed host-side: DC + TS only.

    Same task-table contract as ``_shard_tasks_fn`` (qidx/sidx (T,) with
    -1 padding) plus ``lidx`` (T,) indexing each task's LUT in the
    replicated ``lut_bank`` — the f32 (Q*P, M, CB) array, or a
    (Q*P,)-batched :class:`QuantizedLUT` when the cache runs uint8 (the
    replicated broadcast then ships ~4x fewer bytes).  Skipping RC+LC
    here is what the LUT cache buys the sharded path; DC/TS are
    byte-for-byte the same ops as the uncached step, so results are
    bit-identical per dtype.

    ``lidx == -1`` marks a task with no bank row (a carried-over task
    whose cluster is absent from this batch's probe lists under
    flush=False): it must be invalidated, not scored against row 0."""
    quantized = isinstance(lut_bank, QuantizedLUT)
    n_rows = (lut_bank.lut_q if quantized else lut_bank).shape[0]
    valid = (qidx >= 0) & (lidx >= 0)
    si = jnp.clip(sidx, 0, codes.shape[0] - 1)
    li = jnp.clip(lidx, 0, n_rows - 1)
    lut = jax.tree.map(lambda a: a[li], lut_bank)             # (T, ...) rows
    task_codes = codes[si]                                    # (T, cpart, M)
    task_ids = ids[si]                                        # (T, cpart)
    task_sizes = jnp.where(valid, sizes[si], 0)               # invalid -> 0
    if use_kernels:
        from repro.kernels import ops as kops
        bd, bi = kops.pq_scan_topk(lut, task_codes, task_ids, task_sizes, k,
                                   strategy=strategy)
    else:
        strat = "gather" if strategy == "gather" else "onehot"
        if quantized:
            d = adc_distances_quantized(lut, task_codes, task_sizes, strat)
        else:
            d = adc_distances(lut, task_codes, task_sizes, strat)   # DC
        bd, bi = topk_smallest(d, task_ids, k)                # TS
    bi = jnp.where(jnp.isfinite(bd), bi, -1)
    return bd, bi


@functools.partial(jax.jit, static_argnames=("k", "strategy", "use_kernels"))
def run_shards_vmap_lut(sindex: ShardedIndex, qidx: jax.Array,
                        sidx: jax.Array, lidx: jax.Array,
                        lut_bank: jax.Array, *, k: int,
                        strategy: str = "onehot",
                        use_kernels: bool = False):
    """Simulation path for the cached step: vmap over the shard axis with
    the LUT bank replicated (the host->PIM LUT broadcast)."""
    return jax.vmap(
        lambda c, i, sz, qq, ss, ll: _shard_tasks_lut_fn(
            c, i, sz, qq, ss, ll, lut_bank, k=k, strategy=strategy,
            use_kernels=use_kernels)
    )(sindex.codes, sindex.ids, sindex.sizes, qidx, sidx, lidx)


@functools.partial(jax.jit, static_argnames=("k", "strategy"))
def run_shards_vmap_lut_scoped(sindex: ShardedIndex, qidx: jax.Array,
                               sidx: jax.Array, lidx: jax.Array,
                               lut_bank: jax.Array, meta_tenant: jax.Array,
                               meta_tags: jax.Array, q_tenants: jax.Array,
                               q_terms: jax.Array, *, k: int,
                               strategy: str = "onehot"):
    """Scoped cached step: DC from the replicated LUT bank, then the
    tenant/predicate mask before TS (LUTs depend only on query x cluster,
    so hits are shared between scoped and unscoped traffic)."""
    def per_shard(codes, ids, sizes, qidx, sidx, lidx):
        quantized = isinstance(lut_bank, QuantizedLUT)
        n_rows = (lut_bank.lut_q if quantized else lut_bank).shape[0]
        valid = (qidx >= 0) & (lidx >= 0)
        qi = jnp.clip(qidx, 0, q_tenants.shape[0] - 1)
        si = jnp.clip(sidx, 0, codes.shape[0] - 1)
        li = jnp.clip(lidx, 0, n_rows - 1)
        lut = jax.tree.map(lambda a: a[li], lut_bank)
        task_codes = codes[si]
        task_ids = ids[si]
        task_sizes = jnp.where(valid, sizes[si], 0)
        strat = "gather" if strategy == "gather" else "onehot"
        if quantized:
            d = adc_distances_quantized(lut, task_codes, task_sizes, strat)
        else:
            d = adc_distances(lut, task_codes, task_sizes, strat)
        d = mask_scoped_distances(d, task_ids, meta_tenant, meta_tags,
                                  q_tenants[qi], q_terms[qi])
        bd, bi = topk_smallest(d, task_ids, k)
        return bd, jnp.where(jnp.isfinite(bd), bi, -1)

    return jax.vmap(per_shard)(sindex.codes, sindex.ids, sindex.sizes,
                               qidx, sidx, lidx)


def make_sharded_step_lut(mesh, sindex: ShardedIndex, *, k: int,
                          strategy: str = "onehot",
                          use_kernels: bool = False, axis: str = "shards"):
    """Production path for the cached step: shard_map with task tables
    sharded and the LUT bank replicated alongside queries/centroids."""
    def per_shard(codes, ids, sizes, qidx, sidx, lidx, lut_bank):
        bd, bi = _shard_tasks_lut_fn(codes[0], ids[0], sizes[0], qidx[0],
                                     sidx[0], lidx[0], lut_bank, k=k,
                                     strategy=strategy,
                                     use_kernels=use_kernels)
        return bd[None], bi[None]

    sharded = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(axis), P()),
        out_specs=(P(axis), P(axis)))
    return jax.jit(sharded)


def merge_host(qidx: np.ndarray, best_d: np.ndarray, best_i: np.ndarray,
               n_queries: int, k: int):
    """UPMEM-faithful host merge: per-query top-k over all task candidates."""
    out_d = np.full((n_queries, k), np.inf, np.float32)
    out_i = np.full((n_queries, k), -1, np.int32)
    flat_q = qidx.reshape(-1)
    flat_d = best_d.reshape(-1, k)
    flat_i = best_i.reshape(-1, k)
    buckets_d = [[] for _ in range(n_queries)]
    buckets_i = [[] for _ in range(n_queries)]
    for t in range(flat_q.shape[0]):
        q = int(flat_q[t])
        if q < 0:
            continue
        buckets_d[q].append(flat_d[t])
        buckets_i[q].append(flat_i[t])
    for q in range(n_queries):
        if not buckets_d[q]:
            continue
        ds = np.concatenate(buckets_d[q])
        is_ = np.concatenate(buckets_i[q])
        order = np.argsort(ds, kind="stable")[:k]
        out_d[q, :len(order)] = ds[order]
        out_i[q, :len(order)] = is_[order]
    return out_d, out_i


@functools.partial(jax.jit, static_argnames=("n_queries", "k"))
def merge_on_device(qidx: jax.Array, best_d: jax.Array, best_i: jax.Array,
                    *, n_queries: int, k: int):
    """On-device merge (TPU path): mask-per-query + top-k.  O(Q * S*T*k)
    compare ops — fine for serving batches, avoided on UPMEM by design."""
    flat_q = qidx.reshape(-1)                                  # (ST,)
    flat_d = best_d.reshape(-1)                                # (ST*k,)
    flat_i = best_i.reshape(-1)
    task_q = jnp.repeat(flat_q, k)                             # (ST*k,)
    qmat = task_q[None, :] == jnp.arange(n_queries)[:, None]   # (Q, ST*k)
    dmat = jnp.where(qmat, flat_d[None, :], jnp.inf)
    nd, idx = jax.lax.top_k(-dmat, k)
    return -nd, jnp.where(jnp.isfinite(-nd), flat_i[idx], -1)


# ---------------------------------------------------------------------------
# End-to-end engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EngineConfig:
    n_shards: int
    nprobe: int
    k: int
    split_max: Optional[int] = None
    dup_budget_bytes: int = 0
    tasks_per_shard: int = 1024
    strategy: str = "onehot"
    use_kernels: bool = False
    enable_filter: bool = False
    filter_ratio: float = 1.35
    naive_layout: bool = False
    naive_schedule: bool = False
    # serving v2: batches between heat-driven re-layouts (0 = never;
    # requires a heat_estimator on the engine)
    relayout_every: int = 0
    # quantized-LUT fast path: "uint8" quantizes LUTs per (task, subspace)
    # end to end — LC epilogue, DC scan, the replicated cached-path bank,
    # and the perf model's byte pricing (b_lut 4 -> 1)
    lut_dtype: str = "f32"


class _Placement(NamedTuple):
    """One fully-materialized placement: layout + shard tensors + steps.

    Built off to the side by :meth:`DistributedEngine.prepare_layout`
    (double buffering) and installed atomically by ``swap_layout``.

    ``index``/``latency`` are set only by :meth:`prepare_index` (a live-
    mutation generation swap): the placement then carries the NEW index
    generation's CSR tensors and re-priced latency model, and installing
    it also swaps ``engine.index`` and invalidates per-generation state
    (LUT cache, heat estimator).  Plain re-layouts leave them None."""
    layout: Layout
    sindex: ShardedIndex
    cluster_of_host: np.ndarray
    step: Optional[object]
    step_lut: Optional[object]
    index: Optional[IVFPQIndex] = None
    latency: Optional[TaskLatencyModel] = None
    cold_mask: Optional[np.ndarray] = None   # tiered: True = not on device


@functools.partial(jax.jit, static_argnames=("k", "strategy"))
def _cold_scan(lut, codes, ids, sizes, *, k: int, strategy: str):
    """DC + TS over tier-fetched cold tasks: (T, cap, M) u8 codes +
    per-task LUT rows -> (T, k) candidates (same candidate contract as a
    shard step's output — appended before the host merge, so cold probes
    are exact, never approximated).  Pad tasks carry ``sizes = 0`` and
    fall out as inf/-1."""
    strat = "gather" if strategy == "gather" else "onehot"
    if isinstance(lut, QuantizedLUT):
        d = adc_distances_quantized(lut, codes, sizes, strat)
    else:
        d = adc_distances(lut, codes, sizes, strat)
    bd, bi = topk_smallest(d, ids, k)
    return bd, jnp.where(jnp.isfinite(bd), bi, -1)


@functools.partial(jax.jit, static_argnames=("k", "strategy"))
def _cold_scan_scoped(lut, codes, ids, sizes, meta_tenant, meta_tags,
                      t_tenants, t_terms, *, k: int, strategy: str):
    """Scoped :func:`_cold_scan`: same tier-fetched DC+TS with the
    tenant/predicate mask applied per task row (``t_tenants``/``t_terms``
    already gathered per task host-side; pad tasks carry tenant -1 and
    all-NO_TAG terms on top of ``sizes = 0``)."""
    strat = "gather" if strategy == "gather" else "onehot"
    if isinstance(lut, QuantizedLUT):
        d = adc_distances_quantized(lut, codes, sizes, strat)
    else:
        d = adc_distances(lut, codes, sizes, strat)
    d = mask_scoped_distances(d, ids, meta_tenant, meta_tags,
                              t_tenants, t_terms)
    bd, bi = topk_smallest(d, ids, k)
    return bd, jnp.where(jnp.isfinite(bd), bi, -1)


class DistributedEngine:
    """Offline build (layout + shards) and online batched search.

    Optional serving-v2 collaborators (see module docstring):
    ``lut_cache`` (skip LC on hits), ``heat_estimator`` (online heat +
    periodic re-layout), ``tasks_controller`` (per-batch-size task-table
    width).  All default to None, which reproduces the PR 1 engine
    exactly.
    """

    def __init__(self, index: IVFPQIndex, cfg: EngineConfig,
                 sample_probes: np.ndarray,
                 latency: Optional[TaskLatencyModel] = None,
                 mesh=None, lut_cache=None, heat_estimator=None,
                 tasks_controller=None, tiered_store=None, meta=None):
        from repro.core.perf_model import (IndexParams, UPMEM_PROFILE,
                                           lut_width_bytes)
        if cfg.lut_dtype not in ("f32", "uint8"):
            raise ValueError(f"EngineConfig.lut_dtype must be 'f32' or "
                             f"'uint8', got {cfg.lut_dtype!r}")
        self.cfg = cfg
        self.index = index
        self.heat = estimate_heat(sample_probes, index.nlist)
        sizes = np.asarray(index.sizes)
        # quantized LUTs shrink every b_lut-priced byte term (DC gathers +
        # result writes, LC table writes), so the Eq. 15 latencies behind
        # TasksPerShardController and c2io see the real traffic
        self.latency = latency or make_task_latency_model(
            IndexParams(n_total=int(sizes.sum()), nlist=index.nlist, q=1,
                        d=index.dim, k=cfg.k, p=cfg.nprobe,
                        m=index.codebook.m, cb=index.codebook.cb,
                        b_lut=lut_width_bytes(cfg.lut_dtype)),
            UPMEM_PROFILE)
        if (lut_cache is not None
                and getattr(lut_cache, "lut_dtype", "f32") != cfg.lut_dtype):
            raise ValueError(
                f"lut_cache.lut_dtype={lut_cache.lut_dtype!r} disagrees "
                f"with EngineConfig.lut_dtype={cfg.lut_dtype!r}; cached "
                f"and uncached scans must run the same dtype")
        self.mesh = mesh
        self.lut_cache = lut_cache
        self.heat_estimator = heat_estimator
        self.tasks_controller = tasks_controller
        # tiered storage: device shard tensors hold only the tier's
        # resident clusters; probes of snapshot-cold clusters are scanned
        # host-side through the tier's batched fetch path (_scan_cold)
        self.tiered_store = tiered_store
        # per-vector metadata (repro.core.filter.VectorMeta) for tenant-
        # scoped / predicate-filtered search; None = single-tenant engine
        self.meta = meta
        self._cold_mask: Optional[np.ndarray] = None
        # per-batch degrade report, read by the serving adapter after
        # search() returns (one worker serves a replica, so no race)
        self.last_batch_info: dict = {"degraded": False,
                                      "dropped_probes": 0}
        self.batches_served = 0
        self.relayouts = 0
        self.generations = 0        # index generations installed (mutation)
        self._pending: Optional[_Placement] = None
        self._pending_heat: Optional[np.ndarray] = None
        self._swap_on_next_batch = False
        self._relayout_thread: Optional[threading.Thread] = None
        self._relayout_error: Optional[BaseException] = None
        self._build(self.heat)

    def _materialize(self, heat: np.ndarray,
                     index: Optional[IVFPQIndex] = None,
                     latency: Optional[TaskLatencyModel] = None
                     ) -> _Placement:
        """Build a placement from a heat vector without touching serving
        state.  Plain re-layouts (``index=None``) place the engine's
        current index: cluster ids — and therefore LUT-cache keys — are
        stable across rebuilds; only placement changes.  A generation
        swap passes the NEW index (+ re-priced latency model), which
        rides inside the placement until install."""
        idx = self.index if index is None else index
        lat = self.latency if latency is None else latency
        sizes = np.asarray(idx.sizes)
        bytes_per_row = idx.codebook.m + 4
        layout = build_layout(
            sizes, heat, self.cfg.n_shards, split_max=self.cfg.split_max,
            dup_budget_bytes=self.cfg.dup_budget_bytes,
            bytes_per_row=bytes_per_row, latency=lat,
            naive=self.cfg.naive_layout)
        cold_mask = None
        if self.tiered_store is not None:
            sindex, cold_mask = materialize_shards_tiered(
                idx, layout, self.tiered_store)
        else:
            sindex = materialize_shards(idx, layout)
        step = step_lut = None
        if self.mesh is not None:
            step = make_sharded_step(self.mesh, sindex, k=self.cfg.k,
                                     strategy=self.cfg.strategy,
                                     use_kernels=self.cfg.use_kernels,
                                     quantize=self.cfg.lut_dtype == "uint8")
            step_lut = make_sharded_step_lut(
                self.mesh, sindex, k=self.cfg.k, strategy=self.cfg.strategy,
                use_kernels=self.cfg.use_kernels)
        return _Placement(layout, sindex, np.asarray(sindex.cluster_of),
                          step, step_lut, index=index,
                          latency=None if index is None else lat,
                          cold_mask=cold_mask)

    def _install(self, placement: _Placement) -> None:
        """Point the serving path at ``placement``.  Deferred-task carry
        is dropped — callers re-issue via flush rounds.  A placement
        carrying a new index generation also swaps the engine's index
        and latency model (per-generation cache/heat invalidation is
        handled by ``swap_layout``, the only caller that can see one)."""
        if placement.index is not None:
            self.index = placement.index
            if placement.latency is not None:
                self.latency = placement.latency
        self.layout = placement.layout
        self.sindex = placement.sindex
        self._cluster_of_host = placement.cluster_of_host
        self._cold_mask = placement.cold_mask
        self.carry: list = []
        self._step = placement.step
        self._step_lut = placement.step_lut

    def _build(self, heat: np.ndarray) -> None:
        self._install(self._materialize(heat))

    # -- serving-v2 hooks --------------------------------------------------
    @property
    def nprobe(self) -> int:
        return self.cfg.nprobe

    def prepare_layout(self, heat: Optional[np.ndarray] = None) -> dict:
        """Double-buffered re-layout, phase 1: re-run split/duplicate/
        allocate with refreshed heat (§IV-C fed by the online estimator)
        and materialize the NEXT placement's shard tensors off to the
        side, while the CURRENT placement keeps serving.

        Nothing observable changes until :meth:`swap_layout`; the
        expensive materialize (and, on a mesh, the step rebuild) is thus
        amortized outside the serving path instead of stalling the batch
        that triggered it.  Calling again overwrites the pending
        placement.  Returns predicted imbalance of current vs pending."""
        self._sync_relayout_thread()       # a live background rebuild may
        self._swap_on_next_batch = False   # not race or resurrect pending
        if heat is None:
            if self.heat_estimator is None:
                raise ValueError("prepare_layout needs heat or an estimator")
            heat = self.heat_estimator.heat()
        self._pending_heat = np.asarray(heat, np.float64)
        self._pending = self._materialize(self._pending_heat)
        return {"imbalance_current": self.layout.stats(
                    self.latency)["imbalance"],
                "imbalance_pending": self._pending.layout.stats(
                    self.latency)["imbalance"]}

    def swap_layout(self) -> dict:
        """Double-buffered re-layout, phase 2: atomically install the
        placement built by :meth:`prepare_layout` — an O(1) pointer swap
        between batches (results are placement-independent, tests assert
        it).  Deferred-task carry is dropped — callers re-issue via
        flush rounds.  Returns before/after predicted-imbalance stats."""
        self._sync_relayout_thread()       # complete an in-flight rebuild
        if self._pending is None:
            raise ValueError("swap_layout: no pending placement "
                             "(call prepare_layout first)")
        before = self.layout.stats(self.latency)["imbalance"]
        new_generation = self._pending.index is not None
        self.heat = self._pending_heat
        self._install(self._pending)
        self._pending = None
        self._pending_heat = None
        self._swap_on_next_batch = False
        self.relayouts += 1
        if new_generation:
            # per-generation invalidation: cluster ids changed meaning
            # (splits/merges renumber) and codebooks may have retrained,
            # so cached LUTs and decayed heat are both stale.  The
            # estimator resets IN PLACE (admission policy and router hold
            # references to it), seeded with the heat the new placement
            # was built from so cold-start admission stays sane.
            self.generations += 1
            if self.lut_cache is not None:
                self.lut_cache.clear()
            if self.heat_estimator is not None:
                self.heat_estimator.reset(nlist=self.index.nlist,
                                          seed=self.heat)
        if self.tasks_controller is not None:
            # re-price the width prediction: split decisions (and so
            # tasks/query) may have changed with the new heat
            self.tasks_controller.retune(*self._layout_task_stats())
        after = self.layout.stats(self.latency)["imbalance"]
        return {"imbalance_before": before, "imbalance_after": after}

    def refresh_layout(self, heat: Optional[np.ndarray] = None) -> dict:
        """prepare_layout + swap_layout in one synchronous call (the
        pre-double-buffering API, kept for direct callers)."""
        self.prepare_layout(heat)
        return self.swap_layout()

    # -- live-mutation generation swaps -----------------------------------
    def prepare_index(self, index: IVFPQIndex,
                      heat: Optional[np.ndarray] = None) -> None:
        """Double-buffered *generation* swap, phase 1: materialize a
        placement for a NEW index (mutated / split / merged / retrained
        by the live-index maintenance loop) off to the side, while the
        current generation keeps serving.

        The latency model is re-priced for the new generation's size and
        cluster count.  ``heat`` defaults to the online estimator's view
        when the cluster count is unchanged, else to uniform (split/merge
        renumbered the clusters, so old per-cluster heat is meaningless).
        ``swap_layout`` installs it — swapping ``self.index`` too and
        invalidating the LUT cache + heat estimator."""
        from repro.core.perf_model import (IndexParams, UPMEM_PROFILE,
                                           lut_width_bytes)
        self._sync_relayout_thread()
        self._swap_on_next_batch = False
        nlist = index.nlist
        if heat is None:
            if (self.heat_estimator is not None
                    and self.heat_estimator.nlist == nlist):
                heat = self.heat_estimator.heat()
            elif len(self.heat) == nlist:
                heat = self.heat
            else:
                heat = np.full(nlist, self.cfg.nprobe / max(nlist, 1),
                               np.float64)
        sizes = np.asarray(index.sizes)
        latency = make_task_latency_model(
            IndexParams(n_total=int(sizes.sum()), nlist=nlist, q=1,
                        d=index.dim, k=self.cfg.k, p=self.cfg.nprobe,
                        m=index.codebook.m, cb=index.codebook.cb,
                        b_lut=lut_width_bytes(self.cfg.lut_dtype)),
            UPMEM_PROFILE)
        self._pending_heat = np.asarray(heat, np.float64)
        self._pending = self._materialize(self._pending_heat, index=index,
                                          latency=latency)

    def stage_index(self, index: IVFPQIndex,
                    heat: Optional[np.ndarray] = None) -> None:
        """prepare_index + install at the start of the next served batch
        (the same ``_swap_on_next_batch`` hook periodic re-layout uses) —
        the mutation coordinator's non-blocking install path: searches
        never wait on a generation build."""
        self.prepare_index(index, heat)
        self._swap_on_next_batch = True

    def install_index(self, index: IVFPQIndex,
                      heat: Optional[np.ndarray] = None) -> dict:
        """prepare_index + swap_layout in one synchronous call.  Callers
        must not have searches in flight (the non-blocking path is
        ``stage_index``)."""
        self.prepare_index(index, heat)
        return self.swap_layout()

    def _sync_relayout_thread(self) -> None:
        """Join an in-flight background rebuild (so the pending pair is
        consistent and cannot be re-written after this returns) and
        surface any error it hit."""
        t = self._relayout_thread
        if t is not None:
            t.join()
            self._relayout_thread = None
            if self._relayout_error is not None:
                err, self._relayout_error = self._relayout_error, None
                raise err

    def _begin_prepare_async(self) -> None:
        """Periodic-relayout trigger: snapshot the estimator's heat on
        the serving thread, then build the next placement on a
        background thread so it overlaps the triggering batch's own
        scan/merge work.  ``_join_pending_relayout`` (next batch start)
        joins and swaps."""
        self._sync_relayout_thread()       # never two rebuilds in flight
        if self._pending is not None and self._pending.index is not None:
            # a staged index generation is waiting to swap: a periodic
            # re-layout must not clobber it (the generation swap installs
            # fresh heat anyway; relayout resumes on the new generation)
            return
        heat = np.asarray(self.heat_estimator.heat(), np.float64)

        def build():
            try:
                pending = self._materialize(heat)
            except BaseException as e:           # surfaced at join
                self._relayout_error = e
                return
            self._pending_heat = heat
            self._pending = pending

        self._relayout_thread = threading.Thread(target=build, daemon=True)
        self._relayout_thread.start()

    def _join_pending_relayout(self) -> None:
        try:
            self._sync_relayout_thread()
        except BaseException:
            self._swap_on_next_batch = False
            raise
        if self._pending is not None:
            self.swap_layout()
        else:
            self._swap_on_next_batch = False

    def _layout_task_stats(self):
        """(tasks_per_query, mean_task_s) of the CURRENT layout: expected
        tasks/query = nprobe x heat-weighted mean split parts per probed
        cluster; mean_task_s is the Eq. 15 latency of a mean-size
        instance.  Recomputed after every re-layout."""
        parts = np.zeros(self.index.nlist, np.float64)
        mean_size = 0.0
        n0 = 0
        for inst in self.layout.instances:
            if inst.replica == 0:
                parts[inst.cluster] += 1.0
                mean_size += inst.size
                n0 += 1
        mean_size /= max(n0, 1)
        w = np.maximum(self.heat, 0.0)
        mean_parts = (float((parts * w).sum() / w.sum()) if w.sum() > 0
                      else float(parts.mean()))
        return (self.cfg.nprobe * max(mean_parts, 1.0),
                self.latency.task_latency(mean_size))

    def make_tasks_controller(self, headroom: float = 1.5, floor: int = 16,
                              max_shard_time_s: Optional[float] = None):
        """Build a perf-model-driven TasksPerShardController for this
        layout (see ``_layout_task_stats`` for the pricing)."""
        from repro.runtime.batching import TasksPerShardController
        tasks_per_query, mean_task_s = self._layout_task_stats()
        return TasksPerShardController(
            self.cfg.n_shards, tasks_per_query,
            headroom=headroom, floor=floor, cap=self.cfg.tasks_per_shard,
            mean_task_s=mean_task_s, max_shard_time_s=max_shard_time_s)

    def precompile_lc(self, max_rows: int) -> None:
        """Compile the cached path's miss-batch shapes (pow2 up to
        ``max_rows``) ahead of traffic — both the LUT build and the
        miss-residual RC, whose compiled shapes depend only on the padded
        miss count.  Same contract as LocalEngine.precompile_lc."""
        from repro.runtime.cache import precompile_lut_shapes
        precompile_lut_shapes(self.index.codebook, max_rows,
                              lut_dtype=self.cfg.lut_dtype)
        max_rows = next_pow2(max_rows)
        s = 1
        while s <= max_rows:
            miss_residuals(jnp.asarray(np.zeros((s, self.index.dim),
                                                np.float32)),
                           self.sindex.centroids,
                           jnp.asarray(np.zeros(s, np.int32)),
                           self.sindex.rotation)
            s *= 2

    def serving_info(self) -> dict:
        """Engine-side counters surfaced in ServingRuntime.metrics()."""
        info = {"batches": self.batches_served,
                "relayouts": self.relayouts,
                "generations": self.generations,
                "pending_relayout": self._pending is not None,
                "tasks_per_shard": self.cfg.tasks_per_shard}
        if self.tasks_controller is not None:
            info["tasks_controller"] = self.tasks_controller.summary()
        if self.heat_estimator is not None:
            info["heat_batches"] = self.heat_estimator.batches_observed
        if self.tiered_store is not None:
            info["tier"] = self.tiered_store.serving_info()
        return info

    # -- online ------------------------------------------------------------
    def schedule(self, probes: Optional[np.ndarray] = None, *,
                 tasks_per_shard: Optional[int] = None,
                 drain: bool = False) -> ShardSchedule:
        """Public scheduling API: build one batch's static task tables
        from the (Q, P) probed-cluster lists.

        Keyword-first form of the long-private ``_schedule`` (whose
        positional signature stays frozen for older call sites):
        ``probes`` is required, ``tasks_per_shard`` overrides the
        config's per-shard task cap for this call, and ``drain=True``
        schedules a carry-only flush round (capacity cap kept, balance
        filter off).  Deferred tasks land in ``self.carry`` exactly as
        with the private spelling."""
        if probes is None:
            raise TypeError("schedule() requires probes=(Q, P) "
                            "cluster ids from cluster_locate")
        return self._schedule(np.asarray(probes),
                              tasks_per_shard=tasks_per_shard, drain=drain)

    def _schedule(self, probes: np.ndarray,
                  tasks_per_shard: Optional[int] = None,
                  drain: bool = False) -> ShardSchedule:
        from repro.core.scheduler import schedule_naive
        if tasks_per_shard is None:
            tasks_per_shard = self.cfg.tasks_per_shard
        if self.cfg.naive_schedule:
            return schedule_naive(probes, self.layout, self.latency,
                                  self.sindex.slot_of_instance,
                                  tasks_per_shard=tasks_per_shard)
        # drain rounds keep the hard capacity cap but not the balance
        # filter — otherwise deferred work ping-pongs forever.
        sched = schedule_batch(probes, self.layout, self.latency,
                               self.sindex.slot_of_instance,
                               tasks_per_shard=tasks_per_shard,
                               carry_in=self.carry,
                               filter_ratio=self.cfg.filter_ratio,
                               enable_filter=(self.cfg.enable_filter
                                              and not drain))
        self.carry = list(sched.deferred)
        return sched

    def _lut_bank(self, queries_np: np.ndarray, probes: np.ndarray,
                  n_valid: int):
        """Assemble the per-(query, probed cluster) LUT bank through the
        cache: (Q*P, M, CB) f32, or a (Q*P,)-batched QuantizedLUT when
        the cache runs uint8 (~4x less replicated broadcast traffic).

        One LUT per (query, probed cluster) pair — split parts and
        replicas share it.  Pad rows (>= n_valid) are computed but never
        looked up or inserted, so they cannot distort hit accounting or
        occupy cache slots.  RC+LC run only over the miss rows (hit rows
        skip even the rotation matmul), padded to the next power of two
        so serving sees a bounded set of compiled shapes."""
        from repro.runtime.cache import (lut_fill_misses, lut_miss_scan,
                                         stack_lut_bank)
        cache = self.lut_cache
        nq, npr = probes.shape
        flat_probes = probes.reshape(-1)
        buckets = [cache.bucket_of(queries_np[qi]) for qi in range(n_valid)]
        luts, miss_rows = lut_miss_scan(cache, flat_probes, buckets, npr,
                                        nq * npr)
        if miss_rows:
            nmiss = len(miss_rows)
            mpad = next_pow2(nmiss)
            miss_q = np.zeros((mpad, queries_np.shape[1]), np.float32)
            miss_q[:nmiss] = queries_np[[t // npr for t in miss_rows]]
            crows = np.zeros(mpad, np.int32)
            crows[:nmiss] = flat_probes[miss_rows]
            # residuals stay on device, already pow2-padded —
            # lut_fill_misses feeds them to the LC build as-is
            res = miss_residuals(jnp.asarray(miss_q), self.sindex.centroids,
                                 jnp.asarray(crows), self.sindex.rotation)
            lut_fill_misses(cache, self.index.codebook, luts, miss_rows,
                            flat_probes, buckets, npr, res)
        return stack_lut_bank(luts)

    def _scan_cold(self, queries_np: np.ndarray, probes: np.ndarray,
                   bank, budget_s: Optional[float] = None, scope=None):
        """Scan this batch's snapshot-cold probes through the tier.

        (q, pos) pairs whose cluster is absent from the device tensors
        are gathered from the tier (one deduplicated mmap read per
        batch), scored by :func:`_cold_scan` with the same LUTs the
        device path would use — bank rows when the cache is on (row
        ``q * nprobe + pos``, shared with split parts), a fresh pow2-
        padded RC+LC otherwise — and returned as extra (T, k) candidate
        rows for the host merge.  Returns ``None`` when nothing is cold.

        Fail-operational: the fetch runs degraded — probes the tier
        cannot serve (cold-read IOError, quarantined clusters, or all of
        them when ``budget_s`` says the predicted cold cost would blow
        the deadline) come back with ``size == 0``, so the scan stays
        exact over what it scanned; the drop count lands in
        ``last_batch_info``.
        """
        mask = self._cold_mask
        if mask is None or not mask.any():
            return None
        cold_q, cold_pos = np.nonzero(mask[probes])
        if cold_q.size == 0:
            return None
        clusters = probes[cold_q, cold_pos]
        t = int(cold_q.size)
        tpad = next_pow2(t)
        tier = self.tiered_store
        resident_only = False
        if budget_s is not None:
            n_cold = int(np.unique(clusters).size)
            if n_cold and (budget_s <= 0
                           or tier.estimate_cold_seconds(n_cold)
                           > budget_s):
                resident_only = True
        codes, ids, sizes, dropped = tier.gather_degraded(
            clusters, resident_only=resident_only)
        n_dropped = int(dropped.sum())
        if n_dropped:
            self.last_batch_info = {
                "degraded": True,
                "dropped_probes":
                    self.last_batch_info.get("dropped_probes", 0)
                    + n_dropped}
        codes_p = np.zeros((tpad,) + codes.shape[1:], codes.dtype)
        ids_p = np.full((tpad,) + ids.shape[1:], -1, ids.dtype)
        sizes_p = np.zeros((tpad,), sizes.dtype)
        codes_p[:t], ids_p[:t], sizes_p[:t] = codes, ids, sizes
        if bank is not None:
            lidx = np.zeros(tpad, np.int64)
            lidx[:t] = cold_q.astype(np.int64) * self.cfg.nprobe + cold_pos
            li = jnp.asarray(lidx)
            lut = jax.tree.map(lambda a: a[li], bank)
        else:
            q_p = np.zeros((tpad, queries_np.shape[1]), np.float32)
            q_p[:t] = queries_np[cold_q]
            crows = np.zeros(tpad, np.int32)
            crows[:t] = clusters
            res = miss_residuals(jnp.asarray(q_p), self.sindex.centroids,
                                 jnp.asarray(crows), self.sindex.rotation)
            lut = build_lut_batch(self.index.codebook, res)
            if self.cfg.lut_dtype == "uint8":
                lut = quantize_lut(lut)
        if scope is not None:
            from repro.core.filter import NO_TAG
            mt, mg, _, _, tenants_np, terms_np = scope
            t_ten = np.full(tpad, -1, np.int32)
            t_ten[:t] = tenants_np[cold_q]
            t_terms = np.full((tpad, terms_np.shape[1]), NO_TAG, np.uint32)
            t_terms[:t] = terms_np[cold_q]
            bd, bi = _cold_scan_scoped(
                lut, jnp.asarray(codes_p), jnp.asarray(ids_p),
                jnp.asarray(sizes_p), mt, mg, jnp.asarray(t_ten),
                jnp.asarray(t_terms), k=self.cfg.k,
                strategy=self.cfg.strategy)
        else:
            bd, bi = _cold_scan(lut, jnp.asarray(codes_p),
                                jnp.asarray(ids_p), jnp.asarray(sizes_p),
                                k=self.cfg.k, strategy=self.cfg.strategy)
        qarr = np.full(tpad, -1, np.int64)
        qarr[:t] = cold_q
        return np.asarray(bd), np.asarray(bi), qarr

    def _probe_posmap(self, probes: np.ndarray) -> np.ndarray:
        """(nq, nlist) position of each cluster in its query's probe list
        (-1 absent).  Built once per batch — every drain round reuses it."""
        nq, npr = probes.shape
        posmap = np.full((max(nq, 1), self.index.nlist), -1, np.int64)
        if nq:
            posmap[np.arange(nq)[:, None], probes] = np.arange(npr)[None, :]
        return posmap

    def _lut_idx(self, sched: ShardSchedule, posmap: np.ndarray,
                 nprobe: int) -> np.ndarray:
        """Map the schedule's (S, T) tasks to LUT-bank rows: task (q, slot)
        -> q * nprobe + position of slot's cluster in probes[q].  -1 marks
        tasks with no bank row (invalid, or a flush=False carry-over whose
        cluster this batch didn't probe) — the step masks them out."""
        qi = sched.query_idx
        si = sched.slot_idx
        s_rows = np.arange(qi.shape[0])[:, None]
        cl = self._cluster_of_host[s_rows, np.clip(si, 0, None)]
        pos = posmap[np.clip(qi, 0, None), np.clip(cl, 0, None)]
        lidx = qi.astype(np.int64) * nprobe + pos
        return np.where((qi >= 0) & (pos >= 0), lidx, -1).astype(np.int32)

    def search(self, queries: jax.Array, flush: bool = True,
               n_valid: Optional[int] = None,
               budget_s: Optional[float] = None,
               tenants: Optional[np.ndarray] = None,
               terms: Optional[np.ndarray] = None):
        """Batched search.  With flush=True, deferred tasks are drained in
        follow-up rounds so results are complete (tests); a serving loop
        would instead leave them for the next batch (paper's filter).

        ``n_valid``: rows >= n_valid are serving-batch padding — excluded
        from heat observation and LUT-cache population (their results are
        discarded by the caller).

        ``budget_s``: remaining deadline budget.  Only the tiered cold
        scan consults it — when the predicted cold-read cost would blow
        the budget the cold probes are dropped and the batch is reported
        degraded via ``last_batch_info`` (device-resident scans are
        already paced by the task scheduler and never shed).

        ``tenants`` (Q,) i32 / ``terms`` (Q, W) u32 (PR 10): per-query
        tenant scope (-1 = unscoped) and predicate tags (NO_TAG pad).
        Scoped batches run the scoped shard steps (the tenant/predicate
        mask before TS); CL is additionally restricted to the tenants'
        member clusters.  Requires a ``meta`` table; not supported on the
        mesh path (the service tier always builds vmap engines)."""
        from repro.core.search import cluster_locate, cluster_locate_masked
        self.last_batch_info = {"degraded": False, "dropped_probes": 0}
        scope = None
        if tenants is not None or terms is not None:
            if self.meta is None:
                raise ValueError(
                    "tenant/filtered search needs an engine built with "
                    "per-vector metadata (meta=VectorMeta); got meta=None")
            if self.mesh is not None:
                raise ValueError("scoped search is not supported on the "
                                 "mesh (shard_map) path")
            from repro.core.filter import NO_TAG
            nq_s = queries.shape[0]
            tenants_np = (np.full(nq_s, -1, np.int32) if tenants is None
                          else np.asarray(tenants, np.int32))
            terms_np = (np.full((nq_s, self.meta.tag_fields), NO_TAG,
                                np.uint32) if terms is None
                        else np.asarray(terms, np.uint32))
            mt, mg = self.meta.device_tables()
            scope = (mt, mg, jnp.asarray(tenants_np),
                     jnp.asarray(terms_np), tenants_np, terms_np)
        # a pending periodic re-layout swaps in between batches: the
        # rebuild ran on a background thread concurrently with the
        # triggering batch's own scan/merge, and this batch starts on the
        # new placement after a join (usually free) + O(1) swap
        if self._swap_on_next_batch:
            self._join_pending_relayout()
        nq = queries.shape[0]
        nv = nq if n_valid is None else min(n_valid, nq)
        if scope is not None and (scope[4] >= 0).any():
            # tenant namespaces: probe only the tenants' member clusters
            allowed = self.meta.allowed_for(scope[4],
                                            self.sindex.centroids.shape[0])
            probes, _ = cluster_locate_masked(
                queries.astype(jnp.float32), self.sindex.centroids,
                self.cfg.nprobe, jnp.asarray(allowed))
        else:
            probes, _ = cluster_locate(queries.astype(jnp.float32),
                                       self.sindex.centroids,
                                       self.cfg.nprobe)
        probes = np.asarray(probes)
        if nv > 0:      # all-padding warmup batches don't count as traffic
            if self.heat_estimator is not None:
                self.heat_estimator.observe(probes[:nv])
            if self.tiered_store is not None:
                # tier heat drives promote/demote; residency changes only
                # take effect on device at the next re-layout (the cold
                # mask is a placement snapshot), but the mmap fetch path
                # serves the in-between batches exactly
                self.tiered_store.observe(probes[:nv])
            self.batches_served += 1
            if (self.cfg.relayout_every > 0
                    and self.heat_estimator is not None
                    and self.batches_served % self.cfg.relayout_every == 0):
                # double-buffer: build the next placement on a background
                # thread while this batch is served on the current one;
                # the swap happens at the start of the next batch
                self._begin_prepare_async()
                self._swap_on_next_batch = True
        tps = (self.tasks_controller.tasks_for(nq)
               if self.tasks_controller is not None
               else self.cfg.tasks_per_shard)
        bank = (self._lut_bank(np.asarray(queries, np.float32), probes, nv)
                if self.lut_cache is not None else None)
        posmap = self._probe_posmap(probes) if bank is not None else None
        all_d, all_i, all_q = [], [], []
        rounds = 0
        pending = probes
        while True:
            sched = self._schedule(pending, tps, drain=rounds > 0)
            if rounds == 0 and nv > 0 and self.tasks_controller is not None:
                # nv == 0 is warmup traffic: its degenerate all-equal
                # queries must not teach the controller fake overflows
                full = bool((sched.n_tasks >= tps).any())
                self.tasks_controller.observe(
                    nq, len(sched.deferred) if full else 0)
            qidx = jnp.asarray(sched.query_idx)
            sidx = jnp.asarray(sched.slot_idx)
            if scope is not None and bank is not None:
                lidx = jnp.asarray(self._lut_idx(sched, posmap,
                                                 self.cfg.nprobe))
                bd, bi = run_shards_vmap_lut_scoped(
                    self.sindex, qidx, sidx, lidx, bank, scope[0],
                    scope[1], scope[2], scope[3], k=self.cfg.k,
                    strategy=self.cfg.strategy)
            elif scope is not None:
                bd, bi = run_shards_vmap_scoped(
                    self.sindex, qidx, sidx, queries, scope[0], scope[1],
                    scope[2], scope[3], k=self.cfg.k,
                    strategy=self.cfg.strategy,
                    quantize=self.cfg.lut_dtype == "uint8")
            elif bank is not None:
                lidx = jnp.asarray(self._lut_idx(sched, posmap,
                                                 self.cfg.nprobe))
                if self._step_lut is not None:
                    bd, bi = self._step_lut(self.sindex.codes,
                                            self.sindex.ids,
                                            self.sindex.sizes, qidx, sidx,
                                            lidx, bank)
                else:
                    bd, bi = run_shards_vmap_lut(
                        self.sindex, qidx, sidx, lidx, bank, k=self.cfg.k,
                        strategy=self.cfg.strategy,
                        use_kernels=self.cfg.use_kernels)
            elif self._step is not None:
                bd, bi = self._step(self.sindex.codes, self.sindex.ids,
                                    self.sindex.sizes, self.sindex.cluster_of,
                                    qidx, sidx, queries,
                                    self.sindex.centroids)
            else:
                bd, bi = run_shards_vmap(
                    self.sindex, qidx, sidx, queries, k=self.cfg.k,
                    strategy=self.cfg.strategy,
                    use_kernels=self.cfg.use_kernels,
                    quantize=self.cfg.lut_dtype == "uint8")
            all_d.append(np.asarray(bd))
            all_i.append(np.asarray(bi))
            all_q.append(sched.query_idx)
            rounds += 1
            if not (flush and self.carry):
                break
            pending = np.zeros((0, 0), np.int64)   # only carry-in tasks
        if self.tiered_store is not None:
            cold = self._scan_cold(np.asarray(queries, np.float32), probes,
                                   bank, budget_s=budget_s, scope=scope)
            if cold is not None:
                cd, ci, cq = cold
                all_d.append(cd)
                all_i.append(ci)
                all_q.append(cq)
        d = np.concatenate([a.reshape(-1, self.cfg.k) for a in all_d])
        i = np.concatenate([a.reshape(-1, self.cfg.k) for a in all_i])
        q = np.concatenate([a.reshape(-1) for a in all_q])
        out_d, out_i = merge_host(q, d, i, nq, self.cfg.k)
        return out_d, out_i, {"rounds": rounds}
