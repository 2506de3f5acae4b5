#!/usr/bin/env python3
"""One-chip smoke run of the DRIM-ANN serving stack on a TPU.

Drives the served path once, through the entry points a user calls, at
the big-ann-benchmarks BIGANN-10M shape (the paper's SIFT setting,
``repro/configs/drim_ann.py``, cut to 10M vectors):

  data     10M u8 vectors of dimension 128 + 64 queries from a seed
           (``make_clustered_corpus``); exact top-10 of the queries by
           blocked brute force on the chip (``exact_search``)
  kernels  ``lut_build``, ``lut_build_q`` and ``pq_scan_topk`` (f32 and
           u8 LUTs) plus ``pq_scan_dc``, compiled by Mosaic
           (``interpret=False``), each checked against ``kernels/ref.py``
  build    ``IndexSpec(nlist=4096, m=16, cb=256, train_sample=262144)``
  serve    per LUT dtype (f32, uint8): ``AnnService.build`` (local engine,
           2 replicas, cache_aware router, nprobe 32, k 10) -> ``warmup``
           -> a bulk ``search`` -> 64 ``submit_async`` requests on the
           wall-clock executors -> ``stats`` -> ``shutdown``

Checks: served ids equal a direct ``search_ivfpq`` on the same index,
recall@10 against the exact reference clears ``RECALL_FLOOR``, and no
request failed, was retried on the other replica, degraded or shed.

    python3 chip_smoke.py

Every line but the last is labelled as this one chip run; none is a
benchmark result.  The last line is ``{"ok": true, "device": {...}}``.
The script exits non-zero and prints no such line on any failure, or
when JAX finds no TPU.  It runs in one process and starts no child.
The compile cache lives at ``JAX_COMPILATION_CACHE_DIR`` when that is
set, else at ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time

import numpy as np

SEED = 0
N_POINTS = 10_000_000
DIM = 128
NLIST = 4096
TRAIN_SAMPLE = 262144
N_QUERIES = 64
NPROBE = 32
K = 10
KERNEL_TASKS, KERNEL_ROWS = 256, 4096      # kernel phase: tasks x rows each
INTERPRET = False                          # kernels compiled by Mosaic
# Sanity floor for recall@10 of the served results against exact search,
# set from the first complete chip run on a TPU v5e (0.1078 with f32
# LUTs, 0.1063 with u8).  Recall is low by construction: the generator's
# components are isotropic Gaussians in 128-d, where a query's distances
# to its blob differ little, so both the probed cells and the PQ error
# cut into it (the probe coverage line separates the two).
RECALL_FLOOR = 0.08

_compile_s = [0.0]


def say(key: str, value) -> None:
    print(f"chip_smoke (one chip run, not a benchmark) {key}: {value}",
          flush=True)


def check(ok: bool, what: str) -> None:
    """A failed smoke check (raises; unlike ``assert`` it survives -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _count_compiles() -> None:
    """Sum XLA backend compile time (a persistent-cache hit counts only
    its retrieval) — the 'compile seconds' line."""
    import jax

    def listener(event: str, duration_s: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _compile_s[0] += duration_s
    jax.monitoring.register_event_duration_secs_listener(listener)


def _ids_match_up_to_ties(got_d, got_i, want_d, want_i, rtol, atol):
    """Per row, ids in one top-k but not the other must sit on the k-th
    distance (a boundary tie); returns the count of offending rows."""
    bad = 0
    for gd, gi, wd, wi in zip(got_d, got_i, want_d, want_i):
        kth = wd[-1]
        tol = atol + rtol * abs(kth)
        extra = set(gi.tolist()) ^ set(wi.tolist())
        near = {int(i) for d, i in zip(gd, gi) if abs(d - kth) <= tol}
        near |= {int(i) for d, i in zip(wd, wi) if abs(d - kth) <= tol}
        bad += bool(extra - near)
    return bad


def kernel_phase():
    """The Pallas kernels at M=16, CB=256, ~4096 rows per task, against
    the pure-jnp oracles of kernels/ref.py."""
    import jax.numpy as jnp

    from repro.core.adc import dequantize_lut, quantize_lut
    from repro.kernels import ops, ref

    m, cb, dsub = 16, 256, 8
    t, c, interpret = KERNEL_TASKS, KERNEL_ROWS, INTERPRET
    rng = np.random.default_rng(SEED + 1)
    res = jnp.asarray(rng.normal(size=(t, m * dsub)), jnp.float32)
    books = jnp.asarray(rng.normal(size=(m, cb, dsub)), jnp.float32)
    sqn = jnp.sum(books * books, axis=-1)
    codes = jnp.asarray(rng.integers(0, cb, size=(t, c, m)), jnp.uint8)
    ids = jnp.asarray(rng.integers(0, 1 << 30, size=(t, c)), jnp.int32)
    sizes = jnp.asarray(rng.integers(c // 2, c + 1, size=(t,)), jnp.int32)
    tol = dict(rtol=1e-4, atol=1e-3)

    lut = ops.lut_build(res, books, sqn, interpret=interpret)
    want = ref.lut_build_ref(res.reshape(t, m, dsub), books, sqn)
    np.testing.assert_allclose(np.asarray(lut), np.asarray(want), **tol)
    err = float(jnp.max(jnp.abs(lut - want)))
    say("kernel lut_build", f"matches ref (max abs err {err:.3g})")

    qlut = ops.lut_build_q(res, books, sqn, interpret=interpret)
    qref = quantize_lut(want)
    np.testing.assert_allclose(np.asarray(qlut.scale),
                               np.asarray(qref.scale), **tol)
    np.testing.assert_allclose(np.asarray(qlut.bias),
                               np.asarray(qref.bias), **tol)
    steps = jnp.abs(dequantize_lut(qlut) - want) / qlut.scale[..., None]
    worst = float(jnp.max(steps))
    check(worst <= 1.0, f"lut_build_q off by {worst} quantization steps")
    say("kernel lut_build_q", f"matches ref (worst {worst:.3f} steps)")

    for name, table, dense in (("f32", lut, lut),
                               ("u8", qlut, dequantize_lut(qlut))):
        gd, gi = ops.pq_scan_topk(table, codes, ids, sizes, K,
                                  strategy="onehot", interpret=interpret)
        rd, ri = ref.pq_scan_topk_ref(dense, codes, ids, sizes, K)
        gd, gi, rd, ri = map(np.asarray, (gd, gi, rd, ri))
        np.testing.assert_allclose(gd, rd, **tol)
        bad = _ids_match_up_to_ties(gd, gi, rd, ri, **tol)
        check(bad == 0, f"pq_scan_topk {name}: {bad} rows' ids differ")
        exact = int((gi == ri).all(axis=1).sum())
        say(f"kernel pq_scan_topk {name}",
            f"matches ref ({exact}/{t} rows id-for-id, rest boundary ties)")

    d = np.asarray(ops.pq_scan_dc(lut, codes, sizes, strategy="onehot",
                                  interpret=interpret))
    dref = np.asarray(ref.pq_scan_dc_ref(lut, codes))
    valid = np.arange(c)[None] < np.asarray(sizes)[:, None]
    np.testing.assert_allclose(d[valid], dref[valid], **tol)
    check(np.isinf(d[~valid]).all(), "pq_scan_dc padding rows not +inf")
    say("kernel pq_scan_dc f32", "matches ref")


def probe_coverage(handle, queries: np.ndarray, gt: np.ndarray) -> float:
    """Share of the exact neighbors stored in the clusters CL probes."""
    import jax.numpy as jnp

    from repro.core import cluster_locate

    ivf = handle.ivf
    ids = np.asarray(ivf.ids)
    rank = np.empty(len(ids), np.int64)
    rank[ids] = np.arange(len(ids))
    cluster_of = np.searchsorted(np.asarray(ivf.offsets), rank[gt],
                                 side="right") - 1                # (Q, K)
    probes = np.asarray(cluster_locate(jnp.asarray(queries), ivf.centroids,
                                       NPROBE)[0])
    return float((cluster_of[:, :, None] == probes[:, None, :]).any(-1)
                 .mean())


def serve_phase(handle, queries: np.ndarray, gt: np.ndarray,
                lut_dtype: str, index_spec) -> None:
    """AnnService end to end on one LUT dtype; raises on any check."""
    import jax.numpy as jnp

    from repro.core import SearchParams, recall_at_k, search_ivfpq
    from repro.service import AnnService, ServiceSpec

    spec = ServiceSpec(index=index_spec, engine="local", replicas=2,
                       router="cache_aware", nprobe=NPROBE, k=K,
                       lut_dtype=lut_dtype)
    svc = AnnService.build(spec, index=handle)
    try:
        c0 = _compile_s[0]
        t0 = time.perf_counter()
        svc.warmup()
        _, ids = svc.search(queries)                       # bulk batch
        say(f"{lut_dtype} warmup+first bulk search s",
            f"{time.perf_counter() - t0:.3f} "
            f"(compile {_compile_s[0] - c0:.3f})")
        t0 = time.perf_counter()
        svc.search(queries)               # returns host arrays: synced
        say(f"{lut_dtype} bulk search of {len(queries)} queries s "
            f"(compiled)", f"{time.perf_counter() - t0:.4f}")
        _, direct = search_ivfpq(
            handle.search_view, handle.clusters,
            jnp.asarray(queries, jnp.float32),
            SearchParams(nprobe=NPROBE, k=K, lut_dtype=lut_dtype))
        direct = np.asarray(direct)
        check(np.array_equal(np.asarray(ids), direct),
              f"{lut_dtype}: served bulk ids differ from search_ivfpq")
        recall = float(recall_at_k(jnp.asarray(ids), jnp.asarray(gt)))
        say(f"{lut_dtype} served == direct search_ivfpq",
            f"{len(queries)}/{len(queries)} queries id-for-id")
        say(f"{lut_dtype} recall@10 vs exact", f"{recall:.4f} "
            f"(floor {RECALL_FLOOR})")
        check(recall >= RECALL_FLOOR,
              f"{lut_dtype} recall@10 {recall} < {RECALL_FLOOR}")

        futs = [svc.submit_async(q) for q in queries]
        failed, same = 0, 0
        for row, fut in enumerate(futs):
            try:
                _, fi = fut.result(timeout=300.0)
            except Exception as err:      # noqa: BLE001 — counted, fails
                failed += 1
                print(f"request {row} failed: {err!r}", file=sys.stderr)
                continue
            same += set(np.asarray(fi).tolist()) == set(direct[row].tolist())
            check(not fut.timing()["retried"], f"request {row} retried")
        stats = svc.stats()
    finally:
        svc.shutdown()
    agg = stats["aggregate"]
    health_failures = sum(stats["health"]["failures"])
    say(f"{lut_dtype} async requests",
        f"{len(futs)} submitted, {failed} failed, {same} with the direct "
        f"neighbor set, p50 {agg['p50_ms']:.3f} ms, p99 "
        f"{agg['p99_ms']:.3f} ms (64 back-to-back submits, 2 replicas)")
    say(f"{lut_dtype} stats", f"requests={agg['requests']} "
        f"batches={agg['batches']} retries={agg['retries']} "
        f"shed={agg['shed']} degraded={agg['degraded']} "
        f"wedged={agg['wedged_workers']} batch_failures={health_failures}")
    check(failed == 0 and same == len(futs),
          f"{lut_dtype}: {failed} failed, {same} matched direct search")
    check(agg["requests"] == len(futs), f"{lut_dtype}: {agg}")
    check((agg["retries"], agg["shed"], agg["degraded"],
           agg["wedged_workers"], health_failures) == (0, 0, 0, 0, 0),
          f"{lut_dtype}: {agg}, batch failures {health_failures}")


def run() -> None:
    """Every phase, in this process; raises on any failed check."""
    import jax
    import jax.numpy as jnp

    from repro.core import exact_search
    from repro.data import make_clustered_corpus
    from repro.service import IndexSpec

    _count_compiles()
    t0 = time.perf_counter()
    ds = make_clustered_corpus(SEED, n=N_POINTS, d=DIM,
                               n_queries=N_QUERIES, k_gt=0)
    points = ds.points.block_until_ready()
    queries = np.asarray(ds.queries, np.float32)
    say("data", f"{N_POINTS} x {DIM} u8 points + {N_QUERIES} queries in "
        f"{time.perf_counter() - t0:.3f} s")

    t0 = time.perf_counter()
    _, gt = exact_search(points, jnp.asarray(queries), k=K, chunk=16)
    gt = np.asarray(gt)
    say("exact reference s", f"{time.perf_counter() - t0:.3f}")

    t0, c0 = time.perf_counter(), _compile_s[0]
    kernel_phase()
    say("kernel phase s", f"{time.perf_counter() - t0:.3f} "
        f"(compile {_compile_s[0] - c0:.3f})")

    index_spec = IndexSpec(nlist=NLIST, m=16, cb=256,
                           train_sample=TRAIN_SAMPLE, seed=SEED)
    t0, c0 = time.perf_counter(), _compile_s[0]
    handle = index_spec.build(points)
    clusters = handle.clusters
    jax.block_until_ready(clusters)
    ivf = handle.ivf
    say("build s", f"{time.perf_counter() - t0:.3f} "
        f"(compile {_compile_s[0] - c0:.3f})")
    del ds, points
    gc.collect()
    csr = sum(int(a.nbytes) for a in (ivf.codes, ivf.ids, ivf.offsets,
                                      ivf.centroids, *ivf.codebook))
    padded = sum(int(a.nbytes) for a in clusters)
    say("index", f"{int(handle.sizes.sum())} vectors, nlist "
        f"{handle.nlist}, cmax {clusters.cmax}, CSR {csr} B, padded "
        f"clusters {padded} B")

    say("probe coverage", f"{probe_coverage(handle, queries, gt):.4f} of "
        f"the exact top-{K} lie in the {NPROBE} probed clusters (the "
        f"ceiling on IVF recall before PQ)")
    for lut_dtype in ("f32", "uint8"):
        serve_phase(handle, queries, gt, lut_dtype, index_spec)


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.util import enable_compile_cache

    say("device", f"{dev.device_kind} x{len(jax.devices())}")
    say("compile cache", enable_compile_cache())
    t0 = time.perf_counter()
    run()
    stats = dev.memory_stats() or {}
    say("peak_bytes_in_use", stats.get("peak_bytes_in_use", "not reported"))
    say("compile s", f"{_compile_s[0]:.3f}")
    say("total s", f"{time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
