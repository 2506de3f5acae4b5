"""Self-contained service-layer smoke: ``python -m repro.service --selftest``.

Builds a tiny corpus + index, stands up AnnService twice (1 replica
local, then 2 replicas behind the cache-aware router), streams a skewed
query trace, and asserts the service invariants end to end:

  * 1-replica local search == direct ``search_ivfpq`` (ids equal,
    distances allclose);
  * streamed per-request results match the direct batch per query —
    under the virtual-clock simulator or the wall-clock executor path
    (``--clock virtual|wall``; CI runs both, with a hard timeout so an
    executor deadlock fails fast);
  * every request was routed (pick counts sum to the request count);
  * live mutation: a ``mutable=True`` fleet upserts 64 vectors (>= 0.9
    self-retrieval), deletes half (tombstones never in results, before
    or after a forced maintenance generation swap).

``--spec deploy.json`` (or ``.yaml``) boots the same smoke fleet from a
durable deploy file instead of the built-in specs —
``launch/serve.py --ann --spec ...`` reads the identical artifact, so
the two entrypoints can never drift.

``--autotune`` closes the loop the other way: instead of reading a
spec, it *derives* one — ``core.autotune`` searches the configuration
space against the perf model, validates survivors on a calibration
stream, and prints the winning spec's report (``--save-spec out.json``
persists it as the deploy artifact ``--spec`` can then boot).

Exit code 0 on success — wired into CI as a cheap post-install gate.
"""

from __future__ import annotations

import argparse
import sys

import jax
import numpy as np


def _corpus_and_index():
    from repro.core import build_ivfpq
    from repro.data import make_clustered_corpus

    ds = make_clustered_corpus(seed=0, n=2000, d=16, n_queries=16,
                               n_components=8)
    index = build_ivfpq(jax.random.PRNGKey(0), ds.points, nlist=16, m=8,
                        cb=32, kmeans_iters=4, pq_iters=4)
    return ds, index


def selftest(clock: str = "virtual") -> int:
    import jax.numpy as jnp

    from repro.core import (SearchParams, pad_clusters, search_ivfpq)
    from repro.service import AnnService, ServiceSpec

    ds, index = _corpus_and_index()
    queries = np.asarray(ds.queries, np.float32)

    # -- 1 replica, no cache: facade == direct pipeline -------------------
    spec1 = ServiceSpec(engine="local", replicas=1, nprobe=4, k=5,
                        buckets=(1, 2, 4), max_wait_s=1e-3)
    svc1 = AnnService.build(spec1, index=index)
    d_s, i_s = svc1.search(queries)
    d_d, i_d = search_ivfpq(index, pad_clusters(index),
                            jnp.asarray(queries), SearchParams(nprobe=4, k=5))
    np.testing.assert_array_equal(i_s, np.asarray(i_d))
    np.testing.assert_allclose(d_s, np.asarray(d_d), rtol=1e-5)
    svc1.shutdown()
    print("[selftest] 1-replica search == search_ivfpq: OK")

    # -- 2 replicas, cache-aware router, skewed stream --------------------
    spec2 = ServiceSpec(engine="local", replicas=2, router="cache_aware",
                        nprobe=4, k=5, cache_capacity=512,
                        buckets=(1, 2, 4), max_wait_s=1e-3)
    svc2 = AnnService.build(spec2, index=index)
    svc2.warmup()
    direct_d, direct_i = svc2.search(queries)
    pool = np.arange(24) % 4                    # hot 4-query pool
    stream = [(i * 5e-4, queries[pool[i]]) for i in range(24)]
    reqs = svc2.stream(stream, clock=clock)
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(r.ids, direct_i[pool[i]])
    st = svc2.stats()
    assert sum(st["router"]["picks"]) == len(reqs), st["router"]
    assert st["aggregate"]["requests"] == len(reqs)
    print(f"[selftest] streamed {len(reqs)} requests over 2 replicas "
          f"(clock={clock} router={st['router']['policy']} "
          f"picks={st['router']['picks']} "
          f"lut_hit_rate={st['aggregate'].get('lut_hit_rate', 0.0):.2f}): OK")
    svc2.shutdown()

    # -- quantized-LUT fast path: uint8 spec, byte-budgeted cache ---------
    spec3 = ServiceSpec(engine="local", replicas=1, nprobe=4, k=5,
                        lut_dtype="uint8", cache_capacity_bytes=1 << 20,
                        buckets=(1, 2, 4), max_wait_s=1e-3)
    svc3 = AnnService.build(spec3, index=index)
    svc3.warmup()
    d_q, i_q = svc3.search(queries)
    # quantized distances are compared via neighbor overlap, not values
    # (quantization error is bounded but nonzero)
    overlap = np.mean([len(set(i_q[r]) & set(np.asarray(i_d)[r])) / 5.0
                       for r in range(len(queries))])
    assert overlap >= 0.8, f"u8-vs-f32 neighbor overlap {overlap:.2f}"
    reqs3 = svc3.stream(stream, clock=clock)
    assert all(r.ids is not None and len(r.ids) == 5 for r in reqs3)
    st3 = svc3.stats()
    cache_bytes = st3["replicas"][0]["lut_cache"]["bytes"]
    assert 0 < cache_bytes <= (1 << 20), cache_bytes
    print(f"[selftest] uint8 spec: overlap={overlap:.2f} "
          f"hit_rate={st3['aggregate'].get('lut_hit_rate', 0.0):.2f} "
          f"cache_bytes={cache_bytes}: OK")
    svc3.shutdown()

    # -- live-index mutation: upsert / delete / maintenance ---------------
    spec4 = ServiceSpec(engine="local", replicas=2, nprobe=4, k=5,
                        mutable=True, buckets=(1, 2, 4), max_wait_s=1e-3)
    svc4 = AnnService.build(spec4, points=np.asarray(ds.points))
    new_ids = np.arange(2000, 2064)
    new_vecs = np.asarray(ds.points[:64], np.float32) + 1e-2
    svc4.upsert(new_ids, new_vecs)
    _, i_m = svc4.search(new_vecs)
    overlap = float(np.mean([new_ids[r] in np.asarray(i_m)[r]
                             for r in range(len(new_ids))]))
    assert overlap >= 0.9, f"upsert self-retrieval overlap {overlap:.2f}"
    gone = new_ids[:32]
    svc4.delete(gone)
    _, i_d2 = svc4.search(new_vecs)
    assert not np.isin(np.asarray(i_d2), gone).any(), \
        "deleted ids surfaced in results"
    kept = new_ids[32:]
    kept_hits = float(np.mean([kept[r] in np.asarray(i_d2)[32 + r]
                               for r in range(len(kept))]))
    assert kept_hits >= 0.9, f"survivor retrieval {kept_hits:.2f}"
    maint = svc4.run_maintenance(force=True)
    assert maint["ran"], maint
    _, i_g = svc4.search(new_vecs)
    assert not np.isin(np.asarray(i_g), gone).any(), \
        "deleted ids resurfaced after maintenance"
    mstats = svc4.stats()["mutation"]
    assert mstats["generation"] >= 1 and mstats["deletes"] == len(gone)
    print(f"[selftest] mutation: upserted {len(new_ids)} "
          f"(overlap={overlap:.2f}), deleted {len(gone)}, "
          f"maintenance gen={mstats['generation']} "
          f"nlist={mstats['nlist']}: OK")
    svc4.shutdown()

    # -- tiered storage: beyond-memory serving, results unchanged ---------
    import tempfile

    spec5_kw = dict(engine="local", replicas=1, nprobe=4, k=5,
                    buckets=(1, 2, 4), max_wait_s=1e-3)
    ref5 = AnnService.build(ServiceSpec(**spec5_kw), index=index)
    d_r5, i_r5 = ref5.search(queries)
    ref5.shutdown()
    tdir = tempfile.mkdtemp(prefix="selftest_tier_")
    spec5 = ServiceSpec(storage="tiered", storage_dir=tdir,
                        storage_budget_bytes=1, **spec5_kw)  # fully cold
    svc5 = AnnService.build(spec5, points=np.asarray(ds.points), index=index)
    tier = svc5.index.tiered_store
    budget = max(tier.total_bytes // 4, tier.bytes_per_cluster)
    svc5.shutdown()
    spec5 = ServiceSpec(storage="tiered", storage_dir=tdir + "q",
                        storage_budget_bytes=budget, **spec5_kw)
    svc5 = AnnService.build(spec5, index=index)
    tier = svc5.index.tiered_store
    assert tier.total_bytes >= 4 * tier.budget_bytes >= 4, \
        (tier.total_bytes, tier.budget_bytes)
    d_t5, i_t5 = svc5.search(queries)
    np.testing.assert_array_equal(i_t5, i_r5)
    np.testing.assert_allclose(d_t5, d_r5, rtol=1e-5, atol=1e-4)
    for _ in range(4):                       # churn residency; stay exact
        svc5.search(queries)
    d_t6, i_t6 = svc5.search(queries)
    np.testing.assert_array_equal(i_t6, i_r5)
    assert tier.resident_bytes <= tier.budget_bytes
    tinfo = svc5.stats()["tier"]
    assert tinfo["cold_fetches"] > 0, tinfo
    print(f"[selftest] tiered: {tinfo['total_bytes']}B index under "
          f"{tinfo['budget_bytes']}B budget "
          f"(resident={tinfo['resident_clusters']}/{index.nlist} "
          f"hot_rate={tinfo['hot_rate']:.2f}) "
          f"results == all-resident: OK")
    svc5.shutdown()
    print(f"[selftest] repro.service OK (clock={clock})")
    return 0


def selftest_tenants() -> int:
    """Multi-tenant serving smoke: two tenants with disjoint corpora on
    one shared index; asserts hard isolation (a tenant's results never
    contain the other's rows; scoped results bit-identical to a
    dedicated single-tenant index), predicate-filter exactness against
    the host-side reference mask, quota enforcement (the rate-limited
    tenant is shed, the unlimited one never is), and WFQ fairness
    accounting in ``stats()``."""
    import jax.numpy as jnp

    from repro.core import SearchParams, search_ivfpq
    from repro.core.filter import tenant_subindex
    from repro.core.ivf import pad_clusters
    from repro.data import make_clustered_corpus
    from repro.service import AnnService, ServiceSpec, TenantThrottled

    ds, index = _corpus_and_index()
    queries = np.asarray(ds.queries, np.float32)
    n = len(np.asarray(ds.points))
    tenants = np.zeros(n, np.int32)
    tenants[n // 2:] = 1                        # disjoint halves
    tags = (np.arange(n, dtype=np.uint32) % 3)[:, None]

    spec = ServiceSpec(engine="local", replicas=2, nprobe=4, k=5,
                       buckets=(1, 2, 4), max_wait_s=1e-3,
                       tenants=(("anna", 0, 4.0, 0.0, 1),
                                ("zoe", 1, 1.0, 25.0, 2)),
                       qos_wfq=True)
    svc = AnnService.build(spec, index=index,
                           points=np.asarray(ds.points),
                           tenants=tenants, tags=tags)
    svc.warmup()
    meta = svc.index.meta

    # isolation: scoped == dedicated single-tenant index, bit-identical
    for name, tid in (("anna", 0), ("zoe", 1)):
        d_s, i_s = svc.search(queries, tenant=name)
        ids = np.asarray(i_s)
        live = ids[ids >= 0]
        assert np.all(tenants[live] == tid), f"tenant {name} leak"
        sub, members = tenant_subindex(index, meta, tid)
        p = min(4, len(members))
        d_ref, i_ref = search_ivfpq(sub, pad_clusters(sub),
                                    jnp.asarray(queries),
                                    SearchParams(nprobe=p, k=5))
        np.testing.assert_array_equal(ids, np.asarray(i_ref))
        d_s = np.where(np.isfinite(d_s), d_s, 0.0)
        d_ref = np.where(np.isfinite(np.asarray(d_ref)),
                         np.asarray(d_ref), 0.0)
        np.testing.assert_allclose(d_s, d_ref, rtol=1e-5, atol=1e-5)
    print("[tenants] isolation: scoped == dedicated subindex "
          "(bit-identical ids, both tenants): OK")

    # predicate filtering: every returned row carries a requested term
    d_f, i_f = svc.search(queries, tenant="anna", terms=(1,))
    ids = np.asarray(i_f)
    live = ids[ids >= 0]
    assert np.all(meta.match_host(live, tenant=0, terms=(1,))), \
        "filtered result row fails the predicate"
    print("[tenants] predicate filter (tag==1 under tenant anna): OK")

    # quotas + WFQ on the executor path: anna unlimited, zoe 25 qps
    shed = 0
    futs = []
    for j in range(150):
        who = "anna" if j % 2 else "zoe"
        try:
            futs.append((who, svc.submit_async(queries[j % len(queries)],
                                               tenant=who)))
        except TenantThrottled:
            shed += 1
    for _, f in futs:
        f.result(timeout=60.0)
    st = svc.stats()
    ten = st["tenants"]
    assert ten["anna"]["shed"] == 0, ten
    assert ten["zoe"]["shed"] == shed > 0, (shed, ten)
    assert ten["anna"]["requests"] + ten["zoe"]["requests"] == len(futs)
    assert st["qos"]["queued"] == 0 and st["qos"]["in_flight"] == 0
    served = {w for w, _ in futs}
    assert served == {"anna", "zoe"}
    print(f"[tenants] quotas: zoe shed {shed} over-rate submits, anna 0; "
          f"WFQ dispatched {st['qos']['dispatched']}: OK")
    svc.shutdown()
    print("[tenants] multi-tenant serving OK")
    return 0


def spec_smoke(spec_path: str, clock: str) -> int:
    """Boot the selftest fleet from a durable deploy file and stream the
    same skewed trace through it."""
    from repro.service import AnnService, ServiceSpec

    spec = ServiceSpec.load(spec_path)
    ds, index = _corpus_and_index()
    queries = np.asarray(ds.queries, np.float32)
    svc = AnnService.build(spec, points=np.asarray(ds.points),
                           sample_queries=queries)
    svc.warmup()
    direct_d, direct_i = svc.search(queries)
    pool = np.arange(24) % 4
    stream = [(i * 5e-4, queries[pool[i]]) for i in range(24)]
    reqs = svc.stream(stream, clock=clock)
    for i, r in enumerate(reqs):
        assert set(r.ids.tolist()) == set(direct_i[pool[i]].tolist())
    st = svc.stats()
    assert sum(st["router"]["picks"]) == len(reqs), st["router"]
    print(f"[spec] {spec_path}: booted {svc.n_replicas} replica(s) "
          f"engine={spec.engine} router={st['router']['policy']}, "
          f"streamed {len(reqs)} requests (clock={clock}): OK")
    svc.shutdown()
    return 0


def autotune_smoke(slo_recall: float, slo_p99_ms: float,
                   save_spec: str | None) -> int:
    """Derive a deploy spec for the smoke corpus: run the SLO-driven
    auto-tuner (perf-model shortlist -> measured calibration) and print
    its report; ``--save-spec`` persists the winning ServiceSpec."""
    from repro.service import SLO, SLOInfeasible, TuneSpace, autotune

    from repro.data import make_clustered_corpus

    ds = make_clustered_corpus(seed=0, n=3000, d=16, n_queries=48,
                               n_components=12, k_gt=10)
    space = TuneSpace(m=(4, 8), nprobe=(2, 4, 8),
                      lut_dtype=("uint8", "f32"), buckets=((1, 2, 4, 8),),
                      tasks_per_shard=(1024,),
                      cache_capacity_bytes=(0, 1 << 19))
    slo = SLO(recall_at_k=slo_recall, p99_ms=slo_p99_ms)
    try:
        res = autotune(np.asarray(ds.points), slo,
                       queries=np.asarray(ds.queries),
                       groundtruth=np.asarray(ds.groundtruth),
                       space=space, nlist=16, calibration_requests=48,
                       validate_budget=6, seed=0)
    except SLOInfeasible as e:
        print(f"[autotune] INFEASIBLE: {e}")
        for entry in e.frontier:
            print(f"[autotune]   frontier: m={entry['m']} "
                  f"nprobe={entry['nprobe']} lut={entry['lut_dtype']} "
                  f"recall={entry['recall']:.3f} "
                  f"p99={entry['p99_ms']:.2f}ms")
        return 1
    for line in res.report().splitlines():
        print(f"[autotune] {line}")
    if save_spec:
        path = res.spec.save(save_spec)
        print(f"[autotune] spec saved -> {path} "
              f"(boot it with --spec {path})")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.service",
                                 description=__doc__)
    ap.add_argument("--selftest", action="store_true",
                    help="run the end-to-end service smoke test")
    ap.add_argument("--selftest-chaos", action="store_true",
                    help="run the fault-injection chaos smoke: Zipf "
                         "stream over an armed fleet; asserts "
                         "availability >= 0.95, zero corrupt results, "
                         "and corrupted-spill rebuild")
    ap.add_argument("--selftest-tenants", action="store_true",
                    help="run the multi-tenant serving smoke: two "
                         "tenants, disjoint corpora on one shared "
                         "index; asserts isolation (scoped == dedicated "
                         "subindex), predicate filters, quotas, and WFQ "
                         "accounting")
    ap.add_argument("--chaos-queries", type=int, default=1000,
                    help="chaos smoke: stream length (default 1000)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="chaos smoke: FaultPlan / stream seed")
    ap.add_argument("--clock", choices=("virtual", "wall"),
                    default="virtual",
                    help="stream driver for the smoke: discrete-event "
                         "simulation or wall-clock executors")
    ap.add_argument("--spec", metavar="PATH",
                    help="boot the smoke fleet from a ServiceSpec deploy "
                         "file (.json/.yaml) instead of built-in specs")
    ap.add_argument("--autotune", action="store_true",
                    help="derive a spec for the smoke corpus with the "
                         "SLO-driven auto-tuner and print its report")
    ap.add_argument("--slo-recall", type=float, default=0.8,
                    help="autotune: required recall@10 (default 0.8)")
    ap.add_argument("--slo-p99-ms", type=float, default=50.0,
                    help="autotune: paced p99 budget in ms (default 50)")
    ap.add_argument("--save-spec", metavar="PATH",
                    help="autotune: persist the winning ServiceSpec as a "
                         "deploy file (.json/.yaml)")
    args = ap.parse_args()
    from repro.util import enable_compile_cache
    enable_compile_cache()
    if args.selftest_chaos:
        from repro.service.chaos import selftest_chaos
        return selftest_chaos(seed=args.chaos_seed,
                              n_queries=args.chaos_queries)
    if args.selftest_tenants:
        return selftest_tenants()
    if args.autotune:
        return autotune_smoke(args.slo_recall, args.slo_p99_ms,
                              args.save_spec)
    if args.spec:
        return spec_smoke(args.spec, args.clock)
    if not args.selftest:
        ap.print_help()
        return 2
    return selftest(args.clock)


if __name__ == "__main__":
    sys.exit(main())
