"""Distributed engine tests.

In-process tests use the vmap simulation path (1 CPU device).  The genuine
shard_map + mesh path runs in a subprocess with 8 forced host devices (the
dry-run rule: never override device count inside the main test process).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SearchParams, search_ivfpq, recall_at_k, pad_clusters,
                        cluster_locate)
from repro.core.sharded_search import (DistributedEngine, EngineConfig,
                                       materialize_shards, merge_host,
                                       merge_on_device, run_shards_vmap)
from repro.core.layout import build_layout, estimate_heat


def _engine(small_index, small_corpus, **kw):
    probes, _ = cluster_locate(small_corpus.queries.astype(jnp.float32),
                               small_index.centroids, 8)
    kw.setdefault("strategy", "gather")   # onehot's (T,C,M,CB) one-hot is
    # covered by kernel tests; the CPU vmap simulation keeps gather cheap.
    kw.setdefault("dup_budget_bytes", 1 << 18)
    cfg = EngineConfig(n_shards=8, nprobe=16, k=10, tasks_per_shard=256, **kw)
    return DistributedEngine(small_index, cfg, np.asarray(probes))


def test_distributed_matches_single_device(small_index, small_clusters,
                                           small_corpus):
    """The sharded engine must return the same neighbors as the single-
    device pipeline (same index, same nprobe)."""
    eng = _engine(small_index, small_corpus)
    dd, ii, info = eng.search(small_corpus.queries)
    p = SearchParams(nprobe=16, k=10, query_chunk=64)
    sd, si = search_ivfpq(small_index, small_clusters, small_corpus.queries, p)
    # distances agree (ids can permute on ties)
    np.testing.assert_allclose(dd, np.asarray(sd), rtol=1e-3, atol=0.5)
    overlap = np.mean([
        len(set(ii[q]) & set(np.asarray(si)[q])) / 10
        for q in range(ii.shape[0])])
    assert overlap > 0.97


def test_distributed_recall_constraint(small_index, small_corpus):
    eng = _engine(small_index, small_corpus)
    _, ii, _ = eng.search(small_corpus.queries)
    r = float(recall_at_k(jnp.asarray(ii), small_corpus.groundtruth))
    assert r >= 0.8


def test_split_layout_still_exact(small_index, small_corpus):
    """Splitting clusters must not change results (parts are disjoint)."""
    eng_split = _engine(small_index, small_corpus, split_max=32)
    eng_whole = _engine(small_index, small_corpus, split_max=10**9)
    d1, i1, _ = eng_split.search(small_corpus.queries)
    d2, i2, _ = eng_whole.search(small_corpus.queries)
    np.testing.assert_allclose(d1, d2, rtol=1e-3, atol=0.5)


def test_filter_flush_preserves_results(small_index, small_corpus):
    eng_f = _engine(small_index, small_corpus, enable_filter=True,
                    filter_ratio=1.05)
    eng_n = _engine(small_index, small_corpus, enable_filter=False)
    d1, i1, info1 = eng_f.search(small_corpus.queries, flush=True)
    d2, i2, _ = eng_n.search(small_corpus.queries)
    assert info1["rounds"] >= 1
    np.testing.assert_allclose(d1, d2, rtol=1e-3, atol=0.5)


def test_merge_on_device_matches_host():
    rng = np.random.default_rng(0)
    s, t, k, nq = 4, 16, 5, 12
    qidx = rng.integers(-1, nq, size=(s, t)).astype(np.int32)
    d = rng.normal(size=(s, t, k)).astype(np.float32)
    d.sort(axis=-1)
    ids = rng.integers(0, 10**6, size=(s, t, k)).astype(np.int32)
    hd, hi = merge_host(qidx, d, ids, nq, k)
    dd, di = merge_on_device(jnp.asarray(qidx), jnp.asarray(d),
                             jnp.asarray(ids), n_queries=nq, k=k)
    np.testing.assert_allclose(np.asarray(dd), hd, rtol=1e-6)


def test_materialize_shards_roundtrip(small_index):
    sizes = np.asarray(small_index.sizes)
    heat = np.ones(small_index.nlist)
    lay = build_layout(sizes, heat, 4, split_max=64)
    sx = materialize_shards(small_index, lay)
    # every corpus row appears exactly once across shards
    all_ids = np.asarray(sx.ids).reshape(-1)
    valid = all_ids[all_ids >= 0]
    assert len(valid) == len(set(valid.tolist()))
    assert len(valid) == int(sizes.sum())


def test_duplicated_rows_counted_once(small_index, small_corpus):
    """With duplication ON, ids may appear on several shards but the merge
    must not produce duplicate neighbors for a query."""
    eng = _engine(small_index, small_corpus, dup_budget_bytes=1 << 20)
    _, ii, _ = eng.search(small_corpus.queries)
    for q in range(ii.shape[0]):
        row = ii[q][ii[q] >= 0]
        assert len(row) == len(set(row.tolist()))


SHARD_MAP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import build_ivfpq, cluster_locate, recall_at_k
    from repro.core.sharded_search import (DistributedEngine, EngineConfig,
                                           run_shards_vmap)
    from repro.data import make_clustered_corpus

    assert len(jax.devices()) == 8
    mesh = jax.make_mesh((8,), ("shards",))
    ds = make_clustered_corpus(0, n=4000, d=32, n_queries=32,
                               n_components=16, k_gt=10)
    idx = build_ivfpq(jax.random.PRNGKey(0), ds.points, nlist=32, m=16,
                      cb=128, kmeans_iters=4, pq_iters=4)
    probes, _ = cluster_locate(ds.queries.astype(jnp.float32), idx.centroids, 8)
    cfg = EngineConfig(n_shards=8, nprobe=8, k=10, tasks_per_shard=128,
                       dup_budget_bytes=1 << 18)
    eng = DistributedEngine(idx, cfg, np.asarray(probes), mesh=mesh)
    d_mesh, i_mesh, _ = eng.search(ds.queries)
    # compare against the vmap simulation path
    eng2 = DistributedEngine(idx, cfg, np.asarray(probes), mesh=None)
    d_sim, i_sim, _ = eng2.search(ds.queries)
    np.testing.assert_allclose(d_mesh, d_sim, rtol=1e-3, atol=0.5)
    r = float(recall_at_k(jnp.asarray(i_mesh), ds.groundtruth))
    assert r > 0.6, r
    print("SHARD_MAP_OK recall=%.3f" % r)
""")


def test_shard_map_8dev_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # the parent already loaded JAX; on a TPU host it holds the chip, so
    # the child must stay on the CPU or it blocks on the device lock
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", SHARD_MAP_SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), timeout=600)
    assert "SHARD_MAP_OK" in out.stdout, out.stderr[-3000:]
