import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SearchParams, search_ivfpq, exact_search, recall_at_k,
                        cluster_locate, build_ivfpq, pad_clusters)


def test_exact_search_oracle(small_corpus):
    pts = small_corpus.points.astype(jnp.float32)
    qs = small_corpus.queries.astype(jnp.float32)
    d, i = exact_search(pts, qs, k=10)
    # distances ascending, ids valid
    dn = np.asarray(d)
    assert (np.diff(dn, axis=1) >= -1e-3).all()
    assert (np.asarray(i) >= 0).all() and (np.asarray(i) < pts.shape[0]).all()
    # first neighbor is genuinely the argmin for a spot-checked query
    full = np.sum((np.asarray(qs[0])[None] - np.asarray(pts)) ** 2, -1)
    assert int(i[0, 0]) == int(full.argmin())


def test_cluster_locate_shapes(small_index, small_corpus):
    q = small_corpus.queries.astype(jnp.float32)
    probes, dists = cluster_locate(q, small_index.centroids, nprobe=8)
    assert probes.shape == (q.shape[0], 8)
    assert (np.asarray(probes) < small_index.nlist).all()
    # probes sorted by distance ascending
    assert (np.diff(np.asarray(dists), axis=1) >= -1e-3).all()


@pytest.mark.parametrize("lut_dtype", ["f32", "uint8"])
def test_nprobe_above_nlist_scans_every_cluster_once(small_index,
                                                    small_clusters,
                                                    small_corpus, lut_dtype):
    """nprobe > nlist (a live index after merges) searches, and the surplus
    -1 probes scan nothing: same results as nprobe == nlist."""
    q = small_corpus.queries.astype(jnp.float32)
    nlist = small_index.nlist
    probes, dists = cluster_locate(q, small_index.centroids, nprobe=nlist + 5)
    assert (np.asarray(probes)[:, nlist:] == -1).all()
    assert np.isinf(np.asarray(dists)[:, nlist:]).all()
    over = search_ivfpq(small_index, small_clusters, q,
                        SearchParams(nprobe=nlist + 5, k=10,
                                     lut_dtype=lut_dtype))
    exact = search_ivfpq(small_index, small_clusters, q,
                         SearchParams(nprobe=nlist, k=10,
                                      lut_dtype=lut_dtype))
    np.testing.assert_array_equal(np.asarray(over[1]), np.asarray(exact[1]))
    np.testing.assert_allclose(np.asarray(over[0]), np.asarray(exact[0]))


def test_recall_constraint_paper(small_index, small_clusters, small_corpus):
    """Paper §V-A: all experiments under recall@10 >= 0.8 — reproduce it."""
    p = SearchParams(nprobe=16, k=10, query_chunk=64)
    _, ids = search_ivfpq(small_index, small_clusters, small_corpus.queries, p)
    r = float(recall_at_k(ids, small_corpus.groundtruth))
    assert r >= 0.8, f"recall@10 = {r}"


def test_recall_monotonic_in_nprobe(small_index, small_clusters, small_corpus):
    rs = []
    for nprobe in (1, 4, 16):
        p = SearchParams(nprobe=nprobe, k=10, query_chunk=64)
        _, ids = search_ivfpq(small_index, small_clusters,
                              small_corpus.queries, p)
        rs.append(float(recall_at_k(ids, small_corpus.groundtruth)))
    assert rs[0] <= rs[1] + 0.02 and rs[1] <= rs[2] + 0.02


def test_gather_and_onehot_agree(small_index, small_clusters, small_corpus):
    pg = SearchParams(nprobe=8, k=10, strategy="gather", query_chunk=64)
    po = SearchParams(nprobe=8, k=10, strategy="onehot", query_chunk=64)
    dg, ig = search_ivfpq(small_index, small_clusters, small_corpus.queries, pg)
    do, io = search_ivfpq(small_index, small_clusters, small_corpus.queries, po)
    np.testing.assert_allclose(np.asarray(dg), np.asarray(do), rtol=1e-4,
                               atol=1e-2)
    # id lists may differ only at distance ties
    same = (np.asarray(ig) == np.asarray(io)).mean()
    assert same > 0.97


def test_search_handles_nonmultiple_query_count(small_index, small_clusters,
                                                small_corpus):
    p = SearchParams(nprobe=4, k=5, query_chunk=30)  # 64 % 30 != 0
    d, i = search_ivfpq(small_index, small_clusters,
                        small_corpus.queries, p)
    assert d.shape == (64, 5) and i.shape == (64, 5)
    assert np.isfinite(np.asarray(d)).all()


def test_opq_pipeline_end_to_end(small_corpus):
    idx = build_ivfpq(jax.random.PRNGKey(1), small_corpus.points, nlist=32,
                      m=16, cb=128, kmeans_iters=4, pq_iters=4, opq=True)
    clusters = pad_clusters(idx)
    p = SearchParams(nprobe=8, k=10, query_chunk=64)
    _, ids = search_ivfpq(idx, clusters, small_corpus.queries, p)
    r = float(recall_at_k(ids, small_corpus.groundtruth))
    assert r >= 0.6  # OPQ path functional and reasonably accurate
