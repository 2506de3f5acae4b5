"""Quickstart: build an IVF-PQ index and search it with the five-phase
DRIM-ANN pipeline, validating the paper's recall@10 >= 0.8 regime.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp

from repro.core import (build_ivfpq, pad_clusters, SearchParams,
                        search_ivfpq, exact_search, recall_at_k)
from repro.data import make_clustered_corpus


def main():
    print("generating a SIFT-like clustered uint8 corpus ...")
    ds = make_clustered_corpus(seed=0, n=20_000, d=32, n_queries=128,
                               n_components=32, k_gt=10)

    print("building IVF-PQ (nlist=64, M=16, CB=256) ...")
    index = build_ivfpq(jax.random.PRNGKey(0), ds.points, nlist=64, m=16,
                        cb=256)
    clusters = pad_clusters(index)

    params = SearchParams(nprobe=16, k=10)
    dists, ids = search_ivfpq(index, clusters, ds.queries, params)
    r = float(recall_at_k(ids, ds.groundtruth))
    print(f"recall@10 = {r:.3f}  (paper constraint: >= 0.8)")
    assert r >= 0.8

    # the same search through the Pallas kernel path (interpreted on the
    # CPU, compiled by Mosaic on a TPU, where onehot is the strategy)
    params_k = SearchParams(nprobe=16, k=10, use_kernels=True,
                            strategy="onehot", query_chunk=32)
    _, ids_k = search_ivfpq(index, clusters, ds.queries, params_k)
    rk = float(recall_at_k(ids_k, ds.groundtruth))
    print(f"recall@10 via Pallas kernels = {rk:.3f}")

    q = ds.queries[0]
    print(f"query 0 neighbors: {ids[0].tolist()}")
    print(f"          dists^2: {[round(float(d), 1) for d in dists[0]]}")


if __name__ == "__main__":
    main()
