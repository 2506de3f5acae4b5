"""Plain IVF-PQ reference on the host, in float64, and the comparison
that decides ``correct``.

This is the benchmark's own statement of what an IVF-PQ search returns,
written from the algorithm (paper Fig. 1; Jegou et al., PAMI 2011) and
importing nothing of the program:

    CL  the ``nprobe`` centroids nearest the query (squared L2)
    RC  residual r = q - c for each probed centroid c
    LC  lut[m, j] = |r_m - book[m, j]|^2               (M, CB) per probe
    DC  d(x) = sum_m lut[m, code(x)_m]                 every row x probed
    TS  the k rows of least d

Its input is the built index's public arrays (centroids, PQ codebooks,
codes, ids, offsets): an IVF-PQ answer is defined by them.  What the
answer may be is then judged row by row:

- ``semantics="f32"``: LUT entries as computed, exactly.
- ``semantics="uint8"``: the LUT of each (query, probe) pair quantized
  per subspace to 256 levels, ``lut ~ q * (hi - lo) / 255 + lo`` with
  ``q = round((lut - lo) / step)``, and d summed from the quantized
  entries: the quantized-LUT path's stated result.
- ``"bf16"`` and ``"int4"`` are the controls: the two semantics above one
  precision lower (LUT entries rounded to bfloat16; 16 levels).

The compared number is the *gap*: for each checked query, how far the
worst served row's distance lies above the k-th best distance that the
reference finds in the clusters every correct CL must probe, relative to
that k-th distance.  Exact ties cost nothing, and a row the program
reports from a cluster no correct CL probes, a repeated row or a missing
row makes the gap infinite.  CL near-ties (two centroids within ``tol``
of the probe boundary) are resolved in the program's favour: the k-th
distance is taken over the clusters that are certainly probed, and a
served row may come from any cluster that may be probed.

The build is judged apart (``build_mismatch``): for a sample of corpus
rows, the reference derives in float64, from the built centroids and
codebooks, the cluster each row belongs to (its nearest centroid) and its
code (per subspace the nearest codeword to its residual), and counts the
rows the index stores elsewhere or with another code.  Near-ties go the
program's way, as in CL.  Whether the centroids and codebooks themselves
are good is for recall against the exact top-k.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

# relative tolerance on squared centroid distances within which two
# centroids count as tied at the probe boundary (float32 expansion error
# of |q|^2 - 2 q.c + |c|^2 is ~1e-7 of |q|^2 + |c|^2; 1e-5 leaves room)
CL_RTOL = 1e-5


class RefIndex(NamedTuple):
    centroids: np.ndarray   # (nlist, D) f64
    books: np.ndarray       # (M, CB, dsub) f64
    codes: np.ndarray       # (N, M) uint8, rows grouped by cluster
    ids: np.ndarray         # (N,) row id of each stored row
    offsets: np.ndarray     # (nlist + 1,) start of each cluster's rows
    where: np.ndarray       # (n_ids,) stored position of each row id

    @property
    def nlist(self) -> int:
        return self.centroids.shape[0]


def ref_index(centroids, books, codes, ids, offsets) -> RefIndex:
    ids = np.asarray(ids, np.int64)
    where = np.full(int(ids.max(initial=-1)) + 1, -1, np.int64)
    where[ids] = np.arange(len(ids))
    return RefIndex(np.asarray(centroids, np.float64),
                    np.asarray(books, np.float64), np.asarray(codes),
                    ids, np.asarray(offsets, np.int64), where)


def probe_sets(index: RefIndex, q: np.ndarray, nprobe: int):
    """(sure, possible) cluster ids for one query: ``sure`` are probed by
    every CL whose distances err by less than the tolerance, ``possible``
    by some."""
    d = np.sum((index.centroids - q[None, :]) ** 2, axis=1)
    tol = CL_RTOL * (float(q @ q) + np.max(np.sum(index.centroids ** 2, 1)))
    order = np.argsort(d, kind="stable")
    p = min(nprobe, index.nlist)
    if p == index.nlist:
        return order, order
    inner, outer = d[order[p - 1]], d[order[p]]
    sure = order[:p][d[order[:p]] < outer - 2 * tol]
    possible = np.nonzero(d <= inner + 2 * tol)[0]
    return sure, possible


def lut(index: RefIndex, q: np.ndarray, cluster: int,
        semantics: str) -> np.ndarray:
    """(M, CB) table of one (query, cluster) pair under ``semantics``."""
    m, cb, dsub = index.books.shape
    r = (q - index.centroids[cluster]).reshape(m, 1, dsub)
    t = np.sum((r - index.books) ** 2, axis=2)
    if semantics == "f32":
        return t
    if semantics == "bf16":
        import ml_dtypes
        return t.astype(np.float32).astype(ml_dtypes.bfloat16).astype(
            np.float64)
    levels = {"uint8": 255.0, "int4": 15.0}[semantics]
    lo, hi = t.min(axis=1, keepdims=True), t.max(axis=1, keepdims=True)
    step = np.where(hi > lo, (hi - lo) / levels, 1.0)
    return np.clip(np.round((t - lo) / step), 0.0, levels) * step + lo


def _scan(index: RefIndex, q: np.ndarray, clusters, semantics: str):
    """(distances, row ids) of every row stored in ``clusters``."""
    m = index.books.shape[0]
    ds, rows = [], []
    for c in clusters:
        a, b = index.offsets[c], index.offsets[c + 1]
        if b > a:
            t = lut(index, q, int(c), semantics)
            ds.append(t[np.arange(m), index.codes[a:b].astype(np.int64)]
                      .sum(axis=1))
            rows.append(index.ids[a:b])
    if not ds:
        return np.zeros(0), np.zeros(0, np.int64)
    return np.concatenate(ds), np.concatenate(rows)


def row_distance(index: RefIndex, q: np.ndarray, row: int,
                 semantics: str) -> tuple:
    """(distance, cluster) of one stored row under ``semantics``."""
    pos = index.where[row]
    c = int(np.searchsorted(index.offsets, pos, side="right") - 1)
    t = lut(index, q, c, semantics)
    code = index.codes[pos].astype(np.int64)
    return float(t[np.arange(len(code)), code].sum()), c


def search(index: RefIndex, q: np.ndarray, nprobe: int, k: int,
           semantics: str) -> np.ndarray:
    """The reference's own top-k row ids over the ``nprobe`` nearest
    clusters (the control puts this in the program's place)."""
    d = np.sum((index.centroids - q[None, :]) ** 2, axis=1)
    probes = np.argsort(d, kind="stable")[:nprobe]
    dist, rows = _scan(index, q, probes, semantics)
    top = np.lexsort((rows, dist))[:k]
    return rows[top]


def gap(index: RefIndex, q: np.ndarray, served: np.ndarray, nprobe: int,
        k: int, semantics: str) -> float:
    """Relative gap of one query's served top-k (see the module text)."""
    served = np.asarray(served, np.int64)
    if (len(served) != k or len(set(served.tolist())) != k
            or served.min() < 0 or served.max() >= len(index.where)
            or (index.where[served] < 0).any()):
        return float("inf")
    q = np.asarray(q, np.float64)
    sure, possible = probe_sets(index, q, nprobe)
    dist, _ = _scan(index, q, sure, semantics)
    if len(dist) < k:
        return float("inf")
    kth = float(np.partition(dist, k - 1)[k - 1])
    allowed = set(possible.tolist())
    worst = -np.inf
    for row in served:
        d, c = row_distance(index, q, int(row), semantics)
        if c not in allowed:
            return float("inf")
        worst = max(worst, d)
    return max(0.0, worst - kth) / max(kth, 1e-30)


def build_mismatch(index: RefIndex, vectors: np.ndarray,
                   rows: np.ndarray) -> float:
    """Share of the given corpus rows (``vectors``, row ids ``rows``) that
    the index does not store, stores in a cluster whose centroid is not
    the nearest, or stores with a code that is not the nearest codeword to
    the row's residual in some subspace.  A choice within ``CL_RTOL`` of
    the best (relative to the squared norms compared) counts as right."""
    x = np.asarray(vectors, np.float64)
    rows = np.asarray(rows, np.int64)
    known = rows < len(index.where)
    pos = np.where(known, index.where[np.minimum(rows, len(index.where) - 1)],
                   -1)
    stored = pos >= 0
    pos = np.maximum(pos, 0)
    c = np.searchsorted(index.offsets, pos, side="right") - 1
    cc = np.sum(index.centroids ** 2, axis=1)
    xx = np.sum(x * x, axis=1)
    d = xx[:, None] + cc[None, :] - 2.0 * (x @ index.centroids.T)
    tol = CL_RTOL * (xx + cc.max())
    take = np.arange(len(x))
    ok = d[take, c] <= d.min(axis=1) + 2 * tol

    m, cb, dsub = index.books.shape
    r = (x - index.centroids[c]).reshape(len(x), m, dsub)
    rr = np.sum(r * r, axis=2)                                  # (S, M)
    bb = np.sum(index.books ** 2, axis=2)                       # (M, CB)
    e = rr[:, :, None] + bb[None] - 2.0 * np.einsum("smd,mjd->smj", r,
                                                    index.books)
    tol_m = CL_RTOL * (rr + bb.max(axis=1)[None, :])
    code = index.codes[pos].astype(np.int64)                    # (S, M)
    mine = np.take_along_axis(e, code[:, :, None], axis=2)[:, :, 0]
    ok &= (mine <= e.min(axis=2) + 2 * tol_m).all(axis=1)
    return float(np.mean(~(ok & stored))) if len(x) else 0.0


def max_gap(index: RefIndex, queries: np.ndarray, served: np.ndarray,
            nprobe: int, k: int, semantics: str) -> float:
    """The widest gap over the checked queries."""
    return max((gap(index, q, s, nprobe, k, semantics)
                for q, s in zip(queries, served)), default=0.0)
