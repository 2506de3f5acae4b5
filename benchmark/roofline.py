"""The least time the chip could take for an IVF-PQ search call.

Counted from the algorithm, not from the program's compiled code, so the
count stays the same whichever implementation serves the call.

Bytes, the least HBM traffic of one call:

- the PQ codes of every cluster that some query of the call probes, each
  cluster once (rows x M one-byte codes, at the index's own cluster sizes:
  padding is the implementation's choice, not the algorithm's);
- the centroid table and the PQ codebooks once.

LUTs and row ids are left out: an implementation can keep them on chip.

Operations, the least arithmetic of one call:

- CL: 2 x D per (query, centroid);
- LC: 3 x D x CB per (query, probe) (a subtraction, a product and a sum
  per codebook coordinate);
- DC: 2 x M per (query, probed row) (a lookup-add per subspace).

The least time is the larger of bytes over peak HBM bandwidth and
operations over the chip's peak operation rate.  At the cells' sizes the
bytes bound: a block of 256 queries at nprobe 32 of 4,096 clusters of
~2,441 rows reads 6e7 to 1.6e8 bytes (~0.07 to 0.2 ms at 819 GB/s) and
needs ~1.7e9 operations (~9 us at 197 TFLOP/s).  No implementation can
do less, so the share cannot pass 100%.
"""

from __future__ import annotations

import numpy as np


def probes(centroids: np.ndarray, queries: np.ndarray,
           nprobe: int) -> np.ndarray:
    """(Q, nprobe) nearest centroids by squared L2, in float64."""
    c = np.asarray(centroids, np.float64)
    q = np.asarray(queries, np.float64)
    d = (np.sum(q * q, 1)[:, None] + np.sum(c * c, 1)[None, :]
         - 2.0 * q @ c.T)
    p = min(nprobe, c.shape[0])
    return np.argpartition(d, p - 1, axis=1)[:, :p]


def call_bytes(sizes: np.ndarray, probed: np.ndarray, m: int,
               table_bytes: int) -> int:
    """Least bytes of one call: codes of the union of probed clusters
    (``sizes`` rows of ``m`` one-byte codes each) plus ``table_bytes``
    (centroids and codebooks) once."""
    union = np.unique(np.asarray(probed).ravel())
    return int(np.asarray(sizes, np.int64)[union].sum()) * int(m) + int(
        table_bytes)


def call_ops(sizes: np.ndarray, probed: np.ndarray, nlist: int, dim: int,
             m: int, cb: int) -> int:
    """Least operations of one call (CL + LC + DC, see the module text)."""
    probed = np.asarray(probed)
    q, p = probed.shape
    rows = int(np.asarray(sizes, np.int64)[probed].sum())
    return 2 * q * nlist * dim + 3 * q * p * dim * cb + 2 * m * rows


def least_seconds(total_bytes: float, total_ops: float, peak: dict) -> float:
    """The roofline: the larger of the byte and the operation bound."""
    return max(float(total_bytes) / float(peak["hbm_bytes_per_s"]),
               float(total_ops) / float(peak["bf16_flops_per_s"]))
