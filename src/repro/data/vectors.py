"""Vector corpus generation + loading for the ANNS engine.

SIFT100M/DEEP100M (the paper's datasets) are multi-GB downloads that are not
available offline, so measured experiments run on a *clustered* synthetic
corpus with SIFT-like statistics: a mixture of Gaussians quantized to uint8,
with a Zipfian query distribution over the mixture components so the paper's
load-imbalance phenomena (hot clusters, skewed sizes) actually appear.
Full-scale shapes enter only through the dry-run's ShapeDtypeStructs.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


_GEN_CHUNK = 1 << 18          # rows per float64 generation chunk


class VectorDataset(NamedTuple):
    points: jax.Array        # (N, D) uint8 or f32
    queries: jax.Array       # (Q, D) same dtype
    groundtruth: jax.Array   # (Q, k_gt) i32 exact neighbors (filled lazily)


def make_clustered_corpus(seed: int, n: int, d: int, *, n_queries: int = 256,
                          n_components: int = 64, zipf_a: float = 1.3,
                          size_skew: float = 1.0, dtype=jnp.uint8,
                          k_gt: int = 0) -> VectorDataset:
    """Mixture-of-Gaussians corpus.

    size_skew > 0 draws component weights from a Dirichlet with concentration
    1/size_skew -> skewed cluster populations (Observation 1 of the paper).
    Queries are drawn Zipf(zipf_a) over components -> hot clusters
    (Observations 2-3).
    """
    rng = np.random.default_rng(seed)
    centers = rng.normal(0.0, 40.0, size=(n_components, d))
    alpha = np.full(n_components, 1.0 / max(size_skew, 1e-3))
    weights = rng.dirichlet(alpha)
    comp = rng.choice(n_components, size=n, p=weights)

    # Points are drawn in row chunks so the float64 working set is one
    # chunk, not (N, D); the generator's stream is consumed in the same
    # order, so the corpus is identical to a single draw.  uint8 scaling
    # needs the global range first: pass 1 finds it, pass 2 replays the
    # same stream from the saved state and quantizes.
    buf = np.empty((min(n, _GEN_CHUNK), d))
    mean = np.empty_like(buf)

    def chunks():      # normal(0, 12) is 0 + 12 * standard_normal in numpy
        for s in range(0, n, _GEN_CHUNK):
            rows = comp[s:s + _GEN_CHUNK]
            p, c = buf[:len(rows)], mean[:len(rows)]
            rng.standard_normal(out=p)
            p *= 12.0
            np.take(centers, rows, axis=0, out=c)
            np.add(c, p, out=p)
            yield s, p

    out = np.empty((n, d), np.uint8 if dtype == jnp.uint8 else np.float32)
    if dtype == jnp.uint8:
        state = rng.bit_generator.state
        lo, hi = np.inf, -np.inf
        for _, p in chunks():
            lo, hi = min(lo, p.min()), max(hi, p.max())
        rng.bit_generator.state = state
        scale = 255.0 / (hi - lo)
        for s, p in chunks():
            p -= lo
            p *= scale
            np.round(p, out=p)
            out[s:s + len(p)] = np.clip(p, 0, 255, out=p)
    else:
        for s, p in chunks():
            out[s:s + len(p)] = p

    # Zipfian query component choice over components ranked by weight
    rank = np.argsort(-weights)
    zipf_p = 1.0 / np.arange(1, n_components + 1) ** zipf_a
    zipf_p /= zipf_p.sum()
    qcomp = rank[rng.choice(n_components, size=n_queries, p=zipf_p)]
    qs = centers[qcomp] + rng.normal(0.0, 12.0, size=(n_queries, d))

    pts = out
    if dtype == jnp.uint8:
        qs = np.clip(np.round((qs - lo) * scale), 0, 255).astype(np.uint8)
    else:
        qs = qs.astype(np.float32)

    gt = np.zeros((n_queries, max(k_gt, 1)), np.int32)
    if k_gt > 0:
        from repro.core.search import exact_search
        _, gt = exact_search(jnp.asarray(pts, jnp.float32),
                             jnp.asarray(qs, jnp.float32), k=k_gt)
    return VectorDataset(jnp.asarray(pts), jnp.asarray(qs), jnp.asarray(gt))
