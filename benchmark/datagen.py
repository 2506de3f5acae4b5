"""On-device corpus and query generator for the benchmark's deployments.

A deployment's data is a Gaussian mixture in a low-dimensional latent
space, embedded in the published dimension beside small full-rank noise:

    y = [mu_c + s_c * eps_L ;  noise * eps_(D-L)]      component c of K
    x = R y                                           R: (D, D) orthogonal

The latent set (component centers, spreads and weights, every row's
component and draws, the fixed query set) and the rotation ``R`` come
from the configuration's ``structure_seed``: they are the deployment's
data set, as a public benchmark ships one file.  The run's ``--seed``
orders the query stream (``stream_order``) and draws the samples the
check compares; it does not touch the data.  Every seed therefore gets
the same corpus, the same index and the same work, in another order.
The seed leaves the data alone because even a permutation of coordinates
within PQ subspaces moves the k-means partition by rounding, and with it
recall@10 by 2% between seeds on the chip.

``uint8`` deployments (BIGANN) map ``x`` affinely to a per-coordinate
standard deviation of ``coord_std`` around 127.5 and round, clipping to
[0, 255]; ``unit`` deployments (DEEP) divide each row by its norm.

Everything runs on the device in one jitted call per array, in row
chunks, so no host copy of the corpus is ever made.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (64 bits and more)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    rest = seed >> 32
    while rest:
        key = jax.random.fold_in(key, rest & 0xFFFFFFFF)
        rest >>= 32
    return key


class Structure(NamedTuple):
    """The latent mixture, fixed by the configuration."""
    centers: np.ndarray       # (K, L) f32
    spreads: np.ndarray       # (K,) f32
    corpus_logits: np.ndarray  # (K,) f32, log component weights
    query_logits: np.ndarray  # (K,) f32, Zipf by rank
    scale: float              # affine scale applied after rotation


def structure(data: dict, gen: dict) -> Structure:
    """The mixture's parameters from the configuration's ``generator``."""
    dim, lat, k = int(data["dim"]), int(gen["latent_dim"]), int(gen["components"])
    if not 0 < lat <= dim:
        raise ValueError(f"latent_dim {lat} must lie in (0, {dim}]")
    rng = np.random.default_rng(int(gen["structure_seed"]))
    centers = rng.normal(0.0, float(gen["center_scale"]), size=(k, lat))
    spreads = float(gen["within_scale"]) * np.exp(
        float(gen["within_log_sd"]) * rng.normal(size=k))
    weights = rng.dirichlet(np.full(k, float(gen["weight_concentration"])))
    ranks = np.arange(1, k + 1, dtype=np.float64)
    qlogits = -float(gen["query_zipf"]) * np.log(ranks)
    # mean square of one coordinate of x, from the mixture's moments
    msq = ((np.sum(weights * (np.sum(centers ** 2, 1) + lat * spreads ** 2))
            + (dim - lat) * float(gen["noise_scale"]) ** 2) / dim)
    scale = (float(gen["coord_std"]) / np.sqrt(msq)
             if data["dtype"] == "uint8" else 1.0)
    return Structure(centers.astype(np.float32), spreads.astype(np.float32),
                     np.log(weights).astype(np.float32),
                     qlogits.astype(np.float32), float(scale))


def rotation(gen: dict, dim: int) -> jax.Array:
    """The deployment's (dim, dim) orthogonal map, fixed by the
    configuration's ``structure_seed``."""
    g = jax.random.normal(seed_key(int(gen["structure_seed"])), (dim, dim),
                          jnp.float32)
    q, r = jnp.linalg.qr(g)
    return q * jnp.sign(jnp.diagonal(r))[None, :]


@functools.partial(jax.jit, static_argnames=("n", "chunk", "dtype"))
def _rows(key, centers, spreads, logits, noise, scale, rot, *, n: int,
          chunk: int, dtype: str):
    """(n, D) rows of the mixture, rotated and finished, in row chunks."""
    lat = centers.shape[1]
    dim = rot.shape[0]

    def one(i):
        kc, ke = jax.random.split(jax.random.fold_in(key, i))
        comp = jax.random.categorical(kc, logits, shape=(chunk,))
        eps = jax.random.normal(ke, (chunk, dim), jnp.float32)
        sig = centers[comp] + spreads[comp][:, None] * eps[:, :lat]
        y = jnp.concatenate([sig, noise * eps[:, lat:]], axis=1)
        x = jnp.matmul(y, rot.T, precision=HIGHEST)
        if dtype == "uint8":
            return jnp.clip(jnp.round(127.5 + scale * x), 0, 255
                            ).astype(jnp.uint8)
        return x / jnp.linalg.norm(x, axis=1, keepdims=True)

    blocks = -(-n // chunk)
    out_dtype = jnp.uint8 if dtype == "uint8" else jnp.float32

    def body(i, out):
        return jax.lax.dynamic_update_slice_in_dim(out, one(i), i * chunk, 0)

    out = jax.lax.fori_loop(0, blocks, body,
                            jnp.zeros((blocks * chunk, dim), out_dtype))
    return out[:n]


def _make(data: dict, gen: dict, st: Structure, rot, which: int, n: int,
          logits) -> jax.Array:
    key = jax.random.fold_in(seed_key(int(gen["structure_seed"])), which)
    chunk = min(int(gen.get("chunk_rows", 500_000)), n)
    return _rows(key, jnp.asarray(st.centers), jnp.asarray(st.spreads),
                 jnp.asarray(logits), jnp.float32(gen["noise_scale"]),
                 jnp.float32(st.scale), rot, n=n, chunk=chunk,
                 dtype=str(data["dtype"]))


def corpus(data: dict, gen: dict, st: Structure, rot) -> jax.Array:
    """The (N, D) corpus on the device (uint8 or unit-norm float32)."""
    return _make(data, gen, st, rot, 1, int(data["n"]), st.corpus_logits)


def query_pool(data: dict, gen: dict, st: Structure, rot) -> jax.Array:
    """The deployment's fixed query set, (Q, D), in the corpus's dtype.
    Components are picked Zipf by rank, so a few are hot."""
    return _make(data, gen, st, rot, 2, int(data["queries"]), st.query_logits)


def stream_order(seed: int, n_pool: int, n_eval: int) -> np.ndarray:
    """The order in which a run sends the pool: the evaluation set (the
    pool's first ``n_eval`` rows) first, then the rest, each part shuffled
    by the seed."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x51]))
    return np.concatenate([rng.permutation(n_eval),
                           n_eval + rng.permutation(n_pool - n_eval)])
