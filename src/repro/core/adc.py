"""Asymmetric distance computation (ADC): LUT construction + PQ code scan.

These are the pure-jnp reference implementations of the paper's LC and DC
phases.  The Pallas kernels in ``repro.kernels`` are validated against these
(kernels/ref.py re-exports them).

Phase glossary (paper §II-A):
  RC  residual = query - centroid                      (per (q, probe) pair)
  LC  lut[m, cb] = || residual_m - codebook[m, cb] ||^2
  DC  dist[i]   = sum_m lut[m, codes[i, m]]

Quantized-LUT fast path: the paper's core move is replacing arithmetic
with lookup tables sized to the weak compute next to memory; carrying
those tables as f32 wastes the very bandwidth the substitution saves.
:func:`quantize_lut` compresses each (M, CB) LUT to uint8 with a
per-subspace affine transform ``lut ~ lut_q * scale_m + bias_m``, so

    dist = sum_m lut[m, code_m]
         ~ sum_m scale_m * lut_q[m, code_m]  +  sum_m bias_m

— the DC phase accumulates small integers per subspace and applies M
scales plus one constant at the end.  The absolute error per subspace is
bounded by ``scale_m / 2`` (half a quantization step), so per-distance
error is ``sum_m scale_m / 2`` — a fixed offset-ish perturbation that
preserves top-k ordering well enough for recall parity (asserted in
tests/test_quantized.py).  Traffic per LUT drops 4x: 16 KiB -> 4 KiB +
2*M floats at M=16, CB=256.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.kmeans import HIGHEST
from repro.core.pq import PQCodebook


def build_lut(codebook: PQCodebook, residual: jax.Array) -> jax.Array:
    """LC: (D,) residual -> (M, CB) LUT of exact squared subvector distances.

    Expansion form ||r||^2 - 2 r.c + ||c||^2 — one small GEMM per subspace,
    which is how the MXU wants it. Exact for f32 inputs (modulo fp assoc.).
    """
    r = residual.astype(jnp.float32).reshape(codebook.m, 1, codebook.dsub)
    cross = jnp.einsum("mkd,mcd->mc", r, codebook.codebooks,
                       precision=HIGHEST)                       # (M, CB)
    rsq = jnp.sum(r * r, axis=-1)                               # (M, 1)
    return jnp.maximum(rsq + codebook.sqnorms - 2.0 * cross, 0.0)


@jax.named_scope("LC")
def build_lut_batch(codebook: PQCodebook, residuals: jax.Array) -> jax.Array:
    """(T, D) residuals -> (T, M, CB) LUTs (vmapped LC)."""
    return jax.vmap(lambda r: build_lut(codebook, r))(residuals)


def build_lut_direct(codebook: PQCodebook, residual: jax.Array) -> jax.Array:
    """Subtraction-form LC: sum_d (r_d - c_d)^2.  Numerically the 'honest'
    form (no cancellation); used as the oracle for the expansion form and as
    the basis of the multiplier-less integer path."""
    r = residual.astype(jnp.float32).reshape(codebook.m, 1, codebook.dsub)
    diff = r - codebook.codebooks                               # (M, CB, dsub)
    return jnp.sum(diff * diff, axis=-1)


def scan_codes(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """DC via gather: lut (M, CB), codes (C, M) -> dists (C,).

    This is the paper's DPU inner loop (table lookups + adds). On TPU the
    random lane-gather is the expensive op — see scan_codes_onehot.
    """
    gathered = jax.vmap(lambda l, c: l[c], in_axes=(0, 1), out_axes=1)(
        lut, codes.astype(jnp.int32))                           # (C, M)
    return jnp.sum(gathered, axis=1)


def scan_codes_onehot(lut: jax.Array, codes: jax.Array,
                      compute_dtype=jnp.float32) -> jax.Array:
    """DC via one-hot MXU contraction — the TPU-native inversion of the
    paper's multiplier-less trick (DESIGN.md §2).

    dist = onehot(codes) (C, M*CB) @ lut.flatten() (M*CB,)
    Bit-identical to scan_codes for f32 (each row sums exactly M nonzeros).
    """
    cbn = lut.shape[1]
    onehot = jax.nn.one_hot(codes.astype(jnp.int32), cbn, dtype=compute_dtype)
    flat = onehot.reshape(codes.shape[0], -1)                   # (C, M*CB)
    return jnp.matmul(flat, lut.reshape(-1).astype(compute_dtype),
                      precision=HIGHEST)


@jax.named_scope("DC")
def adc_distances(lut: jax.Array, codes: jax.Array, sizes: jax.Array | None
                  = None, strategy: str = "gather") -> jax.Array:
    """Batched DC over padded clusters.

    lut    (T, M, CB)   one LUT per task (= (query, probe) pair)
    codes  (T, C, M)    padded cluster codes per task
    sizes  (T,)         valid row count per task (None = all valid)
    -> dists (T, C), padding rows set to +inf.
    """
    fn = scan_codes if strategy == "gather" else scan_codes_onehot
    d = jax.vmap(fn)(lut, codes)
    if sizes is not None:
        valid = jnp.arange(codes.shape[1])[None, :] < sizes[:, None]
        d = jnp.where(valid, d, jnp.inf)
    return d


# --------------------------------------------------------------------------
# Quantized-LUT path (uint8 + per-(task, subspace) affine scales)
# --------------------------------------------------------------------------

class QuantizedLUT(NamedTuple):
    """A uint8 LUT with per-subspace affine dequantization parameters.

    Shapes carry an optional leading task axis:
      lut_q  (..., M, CB)  uint8 — quantized table entries
      scale  (..., M)      f32   — per-subspace step, (max - min) / 255
      bias   (..., M)      f32   — per-subspace minimum

    ``dequantize_lut`` recovers ``lut_q * scale + bias``; a degenerate
    subspace (max == min) stores scale=1 with all-zero codes so the
    roundtrip is exact there.
    """
    lut_q: jax.Array
    scale: jax.Array
    bias: jax.Array


@jax.named_scope("LC")
def quantize_lut(lut: jax.Array) -> QuantizedLUT:
    """Affine uint8 quantization over the CB axis, per (task, subspace).

    lut (..., M, CB) f32 -> QuantizedLUT.  Every subspace gets its own
    [min, max] range, so hot subspaces with wide distance spread don't
    steal resolution from tight ones (the per-task part of 'per-(task,
    subspace)' falls out of the leading batch axes).
    """
    lut = lut.astype(jnp.float32)
    lo = jnp.min(lut, axis=-1)                                # (..., M)
    hi = jnp.max(lut, axis=-1)
    scale = jnp.where(hi > lo, (hi - lo) / 255.0, 1.0)
    q = jnp.round((lut - lo[..., None]) / scale[..., None])
    lut_q = jnp.clip(q, 0.0, 255.0).astype(jnp.uint8)
    return QuantizedLUT(lut_q, scale, lo)


def dequantize_lut(qlut: QuantizedLUT) -> jax.Array:
    """(..., M, CB) f32 reconstruction — the reference the quantized scan
    is validated against (max error scale/2 per entry)."""
    return (qlut.lut_q.astype(jnp.float32) * qlut.scale[..., None]
            + qlut.bias[..., None])


def scan_codes_quantized(qlut: QuantizedLUT, codes: jax.Array) -> jax.Array:
    """Quantized DC via gather: per subspace, gather the uint8 entry and
    accumulate ``scale_m * entry``; one shared ``sum_m bias_m`` at the end.

    Bit-identical to ``scan_codes(dequantize_lut(qlut), codes)`` up to f32
    summation order (integers <= 255 are exact in f32).
    """
    gathered = jax.vmap(lambda l, c: l[c], in_axes=(0, 1), out_axes=1)(
        qlut.lut_q, codes.astype(jnp.int32))                  # (C, M) u8
    acc = jnp.matmul(gathered.astype(jnp.float32), qlut.scale,
                     precision=HIGHEST)                       # (C,)
    return acc + jnp.sum(qlut.bias)


def scan_codes_onehot_quantized(qlut: QuantizedLUT,
                                codes: jax.Array) -> jax.Array:
    """Quantized DC via one-hot MXU contraction — the uint8 mirror of
    ``scan_codes_onehot``.

    The onehot operand is built in bf16 (0/1 exact) and contracted
    against the uint8 table as bf16 (integers <= 255 are exact in bf16's
    8-bit significand), accumulating in f32 — so the (C, M*CB) onehot
    intermediate, the VMEM-dominating tensor of the DC phase, shrinks 2x
    while the LUT operand shrinks 4x.  Per-subspace accumulators (M, C)
    then take one tiny (M,) x (M, C) scale contraction.
    """
    m, cbn = qlut.lut_q.shape
    onehot = jax.nn.one_hot(codes.astype(jnp.int32), cbn,
                            dtype=jnp.bfloat16)               # (C, M, CB)
    acc = jax.lax.dot_general(
        onehot, qlut.lut_q.astype(jnp.bfloat16),
        dimension_numbers=(((2,), (1,)), ((1,), (0,))),
        preferred_element_type=jnp.float32)                   # (M, C)
    return (jnp.matmul(qlut.scale, acc, precision=HIGHEST)
            + jnp.sum(qlut.bias))


@jax.named_scope("DC")
def adc_distances_quantized(qlut: QuantizedLUT, codes: jax.Array,
                            sizes: jax.Array | None = None,
                            strategy: str = "gather") -> jax.Array:
    """Batched quantized DC — drop-in for :func:`adc_distances` with a
    (T,)-batched :class:`QuantizedLUT` instead of the f32 (T, M, CB)."""
    fn = (scan_codes_quantized if strategy == "gather"
          else scan_codes_onehot_quantized)
    d = jax.vmap(fn)(qlut, codes)
    if sizes is not None:
        valid = jnp.arange(codes.shape[1])[None, :] < sizes[:, None]
        d = jnp.where(valid, d, jnp.inf)
    return d
