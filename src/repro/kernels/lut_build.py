"""Pallas TPU kernel for the LC phase: batched ADC LUT construction.

For every task t (a (query, probe) pair) and subspace m:

    lut[t, m, cb] = || res[t, m, :] - codebook[m, cb, :] ||^2
                  = ||res_m||^2 + ||C||^2 - 2 * res_m . C^T

All M subspaces are computed in one pass as two (bT, D) x (D, M*CB) MXU
matmuls against block-diagonal operands built once per call:

    W[m*dsub + j, m*CB + c] = codebook[m, c, j]   -> cross = res @ W
    S[m*dsub + j, m*CB + c] = 1                   -> rsq   = (res*res) @ S

so the output row (M*CB lanes) is lane-dense and no in-kernel reshape or
sub-128-lane block is needed (Mosaic's (8, 128) block rule).

Grid  : (T / bT,)  — parallel (no cross-iteration state)
Blocks: res  (bT, D)        VMEM
        W, S (D, M*CB)      VMEM, same block every step (fetched once)
        sqn  (1, M*CB)      VMEM
        out  (bT, M*CB)     VMEM

VMEM per step (bT=64, D=128, M*CB=4096, f32): W + S 4 MB (double-buffered
8 MB) + out 1 MB (2 MB) + ~3 MB of temporaries — under the 16 MB scoped
default of a v5e core.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST


def _block_diag(codebooks: jax.Array):
    """codebooks (M, CB, dsub) -> W, S (M*dsub, M*CB) f32 (see header)."""
    m, cbn, dsub = codebooks.shape
    eye = jnp.eye(m, dtype=jnp.float32)
    w = jnp.einsum("mcj,mn->mjnc", codebooks.astype(jnp.float32), eye)
    s = jnp.einsum("mn,j,c->mjnc", eye, jnp.ones((dsub,), jnp.float32),
                   jnp.ones((cbn,), jnp.float32))
    return (w.reshape(m * dsub, m * cbn), s.reshape(m * dsub, m * cbn))


def _lut_block(res_ref, w_ref, s_ref, sqn_ref) -> jax.Array:
    r = res_ref[...]                                       # (bT, D) f32
    cross = jnp.dot(r, w_ref[...], precision=_HIGHEST,
                    preferred_element_type=jnp.float32)    # (bT, M*CB)
    rsq = jnp.dot(r * r, s_ref[...], precision=_HIGHEST,
                  preferred_element_type=jnp.float32)
    return jnp.maximum(rsq + sqn_ref[...] - 2.0 * cross, 0.0)


def _lut_build_kernel(res_ref, w_ref, s_ref, sqn_ref, out_ref):
    out_ref[...] = _lut_block(res_ref, w_ref, s_ref, sqn_ref)


def _call(kernel, residuals, codebooks, sqnorms, out_specs, out_shape,
          block_t, interpret, name):
    t, d = residuals.shape
    m, cbn, _ = codebooks.shape
    assert t % block_t == 0, (t, block_t)
    w, s = _block_diag(codebooks)
    const = lambda i: (0, 0)                               # noqa: E731
    return pl.pallas_call(
        kernel,
        grid=(t // block_t,),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),
            pl.BlockSpec((d, m * cbn), const),
            pl.BlockSpec((d, m * cbn), const),
            pl.BlockSpec((1, m * cbn), const),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=name,
    )(residuals.astype(jnp.float32), w, s,
      sqnorms.astype(jnp.float32).reshape(1, m * cbn))


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def lut_build_pallas(residuals: jax.Array, codebooks: jax.Array,
                     sqnorms: jax.Array, *, block_t: int,
                     interpret: bool) -> jax.Array:
    """residuals (T, D) f32, codebooks (M, CB, dsub), sqnorms (M, CB)
    -> luts (T, M*CB) f32.  T must be a multiple of block_t (ops.py pads)."""
    t = residuals.shape[0]
    m, cbn, _ = codebooks.shape
    return _call(_lut_build_kernel, residuals, codebooks, sqnorms,
                 pl.BlockSpec((block_t, m * cbn), lambda i: (i, 0)),
                 jax.ShapeDtypeStruct((t, m * cbn), jnp.float32),
                 block_t, interpret, "drim_lut_build")


# --------------------------------------------------------------------------
# Fused quantize epilogue: LC + per-(task, subspace) affine uint8
# quantization in one kernel.  The f32 table exists only inside the VMEM
# block; HBM sees (bT, M*CB) u8 plus two (bT, M) f32 tables — the
# writeback drops ~4x (the paper's shrink-the-LUT move applied to our
# own memory hierarchy).  Quantization math follows core.adc.quantize_lut
# (same ops, same order); an entry on a rounding boundary may differ by
# one count where the chip's division rounds differently.
# --------------------------------------------------------------------------

def _lut_build_q_kernel(res_ref, w_ref, s_ref, sqn_ref, outq_ref, outs_ref,
                        outb_ref):
    lut = _lut_block(res_ref, w_ref, s_ref, sqn_ref)       # (bT, M*CB) f32
    m = outs_ref.shape[1]
    cbn = lut.shape[1] // m
    lane = jax.lax.broadcasted_iota(jnp.int32, outs_ref.shape, 1)
    scales = jnp.zeros(outs_ref.shape, jnp.float32)
    biases = jnp.zeros(outs_ref.shape, jnp.float32)
    for mm in range(m):                           # static unroll over subspaces
        seg = lut[:, mm * cbn:(mm + 1) * cbn]              # (bT, CB)
        lo = jnp.min(seg, axis=1, keepdims=True)           # (bT, 1)
        hi = jnp.max(seg, axis=1, keepdims=True)
        scale = jnp.where(hi > lo, (hi - lo) / 255.0, 1.0)
        q = jnp.clip(jnp.round((seg - lo) / scale), 0.0, 255.0)
        outq_ref[:, mm * cbn:(mm + 1) * cbn] = (
            q.astype(jnp.int32).astype(jnp.uint8))
        scales = jnp.where(lane == mm, scale, scales)
        biases = jnp.where(lane == mm, lo, biases)
    outs_ref[...] = scales
    outb_ref[...] = biases


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def lut_build_q_pallas(residuals: jax.Array, codebooks: jax.Array,
                       sqnorms: jax.Array, *, block_t: int,
                       interpret: bool):
    """residuals (T, D) f32, codebooks (M, CB, dsub), sqnorms (M, CB)
    -> (lut_q (T, M*CB) u8, scale (T, M) f32, bias (T, M) f32).
    T must be a multiple of block_t (ops.py pads)."""
    t = residuals.shape[0]
    m, cbn, _ = codebooks.shape
    row = lambda i: (i, 0)                                 # noqa: E731
    return _call(
        _lut_build_q_kernel, residuals, codebooks, sqnorms,
        [pl.BlockSpec((block_t, m * cbn), row),
         pl.BlockSpec((block_t, m), row),
         pl.BlockSpec((block_t, m), row)],
        [jax.ShapeDtypeStruct((t, m * cbn), jnp.uint8),
         jax.ShapeDtypeStruct((t, m), jnp.float32),
         jax.ShapeDtypeStruct((t, m), jnp.float32)],
        block_t, interpret, "drim_lut_build_q")
