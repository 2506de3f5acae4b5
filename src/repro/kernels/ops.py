"""jit'd public wrappers around the Pallas kernels.

Handles: dtype casts, the kernels' layouts (flattened LUT rows, codes
transposed so the candidate axis is the lane axis), padding to block
multiples, and backend selection.  ``interpret`` defaults to True only on
the CPU backend; everywhere else the kernels are compiled by Mosaic, and
the ``onehot`` strategy is the one that compiles for the TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.adc import QuantizedLUT
from repro.kernels.lut_build import lut_build_pallas, lut_build_q_pallas
from repro.kernels.pq_scan import pq_scan_dc_pallas, pq_scan_topk_pallas
from repro.util import next_pow2


def _default_interpret() -> bool:
    return jax.default_backend() == "cpu"


def _resolve_interpret(interpret: bool | None, strategy: str = "onehot"
                       ) -> bool:
    if interpret is None:
        interpret = _default_interpret()
    if strategy == "gather" and not interpret:
        raise ValueError(
            "strategy='gather' runs only in interpret mode: Mosaic (the TPU "
            "kernel compiler) has no vector gather for the per-subspace "
            "table lookup.  Use strategy='onehot' on the chip, or "
            "interpret=True.")
    return interpret


# Per-block VMEM is one (CB, bC) onehot per subspace (pq_scan.py header);
# quantized LUTs build it in bf16 instead of f32, so the same budget fits
# twice the block — u8 defaults to 2x the f32 block.
_BLOCK_C_F32 = 256
_BLOCK_C_U8 = 512


def _resolve_block_c(block_c: int | None, quantized: bool) -> int:
    if block_c is not None:
        return block_c
    return _BLOCK_C_U8 if quantized else _BLOCK_C_F32


def _pad_rows(residuals: jax.Array, block_t: int):
    t = residuals.shape[0]
    bt = min(block_t, next_pow2(t))
    pad = (-t) % bt
    if pad:
        residuals = jnp.pad(residuals, ((0, pad), (0, 0)))
    return residuals, bt


def lut_build(residuals: jax.Array, codebooks: jax.Array,
              sqnorms: jax.Array, *, block_t: int = 64,
              interpret: bool | None = None) -> jax.Array:
    """(T, D) residuals -> (T, M, CB) LUTs (pads T to block_t multiple)."""
    interpret = _resolve_interpret(interpret)
    t = residuals.shape[0]
    m, cbn, _ = codebooks.shape
    res, bt = _pad_rows(residuals, block_t)
    out = lut_build_pallas(res, codebooks, sqnorms, block_t=bt,
                           interpret=interpret)
    return out[:t].reshape(t, m, cbn)


def lut_build_q(residuals: jax.Array, codebooks: jax.Array,
                sqnorms: jax.Array, *, block_t: int = 64,
                interpret: bool | None = None) -> QuantizedLUT:
    """LC with the fused quantize epilogue: (T, D) residuals ->
    QuantizedLUT of (T, M, CB) u8 + (T, M) scale/bias.  The f32 table
    never leaves the kernel's VMEM block — HBM writeback is the u8 table
    plus two scalars per subspace (~4x less than ``lut_build``)."""
    interpret = _resolve_interpret(interpret)
    t = residuals.shape[0]
    m, cbn, _ = codebooks.shape
    res, bt = _pad_rows(residuals, block_t)
    lut_q, scale, bias = lut_build_q_pallas(res, codebooks, sqnorms,
                                            block_t=bt, interpret=interpret)
    return QuantizedLUT(lut_q[:t].reshape(t, m, cbn), scale[:t], bias[:t])


def _scan_operands(lut, codes: jax.Array, block_c: int):
    """Kernel-layout LUT operands and lane-major codes (T, M, C') padded
    to a multiple of the block; returns (lut, scale, bias), codes, cb."""
    t, c, m = codes.shape
    if isinstance(lut, QuantizedLUT):
        cbn = lut.lut_q.shape[-1]
        luts = (lut.lut_q.astype(jnp.uint8).reshape(t, 1, m * cbn),
                lut.scale.reshape(t, 1, m), lut.bias.reshape(t, 1, m))
    else:
        cbn = lut.shape[-1]
        luts = (lut.reshape(t, 1, m * cbn), None, None)
    codes_t = jnp.swapaxes(codes.astype(jnp.int32), 1, 2)
    pad = (-c) % block_c
    if pad:
        codes_t = jnp.pad(codes_t, ((0, 0), (0, 0), (0, pad)))
    return luts, codes_t, cbn


def pq_scan_dc(lut, codes: jax.Array, sizes: jax.Array | None
               = None, *, strategy: str = "onehot",
               block_c: int | None = None,
               interpret: bool | None = None) -> jax.Array:
    """DC phase: (T, M, CB) x (T, C, M) -> (T, C); padding rows +inf.

    ``lut`` is either the f32 (T, M, CB) table or a
    :class:`~repro.core.adc.QuantizedLUT` (uint8 fast path)."""
    interpret = _resolve_interpret(interpret, strategy)
    t, c, m = codes.shape
    bc = min(_resolve_block_c(block_c, isinstance(lut, QuantizedLUT)),
             next_pow2(c))
    luts, codes_t, cbn = _scan_operands(lut, codes, bc)
    d = pq_scan_dc_pallas(*luts, codes_t, cb=cbn, strategy=strategy,
                          block_c=bc, interpret=interpret)[:, 0, :c]
    if sizes is not None:
        valid = jnp.arange(c)[None, :] < sizes[:, None]
        d = jnp.where(valid, d, jnp.inf)
    return d


def pq_scan_topk(lut, codes: jax.Array, ids: jax.Array,
                 sizes: jax.Array, k: int, *, strategy: str = "onehot",
                 block_c: int | None = None, interpret: bool | None = None):
    """Fused DC+TS: returns (dists (T, k) ascending, ids (T, k)).

    ``lut`` is either the f32 (T, M, CB) table or a
    :class:`~repro.core.adc.QuantizedLUT` (uint8 fast path)."""
    interpret = _resolve_interpret(interpret, strategy)
    t, c, m = codes.shape
    bc = min(_resolve_block_c(block_c, isinstance(lut, QuantizedLUT)),
             next_pow2(c))
    luts, codes_t, cbn = _scan_operands(lut, codes, bc)
    ids_i = ids.astype(jnp.int32)
    pad = codes_t.shape[2] - c
    if pad:
        ids_i = jnp.pad(ids_i, ((0, 0), (0, pad)), constant_values=-1)
    bd, bi = pq_scan_topk_pallas(*luts, codes_t, ids_i[:, None, :], sizes,
                                 k=k, cb=cbn, strategy=strategy, block_c=bc,
                                 interpret=interpret)
    return bd[:, 0, :k], bi[:, 0, :k]
