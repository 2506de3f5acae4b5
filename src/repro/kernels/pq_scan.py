"""Pallas TPU kernels for the DC (+ fused TS) phases: the PQ code scan.

Two inner-loop strategies (DESIGN.md §2 — the multiplier-less inversion):

  * ``onehot`` (TPU-native): per subspace m, dist += lut_m @ onehot_m
    with onehot_m[c, r] = (codes[m, r] == c).  The PQ code gather becomes
    an MXU contraction, because random lane-gather is the expensive op on
    TPU — the mirror image of the paper replacing multiplies with WRAM
    loads on UPMEM.  This is the strategy that compiles for the chip.
  * ``gather`` (paper-faithful dataflow): per-subspace table lookups +
    adds, the literal DPU loop.  It runs in interpret mode only (CPU
    tests); Mosaic has no vector gather for it, and ``ops.py`` refuses it
    with ``interpret=False``.

Kernels (one f32 / uint8-LUT body each, selected statically):
  pq_scan_dc_pallas    — distances only: (T, 1, C) out; TS handled by XLA.
  pq_scan_topk_pallas  — fused DC+TS: per-task running top-k held in VMEM
                         scratch across the C-axis grid (k rounds of
                         min-select per block — no sort HLO), writes
                         (T, 1, KW) winners.  HBM writeback drops from C
                         floats/task to KW floats/task.

Layouts are chosen for Mosaic's (8, 128) block rule: every operand keeps
a lane-dense last axis or a full-extent one.
  lut    (T, 1, M*CB)  f32, or u8 + scale/bias (T, 1, M) f32
  codes  (T, M, C)     i32 — transposed by ops.py so C is the lane axis
  ids    (T, 1, C)     i32
  sizes  (T,)          i32 — scalar-prefetched into SMEM

Quantized LUTs build the onehot in bf16 and contract it against the
bf16-cast u8 table (integers <= 255 are exact in bf16), so each
per-subspace sum is an exact integer; scale_m applies once per subspace
and sum_m bias_m once per row.

Grid: (T, C/bC); the C axis is 'arbitrary' (sequential) for the fused
kernel because scratch accumulates across it; T stays 'parallel'.

VMEM per step (bC=512, M=16, CB=256): lut 16 KB + codes 32 KB + one
(CB, bC) onehot of 512 KB f32 (256 KB bf16), rebuilt per subspace.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# distance block computation (shared by both kernels)
# --------------------------------------------------------------------------

def _block_dists(lut, scale, bias, codes, *, cb: int,
                 strategy: str) -> jax.Array:
    """lut (1, M*CB) f32 or u8; scale/bias (1, M) f32 (u8 LUT) or None;
    codes (M, bC) i32 -> (1, bC) f32 distances."""
    m, bc = codes.shape
    quantized = scale is not None
    acc = jnp.zeros((1, bc), jnp.float32)
    if strategy == "onehot":
        iota = jax.lax.broadcasted_iota(jnp.int32, (cb, bc), 0)
        for mm in range(m):                       # static unroll over subspaces
            lut_m = lut[:, mm * cb:(mm + 1) * cb]
            hit = codes[mm:mm + 1, :] == iota                   # (CB, bC)
            if quantized:          # Mosaic casts u8 only to 32-bit types
                lut_m = lut_m.astype(jnp.int32).astype(jnp.float32)
                part = jnp.dot(lut_m.astype(jnp.bfloat16),
                               hit.astype(jnp.bfloat16),
                               preferred_element_type=jnp.float32)
                acc = acc + scale[:, mm:mm + 1] * part
            else:
                acc = acc + jnp.dot(lut_m, hit.astype(jnp.float32),
                                    precision=_HIGHEST,
                                    preferred_element_type=jnp.float32)
    elif strategy == "gather":
        for mm in range(m):
            g = jnp.take(lut[0, mm * cb:(mm + 1) * cb], codes[mm], axis=0)
            g = g.astype(jnp.float32)[None, :]
            acc = acc + (scale[:, mm:mm + 1] * g if quantized else g)
    else:
        raise ValueError(f"unknown strategy {strategy!r}")
    if quantized:
        acc = acc + jnp.sum(bias, axis=1, keepdims=True)
    return acc


def _split_refs(refs, quantized: bool):
    """(lut, scale|None, bias|None), rest — the LUT operands lead the
    kernel's ref list, three of them for a u8 table."""
    if quantized:
        return (refs[0][0], refs[1][0], refs[2][0]), refs[3:]
    return (refs[0][0], None, None), refs[1:]


def _lut_specs(m: int, cbn: int, quantized: bool, index_map):
    specs = [pl.BlockSpec((1, 1, m * cbn), index_map)]
    if quantized:
        specs += [pl.BlockSpec((1, 1, m), index_map)] * 2
    return specs


def _lut_args(lut, scale, bias):
    if scale is None:
        return (lut.astype(jnp.float32),)
    return (lut, scale.astype(jnp.float32), bias.astype(jnp.float32))


# --------------------------------------------------------------------------
# DC-only kernel
# --------------------------------------------------------------------------

def _pq_scan_dc_kernel(*refs, cb, strategy, quantized):
    (lut, scale, bias), (codes_ref, out_ref) = _split_refs(refs, quantized)
    out_ref[0] = _block_dists(lut, scale, bias, codes_ref[0], cb=cb,
                              strategy=strategy)


@functools.partial(jax.jit, static_argnames=("cb", "strategy", "block_c",
                                             "interpret"))
def pq_scan_dc_pallas(lut: jax.Array, scale, bias, codes: jax.Array, *,
                      cb: int, strategy: str, block_c: int,
                      interpret: bool) -> jax.Array:
    """lut (T, 1, M*CB) f32 (scale/bias None) or u8 with scale/bias
    (T, 1, M) f32; codes (T, M, C) i32 -> dists (T, 1, C) f32.
    C must be a multiple of block_c (ops.py pads)."""
    t, m, c = codes.shape
    quantized = scale is not None
    assert c % block_c == 0, (c, block_c)
    lut_map = lambda i, j: (i, 0, 0)                      # noqa: E731
    return pl.pallas_call(
        functools.partial(_pq_scan_dc_kernel, cb=cb, strategy=strategy,
                          quantized=quantized),
        grid=(t, c // block_c),
        in_specs=_lut_specs(m, cb, quantized, lut_map) + [
            pl.BlockSpec((1, m, block_c), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_c), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((t, 1, c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=f"drim_pq_scan_dc{'_q' if quantized else ''}_{strategy}",
    )(*_lut_args(lut, scale, bias), codes.astype(jnp.int32))


# --------------------------------------------------------------------------
# fused DC + TS kernel
# --------------------------------------------------------------------------

def _select_topk(best_d, best_i, cand_d, cand_i, k: int):
    """k smallest of the union of the running winners (1, KW) and one
    block of candidates (1, bC) -> new (1, KW) winners, ascending in
    lanes [0, k), +inf/-1 beyond.

    k rounds of lane min-reductions: each round takes the smallest value,
    preferring the running list on ties and the lowest lane within a list
    — so equal distances keep scan order, as ``lax.top_k`` does.  A taken
    entry is struck to (+inf, -1) so it is never taken twice."""
    kw, bc = best_d.shape[1], cand_d.shape[1]
    lane_b = jax.lax.broadcasted_iota(jnp.int32, (1, kw), 1)
    lane_c = jax.lax.broadcasted_iota(jnp.int32, (1, bc), 1)
    new_d = jnp.full((1, kw), jnp.inf, jnp.float32)
    new_i = jnp.full((1, kw), -1, jnp.int32)
    for j in range(k):
        mb = jnp.min(best_d, axis=1, keepdims=True)                 # (1, 1)
        mc = jnp.min(cand_d, axis=1, keepdims=True)
        pb = jnp.min(jnp.where(best_d == mb, lane_b, kw), axis=1,
                     keepdims=True)
        pc = jnp.min(jnp.where(cand_d == mc, lane_c, bc), axis=1,
                     keepdims=True)
        from_b = mb <= mc
        hit_b = lane_b == jnp.where(from_b, pb, -1)
        hit_c = lane_c == jnp.where(from_b, -1, pc)
        got_i = jnp.maximum(
            jnp.max(jnp.where(hit_b, best_i, -1), axis=1, keepdims=True),
            jnp.max(jnp.where(hit_c, cand_i, -1), axis=1, keepdims=True))
        new_d = jnp.where(lane_b == j, jnp.minimum(mb, mc), new_d)
        new_i = jnp.where(lane_b == j, got_i, new_i)
        best_d = jnp.where(hit_b, jnp.inf, best_d)
        best_i = jnp.where(hit_b, -1, best_i)
        cand_d = jnp.where(hit_c, jnp.inf, cand_d)
        cand_i = jnp.where(hit_c, -1, cand_i)
    return new_d, new_i


def _pq_scan_topk_kernel(size_ref, *refs, cb, strategy, quantized, block_c,
                         k):
    (lut, scale, bias), rest = _split_refs(refs, quantized)
    codes_ref, ids_ref, outd_ref, outi_ref, bestd_s, besti_s = rest
    task = pl.program_id(0)
    cstep = pl.program_id(1)

    @pl.when(cstep == 0)
    def _init():
        bestd_s[...] = jnp.full(bestd_s.shape, jnp.inf, jnp.float32)
        besti_s[...] = jnp.full(besti_s.shape, -1, jnp.int32)

    dist = _block_dists(lut, scale, bias, codes_ref[0], cb=cb,
                        strategy=strategy)                       # (1, bC)
    row = cstep * block_c + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_c), 1)
    valid = row < size_ref[task]
    dist = jnp.where(valid, dist, jnp.inf)
    ids = jnp.where(valid, ids_ref[0], -1)
    nd, ni = _select_topk(bestd_s[...], besti_s[...], dist, ids, k)
    bestd_s[...] = nd
    besti_s[...] = ni

    @pl.when(cstep == pl.num_programs(1) - 1)
    def _flush():
        outd_ref[0] = bestd_s[...]
        outi_ref[0] = besti_s[...]


@functools.partial(jax.jit, static_argnames=("k", "cb", "strategy",
                                             "block_c", "interpret"))
def pq_scan_topk_pallas(lut: jax.Array, scale, bias, codes: jax.Array,
                        ids: jax.Array, sizes: jax.Array, *, k: int, cb: int,
                        strategy: str, block_c: int, interpret: bool):
    """Fused DC+TS.

    lut (T, 1, M*CB) f32 (scale/bias None) or u8 with scale/bias
    (T, 1, M) f32; codes (T, M, C) i32; ids (T, 1, C) i32; sizes (T,) i32
    -> (best_d (T, 1, KW) f32 ascending, best_i (T, 1, KW) i32), the
    first k lanes valid, KW = k rounded up to a multiple of 128.
    Requires C % block_c == 0.
    """
    t, m, c = codes.shape
    quantized = scale is not None
    assert c % block_c == 0, (c, block_c)
    kw = -(-k // 128) * 128
    lut_map = lambda i, j, s: (i, 0, 0)                   # noqa: E731
    blk_map = lambda i, j, s: (i, 0, j)                   # noqa: E731
    out_map = lambda i, j, s: (i, 0, 0)                   # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t, c // block_c),
        in_specs=_lut_specs(m, cb, quantized, lut_map) + [
            pl.BlockSpec((1, m, block_c), blk_map),
            pl.BlockSpec((1, 1, block_c), blk_map),
        ],
        out_specs=[pl.BlockSpec((1, 1, kw), out_map)] * 2,
        scratch_shapes=[pltpu.VMEM((1, kw), jnp.float32),
                        pltpu.VMEM((1, kw), jnp.int32)],
    )
    bd, bi = pl.pallas_call(
        functools.partial(_pq_scan_topk_kernel, cb=cb, strategy=strategy,
                          quantized=quantized, block_c=block_c, k=k),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t, 1, kw), jnp.float32),
                   jax.ShapeDtypeStruct((t, 1, kw), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=f"drim_pq_scan_topk{'_q' if quantized else ''}_{strategy}",
    )(sizes.astype(jnp.int32), *_lut_args(lut, scale, bias),
      codes.astype(jnp.int32), ids.astype(jnp.int32))
    return bd, bi
