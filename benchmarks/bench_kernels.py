"""Kernel micro-benches (interpret mode on CPU — correctness-scale timing;
TPU-target perf is the roofline story).  One row per kernel x strategy,
for both LUT dtypes: ``*_u8`` rows run the quantized fast path
(uint8 table + per-subspace scales; see core.adc.quantize_lut) against
the same codes, and ``kernels/dc_speedup_u8`` derives the f32/u8 DC
timing ratio plus the 4x LUT byte shrink that holds regardless of
interpret-mode timing noise."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timeit, row
from repro.kernels import ops, ref


def run(quick: bool = False):
    rng = np.random.default_rng(0)
    t_, m, cb, c, dsub = 16, 16, 256, 1024, 8
    res = jnp.asarray(rng.normal(size=(t_, m * dsub)).astype(np.float32))
    books = jnp.asarray(rng.normal(size=(m, cb, dsub)).astype(np.float32))
    sqn = jnp.sum(books * books, -1)
    codes = jnp.asarray(rng.integers(0, cb, size=(t_, c, m)).astype(np.int32))
    ids = jnp.asarray(rng.integers(0, 1 << 20, size=(t_, c)).astype(np.int32))
    sizes = jnp.full((t_,), c, jnp.int32)

    out = []
    t = timeit(lambda: ops.lut_build(res, books, sqn))
    out.append(row("kernels/lut_build", t, f"tasks={t_}"))
    t = timeit(lambda: ops.lut_build_q(res, books, sqn))
    out.append(row("kernels/lut_build_q", t, "fused_quantize_epilogue"))
    lut = ops.lut_build(res, books, sqn)
    qlut = ops.lut_build_q(res, books, sqn)
    lut_bytes = int(np.asarray(lut).nbytes)
    q_bytes = int(sum(np.asarray(a).nbytes for a in qlut))
    dc_times = {}
    # the gather strategy exists only in interpret mode (Mosaic refuses it)
    strategies = (("gather", "onehot") if ops._default_interpret()
                  else ("onehot",))
    for strat in strategies:
        t = timeit(lambda: ops.pq_scan_dc(lut, codes, sizes, strategy=strat))
        dc_times[("f32", strat)] = t
        out.append(row(f"kernels/pq_scan_dc_{strat}", t,
                       f"rows={t_ * c}"))
        t = timeit(lambda: ops.pq_scan_dc(qlut, codes, sizes, strategy=strat))
        dc_times[("u8", strat)] = t
        out.append(row(f"kernels/pq_scan_dc_{strat}_u8", t,
                       f"rows={t_ * c}"))
        t = timeit(lambda: ops.pq_scan_topk(lut, codes, ids, sizes, 10,
                                            strategy=strat))
        out.append(row(f"kernels/pq_scan_topk_{strat}", t, "k=10_fused"))
        t = timeit(lambda: ops.pq_scan_topk(qlut, codes, ids, sizes, 10,
                                            strategy=strat))
        out.append(row(f"kernels/pq_scan_topk_{strat}_u8", t, "k=10_fused"))
    # headline speedup from the gather strategy: interpret mode emulates
    # bf16 dots op-by-op, so the onehot u8 ratio is a CPU-emulation
    # artifact (on TPU the MXU consumes bf16 natively at 2x f32 rate);
    # the gather path's uint8 loads measure honestly everywhere
    head = strategies[0]
    speedup = dc_times[("f32", head)] / max(dc_times[("u8", head)], 1e-12)
    ratio_oh = dc_times[("f32", "onehot")] / max(dc_times[("u8", "onehot")],
                                                 1e-12)
    out.append(row("kernels/dc_speedup_u8", dc_times[("u8", head)],
                   f"{head}_f32_over_u8={speedup:.2f}x"
                   f"_onehot={ratio_oh:.2f}x"
                   f"_lut_bytes={lut_bytes}->{q_bytes}"))
    # oracle comparison cost (ref path)
    t = timeit(lambda: ref.pq_scan_dc_ref(lut, codes))
    out.append(row("kernels/pq_scan_dc_ref", t, "jnp_oracle"))
    return out
