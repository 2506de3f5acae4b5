"""Compile the main path for a described TPU v5e, with no chip attached.

Each case lowers and compiles with Mosaic / XLA:TPU at real widths
(M=16, CB=256, dsub=8, 4096 candidates per task; the served
``search_ivfpq`` step at nlist 4096, nprobe 32), so a kernel or step the
chip's compiler would refuse fails here, at no chip time.  Nothing runs:
these say nothing about results or speed.

The topology is described inside a module fixture — never at import —
because only one process may load the TPU library at a time.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import SearchParams, search_ivfpq
from repro.core.adc import QuantizedLUT
from repro.core.ivf import IVFPQIndex, PaddedClusters
from repro.core.pq import PQCodebook
from repro.kernels import ops

M, CB, DSUB, T, C, K = 16, 256, 8, 256, 4096, 10
D = M * DSUB
NLIST, NPROBE, CMAX, N, Q = 4096, 32, 4096, 1 << 20, 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _cases(s):
    f32, u8, i32 = jnp.float32, jnp.uint8, jnp.int32
    lc = (s((T, D), f32), s((M, CB, DSUB), f32), s((M, CB), f32))
    scan = (s((T, C, M), u8), s((T, C), i32), s((T,), i32))
    qlut = (s((T, M, CB), u8), s((T, M), f32), s((T, M), f32))
    idx = IVFPQIndex(s((NLIST, D), f32),
                     PQCodebook(s((M, CB, DSUB), f32), s((M, CB), f32)),
                     s((N, M), u8), s((N,), i32), s((NLIST + 1,), i32))
    cl = PaddedClusters(s((NLIST, CMAX, M), u8), s((NLIST, CMAX), i32),
                        s((NLIST,), i32))

    def served(lut_dtype):
        p = SearchParams(nprobe=NPROBE, k=K, lut_dtype=lut_dtype)
        return (lambda i, c, q: search_ivfpq(i, c, q, p),
                (idx, cl, s((Q, D), f32)), False)

    return {
        "lut_build": (lambda r, b, n: ops.lut_build(r, b, n,
                                                    interpret=False),
                      lc, True),
        "lut_build_q": (lambda r, b, n: ops.lut_build_q(r, b, n,
                                                        interpret=False),
                        lc, True),
        "pq_scan_topk_f32": (
            lambda lut, c, i, z: ops.pq_scan_topk(lut, c, i, z, K,
                                                  interpret=False),
            (s((T, M, CB), f32),) + scan, True),
        "pq_scan_topk_u8": (
            lambda lq, sc, b, c, i, z: ops.pq_scan_topk(
                QuantizedLUT(lq, sc, b), c, i, z, K, interpret=False),
            qlut + scan, True),
        "pq_scan_dc_f32": (
            lambda lut, c, z: ops.pq_scan_dc(lut, c, z, interpret=False),
            (s((T, M, CB), f32), scan[0], scan[2]), True),
        "search_ivfpq_gather_f32": served("f32"),
        "search_ivfpq_gather_u8": served("uint8"),
    }


CASES = ["lut_build", "lut_build_q", "pq_scan_topk_f32", "pq_scan_topk_u8",
         "pq_scan_dc_f32", "search_ivfpq_gather_f32",
         "search_ivfpq_gather_u8"]


@pytest.mark.parametrize("case", CASES)
def test_compiles_for_v5e(one_chip, case):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args, is_kernel = _cases(s)[case]
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    if is_kernel:
        assert "tpu_custom_call" in compiled.as_text()


def test_gather_kernel_refused_off_interpret():
    """Mosaic has no vector gather for the per-subspace lookup, so the
    wrapper refuses strategy='gather' outside interpret mode instead of
    quietly interpreting."""
    lut = jnp.zeros((2, M, CB), jnp.float32)
    codes = jnp.zeros((2, 8, M), jnp.uint8)
    with pytest.raises(ValueError, match="interpret"):
        ops.pq_scan_dc(lut, codes, strategy="gather", interpret=False)
