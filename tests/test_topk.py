import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:      # degrade to a fixed-example sweep
    from _hypothesis_fallback import given, settings, st

from repro.core import topk_smallest, merge_topk
from repro.core.topk import smallest_k
from repro.kernels.pq_scan import _select_topk


def test_topk_smallest_basic():
    d = jnp.array([5.0, 1.0, 3.0, 2.0, 4.0])
    i = jnp.arange(5, dtype=jnp.int32)
    bd, bi = topk_smallest(d, i, 3)
    np.testing.assert_allclose(np.asarray(bd), [1, 2, 3])
    np.testing.assert_array_equal(np.asarray(bi), [1, 3, 2])


@pytest.mark.parametrize("k", [2, 5, 9])
def test_smallest_k_clamps_and_pads(k):
    """k past the axis (nprobe > nlist) pads with (+inf, -1) instead of
    raising; within the axis it is exactly lax.top_k."""
    d = jnp.array([[4.0, 1.0, 3.0, 2.0, 0.5]])
    vals, idx = smallest_k(d, k)
    full = np.argsort(np.asarray(d[0]), kind="stable")
    n = min(k, 5)
    np.testing.assert_array_equal(np.asarray(idx[0, :n]), full[:n])
    np.testing.assert_allclose(np.asarray(vals[0, :n]),
                               np.asarray(d[0])[full[:n]])
    assert (np.asarray(idx[0, n:]) == -1).all()
    assert np.isinf(np.asarray(vals[0, n:])).all()


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_running_topk_matches_full_sort(seed):
    """Property: folding blocks through the fused kernel's selection step
    (``_select_topk``) == top-k of the concatenation (the in-kernel TS
    invariant), ids included."""
    rng = np.random.default_rng(seed)
    k, nblocks, bs = 16, 5, 64
    blocks_d = rng.normal(size=(nblocks, bs)).astype(np.float32)
    blocks_i = np.arange(nblocks * bs, dtype=np.int32).reshape(nblocks, bs)
    best_d = jnp.full((1, 128), jnp.inf)
    best_i = jnp.full((1, 128), -1, jnp.int32)
    for bd, bi in zip(blocks_d, blocks_i):
        best_d, best_i = _select_topk(best_d, best_i, jnp.asarray(bd)[None],
                                      jnp.asarray(bi)[None], k)
    order = np.argsort(blocks_d.reshape(-1), kind="stable")[:k]
    np.testing.assert_allclose(np.asarray(best_d)[0, :k],
                               blocks_d.reshape(-1)[order], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(best_i)[0, :k], order)
    assert np.isinf(np.asarray(best_d)[0, k:]).all()


def test_select_topk_ties_keep_scan_order():
    """Equal distances resolve to the earlier candidate, across blocks and
    within one, as lax.top_k does — so the fused kernel's ids match the
    full-scan oracle even where quantized distances tie."""
    k = 4
    best_d = jnp.full((1, 128), jnp.inf)
    best_i = jnp.full((1, 128), -1, jnp.int32)
    blocks = [np.array([2.0, 1.0, 1.0, 3.0], np.float32),
              np.array([1.0, 2.0, 0.5, 1.0], np.float32)]
    for b, blk in enumerate(blocks):
        ids = np.arange(4, dtype=np.int32) + 4 * b
        best_d, best_i = _select_topk(best_d, best_i, jnp.asarray(blk)[None],
                                      jnp.asarray(ids)[None], k)
    flat = np.concatenate(blocks)
    _, want = jax.lax.top_k(-jnp.asarray(flat), k)
    np.testing.assert_array_equal(np.asarray(best_i)[0, :k], np.asarray(want))


def test_select_topk_never_repeats_an_id():
    """With fewer finite candidates than k, the surplus slots are
    (+inf, -1): a taken entry is struck, never selected twice."""
    best_d = jnp.full((1, 128), jnp.inf)
    best_i = jnp.full((1, 128), -1, jnp.int32)
    d = jnp.array([[3.0, jnp.inf, 1.0, jnp.inf]])
    i = jnp.array([[7, -1, 9, -1]], jnp.int32)
    nd, ni = _select_topk(best_d, best_i, d, i, 4)
    np.testing.assert_array_equal(np.asarray(ni)[0, :4], [9, 7, -1, -1])
    np.testing.assert_allclose(np.asarray(nd)[0, :2], [1.0, 3.0])


def test_merge_topk():
    d1 = jnp.array([[1.0, 4.0, 9.0]])
    i1 = jnp.array([[10, 40, 90]], dtype=jnp.int32)
    d2 = jnp.array([[2.0, 3.0, 11.0]])
    i2 = jnp.array([[20, 30, 110]], dtype=jnp.int32)
    md, mi = merge_topk(d1, i1, d2, i2, 4)
    np.testing.assert_allclose(np.asarray(md[0]), [1, 2, 3, 4])
    np.testing.assert_array_equal(np.asarray(mi[0]), [10, 20, 30, 40])
