"""The on-device generator: the data set is fixed by the configuration,
and the run's seed orders the stream."""

import numpy as np

import datagen

GEN = dict(structure_seed=3, latent_dim=6, components=8, center_scale=1.0,
           within_scale=0.3, within_log_sd=0.3, weight_concentration=2.0,
           noise_scale=0.03, query_zipf=1.3, coord_std=32.0, chunk_rows=700)


def make(dtype, dim, gen=GEN):
    data = dict(n=2000, dim=dim, dtype=dtype, queries=64)
    st = datagen.structure(data, gen)
    rot = datagen.rotation(gen, dim)
    return (np.asarray(datagen.corpus(data, gen, st, rot)),
            np.asarray(datagen.query_pool(data, gen, st, rot)))


def test_the_configuration_fixes_the_data():
    for dtype, dim in (("uint8", 32), ("unit", 24)):
        a, qa = make(dtype, dim)
        b, qb = make(dtype, dim)
        assert np.array_equal(a, b) and np.array_equal(qa, qb)
        c, _ = make(dtype, dim, dict(GEN, structure_seed=2**40 + 3))
        assert not np.array_equal(a, c)


def test_shapes_dtypes_and_finish():
    x, q = make("uint8", 32)
    assert x.shape == (2000, 32) and x.dtype == np.uint8 and q.shape == (64, 32)
    assert 100 < x.mean() < 155
    u, _ = make("unit", 24)
    assert u.dtype == np.float32
    np.testing.assert_allclose(np.linalg.norm(u, axis=1), 1.0, rtol=1e-5)


def test_stream_order_puts_the_evaluation_set_first():
    o = datagen.stream_order(7, 100, 10)
    assert sorted(o[:10]) == list(range(10))
    assert sorted(o) == list(range(100))
    assert np.array_equal(o, datagen.stream_order(7, 100, 10))
    assert not np.array_equal(o, datagen.stream_order(2**40 + 7, 100, 10))
