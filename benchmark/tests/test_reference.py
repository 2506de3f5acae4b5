"""The plain IVF-PQ reference agrees with the program's ``search_ivfpq``
on the CPU at a small size, for f32 and uint8 LUTs; its one-precision-
lower controls do not."""

import numpy as np
import pytest

import datagen
import reference

GEN = dict(structure_seed=3, latent_dim=8, components=8, center_scale=1.0,
           within_scale=0.3, within_log_sd=0.3, weight_concentration=2.0,
           noise_scale=0.03, query_zipf=1.3, coord_std=32.0)
NPROBE, K = 4, 10


@pytest.fixture(scope="module")
def built():
    import jax.numpy as jnp
    from repro.service import IndexSpec

    data = dict(n=6000, dim=32, dtype="uint8", queries=48)
    st = datagen.structure(data, GEN)
    rot = datagen.rotation(GEN, 32)
    x = datagen.corpus(data, GEN, st, rot)
    q = np.asarray(datagen.query_pool(data, GEN, st, rot), np.float32)
    h = IndexSpec(nlist=16, m=8, cb=256, seed=0).build(x)
    ivf = h.ivf
    ref = reference.ref_index(ivf.centroids, ivf.codebook.codebooks,
                              ivf.codes, ivf.ids, ivf.offsets)
    return h, ref, q, jnp, np.asarray(x)


@pytest.mark.parametrize("lut", ["f32", "uint8"])
def test_program_answers_have_no_gap(built, lut):
    from repro.core import SearchParams, search_ivfpq

    h, ref, q, jnp, _ = built
    _, ids = search_ivfpq(h.ivf, h.clusters, jnp.asarray(q),
                          SearchParams(nprobe=NPROBE, k=K, lut_dtype=lut))
    gaps = [reference.gap(ref, qi, si, NPROBE, K, lut)
            for qi, si in zip(q, np.asarray(ids))]
    assert max(gaps) <= 1e-6


@pytest.mark.parametrize("lut", ["f32", "uint8"])
def test_reference_search_has_no_gap(built, lut):
    _, ref, q, _, _ = built
    for qi in q[:8]:
        own = reference.search(ref, qi.astype(np.float64), NPROBE, K, lut)
        assert reference.gap(ref, qi, own, NPROBE, K, lut) == 0.0


def test_malformed_answers_are_infinitely_far(built):
    _, ref, q, _, _ = built
    own = reference.search(ref, q[0].astype(np.float64), NPROBE, K, "f32")
    dup = own.copy()
    dup[1] = dup[0]
    assert reference.gap(ref, q[0], dup, NPROBE, K, "f32") == np.inf
    assert reference.gap(ref, q[0], own[:-1], NPROBE, K, "f32") == np.inf
    bad = own.copy()
    bad[-1] = -1
    assert reference.gap(ref, q[0], bad, NPROBE, K, "f32") == np.inf


def test_a_row_from_an_unprobed_cluster_is_infinitely_far(built):
    _, ref, q, _, _ = built
    qi = q[0].astype(np.float64)
    own = reference.search(ref, qi, NPROBE, K, "f32")
    _, possible = reference.probe_sets(ref, qi, NPROBE)
    far = next(c for c in range(ref.nlist) if c not in set(possible.tolist())
               and ref.offsets[c + 1] > ref.offsets[c])
    own = own.copy()
    own[-1] = ref.ids[ref.offsets[far]]
    assert reference.gap(ref, q[0], own, NPROBE, K, "f32") == np.inf


def test_the_program_build_has_no_mismatch(built):
    _, ref, _, _, x = built
    rows = np.arange(0, len(x), 3)
    assert reference.build_mismatch(ref, x[rows], rows) == 0.0


def test_a_wrong_code_or_cluster_or_a_missing_row_is_a_mismatch(built):
    _, ref, _, _, x = built
    rows = np.arange(40)
    pos = ref.where[rows]
    codes = ref.codes.copy()
    codes[pos[3], 5] ^= 0x55                   # another codeword
    bad = ref._replace(codes=codes)
    assert reference.build_mismatch(bad, x[rows], rows) == 1 / 40
    # row 7 stored in the place of a row of another cluster
    c = np.searchsorted(ref.offsets, ref.where, side="right") - 1
    other = int(np.nonzero(c != c[7])[0][0])
    where = ref.where.copy()
    where[7], where[other] = where[other], where[7]
    moved = ref._replace(where=where)
    assert reference.build_mismatch(moved, x[rows], rows) >= 1 / 40
    gone = ref.where.copy()
    gone[9] = -1
    assert reference.build_mismatch(ref._replace(where=gone), x[rows],
                                    rows) == 1 / 40
