"""The plain references at tiny sizes."""
import numpy as np
import pytest

import generate
import reference


def _graph(seed=0):
    """120 nodes in 4 well-separated blocks."""
    return generate.sparse_sbm(120, 4, 16.0, 1.0, seed, min_degree=3)


def test_laplacian_matvec_matches_dense():
    edges, _ = _graph()
    w = np.random.default_rng(1).uniform(0.5, 2.0, len(edges))
    v = np.random.default_rng(2).normal(size=(120, 3))
    dense = reference.dense_laplacian(edges[:, 0], edges[:, 1], w, 120)
    got = reference.laplacian_matvec(edges[:, 0], edges[:, 1], w, 120, v)
    np.testing.assert_allclose(got, dense @ v, atol=1e-12)
    np.testing.assert_allclose(dense.sum(1), 0.0, atol=1e-12)


def test_residual_and_angle_of_exact_and_perturbed_panels():
    edges, _ = _graph()
    src, dst, w = edges[:, 0], edges[:, 1], np.ones(len(edges))
    vals, vecs = reference.reference_spectrum(src, dst, w, 120)
    exact = vecs[:, :6] @ np.random.default_rng(3).normal(size=(6, 6))
    assert reference.block_residual(src, dst, w, 120, exact) < 1e-12
    assert reference.subspace_sin(vecs[:, :4], exact) < 1e-12
    noisy = vecs[:, :6] + 1e-3 * np.random.default_rng(4).normal(
        size=exact.shape)
    assert 1e-5 < reference.subspace_sin(vecs[:, :4], noisy) < 1e-1
    rand = np.random.default_rng(5).normal(size=(120, 6))
    assert reference.block_residual(src, dst, w, 120, rand) > 0.1
    assert reference.subspace_sin(vecs[:, :4], rand) > 0.5


def test_lobpcg_space_matches_dense_eigh():
    edges, planted = generate.sparse_sbm(3000, 4, 6.0, 0.6, 7, min_degree=3)
    src, dst, w = edges[:, 0], edges[:, 1], np.ones(len(edges))
    vals, space, rel = reference.bottom_eigenspace(
        src, dst, w, 3000, np.eye(4)[planted])
    dvals, dvecs = reference.reference_spectrum(src, dst, w, 3000)
    assert rel < 1e-6
    np.testing.assert_allclose(vals, dvals[:4], atol=1e-8)
    assert reference.subspace_sin(dvecs[:, :4], space) < 1e-6


def test_reference_labels_recover_planted_blocks():
    edges, planted = _graph()
    src, dst, w = edges[:, 0], edges[:, 1], np.ones(len(edges))
    _, vecs = reference.reference_spectrum(src, dst, w, 120)
    labels = reference.reference_labels(vecs, 4, seed=0)
    assert reference.agreement(labels, planted, 4) == 1.0


def test_agreement_is_permutation_invariant():
    truth = np.array([0, 0, 1, 1, 2, 2, 3, 3])
    assert reference.agreement((truth + 1) % 4, truth, 4) == 1.0
    off = np.array([0, 0, 1, 1, 2, 2, 3, 0])
    assert reference.agreement(off, truth, 4) == pytest.approx(7 / 8)


def test_plain_kmeans_separates_blobs():
    rng = np.random.default_rng(0)
    centers = np.array([[0, 0], [10, 0], [0, 10]], float)
    truth = np.repeat(np.arange(3), 30)
    x = centers[truth] + rng.normal(scale=0.3, size=(90, 2))
    labels = reference.plain_kmeans(x, 3, seed=1)
    assert reference.agreement(labels, truth, 3) == 1.0
