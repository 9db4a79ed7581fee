"""SVD drivers vs jnp.linalg.svd; paper Fig. 2 accuracy levels."""

import numpy as np
import pytest

import jax.numpy as jnp
import repro.core as C

from conftest import make_matrix


@pytest.mark.parametrize("kappa", [1.29, 14.0, 9.06e3, 3.16e8, 3.46e11])
def test_zolo_svd_accuracy(kappa):
    """Paper Fig. 2: residual and orthogonality at machine-precision level
    for the UF-matrix condition numbers."""
    a = make_matrix(96, 96, kappa, seed=int(np.log10(kappa) * 7) + 1)
    u, s, vh = C.polar_svd(a, method="zolo", r=2)
    assert float(C.svd_residual(a, u, s, vh)) < 5e-13
    assert float(C.orthogonality(u)) < 1e-14 * a.shape[0]
    assert float(C.orthogonality(vh.T)) < 1e-14 * a.shape[0]
    s0 = np.linalg.svd(np.asarray(a), compute_uv=False)
    np.testing.assert_allclose(np.asarray(s), s0, atol=1e-13)


def test_qdwh_svd_matches():
    a = make_matrix(80, 80, 1e7, seed=3)
    u, s, vh = C.polar_svd(a, method="qdwh")
    s0 = np.linalg.svd(np.asarray(a), compute_uv=False)
    np.testing.assert_allclose(np.asarray(s), s0, atol=1e-13)
    assert float(C.svd_residual(a, u, s, vh)) < 5e-13


def test_rectangular_both_orientations():
    for (m, n) in [(120, 72), (72, 120)]:
        a = make_matrix(m, n, 50.0, seed=m)
        u, s, vh = C.polar_svd(a, method="zolo", r=2)
        assert u.shape == (m, min(m, n))
        assert vh.shape == (min(m, n), n)
        rec = u * s[None, :] @ vh
        assert float(jnp.linalg.norm(rec - a) / jnp.linalg.norm(a)) < 1e-12


def test_block_jacobi_eigh():
    h = np.asarray(make_matrix(96, 96, 1e3, seed=6))
    h = h + h.T
    w, v = C.padded_block_jacobi_eigh(jnp.asarray(h), nb=16)
    w0 = np.linalg.eigvalsh(h)
    np.testing.assert_allclose(np.asarray(w), w0, atol=1e-12)
    assert float(C.orthogonality(v)) < 1e-14


def test_block_jacobi_eigh_padded_sizes():
    # n = 90 forces both block padding and even-block-count padding
    h = np.asarray(make_matrix(90, 90, 10.0, seed=2))
    h = h + h.T
    w, v = C.padded_block_jacobi_eigh(jnp.asarray(h), nb=16)
    w0 = np.linalg.eigvalsh(h)
    np.testing.assert_allclose(np.asarray(w), w0, atol=1e-11)


@pytest.mark.parametrize("n", [1000, 1024])
def test_block_jacobi_eigh_f32_clustered_spectrum(n):
    """nemeth03's H in f32 (eigenvalues in [1/1.29, 1]), padded (1000)
    and not (1024), at the chip's block size: eigenvalues to the accuracy
    of LAPACK's f32 eigh, which the diagonal the sweeps leave, or an
    unshifted H, or a corner of large padding misses by 10-100x."""
    rng = np.random.default_rng(n)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    w0 = np.geomspace(1.0 / 1.29, 1.0, n)
    h = ((q * w0) @ q.T).astype(np.float32)
    w, v = C.padded_block_jacobi_eigh(jnp.asarray(h), nb=128)
    assert float(np.max(np.abs(np.asarray(w, np.float64) - w0))) < 5e-7
    assert float(C.orthogonality(v.astype(jnp.float64))) < 1e-7


def test_polar_svd_with_jacobi_eig():
    a = make_matrix(64, 64, 100.0, seed=12)
    u, s, vh = C.polar_svd(a, method="zolo", eig_method="jacobi", nb=16)
    s0 = np.linalg.svd(np.asarray(a), compute_uv=False)
    np.testing.assert_allclose(np.asarray(s), s0, atol=1e-12)


def test_jacobi_svd_shape_validation():
    """Misuse raises ValueError with the offending shapes (not a bare
    assert, so it still fails under python -O)."""
    a = make_matrix(32, 24, 10.0, seed=4)
    with pytest.raises(ValueError, match=r"nb=10"):
        C.jacobi_svd(a, nb=10)  # 24 % 10 != 0
    with pytest.raises(ValueError, match=r"even block count"):
        C.jacobi_svd(a, nb=8)  # 24 // 8 == 3 blocks: odd
    with pytest.raises(ValueError, match="one"):
        C.jacobi_svd(jnp.zeros((2, 16, 16)), nb=8)


def test_jacobi_svd_baseline():
    a = make_matrix(100, 64, 50.0, seed=1)
    u, s, vh = C.jacobi_svd(a, nb=16)
    s0 = np.linalg.svd(np.asarray(a), compute_uv=False)
    np.testing.assert_allclose(np.asarray(s), s0, atol=1e-12)
    assert float(C.svd_residual(a, u, s, vh)) < 1e-12
