"""The blocked triangular solve of the polar iterations against
``lax.linalg.triangular_solve``.

The sizes are not multiples of the block (600: padded, 3 blocks) or are
several whole blocks (768: 3 blocks), so the rolled loop runs more than
one iteration and the padding is exercised; a leading batch axis stands
in for the r Zolotarev terms.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.trisolve import SOLVE_BLOCK, solve_lower


def _lower(n, seed):
    rng = np.random.default_rng(seed)
    l = np.tril(rng.standard_normal((2, n, n))) / np.sqrt(n)
    l[:, np.arange(n), np.arange(n)] = 1.0 + rng.random((2, n))
    return jnp.asarray(l)


@pytest.mark.parametrize("transpose_a", [False, True])
@pytest.mark.parametrize("left_side", [True, False])
@pytest.mark.parametrize("n", [600, 768])
def test_solve_lower_matches_xla(n, left_side, transpose_a):
    assert n > 2 * SOLVE_BLOCK
    l = _lower(n, seed=n)
    k = 37
    rng = np.random.default_rng(1)
    shape = (2, n, k) if left_side else (2, k, n)
    b = jnp.asarray(rng.standard_normal(shape))
    got = solve_lower(l, b, left_side=left_side, transpose_a=transpose_a)
    want = jax.lax.linalg.triangular_solve(
        l, b, left_side=left_side, lower=True, transpose_a=transpose_a)
    assert got.shape == want.shape
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < 1e-12, err
