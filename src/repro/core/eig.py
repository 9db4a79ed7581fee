"""Symmetric eigensolvers for the H-factor stage (paper Alg. 2 step 2).

The paper uses ELPA (two-stage tridiagonalization).  Per DESIGN.md §3 we
supply the *role* with TPU-native solvers:

* :func:`eigh`         — ``jnp.linalg.eigh`` (XLA's TPU eigh is itself a
                         QDWH-based spectral divide-and-conquer, i.e. the
                         same algorithm family as this paper).
* :func:`block_jacobi_eigh` — two-sided block-Jacobi with a round-robin
                         (tournament) ordering: every round applies b/2
                         *disjoint* block rotations, so rounds vmap/shard
                         cleanly — the matmul-rich, loosely-coupled member
                         of the family (ELPA's scalability role).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp


def eigh(h):
    return jnp.linalg.eigh(h)


def round_robin_schedule(b: int) -> np.ndarray:
    """Tournament schedule: (b-1) rounds x (b/2) disjoint pairs covering all
    unordered pairs of {0..b-1}.  b must be even."""
    if b % 2 != 0:
        raise ValueError(f"tournament schedule needs an even block "
                         f"count; got b={b}")
    players = list(range(b))
    rounds = []
    for _ in range(b - 1):
        pairs = [(players[i], players[b - 1 - i]) for i in range(b // 2)]
        rounds.append([(min(p, q), max(p, q)) for p, q in pairs])
        players = [players[0]] + [players[-1]] + players[1:-1]
    return np.asarray(rounds)  # (b-1, b/2, 2)


def _offdiag_norm(h, nb: int):
    n = h.shape[-1]
    b = n // nb
    hb = h.reshape(b, nb, b, nb)
    mask = 1.0 - jnp.eye(b, dtype=h.dtype)[:, None, :, None]
    return jnp.sqrt(jnp.sum((hb * mask) ** 2))


@functools.partial(jax.jit, static_argnames=("nb", "max_sweeps", "n_real"))
def block_jacobi_eigh(h, nb: int = 32, max_sweeps: int = 12, tol=None,
                      n_real=None):
    """Two-sided block-Jacobi eigendecomposition of symmetric ``h``.

    Returns (w, v) with ``h @ v = v * w`` (ascending), like jnp.linalg.eigh.
    ``n`` must be divisible by ``nb`` and ``n // nb`` must be even
    (:func:`padded_block_jacobi_eigh` pads otherwise).

    ``n_real`` < n marks the trailing coordinates as zero padding, and
    (w, v) are then those of the leading ``n_real`` x ``n_real`` block.
    Each small problem that holds padding gets it as c I, with c just
    above the infinity norm of its real part: the padding eigenvalues
    stay apart from the real ones, and the small eigensolver's
    tolerance, which is relative to the whole small matrix, stays at
    the real part's scale.  The coupling entries of each J are zeroed,
    so the rotations never mix the two.

    The sweeps stop below ``tol`` (relative off-diagonal norm) or when,
    below sqrt(tol), a sweep no longer halves it: the floor the small
    eigensolver's own tolerance sets (XLA's TPU Jacobi stops at 1e-6)
    can lie above ``tol``, and sweeps at the floor only add rounding.
    """
    n = h.shape[-1]
    n_real = n if n_real is None else n_real
    dtype = h.dtype
    if n % nb != 0 or (n // nb) % 2 != 0:
        raise ValueError(
            f"block_jacobi_eigh needs n divisible by nb with an even "
            f"block count; got n={n}, nb={nb} — use "
            f"padded_block_jacobi_eigh for arbitrary n")
    b = n // nb
    sched = jnp.asarray(round_robin_schedule(b))  # (rounds, pairs, 2)
    tol = tol if tol is not None else 30 * float(jnp.finfo(dtype).eps)

    def do_round(carry, pairs):
        h, v = carry
        with jax.named_scope("rotate"):
            p = pairs[:, 0]
            q = pairs[:, 1]
            # gather row indices for each pair: (npairs, 2*nb)
            row_ids = (jnp.concatenate(
                [p[:, None] * nb + jnp.arange(nb)[None, :],
                 q[:, None] * nb + jnp.arange(nb)[None, :]], axis=1))
            rows = h[row_ids.reshape(-1), :].reshape(-1, 2 * nb, n)
            # subproblem S_i = rows_i[:, row_ids_i]
            sub = jnp.take_along_axis(
                rows, row_ids[:, None, :].repeat(2 * nb, axis=1), axis=2)
            sub = 0.5 * (sub + jnp.swapaxes(sub, -1, -2))
            if n_real < n:
                real = row_ids < n_real
                c = 1.01 * jnp.max(jnp.sum(jnp.abs(sub), axis=-1), axis=-1)
                c = c + jnp.finfo(dtype).tiny
                sub = sub + (c[:, None] * (~real).astype(dtype))[
                    :, :, None] * jnp.eye(2 * nb, dtype=dtype)
        with jax.named_scope("small_eigh"):
            _, j = jnp.linalg.eigh(sub)  # (npairs, 2nb, 2nb)
        with jax.named_scope("rotate"):
            if n_real < n:
                j = j * (real[:, :, None] == real[:, None, :]).astype(
                    j.dtype)
            # HIGHEST on the rotations: they are the eigenvectors and the
            # next round's H, and at TPU DEFAULT precision an f32 product
            # runs as one bf16 pass (~2e-3 relative)
            hi = jax.lax.Precision.HIGHEST
            # one Newton-Schulz step, J (3I - J^T J) / 2: XLA's TPU Jacobi
            # returns J with |J^T J - I| up to ~7e-6, and every round that
            # rotates H by a J that far from orthogonal moves its spectrum
            acc = jnp.promote_types(dtype, jnp.float32)
            jtj = jnp.einsum("pki,pkj->pij", j, j, precision=hi,
                             preferred_element_type=acc)
            j = jnp.einsum("pik,pkj->pij", j,
                           1.5 * jnp.eye(2 * nb, dtype=acc) - 0.5 * jtj,
                           precision=hi, preferred_element_type=acc
                           ).astype(dtype)
            # row phase: rows <- J^T rows
            rows_new = jnp.einsum("pij,pin->pjn", j, rows, precision=hi,
                                  preferred_element_type=acc).astype(dtype)
            h = h.at[row_ids.reshape(-1), :].set(rows_new.reshape(-1, n))
            # column phase: cols <- cols J
            cols = h[:, row_ids.reshape(-1)].reshape(n, -1, 2 * nb)
            cols = jnp.swapaxes(cols, 0, 1)  # (npairs, n, 2nb)
            cols_new = jnp.einsum("pni,pij->pnj", cols, j, precision=hi,
                                  preferred_element_type=acc).astype(dtype)
            h = h.at[:, row_ids.reshape(-1)].set(
                jnp.swapaxes(cols_new, 0, 1).reshape(n, -1))
            # accumulate eigenvectors: V <- V J (column op)
            vcols = v[:, row_ids.reshape(-1)].reshape(n, -1, 2 * nb)
            vcols = jnp.swapaxes(vcols, 0, 1)
            vcols_new = jnp.einsum("pni,pij->pnj", vcols, j, precision=hi)
            v = v.at[:, row_ids.reshape(-1)].set(
                jnp.swapaxes(vcols_new, 0, 1).reshape(n, -1))
        return (h, v), None

    def sweep_body(state):
        h, v, s, off, _ = state
        (h, v), _ = jax.lax.scan(do_round, (h, v), sched)
        # runs once per sweep: its device events count the sweeps
        with jax.named_scope("sweep_test"):
            new = _offdiag_norm(h, nb) / jnp.maximum(
                jnp.sqrt(jnp.sum(h * h)), jnp.finfo(dtype).tiny)
        return h, v, s + 1, new, off

    def sweep_cond(state):
        _, _, s, off, prev = state
        # a stall counts only once the sweeps converge quadratically
        # (below sqrt(tol)); the first sweeps can shrink it slowly
        floor = jnp.sqrt(jnp.asarray(tol, dtype))
        stalled = (off < floor) & (off > 0.5 * prev)
        return (s < max_sweeps) & (off > tol) & ~stalled

    v0 = jnp.eye(n, dtype=dtype)
    one = jnp.asarray(1.0, dtype)
    h, v, _, _, _ = jax.lax.while_loop(
        sweep_cond, sweep_body,
        (h, v0, jnp.int32(0), one, jnp.asarray(jnp.inf, dtype)))
    # the real coordinates never mix with the padding: the leading
    # n_real columns of v are the real eigenvectors
    w = jnp.diag(h)[:n_real]
    v = v[:n_real, :n_real]
    order = jnp.argsort(w)
    return w[order], v[:, order]


def padded_block_jacobi_eigh(h, nb: int = 32, max_sweeps: int = 12):
    """block_jacobi_eigh with automatic padding to (even multiple of nb).

    The sweeps run on H - mu I with mu = trace(H) / n, since each round's
    rotations round at the scale of the matrix they rotate.  The
    vectors then take one Newton-Schulz step back towards orthogonality,
    and the eigenvalues are their Rayleigh quotients against that
    shifted H, not the diagonal the sweeps leave: the diagonal carries
    the rounding of every round, while the vectors are accurate enough
    that a quotient's error is second order.  For the spectrum
    geomspace(1, 1/1.29, n) in f32 (CPU, nb = 128) at n = 2048 the max
    eigenvalue error is 5.6e-8 this way against 3.5e-6 from the diagonal
    (LAPACK's f32 eigh: 4.6e-7), and ||I - V^T V||_F / n is 1.5e-8
    against 2.7e-7 before the step."""
    n = h.shape[-1]
    with jax.named_scope("refine"):
        mu = jnp.trace(h) / n
        hs = h - mu * jnp.eye(n, dtype=h.dtype)
    _, v = _padded_block_jacobi_eigh(hs, nb=nb, max_sweeps=max_sweeps)
    with jax.named_scope("refine"):
        hi = jax.lax.Precision.HIGHEST
        # one Newton-Schulz step, V (3I - V^T V) / 2, squares the loss of
        # orthogonality the rounds accumulated in V
        vtv = jnp.matmul(jnp.swapaxes(v, -1, -2), v, precision=hi)
        v = jnp.matmul(v, 1.5 * jnp.eye(n, dtype=v.dtype) - 0.5 * vtv,
                       precision=hi)
        hv = jnp.matmul(hs, v, precision=hi)
        w = jnp.sum(v * hv, axis=0) / jnp.sum(v * v, axis=0)
        order = jnp.argsort(w)
        return w[order] + mu, v[:, order]


def _padded_block_jacobi_eigh(h, nb: int, max_sweeps: int):
    n = h.shape[-1]
    b = -(-n // nb)
    if b % 2:
        b += 1
    npad = b * nb - n
    if npad:
        hp = jnp.pad(h, ((0, npad), (0, npad)))
        return block_jacobi_eigh(hp, nb=nb, max_sweeps=max_sweeps, n_real=n)
    return block_jacobi_eigh(h, nb=nb, max_sweeps=max_sweeps)
