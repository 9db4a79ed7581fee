"""Collective :class:`~repro.core.zolo.ZoloOps` bundles: the grouped
(Algorithm 3) execution of the one Zolotarev engine in
:mod:`repro.core.zolo` as two composable ops layers.

* :func:`sep_reduce_ops` — the intra-group 2-D distribution of one
  Zolotarev term (the paper's per-group ScaLAPACK/SEP grid, §4).
  Inside a group, the iterate X lives as an (m/sep, n) row block per
  device.  The *only* place the term math needs the whole matrix is the
  Gram product, and CholeskyQR2's communication-avoiding structure
  makes that one collective: each device forms the partial product of
  its row block and a single ``psum`` over the "sep" axis yields the
  global ``X^T X`` (the paper's per-grid PDSYRK + DGSUM2D).  Everything
  else in the engine's term bodies — the n x n Cholesky (replicated per
  device, the standard CholeskyQR trick), the triangular solves and the
  polar update (row-local) — already operates block-row-wise, so the
  *same* iteration code runs distributed by swapping this bundle in:
  no forked math.  It also supplies the collective ``fnorm`` the
  dynamic engine's residual stopping rule needs on row-sharded
  iterates.

* :func:`zolo_term_group_ops` — the inter-group "zolo"-axis layer (the
  paper's TOP context): per-group coefficient selection for the dynamic
  engine (each group evaluates ONE term of the in-graph coefficient
  set, via ``axis_index("zolo")``) and the fused combine-with-DGSUM2D
  ``polar_update`` (each group contributes ``mhat * (xw * X + a * T)``
  with ``xw`` one-hot over groups through
  :mod:`repro.kernels.grouped_combine`, and the ``psum`` over "zolo"
  output IS the next iterate — no replicated post-psum epilogue).

Both wrap any base bundle (the default jnp ops, or the Pallas-kernel
ops of :mod:`repro.core.zolo_pallas`): the base computes the local
work, these layers add the collectives.  A grouped driver composes
``zolo_term_group_ops(sep_reduce_ops(base), ...)`` and hands the result
to the engine's :func:`~repro.core.zolo.run_schedule` /
:func:`~repro.core.zolo.run_dynamic` — the grouped backends are that
composition, not a separate loop.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import zolo as _zolo


@jax.named_scope("psum")
def _psum(x, axis: str):
    """``jax.lax.psum`` under the name scope ``psum``, which every
    collective of the grouped bundles carries."""
    return jax.lax.psum(x, axis)


def sep_reduce_ops(base: Optional[_zolo.ZoloOps] = None,
                   *, axis: str = "sep") -> _zolo.ZoloOps:
    """A ZoloOps bundle whose ``gram`` (and ``fnorm``) all-reduce over
    the row-shard ``axis``.

    Must run inside a ``shard_map`` body over a mesh with that axis; the
    operand of ``gram`` is the local (m/sep, n) row block and the result
    is the *global* (n, n) shifted Gram, identical on every device of
    the group.  A nonzero shift is FUSED into the collective: it is
    one-hotted onto the axis-0 shard's partial product (where the base
    gram — the Pallas kernel on TPU — also applies the shift clamp), so
    the psum output already carries ``+ c I`` and no replicated epilogue
    runs after the reduce.  ``gram_local`` stays the base implementation
    (replicated operands such as the CholeskyQR2 identity block are
    never reduced), and ``polar_update`` is row-local, so the base
    version applies to the block unchanged.  ``fnorm_pair`` fuses the
    dynamic engine's two residual norms into one length-2 psum.
    """
    base = _zolo.DEFAULT_OPS if base is None else base

    def gram(x, c=0.0):
        if isinstance(c, (int, float)) and c == 0.0:
            return _psum(base.gram(x, 0.0), axis)
        # fused sep-psum shifted Gram: one-hot the shift onto the
        # axis-0 shard's partial product so the psum output IS
        # G + c I — no replicated post-psum +cI epilogue serializing
        # after the collective.  (A uniform shift pre-psum would add
        # c * sep to the diagonal; the one-hot adds it exactly once.)
        # The base gram's shift clamp rides along on the shard that
        # carries c.
        c_arr = jnp.asarray(c)
        w = (jax.lax.axis_index(axis) == 0).astype(c_arr.dtype)
        return _psum(base.gram(x, w * c_arr), axis)

    def fnorm(x):
        # global Frobenius norm of the row-sharded iterate: local sum of
        # squares + one psum.  (Over "zolo" the iterate is replicated —
        # every group computes the identical value, no reduction.)
        return jnp.sqrt(_psum(jnp.sum(jnp.abs(x) ** 2), axis))

    def fnorm_pair(a, b):
        # both residual-rule norms in ONE all-reduce: stack the two
        # local sums-of-squares and psum the length-2 vector (two
        # fnorm calls would cost two latency-bound collectives per
        # dynamic iteration)
        loc = jnp.stack([jnp.sum(jnp.abs(a) ** 2),
                         jnp.sum(jnp.abs(b) ** 2)])
        return jnp.sqrt(_psum(loc, axis))

    return base._replace(gram=gram, fnorm=fnorm, fnorm_pair=fnorm_pair)


def zolo_term_group_ops(base: Optional[_zolo.ZoloOps] = None,
                        *, xw, combine_kernel=None,
                        axis: str = "zolo") -> _zolo.ZoloOps:
    """Wrap ``base`` with the inter-group "zolo"-axis behavior: this
    group evaluates ONE Zolotarev term and the combine is the collective.

    ``xw`` is this group's X-carry weight (one-hot over the ``axis`` —
    exactly one group carries X into the combine psum, no 1/r rescale
    rounding); ``combine_kernel`` forces (True) / suppresses (False) the
    Pallas grouped-combine kernel (None: compiled on TPU, jnp oracle
    elsewhere).  Must run inside a ``shard_map`` body over a mesh with
    the ``axis``.

    * ``polar_update`` becomes the fused combine-with-DGSUM2D: the
      group's contribution ``mhat * (xw * X + sum_j a_j T_j)`` (one
      fused pass, :mod:`repro.kernels.grouped_combine`) followed by the
      ``psum`` over ``axis`` whose output IS the next iterate.
    * ``coeff_select`` takes this group's length-1 slice of the
      in-graph (c_odd, a) coefficient arrays via ``axis_index`` — the
      dynamic engine computes all r coefficients on every device and
      selects here.  (Static grouped execution slices by data layout —
      shard_map in_specs — and never calls this; defining it anyway
      keeps one bundle serving both schedule sources.)
    """
    from repro.kernels import ops as _kops

    base = _zolo.DEFAULT_OPS if base is None else base

    def polar_update(x, t, a, mhat):
        y = _kops.grouped_combine(x, t, a, mhat, xw,
                                  use_pallas=combine_kernel)
        return _psum(y, axis)

    def coeff_select(c_odd, a):
        j = jax.lax.axis_index(axis)
        return (jax.lax.dynamic_slice_in_dim(c_odd, j, 1),
                jax.lax.dynamic_slice_in_dim(a, j, 1))

    return base._replace(polar_update=polar_update,
                         coeff_select=coeff_select)
