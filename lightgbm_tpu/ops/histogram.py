"""Histogram construction — the framework's hottest kernel.

TPU-native replacement for the reference's per-feature gather-accumulate
loops (DenseBin::ConstructHistogram, src/io/dense_bin.hpp:39-104, and the
ordered sparse variant).  Instead of pointer-chasing over row indices, we
build `hist[F, B, 3]` (sum_grad, sum_hess, count — bin.h:18-28) for ALL
features in one vectorized scatter-add, with row masking standing in for
the reference's leaf-index partitions (DataPartition).

Two implementations:
* ``histogram_feature_major`` — `jax.ops.segment_sum` over a [F, n]
  feature-major bin matrix (vmapped scatter).  Works everywhere.
* a Pallas VMEM-accumulation kernel (ops/pallas_histogram.py) is selected
  automatically for large inputs on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..obs.device_time import phase_scope


@functools.partial(jax.jit, static_argnames=("num_bins",))
@phase_scope("histogram")
def histogram_feature_major(
    bins_T: jax.Array,  # [F, n] integer bins, feature-major
    grad: jax.Array,  # [n]
    hess: jax.Array,  # [n]
    mask: jax.Array,  # [n] 0/1 row mask (bagging x leaf membership)
    num_bins: int,
) -> jax.Array:
    """Returns hist[F, num_bins, 3] with (sum_grad, sum_hess, count)."""
    gm = grad * mask
    hm = hess * mask
    stats = jnp.stack([gm, hm, mask], axis=-1)  # [n, 3]

    def per_feature(b_row):
        return jax.ops.segment_sum(stats, b_row, num_segments=num_bins)

    return jax.vmap(per_feature)(bins_T.astype(jnp.int32))


def select_single_hist_fn(num_bins: int, use_pallas: bool):
    """ONE place choosing the per-row-set histogram implementation
    (signature: bins_T, grad, hess, mask -> [F, B, 3]): the single-leaf
    MXU kernel when requested, segment_sum otherwise.  Shared by the
    serial learner wiring and every parallel maker."""
    if use_pallas:
        from .pallas_histogram import make_single_hist_fn

        return make_single_hist_fn(num_bins)
    return functools.partial(histogram_feature_major, num_bins=num_bins)
