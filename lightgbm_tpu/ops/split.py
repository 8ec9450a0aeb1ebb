"""Vectorized best-split search over feature histograms.

Replaces the reference's per-feature threshold scans
(FeatureHistogram::FindBestThresholdForNumerical,
src/treelearner/feature_histogram.hpp:116-181, and
FindBestThresholdForCategorical, feature_histogram.hpp:187-246) with one
masked reduction over the whole [F, B] candidate grid:

* numerical: right-side sums via reverse cumulative sums over the bin
  axis (the reference's accumulation order, including the kEpsilon seed
  on the right hessian); left-side gradient and hessian via forward
  cumulative sums, where the reference takes ``leaf totals - right`` in
  float64: in float32 that subtraction hands a node's absolute rounding
  to a small left child.  Counts are exact and stay ``total - right``.
* categorical: one-vs-rest — "left" is the single bin == threshold,
  any bin but the column's last, the others' bin (io/binner.py: values
  that are no kept category, which go right in training as ``x == c``
  sends them at prediction).
* gain/leaf-output formulas with L1/L2 regularization mirror
  GetLeafSplitGain / CalculateSplittedLeafOutput
  (feature_histogram.hpp:290-313).
* determinism: the reference scans thresholds HIGH->LOW with strict
  improvement (feature_histogram.hpp:129,154), so equal-gain ties keep
  the LARGEST threshold within a feature; across features the smaller
  feature index wins (SplitInfo::operator>, split_info.hpp:98-103).  We
  reproduce this by argmax-ing over (feature asc, bin desc) order.
  Matters for raw-space routing when bins between tied thresholds are
  empty — verified against the reference binary on binary.train.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..obs.device_time import phase_scope

K_EPSILON = 1e-15
K_MIN_SCORE = -jnp.inf


class SplitResult(NamedTuple):
    """Scalar split decision for one leaf (SplitInfo, split_info.hpp:17-44)."""

    gain: jax.Array  # improvement over the un-split leaf (minus gain_shift)
    feature: jax.Array  # inner feature index (int32), -1 if no split
    threshold: jax.Array  # bin threshold (int32); left is bin <= t (== for cat)
    left_sum_grad: jax.Array
    left_sum_hess: jax.Array
    left_count: jax.Array
    right_sum_grad: jax.Array
    right_sum_hess: jax.Array
    right_count: jax.Array
    left_output: jax.Array
    right_output: jax.Array


def _leaf_split_gain(sum_grad, sum_hess, l1, l2):
    """GetLeafSplitGain (feature_histogram.hpp:290-298)."""
    reg = jnp.maximum(jnp.abs(sum_grad) - l1, 0.0)
    return reg * reg / (sum_hess + l2)


def _leaf_output(sum_grad, sum_hess, l1, l2):
    """CalculateSplittedLeafOutput (feature_histogram.hpp:306-313)."""
    reg = jnp.maximum(jnp.abs(sum_grad) - l1, 0.0)
    return -jnp.sign(sum_grad) * reg / (sum_hess + l2)


@functools.partial(jax.jit, static_argnames=())
@phase_scope("split-search")
def find_best_split(
    hist: jax.Array,  # [F, B, 3] (sum_grad, sum_hess, count) for one leaf
    sum_grad: jax.Array,  # scalar leaf totals (bookkept, not re-summed)
    sum_hess: jax.Array,
    num_data: jax.Array,  # scalar bagged row count in leaf
    feature_mask: jax.Array,  # [F] bool: usable this tree (feature_fraction)
    num_bins_per_feature: jax.Array,  # [F] int32
    is_categorical: jax.Array,  # [F] bool
    min_data_in_leaf: jax.Array,
    min_sum_hessian_in_leaf: jax.Array,
    lambda_l1: jax.Array,
    lambda_l2: jax.Array,
    min_gain_to_split: jax.Array,
    can_split: jax.Array,  # scalar bool (depth / leaf-size gating)
) -> SplitResult:
    F, B, _ = hist.shape
    dt = hist.dtype
    bins = jnp.arange(B, dtype=jnp.int32)

    # The body is written to compile to FEW LARGE ops rather than many
    # small ones: one suffix cumsum over the whole [F, B, 3] tensor (all
    # three stats at once), stat-keeping wheres on [F, B, 3], and ONE
    # dynamic-slice extracting all six winner stats.  The round-3 TPU
    # profile (tools/profile_split.py) showed the previous per-stat
    # formulation spending ~1.6 ms/split on ~60 tiny-op fusions — 4x the
    # histogram kernel itself.  Math, dtype and tie-break order are
    # unchanged bit-for-bit.

    # ---- right-side sums for numerical threshold t: bins > t
    # suffix[t] = sum_{b >= t+1} hist[b]; kEpsilon seeds the right
    # hessian (feature_histogram.hpp:123)
    suf = jnp.cumsum(hist[:, ::-1, :], axis=1)[:, ::-1, :]
    tail = jnp.concatenate([suf[:, 1:], jnp.zeros((F, 1, 3), dt)], axis=1)
    tail = tail + jnp.asarray([0.0, K_EPSILON, 0.0], dt)

    tot = jnp.stack([
        jnp.asarray(sum_grad, dt),
        jnp.asarray(sum_hess, dt),
        jnp.asarray(num_data, dt),
    ])  # [3]

    # ---- categorical one-vs-rest: "left" is the single bin t
    is_cat3 = is_categorical[:, None, None]
    # a numerical left side's gradient and hessian are the histogram's
    # own prefix, not ``tot - tail``: a small left child under a large
    # node would otherwise keep the absolute rounding of the node's
    # totals, and of every ancestor's down a chain of left children
    # (PERF.md, PR 28).  The count is exact either way and stays
    # ``tot - tail`` (one prefix fewer in the kernels, which mirror this)
    head = jnp.concatenate(
        [jnp.cumsum(hist[..., :2], axis=1), (tot - tail)[..., 2:]], axis=-1)
    left = jnp.where(is_cat3, hist, head)  # [F, B, 3]
    right = jnp.where(is_cat3, tot - hist, tail)

    left_h, left_c = left[..., 1], left[..., 2]
    right_h, right_c = right[..., 1], right[..., 2]

    # ---- validity (feature_histogram.hpp:133-142, 199-208)
    # a column's last bin is never a candidate: no rows lie right of a
    # numerical column's, and a categorical column's is the others' bin
    nb = num_bins_per_feature[:, None]
    in_range = bins[None, :] < nb - 1
    valid = (
        in_range
        & feature_mask[:, None]
        & (left_c >= min_data_in_leaf)
        & (right_c >= min_data_in_leaf)
        & (left_h >= min_sum_hessian_in_leaf)
        & (right_h >= min_sum_hessian_in_leaf)
    )

    gain_shift = _leaf_split_gain(sum_grad, sum_hess, lambda_l1, lambda_l2)
    min_gain_shift = gain_shift + min_gain_to_split
    gains = _leaf_split_gain(
        left[..., 0], left_h, lambda_l1, lambda_l2
    ) + _leaf_split_gain(right[..., 0], right_h, lambda_l1, lambda_l2)
    valid = valid & (gains >= min_gain_shift) & can_split
    gains = jnp.where(valid, gains, K_MIN_SCORE)

    # argmax over (feature asc, bin desc): reverse the bin axis so the
    # first maximum is the smallest feature with the LARGEST threshold
    flat = gains[:, ::-1].reshape(-1)
    best = jnp.argmax(flat)
    best_gain_raw = flat[best]
    feat = (best // B).astype(jnp.int32)
    thr = (B - 1 - best % B).astype(jnp.int32)
    splittable = best_gain_raw > K_MIN_SCORE

    # all six winner stats in one dynamic-slice of the stacked tensor
    lr = jnp.stack([left, right])  # [2, F, B, 3]
    pick = jax.lax.dynamic_slice(
        lr, (jnp.int32(0), feat, thr, jnp.int32(0)), (2, 1, 1, 3)
    ).reshape(2, 3)
    lg, lh, lc = pick[0, 0], pick[0, 1], pick[0, 2]
    rg, rh, rc = pick[1, 0], pick[1, 1], pick[1, 2]
    return SplitResult(
        gain=jnp.where(splittable, best_gain_raw - gain_shift, K_MIN_SCORE),
        feature=jnp.where(splittable, feat, -1),
        threshold=jnp.where(splittable, thr, 0),
        left_sum_grad=lg,
        left_sum_hess=lh,
        left_count=lc,
        right_sum_grad=rg,
        right_sum_hess=rh,
        right_count=rc,
        left_output=_leaf_output(lg, lh, lambda_l1, lambda_l2),
        right_output=_leaf_output(rg, rh, lambda_l1, lambda_l2),
    )


# vectorized over leaves (the canonical grower's two-child search, the
# batched forest, analysis/kernel_parity.py)
find_best_split_leaves = jax.vmap(
    find_best_split,
    in_axes=(0, 0, 0, 0, None, None, None, None, None, None, None, None, 0),
)
