"""The fused grower (learners/fused.py: raw [Fp, 4, Bp] histograms,
the one-launch split step and the placement, all in interpret mode on
the CPU) must grow the same trees as the canonical grower
(learners/serial.py, [F, B, 3])."""

import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.learners import fused
from lightgbm_tpu.learners.serial import grow_tree, TreeLearnerParams


def params(min_data=1, min_hess=0.0, l1=0.0, l2=0.0, min_gain=0.0,
           max_depth=-1):
    return TreeLearnerParams(
        jnp.float32(min_data), jnp.float32(min_hess), jnp.float32(l1),
        jnp.float32(l2), jnp.float32(min_gain), jnp.int32(max_depth))


def _grow(bins, grad, hess, num_bins, raw, max_leaves=16, bag=None,
          is_cat=None, **kw):
    """``raw``: the fused grower; else the canonical one."""
    n, F = bins.shape
    return (fused.grow_tree if raw else grow_tree)(
        jnp.asarray(bins.T.astype(np.uint8)),
        jnp.asarray(grad, jnp.float32),
        jnp.asarray(hess, jnp.float32),
        jnp.ones(n, jnp.float32) if bag is None else jnp.asarray(
            bag, jnp.float32),
        jnp.ones(F, bool),
        jnp.full(F, num_bins, jnp.int32),
        jnp.zeros(F, bool) if is_cat is None else jnp.asarray(is_cat, bool),
        params(**kw),
        num_bins=num_bins,
        max_leaves=max_leaves,
    )


def _mk(n=4000, F=7, num_bins=23, seed=0):
    """Integer-valued grad/hess: histogram partial sums are then exact
    in f32 under ANY accumulation order, so the fused grower (MXU
    triangular-dot suffix sums) and the canonical path (sequential
    reverse cumsum) compute bitwise-identical gains and must grow
    IDENTICAL trees — no tolerance needed, no near-tie flakiness."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, num_bins, (n, F))
    grad = rng.randint(-8, 9, n).astype(np.float32)
    hess = rng.randint(1, 5, n).astype(np.float32)
    return bins, grad, hess


@pytest.mark.parametrize("seed", [0, 3])
def test_opt_matches_canonical(seed):
    bins, grad, hess = _mk(seed=seed)
    t0, l0 = _grow(bins, grad, hess, 23, raw=False)
    t1, l1 = _grow(bins, grad, hess, 23, raw=True)
    assert int(t0.num_leaves) == int(t1.num_leaves) > 4
    np.testing.assert_array_equal(
        np.asarray(t0.split_feature), np.asarray(t1.split_feature))
    np.testing.assert_array_equal(
        np.asarray(t0.threshold_bin), np.asarray(t1.threshold_bin))
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))
    np.testing.assert_allclose(
        np.asarray(t0.leaf_value), np.asarray(t1.leaf_value),
        rtol=2e-5, atol=2e-5)


def test_opt_with_bagging_and_categorical():
    bins, grad, hess = _mk(seed=1)
    rng = np.random.RandomState(7)
    bag = (rng.rand(len(grad)) < 0.7).astype(np.float32)
    is_cat = np.zeros(bins.shape[1], bool)
    is_cat[2] = True
    t0, l0 = _grow(bins, grad, hess, 23, raw=False, bag=bag, is_cat=is_cat,
                   min_data=5)
    t1, l1 = _grow(bins, grad, hess, 23, raw=True, bag=bag, is_cat=is_cat,
                   min_data=5)
    np.testing.assert_array_equal(
        np.asarray(t0.split_feature), np.asarray(t1.split_feature))
    np.testing.assert_array_equal(
        np.asarray(t0.threshold_bin), np.asarray(t1.threshold_bin))
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))


def test_opt_u16_bins_and_feature_mask():
    """max_bin > 256 stores u16 bins (2 per record word, k=2): the
    packed-record path must match the canonical path there too, and
    under feature_fraction masking."""
    rng = np.random.RandomState(5)
    n, F, num_bins = 3000, 5, 300  # > 256 -> uint16 bins
    bins = rng.randint(0, num_bins, (n, F))
    grad = rng.randint(-8, 9, n).astype(np.float32)
    hess = rng.randint(1, 5, n).astype(np.float32)
    fmask = np.array([True, False, True, True, False])

    def grow(raw):
        return (fused.grow_tree if raw else grow_tree)(
            jnp.asarray(bins.T.astype(np.uint16)),
            jnp.asarray(grad), jnp.asarray(hess),
            jnp.ones(n, jnp.float32),
            jnp.asarray(fmask),
            jnp.full(F, num_bins, jnp.int32),
            jnp.zeros(F, bool),
            params(min_data=3),
            num_bins=num_bins,
            max_leaves=16,
        )

    t0, l0 = grow(False)
    t1, l1 = grow(True)
    assert int(t0.num_leaves) == int(t1.num_leaves) > 4
    np.testing.assert_array_equal(
        np.asarray(t0.split_feature), np.asarray(t1.split_feature))
    np.testing.assert_array_equal(
        np.asarray(t0.threshold_bin), np.asarray(t1.threshold_bin))
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))
    # masked features never appear as split features
    used = np.asarray(t1.split_feature)
    assert not np.isin(used[used >= 0], [1, 4]).any()
