"""Bounded histogram pool (Config.histogram_pool_size): LRU eviction +
parent recompute must reproduce the unpooled learner, and a
large-feature-count shape must train inside a stated HBM budget — the
reference's HistogramPool semantics (serial_tree_learner.cpp:25-37,
feature_histogram.hpp:337-481)."""

import pytest
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu.config import Config
from lightgbm_tpu.learners.serial import TreeLearnerParams, grow_tree


def _problem(n, F, B, seed=0):
    rng = np.random.RandomState(seed)
    bins_T = rng.randint(0, B, size=(F, n)).astype(np.uint8)
    # gradients/hessians in {±1, ±0.5, 1} are exactly representable and
    # sum exactly in f32, so a RECOMPUTED parent histogram is bit-equal
    # to the resident one and pooled trees must match exactly
    grad = rng.choice([-1.0, -0.5, 0.5, 1.0], size=n).astype(np.float32)
    hess = np.ones(n, np.float32)
    return (
        jnp.asarray(bins_T), jnp.asarray(grad), jnp.asarray(hess),
        jnp.ones(n, jnp.float32), jnp.ones(F, bool),
        jnp.full(F, B, jnp.int32), jnp.zeros(F, bool),
    )


def _params():
    return TreeLearnerParams.from_config(
        Config(min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3)
    )


def test_pooled_matches_unpooled_exactly():
    n, F, B, L = 3000, 10, 32, 31
    args = _problem(n, F, B, seed=11)
    params = _params()
    t0, leaf0 = grow_tree(*args, params, num_bins=B, max_leaves=L)
    for pool in (4, 2):
        t1, leaf1 = grow_tree(
            *args, params, num_bins=B, max_leaves=L, hist_pool=pool
        )
        assert int(t0.num_leaves) == int(t1.num_leaves)
        nl = int(t0.num_leaves)
        for f in ("split_feature", "threshold_bin", "leaf_count"):
            np.testing.assert_array_equal(
                np.asarray(getattr(t0, f))[:nl],
                np.asarray(getattr(t1, f))[:nl],
                err_msg=f"{f} pool={pool}",
            )
        np.testing.assert_allclose(
            np.asarray(t0.leaf_value)[:nl], np.asarray(t1.leaf_value)[:nl],
            rtol=1e-6,
        )
        np.testing.assert_array_equal(np.asarray(leaf0), np.asarray(leaf1))


@pytest.mark.slow  # tier-1 time budget (ROADMAP verify runs -m 'not slow'; see pyproject)
def test_large_feature_count_trains_in_budget():
    """F=2000, B=256, L=255: unpooled histograms would need
    255*2000*256*3*4 B ~= 1.5 GB; a 64 MB histogram_pool_size caps the
    buffer at floor(64MB / 6MB) = 10 slots (~60 MB) and the tree still
    trains."""
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.io.metadata import Metadata
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective

    n, F = 4096, 2000
    rng = np.random.RandomState(5)
    X = rng.randn(n, F).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
    cfg = Config(
        objective="binary", num_leaves=255, max_bin=256,
        min_data_in_leaf=5, histogram_pool_size=64.0,
        tree_learner="serial",
    )
    ds = BinnedDataset.from_matrix(X, Metadata(label=y), config=cfg)
    booster = GBDT(cfg, ds, create_objective(cfg, ds.metadata, n))
    assert booster._hist_pool_slots() == 10
    booster.train_one_iter()
    tree = booster.models[-1]
    # growth far past the 10 resident slots proves eviction + recompute
    assert int(tree.num_leaves) > 50
    assert np.isfinite(np.asarray(booster._scores)).all()


def test_pooled_data_parallel_matches_unpooled():
    """The LRU pool composes with the reduce-scatter data-parallel
    learner: per-device slots hold [Fs, B, 3] shards and the recompute
    branch runs the same psum_scatter as a child histogram."""
    import jax

    from lightgbm_tpu.parallel import data_mesh, make_data_parallel_grower

    assert len(jax.devices()) == 8
    n, F, B, L = 3000, 10, 32, 31
    args = _problem(n, F, B, seed=12)
    params = _params()
    mesh = data_mesh()
    g0 = make_data_parallel_grower(mesh, num_bins=B, max_leaves=L)
    g1 = make_data_parallel_grower(mesh, num_bins=B, max_leaves=L,
                                   hist_pool=4)
    t0, leaf0 = g0(*args, params)
    t1, leaf1 = g1(*args, params)
    assert int(t0.num_leaves) == int(t1.num_leaves)
    nl = int(t0.num_leaves)
    for f in ("split_feature", "threshold_bin", "leaf_count"):
        np.testing.assert_array_equal(
            np.asarray(getattr(t0, f))[:nl], np.asarray(getattr(t1, f))[:nl],
            err_msg=f,
        )
    np.testing.assert_array_equal(np.asarray(leaf0), np.asarray(leaf1))
