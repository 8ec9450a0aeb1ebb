"""Pallas sorted-matmul histogram == segment_sum histogram (interpret
mode on CPU; the compiled kernel runs on real TPU only)."""

import numpy as np
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.histogram import histogram_by_leaf
from lightgbm_tpu.ops.pallas_histogram import histogram_by_leaf_sorted


def _problem(n, F, B, L, seed=0):
    rng = np.random.RandomState(seed)
    return (
        jnp.asarray(rng.randint(0, B, size=(F, n)).astype(np.uint8)),
        jnp.asarray(rng.randint(0, L, size=n).astype(np.int32)),
        jnp.asarray(rng.randn(n).astype(np.float32)),
        jnp.asarray(np.abs(rng.randn(n)).astype(np.float32)),
        jnp.asarray((rng.rand(n) > 0.3).astype(np.float32)),
    )


@pytest.mark.parametrize("n,F,B,L,chunk", [
    (5000, 6, 16, 8, 256),
    (1000, 3, 32, 4, 128),      # n not divisible by chunk
    (300, 2, 7, 5, 128),        # B not a lane multiple
])
def test_kernel_matches_segment_sum(n, F, B, L, chunk):
    bins_T, leaf, g, h, m = _problem(n, F, B, L)
    ref = histogram_by_leaf(bins_T, leaf, g, h, m, num_bins=B, num_leaves=L)
    got = histogram_by_leaf_sorted(
        bins_T, leaf, g, h, m, num_bins=B, num_leaves=L,
        chunk=chunk, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_kernel_empty_and_skewed_leaves():
    bins_T, _, g, h, m = _problem(2000, 4, 16, 8)
    for leaf_np in [
        np.zeros(2000),                       # all rows in leaf 0
        np.where(np.arange(2000) < 5, 7, 2),  # tiny leaf + empty leaves
    ]:
        leaf = jnp.asarray(leaf_np.astype(np.int32))
        ref = histogram_by_leaf(bins_T, leaf, g, h, m, num_bins=16, num_leaves=8)
        got = histogram_by_leaf_sorted(
            bins_T, leaf, g, h, m, num_bins=16, num_leaves=8,
            chunk=256, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-4)


def test_depthwise_training_with_matmul_hist():
    """End-to-end: hist_impl=matmul trains the same model as segment."""
    rng = np.random.RandomState(11)
    X = rng.randn(1200, 5)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    preds = {}
    for impl in ("segment", "matmul"):
        bst = lgb.train(
            {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 20,
             "min_sum_hessian_in_leaf": 1.0, "tree_growth": "depthwise",
             "hist_impl": impl, "max_bin": 32, "verbose": 0},
            lgb.Dataset(X, label=y, max_bin=32),
            num_boost_round=3, verbose_eval=False,
        )
        preds[impl] = bst.predict(X)
    np.testing.assert_allclose(preds["matmul"], preds["segment"],
                               rtol=1e-4, atol=1e-5)


def test_data_parallel_sorted_hist():
    """psum over the Pallas kernel on the 8-device mesh matches the
    single-device depthwise tree (review fix: path was unexercised)."""
    import jax.numpy as jnp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.learners.depthwise import grow_tree_depthwise
    from lightgbm_tpu.learners.serial import TreeLearnerParams
    from lightgbm_tpu.parallel import data_mesh, make_data_parallel_grower

    rng = np.random.RandomState(4)
    n, F, B, L = 2048, 4, 16, 15
    bins_T = jnp.asarray(rng.randint(0, B, size=(F, n)).astype(np.uint8))
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.asarray(np.abs(rng.randn(n)).astype(np.float32) + 0.1)
    args = (bins_T, grad, hess, jnp.ones(n, jnp.float32),
            jnp.ones(F, bool), jnp.full(F, B, jnp.int32), jnp.zeros(F, bool))
    params = TreeLearnerParams.from_config(Config(min_data_in_leaf=20,
                                                  min_sum_hessian_in_leaf=1e-3))
    t1, _ = grow_tree_depthwise(*args, params, num_bins=B, max_leaves=L)
    grow = make_data_parallel_grower(
        data_mesh(), num_bins=B, max_leaves=L,
        growth="depthwise", sorted_hist=True,
    )
    t2, _ = grow(*args, params)
    assert int(t1.num_leaves) == int(t2.num_leaves)
    nl = int(t1.num_leaves)
    same = sum(
        int(np.asarray(t1.split_feature)[i]) == int(np.asarray(t2.split_feature)[i])
        and int(np.asarray(t1.threshold_bin)[i]) == int(np.asarray(t2.threshold_bin)[i])
        for i in range(nl - 1)
    )
    assert same >= nl - 2  # psum reduction-order ulps may flip one near-tie


def test_single_leaf_hist_matches_segment():
    """histogram_single_leaf (the leaf-wise per-split kernel) ==
    histogram_feature_major on the same masked rows."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import histogram_feature_major
    from lightgbm_tpu.ops.pallas_histogram import histogram_single_leaf

    rng = np.random.RandomState(11)
    F, cap, B = 5, 700, 37  # odd sizes exercise F/chunk/bin padding
    bins_T = jnp.asarray(rng.randint(0, B, size=(F, cap)).astype(np.uint8))
    grad = jnp.asarray(rng.randn(cap).astype(np.float32))
    hess = jnp.asarray(np.abs(rng.randn(cap)).astype(np.float32))
    mask = jnp.asarray((rng.rand(cap) < 0.7).astype(np.float32))
    a = histogram_single_leaf(bins_T, grad, hess, mask, num_bins=B,
                              interpret=True)
    b = histogram_feature_major(bins_T, grad, hess, mask, num_bins=B)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


def test_leafwise_training_matmul_vs_segment():
    """Leaf-wise trees built with the single-leaf MXU kernel match the
    segment_sum path end-to-end."""
    import lightgbm_tpu as lgb
    import lightgbm_tpu.engine as engine

    rng = np.random.RandomState(12)
    X = rng.randn(3000, 6)
    y = (X[:, 0] - X[:, 1] * X[:, 2] > 0).astype(np.float32)
    preds = {}
    for impl in ("matmul", "segment"):
        bst = engine.train(
            {"objective": "binary", "num_leaves": 15, "verbose": -1,
             "min_data_in_leaf": 20, "hist_impl": impl,
             "tree_growth": "leafwise"},
            lgb.Dataset(X, label=y, max_bin=32),
            num_boost_round=3, verbose_eval=False,
        )
        preds[impl] = bst.predict(X)
    np.testing.assert_allclose(preds["matmul"], preds["segment"],
                               rtol=1e-4, atol=1e-5)


def test_data_parallel_leafwise_matmul_hist():
    """Leaf-wise data-parallel growth with per-shard single-leaf MXU
    histograms (+psum) matches the single-device leaf-wise tree."""
    import jax.numpy as jnp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.learners.serial import TreeLearnerParams, grow_tree
    from lightgbm_tpu.parallel import data_mesh, make_data_parallel_grower

    rng = np.random.RandomState(7)
    n, F, B, L = 2048, 4, 16, 15
    bins_T = jnp.asarray(rng.randint(0, B, size=(F, n)).astype(np.uint8))
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.asarray(np.abs(rng.randn(n)).astype(np.float32) + 0.1)
    args = (bins_T, grad, hess, jnp.ones(n, jnp.float32),
            jnp.ones(F, bool), jnp.full(F, B, jnp.int32), jnp.zeros(F, bool))
    params = TreeLearnerParams.from_config(
        Config(min_data_in_leaf=20, min_sum_hessian_in_leaf=1e-3)
    )
    t1, _ = grow_tree(*args, params, num_bins=B, max_leaves=L)
    grow = make_data_parallel_grower(
        data_mesh(), num_bins=B, max_leaves=L,
        growth="leafwise", sorted_hist=True,
    )
    t2, _ = grow(*args, params)
    assert int(t1.num_leaves) == int(t2.num_leaves)
    nl = int(t1.num_leaves)
    same = sum(
        int(np.asarray(t1.split_feature)[i]) == int(np.asarray(t2.split_feature)[i])
        and int(np.asarray(t1.threshold_bin)[i]) == int(np.asarray(t2.threshold_bin)[i])
        for i in range(nl - 1)
    )
    assert same >= nl - 2  # psum reduction-order ulps may flip one near-tie


def _cell_like_table(n, F, seed):
    """Bins as the benchmark's cells have them: most columns of 2-3000
    distinct values binned to histograms of unequal, often short,
    length, the rest full 255-bin columns; hessians like a second
    binary tree's; a bagging mask."""
    rng = np.random.RandomState(seed)
    bins = np.zeros((F, n), np.uint8)
    for f in range(F):
        if f % 3 == 2:
            bins[f] = rng.randint(0, 255, n)
        else:
            distinct = int(rng.choice([2, 3, 7, 40, 300, 3000]))
            values = rng.zipf(1.3, n) % distinct
            edges = np.unique(np.quantile(values, np.linspace(0, 1, 255)))
            bins[f] = np.searchsorted(edges, values).clip(0, 254)
    p = 1.0 / (1.0 + np.exp(-rng.randn(n)))
    g = (p - (rng.rand(n) < 0.5)).astype(np.float32)
    h = (p * (1.0 - p)).astype(np.float32)
    m = (rng.rand(n) < 0.8).astype(np.float32)
    return bins, g, h, m


@pytest.mark.parametrize("chunk", [512, None])  # None: the default
@pytest.mark.parametrize("n,F", [
    (9_011, 81),    # malware-81's width (padded to 88), a ragged last chunk
    (8_707, 100),   # synthetic-100's (padded to 104)
    (300, 5),       # less than one chunk, less than one feature group
])
def test_single_leaf_raw_matches_float64(n, F, chunk):
    """histogram_single_leaf_raw (the root of every tree on the chip)
    against a float64 numpy histogram, at the widths and kinds of column
    the cells have: every bin to float32's own rounding of ITS sum of
    magnitudes (gradients cancel), counts exactly, padding rows zero."""
    from lightgbm_tpu.ops.pallas_histogram import (
        FGROUP, histogram_single_leaf_raw)

    B = 255
    bins, g, h, m = _cell_like_table(n, F, seed=n)
    kw = {} if chunk is None else {"chunk": chunk}
    got = np.asarray(histogram_single_leaf_raw(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(m),
        num_bins=B, interpret=True, **kw))
    Fp = -(-F // FGROUP) * FGROUP
    assert got.shape == (Fp, 4, 256)
    live = m > 0
    want = np.zeros((F, 3, 256))
    size = np.zeros((F, 3, 256))
    for f in range(F):
        for s, v in enumerate((g, h, m)):
            v = v.astype(np.float64)[live]
            want[f, s] = np.bincount(bins[f, live], v, 256)
            size[f, s] = np.bincount(bins[f, live], np.abs(v), 256)
    assert (np.abs(got[:F, :3] - want) <= 3e-7 * size).all(), (
        np.abs(got[:F, :3] - want) / np.maximum(size, 1e-30)).max()
    np.testing.assert_array_equal(got[:F, 2], want[:, 2])
    assert not got[:, 3].any() and not got[:F, :, 255].any()
    # padded feature rows read bin 0 on every row (the split step's
    # padded rows match them: ops/record.py _hist_tile_body)
    np.testing.assert_array_equal(
        got[F:, :3, 1:], np.zeros((Fp - F, 3, 255), np.float32))
    np.testing.assert_array_equal(got[F:, 2, 0], np.full(Fp - F, live.sum()))
