"""Sparse ingest (io/sparse.py): O(nnz) loading + parity with dense path.

Reference behavior being matched: sparse input handling via
src/io/sparse_bin.hpp + parser.cpp LibSVM pairs, with bin finding that
counts elided zeros (bin.cpp:48-85).
"""

import os

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.metadata import Metadata
from lightgbm_tpu.io.sparse import (
    SparseBins,
    _ranges_concat,
    parse_libsvm_csr,
)


def _random_csr(n, f, density, seed=3):
    rng = np.random.RandomState(seed)
    mask = rng.rand(n, f) < density
    dense = np.where(mask, rng.randn(n, f), 0.0)
    rows, cols = np.nonzero(dense)
    row_lens = np.bincount(rows, minlength=n)
    indptr = np.concatenate([[0], np.cumsum(row_lens)]).astype(np.int64)
    return dense, indptr, cols.astype(np.int64), dense[rows, cols]


def test_ranges_concat():
    starts = np.array([2, 10, 7, 30])
    lens = np.array([3, 0, 2, 1])
    np.testing.assert_array_equal(
        _ranges_concat(starts, lens), [2, 3, 4, 7, 8, 30]
    )
    assert len(_ranges_concat(np.array([5]), np.array([0]))) == 0


def test_csr_parity_with_dense_path():
    """from_csr must produce bit-identical bins to from_matrix."""
    dense, indptr, indices, values = _random_csr(300, 25, 0.15)
    y = (dense.sum(axis=1) > 0).astype(np.float32)
    cfg = Config(max_bin=64)
    ds_dense = BinnedDataset.from_matrix(dense, Metadata(label=y), cfg)
    ds_sparse = BinnedDataset.from_csr(
        indptr, indices, values, 25, Metadata(label=y), cfg
    )
    assert ds_sparse.is_sparse  # density 0.15 < 0.2 keeps CSR storage
    np.testing.assert_array_equal(
        ds_sparse.used_feature_map, ds_dense.used_feature_map
    )
    for a, b in zip(ds_sparse.bin_mappers, ds_dense.bin_mappers):
        assert a.num_bin == b.num_bin
        np.testing.assert_array_equal(a.bin_upper_bound, b.bin_upper_bound)
    np.testing.assert_array_equal(ds_sparse.dense_bins(), ds_dense.X_bin)


def test_csr_densifies_when_dense_enough():
    dense, indptr, indices, values = _random_csr(200, 10, 0.5)
    y = np.zeros(200, np.float32)
    ds = BinnedDataset.from_csr(
        indptr, indices, values, 10, Metadata(label=y), Config(max_bin=32)
    )
    assert not ds.is_sparse


def test_sparse_subset_and_binary_cache(tmp_path):
    dense, indptr, indices, values = _random_csr(120, 30, 0.1)
    y = np.arange(120, dtype=np.float32)
    ds = BinnedDataset.from_csr(
        indptr, indices, values, 30, Metadata(label=y), Config(max_bin=16)
    )
    assert ds.is_sparse
    idx = np.array([3, 50, 117, 4])
    sub = ds.subset(idx)
    np.testing.assert_array_equal(sub.dense_bins(), ds.dense_bins()[idx])
    np.testing.assert_array_equal(sub.metadata.label, y[idx])

    p = str(tmp_path / "ds.bin")
    ds.save_binary(p)
    ds2 = BinnedDataset.load_binary(p)
    assert ds2.is_sparse
    np.testing.assert_array_equal(ds2.dense_bins(), ds.dense_bins())
    np.testing.assert_array_equal(ds2.metadata.label, y)


def _write_libsvm(path, dense, y):
    with open(path, "w") as fh:
        for i in range(dense.shape[0]):
            nz = np.nonzero(dense[i])[0]
            pairs = " ".join(f"{j}:{dense[i, j]:.6g}" for j in nz)
            fh.write(f"{y[i]:g} {pairs}\n".rstrip() + "\n")


def test_libsvm_file_parity(tmp_path):
    """from_file on LibSVM (sparse route) == binning the densified data."""
    dense, _, _, _ = _random_csr(150, 12, 0.2, seed=11)
    y = (dense[:, 0] > 0).astype(np.float32)
    p = str(tmp_path / "data.libsvm")
    _write_libsvm(p, dense, y)

    cfg = Config(max_bin=32, is_save_binary_file=False)
    ds = BinnedDataset.from_file(p, cfg)
    # dense reference: parse values back the same way the file stores them
    lab, indptr, indices, values, ncols = parse_libsvm_csr(p)
    full = np.zeros((150, 12))
    rows = np.repeat(np.arange(150), np.diff(indptr))
    full[rows, indices] = values
    ds_ref = BinnedDataset.from_matrix(full, Metadata(label=lab), cfg)
    np.testing.assert_array_equal(ds.dense_bins(), ds_ref.X_bin)
    np.testing.assert_array_equal(ds.metadata.label, y)


def test_libsvm_million_columns_onnz(tmp_path):
    """1M-column LibSVM with ~0.1%-density rows loads in O(nnz) memory:
    the dense f64 matrix would be 2000 x 1M x 8B = 16 GB."""
    rng = np.random.RandomState(0)
    n, f, per_row = 2000, 1_000_000, 10
    p = str(tmp_path / "wide.libsvm")
    with open(p, "w") as fh:
        for i in range(n):
            cols = np.sort(rng.choice(f, size=per_row, replace=False))
            # force the max column index to exist so num_cols == f
            if i == 0:
                cols[-1] = f - 1
            pairs = " ".join(f"{j}:{rng.randn():.4g}" for j in cols)
            fh.write(f"{i % 2} {pairs}\n")

    ds = BinnedDataset.from_file(p, Config(max_bin=255))
    assert ds.num_total_features == f
    assert ds.num_data == n
    assert ds.is_sparse
    # storage is O(nnz), nowhere near n x F_used
    assert ds.X_bin.nnz <= n * per_row
    assert ds.X_bin.nbytes < 50 * n * per_row
    # every stored row decodes; spot-check densified subset round-trip
    sub = ds.subset(np.arange(5))
    assert sub.dense_bins().shape == (5, ds.num_features)


def test_scipy_csr_dataset_stays_sparse():
    scipy_sparse = pytest.importorskip("scipy.sparse")
    from lightgbm_tpu.basic import Dataset

    dense, indptr, indices, values = _random_csr(200, 40, 0.08, seed=5)
    y = (dense.sum(axis=1) > 0).astype(np.float32)
    csr = scipy_sparse.csr_matrix(dense)
    ds = Dataset(csr, label=y, params={"max_bin": 32})
    inner = ds.construct()
    assert inner.is_sparse
    ref = BinnedDataset.from_matrix(
        dense, Metadata(label=y), Config(max_bin=32)
    )
    np.testing.assert_array_equal(inner.dense_bins(), ref.X_bin)

    # validation set aligned through the sparse route
    valid = ds.create_valid(csr[:50], label=y[:50])
    vi = valid.construct()
    np.testing.assert_array_equal(vi.dense_bins(), ref.X_bin[:50])


def test_sparse_training_end_to_end():
    """Booster trains identically from sparse and dense input."""
    scipy_sparse = pytest.importorskip("scipy.sparse")
    import lightgbm_tpu as lgb

    dense, _, _, _ = _random_csr(400, 15, 0.15, seed=9)
    y = (dense @ np.arange(15) > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 32,
              "num_iterations": 5, "verbose": -1, "min_data_in_leaf": 5}
    b_dense = lgb.train(params, lgb.Dataset(dense, label=y))
    b_sparse = lgb.train(
        params, lgb.Dataset(scipy_sparse.csr_matrix(dense), label=y)
    )
    np.testing.assert_allclose(
        b_dense.predict(dense), b_sparse.predict(dense), rtol=1e-6
    )


def test_sparse_predict_chunked_matches_dense():
    """Above the chunking threshold, scipy-sparse prediction densifies
    per row-chunk (peak memory one chunk); results must equal the dense
    path exactly."""
    import numpy as np
    import scipy.sparse as sp
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(0)
    n_tr = 2000
    Xtr = rng.randn(n_tr, 8)
    y = (Xtr[:, 0] + Xtr[:, 1] > 0).astype(np.float64)
    bst = lgb.train({"objective": "binary", "num_leaves": 15, "verbose": -1},
                    lgb.Dataset(Xtr, label=y), num_boost_round=5)

    n = 70_000  # crosses the 65536 chunk threshold
    dense = np.zeros((n, 8))
    mask = rng.rand(n, 8) < 0.1
    dense[mask] = rng.randn(int(mask.sum()))
    csr = sp.csr_matrix(dense)
    p_dense = bst.predict(dense)
    p_sparse = bst.predict(csr)
    np.testing.assert_allclose(p_sparse, p_dense, rtol=1e-6)
    assert p_sparse.shape == (n,)


@pytest.mark.parametrize("learner", ["serial", "data", "feature", "voting"])
def test_csr_table_grows_its_dense_twins_trees(learner):
    """Sparse ingest reaches every leaf-wise learner through dense bins: a
    CSR-ingested table grows, node for node and bit for bit, the trees of
    its dense twin, on one device and over the CPU mesh."""
    import jax

    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective

    dense, _, _, _ = _random_csr(512, 24, 0.1, seed=13)
    y = (dense @ np.arange(24) > 0).astype(np.float32)
    cfg = Config(objective="binary", num_leaves=7, max_bin=16,
                 min_data_in_leaf=5, tree_learner=learner, top_k=4)
    ds_csr = BinnedDataset.from_csr(*_csr_parts(dense), Metadata(label=y),
                                    config=cfg)
    assert ds_csr.is_sparse
    trees = []
    for ds in (ds_csr, BinnedDataset.from_matrix(dense, Metadata(label=y),
                                                 config=cfg)):
        gb = GBDT(cfg, ds, create_objective(cfg, ds.metadata, ds.num_data))
        for _ in range(3):
            gb.train_one_iter()
        trees.append(jax.device_get(gb.models))
    assert [t.num_leaves > 1 for t in trees[0]] == [True] * 3
    for a, b in zip(*trees):
        for field, x, z in zip(a._fields, a, b):
            np.testing.assert_array_equal(x, z, err_msg=field)


def _csr_parts(dense):
    rows, cols = np.nonzero(dense)
    n = dense.shape[0]
    row_lens = np.bincount(rows, minlength=n)
    indptr = np.concatenate([[0], np.cumsum(row_lens)]).astype(np.int64)
    return indptr, cols.astype(np.int64), dense[rows, cols], dense.shape[1]
