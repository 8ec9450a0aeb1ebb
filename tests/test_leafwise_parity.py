"""Accuracy parity against the reference CLI at bench-style scale.

The pinned AUC below was produced by the reference C++ binary (built
from /root/reference) on the identical synthetic data and parameters:

    bench.make_data(50_000) -> /tmp CSV ->
    lightgbm task=train objective=binary num_trees=30 num_leaves=31
             max_bin=255 learning_rate=0.1 min_data_in_leaf=100
    train AUC computed from its saved model's raw scores: 0.88901
    (reference run 2026-07, see BASELINE.md)

Leaf-wise growth, the reference's, must track it to |dAUC| <= 0.002.
"""

import numpy as np
import pytest

import bench
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.metadata import Metadata
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objectives import create_objective

REF_AUC = 0.88901  # reference CLI, 50k rows / 30 trees / 31 leaves
ROWS, TREES, LEAVES = 50_000, 30, 31


@pytest.mark.slow  # tier-1 time budget (ROADMAP verify runs -m 'not slow'; see pyproject)
def test_leafwise_auc_matches_reference():
    X, y = bench.make_data(ROWS)
    cfg = Config(objective="binary", num_leaves=LEAVES, max_bin=255,
                 learning_rate=0.1, min_data_in_leaf=100, metric=["auc"])
    ds = BinnedDataset.from_matrix(
        X, Metadata(label=y.astype(np.float32)), config=cfg
    )
    booster = GBDT(cfg, ds, create_objective(cfg, ds.metadata, ds.num_data))
    for _ in range(TREES):
        booster.train_one_iter()
    auc = booster.eval_at(0)["auc"]
    assert abs(auc - REF_AUC) <= 0.002, f"leafwise AUC {auc:.5f} vs {REF_AUC}"
