"""DART: Dropouts meet Multiple Additive Regression Trees.

Re-expresses the reference DART (src/boosting/dart.hpp:17-196): per
iteration a random subset of past trees is dropped from the training
score before gradients are computed, the new tree is trained with
shrinkage lr/(1+k) (or lr/(lr+k) in xgboost_dart_mode), and the dropped
trees are renormalized to k/(k+1) (resp. k/(k+lr)) of their weight —
the exact Shrinkage(-1) / Shrinkage(1/(k+1)) / Shrinkage(-k) score
algebra of dart.hpp:144-183 collapsed into direct array updates.
"""

from __future__ import annotations

from typing import List

import numpy as np

from ..config import Config
from .gbdt import GBDT
from .tree import predict_binned


class DART(GBDT):
    name = "dart"

    def __init__(self, config: Config, train_set=None, objective=None):
        super().__init__(config, train_set, objective)
        self._drop_rng = np.random.RandomState(config.drop_seed)
        self.tree_weight: List[float] = []
        self.sum_weight = 0.0

    def _select_drops(self) -> List[int]:
        """DroppingTrees (dart.hpp:89-133)."""
        cfg = self.config
        if self._drop_rng.rand() < cfg.skip_drop:
            return []
        drop_rate = cfg.drop_rate
        drops = []
        if not cfg.uniform_drop:
            if self.sum_weight <= 0:
                return []
            inv_avg = len(self.tree_weight) / self.sum_weight
            if cfg.max_drop > 0:
                drop_rate = min(drop_rate, cfg.max_drop * inv_avg / self.sum_weight)
            for i in range(self.iter_):
                if self._drop_rng.rand() < drop_rate * self.tree_weight[i] * inv_avg:
                    drops.append(i)
        else:
            if cfg.max_drop > 0 and self.iter_ > 0:
                drop_rate = min(drop_rate, cfg.max_drop / float(self.iter_))
            for i in range(self.iter_):
                if self._drop_rng.rand() < drop_rate:
                    drops.append(i)
        return drops

    def train_one_iter(self, grad=None, hess=None) -> bool:
        cfg = self.config
        K = self.num_class
        drops = self._select_drops()
        k = float(len(drops))

        # subtract dropped trees from the training score (dart.hpp:117-123)
        for i in drops:
            for c in range(K):
                tree = self.models[i * K + c]
                self._scores = self._scores.at[c].add(
                    -predict_binned(tree, self._bins_T.T, self._missing_bin)
                )

        # shrinkage for the new tree (dart.hpp:124-132)
        if not cfg.xgboost_dart_mode:
            shrinkage = cfg.learning_rate / (1.0 + k)
        else:
            shrinkage = (
                cfg.learning_rate
                if not drops
                else cfg.learning_rate / (cfg.learning_rate + k)
            )
        saved_lr, self.learning_rate = self.learning_rate, shrinkage
        try:
            stop = super().train_one_iter(grad, hess)
        finally:
            self.learning_rate = saved_lr

        # renormalize dropped trees (Normalize, dart.hpp:144-183)
        # kept fraction of each dropped tree's weight; valid scores (which
        # still hold the full tree) are adjusted by (keep - 1)
        if not cfg.xgboost_dart_mode:
            keep = k / (k + 1.0)
        else:
            keep = k / (k + cfg.learning_rate)
        for i in drops:
            for c in range(K):
                idx = i * K + c
                tree = self.models[idx]
                delta = predict_binned(tree, self._bins_T.T,
                                       self._missing_bin)
                # train score gets the renormalized tree back
                self._scores = self._scores.at[c].add(keep * delta)
                # valid scores still hold the full tree; adjust by (keep-1)
                for vi in range(len(self.valid_sets)):
                    self._valid_scores[vi] = self._valid_scores[vi].at[c].add(
                        (keep - 1.0) * predict_binned(
                            tree, self._valid_bins[vi], self._missing_bin)
                    )
                self.models[idx] = tree.shrink(keep)
            if not cfg.uniform_drop and self.tree_weight:
                denom = (k + 1.0) if not cfg.xgboost_dart_mode else (k + cfg.learning_rate)
                self.sum_weight -= self.tree_weight[i] * (1.0 / denom)
                self.tree_weight[i] *= keep
        if not cfg.uniform_drop:
            self.tree_weight.append(shrinkage)
            self.sum_weight += shrinkage
        return stop


def create_boosting(config: Config, train_set=None, objective=None) -> GBDT:
    """Boosting factory (src/boosting/boosting.cpp:30-66)."""
    if config.boosting_type == "dart":
        return DART(config, train_set, objective)
    return GBDT(config, train_set, objective)
