"""L2 regression as LightGBM defines it (regression_objective.hpp):
gradient s - y, hessian 1; the loss is the mean squared error.  Plain
numpy, float32 as the configuration states."""

from __future__ import annotations

import numpy as np


class Objective:
    def __init__(self, data: dict, params: dict):
        self.y = np.asarray(data["y"], np.float32)

    def gradients(self, scores: np.ndarray):
        return scores - self.y, np.ones_like(scores)

    def loss(self, scores: np.ndarray) -> float:
        """Mean squared error, in float64."""
        return float(np.mean(
            (scores.astype(np.float64) - self.y.astype(np.float64)) ** 2))
