"""Binary log-loss on labels {0, 1}, as LightGBM defines it
(binary_objective.hpp): with l = +-1 and sigma the ``sigmoid`` parameter,
response = -2 l sigma / (1 + exp(2 l sigma s)), hessian = |r| (2 sigma - |r|).
Plain numpy, float32 as the configuration states."""

from __future__ import annotations

import numpy as np


class Objective:
    def __init__(self, data: dict, params: dict):
        self.sign = np.where(data["y"] > 0, np.float32(1), np.float32(-1))
        self.sigma = np.float32(params["sigmoid"])

    def gradients(self, scores: np.ndarray):
        two = np.float32(2)
        r = -two * self.sign * self.sigma / (
            np.float32(1) + np.exp(two * self.sign * self.sigma * scores))
        a = np.abs(r)
        return r, a * (two * self.sigma - a)

    def loss(self, scores: np.ndarray) -> float:
        """Mean log-loss of sigmoid(2 sigma s), in float64."""
        z = 2.0 * float(self.sigma) * self.sign.astype(np.float64) \
            * scores.astype(np.float64)
        return float(np.mean(np.logaddexp(0.0, -z)))
