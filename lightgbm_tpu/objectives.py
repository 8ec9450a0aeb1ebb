"""Objective functions: per-row gradients/hessians, jitted.

Each objective re-expresses its reference counterpart
(src/objective/*.hpp) as a vectorized function
``(scores, label, weights) -> (grad, hess)`` suitable for jit/shard_map.
Scores are class-major ``[num_class, n]`` for multiclass (matching the
reference's ``curr_class * num_data_`` offsets, gbdt.cpp:226-244) and
``[n]`` otherwise.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .obs.device_time import phase_scope


class ObjectiveFunction:
    """Base: mirrors ObjectiveFunction (objective_function.h:13-49)."""

    name = "none"
    num_class = 1
    # sigmoid parameter used by prediction transform (-1 = no transform)
    sigmoid = -1.0

    def init(self, metadata, num_data: int) -> None:
        self.label = jnp.asarray(metadata.label, jnp.float32)
        self.weights = (
            None
            if metadata.weights is None
            else jnp.asarray(metadata.weights, jnp.float32)
        )
        self.num_data = num_data

    def get_gradients(self, scores: jax.Array):
        raise NotImplementedError


class RegressionL2(ObjectiveFunction):
    """L2 regression: g = score - label, h = 1 (x weight)
    (regression_objective.hpp:24-39)."""

    name = "regression"

    def get_gradients(self, scores):
        return _l2_grads(scores, self.label, self.weights)


@jax.jit
@phase_scope("gradients")
def _l2_grads(score, label, weights):
    g = score - label
    h = jnp.ones_like(score)
    if weights is not None:
        g, h = g * weights, h * weights
    return g, h


class BinaryLogloss(ObjectiveFunction):
    """Binary logloss on labels {0,1} -> {-1,+1}
    (binary_objective.hpp:62-88): response = -2*l*sig / (1 + exp(2*l*sig*s));
    hess = |r| * (2*sig - |r|).  Supports is_unbalance and scale_pos_weight
    class weights (binary_objective.hpp:40-59)."""

    name = "binary"

    def __init__(self, config):
        if config.sigmoid <= 0:
            raise ValueError("sigmoid parameter must be > 0")
        self.sigmoid = float(config.sigmoid)
        self.is_unbalance = bool(config.is_unbalance)
        self.scale_pos_weight = float(config.scale_pos_weight)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lab = np.asarray(metadata.label)
        cnt_pos = int((lab == 1).sum())
        cnt_neg = int(num_data - cnt_pos)
        if cnt_pos == 0 or cnt_neg == 0:
            raise ValueError("Training data only contains one class")
        w_neg, w_pos = 1.0, 1.0
        if self.is_unbalance:
            if cnt_pos > cnt_neg:
                w_neg = cnt_pos / cnt_neg
            else:
                w_pos = cnt_neg / cnt_pos
        w_pos *= self.scale_pos_weight
        self._label_weight = (float(w_neg), float(w_pos))

    def get_gradients(self, scores):
        return _binary_grads(
            scores,
            self.label,
            self.weights,
            jnp.float32(self.sigmoid),
            jnp.float32(self._label_weight[0]),
            jnp.float32(self._label_weight[1]),
        )


@jax.jit
@phase_scope("gradients")
def _binary_grads(score, label, weights, sigmoid, w_neg, w_pos):
    is_pos = label > 0
    sign = jnp.where(is_pos, 1.0, -1.0)
    lw = jnp.where(is_pos, w_pos, w_neg)
    response = -2.0 * sign * sigmoid / (1.0 + jnp.exp(2.0 * sign * sigmoid * score))
    abs_r = jnp.abs(response)
    g = response * lw
    h = abs_r * (2.0 * sigmoid - abs_r) * lw
    if weights is not None:
        g, h = g * weights, h * weights
    return g, h


class MulticlassSoftmax(ObjectiveFunction):
    """Softmax multiclass (multiclass_objective.hpp:13-94): scores are
    [K, n]; g = p - 1{y=k}, h = 2 p (1-p)."""

    name = "multiclass"

    def __init__(self, config):
        self.num_class = int(config.num_class)
        if self.num_class <= 1:
            raise ValueError("multiclass objective needs num_class > 1")

    def get_gradients(self, scores):
        return _multiclass_grads(scores, self.label, self.weights)


@jax.jit
@phase_scope("gradients")
def _multiclass_grads(scores, label, weights):
    # scores [K, n]
    p = jax.nn.softmax(scores, axis=0)
    onehot = (label[None, :] == jnp.arange(scores.shape[0])[:, None]).astype(
        jnp.float32
    )
    g = p - onehot
    h = 2.0 * p * (1.0 - p)
    if weights is not None:
        g, h = g * weights[None, :], h * weights[None, :]
    return g, h


def create_objective(config, metadata=None, num_data: Optional[int] = None):
    """Factory (objective_function.cpp:9-20).  lambdarank lives in
    objectives_rank.py to keep the NDCG machinery together."""
    name = config.objective
    if name in ("regression", "regression_l2", "mean_squared_error", "mse", "l2"):
        obj = RegressionL2()
    elif name == "binary":
        obj = BinaryLogloss(config)
    elif name in ("multiclass", "softmax"):
        obj = MulticlassSoftmax(config)
    elif name == "lambdarank":
        from .objectives_rank import LambdarankNDCG

        obj = LambdarankNDCG(config)
    else:
        raise ValueError(f"Unknown objective: {name!r}")
    if metadata is not None:
        obj.init(metadata, num_data if num_data is not None else len(metadata.label))
    return obj
