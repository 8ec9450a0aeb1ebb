"""Evaluation metrics (src/metric/*.hpp re-expressed).

All metrics expose ``eval(scores) -> float`` plus ``bigger_is_better``
(factor_to_bigger_better, metric.h:31) which drives early-stopping
direction.  Scores are raw (pre-transform) model outputs, class-major
[K, n] for multiclass — the transforms (sigmoid/softmax) are applied
inside the metric exactly like the reference.

Two evaluation paths: ``eval`` (host numpy, the reference-parity
implementation) and, where implemented, ``eval_jax`` (device-resident:
scores never leave HBM, only the scalar comes back — the reference has
no analog because its scores already live in host memory; here a per-
iteration eval of a 10M-row score vector would otherwise pay a 40MB
device->host copy plus a host sort for AUC).  NDCG keeps host-only eval.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

import jax

# jaxlint: disable-file=f64-literal-in-traced — the eval_jax reductions
# deliberately accumulate in f64 under the enable_x64 context installed
# by eval_jax_jit (f32 cumsums drift in the 4th AUC decimal at ~10M
# rows; with >2^24 unit-weight rows the increments vanish entirely).

_EPS = 1e-15


class Metric:
    name = "none"
    bigger_is_better = False
    eval_jax = None  # device path; subclasses override where supported

    def init(self, metadata, num_data: int) -> None:
        self.label = np.asarray(metadata.label, np.float64)
        self.weights = (
            None if metadata.weights is None else np.asarray(metadata.weights, np.float64)
        )
        self.sum_weights = (
            float(num_data) if self.weights is None else float(self.weights.sum())
        )
        self.num_data = num_data
        self.metadata = metadata
        self._dev = None  # lazy (label, weights) device arrays
        self._jfn = None  # lazy jitted eval_jax

    def eval_jax_jit(self, scores):
        """Jitted device eval; traces once per score shape.  Runs under
        enable_x64 so the reductions inside eval_jax accumulate in f64
        like the host/reference path (f32 cumsums visibly drift in the
        4th AUC decimal at ~10M rows; with >2^24 unit-weight rows the
        increments drop below f32 spacing entirely)."""
        import jax

        with jax.enable_x64(True):
            if self._jfn is None:
                self._jfn = jax.jit(self.eval_jax)
            return self._jfn(scores)

    def _dev_arrays(self):
        if self._dev is None:
            import jax.numpy as jnp

            lab = jnp.asarray(self.label, jnp.float32)
            w = (
                jnp.ones_like(lab)
                if self.weights is None
                else jnp.asarray(self.weights, jnp.float32)
            )
            self._dev = (lab, w)
        return self._dev

    def _avg(self, loss: np.ndarray) -> float:
        if self.weights is not None:
            return float((loss * self.weights).sum() / self.sum_weights)
        return float(loss.sum() / self.sum_weights)

    def eval(self, scores: np.ndarray) -> float:
        raise NotImplementedError


class L2Metric(Metric):
    """Reports RMSE (AverageLoss takes sqrt, regression_metric.hpp:98-101)."""

    name = "l2"

    def eval(self, scores):
        scores = np.asarray(scores, np.float64).reshape(-1)
        return float(np.sqrt(self._avg((scores - self.label) ** 2)))

    def eval_jax(self, scores):
        import jax.numpy as jnp

        lab, w = self._dev_arrays()
        s = scores.reshape(-1)
        sq = ((s - lab) ** 2 * w).astype(jnp.float64)
        return jnp.sqrt(jnp.sum(sq) / self.sum_weights)


class L1Metric(Metric):
    name = "l1"

    def eval(self, scores):
        scores = np.asarray(scores, np.float64).reshape(-1)
        return self._avg(np.abs(scores - self.label))

    def eval_jax(self, scores):
        import jax.numpy as jnp

        lab, w = self._dev_arrays()
        l1 = (jnp.abs(scores.reshape(-1) - lab) * w).astype(jnp.float64)
        return jnp.sum(l1) / self.sum_weights


class BinaryLoglossMetric(Metric):
    """prob = sigmoid(2*sig*score); loss = -log p_y
    (binary_metric.hpp:44-98)."""

    name = "binary_logloss"

    def __init__(self, config):
        self.sigmoid = float(config.sigmoid)

    def eval(self, scores):
        scores = np.asarray(scores, np.float64).reshape(-1)
        prob = 1.0 / (1.0 + np.exp(-2.0 * self.sigmoid * scores))
        prob = np.clip(prob, _EPS, 1.0 - _EPS)
        loss = np.where(self.label > 0, -np.log(prob), -np.log(1.0 - prob))
        return self._avg(loss)

    def eval_jax(self, scores):
        import jax.numpy as jnp

        lab, w = self._dev_arrays()
        s = scores.reshape(-1)
        prob = jnp.clip(
            1.0 / (1.0 + jnp.exp(-2.0 * self.sigmoid * s)), 1e-7, 1 - 1e-7
        )
        loss = jnp.where(lab > 0, -jnp.log(prob), -jnp.log(1.0 - prob))
        return jnp.sum((loss * w).astype(jnp.float64)) / self.sum_weights


class BinaryErrorMetric(Metric):
    """Misclassification rate at prob 0.5 (binary_metric.hpp:105-140)."""

    name = "binary_error"

    def __init__(self, config):
        self.sigmoid = float(config.sigmoid)

    def eval(self, scores):
        scores = np.asarray(scores, np.float64).reshape(-1)
        pred_pos = scores > 0
        err = (pred_pos != (self.label > 0)).astype(np.float64)
        return self._avg(err)

    def eval_jax(self, scores):
        import jax.numpy as jnp

        lab, w = self._dev_arrays()
        err = ((scores.reshape(-1) > 0) != (lab > 0)).astype(jnp.float32)
        return jnp.sum((err * w).astype(jnp.float64)) / self.sum_weights


class AUCMetric(Metric):
    """Weighted ROC AUC via a single sort sweep with tie handling
    (binary_metric.hpp:181-238)."""

    name = "auc"
    bigger_is_better = True

    def eval(self, scores):
        scores = np.asarray(scores, np.float64).reshape(-1)
        w = self.weights if self.weights is not None else np.ones_like(self.label)
        pos = (self.label > 0).astype(np.float64) * w
        neg = (self.label <= 0).astype(np.float64) * w
        order = np.argsort(-scores, kind="mergesort")
        s, p, ng = scores[order], pos[order], neg[order]
        # group ties: average rank treatment == trapezoid on grouped counts
        boundaries = np.nonzero(np.diff(s))[0]
        group_id = np.zeros(len(s), np.int64)
        group_id[1:] = np.cumsum(np.diff(s) != 0)
        npos = np.bincount(group_id, weights=p)
        nneg = np.bincount(group_id, weights=ng)
        cum_neg_before = np.concatenate([[0.0], np.cumsum(nneg)[:-1]])
        # each positive beats all negatives ranked below; ties count half
        auc_sum = (npos * (cum_neg_before + nneg * 0.5)).sum()
        total_pos, total_neg = npos.sum(), nneg.sum()
        if total_pos == 0 or total_neg == 0:
            return 1.0
        return float(1.0 - auc_sum / (total_pos * total_neg))

    def eval_jax(self, scores):
        """Device AUC: sort + tie-grouped segment sums, no host copy.
        Same grouped-tie math as ``eval`` with groups keyed by sorted
        position via cumsum (bincount -> segment_sum)."""
        import jax.numpy as jnp

        lab, w = self._dev_arrays()
        s = scores.reshape(-1)
        order = jnp.argsort(-s, stable=True)
        ss = s[order]
        p = jnp.where(lab > 0, w, 0.0)[order].astype(jnp.float64)
        ng = jnp.where(lab <= 0, w, 0.0)[order].astype(jnp.float64)
        new_group = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), (jnp.diff(ss) != 0).astype(jnp.int32)]
        )
        gid = jnp.cumsum(new_group)
        n = s.shape[0]
        import jax

        npos = jax.ops.segment_sum(p, gid, num_segments=n)
        nneg = jax.ops.segment_sum(ng, gid, num_segments=n)
        cum_neg_before = jnp.concatenate(
            [jnp.zeros(1, nneg.dtype), jnp.cumsum(nneg)[:-1]]
        )
        auc_sum = jnp.sum(npos * (cum_neg_before + nneg * 0.5))
        total_pos, total_neg = jnp.sum(npos), jnp.sum(nneg)
        denom = total_pos * total_neg
        return jnp.where(denom > 0, 1.0 - auc_sum / denom, 1.0)


class MultiLoglossMetric(Metric):
    """Softmax logloss (multiclass_metric.hpp)."""

    name = "multi_logloss"

    def eval(self, scores):
        scores = np.asarray(scores, np.float64)  # [K, n]
        z = scores - scores.max(axis=0, keepdims=True)
        logp = z - np.log(np.exp(z).sum(axis=0, keepdims=True))
        idx = self.label.astype(np.int64)
        loss = -logp[idx, np.arange(scores.shape[1])]
        return self._avg(loss)

    def eval_jax(self, scores):
        import jax.numpy as jnp

        lab, w = self._dev_arrays()
        z = scores - scores.max(axis=0, keepdims=True)
        logp = z - jnp.log(jnp.exp(z).sum(axis=0, keepdims=True))
        idx = lab.astype(jnp.int32)
        loss = -logp[idx, jnp.arange(scores.shape[1])]
        return jnp.sum((loss * w).astype(jnp.float64)) / self.sum_weights


class MultiErrorMetric(Metric):
    name = "multi_error"

    def eval(self, scores):
        scores = np.asarray(scores, np.float64)
        pred = scores.argmax(axis=0)
        err = (pred != self.label.astype(np.int64)).astype(np.float64)
        return self._avg(err)

    def eval_jax(self, scores):
        import jax.numpy as jnp

        lab, w = self._dev_arrays()
        err = (scores.argmax(axis=0) != lab.astype(jnp.int32)).astype(
            jnp.float32
        )
        return jnp.sum((err * w).astype(jnp.float64)) / self.sum_weights


def create_metrics(config, metadata=None, num_data: Optional[int] = None) -> List[Metric]:
    """Factory (metric.cpp:9-28); unknown names raise."""
    out: List[Metric] = []
    names = config.metric or _default_metric(config.objective)
    for name in names:
        name = name.strip()
        if name in ("l2", "mse", "mean_squared_error", "regression"):
            m: Metric = L2Metric()
        elif name in ("l1", "mae", "mean_absolute_error"):
            m = L1Metric()
        elif name == "binary_logloss":
            m = BinaryLoglossMetric(config)
        elif name == "binary_error":
            m = BinaryErrorMetric(config)
        elif name == "auc":
            m = AUCMetric()
        elif name == "multi_logloss":
            m = MultiLoglossMetric()
        elif name == "multi_error":
            m = MultiErrorMetric()
        elif name in ("ndcg", "ndcg@"):
            from .metrics_rank import NDCGMetric

            m = NDCGMetric(config)
        elif name in ("", "none", "null"):
            continue
        else:
            raise ValueError(f"Unknown metric: {name!r}")
        if metadata is not None:
            m.init(metadata, num_data if num_data is not None else len(metadata.label))
        out.append(m)
    return out


def _default_metric(objective: str) -> List[str]:
    return {
        "regression": ["l2"],
        "binary": ["binary_logloss"],
        "multiclass": ["multi_logloss"],
        "lambdarank": ["ndcg"],
    }.get(objective, ["l2"])
