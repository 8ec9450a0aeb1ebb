"""LambdaRank with NDCG as LightGBM defines it (``LambdarankNDCG``,
rank_objective.hpp:19-227), a query at a time.  Plain numpy: float32 where
the configuration says float32 (scores, gains, discounts, every pair's
terms, the gradients handed back), float64 for the sums over a row's
partners, the ideal DCG and the loss.

For each query, with its rows ordered by score descending and position i
discounted by ``d_i = 1 / log2(2 + i)``, gains ``G[l] = 2^l - 1`` (or the
configuration's ``label_gain``) and ``inv_max_dcg`` one over the DCG of
the ideal order cut at ``max_position`` (0 where that DCG is 0): for every
pair of positions whose labels differ, h the higher label and l the lower,

    delta   = (G[label_h] - G[label_l]) * |d_h - d_l| * inv_max_dcg
              [/ (0.01 + |s_h - s_l|)  when the query's best and worst
                                       scores differ]
    p       = 2 / (1 + exp(2 * sigmoid * (s_h - s_l)))
    grad_h -= delta * p                grad_l += delta * p
    hess_h += 2 * delta * p * (2 - p)  hess_l += the same

Two departures from the source, both the program's own and stated there
(lightgbm_tpu/objectives_rank.py):

* ``p`` is the exact sigmoid; the source reads a table of 1,048,576
  entries over [-50/sigmoid/2, 50/sigmoid/2] (rank_objective.hpp:179-192).
* Rows of equal score keep their row order (a stable sort); the source's
  ``std::sort`` leaves the order of ties undefined.  After a tree or two a
  query's rows share a few leaf values, so ties are the rule, and at the
  first tree every score is equal: the order of ties decides the discounts.

``loss`` is the smooth cost the lambdas descend without their NDCG weights:
the mean over all label-ordered pairs of ``log(1 + exp(-2 sigmoid (s_h -
s_l)))``.  NDCG itself jumps at every swap of two rows, so a relative gap
of it between two sets of scores that differ in the sixth digit is either
0 or a swap's whole step; ``ndcg`` is there to be printed, not held.

Only rows whose label lies above the query's lowest can be the ``h`` of a
pair, so the pairs are formed as [those rows, every row]: every
label-ordered pair once, none left out.
"""

from __future__ import annotations

import numpy as np


class Objective:
    def __init__(self, data: dict, params: dict):
        self.label = np.asarray(data["y"]).astype(np.int64)
        sizes = np.asarray(data["group"], np.int64)
        self.bounds = np.concatenate([[0], np.cumsum(sizes)])
        if self.bounds[-1] != len(self.label):
            raise ValueError("query sizes do not sum to the rows")
        self.sigma = np.float32(params["sigmoid"])
        top = int(self.label.max()) + 1
        gain = params.get("label_gain") or list(2.0 ** np.arange(top) - 1)
        self.gain64 = np.asarray(gain, np.float64)
        self.gain = self.gain64.astype(np.float32)
        longest = int(sizes.max())
        self.discount64 = 1.0 / np.log2(2.0 + np.arange(longest))
        self.discount = self.discount64.astype(np.float32)
        k = int(params["max_position"])
        self.inv_max_dcg = np.zeros(len(sizes), np.float32)
        for q, a, b in self.queries():
            dcg = self.dcg(np.sort(self.label[a:b])[::-1], k)
            self.inv_max_dcg[q] = 1.0 / dcg if dcg > 0 else 0.0

    def queries(self):
        return zip(range(len(self.bounds) - 1), self.bounds[:-1],
                   self.bounds[1:])

    def dcg(self, labels_in_order: np.ndarray, k: int) -> float:
        top = labels_in_order[:k]
        return float(np.sum(self.gain64[top] * self.discount64[:len(top)]))

    def gradients(self, scores: np.ndarray):
        scores = np.asarray(scores, np.float32)
        grad = np.zeros(len(scores), np.float32)
        hess = np.zeros(len(scores), np.float32)
        two = np.float32(2)
        for q, a, b in self.queries():
            lab = self.label[a:b]
            if lab.min() == lab.max():
                continue
            order = np.argsort(-scores[a:b], kind="stable")
            s, lab = scores[a:b][order], lab[order]
            d = self.discount[:b - a]
            hi = np.flatnonzero(lab > lab.min())  # positions that can be h
            pair = lab[hi, None] > lab[None, :]
            diff = s[hi, None] - s[None, :]  # s_h - s_l
            delta = ((self.gain[lab[hi]][:, None] - self.gain[lab][None, :])
                     * np.abs(d[hi, None] - d[None, :]) * self.inv_max_dcg[q])
            if s[0] != s[-1]:
                delta = delta / (np.float32(0.01) + np.abs(diff))
            with np.errstate(over="ignore"):
                p = two / (np.float32(1) + np.exp(two * self.sigma * diff))
            lam = np.where(pair, delta * p, 0).astype(np.float64)
            hes = np.where(pair, two * delta * p * (two - p), 0
                           ).astype(np.float64)
            g = lam.sum(axis=0)
            g[hi] -= lam.sum(axis=1)
            h = hes.sum(axis=0)
            h[hi] += hes.sum(axis=1)
            grad[a + order] = g
            hess[a + order] = h
        return grad, hess

    def loss(self, scores: np.ndarray) -> float:
        """Mean over label-ordered pairs of log(1 + exp(-2 sigma (s_h -
        s_l))), in float64."""
        s64 = np.asarray(scores, np.float64)
        total, pairs = 0.0, 0
        for _, a, b in self.queries():
            lab, s = self.label[a:b], s64[a:b]
            hi = np.flatnonzero(lab > lab.min())
            if not len(hi):
                continue
            pair = lab[hi, None] > lab[None, :]
            diff = (s[hi, None] - s[None, :])[pair]
            total += float(np.logaddexp(
                0.0, -2.0 * float(self.sigma) * diff).sum())
            pairs += len(diff)
        return total / max(pairs, 1)

    def ndcg(self, scores: np.ndarray, k: int = 10) -> float:
        """Mean NDCG@k over the queries that have a relevant row: for a
        reader to print beside the loss; no comparison takes it."""
        out = []
        for _, a, b in self.queries():
            lab = self.label[a:b]
            best = self.dcg(np.sort(lab)[::-1], k)
            if best > 0:
                by_score = lab[np.argsort(-scores[a:b], kind="stable")]
                out.append(self.dcg(by_score, k) / best)
        return float(np.mean(out)) if out else 0.0
