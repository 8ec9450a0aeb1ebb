"""LambdaRank-NDCG objective, TPU-native.

Re-expresses LambdarankNDCG (src/objective/rank_objective.hpp:19-227) as a
padded, vmapped pairwise computation, replacing the reference's per-query
OpenMP loop (rank_objective.hpp:68-74) and its O(cnt^2) nested pair loops
(rank_objective.hpp:109-156) with dense [C,Q,Q] tensor ops.  Queries are
BUCKETED by power-of-two length and each bucket is padded only to its own
bound and processed in fixed-size chunks (``lax.map``): real query-length
distributions (MSLR-style: median ~100, max >1000) would waste ~(Qmax/Q)^2
pair work per query under a single global pad, while bucketing bounds the
waste per query at <4x and keeps every shape static for XLA.  The 1M-entry
sigmoid lookup table (rank_objective.hpp:179-192) is replaced by the exact
sigmoid — table lookup is a CPU trick; the VPU evaluates exp directly.

Per pair (high=rank i, low=rank j, label_high > label_low):
  delta_ndcg = (gain[lh]-gain[ll]) * |disc_i - disc_j| * inv_max_dcg
               [/ (0.01 + |s_h - s_l|) when best != worst score]
  p        = 2 / (1 + exp(2*sigma*(s_h - s_l)))
  lambda_h += -delta_ndcg * p        lambda_l -= -delta_ndcg * p
  hess_{h,l} += 2 * delta_ndcg * p * (2 - p)

Rows of equal score keep their row order (both sorts are stable); the
reference's ``std::sort`` leaves it undefined.  The plain numpy statement
of the same equations, a query at a time, is
``benchmarks/references/lambdarank.py``; ``tests/test_rank_reference.py``
holds this program to it.

One jitted program (``jit__lambdarank_grads``) a length bucket, three
scopes inside it under ``lgbm.gradients`` (obs/device_time.SCOPES):
``lgbm.rank.sort`` (the gather of scores into ``[queries, Q]``, both
argsorts and the reorderings by them), ``lgbm.rank.pairs`` (the
``[C, Q, Q]`` pair arithmetic and its row sums, and the ``lax.map`` that
carries the chunks) and ``lgbm.rank.scatter`` (the two ``.at[idx].add``
back to rows).  ``init`` counts what a tree's gradients cost, once:
``rank.queries``, ``rank.buckets``, ``rank.launches_per_tree`` (one launch
a bucket), ``rank.label_pairs`` (pairs of rows of one query whose labels
differ) and ``rank.pair_slots`` (cells of the padded pair tensors, of
which each label-ordered pair fills one: their ratio is the padding's
and the symmetry's waste).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .dcg import label_gains_from_config, max_dcg_at_k, position_discounts
from .objectives import ObjectiveFunction
from .obs import telemetry
from .obs.device_time import phase_scope


class LambdarankNDCG(ObjectiveFunction):
    name = "lambdarank"

    def __init__(self, config):
        if config.sigmoid <= 0:
            raise ValueError("sigmoid parameter must be > 0")
        self.sigmoid = float(config.sigmoid)
        self.optimize_pos_at = int(config.max_position)
        self._gains_np = label_gains_from_config(config.label_gain)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            raise ValueError("Lambdarank tasks require query information")
        qb = np.asarray(metadata.query_boundaries)
        label_np = np.asarray(metadata.label)
        nq = len(qb) - 1
        sizes = qb[1:] - qb[:-1]
        inv_max_dcg = np.zeros(nq, np.float64)
        label_pairs = 0
        for q in range(nq):
            lab = label_np[qb[q] : qb[q + 1]]
            m = max_dcg_at_k(self.optimize_pos_at, lab, self._gains_np)
            inv_max_dcg[q] = 1.0 / m if m > 0 else 0.0
            per_label = np.bincount(lab.astype(np.int64))
            label_pairs += (len(lab) ** 2 - int((per_label ** 2).sum())) // 2
        self._gains = jnp.asarray(self._gains_np, jnp.float32)

        # bucket queries by next-power-of-two length (min 16): each
        # bucket pads to its own bound, so pair work tracks the actual
        # length distribution instead of the global max
        bucket_of = np.maximum(
            16, 1 << np.ceil(np.log2(np.maximum(sizes, 1))).astype(np.int64)
        )
        self._buckets = []
        pair_slots = 0
        for Qb in sorted(set(int(b) for b in bucket_of)):
            qsel = np.flatnonzero(bucket_of == Qb)
            bq = len(qsel)
            pad_idx = np.full((bq, Qb), num_data, np.int32)
            for i, q in enumerate(qsel):
                c = int(sizes[q])
                pad_idx[i, :c] = np.arange(qb[q], qb[q + 1])
            valid = pad_idx < num_data
            labels_padded = np.where(
                valid, label_np[np.minimum(pad_idx, num_data - 1)], 0
            ).astype(np.int32)
            # chunk queries to bound the [C, Q, Q] pair tensors to ~64MB
            chunk = max(1, min(bq, (1 << 24) // max(Qb * Qb, 1)))
            self._buckets.append((
                jnp.asarray(pad_idx),
                jnp.asarray(valid),
                jnp.asarray(labels_padded),
                jnp.asarray(inv_max_dcg[qsel], jnp.float32),
                jnp.asarray(position_discounts(Qb), jnp.float32),
                chunk,
            ))
            pair_slots += -(-bq // chunk) * chunk * Qb * Qb
        telemetry.count_many({
            "rank.queries": nq,
            "rank.buckets": len(self._buckets),
            "rank.launches_per_tree": len(self._buckets),
            "rank.label_pairs": label_pairs,
            "rank.pair_slots": pair_slots,
        })

    def get_gradients(self, scores):
        grad = jnp.zeros(self.num_data, jnp.float32)
        hess = jnp.zeros(self.num_data, jnp.float32)
        for pad_idx, valid, labels, imd, discounts, chunk in self._buckets:
            g, h = _lambdarank_grads(
                scores, pad_idx, valid, labels, imd, self._gains, discounts,
                jnp.float32(self.sigmoid), None, self.num_data, chunk,
            )
            grad, hess = grad + g, hess + h
        if self.weights is not None:
            grad, hess = grad * self.weights, hess * self.weights
        return grad, hess


@functools.partial(jax.jit, static_argnames=("num_data", "chunk"))
@phase_scope("gradients")
def _lambdarank_grads(
    scores,
    pad_idx,
    valid,
    labels,
    inv_max_dcg,
    gains,
    discounts,
    sigmoid,
    weights,
    num_data: int,
    chunk: int,
):
    nq, Q = pad_idx.shape
    nchunks = -(-nq // chunk)
    pad_q = nchunks * chunk - nq
    with phase_scope("rank.sort"):
        # pad scores with a sentinel slot at index n
        s_ext = jnp.concatenate([scores, jnp.zeros(1, scores.dtype)])
        if pad_q:
            pad_idx = jnp.concatenate(
                [pad_idx, jnp.full((pad_q, Q), num_data, pad_idx.dtype)]
            )
            valid = jnp.concatenate([valid, jnp.zeros((pad_q, Q), bool)])
            labels = jnp.concatenate(
                [labels, jnp.zeros((pad_q, Q), labels.dtype)])
            inv_max_dcg = jnp.concatenate(
                [inv_max_dcg, jnp.zeros(pad_q, inv_max_dcg.dtype)])

    def one_chunk(args):
        idx, vld, lab, imd = args
        with phase_scope("rank.sort"):
            s = jnp.where(vld, s_ext[idx], -jnp.inf)  # [C, Q]
            order = jnp.argsort(-s, axis=1, stable=True)  # rank -> slot
            s_r = jnp.take_along_axis(s, order, axis=1)
            l_r = jnp.take_along_axis(lab, order, axis=1)
            v_r = jnp.take_along_axis(vld, order, axis=1)
            cnt = vld.sum(axis=1)
            best = s_r[:, 0]
            worst = jnp.take_along_axis(
                s_r, jnp.maximum(cnt - 1, 0)[:, None], axis=1
            )[:, 0]
        with phase_scope("rank.pairs"):
            regularize = (best != worst)[:, None, None]
            g_r = gains[jnp.clip(l_r, 0, gains.shape[0] - 1)]
            D = s_r[:, :, None] - s_r[:, None, :]  # s_high - s_low
            cond = (
                (l_r[:, :, None] > l_r[:, None, :])
                & v_r[:, :, None]
                & v_r[:, None, :]
            )
            dcg_gap = g_r[:, :, None] - g_r[:, None, :]
            pd = jnp.abs(discounts[None, :, None] - discounts[None, None, :])
            dn = dcg_gap * pd * imd[:, None, None]
            dn = jnp.where(regularize, dn / (0.01 + jnp.abs(D)), dn)
            p = 2.0 / (1.0 + jnp.exp(jnp.clip(2.0 * sigmoid * D, -88.0, 88.0)))
            lam = jnp.where(cond, -dn * p, 0.0)
            hes = jnp.where(cond, 2.0 * dn * p * (2.0 - p), 0.0)
            lam_r = lam.sum(axis=2) - lam.sum(axis=1)  # high gets +, low -
            hes_r = hes.sum(axis=2) + hes.sum(axis=1)
        with phase_scope("rank.sort"):
            # unsort back to slot order
            unsort = jnp.argsort(order, axis=1, stable=True)
            lam_s = jnp.take_along_axis(lam_r, unsort, axis=1)
            hes_s = jnp.take_along_axis(hes_r, unsort, axis=1)
        return lam_s, hes_s

    with phase_scope("rank.pairs"):  # the loop over chunks itself
        idx_c = pad_idx.reshape(nchunks, chunk, Q)
        vld_c = valid.reshape(nchunks, chunk, Q)
        lab_c = labels.reshape(nchunks, chunk, Q)
        imd_c = inv_max_dcg.reshape(nchunks, chunk)
        lam, hes = jax.lax.map(one_chunk, (idx_c, vld_c, lab_c, imd_c))

    with phase_scope("rank.scatter"):
        flat_idx = pad_idx.reshape(-1)
        grad = jnp.zeros(num_data + 1, jnp.float32).at[flat_idx].add(
            lam.reshape(-1))[:num_data]
        hess = jnp.zeros(num_data + 1, jnp.float32).at[flat_idx].add(
            hes.reshape(-1))[:num_data]
        if weights is not None:
            grad, hess = grad * weights, hess * weights
    return grad, hess
