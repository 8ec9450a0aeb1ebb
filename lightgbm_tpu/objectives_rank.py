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

Rows of equal score keep their row order (the sort by score is stable);
the reference's ``std::sort`` leaves it undefined.  The plain numpy statement
of the same equations, a query at a time, is
``benchmarks/references/lambdarank.py``; ``tests/test_rank_reference.py``
holds this program to it.

One jitted program (``jit__lambdarank_grads``) a length bucket, three
scopes inside it under ``lgbm.gradients`` (obs/device_time.SCOPES):
``lgbm.rank.sort`` (the gather of scores into ``[queries, Q]`` and the
two ``lax.sort`` calls: every reordering between slot order and rank order
rides through the sort that defines it as a payload operand, so scores,
slots, labels and gains go out with the sort by score and the lambdas come
back with a sort by slot; an XLA gather by the permutation costs 12-30 ns an
element on the chip, a sort operand next to nothing), ``lgbm.rank.pairs``
(the ``[C, Q, Q]`` pair arithmetic and its row sums, and the ``lax.map``
that carries the chunks) and ``lgbm.rank.scatter`` (the sums' way back to
rows, in the tree's LAST launch: it lays the buckets' sums end to end and
every row reads its slot, one gather a channel by a map that ``init``
builds; a scatter-add a bucket was 56 ms a tree on the chip where the
two gathers are 30, PERF.md, PR 33).  ``init`` counts what a tree's gradients
cost, once: ``rank.queries``, ``rank.buckets``, ``rank.launches_per_tree``
(one launch a bucket), ``rank.label_pairs`` (pairs of rows of one query
whose labels differ), ``rank.pair_slots`` (cells of the padded pair
tensors, of which each label-ordered pair fills one: their ratio is the
padding's and the symmetry's waste) and ``rank.row_slots`` (cells of the
``[queries, Q]`` layouts: what every sort operand and every transfer
between row order and slot order moves).
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

        # bucket queries by next-power-of-two length (min 16): each
        # bucket pads to its own bound, so pair work tracks the actual
        # length distribution instead of the global max
        bucket_of = np.maximum(
            16, 1 << np.ceil(np.log2(np.maximum(sizes, 1))).astype(np.int64)
        )
        gains32 = self._gains_np.astype(np.float32)
        self._buckets = []
        # row -> its slot in the buckets' [queries, Q] layouts laid end to end
        row_slot = np.zeros(num_data, np.int32)
        pair_slots = row_slots = 0
        for Qb in sorted(set(int(b) for b in bucket_of)):
            qsel = np.flatnonzero(bucket_of == Qb)
            bq = len(qsel)
            # a query's rows fill its first slots, in row order
            pad_idx = np.full((bq, Qb), num_data, np.int32)
            for i, q in enumerate(qsel):
                c = int(sizes[q])
                pad_idx[i, :c] = np.arange(qb[q], qb[q + 1])
                row_slot[qb[q] : qb[q + 1]] = row_slots + i * Qb + np.arange(c)
            labels_padded = np.where(
                pad_idx < num_data,
                label_np[np.minimum(pad_idx, num_data - 1)], 0
            ).astype(np.int32)
            # labels never change: their gains are looked up here, once
            gains_padded = gains32[np.clip(labels_padded, 0, len(gains32) - 1)]
            # chunk queries to bound the [C, Q, Q] pair tensors to ~64MB
            chunk = max(1, min(bq, (1 << 24) // max(Qb * Qb, 1)))
            self._buckets.append((
                jnp.asarray(pad_idx),
                jnp.asarray(sizes[qsel], jnp.int32),
                jnp.asarray(labels_padded),
                jnp.asarray(gains_padded),
                jnp.asarray(inv_max_dcg[qsel], jnp.float32),
                jnp.asarray(position_discounts(Qb), jnp.float32),
                chunk,
            ))
            pair_slots += -(-bq // chunk) * chunk * Qb * Qb
            row_slots += bq * Qb
        self._row_slot = jnp.asarray(row_slot)
        telemetry.count_many({
            "rank.queries": nq,
            "rank.buckets": len(self._buckets),
            "rank.launches_per_tree": len(self._buckets),
            "rank.label_pairs": label_pairs,
            "rank.pair_slots": pair_slots,
            "rank.row_slots": row_slots,
        })

    def get_gradients(self, scores):
        sigmoid = jnp.float32(self.sigmoid)
        *first, (*bucket, chunk) = self._buckets
        sums = tuple(
            _lambdarank_grads(scores, *b, sigmoid, chunk=c) for *b, c in first)
        grad, hess = _lambdarank_grads(  # the last launch takes them to rows
            scores, *bucket, sigmoid, chunk=chunk, before=sums,
            row_slot=self._row_slot)
        if self.weights is not None:
            grad, hess = grad * self.weights, hess * self.weights
        return grad, hess


def _to_rank_order(s, slot, *payload):
    """``s`` by score descending along axis 1, rows of equal score in slot
    order, and ``slot`` and every ``payload`` array moved with it: they ride
    the sort as operands.  The sorted ``slot`` is ``order``, rank -> slot,
    what ``argsort(-s, stable=True)`` gives; the negated key is ``s`` in
    rank order bit for bit."""
    key_r, *moved = jax.lax.sort(
        (-s, slot, *payload), dimension=1, num_keys=1, is_stable=True)
    return (-key_r, *moved)


def _to_slot_order(order, *payload):
    """``payload`` arrays in rank order, back in slot order: ``order`` is a
    permutation of the slots, so sorting by it puts every element back
    where it came from, with no ``argsort(order)`` and no gather by it
    (no key repeats: a stable sort would carry an index operand more)."""
    return tuple(jax.lax.sort(
        (order, *payload), dimension=1, num_keys=1, is_stable=False)[1:])


@functools.partial(jax.jit, static_argnames=("chunk",))
@phase_scope("gradients")
def _lambdarank_grads(
    scores,
    pad_idx,
    cnt,
    labels,
    gains,
    inv_max_dcg,
    discounts,
    sigmoid,
    chunk: int,
    before=None,
    row_slot=None,
):
    """One bucket's sums of lambdas and of hessians, ``[queries * Q]`` in
    slot order; or, given the buckets ``before`` it (their sums) and the
    ``row_slot`` map, the gradients and hessians of every row.
    ``pad_idx``, ``labels`` and ``gains`` are ``[queries, Q]`` in slot order
    (a query's ``cnt`` rows first, then padding that points at row n)."""
    num_data = scores.shape[0]
    nq, Q = pad_idx.shape
    nchunks = -(-nq // chunk)
    pad_q = nchunks * chunk - nq
    with phase_scope("rank.sort"):
        # pad scores with a sentinel slot at index n
        s_ext = jnp.concatenate([scores, jnp.zeros(1, scores.dtype)])
        if pad_q:  # queries of no rows fill the last chunk
            pad_idx = jnp.concatenate(
                [pad_idx, jnp.full((pad_q, Q), num_data, pad_idx.dtype)]
            )
            cnt = jnp.concatenate([cnt, jnp.zeros(pad_q, cnt.dtype)])
            labels = jnp.concatenate(
                [labels, jnp.zeros((pad_q, Q), labels.dtype)])
            gains = jnp.concatenate(
                [gains, jnp.zeros((pad_q, Q), gains.dtype)])
            inv_max_dcg = jnp.concatenate(
                [inv_max_dcg, jnp.zeros(pad_q, inv_max_dcg.dtype)])

    def one_chunk(args):
        idx, c, lab, gain, imd = args
        with phase_scope("rank.sort"):
            slot = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 1)
            # the first ``c`` slots are the valid ones, and so are the
            # first ``c`` ranks: valid scores sort ahead of the padding's
            # -inf, or tie with it and keep their place (a stable sort)
            vld = slot < c[:, None]
            s = jnp.where(vld, s_ext[idx], -jnp.inf)  # [C, Q]
            s_r, order, l_r, g_r = _to_rank_order(s, slot, lab, gain)
            best = s_r[:, 0]
            worst = jnp.min(jnp.where(vld, s, jnp.inf), axis=1)
        with phase_scope("rank.pairs"):
            # (a query of no rows reads best -inf, worst inf and NaN
            # differences: ``cond`` is false on every pair of it, so its
            # sums are exact zeros all the same)
            regularize = (best != worst)[:, None, None]
            D = s_r[:, :, None] - s_r[:, None, :]  # s_high - s_low
            cond = (
                (l_r[:, :, None] > l_r[:, None, :])
                & vld[:, :, None]
                & vld[:, None, :]
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
            return _to_slot_order(order, lam_r, hes_r)

    with phase_scope("rank.pairs"):  # the loop over chunks itself
        lam, hes = jax.lax.map(one_chunk, (
            pad_idx.reshape(nchunks, chunk, Q),
            cnt.reshape(nchunks, chunk),
            labels.reshape(nchunks, chunk, Q),
            gains.reshape(nchunks, chunk, Q),
            inv_max_dcg.reshape(nchunks, chunk),
        ))
        lam = lam.reshape(-1, Q)[:nq].reshape(-1)
        hes = hes.reshape(-1, Q)[:nq].reshape(-1)

    if row_slot is None:
        return lam, hes
    with phase_scope("rank.scatter"):
        grad = jnp.concatenate([*(b[0] for b in before), lam])[row_slot]
        hess = jnp.concatenate([*(b[1] for b in before), hes])[row_slot]
    return grad, hess
