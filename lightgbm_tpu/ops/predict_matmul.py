"""Gather-free ensemble prediction as MXU matmuls.

The canonical per-row root-to-leaf walk (models/tree.py
predict_leaf_raw; reference Tree::Predict, tree.h:226-238,
predictor.hpp:82-155) costs one indexed feature gather per row per
level per tree — the exact HBM access pattern (~30 ns/element) whose
elimination from TRAINING was the round-3/4 headline.  Round-4
measured the walk at 104.9 s for 1M rows x 100 trees on a v5e-1
against the reference's 17.0 s threaded file predictor.

This module re-states prediction as three dense per-tree ops with NO
indexed access at all:

1. ``vals = X @ Sel`` — the per-node split-feature values via a
   one-hot selection matmul ``[n, F] @ [F, L-1]``.  One-hot selection
   is EXACT on the MXU: bf16x3/bf16x6 decomposition represents each
   f32 addend exactly and 0-products vanish, so ``vals[i, j]`` is
   bitwise ``X[i, feat[j]]``.
2. ``go = cmp(vals, thr)`` — elementwise; numerical ``<=``,
   categorical ``==`` on int casts (tree.h:116-122 routing).
3. ``match = go @ M + base`` — the signed path-incidence matmul
   ``[n, L-1] @ [L-1, L]``.  ``M[a, l]`` is +1 when node ``a`` is an
   ancestor of leaf ``l`` with ``l`` in its LEFT subtree, -1 for
   RIGHT, else 0; ``base[l]`` counts the -1 entries.  ``match[i, l]``
   then counts the ancestors of ``l`` whose decision row ``i``
   satisfies, so ``match == depth[l]`` picks exactly the leaf the walk
   would reach.  All operands are 0/±1 and depths are < 2^8, exact in
   bf16 inputs with f32 MXU accumulation.

Leaf values follow as ``hit @ leaf_value`` and leaf indices as
``argmax(hit)`` — every step a large, static-shape, fusable dense op.

Missing values: a NaN would poison the selection matmul (0 * NaN =
NaN), so X is sanitized NaN -> 0.0 first, which is how a node whose
column has no missing bin reads a NaN (models/tree.py
numerical_left; a categorical node reads category 0, as the walk's
int cast does).  Where the batch holds a NaN, a second one-hot matmul
of the NaN mask (0/1, exact in one bf16 pass) says which selected
values were NaN, and a node whose missing type is NaN sends those rows
by its default direction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..models.tree import numerical_left
from ..obs.device_time import phase_scope

_FLT_MAX = jnp.float32(3.4028235e38)


def _sanitize(X):
    """NaN AND +/-inf would poison the selection matmul (0 * inf = NaN
    contaminates every non-selecting node).  NaN reads 0.0 (the module
    docstring) and +/-inf clamp to +/-FLT_MAX sign-preserving: with
    finite thresholds, +/-FLT_MAX routes exactly like +/-inf does in the
    walk path.  Returns ``(X, NaN mask as bf16, whether any is NaN)``."""
    nan = jnp.isnan(X)
    return (jnp.nan_to_num(X, nan=0.0, posinf=_FLT_MAX, neginf=-_FLT_MAX),
            nan.astype(jnp.bfloat16), jnp.any(nan))


@jax.jit
@phase_scope("predict")
def build_path_tables(stacked):
    """Per-tree path-incidence tables from a stacked Tree pytree
    (leading axis [T], or [n_iter, K] — mirrored in the outputs):
    ``(M [.., L-1, L] bf16, base [.., L] f32, depth [.., L] i32,
    valid [.., L] bool)``.

    Relies on the construction invariant that an internal node's
    internal children carry LARGER node indices (node ids are assigned
    in split order, tree.cpp:52-96; both our grower and reference
    model files satisfy it), so one ascending pass propagates each
    node's signed ancestor vector to its children.
    """

    def per_tree(num_leaves, left_child, right_child, leaf_parent):
        Lm1 = left_child.shape[0]
        L = Lm1 + 1

        def body(j, pd):
            P, D = pd
            rowj = P[j]
            dj = D[j]
            cl = left_child[j]
            cr = right_child[j]
            ok = j < num_leaves - 1  # unused nodes carry zeroed children
            okl = ok & (cl >= 0)
            okr = ok & (cr >= 0)
            # dump writes for leaf/invalid children into the spare row
            il = jnp.where(okl, cl, Lm1)
            ir = jnp.where(okr, cr, Lm1)
            P = P.at[il].set(jnp.where(okl, rowj.at[j].set(1.0), P[il]))
            D = D.at[il].set(jnp.where(okl, dj + 1, D[il]))
            P = P.at[ir].set(jnp.where(okr, rowj.at[j].set(-1.0), P[ir]))
            D = D.at[ir].set(jnp.where(okr, dj + 1, D[ir]))
            return P, D

        P0 = jnp.zeros((Lm1 + 1, Lm1), jnp.float32)
        D0 = jnp.zeros(Lm1 + 1, jnp.int32)
        P, D = jax.lax.fori_loop(0, Lm1, body, (P0, D0))

        leaves = jnp.arange(L, dtype=jnp.int32)
        has_p = leaf_parent >= 0
        pidx = jnp.maximum(leaf_parent, 0)
        is_left = left_child[pidx] == ~leaves
        sign = jnp.where(is_left, 1.0, -1.0).astype(jnp.float32)
        own = sign[:, None] * (
            pidx[:, None] == jnp.arange(Lm1, dtype=jnp.int32)[None, :]
        ).astype(jnp.float32)
        cols = jnp.where(has_p[:, None], P[pidx] + own, 0.0)  # [L, L-1]
        depth = jnp.where(has_p, D[pidx] + 1, 0)
        base = jnp.sum((cols == -1.0).astype(jnp.float32), axis=1)
        valid = leaves < num_leaves
        return cols.T.astype(jnp.bfloat16), base, depth, valid

    lead = stacked.num_leaves.shape  # (T,) or (n_iter, K)
    nd = len(lead)
    args = (stacked.num_leaves, stacked.left_child, stacked.right_child,
            stacked.leaf_parent)
    flat = [a.reshape((-1,) + a.shape[nd:]) for a in args]
    out = jax.vmap(per_tree)(*flat)
    return tuple(o.reshape(lead + o.shape[1:]) for o in out)


def _tree_hit(Xs, feat, thr, dt, M, base, depth, valid):
    """[n, L] bool: which (valid) leaf each row lands in, for one tree;
    ``Xs`` is ``_sanitize``'s triple, ``dt`` the nodes' decision_type."""
    X, nan, any_nan = Xs
    F = X.shape[1]
    sel = (
        (jnp.maximum(feat, 0)[None, :] == jnp.arange(F, dtype=jnp.int32)[:, None])
        & (feat >= 0)[None, :]
    ).astype(jnp.float32)
    vals = jax.lax.dot_general(
        X, sel, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # [n, L-1], exact copies of the selected feature values
    # which selected values were NaN, where the batch holds one (the
    # predicate is the batch's, unbatched under the per-class vmap)
    nan_sel = jax.lax.cond(
        any_nan,
        lambda: jax.lax.dot_general(
            nan, sel.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) > 0,
        lambda: jnp.zeros(vals.shape, bool))
    go = jnp.where(
        ((dt & 1) == 1)[None, :],
        vals.astype(jnp.int32) == thr.astype(jnp.int32),
        numerical_left(jnp.where(nan_sel, jnp.nan, vals), thr[None, :],
                       dt[None, :]),
    ).astype(jnp.bfloat16)
    match = jax.lax.dot_general(
        go, M, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + base[None, :]
    return (match.astype(jnp.int32) == depth[None, :]) & valid[None, :]


@jax.jit
@phase_scope("predict")
def ensemble_sum_matmul(tables, stacked, X):
    """Σ over trees of per-row outputs on RAW features; ``stacked`` and
    each table carry leading axes [n_iter, K]; returns [K, n].  Same
    contract as models/tree.py ensemble_sum_raw, per-tree outputs
    bitwise identical (one-hot selection and 0/1-weighted leaf-value
    sums are exact)."""
    K, n = stacked.leaf_value.shape[1], X.shape[0]
    Xs = _sanitize(X)

    def step(acc, xs):
        t, (M, base, depth, valid) = xs
        def one(feat, thr, dt, lv, M, base, depth, valid):
            hit = _tree_hit(Xs, feat, thr, dt, M, base, depth, valid)
            return jnp.sum(hit.astype(jnp.float32) * lv[None, :], axis=1)
        out = jax.vmap(one)(
            t.split_feature_real, t.threshold_real, t.decision_type,
            t.leaf_value, M, base, depth, valid,
        )
        return acc + out, None

    acc, _ = jax.lax.scan(
        step, jnp.zeros((K, n), jnp.float32), (stacked, tables))
    return acc


@jax.jit
@phase_scope("predict")
def ensemble_leaves_matmul(tables, stacked, X):
    """Per-tree leaf indices on raw features (flat leading axis [T]) ->
    [T, n] int32 — contract of models/tree.py ensemble_leaves_raw."""
    Xs = _sanitize(X)

    def step(_, xs):
        t, (M, base, depth, valid) = xs
        hit = _tree_hit(
            Xs, t.split_feature_real, t.threshold_real,
            t.decision_type, M, base, depth, valid,
        )
        return None, jnp.argmax(hit, axis=1).astype(jnp.int32)

    _, leaves = jax.lax.scan(step, None, (stacked, tables))
    return leaves
