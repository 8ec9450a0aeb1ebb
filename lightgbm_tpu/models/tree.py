"""Decision tree model as a fixed-shape array pytree.

The reference's flat-array ``Tree`` (include/LightGBM/tree.h:18-198,
src/io/tree.cpp) is already array-oriented; we keep its exact layout —
internal nodes 0..L-2, leaves addressed as ``~leaf`` in child pointers
(tree.cpp:78-79) — but store every field as a fixed-size jax array so a
whole ensemble stacks into one pytree and prediction is a vectorized
gather loop instead of per-row pointer chasing (tree.h:226-238).

``num_leaves`` is the *used* leaf count; arrays are padded to the
``max_leaves`` training budget so shapes stay static under jit.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Tree(NamedTuple):
    num_leaves: jax.Array  # scalar int32: used leaves (1 = stump)
    # internal nodes [max_leaves-1]
    split_feature: jax.Array  # inner feature index
    split_feature_real: jax.Array  # original column index (model IO)
    threshold_bin: jax.Array  # bin-space threshold
    threshold_real: jax.Array  # raw-value threshold (filled at finalize)
    # LightGBM 2.x bits: 1 categorical (==, else <=), 2 missing rows go
    # left, 8 missing type NaN (the column has a missing bin)
    decision_type: jax.Array
    left_child: jax.Array  # node idx or ~leaf
    right_child: jax.Array
    split_gain: jax.Array
    internal_value: jax.Array
    internal_count: jax.Array
    # leaves [max_leaves]
    leaf_value: jax.Array
    leaf_count: jax.Array
    leaf_parent: jax.Array
    leaf_depth: jax.Array

    @property
    def max_leaves(self) -> int:
        return self.leaf_value.shape[-1]

    def shrink(self, rate) -> "Tree":
        """Tree::Shrinkage (tree.h:103-107): scale outputs in place."""
        return self._replace(
            leaf_value=self.leaf_value * rate,
            internal_value=self.internal_value * rate,
        )


def empty_tree(max_leaves: int) -> Tree:
    li = max_leaves - 1
    return Tree(
        num_leaves=jnp.int32(1),
        split_feature=jnp.full(li, -1, jnp.int32),
        split_feature_real=jnp.full(li, -1, jnp.int32),
        threshold_bin=jnp.zeros(li, jnp.int32),
        threshold_real=jnp.zeros(li, jnp.float32),
        decision_type=jnp.zeros(li, jnp.int32),
        left_child=jnp.zeros(li, jnp.int32),
        right_child=jnp.zeros(li, jnp.int32),
        split_gain=jnp.zeros(li, jnp.float32),
        internal_value=jnp.zeros(li, jnp.float32),
        internal_count=jnp.zeros(li, jnp.float32),
        leaf_value=jnp.zeros(max_leaves, jnp.float32),
        leaf_count=jnp.zeros(max_leaves, jnp.float32),
        leaf_parent=jnp.full(max_leaves, -1, jnp.int32),
        leaf_depth=jnp.zeros(max_leaves, jnp.int32),
    )


def numerical_left(v, t, decision_type):
    """Whether a RAW value goes left at numerical nodes of these
    ``decision_type``s: a NaN by the node's default direction where its
    missing type is NaN, as 0.0 elsewhere; every other value by ``v <=
    t`` (LightGBM 2.x's NumericalDecision)."""
    nan_left = jnp.where((decision_type & 12) == 8,
                         (decision_type & 2) == 2, 0.0 <= t)
    return jnp.where(jnp.isnan(v), nan_left, v <= t)


@jax.jit
def predict_leaf_binned(tree: Tree, X_bin: jax.Array,
                        missing_bin: jax.Array | None = None) -> jax.Array:
    """Vectorized root-to-leaf walk over BINNED features -> leaf index.

    Equivalent to Tree::GetLeaf over bin iterators (tree.cpp:98-122).
    All rows walk in lockstep for at most max_leaves-1 steps; rows that
    reached a leaf stop updating (their node stays negative).
    ``missing_bin`` [F] (-1: none): a row in its column's missing bin
    goes by the node's default direction (it is right of every
    threshold, so only a default-left node needs it).
    """
    n = X_bin.shape[0]
    max_steps = tree.leaf_value.shape[-1] - 1

    # node >= 0: internal; node < 0: ~leaf
    start = jnp.where(tree.num_leaves > 1, 0, ~0)
    node = jnp.full((n,), start, jnp.int32)

    def body(state):
        node, _ = state
        active = node >= 0
        idx = jnp.maximum(node, 0)
        f = tree.split_feature[idx]
        t = tree.threshold_bin[idx]
        dt = tree.decision_type[idx]
        is_cat = (dt & 1) == 1
        v = jnp.take_along_axis(
            X_bin, f[:, None].astype(jnp.int32), axis=1
        )[:, 0].astype(jnp.int32)
        num = v <= t
        if missing_bin is not None:
            num = num | (((dt & 2) == 2) & (v == missing_bin[f]))
        go_left = jnp.where(is_cat, v == t, num)
        nxt = jnp.where(go_left, tree.left_child[idx], tree.right_child[idx])
        node = jnp.where(active, nxt, node)
        return node, jnp.any(node >= 0)

    def cond(state):
        return state[1]

    node, _ = jax.lax.while_loop(cond, body, (node, tree.num_leaves > 1))
    return ~node  # leaf index


@jax.jit
def predict_binned(tree: Tree, X_bin: jax.Array,
                   missing_bin: jax.Array | None = None) -> jax.Array:
    """Per-row tree output on binned features."""
    leaves = predict_leaf_binned(tree, X_bin, missing_bin)
    return tree.leaf_value[leaves]


@jax.jit
def predict_leaf_raw(tree: Tree, X: jax.Array) -> jax.Array:
    """Root-to-leaf walk over RAW feature values (Tree::Predict,
    tree.h:226-238): numerical goes left when value <= threshold_real
    (a NaN: numerical_left), categorical when int(value) ==
    threshold_real."""
    n = X.shape[0]
    start = jnp.where(tree.num_leaves > 1, 0, ~0)
    node = jnp.full((n,), start, jnp.int32)

    def body(state):
        node, _ = state
        active = node >= 0
        idx = jnp.maximum(node, 0)
        f = tree.split_feature_real[idx]
        t = tree.threshold_real[idx]
        dt = tree.decision_type[idx]
        v = jnp.take_along_axis(X, jnp.maximum(f, 0)[:, None], axis=1)[:, 0]
        go_left = jnp.where((dt & 1) == 1,
                            v.astype(jnp.int32) == t.astype(jnp.int32),
                            numerical_left(v, t, dt))
        nxt = jnp.where(go_left, tree.left_child[idx], tree.right_child[idx])
        node = jnp.where(active, nxt, node)
        return node, jnp.any(node >= 0)

    node, _ = jax.lax.while_loop(lambda s: s[1], body, (node, tree.num_leaves > 1))
    return ~node


@jax.jit
def predict_raw(tree: Tree, X: jax.Array) -> jax.Array:
    return tree.leaf_value[predict_leaf_raw(tree, X)]


# The longest leaf table the score update reads by selects; past it, by
# XLA's element gather, whose cost a row does not grow with the table.
# On a v5e at 2^24 rows the selects take 1.8 / 6.7 / 13.0 / 24.9 ms at
# 255 / 1,023 / 2,047 / 4,095 leaves against the gather's 144-164, but
# compile in 4.3 / 9.0 / 21.7 / 43.1 s against 0.3: up to 1,024 leaves
# a 2^24-row table pays the compile back within 100 trees.
LEAF_SELECT_MAX_LEAVES = 1024


def leaf_lookup_path(num_leaves: int) -> str:
    """How :func:`leaf_lookup` reads a table of ``num_leaves`` entries:
    ``"select"`` or ``"gather"``, from the static length alone."""
    return "select" if num_leaves <= LEAF_SELECT_MAX_LEAVES else "gather"


def leaf_lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    """``table[ids]`` bit for bit, every id clamped to ``[0, L - 1]``.

    Up to ``LEAF_SELECT_MAX_LEAVES`` entries it is a mux tree on the
    id's bits: level ``b`` picks between pairs of the level below by bit
    ``b``, so ``ceil(log2 L)`` bit tests and at most ``L - 1`` selects a
    row, all elementwise, which XLA fuses into one pass over ``ids``
    (and GSPMD partitions by rows with no collective).  A select moves bits:
    NaN payloads, -0.0 and subnormals come out as the table holds them.
    On a v5e it reads 2^24 rows from 255 leaves in 1.8 ms, where the
    element gather it replaces takes about 164."""
    L = table.shape[-1]
    ids = jnp.clip(ids, 0, L - 1)
    if leaf_lookup_path(L) == "gather":
        return table[ids]
    # the table padded to a power of two with its last entry, which no
    # clamped id reaches; equal neighbours need no select
    vals = [table[i] for i in range(L)]
    vals += vals[-1:] * ((1 << (L - 1).bit_length()) - L)
    b = 0
    while len(vals) > 1:
        bit = (ids >> b) & 1 == 1
        vals = [lo if lo is hi else jnp.where(bit, hi, lo)
                for lo, hi in zip(vals[0::2], vals[1::2])]
        b += 1
    return jnp.broadcast_to(vals[0], ids.shape)


# ------------------------------------------------------------- ensembles
def pad_tree(tree: Tree, max_leaves: int) -> Tree:
    """Pad a tree's arrays to a larger leaf budget (no-op when equal) so
    trees from models with different ``num_leaves`` can stack."""
    cur = tree.max_leaves
    if cur == max_leaves:
        return tree
    dl = max_leaves - cur

    def pad(x, extra):
        return jnp.pad(x, (0, extra))

    return tree._replace(
        split_feature=pad(tree.split_feature, dl),
        split_feature_real=pad(tree.split_feature_real, dl),
        threshold_bin=pad(tree.threshold_bin, dl),
        threshold_real=pad(tree.threshold_real, dl),
        decision_type=pad(tree.decision_type, dl),
        left_child=pad(tree.left_child, dl),
        right_child=pad(tree.right_child, dl),
        split_gain=pad(tree.split_gain, dl),
        internal_value=pad(tree.internal_value, dl),
        internal_count=pad(tree.internal_count, dl),
        leaf_value=pad(tree.leaf_value, dl),
        leaf_count=pad(tree.leaf_count, dl),
        leaf_parent=pad(tree.leaf_parent, dl),
        leaf_depth=pad(tree.leaf_depth, dl),
    )


def stack_trees(trees) -> Tree:
    """Stack per-tree pytrees into one batched Tree (leading axis =
    tree) — the ensemble-as-one-pytree layout this module's docstring
    promises.  Replaces the reference's per-tree prediction loop
    (gbdt.cpp:388-426) with a single device program."""
    max_l = max(t.max_leaves for t in trees)
    trees = [pad_tree(t, max_l) for t in trees]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


@jax.jit
def ensemble_sum_raw(stacked: Tree, X: jax.Array) -> jax.Array:
    """Σ over trees of per-row outputs on RAW features.

    ``stacked`` has leading axes [n_iter, K]; returns [K, n].  A
    lax.scan over iterations (each step vmaps the K per-class trees)
    keeps memory at O(K * n) while compiling to ONE dispatch for the
    whole ensemble — vs. the reference's per-tree threaded row loop
    (predictor.hpp:82, tree.cpp:98-122)."""
    K, n = stacked.leaf_value.shape[1], X.shape[0]

    def step(acc, trees_k):
        out = jax.vmap(lambda t: predict_raw(t, X))(trees_k)
        return acc + out, None

    acc, _ = jax.lax.scan(step, jnp.zeros((K, n), jnp.float32), stacked)
    return acc


@jax.jit
def ensemble_sum_binned(stacked: Tree, X_bin: jax.Array) -> jax.Array:
    """Σ over trees on BINNED features; stacked axes [n_iter, K] -> [K, n]."""
    K, n = stacked.leaf_value.shape[1], X_bin.shape[0]

    def step(acc, trees_k):
        out = jax.vmap(lambda t: predict_binned(t, X_bin))(trees_k)
        return acc + out, None

    acc, _ = jax.lax.scan(step, jnp.zeros((K, n), jnp.float32), stacked)
    return acc


@jax.jit
def ensemble_leaves_raw(stacked: Tree, X: jax.Array) -> jax.Array:
    """Per-tree leaf indices on raw features: stacked leading axis [T]
    -> [T, n] (PredictLeafIndex, gbdt.cpp:647-655)."""
    return jax.vmap(lambda t: predict_leaf_raw(t, X))(stacked)


# ---------------------------------------------------------------- host side
def float32_thresholds(bounds) -> np.ndarray:
    """Bin upper bounds as the model's float32 thresholds: each the
    LARGEST float32 at most the bound, so that a float32 value is ``<=``
    the threshold exactly when training binned it at or below the bound
    (a bound is a float64 midpoint, and the nearest float32 can be a
    value of the data itself); +inf (and a missing bin's NaN) as float32
    max."""
    v = np.asarray(bounds, np.float64)
    f = np.where(np.isfinite(v), v, np.finfo(np.float32).max).astype(
        np.float32)
    return np.where(f > v, np.nextafter(f, np.float32(-np.inf)), f)


def pack_threshold_bounds(bin_thresholds: list, real_feature_indices):
    """Host-side, once per dataset: the per-feature bin upper-bound lists
    as one padded [F, Bmax] f32 matrix (``float32_thresholds``, as
    finalize_thresholds takes them) plus the real-feature index vector —
    the operands of finalize_thresholds_device."""
    F = len(bin_thresholds)
    bmax = max((len(b) for b in bin_thresholds), default=1)
    mat = np.full((max(F, 1), max(bmax, 1)), np.finfo(np.float32).max,
                  np.float32)
    for f, bounds in enumerate(bin_thresholds):
        mat[f, :len(bounds)] = float32_thresholds(bounds)
        # clip semantics of the host path: bins past the list reuse the
        # last bound
        mat[f, len(bounds):] = mat[f, max(len(bounds) - 1, 0)]
    return (
        jnp.asarray(mat),
        jnp.asarray(np.asarray(real_feature_indices, np.int32)),
    )


def finalize_thresholds_device(tree: Tree, bounds_mat, real_feat) -> Tree:
    """finalize_thresholds as pure device ops — the host version's
    np.asarray/int() force a full device sync per built tree, which
    drains the dispatch pipeline (round-3 profiling; the cost on this
    machine is not measured).  Same outputs: real thresholds from
    the bin upper bounds, real feature ids, -1/0 on non-split nodes."""
    sf = tree.split_feature
    is_split = sf >= 0
    fc = jnp.maximum(sf, 0)
    tb = jnp.clip(tree.threshold_bin, 0, bounds_mat.shape[1] - 1)
    tr = jnp.where(is_split, bounds_mat[fc, tb], 0.0).astype(jnp.float32)
    sfr = jnp.where(is_split, real_feat[fc], -1).astype(jnp.int32)
    return tree._replace(threshold_real=tr, split_feature_real=sfr)


def finalize_thresholds(tree: Tree, bin_thresholds: list, real_feature_indices: np.ndarray) -> Tree:
    """Fill threshold_real / split_feature_real from bin mappers (host-side,
    once per built tree).  For numerical features the real threshold is the
    bin's upper bound (matching how the reference stores thresholds for raw
    prediction, serial_tree_learner.cpp Split -> BinToValue); categorical
    thresholds are the category id."""
    sf = np.asarray(tree.split_feature)
    tb = np.asarray(tree.threshold_bin)
    nl = int(tree.num_leaves)
    tr = np.zeros_like(np.asarray(tree.threshold_real))
    sfr = np.full_like(sf, -1)
    for i in range(nl - 1):
        f = int(sf[i])
        if f >= 0:
            bounds = bin_thresholds[f]
            b = min(int(tb[i]), len(bounds) - 1)
            v = bounds[b]
            # +inf upper bound (last bin) can't be a numerical threshold;
            # it never appears because t <= num_bin-2 for numerical splits
            tr[i] = float32_thresholds(v)
            sfr[i] = real_feature_indices[f]
    return tree._replace(
        threshold_real=jnp.asarray(tr), split_feature_real=jnp.asarray(sfr)
    )
