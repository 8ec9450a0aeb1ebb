"""Forest-level batched dispatch: one launch advances B independent trees.

Every small-shape loss (the categorical config-3 gap, ROADMAP item 2)
is the same ~0.45 ms/split dispatch floor that only 10M-row shapes
amortize.  This module amortizes it STRUCTURALLY: stack B independent
tree-growth problems (per-tree grad/hess, bagging masks, feature
samples, per-model scalar knobs) into a leading batch axis so ONE
traced program — one dispatch per call — grows B trees instead of B
programs growing one tree each.

The B-sources routed through here (models/gbdt.py, engine.py):

* multiclass per-class trees within one boosting iteration (the K-loop
  in GBDT._train_one_iter_impl shares grad/hess batches already);
* ``engine.cv()`` folds — with the shared-binning path every fold
  trains on the SAME binned matrix under a per-fold row mask, so fold
  problems differ only in batched operands;
* ``engine.train_many()`` — N independent small models sharing one
  binned dataset (per-model configs restricted to shape-compatible
  knobs; the scalar knobs ride the batched ``TreeLearnerParams`` lanes).

Two implementations, chosen on measured evidence (docs/forest_batching.md):

* ``impl="batched"`` (default) — an EXPLICIT batched grow loop.  The
  sequential learner's strength — O(|parent|) per-split work via the
  leaf-sorted ``order`` permutation and capacity-tiered windows — is
  exactly what pessimizes under vmap: per-lane window offsets turn the
  contiguous dynamic-slices into per-element gathers/scatters, and the
  tier ``lax.cond`` chains into execute-every-branch selects.  The
  batched loop therefore drops the permutation entirely and carries a
  direct row->leaf map ``leaf_id[B, n]``: the partition is a masked
  elementwise update, the smaller child's histogram is a full-data
  masked segment-sum, and per-leaf bookkeeping is two column writes on
  [B, rows, L] tables.  Per-split work is O(n) per lane — the right
  trade at the small shapes forest batching exists for (the sequential
  windows bottom out at the 512-row tier floor anyway, so for n at or
  below ~512 the batched loop does no more histogram work per lane
  than the sequential one).
* ``impl="vmap"`` — ``jax.vmap`` over the UNMODIFIED sequential grow
  program.  Kept as the reference lowering and A/B foil: on the CPU
  container it is parity-exact but ~1x (no win) at the 512-row tier
  floor and up to ~5x SLOWER once multiple capacity tiers exist,
  because every tier branch executes under batched predicates.

Parity contract (tier-1 pinned in tests/test_forest_batching.py):
batching changes scheduling, never math — every lane's tree is
byte-identical to the tree ``grow_tree`` grows for that lane's inputs
alone.  For the explicit loop this holds because (a) the stable
partition keeps within-leaf rows in ascending row order, so the
full-data masked histogram accumulates the same nonzero contributions
in the same order as the sequential window gather (masked rows add
exact zeros, which cannot perturb an accumulator), and (b) the split
search / leaf-value math is the same ``find_best_split*`` program,
vmapped — reductions stay per-lane over the same axes.

Kernel note: the batched path always uses the jnp reference search and
segment-sum histograms.  Whether vmap pessimizes the Pallas
search/histogram kernels has not been measured on the chip — the
eligibility gate in models/gbdt.py falls back to the sequential
learner whenever a kernel path is selected.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..models.tree import Tree
from ..obs import telemetry
from ..ops.split import K_MIN_SCORE, find_best_split, find_best_split_leaves
from ..ops.totals import root_totals
from .serial import TreeLearnerParams, grow_tree
from .tables import (
    _BF, _BG, _BLC, _BLDEP, _BLO, _BLPAR, _BLSG, _BLSH, _BLV, _BLCNT,
    _BRC, _BRO, _BROWS, _BRSG, _BRSH, _BT, _sr_row,
)

# batch every per-tree operand; share the binned matrix and the
# per-feature metadata across lanes.  TreeLearnerParams is batched
# per-FIELD ([B] scalars) so train_many can give each model its own
# regularization/depth knobs without retracing.
_IN_AXES = (
    None,  # bins_T        [F, n]    shared
    0,     # grad          [B, n]
    0,     # hess          [B, n]
    0,     # bag_mask      [B, n]
    0,     # feature_mask  [B, F]
    None,  # num_bins_per_feature [F] shared
    None,  # is_categorical       [F] shared
    TreeLearnerParams(0, 0, 0, 0, 0, 0),  # per-lane scalar knobs
)

# the two-child search, one lane per tree: hist [B, 2, F, nb, 3],
# leaf totals [B, 2], per-lane feature masks and scalar knobs
_search2_lanes = jax.vmap(
    find_best_split_leaves,
    in_axes=(0, 0, 0, 0, 0, None, None, 0, 0, 0, 0, 0, 0),
)
# the root search: one leaf per lane
_search_root = jax.vmap(
    find_best_split,
    in_axes=(0, 0, 0, 0, 0, None, None, 0, 0, 0, 0, 0, 0),
)


def _batched_hist(bins_i32, grad, hess, mask, num_bins: int):
    """hist[B, F, num_bins, 3] — per-lane full-data masked histogram,
    the exact per-lane op sequence of ops.histogram_feature_major so
    each lane's result is bitwise the sequential kernel's."""
    gm = grad * mask
    hm = hess * mask
    stats = jnp.stack([gm, hm, mask], axis=-1)  # [B, n, 3]

    def lane(st):
        def per_feature(b_row):
            return jax.ops.segment_sum(st, b_row, num_segments=num_bins)

        return jax.vmap(per_feature)(bins_i32)

    return jax.vmap(lane)(stats)


class _ForestState(NamedTuple):
    hists: jax.Array    # [B, L, F, nb, 3]
    best_mat: jax.Array  # [B, 16, L]
    tree_i: jax.Array   # [B, 5, L]
    tree_f: jax.Array   # [B, 3, L]
    leaf_id: jax.Array  # [B, n] direct row->leaf map (no order permutation)
    nleaves: jax.Array  # [B]


@functools.lru_cache(maxsize=None)
def make_grow_forest(num_bins: int, max_leaves: int, impl: str = "batched",
                     choice_by_mask_counts: bool = False):
    """The batched grower for a (num_bins, max_leaves) shape family.

    Returns a jitted callable
    ``(bins_T, grad[B,n], hess[B,n], bag_mask[B,n], feature_mask[B,F],
    nbpf, is_cat, params[B-per-field]) -> (Tree[B,...], leaf_id[B,n])``.

    Cached per (num_bins, max_leaves, impl) so every caller — the
    multiclass K-loop, cv folds, train_many — shares ONE jit cache: a
    given (B, n, F) shape traces once process-wide, which is what the
    tier-1 ``grow_traces`` pin asserts.
    """
    if impl == "vmap":
        core = functools.partial(
            # the UNJITTED grow core: vmap of the jitted wrapper would
            # nest jit-under-vmap and re-trace per outer call; the
            # single outer jit below owns caching and the trace-time
            # telemetry count inside the core fires once per trace.
            grow_tree.__wrapped__,
            num_bins=num_bins,
            max_leaves=max_leaves,
            choice_by_mask_counts=choice_by_mask_counts,
        )
        batched = jax.vmap(core, in_axes=_IN_AXES)

        def grow_forest_vmap(bins_T, grad, hess, bag_mask, feature_mask,
                             num_bins_per_feature, is_categorical,
                             params: TreeLearnerParams):
            return batched(bins_T, grad, hess, bag_mask, feature_mask,
                           num_bins_per_feature, is_categorical, params)

        return jax.jit(grow_forest_vmap)
    if impl != "batched":
        raise ValueError(f"unknown forest impl: {impl!r}")

    L = max_leaves

    def grow_forest(bins_T, grad, hess, bag_mask, feature_mask,
                    num_bins_per_feature, is_categorical,
                    params: TreeLearnerParams) -> Tuple[Tree, jax.Array]:
        telemetry.count("grow_traces")  # trace-time: once per (B, shape)
        B, n = grad.shape
        dt = grad.dtype
        bT = bins_T.astype(jnp.int32)
        lanes = jnp.arange(B, dtype=jnp.int32)

        # ---- root (mirrors serial.py's BeforeTrain block, one lane each)
        hist0 = _batched_hist(bT, grad, hess, bag_mask, num_bins)
        # per-lane root totals, the serial root's (ops/totals.py): exact
        # up to a fixed grid, so accurate and the same bits whatever
        # zero-mask rows are interleaved -- the parity pins
        # (stacked-vs-loop, cv bin-once) depend on that
        sum_g0, sum_h0 = root_totals(grad, hess, bag_mask)
        cnt0 = jnp.sum(bag_mask, axis=1)
        can0 = (params.max_depth <= 0) | (0 < params.max_depth)
        root_best = _search_root(
            hist0, sum_g0, sum_h0, cnt0,
            feature_mask, num_bins_per_feature, is_categorical,
            params.min_data_in_leaf, params.min_sum_hessian_in_leaf,
            params.lambda_l1, params.lambda_l2, params.min_gain_to_split,
            can0,
        )
        bm = (
            jnp.zeros((B, _BROWS, L), dt)
            .at[:, _BG].set(K_MIN_SCORE)
            .at[:, _BF].set(-1.0)
            .at[:, _BLPAR].set(-1.0)
        )
        bm = bm.at[:, :11, 0].set(_sr_row(root_best, dt).T)
        state = _ForestState(
            hists=jnp.zeros((B, L) + hist0.shape[1:], dt).at[:, 0].set(hist0),
            best_mat=bm,
            tree_i=jnp.zeros((B, 5, L), jnp.int32).at[:, 0].set(-1),
            tree_f=jnp.zeros((B, 3, L), jnp.float32),
            leaf_id=jnp.zeros((B, n), jnp.int32),
            nleaves=jnp.ones(B, jnp.int32),
        )

        def body(step, st: _ForestState) -> _ForestState:
            node = jnp.int32(step)
            new_leaf = node + 1
            gain_row = st.best_mat[:, _BG, :]  # [B, L]
            best_leaf = jnp.argmax(gain_row, axis=1).astype(jnp.int32)
            do_split = jnp.take_along_axis(
                gain_row, best_leaf[:, None], axis=1)[:, 0] > 0.0

            bcol = jnp.take_along_axis(
                st.best_mat, best_leaf[:, None, None], axis=2)[:, :, 0]
            bcolN = jax.lax.dynamic_index_in_dim(
                st.best_mat, new_leaf, axis=2, keepdims=False)
            f = bcol[:, _BF].astype(jnp.int32)
            thr = bcol[:, _BT].astype(jnp.int32)
            isc = is_categorical[jnp.maximum(f, 0)]
            lsg, lsh, lc = bcol[:, _BLSG], bcol[:, _BLSH], bcol[:, _BLC]
            rsg, rsh, rc = bcol[:, _BRSG], bcol[:, _BRSH], bcol[:, _BRC]
            depth_child = bcol[:, _BLDEP].astype(jnp.int32) + 1

            # ---- partition: a masked elementwise update of the direct
            # row->leaf map — the batched replacement for the sequential
            # order-permutation scatter (left child keeps the parent's
            # leaf index, right child takes the fresh one, tree.cpp:78-89)
            vals = bT[jnp.maximum(f, 0)]  # [B, n] per-lane feature rows
            in_leaf = st.leaf_id == best_leaf[:, None]
            dec = jnp.where(
                isc[:, None], vals == thr[:, None], vals <= thr[:, None])
            go = dec & in_leaf
            go_r = in_leaf & ~dec
            nleft = jnp.sum(go, axis=1, dtype=jnp.int32)
            pcnt = jnp.sum(in_leaf, axis=1, dtype=jnp.int32)
            nright = pcnt - nleft
            leaf_id = jnp.where(
                go_r & do_split[:, None], new_leaf, st.leaf_id)

            # ---- smaller child's histogram as a full-data masked
            # segment-sum (bitwise the sequential window gather: same
            # nonzero contributions in the same ascending-row order);
            # sibling by subtraction (feature_histogram.hpp:97-106)
            if choice_by_mask_counts:
                # base-row-mask mode: masked counts, matching the
                # subset-trained run's positional choice (serial.py
                # carries the full argument at its small_is_left)
                small_is_left = lc <= rc
            else:
                small_is_left = nleft <= nright
            child = jnp.where(small_is_left[:, None], go, go_r)
            h_small = _batched_hist(
                bT, grad, hess, bag_mask * child.astype(dt), num_bins)
            h_parent = jnp.take_along_axis(
                st.hists, best_leaf[:, None, None, None, None],
                axis=1)[:, 0]
            h_prev_new = jax.lax.dynamic_index_in_dim(
                st.hists, new_leaf, axis=1, keepdims=False)
            h_large = h_parent - h_small
            sl = small_is_left[:, None, None, None]
            h_left = jnp.where(sl, h_small, h_large)
            h_right = jnp.where(sl, h_large, h_small)

            # ---- both children's best splits, one batched search
            can = (params.max_depth <= 0) | (depth_child < params.max_depth)
            res = _search2_lanes(
                jnp.stack([h_left, h_right], axis=1),
                jnp.stack([lsg, rsg], axis=1),
                jnp.stack([lsh, rsh], axis=1),
                jnp.stack([lc, rc], axis=1),
                feature_mask, num_bins_per_feature, is_categorical,
                params.min_data_in_leaf, params.min_sum_hessian_in_leaf,
                params.lambda_l1, params.lambda_l2,
                params.min_gain_to_split,
                jnp.stack([can, can], axis=1),
            )
            rowL = _sr_row(type(res)(*[a[:, 0] for a in res]), dt).T
            rowR = _sr_row(type(res)(*[a[:, 1] for a in res]), dt).T

            # ---- in-place hists update behind the same barrier idiom
            # as the sequential loop: after it the buffer has no other
            # live readers, so the two row writes stay in place
            dsm = do_split[:, None, None, None]
            new_l = jnp.where(dsm, h_left, h_parent)
            new_r = jnp.where(dsm, h_right, h_prev_new)
            new_l, new_r, rowL, rowR, hists_in = jax.lax.optimization_barrier(
                (new_l, new_r, rowL, rowR, st.hists))
            hists = hists_in.at[lanes, best_leaf].set(
                new_l, unique_indices=True)
            hists = hists.at[:, new_leaf].set(new_r)

            # ---- packed column updates (two columns per table)
            node_f = jnp.broadcast_to(node.astype(dt), lc.shape)
            dep_f = depth_child.astype(dt)
            zero = jnp.zeros_like(lc)
            tailL = jnp.stack([bcol[:, _BLO], lc, node_f, dep_f, zero], 1)
            tailR = jnp.stack([bcol[:, _BRO], rc, node_f, dep_f, zero], 1)
            colL = jnp.where(do_split[:, None],
                             jnp.concatenate([rowL, tailL], axis=1), bcol)
            colR = jnp.where(do_split[:, None],
                             jnp.concatenate([rowR, tailR], axis=1), bcolN)
            best_mat = st.best_mat.at[lanes, :, best_leaf].set(
                colL, unique_indices=True)
            best_mat = best_mat.at[:, :, new_leaf].set(colR)

            # ---- tree bookkeeping (Tree::Split, tree.cpp:52-96)
            parent = bcol[:, _BLPAR].astype(jnp.int32)
            has_parent = parent >= 0
            pidx = jnp.maximum(parent, 0)
            colP = jnp.take_along_axis(
                st.tree_i, pidx[:, None, None], axis=2)[:, :, 0]
            was_left = colP[:, 3] == ~best_leaf
            colP = colP.at[:, 3].set(jnp.where(
                do_split & has_parent & was_left, node, colP[:, 3]))
            colP = colP.at[:, 4].set(jnp.where(
                do_split & has_parent & ~was_left, node, colP[:, 4]))
            tree_i = st.tree_i.at[lanes, :, pidx].set(
                colP, unique_indices=True)
            colNd = jax.lax.dynamic_index_in_dim(
                tree_i, node, axis=2, keepdims=False)
            colNd = jnp.where(
                do_split[:, None],
                jnp.stack([
                    f, thr, isc.astype(jnp.int32), ~best_leaf,
                    jnp.broadcast_to(~new_leaf, f.shape)], axis=1),
                colNd,
            )
            tree_i = tree_i.at[:, :, node].set(colNd)

            colTf = jax.lax.dynamic_index_in_dim(
                st.tree_f, node, axis=2, keepdims=False)
            colTf = jnp.where(
                do_split[:, None],
                jnp.stack([bcol[:, _BG], bcol[:, _BLV], lc + rc],
                          axis=1).astype(jnp.float32),
                colTf,
            )
            tree_f = st.tree_f.at[:, :, node].set(colTf)

            return _ForestState(
                hists=hists,
                best_mat=best_mat,
                tree_i=tree_i,
                tree_f=tree_f,
                leaf_id=leaf_id,
                nleaves=st.nleaves + do_split.astype(jnp.int32),
            )

        state = jax.lax.fori_loop(0, L - 1, body, state)

        li = L - 1
        B_ = state.tree_i.shape[0]
        tree = Tree(
            num_leaves=state.nleaves,
            split_feature=state.tree_i[:, 0, :li],
            split_feature_real=jnp.full((B_, li), -1, jnp.int32),
            threshold_bin=state.tree_i[:, 1, :li],
            threshold_real=jnp.zeros((B_, li), jnp.float32),
            decision_type=state.tree_i[:, 2, :li],
            left_child=state.tree_i[:, 3, :li],
            right_child=state.tree_i[:, 4, :li],
            split_gain=state.tree_f[:, 0, :li],
            internal_value=state.tree_f[:, 1, :li],
            internal_count=state.tree_f[:, 2, :li],
            leaf_value=state.best_mat[:, _BLV].astype(jnp.float32),
            leaf_count=state.best_mat[:, _BLCNT].astype(jnp.float32),
            leaf_parent=state.best_mat[:, _BLPAR].astype(jnp.int32),
            leaf_depth=state.best_mat[:, _BLDEP].astype(jnp.int32),
        )
        return tree, state.leaf_id

    return jax.jit(grow_forest)


def stack_learner_params(params_list) -> TreeLearnerParams:
    """[B] TreeLearnerParams -> one TreeLearnerParams of [B] arrays
    (the batched-lane layout ``make_grow_forest`` expects)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)


def unstack_tree(trees: Tree, i: int) -> Tree:
    """Lane ``i`` of a batched Tree pytree as a plain per-tree pytree
    (the shape the post-grow step and the models list consume)."""
    return jax.tree.map(lambda a: a[i], trees)


def grow_forest_trees(bins_T, grads, hesses, bag_masks, feature_masks,
                      num_bins_per_feature, is_categorical, params_list,
                      *, num_bins: int, max_leaves: int,
                      impl: str = "batched"):
    """Convenience one-shot: stack per-lane operands, run the batched
    grower, count the dispatch.  ``grads``/``hesses``/``bag_masks``/
    ``feature_masks`` are sequences of per-lane arrays (or already
    stacked [B, ...] arrays); ``params_list`` a sequence of
    TreeLearnerParams (or one batched TreeLearnerParams)."""
    stk = lambda v: v if isinstance(v, jax.Array) else jnp.stack(list(v))  # noqa: E731
    params = (params_list if isinstance(params_list, TreeLearnerParams)
              and getattr(params_list.max_depth, "ndim", 0) == 1
              else stack_learner_params(list(params_list)))
    gf = make_grow_forest(num_bins, max_leaves, impl)
    trees, leaf_ids = gf(
        bins_T, stk(grads), stk(hesses), stk(bag_masks),
        stk(feature_masks), num_bins_per_feature, is_categorical, params,
    )
    telemetry.count("forest_dispatches")
    telemetry.count("forest_batched_trees", int(leaf_ids.shape[0]))
    return trees, leaf_ids
