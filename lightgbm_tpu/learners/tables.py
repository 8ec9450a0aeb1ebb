"""The packed tables of best-first growth, shared by the two leaf-wise
growers (learners/serial.py, learners/fused.py).

All per-leaf scalar state lives in a few ``[rows, L]`` matrices so one
split updates two matrix COLUMNS instead of ~60 individual ``[L]``
arrays (half the device time at 100k rows / 63 leaves was per-op launch
gaps from the unpacked representation's ~100 tiny ops a split):

    best_mat [16, L] acc_dt : a leaf's best split (rows 0-10, the Pallas
                              search kernels' result row), the leaf
                              half of the Tree (rows 11-14) and, where
                              the table has missing values, whether the
                              best split sends them left (row 15)
    pos_mat  [3, L]  i32    : leaf_begin, pos_cnt, gate_cnt
    tree_i   [5, L]  i32    : node table: feat, thr, dtype, lch, rch
    tree_f   [3, L]  f32    : node table: gain, int_value, int_count

Every function here is a pure function of those tables: none knows
which grower calls it.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..models.tree import Tree
from ..obs.device_time import phase_scope
from ..ops.split import K_MIN_SCORE, SplitResult
from ..ops.totals import root_totals

# best_mat row indices.  Rows 0-10 are EXACTLY the Pallas search
# kernels' packed [2, 16] result layout (ops/pallas_search._unpack), so
# a kernel result row drops into a best_mat column unchanged; rows
# 11-14 carry the per-leaf half of the Tree so the same two column
# writes cover split state AND leaf bookkeeping.  Feature/threshold/
# counts ride as floats — exact to 2^24, the same envelope the f32
# kernel result already imposes.
_BG, _BF, _BT = 0, 1, 2
_BLSG, _BLSH, _BLC = 3, 4, 5
_BRSG, _BRSH, _BRC = 6, 7, 8
_BLO, _BRO = 9, 10
_BLV, _BLCNT, _BLPAR, _BLDEP = 11, 12, 13, 14
_BDL = 15
_BROWS = 16
# decision_type bits (LightGBM 2.x): categorical, default left, and the
# missing type NaN in bits 2-3
DT_CATEGORICAL, DT_DEFAULT_LEFT, DT_MISSING_NAN = 1, 2, 8


def _sr_row(sr: SplitResult, dt):
    """SplitResult -> kernel-result row layout [11(, L)]."""
    return jnp.stack([
        sr.gain.astype(dt), sr.feature.astype(dt), sr.threshold.astype(dt),
        sr.left_sum_grad.astype(dt), sr.left_sum_hess.astype(dt),
        sr.left_count.astype(dt),
        sr.right_sum_grad.astype(dt), sr.right_sum_hess.astype(dt),
        sr.right_count.astype(dt),
        sr.left_output.astype(dt), sr.right_output.astype(dt),
    ])


def root_sums(grad, hess, bag_mask):
    """The root's (Σg, Σh, count) (BeforeTrain / LeafSplits::Init,
    leaf_splits.hpp:51-92).  Σg and Σh are exact up to a fixed grid
    (ops/totals.py), so ACCURATE -- the root's gain and every
    categorical ``total - bin`` read these two, and a row-by-row float32
    accumulation read a varying hessian 0.13% high at 9M rows, which the
    first leaf's chain inherited whole -- and INDEPENDENT OF ORDER: a
    masked-out row adds an exact 0.0 wherever it rides along, which the
    base-row-mask contract (cv bin-once trains fold boosters on the full
    matrix and pins their metrics bitwise to subset-trained ones) and
    the batched forest grower's stacked-vs-loop pin rest on.
    tests/test_root_totals.py holds both properties,
    tests/test_reference_agreement.py the leaves that follow.  The count
    stays jnp.sum: counts are exact small integers in any grouping."""
    sum_g0, sum_h0 = root_totals(grad, hess, bag_mask)
    return sum_g0, sum_h0, jnp.sum(bag_mask)


def root_tables(root_best: SplitResult, acc_dt, L: int, n: int,
                gate=None, default_left=None):
    """(best_mat, pos_mat, tree_i, tree_f) of a tree that is its root:
    leaf 0 holds every row and ``root_best``.  ``gate`` is the root's
    entry in pos_mat's third row, ``n`` where None (the data-parallel
    fused grower keeps there a leaf's bagged row count over every chip,
    in int32: learners/fused.py).  ``default_left``: the root split's
    side for missing rows, where the table has them."""
    best_mat = (
        jnp.zeros((_BROWS, L), acc_dt)
        .at[_BG].set(K_MIN_SCORE)
        .at[_BF].set(-1.0)
        .at[_BLPAR].set(-1.0)  # empty_tree's leaf_parent = -1
    )
    best_mat = jax.lax.dynamic_update_slice(
        best_mat, _sr_row(root_best, acc_dt)[:, None], (0, 0))
    if default_left is not None:
        best_mat = best_mat.at[_BDL, 0].set(default_left.astype(acc_dt))
    # root gate: every shard's padded local row count is the same n
    pos_mat = jnp.zeros((3, L), jnp.int32).at[1, 0].set(n).at[2, 0].set(
        n if gate is None else gate)
    tree_i = jnp.zeros((5, L), jnp.int32).at[0].set(-1)
    tree_f = jnp.zeros((3, L), jnp.float32)
    return best_mat, pos_mat, tree_i, tree_f


def pick_leaf(best_mat):
    """The leaf with the globally best gain, and whether it splits."""
    with phase_scope("grow.select"):
        gain_row = best_mat[_BG]
        best_leaf = jnp.argmax(gain_row).astype(jnp.int32)
        do_split = gain_row[best_leaf] > 0.0
    return best_leaf, do_split


class SplitColumns(NamedTuple):
    """What one split reads of the tables: the parent's and the
    prospective new leaf's columns, and the scalars decoded from them."""

    bcol: jax.Array  # [16] best_mat column of the split leaf
    bcolN: jax.Array  # [16] best_mat column of the new leaf
    pcol: jax.Array  # [3] pos_mat column of the split leaf
    pcolN: jax.Array  # [3] pos_mat column of the new leaf
    f: jax.Array
    thr: jax.Array
    is_cat: jax.Array
    lsg: jax.Array
    lsh: jax.Array
    lc: jax.Array
    rsg: jax.Array
    rsh: jax.Array
    rc: jax.Array
    depth_child: jax.Array
    begin: jax.Array
    pcnt: jax.Array
    gate: jax.Array
    # where the table has missing values: the bin that goes left besides
    # ``bin <= thr`` (the split column's missing bin if the node sends
    # missing rows left, else -1), and the node's decision_type
    miss_left: jax.Array | None = None
    decision: jax.Array | None = None


def read_split_columns(best_mat, pos_mat, best_leaf, new_leaf,
                       is_categorical, missing_bin=None) -> SplitColumns:
    """ALL per-leaf scalar reads of a split come from four column slices
    (parent + prospective-new-leaf columns of the two packed matrices)
    instead of ~40 individual [L]-array gathers.  ``missing_bin`` [F]
    (-1: none), where the table has missing values."""
    with phase_scope("grow.select"):
        z0 = jnp.int32(0)
        bcol = jax.lax.dynamic_slice(
            best_mat, (z0, best_leaf), (_BROWS, 1))[:, 0]
        bcolN = jax.lax.dynamic_slice(
            best_mat, (z0, new_leaf), (_BROWS, 1))[:, 0]
        pcol = jax.lax.dynamic_slice(pos_mat, (z0, best_leaf), (3, 1))[:, 0]
        pcolN = jax.lax.dynamic_slice(pos_mat, (z0, new_leaf), (3, 1))[:, 0]

        f = bcol[_BF].astype(jnp.int32)
        c = SplitColumns(
            bcol=bcol, bcolN=bcolN, pcol=pcol, pcolN=pcolN,
            f=f,
            thr=bcol[_BT].astype(jnp.int32),
            is_cat=is_categorical[jnp.maximum(f, 0)],
            lsg=bcol[_BLSG], lsh=bcol[_BLSH], lc=bcol[_BLC],
            rsg=bcol[_BRSG], rsh=bcol[_BRSH], rc=bcol[_BRC],
            depth_child=bcol[_BLDEP].astype(jnp.int32) + 1,
            # the tier gate (cross-shard max of the parent's positional
            # count) was stored at the split that CREATED this leaf
            begin=pcol[0], pcnt=pcol[1], gate=pcol[2],
        )
        if missing_bin is None:
            return c
        mb = missing_bin[jnp.maximum(f, 0)]
        dl = (bcol[_BDL] > 0) & (mb >= 0)
        return c._replace(
            miss_left=jnp.where(dl, mb, -1),
            decision=(c.is_cat.astype(jnp.int32)
                      + jnp.where(mb >= 0, DT_MISSING_NAN, 0)
                      + jnp.where(dl, DT_DEFAULT_LEFT, 0)))


def write_split(best_mat, pos_mat, tree_i, tree_f, c: SplitColumns,
                node, best_leaf, new_leaf, do_split, rowL, rowR,
                nleft, nright, nleft_gate, nright_gate,
                default_left_L=None, default_left_R=None):
    """The packed column updates of one split, every store MASKED on
    ``do_split`` (an exhausted tree round-trips its tables unchanged;
    a ``lax.cond`` with an identity branch made XLA copy the carried
    buffers every iteration).  Per-leaf split state + the leaf half of
    the tree ride best_mat (two column writes); partition ranges ride
    pos_mat (two column writes); the node half of the tree rides
    tree_i/tree_f (three column read-modify-writes).  ``rowL``/``rowR``
    are the children's best splits in the kernel-result row layout, and
    ``default_left_L``/``_R`` their sides for missing rows where the
    table has them (best_mat row 15)."""
    z0 = jnp.int32(0)
    bcol = c.bcol
    dt = bcol.dtype
    node_f = node.astype(dt)
    dep_f = c.depth_child.astype(dt)
    zero = jnp.zeros((), dt)
    dlL = zero if default_left_L is None else default_left_L.astype(dt)
    dlR = zero if default_left_R is None else default_left_R.astype(dt)
    tailL = jnp.stack([bcol[_BLO], c.lc, node_f, dep_f, dlL])
    tailR = jnp.stack([bcol[_BRO], c.rc, node_f, dep_f, dlR])
    colL = jnp.where(do_split, jnp.concatenate([rowL, tailL]), bcol)
    colR = jnp.where(do_split, jnp.concatenate([rowR, tailR]), c.bcolN)
    best_mat = jax.lax.dynamic_update_slice(
        best_mat, colL[:, None], (z0, best_leaf))
    best_mat = jax.lax.dynamic_update_slice(
        best_mat, colR[:, None], (z0, new_leaf))

    pcL = jnp.where(
        do_split, jnp.stack([c.begin, nleft, nleft_gate]), c.pcol)
    pcR = jnp.where(
        do_split, jnp.stack([c.begin + nleft, nright, nright_gate]),
        c.pcolN)
    pos_mat = jax.lax.dynamic_update_slice(
        pos_mat, pcL[:, None], (z0, best_leaf))
    pos_mat = jax.lax.dynamic_update_slice(
        pos_mat, pcR[:, None], (z0, new_leaf))

    # ---- tree bookkeeping (Tree::Split, tree.cpp:52-96): fix up the
    # parent's child pointer (the split leaf keeps its node id ~leaf
    # until it becomes internal node ``node``), then write the new
    # node's column.  pidx < node always, so the two writes never
    # collide.
    parent = bcol[_BLPAR].astype(jnp.int32)
    has_parent = parent >= 0
    pidx = jnp.maximum(parent, 0)
    colP = jax.lax.dynamic_slice(tree_i, (z0, pidx), (5, 1))[:, 0]
    was_left = colP[3] == ~best_leaf
    colP = colP.at[3].set(
        jnp.where(do_split & has_parent & was_left, node, colP[3]))
    colP = colP.at[4].set(
        jnp.where(do_split & has_parent & ~was_left, node, colP[4]))
    tree_i = jax.lax.dynamic_update_slice(tree_i, colP[:, None], (z0, pidx))
    colNd = jax.lax.dynamic_slice(tree_i, (z0, node), (5, 1))[:, 0]
    colNd = jnp.where(
        do_split,
        jnp.stack(
            [c.f, c.thr,
             c.is_cat.astype(jnp.int32) if c.decision is None
             else c.decision, ~best_leaf, ~new_leaf]),
        colNd,
    )
    tree_i = jax.lax.dynamic_update_slice(tree_i, colNd[:, None], (z0, node))

    colTf = jax.lax.dynamic_slice(tree_f, (z0, node), (3, 1))[:, 0]
    colTf = jnp.where(
        do_split,
        # cast explicitly: under hist_dtype=float64 the split stats
        # are f64 while tree buffers stay f32
        jnp.stack([bcol[_BG], bcol[_BLV], c.lc + c.rc]).astype(jnp.float32),
        colTf,
    )
    tree_f = jax.lax.dynamic_update_slice(tree_f, colTf[:, None], (z0, node))
    return best_mat, pos_mat, tree_i, tree_f


def unpack_tree(nleaves, best_mat, tree_i, tree_f, L: int) -> Tree:
    """The Tree pytree from the packed node/leaf tables (one set of
    static row slices per TREE, replacing the ~30 per-SPLIT masked
    stores of the unpacked representation)."""
    li = L - 1
    return Tree(
        num_leaves=nleaves,
        split_feature=tree_i[0, :li],
        split_feature_real=jnp.full(li, -1, jnp.int32),
        threshold_bin=tree_i[1, :li],
        threshold_real=jnp.zeros(li, jnp.float32),
        decision_type=tree_i[2, :li],
        left_child=tree_i[3, :li],
        right_child=tree_i[4, :li],
        split_gain=tree_f[0, :li],
        internal_value=tree_f[1, :li],
        internal_count=tree_f[2, :li],
        leaf_value=best_mat[_BLV].astype(jnp.float32),
        leaf_count=best_mat[_BLCNT].astype(jnp.float32),
        leaf_parent=best_mat[_BLPAR].astype(jnp.int32),
        leaf_depth=best_mat[_BLDEP].astype(jnp.int32),
    )


def leaf_ids_from_record(rec, F: int, k: int, n: int):
    """Per-row leaf assignment of a tree grown on the packed record
    (ops/record.py): the partition stamped every position's leaf id
    into the record's leaf-id row, so one contiguous read replaces a
    searchsorted over the leaf ranges (~75 ms/tree of binary-search
    gathers at 1M rows), then one unique-index scatter maps positions
    back to rows."""
    from ..ops.record import num_words  # Pallas: imported when first used

    leaf_of_pos = rec[num_words(F, k) + 4, :n]
    rows = jnp.minimum(rec[num_words(F, k) + 3, :n], n - 1)
    return scatter_leaf_ids(rows, leaf_of_pos, n)


def scatter_leaf_ids(rows, leaf_of_pos, n: int):
    """leaf_id[rows[p]] = leaf_of_pos[p]."""
    return jnp.zeros(n, jnp.int32).at[rows].set(
        leaf_of_pos, unique_indices=True)
