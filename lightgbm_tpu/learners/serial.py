"""The canonical leaf-wise tree learner, fully jittable.

TPU-native re-design of SerialTreeLearner
(src/treelearner/serial_tree_learner.cpp:116-150): the same best-first
growth — repeatedly split the leaf with the globally best gain until the
``num_leaves`` budget or no positive gain remains — expressed as a
fixed-shape ``lax.fori_loop``.

There are TWO leaf-wise growers, and ``models/gbdt.py select_grower`` is
the one place that picks: learners/fused.py is what a TPU chip runs for
serial float32 training (packed record, one launch pair a split, no
``lax.cond``); THIS one serves everything else — the CPU, float64
accumulation, the parallel learners' hooks, the cv row mask and
``histogram_pool_size`` — and is the reference the fused
one is pinned to.  Histograms are in the canonical ``[F, B, 3]`` layout
throughout.

* the row partition is a PERSISTENT leaf-sorted permutation ``order``
  plus per-leaf ``(begin, count)`` ranges — the reference's
  DataPartition (data_partition.hpp:91-139) re-cast for static shapes.
  Each split touches only the parent leaf's contiguous range via
  capacity-tiered ``dynamic_slice`` (a ``lax.cond`` chain picks the
  smallest static capacity that fits), so per-split work is
  O(|parent|), not O(n): the whole tree costs O(n * depth) partition
  work like the reference, instead of O(n * num_leaves).  Under
  ``record_mode`` (every parallel learner) the partition is the
  leaf-sorted packed record of ops/record.py instead, under the same
  tier chain.
* per split, only the SMALLER child's histogram is built from data —
  its rows are one contiguous ``dynamic_slice`` of ``order`` (the
  ordered-gradients gather, serial_tree_learner.cpp:259-315); the
  larger child is parent - smaller (the Subtract trick,
  feature_histogram.hpp:97-106).  Histograms for every live leaf stay
  resident in HBM (``hists[L, F, B, 3]``) unless ``hist_pool`` bounds
  them (the LRU HistogramPool, feature_histogram.hpp:337-481).
* leaf numbering matches the reference exactly (left child keeps the
  parent's leaf index, right child gets the next fresh index,
  tree.cpp:78-89), so trees are comparable node-for-node.
* every store in the split step is MASKED on the split-fired predicate
  (rather than branching with ``lax.cond``, whose pass-through branch
  forced XLA to copy the histogram buffer each iteration), so all state
  updates stay in place and an exhausted tree simply no-ops its
  remaining steps.

The data-parallel learner wraps this same step with psum'd histograms
(parallel/data_parallel.py); determinism of argmax tie-breaks keeps
parallel == serial trees (split_info.hpp:98-103 semantics).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..device import on_tpu
from ..models.tree import Tree
from ..obs import telemetry
from ..obs.device_time import phase_scope
from ..ops.histogram import histogram_feature_major
from ..ops.split import SplitResult, find_best_split, find_best_split_leaves
from . import tables
from .tables import _sr_row

# geometric step between hist/partition tier capacities (_hist_tiers)
TIER_SPACING = 2


# A histogram's count channel is float32 under the default hist_dtype:
# integers are exact in float32 only up to 2**24, so a histogram over more
# rows than that could round a bin's count (and the leaf counts and the
# min_data_in_leaf comparisons read from it).  What bounds a histogram is
# the rows one device sums: all of them on one device, one shard's where
# the fused grower deals rows to the chips of a mesh (models/gbdt.py
# _count_shards), so the envelope is checked once per
# reset_training_data against the rows of a shard.
F32_COUNT_EXACT_ROWS = 1 << 24


def check_count_envelope(num_rows: int, hist_dtype: str,
                         shards: int = 1) -> None:
    """Reject a table whose rows a shard can overflow the float32
    integer-exact range of the histogram count channel.

    Per shard, not per table, because that is all any one histogram
    holds where the rows are dealt to ``shards`` chips and summed by the
    data-parallel fused grower (learners/fused.py): each chip's channel
    counts at most ``ceil(num_rows / shards)`` rows, exactly, and the
    TREE's counts are summed over the chips in int32, exact at any
    height.  The search reads the summed float32 channel, which may round
    a count past 2**24, and that is enough: every comparison that can
    fail on a count (``min_data_in_leaf``, the smaller-child choice)
    holds a count below 2**24 on one side, which float32 holds exactly,
    and every chip reads the same sums and decides alike."""
    per_shard = -(-int(num_rows) // max(int(shards), 1))
    if hist_dtype == "float32" and per_shard > F32_COUNT_EXACT_ROWS:
        where = (f"num_data={num_rows} over {shards} shards is {per_shard} "
                 "rows a shard" if shards > 1 else f"num_data={num_rows}")
        raise ValueError(
            f"{where}, past the float32 integer-exact envelope "
            f"({F32_COUNT_EXACT_ROWS} = 2**24) of the histogram count "
            "channel: its counts could round silently.  Deal the rows to "
            "more chips (tree_learner=data on the fused grower), or set "
            "hist_dtype=float64 (the reference's double accumulation).")


class TreeLearnerParams(NamedTuple):
    """Scalar tree-growth constraints (TreeConfig, config.h:165-190)."""

    min_data_in_leaf: jax.Array
    min_sum_hessian_in_leaf: jax.Array
    lambda_l1: jax.Array
    lambda_l2: jax.Array
    min_gain_to_split: jax.Array
    max_depth: jax.Array  # <= 0 means unlimited

    @staticmethod
    def from_config(cfg) -> "TreeLearnerParams":
        return TreeLearnerParams(
            min_data_in_leaf=jnp.float32(cfg.min_data_in_leaf),
            min_sum_hessian_in_leaf=jnp.float32(cfg.min_sum_hessian_in_leaf),
            lambda_l1=jnp.float32(cfg.lambda_l1),
            lambda_l2=jnp.float32(cfg.lambda_l2),
            min_gain_to_split=jnp.float32(cfg.min_gain_to_split),
            max_depth=jnp.int32(cfg.max_depth),
        )


class _GrowState(NamedTuple):
    """Loop carry of the best-first growth (tables: learners/tables.py)."""

    order: jax.Array  # [n + max_cap] leaf-sorted row permutation (pad = n),
    # or the [W, n_pad] packed record under record_mode
    pos_mat: jax.Array  # [3, L] i32 rows: (leaf_begin, pos_cnt, gate_cnt)
    hists: jax.Array  # [L, F, B, 3] resident, or [P, F, B, 3] pooled
    slot_of: jax.Array  # [L] int32 pool slot per leaf, -1 = evicted ([0] off)
    slot_leaf: jax.Array  # [P] int32 leaf occupying each slot, -1 = free
    slot_last: jax.Array  # [P] int32 last-use step per slot, -1 = free
    best_mat: jax.Array  # [16, L] acc_dt
    tree_i: jax.Array  # [5, L] i32
    tree_f: jax.Array  # [3, L] f32
    nleaves: jax.Array  # scalar int32 used-leaf count


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _hist_tiers(n: int):
    """Static slice capacities for the smaller-child histogram: fractions
    of n, lane-aligned, ascending.  Includes a full-n tier: under row
    sharding the LOCAL count of the globally-smaller child can approach
    n_local (global balance says nothing about one shard's split), so
    ceil(n/2) is not a guaranteed fit there.

    TIER_SPACING sets the geometric step between capacities: 2 wastes
    <2x gather work per split but instantiates ~9 tier bodies (one
    Mosaic kernel compile each on TPU); 4 would halve the tier count
    for <4x gather waste."""
    step = TIER_SPACING
    caps = {max(512, _round_up(n, 128))}
    frac = 2
    while frac <= 256:  # step=2 reproduces the original 2,4,...,256 set
        caps.add(max(512, _round_up(-(-n // frac), 128)))
        frac *= step
    return tuple(sorted(caps))


def _tier_chain(caps, gate_cnt, branch_fn):
    """Run ``branch_fn(cap)`` for the smallest static cap >= gate_cnt.
    ``caps`` must be ascending with its largest entry a guaranteed fit."""
    fn = lambda _: branch_fn(caps[-1])  # noqa: E731 — guaranteed fallback
    for cap in sorted(caps[:-1], reverse=True):
        def tiered(_, cap=cap, nxt=fn):
            return jax.lax.cond(
                gate_cnt <= cap, lambda __: branch_fn(cap), nxt, None
            )

        fn = tiered
    return fn(None)


def _go_i32(fv, thr, is_cat):
    """Left-going decision as i32 WITHOUT a bool intermediate: [cap]-ish
    pred tensors bounce between bit layouts on this stack (round-3
    measured ~80-100 ms/tree of pure copies at 1M rows)."""
    isc = is_cat.astype(jnp.int32)
    return isc * (fv == thr).astype(jnp.int32) + (1 - isc) * (
        fv <= thr).astype(jnp.int32)


def _partition_branch(order, bins_T, f, thr, is_cat, begin, pcnt, do_split, cap):
    """Stably partition the parent's [begin, begin+pcnt) range of
    ``order`` by the split decision (DataPartition::Split,
    data_partition.hpp:91-139): left-going rows keep their relative
    order at the front, right-going rows follow.  Positions past pcnt
    (other leaves' rows inside the static cap window) are written back
    unchanged.  Returns (order, nleft)."""
    n = bins_T.shape[1]
    rows_p = jax.lax.dynamic_slice(order, (begin,), (cap,))
    validp = jnp.arange(cap, dtype=jnp.int32) < pcnt
    rows_c = jnp.minimum(rows_p, n - 1)
    frow = jax.lax.dynamic_index_in_dim(bins_T, f, axis=0, keepdims=False)
    vals = frow[rows_c].astype(jnp.int32)
    go = jnp.where(is_cat, vals == thr, vals <= thr) & validp
    # dtype pinned: under jax_enable_x64 (hist_dtype=float64) a plain sum
    # promotes to int64 and the int32 leaf_begin/pos_cnt scatters become
    # unsafe casts
    nleft = jnp.sum(go, dtype=jnp.int32)
    lpos = jnp.cumsum(go.astype(jnp.int32)) - 1
    rpos = nleft + jnp.cumsum((validp & ~go).astype(jnp.int32)) - 1
    # invalid positions get DISTINCT out-of-bounds indices (cap + j):
    # unique_indices promises every index distinct, and mode="drop"
    # discards all of them
    newpos = jnp.where(
        go,
        lpos,
        jnp.where(validp, rpos, cap + jnp.arange(cap, dtype=jnp.int32)),
    )
    buf = rows_p.at[newpos].set(rows_p, mode="drop", unique_indices=True)
    out = jnp.where(do_split, buf, rows_p)
    return jax.lax.dynamic_update_slice(order, out, (begin,)), nleft


def _child_hist_branch(hist_fn, order, bins_T, grad, hess, bag_mask,
                       begin_s, cnt_s, cap):
    """Histogram of one child from its contiguous ``order`` range: slice
    the row ids, gather bins/grad/hess, mask rows past cnt_s and
    unbagged rows, and run the histogram kernel over the capped buffer
    only (the ordered-gradients gather, serial_tree_learner.cpp:283-315)."""
    n = grad.shape[0]
    rows = jax.lax.dynamic_slice(order, (begin_s,), (cap,))
    valid = jnp.arange(cap, dtype=jnp.int32) < cnt_s
    rows_c = jnp.minimum(rows, n - 1)
    sub = jnp.take(bins_T, rows_c, axis=1)
    m = valid.astype(grad.dtype) * bag_mask[rows_c]
    return hist_fn(sub, grad[rows_c], hess[rows_c], m)


def default_search_fn(
    hist, sum_grad, sum_hess, count, can_split,
    feature_mask, num_bins_per_feature, is_categorical, params,
):
    """Local split search over the full feature set (the serial learner's
    FindBestThresholds).  Parallel learners substitute variants that search
    a feature shard and combine across the mesh."""
    return find_best_split(
        hist,
        sum_grad,
        sum_hess,
        count,
        feature_mask,
        num_bins_per_feature,
        is_categorical,
        params.min_data_in_leaf,
        params.min_sum_hessian_in_leaf,
        params.lambda_l1,
        params.lambda_l2,
        params.min_gain_to_split,
        can_split,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_bins", "max_leaves", "hist_fn", "reduce_fn", "search_fn",
        "reduce_max_fn", "child_counts_fn", "search2_fn", "hist_pool",
        "record_mode", "choice_by_mask_counts",
    ),
)
def grow_tree(
    bins_T: jax.Array,  # [F, n] feature-major binned matrix
    grad: jax.Array,  # [n]
    hess: jax.Array,  # [n]
    bag_mask: jax.Array,  # [n] 0/1 bagging mask
    feature_mask: jax.Array,  # [F] bool, feature_fraction sample
    num_bins_per_feature: jax.Array,  # [F] int32
    is_categorical: jax.Array,  # [F] bool
    params: TreeLearnerParams,
    num_bins: int,
    max_leaves: int,
    hist_fn=None,
    reduce_fn=None,
    search_fn=None,
    reduce_max_fn=None,
    child_counts_fn=None,
    search2_fn=None,
    hist_pool: int = 0,
    record_mode: bool = False,
    choice_by_mask_counts: bool = False,
) -> Tuple[Tree, jax.Array]:
    """Grow one tree; returns (tree, final leaf_id per row).

    ``hist_fn(bins_T, grad, hess, mask) -> [F, B, 3]`` abstracts histogram
    construction so the data-parallel learner can psum across the mesh;
    default is the local kernel.  ``reduce_fn`` (cross-device sum) is
    applied to the root (Σg, Σh, count) scalars — the analog of the
    data-parallel learner's tree-start allreduce
    (data_parallel_tree_learner.cpp:97-125).

    Per-split cross-device traffic is concentrated in two hooks so a
    parallel learner pays the minimum collective count per split:

    * ``child_counts_fn(nleft, nright) -> (sum_l, sum_r, max_l, max_r)``
      reduces the two children's LOCAL positional counts once — the sums
      pick the globally smaller child (whose histogram partials the mesh
      reduces), the maxes feed the static-capacity tier gates of BOTH
      later splits of these leaves (stored in ``pos_mat`` row 2, so no
      per-split pmax is needed at consume time).  Default: local values
      through ``reduce_fn``/``reduce_max_fn`` when given, else identity.
    * ``search2_fn(h_left, h_right, lsg, lsh, lc, rsg, rsh, rc, can,
      feature_mask, nbpf, is_cat, params) -> (SplitResult, SplitResult)``
      searches BOTH children in one go so a sharded-search learner can
      combine the two results in a single all_gather.  Default: two
      ``search_fn`` calls.

    ``record_mode``: the parallel learners choose the leaf-sorted
    packed-record partition (ops/record.py; the reference's parallel
    learners inherit the serial hot loop, parallel_tree_learner.h:46-90).
    Histograms of a child's window still flow through ``hist_fn`` (which
    reduce-scatters across the mesh) and searches through the hooks;
    only the partition and the contiguous-window child access change.
    Float32 and unpooled: otherwise the row permutation runs.

    ``hist_pool`` bounds histogram HBM: when ``2 <= hist_pool <
    max_leaves`` only that many leaf histograms stay resident
    (``[P, F, B, 3]``) under an LRU policy, and a split whose parent was
    evicted RECOMPUTES the parent histogram from the leaf's contiguous
    ``order`` range — the reference's HistogramPool
    (feature_histogram.hpp:337-481, serial_tree_learner.cpp:25-32)
    re-cast for static shapes.  ``0`` (default) keeps every leaf
    resident.

    ``choice_by_mask_counts``: base-row-mask mode (cv bin-once,
    gbdt.set_base_row_mask), see the split step below.
    """
    # Python here runs once per TRACE, so this counts grow-program
    # retraces exactly (obs: a timed loop whose grow_traces counter
    # moves is re-tracing — the same hazard the bench warm-up gate and
    # the steady-loop recompile test watch from the compile side)
    telemetry.count("grow_traces")
    F, n = bins_T.shape
    L = max_leaves
    # the partition's capacities are the histogram's (the root split
    # spans every row; _hist_tiers tops out at full n)
    h_tiers = p_tiers = _hist_tiers(n)
    order_pad = max(p_tiers + h_tiers)
    pooled = 0 < hist_pool < L

    if hist_fn is None:
        hist_fn = functools.partial(histogram_feature_major, num_bins=num_bins)
    rec = record_mode and grad.dtype == jnp.float32 and not pooled
    if search_fn is None:
        search_fn = default_search_fn
        if search2_fn is None:
            use_kernel = on_tpu()

            def search2_fn(hl, hr, lsg, lsh, lc, rsg, rsh, rc, can,
                           fmask, nbpf, is_cat, prm):
                # TPU: the whole two-child search is ONE Pallas launch
                # (ops/pallas_search.py) — the jnp search compiles to
                # ~60 small fusions per split (~1.6 ms, 4x the histogram
                # kernel), all per-op overhead no jnp restructuring
                # removes.  The jnp path stays the reference
                # implementation (CPU, float64).
                if use_kernel and hl.dtype == jnp.float32:
                    from ..ops.pallas_search import search2_pallas

                    return search2_pallas(
                        hl, hr, lsg, lsh, lc, rsg, rsh, rc, can,
                        fmask, nbpf, is_cat,
                        prm.min_data_in_leaf,
                        prm.min_sum_hessian_in_leaf,
                        prm.lambda_l1, prm.lambda_l2,
                        prm.min_gain_to_split,
                    )
                res = find_best_split_leaves(
                    jnp.stack([hl, hr]),
                    jnp.stack([lsg, rsg]),
                    jnp.stack([lsh, rsh]),
                    jnp.stack([lc, rc]),
                    fmask, nbpf, is_cat,
                    prm.min_data_in_leaf, prm.min_sum_hessian_in_leaf,
                    prm.lambda_l1, prm.lambda_l2, prm.min_gain_to_split,
                    jnp.stack([can, can]),
                )
                return (
                    SplitResult(*[a[0] for a in res]),
                    SplitResult(*[a[1] for a in res]),
                )
    if rec:
        # record mode: the loop state carries the leaf-sorted PACKED
        # RECORD [W, n_pad] (ops/record.py) instead of the row
        # permutation — every per-split access becomes a contiguous
        # slice and the partition runs as the block-compaction kernel
        # (the order-based path's per-index gathers/scatters cost
        # ~0.4 s/tree at 1M rows).
        from ..ops import record as _record
        from ..ops.record import (
            bins_per_word, build_record, extract_feature, num_words,
            partition_window, rec_height, unpack_window,
        )

        _interp = not on_tpu()
        _T = _record.TILE
        k_pack = bins_per_word(bins_T.dtype)
        Wrec = rec_height(F, k_pack)
        _leaf_row = num_words(F, k_pack) + 4
        bin_dt = bins_T.dtype
        h_tiers = tuple(sorted({_round_up(c, _T) for c in h_tiers}))
        p_tiers = tuple(sorted({_round_up(c, _T) for c in p_tiers}))
        order_pad = max(p_tiers + h_tiers)
    if child_counts_fn is None:
        _sum = (lambda x: x) if reduce_fn is None else reduce_fn
        _max = (lambda x: x) if reduce_max_fn is None else reduce_max_fn

        def child_counts_fn(nl, nr):
            return _sum(nl), _sum(nr), _max(nl), _max(nr)

    def best_for(hist, sg, sh, c, depth_child):
        can = (params.max_depth <= 0) | (depth_child < params.max_depth)
        return search_fn(
            hist, sg, sh, c, can,
            feature_mask, num_bins_per_feature, is_categorical, params,
        )

    def best2_for(hl, hr, lsg, lsh, lc, rsg, rsh, rc, depth_child):
        can = (params.max_depth <= 0) | (depth_child < params.max_depth)
        if search2_fn is not None:
            return search2_fn(
                hl, hr, lsg, lsh, lc, rsg, rsh, rc, can,
                feature_mask, num_bins_per_feature, is_categorical, params,
            )
        return (
            search_fn(hl, lsg, lsh, lc, can,
                      feature_mask, num_bins_per_feature, is_categorical,
                      params),
            search_fn(hr, rsg, rsh, rc, can,
                      feature_mask, num_bins_per_feature, is_categorical,
                      params),
        )

    with phase_scope("grow.root"):
        P = max(hist_pool, 2) if pooled else L
        # ---- root (BeforeTrain / LeafSplits::Init, leaf_splits.hpp:51-92)
        hist0 = hist_fn(bins_T, grad, hess, bag_mask)
        sum_g0, sum_h0, cnt0 = tables.root_sums(grad, hess, bag_mask)
        if reduce_fn is not None:
            # one stacked collective for the tree-start allreduce
            s = reduce_fn(jnp.stack([sum_g0, sum_h0, cnt0]))
            sum_g0, sum_h0, cnt0 = s[0], s[1], s[2]
        # hist0's feature extent may be a shard of F (feature-parallel
        # learner); accumulation dtype follows grad/hess — float64 when
        # Config.hist_dtype asks for the reference's double accumulation
        # (include/LightGBM/bin.h:21-22)
        acc_dt = hist0.dtype
        root_best = best_for(hist0, sum_g0, sum_h0, cnt0, jnp.int32(0))
        best_mat0, pos_mat0, tree_i0, tree_f0 = tables.root_tables(
            root_best, acc_dt, L, n)
        state = _GrowState(
            order=build_record(
                bins_T, grad, hess, bag_mask,
                _round_up(n, _T) + order_pad,
            )
            if rec
            else jnp.concatenate(
                [
                    jnp.arange(n, dtype=jnp.int32),
                    jnp.full(order_pad, n, jnp.int32),
                ]
            ),
            pos_mat=pos_mat0,
            hists=jnp.zeros((P,) + hist0.shape, acc_dt).at[0].set(hist0),
            slot_of=(jnp.full(L, -1, jnp.int32).at[0].set(0) if pooled
                     else jnp.zeros(0, jnp.int32)),
            slot_leaf=(jnp.full(P, -1, jnp.int32).at[0].set(0) if pooled
                       else jnp.zeros(0, jnp.int32)),
            slot_last=(jnp.full(P, -1, jnp.int32).at[0].set(0) if pooled
                       else jnp.zeros(0, jnp.int32)),
            best_mat=best_mat0,
            tree_i=tree_i0,
            tree_f=tree_f0,
            nleaves=jnp.int32(1),
        )

    @phase_scope("grow.book")
    def split_branch(state, step, best_leaf, do_split):
        """One split step with MASKED writes: when ``do_split`` is false
        every store preserves the old value, so the state round-trips
        unchanged.  An earlier version wrapped this in lax.cond with an
        identity branch; XLA's copy insertion then duplicated the whole
        [L, F, B, 3] histogram buffer every iteration (O(L^2*F*B) traffic
        per tree), which dominated the run time.  Masked straight-line
        writes keep every buffer update in place."""
        new_leaf = step + 1
        c = tables.read_split_columns(
            state.best_mat, state.pos_mat, best_leaf, new_leaf,
            is_categorical)
        f, thr, is_cat = c.f, c.thr, c.is_cat
        lsg, lsh, lc, rsg, rsh, rc = c.lsg, c.lsh, c.lc, c.rsg, c.rsh, c.rc
        begin, pcnt, gate = c.begin, c.pcnt, c.gate

        # ---- partition the parent's range in place
        # (DataPartition::Split), at the smallest tier its gate fits
        if rec:

            def _part_rec(cap):
                fv = extract_feature(state.order, f, begin, cap, k_pack)
                go = _go_i32(fv, thr, is_cat)
                return partition_window(
                    state.order, go, begin, pcnt, do_split, cap,
                    left_leaf=best_leaf, right_leaf=new_leaf,
                    leaf_row=_leaf_row, interpret=_interp,
                )

            with phase_scope("grow.tier.part"):
                order, nleft = _tier_chain(p_tiers, gate, _part_rec)
        else:
            with phase_scope("grow.tier.part"):
                order, nleft = _tier_chain(
                    p_tiers,
                    gate,
                    lambda cap: _partition_branch(
                        state.order, bins_T, f, thr, is_cat, begin, pcnt,
                        do_split, cap
                    ),
                )
        nright = pcnt - nleft

        # ---- smaller-child histogram from its contiguous range; sibling
        # by subtraction.  "Smaller" is by POSITIONAL count (the work the
        # gather actually does) — reduced across row shards: every shard
        # must pick the SAME child (the cross-shard reduction inside the
        # hist branch sums one child's partials), even though local counts
        # differ.  ONE child_counts_fn call yields both the global sums
        # (child choice) and the cross-shard maxes (tier gates for this
        # split's histogram AND both children's later partitions).
        nleft_g, nright_g, nleft_gate, nright_gate = child_counts_fn(
            nleft, nright
        )
        if choice_by_mask_counts:
            # base-row-mask mode (cv bin-once, gbdt.set_base_row_mask):
            # pick the small child by the split's MASKED counts instead.
            # A fold booster trained on the full matrix with the fold
            # mask sees positional counts inflated by held-out rows,
            # which could flip this choice vs. the subset-trained run —
            # and the direct-vs-subtracted child histograms differ in
            # final ulps.  lc/rc are the mask-weighted counts from the
            # split search, exactly the subset run's positional counts
            # (its mask is all-ones), so the choice — hence every
            # histogram — matches the subset run bitwise.  Window sizes
            # below stay positional: held-out rows still occupy slots.
            small_is_left = lc <= rc
        else:
            small_is_left = nleft_g <= nright_g
        cnt_s = jnp.where(small_is_left, nleft, nright)
        cnt_s_gate = jnp.where(small_is_left, nleft_gate, nright_gate)
        begin_s = jnp.where(small_is_left, begin, begin + nleft)
        if rec:
            # record mode: the child's rows are a CONTIGUOUS slice of
            # the leaf-sorted record — unpack (vector shifts) + kernel,
            # no indexed access at all.  Under hooks, hist_fn carries
            # the cross-mesh reduce-scatter.
            def _hist_rec(cap):
                win = jax.lax.dynamic_slice(
                    order, (0, begin_s), (Wrec, cap))
                bins_w, g_w, h_w, m_w = unpack_window(
                    win, F, k_pack, bin_dt)
                m_w = m_w * (
                    jnp.arange(cap, dtype=jnp.int32) < cnt_s
                ).astype(m_w.dtype)
                return hist_fn(bins_w, g_w, h_w, m_w)

            with phase_scope("grow.tier.hist"):
                h_small = _tier_chain(h_tiers, cnt_s_gate, _hist_rec)
        else:
            with phase_scope("grow.tier.hist"):
                h_small = _tier_chain(
                    h_tiers,
                    cnt_s_gate,
                    lambda cap: _child_hist_branch(
                        hist_fn, order, bins_T, grad, hess, bag_mask,
                        begin_s, cnt_s, cap,
                    ),
                )
        if pooled:
            # ---- HistogramPool residency (feature_histogram.hpp:337-481):
            # the parent's histogram may have been LRU-evicted since the
            # split that computed it; recompute it from the leaf's
            # contiguous order range then (same O(|parent|) gather as a
            # child histogram — the range holds exactly the parent's rows,
            # partition order does not change the histogram).  The
            # residency flag is uniform across shards (slot state is
            # deterministic), so collectives inside the cond are safe.
            ps = state.slot_of[best_leaf]
            resident = ps >= 0
            with phase_scope("grow.tier.hist"):
                h_parent = jax.lax.cond(
                    resident,
                    lambda _: state.hists[jnp.maximum(ps, 0)],
                    lambda _: _tier_chain(
                        h_tiers,
                        gate,
                        lambda cap: _child_hist_branch(
                            hist_fn, order, bins_T, grad, hess, bag_mask,
                            begin, pcnt, cap,
                        ),
                    ).astype(acc_dt),
                    None,
                )
            # LRU slot choice: overwrite the parent's slot for the left
            # child when resident; otherwise the least-recently-used slot
            # (free slots carry last-use -1 and win argmin).  The right
            # child takes the LRU slot excluding s1.
            s1 = jnp.where(
                resident, ps, jnp.argmin(state.slot_last).astype(jnp.int32)
            )
            idxP = jnp.arange(P, dtype=jnp.int32)
            s2 = jnp.argmin(
                jnp.where(idxP == s1, jnp.int32(2**30), state.slot_last)
            ).astype(jnp.int32)
            h_prev_new = state.hists[s2]
        else:
            h_parent = state.hists[best_leaf]
            h_prev_new = state.hists[new_leaf]
        h_large = h_parent - h_small
        h_left = jnp.where(small_is_left, h_small, h_large)
        h_right = jnp.where(small_is_left, h_large, h_small)

        # ---- child best splits (FindBestThresholds on the two new
        # leaves) — computed BEFORE the buffer update so that every
        # read of state.hists is finished by then (see barrier below)
        best_l_new, best_r_new = best2_for(
            h_left, h_right, lsg, lsh, lc, rsg, rsh, rc, c.depth_child
        )

        # ---- in-place buffer update.  Everything derived from reads
        # of state.hists (the stacked new rows and the child
        # searches) goes through ONE optimization_barrier together
        # with the buffer itself: after the barrier the buffer has no
        # other live readers, so XLA's copy insertion lets the
        # two-row scatter update it in place.  (Without this, the
        # compiled while body copied the full [L, F, B, 3] buffer
        # twice per split — measured in the HLO.)
        if pooled:
            # preserve the slots' old contents when the step no-ops
            new_rows = jnp.stack(
                [
                    jnp.where(do_split, h_left, state.hists[s1]),
                    jnp.where(do_split, h_right, h_prev_new),
                ]
            )
            rows_idx = jnp.stack([s1, s2])
        else:
            new_rows = jnp.stack(
                [
                    jnp.where(do_split, h_left, h_parent),
                    jnp.where(do_split, h_right, h_prev_new),
                ]
            )
            rows_idx = jnp.stack([best_leaf, new_leaf])
        new_rows, best_l_new, best_r_new, hists_in = (
            jax.lax.optimization_barrier(
                (new_rows, best_l_new, best_r_new, state.hists)
            )
        )
        hists = hists_in.at[rows_idx].set(new_rows, unique_indices=True)

        if pooled:
            # residency bookkeeping, all masked on do_split: evicted
            # occupants lose their slot, then the two children claim
            # s1/s2 (ORDER MATTERS: the parent may be its own evictee)
            def mi(arr, i, val):
                return arr.at[i].set(
                    jnp.where(do_split, val, arr[i]).astype(arr.dtype)
                )

            e1, e2 = state.slot_leaf[s1], state.slot_leaf[s2]
            slot_of = state.slot_of
            slot_of = mi(slot_of, jnp.maximum(e1, 0),
                         jnp.where(e1 >= 0, -1, slot_of[jnp.maximum(e1, 0)]))
            slot_of = mi(slot_of, jnp.maximum(e2, 0),
                         jnp.where(e2 >= 0, -1, slot_of[jnp.maximum(e2, 0)]))
            slot_of = mi(mi(slot_of, best_leaf, s1), new_leaf, s2)
            slot_leaf = mi(mi(state.slot_leaf, s1, best_leaf), s2, new_leaf)
            slot_last = mi(mi(state.slot_last, s1, step), s2, step)
        else:
            slot_of = state.slot_of
            slot_leaf = state.slot_leaf
            slot_last = state.slot_last

        dt = c.bcol.dtype
        best_mat, pos_mat, tree_i, tree_f = tables.write_split(
            state.best_mat, state.pos_mat, state.tree_i, state.tree_f, c,
            step, best_leaf, new_leaf, do_split,
            _sr_row(best_l_new, dt), _sr_row(best_r_new, dt),
            nleft, nright, nleft_gate, nright_gate,
        )
        return _GrowState(
            order=order,
            pos_mat=pos_mat,
            hists=hists,
            slot_of=slot_of,
            slot_leaf=slot_leaf,
            slot_last=slot_last,
            best_mat=best_mat,
            tree_i=tree_i,
            tree_f=tree_f,
            nleaves=state.nleaves + do_split.astype(jnp.int32),
        )

    def body(step, state):
        best_leaf, do_split = tables.pick_leaf(state.best_mat)
        return split_branch(state, jnp.int32(step), best_leaf, do_split)

    with phase_scope("grow.loop"):
        state = jax.lax.fori_loop(0, L - 1, body, state)

    with phase_scope("grow.unpack"):
        tree = tables.unpack_tree(
            state.nleaves, state.best_mat, state.tree_i, state.tree_f, L)
        if rec:
            leaf_id = tables.leaf_ids_from_record(state.order, F, k_pack, n)
        else:
            # ---- per-row leaf assignment from the final ranges: leaves
            # own disjoint contiguous [begin, begin+count) spans of
            # ``order``, so the leaf of a position is a searchsorted over
            # the (few) sorted begins, then one unique-index scatter maps
            # positions back to rows.
            idxL = jnp.arange(L, dtype=jnp.int32)
            valid_leaf = (idxL < tree.num_leaves) & (state.pos_mat[1] > 0)
            key = jnp.where(
                valid_leaf, state.pos_mat[0], jnp.int32(n + order_pad))
            perm = jnp.argsort(key).astype(jnp.int32)
            sb = key[perm]
            leaf_of_pos = perm[
                jnp.searchsorted(
                    sb, jnp.arange(n, dtype=jnp.int32), side="right") - 1
            ]
            leaf_id = tables.scatter_leaf_ids(
                jnp.minimum(state.order[:n], n - 1), leaf_of_pos, n)
    return tree, leaf_id
