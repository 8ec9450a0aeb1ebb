"""Data-parallel tree learner: rows sharded over the mesh.

Two growers stand here, and ``models/gbdt.py select_grower`` chooses.
On the chips of one process ``make_fused_data_parallel_grower`` (at the
end) runs the fused grower itself a chip (learners/fused.py with
``axis``: one histogram all-reduce a split).  Everything else -- the
CPU, more than one process (parallel/multihost.py) -- runs the canonical
grower through the hooks below.

TPU-native re-design of DataParallelTreeLearner
(src/treelearner/data_parallel_tree_learner.cpp), the canonical hooks:

* rows are sharded over the mesh's row axis — the analog of the
  per-machine row partition at load (dataset_loader.cpp:500-605);
* each shard builds local histograms for ALL features, then a single
  `psum_scatter` over the FEATURE axis hands every device its feature
  shard of the GLOBAL histogram — the same reduce-scatter-of-histogram-
  blocks pattern as the reference's recursive-halving ReduceScatter
  (data_parallel_tree_learner.cpp:127-157, network.cpp:99-185), at half
  an allreduce's comm volume.  Each device searches only its own shard
  and the winners meet in an all_gather + deterministic max — the
  reference's Allreduce(SplitInfo, MaxReducer)
  (data_parallel_tree_learner.cpp:192-227);
* the root (Σg, Σh, n) allreduce at tree start
  (data_parallel_tree_learner.cpp:97-125) is the `reduce_fn` psum hook;
* the leaf partition stays fully local to each shard (leaf ids are
  global indices), mirroring the local DataPartition with global leaf
  counts (data_parallel_tree_learner.cpp:229-235).

Per-SPLIT collective budget of the leaf-wise learner (the reference pays
one reduce-scatter + one SplitInfo allreduce per LEVEL):

1. one all_gather of the two children's local positional counts [2]
   (child choice by global sum + tier gates by cross-shard max — both
   derived locally from the gathered vector);
2. one psum_scatter of the smaller child's [F, B, 3] histogram partials;
3. one all_gather of the two children's per-shard best SplitInfos
   (stacked — a single collective for both searches).

Per-device histogram residency shrinks to ``[L, F/D, B, 3]`` — the mesh
is also a histogram-memory shard (cf. HistogramPool,
feature_histogram.hpp:337-481).

Determinism: psum_scatter sums the same D partials as psum (reduction
order may differ from serial by association only), and the SplitInfo
combine reproduces split_info.hpp:98-103 tie-breaks, so parallel trees
match serial trees up to float reduction order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..device import on_tpu
from ..learners.serial import grow_tree
from ..obs import telemetry
from ..obs.dist import record_collective_site
from ..ops.split import SplitResult, find_best_split
from .mesh import ROW_AXIS, row_padded_grower
from .split_comm import (combine_gathered_split_infos, gather_and_combine,
                         pack_split, unpack_split)


def data_parallel_sharded(
    mesh, num_bins: int, max_leaves: int, axis: str = ROW_AXIS,
    sorted_hist: bool = False, hist_pool: int = 0, record: bool = True,
):
    """The raw shard-mapped grow fn over ``mesh`` (rows sharded on
    ``axis``).  Callers are responsible for row padding / global-array
    plumbing: use :func:`make_data_parallel_grower` single-host and
    multihost.make_multihost_data_parallel_grower across processes."""
    from ..ops.histogram import select_single_hist_fn

    num_shards = mesh.shape[axis]

    # the per-shard kernel: the per-split histogram over the gathered
    # smaller child
    hist_local = select_single_hist_fn(num_bins, sorted_hist)

    def reduce_sum(x):
        return jax.lax.psum(x, axis)

    def shard_body(bins_T, grad, hess, bag_mask, fmask, nbpf, is_cat, params):
        # trace-time retrace counter (obs; see serial.grow_tree)
        telemetry.count("dp_grow_traces")
        F = bins_T.shape[0]
        Fs = -(-F // num_shards)  # feature-shard width of the scattered hist
        pad = Fs * num_shards - F
        fmask_p = jnp.pad(fmask, (0, pad))  # padding: unusable features
        nbpf_p = jnp.pad(nbpf, (0, pad), constant_values=1)
        iscat_p = jnp.pad(is_cat, (0, pad))
        start = jax.lax.axis_index(axis) * Fs

        def local(a):
            return jax.lax.dynamic_slice_in_dim(a, start, Fs, axis=0)

        def offset_feature(r):
            return r._replace(
                feature=jnp.where(r.feature >= 0, r.feature + start, -1)
            )

        def hist_scatter(bins_arg, g, h, m):
            # local full-feature partials -> reduce-scatter feature blocks:
            # this device leaves owning the GLOBAL histogram of features
            # [start, start+Fs) only (data_parallel_tree_learner.cpp:
            # 127-157)
            hp = hist_local(bins_arg, g, h, m)
            hp = jnp.pad(hp, ((0, pad), (0, 0), (0, 0)))
            out = jax.lax.psum_scatter(hp, axis, scatter_dimension=0,
                                       tiled=True)
            record_collective_site("dp.hist_reduce_scatter",
                                   "reduce-scatter",
                                   out.size * out.dtype.itemsize)
            return out

        def search_local(hist, sg, sh, c, can, prm):
            r = find_best_split(
                hist, sg, sh, c,
                local(fmask_p), local(nbpf_p), local(iscat_p),
                prm.min_data_in_leaf, prm.min_sum_hessian_in_leaf,
                prm.lambda_l1, prm.lambda_l2, prm.min_gain_to_split, can,
            )
            return offset_feature(r)

        def search_fn(hist, sg, sh, c, can, _fm, _nb, _ic, prm):
            # root search: one shard-best SplitInfo per device, one
            # (packed) all_gather + deterministic max
            return gather_and_combine(
                search_local(hist, sg, sh, c, can, prm), axis,
                site="dp.root_split_allgather",
            )

        # the per-split shard search: ONE Pallas launch on TPU (the
        # jnp search compiles to ~60 small fusions, ~1.6 ms/split —
        # round-3 profile), the jnp reference path elsewhere/under f64.
        use_kernel_search = on_tpu()

        def search2_fn(hl, hr, lsg, lsh, lc, rsg, rsh, rc, can,
                       _fm, _nb, _ic, prm):
            # both children's shard-bests ride ONE packed all_gather
            if use_kernel_search and hl.dtype == jnp.float32:
                from ..ops.pallas_search import search2_pallas

                rl, rr = search2_pallas(
                    hl, hr, lsg, lsh, lc, rsg, rsh, rc, can,
                    local(fmask_p), local(nbpf_p), local(iscat_p),
                    prm.min_data_in_leaf, prm.min_sum_hessian_in_leaf,
                    prm.lambda_l1, prm.lambda_l2, prm.min_gain_to_split,
                )
                rl, rr = offset_feature(rl), offset_feature(rr)
            else:
                rl = search_local(hl, lsg, lsh, lc, can, prm)
                rr = search_local(hr, rsg, rsh, rc, can, prm)
            both = jnp.stack([pack_split(rl), pack_split(rr)])  # [2, 11]
            g = jax.lax.all_gather(both, axis)  # [D, 2, 11]
            record_collective_site("dp.split_allgather", "all-gather",
                                   g.size * g.dtype.itemsize)
            w = combine_gathered_split_infos(unpack_split(g))
            return (SplitResult(*[f[0] for f in w]),
                    SplitResult(*[f[1] for f in w]))

        def child_counts_fn(nl, nr):
            # ONE collective for the per-split scalar plumbing: gather the
            # two local counts, then global sums (smaller-child choice)
            # and cross-shard maxes (tier gates) are local reductions
            g = jax.lax.all_gather(jnp.stack([nl, nr]), axis)  # [D, 2]
            record_collective_site("dp.child_counts_allgather",
                                   "all-gather",
                                   g.size * g.dtype.itemsize)
            s = jnp.sum(g, axis=0)
            m = jnp.max(g, axis=0)
            return s[0], s[1], m[0], m[1]

        return grow_tree(
            bins_T,
            grad,
            hess,
            bag_mask,
            fmask,
            nbpf,
            is_cat,
            params,
            num_bins=num_bins,
            max_leaves=max_leaves,
            hist_fn=hist_scatter,
            reduce_fn=reduce_sum,
            search_fn=search_fn,
            search2_fn=search2_fn,
            child_counts_fn=child_counts_fn,
            hist_pool=hist_pool,
            # the packed-record partition (VERDICT r4 item 1): the
            # parallel learner runs the serial fast path's leaf-sorted
            # record locally; only histogram blocks and SplitInfos
            # cross the mesh
            record_mode=record,
        )

    return jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(None, axis), P(axis), P(axis), P(axis), P(), P(), P(), P()),
        out_specs=(P(), P(axis)),
        check_vma=False,
    )


def make_data_parallel_grower(
    mesh, num_bins: int, max_leaves: int, axis: str = ROW_AXIS,
    sorted_hist: bool = False, hist_pool: int = 0, record: bool = True,
):
    """Build a grow(bins_T, grad, hess, bag_mask, feature_mask,
    num_bins_per_feature, is_categorical, params) -> (tree, leaf_id)
    callable running the serial growth algorithm SPMD over ``mesh``."""
    sharded = data_parallel_sharded(
        mesh, num_bins, max_leaves, axis=axis, sorted_hist=sorted_hist,
        hist_pool=hist_pool, record=record,
    )
    return row_padded_grower(sharded, mesh.shape[axis])


def make_fused_data_parallel_grower(mesh, num_bins: int, max_leaves: int,
                                    axis: str = ROW_AXIS):
    """The fused grower (learners/fused.py) over the chips of ``mesh``,
    rows sharded on ``axis``: what ``tree_learner=data`` runs on the chips
    of one host (``models/gbdt.py select_grower``).  Each chip grows the
    tree on its contiguous share of the rows; one ``psum`` of the smaller
    child's ``[Fp, 4, Bp]`` histogram a split (and of the root's once a
    tree) is all that crosses the chips (the module docstring of
    learners/fused.py).  Rows that do not divide the chips are padded
    with bag mask 0 (``row_padded_grower``), and the program keeps the
    one-chip grower's name, ``jit_grow_tree``."""
    from ..learners import fused

    def body(bins_T, grad, hess, bag_mask, fmask, nbpf, is_cat, params):
        return fused.grow_tree(
            bins_T, grad, hess, bag_mask, fmask, nbpf, is_cat, params,
            num_bins=num_bins, max_leaves=max_leaves, axis=axis)

    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis), P(axis), P(axis), P(axis), P(), P(), P(),
                  P()),
        out_specs=(P(), P(axis)),
        check_vma=False,
    )
    return row_padded_grower(sharded, mesh.shape[axis])
