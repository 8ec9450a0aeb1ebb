"""Grid-parallel tree learner: rows x split-search over a 2-D mesh.

A TPU-native extension beyond the reference's three 1-D modes
(src/treelearner/parallel_tree_learner.h): on an (R x C) device mesh,
rows shard over the ``row`` axis (each row shard replicated across the
``feature`` axis) and the split SEARCH shards over the ``feature`` axis.
Per split, each device

1. builds the local histogram for its FEATURE SLICE over its ROW SHARD
   (n/R rows x F/C features of work — the 2-D scaling product),
2. ``psum``s over the row axis (the data-parallel reduce,
   data_parallel_tree_learner.cpp:127-157 semantics),
3. searches its feature slice and combines one SplitInfo per slice over
   the feature axis with the reference's deterministic max (larger
   gain, smaller feature on ties — split_info.hpp:98-103), exactly the
   feature-parallel combine (feature_parallel_tree_learner.cpp:64-77).

Because every device stores full-F bins for its row shard, the winning
split partitions locally with the global feature id, and the grown tree
is replicated — the same invariants as the 1-D learners, composed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..learners.serial import grow_tree
from ..ops.split import find_best_split
from .split_comm import gather_and_combine
from .mesh import FEATURE_AXIS, ROW_AXIS, row_padded_grower


def grid_mesh(shape, devices=None) -> Mesh:
    """An (R, C) mesh with axes (row, feature)."""
    if devices is None:
        devices = jax.devices()
    r, c = shape
    return Mesh(
        np.asarray(devices[: r * c]).reshape(r, c), (ROW_AXIS, FEATURE_AXIS)
    )


def make_grid_parallel_grower(mesh: Mesh, num_bins: int, max_leaves: int,
                              sorted_hist: bool = False,
                              hist_pool: int = 0):
    """grow(bins_T, grad, hess, bag_mask, feature_mask, nbpf, is_cat,
    params) -> (tree, leaf_id) over a 2-D (row, feature) mesh."""
    from ..ops.histogram import select_single_hist_fn

    num_fshards = mesh.shape[FEATURE_AXIS]
    local_hist = select_single_hist_fn(num_bins, sorted_hist)

    def shard_body(bins_T, grad, hess, bag_mask, fmask, nbpf, is_cat, params):
        F = bins_T.shape[0]
        Fs = -(-F // num_fshards)
        pad = Fs * num_fshards - F
        fstart = jax.lax.axis_index(FEATURE_AXIS) * Fs

        def fslice(a, fill=0):
            return jax.lax.dynamic_slice_in_dim(
                jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1),
                        constant_values=fill),
                fstart, Fs, axis=0,
            )

        def hist_fn(bins_arg, g, h, m):
            # local feature slice of the (possibly gathered) matrix, then
            # the data-parallel reduce over the row axis
            h_local = local_hist(fslice(bins_arg), g, h, m)
            return jax.lax.psum(h_local, ROW_AXIS)

        def search_fn(hist, sg, sh, c, can, _fm, _nb, _ic, prm):
            r = find_best_split(
                hist, sg, sh, c,
                fslice(fmask), fslice(nbpf, fill=1), fslice(is_cat),
                prm.min_data_in_leaf, prm.min_sum_hessian_in_leaf,
                prm.lambda_l1, prm.lambda_l2, prm.min_gain_to_split, can,
            )
            r = r._replace(
                feature=jnp.where(r.feature >= 0, r.feature + fstart, -1)
            )
            return gather_and_combine(r, FEATURE_AXIS)

        return grow_tree(
            bins_T, grad, hess, bag_mask, fmask, nbpf, is_cat, params,
            num_bins=num_bins, max_leaves=max_leaves,
            hist_fn=hist_fn,
            search_fn=search_fn,
            reduce_fn=lambda x: jax.lax.psum(x, ROW_AXIS),
            reduce_max_fn=lambda x: jax.lax.pmax(x, ROW_AXIS),
            hist_pool=hist_pool,
            record_mode=True,
        )

    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(None, ROW_AXIS), P(ROW_AXIS), P(ROW_AXIS), P(ROW_AXIS),
                  P(), P(), P(), P()),
        out_specs=(P(), P(ROW_AXIS)),
        check_vma=False,
    )
    return row_padded_grower(sharded, mesh.shape[ROW_AXIS])
