"""Feature-parallel tree learner: data replicated, split search sharded.

TPU-native re-design of FeatureParallelTreeLearner
(src/treelearner/feature_parallel_tree_learner.cpp): every device holds
ALL rows, but builds histograms and searches thresholds only for its
feature shard (the greedy bin-balanced assignment of
feature_parallel_tree_learner.cpp:29-42 becomes a plain contiguous shard
— bins are uniform-width tensors here, so there is nothing to balance).
The global best split is ONE packed `all_gather` of each device's best
SplitInfo + the reference's deterministic max (larger gain, ties to the
smaller feature index — SplitInfo::MaxReducer / operator>,
split_info.hpp:78-104), replacing Network::Allreduce over byte buffers
(feature_parallel_tree_learner.cpp:64-77) — see parallel/split_comm.py.
Every device then performs the identical split locally — no split
broadcast is needed because data is replicated, exactly as in the
reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..learners.serial import grow_tree
from ..ops.histogram import histogram_feature_major
from ..ops.split import SplitResult, find_best_split
from .split_comm import gather_and_combine


def make_feature_parallel_grower(mesh, num_bins: int, max_leaves: int,
                                 sorted_hist: bool = False,
                                 hist_pool: int = 0):
    axis = mesh.axis_names[0]
    num_shards = mesh.shape[axis]
    from ..ops.histogram import select_single_hist_fn

    local_hist = select_single_hist_fn(num_bins, sorted_hist)

    def shard_body(bins_T, grad, hess, bag_mask, fmask, nbpf, is_cat, params):
        F = bins_T.shape[0]
        Fs = -(-F // num_shards)  # shard width (feature axis, padded)
        pad = Fs * num_shards - F
        bins_p = jnp.pad(bins_T, ((0, pad), (0, 0)))
        fmask_p = jnp.pad(fmask, (0, pad))  # padding: unusable features
        nbpf_p = jnp.pad(nbpf, (0, pad), constant_values=1)
        iscat_p = jnp.pad(is_cat, (0, pad))
        start = jax.lax.axis_index(axis) * Fs

        def local(a):
            return jax.lax.dynamic_slice_in_dim(a, start, Fs, axis=0)

        def hist_fn(bins_arg, g, h, m):
            # local-shard histogram: the per-device share of the search
            # work.  Pad + slice the PASSED matrix (not the closed-over
            # full one): grow_tree may hand us a gathered smaller-child
            # row buffer whose row count differs from n.
            bp = jnp.pad(bins_arg, ((0, pad), (0, 0)))
            return local_hist(local(bp), g, h, m)

        def search_fn(hist, sg, sh, c, can, _fm, _nb, _ic, prm):
            r = find_best_split(
                hist, sg, sh, c,
                local(fmask_p), local(nbpf_p), local(iscat_p),
                prm.min_data_in_leaf, prm.min_sum_hessian_in_leaf,
                prm.lambda_l1, prm.lambda_l2, prm.min_gain_to_split, can,
            )
            r = r._replace(
                feature=jnp.where(r.feature >= 0, r.feature + start, -1)
            )
            return gather_and_combine(r, axis)

        return grow_tree(
            bins_T, grad, hess, bag_mask, fmask, nbpf, is_cat, params,
            num_bins=num_bins, max_leaves=max_leaves,
            hist_fn=hist_fn, search_fn=search_fn, hist_pool=hist_pool,
            record_mode=True,
        )

    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )

    def grow(bins_T, grad, hess, bag_mask, feature_mask, nbpf, is_cat, params):
        # NOTE: the winning split's partition runs on the full replicated
        # matrix, so grow_tree indexes bins_T with GLOBAL feature ids and
        # the returned tree/leaf partition is replicated on every device.
        return sharded(bins_T, grad, hess, bag_mask, feature_mask, nbpf, is_cat, params)

    return jax.jit(grow)
