"""Voting-parallel (PV-Tree) learner: data parallel with top-k voting.

TPU-native re-design of VotingParallelTreeLearner
(src/treelearner/voting_parallel_tree_learner.cpp): rows are sharded as
in the data-parallel learner, but instead of reducing histograms for ALL
features, each device (a) searches its LOCAL histograms with constraints
scaled by 1/num_shards (voting_parallel_tree_learner.cpp:52-54),
(b) proposes its local top-2k features (ArrayArgs::MaxK,
voting_parallel_tree_learner.cpp:229-232), (c) a global vote weighted by
local data counts picks <=2*top_k features
(voting_parallel_tree_learner.cpp:137-166), and (d) only the winners'
histograms are summed across the mesh
(voting_parallel_tree_learner.cpp:260-265) — one small `psum` instead of
a full-width reduce-scatter, cutting per-level comm from O(F*B) to
O(top_k*B).  The final search over the reduced histograms runs
identically on every device, subsuming the SplitInfo allreduce.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..learners.serial import grow_tree
from ..ops.histogram import histogram_feature_major
from ..ops.split import find_best_split
from .mesh import ROW_AXIS, row_padded_grower


def make_voting_parallel_grower(
    mesh, num_bins: int, max_leaves: int, top_k: int, axis: str = ROW_AXIS,
    sorted_hist: bool = False, hist_pool: int = 0,
):
    num_shards = mesh.shape[axis]
    from ..ops.histogram import select_single_hist_fn

    hist_local = select_single_hist_fn(num_bins, sorted_hist)

    def shard_body(bins_T, grad, hess, bag_mask, fmask, nbpf, is_cat, params):
        F = bins_T.shape[0]
        k2 = min(2 * top_k, F)

        def search_fn(hist, sg, sh, c, can, fm, nb, ic, prm):
            # local leaf totals: any feature's bins sum to the local totals
            lsg = jnp.sum(hist[0, :, 0])
            lsh = jnp.sum(hist[0, :, 1])
            lc = jnp.sum(hist[0, :, 2])
            scale = 1.0 / num_shards

            # (a) per-feature LOCAL best gains (FindBestThresholds on the
            # local histogram with 1/num_machines-scaled constraints)
            def one_feature(h, fmk, nbf, icf):
                return find_best_split(
                    h[None], lsg, lsh, lc,
                    fmk[None], nbf[None], icf[None],
                    prm.min_data_in_leaf * scale,
                    prm.min_sum_hessian_in_leaf * scale,
                    prm.lambda_l1, prm.lambda_l2,
                    prm.min_gain_to_split, can,
                ).gain

            local_gain = jax.vmap(one_feature)(hist, fm, nb, ic)  # [F]

            # (b) local proposal + (c) count-weighted global vote
            _, top_idx = jax.lax.top_k(local_gain, k2)
            proposal = jnp.zeros(F, jnp.float32).at[top_idx].set(1.0)
            votes = jax.lax.psum(proposal * lc, axis)
            _, selected = jax.lax.top_k(votes, k2)
            selected = jnp.sort(selected)  # ascending: smaller-feature tie-break

            # (d) reduce only the winners' histograms, search globally
            sel_hist = jax.lax.psum(hist[selected], axis)
            r = find_best_split(
                sel_hist, sg, sh, c,
                fm[selected], nb[selected], ic[selected],
                prm.min_data_in_leaf, prm.min_sum_hessian_in_leaf,
                prm.lambda_l1, prm.lambda_l2, prm.min_gain_to_split, can,
            )
            return r._replace(
                feature=jnp.where(r.feature >= 0, selected[r.feature], -1)
            )

        return grow_tree(
            bins_T, grad, hess, bag_mask, fmask, nbpf, is_cat, params,
            num_bins=num_bins, max_leaves=max_leaves,
            hist_fn=hist_local,
            reduce_fn=lambda x: jax.lax.psum(x, axis),
            search_fn=search_fn,
            reduce_max_fn=lambda x: jax.lax.pmax(x, axis),
            hist_pool=hist_pool,
            record_mode=True,
        )

    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(None, axis), P(axis), P(axis), P(axis), P(), P(), P(), P()),
        out_specs=(P(), P(axis)),
        check_vma=False,
    )
    return row_padded_grower(sharded, num_shards)
