"""The fused leaf-wise grower: what one TPU chip runs.

The same best-first growth as learners/serial.py (SerialTreeLearner,
serial_tree_learner.cpp:116-150), with every per-split access made a
contiguous one and every buffer updated in place:

* the rows live in a leaf-sorted PACKED RECORD ``[W, n_pad]``
  (ops/record.py), the TPU's DataPartition: a leaf's rows are one
  contiguous window of it;
* the per-leaf histograms stay in the histogram kernel's native
  ``[Fp, 4, Bp]`` layout from the root to the last split -- that layout
  exists nowhere else in the library;
* a split is ONE launch pair: ``split_step_window`` (stable compaction of
  the parent's window, the smaller child's histogram, the sibling by
  subtraction, both children's split search, the two ``hists`` rows
  written in place) and ``place_runs`` (one grid step a tile: both
  compacted runs appended to two VMEM write rings, leaf ids stamped, and
  written back into the record as whole aligned blocks).  Both take the
  window's TILE COUNT as an operand and are sized once, at the largest
  capacity;
* a table wider than one block of ``hists`` (256 features at 256 bins) is
  walked in FEATURE CHUNKS by the root histogram, by the split step's
  subtraction and search, and by its ``hists`` row traffic
  (``chunking`` below; ops/pallas_histogram.py feature_chunk): one body
  for every width, one chunk for every table the grower took before.

There is no conditional here, and there must not be one round the
record or ``hists``: a conditional's result is a buffer of its own, so a
capacity-tier conditional round these kernels cost two whole-record
copies a split, 3,132 of 5,704 ms/tree (PERF.md, PR 26/27;
tests/test_chip_compile.py holds the compiled program to none).  With the
tile count an operand the record and ``hists`` go kernel > kernel >
carry through aliased calls and are never copied.

The grower takes no hooks, no resume, no pool and no row-mask mode:
learners/serial.py serves those, and ``models/gbdt.py select_grower`` is
the one place that chooses between the two.

DATA-PARALLEL (``axis``: the body under ``jax.shard_map`` over a row
mesh, parallel/data_parallel.py).  Each chip holds a contiguous share of
the rows, its own record and its own ``pos_mat`` (its leaves' windows in
its record); ``hists``, ``best_mat`` and the node tables are the same on
every chip, because every chip searches the same summed histograms.  The
exchange is one ``psum`` of a ``[Fp, 4, Bp]`` block under
``lgbm.grow.exchange``: of the root histogram once a tree, and of the
smaller child's every split, between the launch that compacts and sums
this chip's rows (``split_hist_counted``) and the one that subtracts and
searches (``split_search``: the one-chip split step's tail as a launch of
its own, ``lgbm.split_step.search``).  Placement stays local.  The root's
sums of gradients and hessians are the one-chip totals bit for bit
(ops/totals.py).  With ``axis`` None nothing of this is traced: the
one-chip program is the same program (tests/test_chip_compile.py holds
its digest).

Counts.  A chip's count channel is exact (it holds at most 2**24 rows:
learners/serial.py check_count_envelope, per shard), and every count the
search compares against something that can flip it (``min_data_in_leaf``,
the smaller-child choice) is one below 2**24 or a tie that every chip
breaks alike, so the summed float32 channel serves the search.  The
TREE's counts are exact at any height: each chip's count of the smaller
child (the count channel of feature 0, an exact float32 integer) rides
the exchanged block as two 12-bit pieces in channel 3, which the
histograms never use, and comes back summed in int32; a leaf's count is
kept in ``pos_mat``'s third row, a node's in ``node_cnt``, and the tree
carries them as int32.
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
from ..ops.pallas_histogram import (
    FGROUP, feature_chunk, make_single_hist_fn_raw, onehot_planes)
from ..ops.pallas_search import _pack_meta, _pack_scal
from ..ops import record
from ..ops.record import (
    bins_per_word, build_record, num_words, place_runs, round_up,
    split_hist_counted, split_search, split_step_window,
)
from ..ops.totals import root_totals
from . import tables
from .serial import TreeLearnerParams, default_search_fn


class Chunking(NamedTuple):
    """How the kernels walk a table's feature axis, and what the split
    step keeps in VMEM for it (a booster computes it once,
    ``GBDT._chunking``: ``select_grower`` reads ``fits``, the log line
    ``said``, and the ``grow.*`` counters are these numbers)."""

    chunk_features: int  # Fc: features a [Fc, 4, Bp] block holds
    feature_chunks: int  # NC: blocks a launch walks
    hist_block_bytes: int  # Fc * 4 * Bp * 4
    record_words: int  # W: the packed record's height
    vmem_bytes: int  # the wider of the loop's two launches (ops/record.py)
    vmem_max: int  # what the gate admits: 3/4 of the chip's VMEM
    onehot_planes: int  # H: 128-bin planes of the one-hot body's dot

    @property
    def fits(self) -> bool:
        return self.vmem_bytes <= self.vmem_max

    @property
    def said(self) -> str:
        return (f"{self.feature_chunks} chunk"
                f"{'s' if self.feature_chunks > 1 else ''} of "
                f"{self.chunk_features} features, record of "
                f"{self.record_words} words, split step VMEM "
                f"{self.vmem_bytes >> 20} of {self.vmem_max >> 20} MiB, "
                f"one-hot of {self.onehot_planes} x 128 bins")


def chunking(num_features: int, num_bins: int) -> Chunking:
    """The fused grower's one bound on a table's width, from what is
    observed: the features, the bins (a uint8 table packs four to a
    record word, a wider one two) and the chip's VMEM.

    What is resident, in bytes as a function of ``(Fc, Bp, W)``
    (ops/record.py split_step_vmem_bytes has the terms and what Mosaic
    asked for beside them): the split step holds the accumulators of
    EVERY chunk, ``3 * NC * Fc * 4 * Bp * 4``, four ``[Fc, 4, Bp]``
    blocks of ``hists`` rows, eleven ``[W, TILE]`` blocks of the record
    and its bodies' temporaries: 9.6 MiB at 100 columns of 256 bins,
    54.5 MiB at 2,000, and the 96 MiB the gate admits of a v5e's 128 at
    4,096 columns, where the accumulators are 48 and the record's blocks
    and working tiles 32 (5,836 columns at 128 bins, where the record's
    are the most, 45; 2,038 at 512).  ``place_runs`` keeps less at every height
    (ops/record.py place_vmem_bytes); the root kernel and the search
    hold one chunk's blocks whatever the width (ops/pallas_histogram.py
    _hist_pallas_call).  The deviceless compile is the measure:
    tests/test_chip_compile.py compiles the kernels at the widest table
    this gate admits; obs/memmodel.py does not guess it."""
    from ..device import vmem_bytes  # looked up a call: tests stand in

    Fp, Bp = round_up(num_features, FGROUP), round_up(num_bins, 128)
    Fc, NC = feature_chunk(Fp, Bp)
    W = record.rec_height(num_features, 4 if num_bins <= 256 else 2)
    return Chunking(
        chunk_features=Fc, feature_chunks=NC,
        hist_block_bytes=Fc * Bp * 16, record_words=W,
        vmem_bytes=max(record.split_step_vmem_bytes(Fp, Bp, W),
                       record.place_vmem_bytes(W)),
        vmem_max=vmem_bytes() * 3 // 4,
        onehot_planes=onehot_planes(Bp))


class _State(NamedTuple):
    """Loop carry (tables: learners/tables.py)."""

    rec: jax.Array  # [W, n_pad] i32 leaf-sorted packed record
    pos_mat: jax.Array
    hists: jax.Array  # [L, Fp, 4, Bp] f32, every leaf resident
    best_mat: jax.Array
    tree_i: jax.Array
    tree_f: jax.Array
    nleaves: jax.Array  # scalar int32 used-leaf count
    # [L - 1] i32 rows of every internal node over all chips; None (no
    # leaf of the carry) on one device, where tree_f holds the count
    node_cnt: jax.Array | None = None


# Each chip's row count rides the exchanged block in channel 3 (always 0
# in a histogram: ops/pallas_histogram.py split_stats) as two pieces of
# COUNT_BITS bits, each summed exactly in float32 over up to 2**12 chips.
COUNT_BITS = 12


def exchange(h: jax.Array, axis: str):
    """Sum a ``[Fp, 4, Bp]`` histogram block over the chips of ``axis``,
    in ONE collective: ``(summed block, rows counted over every chip)``,
    the count exact in int32.  A chip's count is its count channel of
    feature 0 (every row is in one of its bins), exact below 2**24."""
    with phase_scope("grow.exchange"):
        cnt = jnp.sum(h[0, 2]).astype(jnp.int32)
        lo = (1 << COUNT_BITS) - 1
        h = h.at[0, 3, :2].set(
            jnp.stack([cnt >> COUNT_BITS, cnt & lo]).astype(h.dtype))
        h = jax.lax.psum(h, axis)
        hi_lo = h[0, 3, :2].astype(jnp.int32)
        return h.at[0, 3, :2].set(0.0), (hi_lo[0] << COUNT_BITS) + hi_lo[1]


# The benchmark reads the program by this function's name
# (``jit_grow_tree``) and its ops by the ``lgbm.*`` scopes below.
@functools.partial(jax.jit,
                   static_argnames=("num_bins", "max_leaves", "axis"))
def grow_tree(
    bins_T: jax.Array,  # [F, n] feature-major binned matrix
    grad: jax.Array,  # [n] f32
    hess: jax.Array,  # [n] f32
    bag_mask: jax.Array,  # [n] 0/1 bagging mask
    feature_mask: jax.Array,  # [F] bool, feature_fraction sample
    num_bins_per_feature: jax.Array,  # [F] int32
    is_categorical: jax.Array,  # [F] bool
    params: TreeLearnerParams,
    num_bins: int,
    max_leaves: int,
    axis: str | None = None,  # the row axis of a data-parallel mesh
    missing_bin: jax.Array | None = None,  # [F] int32, -1: none
) -> Tuple[Tree, jax.Array]:
    """Grow one tree; returns (tree, final leaf_id per row).  Under
    ``axis`` the arrays are this chip's shard (the module's docstring).
    ``missing_bin`` (a table with missing values, io/binner.py): every
    split's search scores both sides for a column's missing rows and the
    kernels route them by the node's default direction (ops/record.py
    _tile_go); None traces none of it, the program of a table without."""
    # Python here runs once per TRACE: counts grow-program retraces
    telemetry.count("grow_traces")
    assert grad.dtype == jnp.float32, grad.dtype
    missing = missing_bin is not None
    F, n = bins_T.shape
    L = max_leaves
    interpret = not on_tpu()
    hist_fn = make_single_hist_fn_raw(num_bins)
    k = bins_per_word(bins_T.dtype)
    T = record.TILE
    # every row in one window: the root split's, and the buffers' size;
    # in whole blocks of the split step's tiles a grid step, so that
    # every block of its last step lies inside the buffers
    block = T * record.split_tiles(record.rec_height(F, k))
    cap = max(block, round_up(n, block))

    with phase_scope("grow.root"):
        hist0 = hist_fn(bins_T, grad, hess, bag_mask)  # [Fp, 4, Bp]
        # constant per tree: the search's meta, whole feature chunks of
        # it (padded features never validate)
        Fp, _, Bp = hist0.shape
        Fc, NC = feature_chunk(Fp, Bp)
        meta = _pack_meta(
            feature_mask, num_bins_per_feature, is_categorical, NC * Fc,
            missing_bin)
        if axis is None:
            sum_g0, sum_h0, cnt0 = tables.root_sums(grad, hess, bag_mask)
            count0 = node_cnt = None
        else:
            hist0, count0 = exchange(hist0, axis)
            sum_g0, sum_h0 = root_totals(grad, hess, bag_mask, axis)
            cnt0 = count0.astype(jnp.float32)
            node_cnt = jnp.zeros(L - 1, jnp.int32)
        # the once-a-tree root search reads the canonical view
        root_best = default_search_fn(
            hist0[:F, :3, :num_bins].transpose(0, 2, 1),
            sum_g0, sum_h0, cnt0,
            (params.max_depth <= 0) | (jnp.int32(0) < params.max_depth),
            feature_mask, num_bins_per_feature, is_categorical, params,
            missing_bin=missing_bin,
        )
        root_left = None
        if missing:
            root_best, root_left = root_best
        best_mat, pos_mat, tree_i, tree_f = tables.root_tables(
            root_best, hist0.dtype, L, n, count0, root_left)
        state = _State(
            rec=build_record(
                bins_T, grad, hess, bag_mask, round_up(n, block) + cap),
            pos_mat=pos_mat,
            hists=jnp.zeros((L,) + hist0.shape, hist0.dtype).at[0].set(hist0),
            best_mat=best_mat,
            tree_i=tree_i,
            tree_f=tree_f,
            nleaves=jnp.int32(1),
            node_cnt=node_cnt,
        )

    @phase_scope("grow.book")
    def split(state, step, best_leaf, do_split):
        new_leaf = step + 1
        c = tables.read_split_columns(
            state.best_mat, state.pos_mat, best_leaf, new_leaf,
            is_categorical, missing_bin)
        with phase_scope("grow.select"):
            # depth gate + per-split scalars for the in-kernel search
            can = (params.max_depth <= 0) | (
                c.depth_child < params.max_depth)
            scal_f = _pack_scal(
                can.astype(jnp.float32),
                c.lsg, c.lsh, c.lc, c.rsg, c.rsh, c.rc,
                params.min_data_in_leaf, params.min_sum_hessian_in_leaf,
                params.lambda_l1, params.lambda_l2,
                params.min_gain_to_split,
            )
        # the decision AND the tile counts live in the kernel: no
        # XLA-side read of the record at all, so the aliased placement
        # updates it in place (a materialized window + go vector forced
        # a full-record copy per split, ~1 s/tree at 10M rows)
        live_tiles = -(-c.pcnt // T)
        if axis is None:
            hists, comp, nleft, res, cl, cr, rec_pass = split_step_window(
                state.hists, state.rec, c.begin, c.pcnt, do_split,
                c.f, c.thr, c.is_cat, best_leaf, new_leaf,
                scal_f, meta, F=F, cap=cap, k=k, fgroup=FGROUP,
                interpret=interpret, live_tiles=live_tiles,
                missing=missing, miss_left=c.miss_left,
            )
        else:
            h_small, comp, nleft, cl, cr, rec_pass, _ = split_hist_counted(
                state.rec, c.begin, c.pcnt, do_split, c.f, c.thr,
                c.is_cat, scal_f, F=F, cap=cap, k=k, Fp=Fp, Bp=Bp,
                fgroup=FGROUP, interpret=interpret, live_tiles=live_tiles,
                missing=missing, miss_left=c.miss_left,
            )
            h_small, small = exchange(h_small, axis)
            hists, res = split_search(
                state.hists, h_small, best_leaf, new_leaf, do_split,
                scal_f, meta, interpret=interpret, missing=missing)
        rec = place_runs(
            rec_pass, comp, (cl, cr), c.begin, c.pcnt, nleft, do_split,
            best_leaf, new_leaf, cap=cap, leaf_row=num_words(F, k) + 4,
            interpret=interpret, live_tiles=live_tiles,
        )
        nright = c.pcnt - nleft
        node_cnt = state.node_cnt
        if axis is None:
            left_rows, right_rows = nleft, nright
        else:
            # the children's rows over every chip, from the smaller one's
            # (the kernel's own choice) and the parent's, kept in the gate
            # row: exact in int32 at any height
            left_rows = jnp.where(c.lc <= c.rc, small, c.gate - small)
            right_rows = c.gate - left_rows
            node_cnt = jax.lax.dynamic_update_slice(
                node_cnt, jnp.where(do_split, c.gate, node_cnt[step])[None],
                (step,))
        # the search results come out of the kernel ALREADY in the
        # best_mat row layout -- no unpack/repack
        dt = c.bcol.dtype
        best_mat, pos_mat, tree_i, tree_f = tables.write_split(
            state.best_mat, state.pos_mat, state.tree_i, state.tree_f, c,
            step, best_leaf, new_leaf, do_split,
            res[0, :11].astype(dt), res[1, :11].astype(dt),
            nleft, nright, left_rows, right_rows,
            *((res[0, 11], res[1, 11]) if missing else ()),
        )
        return _State(
            rec=rec, pos_mat=pos_mat, hists=hists, best_mat=best_mat,
            tree_i=tree_i, tree_f=tree_f,
            nleaves=state.nleaves + do_split.astype(jnp.int32),
            node_cnt=node_cnt,
        )

    def body(step, state):
        best_leaf, do_split = tables.pick_leaf(state.best_mat)
        return split(state, jnp.int32(step), best_leaf, do_split)

    with phase_scope("grow.loop"):
        state = jax.lax.fori_loop(0, L - 1, body, state)

    with phase_scope("grow.unpack"):
        tree = tables.unpack_tree(
            state.nleaves, state.best_mat, state.tree_i, state.tree_f, L)
        if axis is not None:
            tree = tree._replace(internal_count=state.node_cnt,
                                 leaf_count=state.pos_mat[2])
        leaf_id = tables.leaf_ids_from_record(state.rec, F, k, n)
    return tree, leaf_id
