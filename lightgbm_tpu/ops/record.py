"""Leaf-sorted packed training record: the TPU-native DataPartition.

The leaf-wise split loop is bound by per-index gather/scatter work on
[n]-sized arrays (~30 ns/element on the chip): a row permutation's
feature-row gather and order scatter plus the smaller child's
bins/grad/hess takes total ~42M indexed elements per 1M-row 255-leaf
tree, while contiguous streams run ~40x faster.  The reference's
DataPartition (data_partition.hpp:91-139) leans on CPU caches to make
indices()-indirected histogram reads cheap; the TPU analog keeps the
DATA ITSELF physically leaf-ordered so every per-split access is a
contiguous slice.

Storage: one i32 record matrix [W, n_pad] whose word-rows are

    rows 0..Wb-1 : binned features, packed k per word (k=4 for u8
                   bins, k=2 for u16; little-endian within the word)
    row  Wb      : gradient  (f32 bitcast)
    row  Wb+1    : hessian   (f32 bitcast)
    row  Wb+2    : bagging mask (f32 bitcast)
    row  Wb+3    : original row id (int32; n past the valid prefix)
    row  Wb+4    : leaf id (stamped by every placement)

Who calls what:

 *  the FUSED grower (learners/fused.py, what a TPU chip runs) splits a
    leaf with one launch pair: ``split_step_window`` — per [W, TILE]
    tile of the leaf's window the go flags, a stable compaction and the
    tile's left count, K tiles a grid step (split_tiles: 4 up to 64
    words, 1 past them, from the record's height alone); the smaller
    child's histogram; then the sibling
    by subtraction, both children's split search and the two ``hists``
    rows in place — and ``place_runs``, one grid step a tile: the
    tile's compacted block is read once, its left run appended to a
    VMEM write ring of the left child and its right run to one of the
    right child, and each ring leaves as whole T-aligned blocks, DMA'd
    into the ALIASED record (_place_kernel; the blocks shared with the
    leaves either side, and the one where the lefts end and the rights
    begin, are each written once, at the end).  Both take the window's
    tile count as an OPERAND (a dynamic Mosaic grid), so one compiled
    body serves every leaf size and no ``lax.cond`` stands round them.

    The DATA-PARALLEL fused grower (learners/fused.py under a mesh) cuts
    that split step in two at the child's histogram, which it sums over
    the chips between them: ``split_hist_counted`` (the same kernel with
    ``exchange``: the tile steps, then the histogram out) and
    ``split_search`` (_split_search_kernel: the subtraction, search and
    row writes of the split step's tail, ``lgbm.split_step.search``).

    Where the smaller child's histogram comes from: the compaction has
    already laid that child's rows of a tile side by side, so each tile
    step appends its K tiles' runs, in tile order, to a [K+1, W, TILE]
    staging buffer in VMEM (_stage), and the one-hot body
    (_hist_tile_body) runs on a FULL tile of staged rows for each one
    that has filled, and once on the remainder before the search:
    ``ceil(rows / TILE)`` bodies a split whatever K (the kernel counts
    them: _hist_tiles), where summing every tile of the parent with the
    sibling masked ran 2.7 times as many (PERF.md, PR 31).
 *  the canonical grower under ``record_mode`` (learners/serial.py, the
    parallel learners) partitions with ``partition_window`` — the same
    compaction as a kernel of its own plus ``place_runs`` — and reads a
    child's window back with ``unpack_window`` for its ``hist_fn``.

The compaction is per-tile prefix-sum routing: a lane cumsum over the
go bitmask yields each column's destination offset directly; an
LSB-first staged-shift compress network (Hacker's Delight 7-4, a digit
of base SCAN_RADIX a round) runs on ONE row of packed lane ids a child,
which leaves in every destination lane the lane it takes; and the
tile's words follow by lane gathers (_compact_tiles).  The K tiles of a
grid step stack their rows in the sublanes of one [2K, TILE] operand,
so one prefix sum and one network, six trips through the rotate unit,
serve all K (_source_rows).  (Every word of the tile rode both networks
until PR 35; a one-hot routing matrix on the MXU, O(TILE^2) a tile, was
the first design and read 8.3% and 9.7% slower a tree than that in the
benchmark's two cells, PERF.md PR 30.)  Zero per-element descriptors
anywhere.

Off the chip (``interpret=True``) two branches differ from what the
chip runs: ``split_step_window`` reads a materialised window slice
where the chip roll-merges two aligned [W, K*TILE] blocks of the
aliased record,
and ``place_runs`` returns the XLA reference placement (``_xla_place``)
where the chip runs its kernel.  analysis/kernel_parity.py holds both
to numpy on the chip; tests/test_place_kernel.py runs the placement
kernel interpreted against ``_xla_place``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.device_time import phase_scope
from .totals import two_sum

# partition tile width; larger tiles halve both tile passes' grid step
# count at one extra compress stage per doubling.  A positive multiple
# of 128 (Mosaic lane alignment: the kernels' DMA offsets and the
# cap % TILE asserts both require it).
TILE = 512
# Mosaic's scoped VMEM when a call asks for nothing.
VMEM_DEFAULT_BYTES = 16 << 20
# What a split's placement takes (the ``grow.place_*`` counters): one
# grid step a live parent tile, plus a closing step, in one launch
# (_place_call).
PLACE_STEPS_PER_TILE = 1
PLACE_LAUNCHES_PER_SPLIT = 1
# Packed words (sublane rows of the record) one step of the histogram
# body's loop takes, LOOP_WORDS * k features unrolled a step.  The step
# alone on the chip, ms a window split in half at 7.5M x 100 / 400,000 x
# 2,000 (PERF.md, PR 34): 8 words 99.33 / 85.21, 16 words 96.73 / 83.84,
# 32 words 96.76 / 101.74 (every feature unrolled, as before the loop,
# 97.45 at the first; Mosaic takes no partial ``unroll=``).  Read under
# the ``[256, TILE]`` one-hot of the time; PR 37's body runs nearer its
# schedule than that one did and the step was not timed again.
LOOP_WORDS = 16


def split_tiles(W: int) -> int:
    """Parent tiles a grid step of the split step takes at a record of
    ``W`` words: 4 up to 64 words, 1 past them.  K tiles share one prefix
    sum and one compress network (_source_rows), whose rotates are the
    same vregs for up to four tiles as for one; the gathers and the
    staging stay per tile.  At 512 words the network is 3% of a tile
    (PERF.md, PR 35) and a ``[W, 4 * TILE]`` block 4 MiB, so one.  The
    grower pads its window and record to whole blocks of
    ``split_tiles(W) * TILE`` columns (learners/fused.py), and
    _split_step_call refuses buffers that are not."""
    return 4 if W <= 64 else 1


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def bins_per_word(bin_dtype) -> int:
    return 4 if jnp.dtype(bin_dtype).itemsize == 1 else 2


def num_words(F: int, k: int) -> int:
    return -(-F // k)


def rec_height(F: int, k: int) -> int:
    """Record row count: packed words + 5 stat rows (grad, hess, mask,
    row id, leaf id), padded to a sublane-tile multiple of 8 — Mosaic
    DMA slices must be 8-aligned in the sublane dimension, so the pad
    rows ride along for free instead of a per-split pad/unpad pass.

    The LEAF-ID row rides the partition: each split stamps the two
    child ids over the parent's window, so end-of-tree leaf assignment
    is a contiguous row read instead of a searchsorted over the leaf
    ranges (profiled ~75 ms/tree of binary-search gathers at 1M)."""
    return round_up(num_words(F, k) + 5, 8)


def pack_bins(bins_T: jax.Array, n_pad: int) -> jax.Array:
    """[F, n] u8/u16 -> [Wb, n_pad] i32, k features per word (feature
    ``w*k + j`` in byte/half ``j`` of word-row ``w``).

    Widened to i32 eight word-rows at a time and padded after packing:
    widening the whole matrix at ``n_pad`` first made XLA hold an
    ``[F, n_pad]`` and a ``[Wb, k, n_pad]`` i32 buffer at the root —
    all 11.4 GiB of the grow program's scratch at 7.5M x 100 (buffer
    assignment of the deviceless compile, PERF.md PR 27).  A group is
    8*k feature rows, whole sublane tiles of the narrow matrix."""
    F, n = bins_T.shape
    k = bins_per_word(bins_T.dtype)
    shift = 32 // k
    groups = []
    for lo in range(0, F, 8 * k):
        x = bins_T[lo: lo + 8 * k].astype(jnp.int32)
        wg = num_words(x.shape[0], k)
        if x.shape[0] % k:
            x = jnp.pad(x, ((0, wg * k - x.shape[0]), (0, 0)))
        x = x.reshape(wg, k, n)
        out = x[:, 0, :]
        for j in range(1, k):
            out = out | (x[:, j, :] << (shift * j))
        groups.append(out)
    out = jnp.concatenate(groups)
    if n_pad > n:
        out = jnp.pad(out, ((0, 0), (0, n_pad - n)))
    return out


@phase_scope("partition")
def build_record(
    bins_T: jax.Array,  # [F, n] u8/u16
    grad: jax.Array,  # [n] f32
    hess: jax.Array,  # [n] f32
    bag_mask: jax.Array,  # [n]
    n_pad: int,
) -> jax.Array:
    """Assemble the per-tree record in identity order: one contiguous
    O(n*W) pass."""
    n = grad.shape[0]

    def stat_row(v):
        v = v.astype(jnp.float32)
        if n_pad > n:
            v = jnp.pad(v, (0, n_pad - n))
        return jax.lax.bitcast_convert_type(v, jnp.int32)[None]

    F = bins_T.shape[0]
    k = bins_per_word(bins_T.dtype)
    pad_rows = rec_height(F, k) - num_words(F, k) - 5
    return jnp.concatenate([
        pack_bins(bins_T, n_pad),
        stat_row(grad),
        stat_row(hess),
        stat_row(bag_mask),
        jnp.pad(jnp.arange(n, dtype=jnp.int32), (0, n_pad - n),
                constant_values=n)[None],
        # leaf-id row: every row starts in the root leaf (0)
        jnp.zeros((1 + pad_rows, n_pad), jnp.int32),
    ])


def extract_feature(
    rec: jax.Array, f: jax.Array, begin: jax.Array, cap: int, k: int
) -> jax.Array:
    """Split-feature bin values of window [begin, begin+cap): dynamic
    word-row index + contiguous slice + shift.  ``f`` may be -1 on a
    no-op step — clamped; the result is masked upstream."""
    shift = 32 // k
    f = jnp.maximum(f, 0)
    word = jax.lax.dynamic_index_in_dim(rec, f // k, axis=0, keepdims=False)
    win = jax.lax.dynamic_slice(word, (begin,), (cap,))
    return jax.lax.shift_right_logical(win, (f % k) * shift) & (
        (1 << shift) - 1)


def unpack_window(win: jax.Array, F: int, k: int, bin_dtype):
    """[W, cap] record slice -> (bins [F, cap], grad, hess, mask)."""
    Wb = num_words(F, k)
    shift = 32 // k
    words = win[:Wb]
    parts = [((words >> (shift * j)) & ((1 << shift) - 1)) for j in range(k)]
    bins = jnp.stack(parts, axis=1).reshape(Wb * k, -1)[:F].astype(bin_dtype)
    g = jax.lax.bitcast_convert_type(win[Wb], jnp.float32)
    h = jax.lax.bitcast_convert_type(win[Wb + 1], jnp.float32)
    m = jax.lax.bitcast_convert_type(win[Wb + 2], jnp.float32)
    return bins, g, h, m


def _tile_go(tile, scal_i_ref, col0, *, F, k, missing=False):
    """Left-going flags of a [W, lanes] block of the window whose lane 0
    is window column ``col0``, recomputed IN-KERNEL from the split
    scalars — the [cap, 1] go-column operand this replaces cost a layout
    copy per split per tier on the XLA side (profiled ~300 ms/tree at 10M
    rows: {0,1:T(1,128)} -> {1,0:T(8,128)} relayouts of every tier's
    column).

    Returns one [1, lanes] f32 row: 1.0 = left AND valid (rows past pcnt
    are 0).
    scal_i layout: (.., .., .., .., f, thr, is_cat, pcnt) — indices 4-7;
    with ``missing`` (static: the table has missing values) a twelfth
    scalar is the bin that goes left besides ``fv <= thr``: the split
    column's missing bin where the node sends missing rows left, else -1
    (a row of the missing bin, the column's last, is right of every
    threshold).
    """
    f = scal_i_ref[4]
    thr = scal_i_ref[5]
    is_cat = scal_i_ref[6]
    pcnt = scal_i_ref[7]
    shift = 32 // k
    mask_v = (1 << shift) - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile.shape[-1]), 1)
    valid = ((col0 + lane) < pcnt).astype(jnp.int32)
    fw = f // k
    fs = (f % k) * shift
    # compare-select row pick: Mosaic has no dynamic_slice lowering,
    # and a dynamically-indexed sublane load is the failure class the
    # histogram kernel's FGROUP loop dodges.  One masked sum down the
    # sublanes: a compare, a select and an add a vreg whatever the
    # record's height (a slice, a select and an add a WORD would be 500
    # of each at 2,000 columns)
    wrow = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    frow = jnp.sum(jnp.where(wrow == fw, tile, 0), axis=0, keepdims=True)
    fv = jax.lax.shift_right_logical(frow, fs) & mask_v
    # ARITHMETIC select: an i1-on-i1 arith.select fails legalization
    if missing:
        num = jnp.maximum((fv <= thr).astype(jnp.int32),
                          (fv == scal_i_ref[11]).astype(jnp.int32))
        go = is_cat * (fv == thr).astype(jnp.int32) + (1 - is_cat) * num
    else:
        go = is_cat * (fv == thr).astype(jnp.int32) + (1 - is_cat) * (
            fv <= thr).astype(jnp.int32)
    return (go * valid).astype(jnp.float32)


# Lane offsets one round of the prefix sum and of the compress network
# looks across: 16 = four bits of the shift a round, fifteen rotates that
# do not wait for one another.  A round is one trip through the rotate
# unit, and the trips are what a tile of 32 words waits for: 18 rounds
# of one rotate each were 0.95 us of its 2.22 a tile.  The step alone, ms
# a window of 14,649 / 3,991 parent tiles of 32 / 64 words split 3% left
# (PERF.md, PR 35): radix 2 32.54 / -, 4 26.56 / 9.95, 8 25.64 / 9.56,
# 16 24.97 / 9.43, 32 27.51 / 10.07 (the parent form 35.76 / 13.24).
SCAN_RADIX = 16


def _lane_cumsum(g):
    """Inclusive prefix sum along the LANE axis of each row of an
    [rows, T] i32 array: ceil(log_R(T)) rounds (R = SCAN_RADIX) in which
    lane t adds lanes t - s, t - 2s, .. t - (R-1)s of the round before,
    s = R^round: a
    Hillis-Steele scan of radix R.  Mosaic has no reliable cumsum
    lowering on the lane axis; ``pltpu.roll`` plus an iota mask is the
    portable scan — and it runs identically under interpret mode, so
    CPU parity tests exercise the same math the chip does."""
    T = g.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    c = g
    step = 1
    while step < T:
        # the iota mask zeroes the wrapped lanes (< off), so the
        # circular roll acts as a shift.  jnp.where (i1 pred, i32
        # operands — the _tile_go row-pick pattern) instead of a
        # cast-and-multiply: a select lowers with no convert op,
        # keeping the hlo_audit convert budget tight
        c = c + sum(
            jnp.where(lane >= off, pltpu.roll(c, off, axis=1), 0)
            for off in range(step, min(SCAN_RADIX * step, T), step))
        step *= SCAN_RADIX
    return c


def _source_lanes(live, shift):
    """Which lane each destination lane of a stable left-compaction
    takes: row ``r`` of the result holds, for every lane of the run of
    ``live[r]`` columns compacted to [0, count) in their order, the lane
    the column came from (0 past the run: in bounds, and garbage).

    Column t moves LEFT by ``shift[r, t]`` lanes (its lane minus its
    prefix-sum destination), applied LSB-first a DIGIT of the shift a
    round (base SCAN_RADIX; the Hacker's Delight 7-4 'compress' network
    is base 2) to ONE packed word a lane, the source lane in the low
    bits and the pending shift above them: a rotate moves both, and
    every row of the operand is a network of its own in the same vregs.
    In the round of digit position p a lane takes the word d * R^p lanes
    to its right whose digit is d, for d = 1 .. R-1.  Monotone
    zero-count shifts make the rounds conflict-free: a live column with
    digit d pending sits at lane >= d * R^p (its destination is >= 0),
    so no live column ever wraps, and two columns never land on one
    lane since they never do in the base-2 network this composes.  A
    lane is DEAD when its word is 0: a dead lane has no pending digit,
    so it never moves, and a lane a column has left is cleared unless
    another moves in.  i1 selects on int32 operands throughout (the
    form _lane_cumsum has), no multiply."""
    T = live.shape[-1]
    nbits = (T - 1).bit_length()
    digit_bits = (SCAN_RADIX - 1).bit_length()
    lane = jax.lax.broadcasted_iota(jnp.int32, live.shape, 1)
    work = jnp.where(live > 0, lane | (shift << nbits), 0)
    for j in range(0, nbits, digit_bits):
        at = nbits + j  # where this round's digit sits in the word
        digit = ((1 << min(digit_bits, nbits - j)) - 1) << at
        # a column whose digit is pending leaves its lane
        out = jnp.where((work & digit) != 0, 0, work)
        for d in range(1, (digit >> at) + 1):
            # left-rotate by d * 2^j: lane t sees lane t + d * 2^j
            # (pltpu.roll shifts toward higher lanes)
            r_work = pltpu.roll(work, T - (d << j), axis=1)
            out = jnp.where((r_work & digit) == (d << at), r_work, out)
        work = out
    return work & (T - 1)


# lanes of a vreg: what one ``tpu.dynamic_gather`` reaches along the
# lane axis (Mosaic refuses a gather across a wider operand)
GATHER_LANES = 128


def _gather_lanes(tile, src):
    """``out[:, t] = tile[:, src[0, t]]`` for a [W, T] tile and one
    [1, T] row of source lanes of a LEFT-compaction (``t <= src[0, t] <
    T`` on the run; past it any lane in [0, T), and garbage comes out):
    a block of GATHER_LANES destination lanes takes its words from each
    source block by one lane gather (``tpu.dynamic_gather`` on the
    chip: a ``vperm`` a vreg; plain jax interpreted) and keeps those
    whose source lies in it.  No column moves right, so the source
    blocks below the destination's hold nothing it takes: ten gathers
    a sublane group at T = 512, not sixteen."""
    W, T = tile.shape
    G = GATHER_LANES
    outs = []
    for d in range(0, T, G):
        idx = jnp.broadcast_to(src[:, d: d + G], (W, G))
        within = idx & (G - 1)
        out = None
        for s in range(d, T, G):
            got = jnp.take_along_axis(
                tile[:, s: s + G], within, axis=1, mode="promise_in_bounds")
            out = got if out is None else jnp.where(idx >= s, got, out)
        outs.append(out)
    return jnp.concatenate(outs, axis=1)


def _source_rows(gos):
    """The source lanes (_source_lanes) of K tiles' stable compactions
    from their go rows, a list of K [1, T] 0/1 i32 rows: a [2K, T] array
    whose row 2t is tile t's left run and row 2t+1 its right run.  ONE
    prefix sum and ONE compress network serve all K tiles: both run on a
    [2K, T] operand whose rows 2t and 2t+1 hold tile t's go row.  A vreg
    has eight sublanes and a lone row pair filled two of them, so up to
    four tiles ride the vregs one did, through the same chain of six
    trips through the rotate unit (PERF.md section 6)."""
    K = len(gos)
    T = gos[0].shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (2 * K, T), 0)
    g = jnp.broadcast_to(gos[0], (2 * K, T))
    for t in range(1, K):
        g = jnp.where(row >= 2 * t, gos[t], g)
    csum = _lane_cumsum(g)  # inclusive left count per lane
    lane = jax.lax.broadcasted_iota(jnp.int32, (2 * K, T), 1)
    right = (row & 1) == 1
    # go column at lane t: dest = csum[t]-1, shift = t - csum[t] + 1
    # (= non-go count strictly below t); non-go column: dest =
    # t - csum[t], shift = csum[t] (= go count strictly below t)
    return _source_lanes(jnp.where(right, 1 - g, g),
                         jnp.where(right, csum, lane - csum + 1))


def _compact_tiles(tiles, gos):
    """Stable compaction of the K tiles of a [W, K*T] block by prefix
    sums (shared by the plain and the fused kernel).  A lane cumsum of a
    tile's go row yields destination offsets directly — lefts land at
    ``cumsum(go)-1``, everything else (the invalid tail included) at
    ``cumsum(1-go)-1`` in the right half.  The permutations are COMPUTED
    on one [2K, T] operand of control words (_source_rows: four vregs
    whatever the record's height, up to four tiles) and APPLIED once a
    tile, by lane gathers (_gather_lanes).  Rolling and blending every
    word of the tile through 18 stages, as this did up to PR 34, cost a
    parent tile of 32 / 64 / 512 words 2.44 / 3.32 / 22.7 us in the split
    step where one tile's network cost 1.70 / 2.36 / 13.1 (the step alone
    on the chip: PERF.md, PR 35; at 512 words the tile spilled at every
    stage).  The i32 words move untouched, so routed content is exact by
    construction.

    tiles [W, K*T] i32, gos: K [1, T] 0/1 rows (f32 or i32; 1 = left
    AND valid), a tile's each -> K [W, 2T] blocks: lefts compacted to
    [0, T), everything else to [T, 2T), original order inside each,
    garbage lanes beyond each run.
    """
    T = TILE
    K = len(gos)
    src = _source_rows([g.astype(jnp.int32) for g in gos])
    return [jnp.concatenate(
        [_gather_lanes(tiles[:, t * T: (t + 1) * T], src[r: r + 1])
         for r in (2 * t, 2 * t + 1)], axis=1) for t in range(K)]


def _compact_kernel(win_ref, grow_ref, out_ref):
    """One grid step = K [W, T] tiles of partition_window: stable
    compaction, lefts to [0, T) and everything else to [T, 2T) of
    ``out_ref`` [K, W, 2T].  The go flags arrive as ROW 0 of a
    sublane-aligned [8, K*T] operand — the compress network runs on the
    lane axis, and a bare [1, cap] row block (sublane dim 1) is not
    Mosaic-legal."""
    T = TILE
    gos = [grow_ref[0:1, t * T: (t + 1) * T] for t in range(out_ref.shape[0])]
    for t, comp in enumerate(_compact_tiles(win_ref[...], gos)):
        out_ref[t] = comp


def compact_tiles(win, go, interpret: bool = False, tiles: int = 1):
    """``_compact_tiles`` over every tile of a [W, cap] window, ``tiles``
    a grid step (partition_window takes one; tools/kernel_bundles.py
    schedules the split step's K): go [cap] i32 0/1 (1 = left AND valid)
    -> comp [cap // TILE, W, 2 * TILE]."""
    W, cap = win.shape
    KT = tiles * TILE
    # go flags ride ROW 0 of a sublane-aligned [8, cap] operand (see
    # _compact_kernel); rows 1-7 are zero padding
    flags = jnp.pad(go[None], ((0, 7), (0, 0)))
    return pl.pallas_call(
        _compact_kernel,
        grid=(cap // KT,),
        in_specs=[pl.BlockSpec((W, KT), lambda i: (0, i)),
                  pl.BlockSpec((8, KT), lambda i: (0, i))],
        out_specs=pl.BlockSpec((tiles, W, 2 * TILE), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((cap // TILE, W, 2 * TILE), jnp.int32),
        interpret=interpret,
    )(win, flags)


def _hist_tile_body(stage_ref, blk, hacc_ref, *, F, k, Bp, live, fgroup=8):
    """Histogram accumulation over the [W, T] block ``blk`` of the
    smaller child's STAGED rows, the ring's head (_split_step_kernel's
    one call).  ``live``
    [1, T] flags the lanes that hold a row: all of them on a full
    staged tile, the first ``fill`` at the drain.  Stats stack on
    sublanes; the one-hot is born transposed against a sublane iota and
    contracts the shared lane axis on the MXU — no relayouts
    (pallas_histogram.bin_sums: the root kernel's body).

    Feature ``fi``'s [4, Bp] is added into ``hacc_ref[fi]``.  The
    record's whole groups of LOOP_WORDS words (aligned sublane tiles of
    ``stage_ref``, LOOP_WORDS * k features) are walked in a
    ``fori_loop`` that unrolls inside a group, as the root kernel does
    (pallas_histogram._hist_kernel), and the features past the last
    whole group are unrolled after it: compiled code size stays
    O(LOOP_WORDS * k) whatever the width, and every width runs the one
    form.
    """
    from .pallas_histogram import bin_sums, split_stats

    T = TILE
    shift = 32 // k
    mask_v = (1 << shift) - 1

    Wb = num_words(F, k)
    stat = stage_ref[blk, Wb: Wb + 3]
    grow = jax.lax.bitcast_convert_type(stat[0:1], jnp.float32)
    hrow = jax.lax.bitcast_convert_type(stat[1:2], jnp.float32)
    mrow = jax.lax.bitcast_convert_type(stat[2:3], jnp.float32)
    mw = mrow * live  # the bagging mask, on the lanes that hold a row
    # exact three-piece bf16 split of the stat rows: one MXU pass at
    # float32 accuracy (see pallas_histogram.split_stats)
    stats = split_stats(jnp.concatenate(
        [grow * mw, hrow * mw, mw, jnp.zeros_like(mw)], axis=0))

    sums_of = bin_sums(stats, Bp)  # [1, T] bins -> [4, Bp]

    def add(fi, row):
        hacc_ref[fi] = hacc_ref[fi] + sums_of(row)

    def group(words, f0, count):
        """Features [f0, f0 + count) from ``words``, whose row 0 holds
        feature ``f0`` in its low bits."""
        for j in range(count):
            add(f0 + j, jax.lax.shift_right_logical(
                words[j // k: j // k + 1, :], (j % k) * shift) & mask_v)

    LW = LOOP_WORDS
    steps = F // (LW * k)

    def whole_group(g, _):
        group(stage_ref[blk, pl.ds(pl.multiple_of(g * LW, LW), LW)],
              g * (LW * k), LW * k)
        return 0

    if steps:
        jax.lax.fori_loop(0, steps, whole_group, 0)
    w0 = steps * LW
    if w0 < Wb:
        group(stage_ref[blk, w0: Wb], w0 * k, F - w0 * k)
    # caller-sized histogram block: the padded-feature fill below must
    # cover exactly the caller's round_up(F, fgroup) rows (ADVICE r4 —
    # a literal 8 here would leave rows [round_up(F,8), Fp) zero and
    # break parent-minus-left subtraction consistency for fgroup != 8)
    Fp = round_up(F, fgroup)
    # padded features: bin-0 totals, matching _prep_single_leaf's
    # zero-padded feature rows (subtract consistency with the buffer's
    # existing rows)
    if Fp > F:
        contrib0 = sums_of(jnp.zeros((1, T), jnp.int32))
        for fi in range(F, Fp):
            hacc_ref[fi] = hacc_ref[fi] + contrib0


def _run_offsets(cl, cr):
    """Exclusive per-tile start offsets of the left/right runs within
    their halves, from the per-tile left/right counts [nt]."""
    loff = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(cl)])[:-1]
    roff = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(cr)])[:-1]
    return loff, roff


def _xla_place(rec, comp, loff, roff, begin, pcnt, nleft, do_split, cap,
               leaf_row, left_leaf, right_leaf):
    """Reference XLA placement: scan-of-DUS run packing + roll/merge +
    optional leaf-id stamping + window write-back: place_runs'
    interpret-mode fallback, so CPU tests run THIS and not the kernel
    (analysis/kernel_parity.py holds the kernel to numpy on the chip)."""
    T = TILE
    W = rec.shape[0]
    win = jax.lax.dynamic_slice(rec, (0, begin), (W, cap))
    iota = jnp.arange(cap, dtype=jnp.int32)
    valid = (iota < pcnt).astype(jnp.int32)

    def place(carry, x):
        lbuf, rbuf = carry
        c, lo, ro = x
        lbuf = jax.lax.dynamic_update_slice(lbuf, c[:, :T], (0, lo))
        rbuf = jax.lax.dynamic_update_slice(rbuf, c[:, T:], (0, ro))
        return (lbuf, rbuf), None

    buf0 = jnp.zeros((W, cap + T), jnp.int32)
    (lbuf, rbuf), _ = jax.lax.scan(place, (buf0, buf0), (comp, loff, roff))

    rolled = jnp.roll(rbuf, nleft, axis=1)[:, :cap]
    is_left = (iota < nleft).astype(jnp.int32)[None, :]
    merged = lbuf[:, :cap] * is_left + rolled * (1 - is_left)
    keep = (valid * do_split.astype(jnp.int32))[None, :]
    out = merged * keep + win * (1 - keep)
    if leaf_row >= 0 and left_leaf is not None:
        # after the roll, [0, nleft) is the left child, [nleft, pcnt)
        # the right — stamp the child ids over the kept range
        leafvals = (is_left[0] * left_leaf.astype(jnp.int32)
                    + (1 - is_left[0]) * right_leaf.astype(jnp.int32))
        out = out.at[leaf_row].set(
            keep[0] * leafvals + (1 - keep[0]) * out[leaf_row])
    return jax.lax.dynamic_update_slice(rec, out, (0, begin))


# Histogram tiles (of the smaller child's staged rows) between two folds
# of the split step's small accumulator into its two-float running sum
# (see _fold_hacc): 8,192 rows at TILE=512.
FOLD_TILES = 16


def _fold_hacc(hacc_ref, hhi_ref, hlo_ref, Fc, NC):
    """Fold the tiles accumulated in ``hacc_ref`` into the running sum
    ``hhi + hlo`` and clear it.  A float32 accumulator that takes every
    tile itself rounds once a tile at the size the bin has reached, and
    a bin of a million rows is then off by tens of ulps, which the
    sibling (parent minus this child) and every small leaf below
    inherit whole (PERF.md, PR 28).  Here the roundings at full size
    happen once in FOLD_TILES tiles and each one's error is kept
    (ops/totals.py two_sum), so the bin the search reads is the correctly rounded sum
    of the tiles' exact partial sums.  The hot per-feature loop is
    unchanged: it still adds into ``hacc_ref`` alone.

    Accumulators of ``NC`` feature chunks fold a chunk of ``Fc`` rows
    at a time, so the fold's temporaries are one chunk's whatever the
    table's width."""
    def chunk(c, _):
        rows = _chunk_rows(c, Fc)
        hhi_ref[rows], err = two_sum(hhi_ref[rows], hacc_ref[rows])
        hlo_ref[rows] = hlo_ref[rows] + err
        hacc_ref[rows] = jnp.zeros((Fc,) + hacc_ref.shape[1:],
                                   hacc_ref.dtype)
        return 0

    jax.lax.fori_loop(0, NC, chunk, 0)


def _chunk_rows(c, Fc):
    """Accumulator rows of feature chunk ``c``."""
    return pl.ds(pl.multiple_of(c * Fc, Fc), Fc)


def _split_tiles(tiles, scal_i_ref, small_left_b, j, comp_ref, cnt_ref,
                 stage_ref, fill_ref, *, F, k, missing=False):
    """Per-step work of the split step on K parent tiles, the [W, K*T]
    block ``tiles`` (``j``: the step's ordinal, so its tiles are jK ..
    jK+K-1): ONE in-kernel go computation (no [cap, 1] column operand
    from XLA — see _tile_go) shared by the compaction, the per-tile
    left-count output, and the STAGING of the smaller child's rows for
    the histogram (``small_left_b``: the left-going rows, or the valid
    rows that do not go left).  Each tile's compacted block and left
    count are what a step of one tile writes for it."""
    T = TILE
    K = tiles.shape[-1] // T
    go = _tile_go(tiles, scal_i_ref, j * (K * T), F=F, k=k, missing=missing)
    gos = [go[:, t * T: (t + 1) * T] for t in range(K)]
    group = jax.lax.broadcasted_iota(jnp.int32, (1, K * 128), 1) // 128
    counts = jnp.zeros((1, K * 128), jnp.int32)
    for t, comp in enumerate(_compact_tiles(tiles, gos)):
        comp_ref[t] = comp
        nleft = jnp.sum(gos[t]).astype(jnp.int32)
        counts = jnp.where(group == t, nleft, counts)
        nvalid = jnp.clip(scal_i_ref[7] - (j * K + t) * T, 0, T)
        _stage(jnp.where(small_left_b, comp[:, :T], comp[:, T:]),
               jnp.where(small_left_b, nleft, nvalid - nleft),
               stage_ref, fill_ref)
    cnt_ref[...] = counts


def _stage(half, run, stage_ref, fill_ref):
    """Append the first ``run`` lanes of ``half`` [W, T] to the smaller
    child's staged rows: ``fill_ref[0]`` of them wait in ``stage_ref``
    [K+1, W, T], a RING whose head, the block the body takes next, is
    ``ran % (K+1)`` after ``ran = fill_ref[1]`` bodies (no block moves
    when one is taken).  The compaction has already put the child's rows
    of a tile side by side: the lefts in lanes [0, T) of the compacted
    tile, the valid rights as a PREFIX of [T, 2T) (the invalid tail
    follows them).  That run lands at lane ``fill % T`` of the ring's
    block ``fill // T`` from the head and runs into the next — one
    dynamic roll, two lane masks, as the direct read and _place_kernel's
    rings move unaligned runs — so the histogram body
    (_split_step_kernel) runs on full tiles of the child's rows and never
    sees the sibling's.  ``fill < T`` when a step begins and a run is at
    most T long, so a step's K appends fit in K+1 blocks and leave at
    most K full tiles due."""
    T = TILE
    n = stage_ref.shape[0]
    fill = fill_ref[0]
    q = fill // T
    at = fill - q * T
    end = at + run  # in the lanes of the ring's block q
    b = (fill_ref[1] + q) % n
    nb = (b + 1) % n
    rolled = pltpu.roll(half, at, axis=1)  # lane t -> (t + at) % T
    lane = jax.lax.broadcasted_iota(jnp.int32, half.shape, 1)
    stage_ref[b] = jnp.where((lane >= at) & (lane < end), rolled,
                             stage_ref[b])
    stage_ref[nb] = jnp.where(lane < end - T, rolled, stage_ref[nb])
    fill_ref[0] = fill + run


def _subtract_and_search(c, rows, parent, h_small, small_left_b, do_split,
                         hists_out_ref, stash_ref, scal_f_ref, meta_ref,
                         res_ref, best_ref, *, Fc, Bp, missing=False):
    """Search step ``c`` of a split (``rows``: its chunk's accumulator
    rows, _chunk_rows): feature chunk ``c`` of the larger child by
    subtraction from the parent's block, the left child's block written,
    the right's stashed for the write steps, and both searched on the
    chunk (pallas_search._child_search keeps the best across chunks).
    The tail of _split_step_kernel and the search steps of
    _split_search_kernel."""
    from .pallas_search import (
        K_EPSILON, _child_search, _head_of, _tail_of, _tri)

    h_large = parent - h_small
    h_left = jnp.where(small_left_b, h_small, h_large)
    h_right = jnp.where(small_left_b, h_large, h_small)
    hists_out_ref[0] = jnp.where(do_split, h_left, parent)
    stash_ref[rows] = h_right  # stash for the write steps

    B = Bp
    tri = _tri(B)
    for cc in range(2):
        side = (h_left, h_right)[cc]
        hg, hh, hc = side[:, 0, :], side[:, 1, :], side[:, 2, :]
        _child_search(
            cc, hg, hh, hc,
            _tail_of(hg, tri), _tail_of(hh, tri) + K_EPSILON,
            _tail_of(hc, tri), _head_of(hg, tri), _head_of(hh, tri),
            scal_f_ref, meta_ref, res_ref, Fc, B, c * Fc, best_ref,
            missing,
        )


def _split_step_kernel(
    scal_i_ref, scal_f_ref, *refs,
    W, F, k, Bp, Fc, NC, fgroup=8, direct_read=False, exchange=False,
    missing=False,
):
    """The WHOLE split step in one launch: per-tile compaction and
    staging of the smaller child's rows, K parent tiles a tile step
    (split_tiles), its histogram over full tiles of them (steps
    0..nt-1; the remainder drains at step nt), then subtract + two-child
    search + in-place histogram-buffer row updates (steps nt and nt+1).
    One launch and not two: a Mosaic call of zero
    or one grid step costs 13-14 us on a v5e (measured back to back in a
    fori_loop: PERF.md, PR 27), and the [Fp, 4, Bp] h_small never makes
    a round trip through HBM.

    The tail walks the feature axis in ``NC`` chunks of ``Fc`` features
    (pallas_histogram.feature_chunk): ``NC`` search steps, each of which
    reads one ``[Fc, 4, Bp]`` block of the parent's row, subtracts,
    writes the left child's block, stashes the right child's and
    searches both on that chunk (pallas_search._child_search keeps the
    best across chunks), then ``NC`` steps that write the right child's
    blocks.  The tile steps see no chunk: compaction, staging and the
    histogram body run once a split on the whole record height, into
    accumulators of every chunk.  A table of one chunk (Fp <= Fc) has
    the two tail steps it always had.

    ``nt`` is the count of TILE STEPS, ``ceil(live tiles / K)``, a
    run-time value (scal_i[10]; the grid is ``nt + 2 * NC (+1 direct)``
    steps, a dynamic bound): one body serves every window size, so the
    grower launches it outside any ``lax.cond`` and the record stays in
    the loop's carry.  Tiles past the live count inside the last step
    are invalid by ``pcnt`` (no lefts, no staged rows); tiles past the
    last step are never visited — their ``comp``/``cnt`` blocks keep
    whatever the buffer held, and split_step_window masks them.

    scal_i [11]: (parent_slot, left_slot, new_slot, do_split, f, thr,
                 is_cat, pcnt, begin//KT, begin%KT, tile steps), and with
                 ``missing`` a twelfth: the missing bin that goes left,
                 or -1 (_tile_go); the search then scores both sides for
                 the missing rows (pallas_search._child_search)
    scal_f [16]: pallas_search._pack_scal layout
    win_ref    : the [W, K*T] window block of K tiles (non-direct mode).
                 With ``direct_read`` the RECORD itself is the (single,
                 ALIASED) data operand: each step fetches one
                 KT-aligned block and writes it back unchanged through
                 the aliased output, and the unaligned window block i-1
                 is roll-merged from the PREVIOUS block (VMEM scratch)
                 and the current one — the grid gains one pipeline step.
                 The single-mention aliased pass-through is what lets
                 XLA chain the record in place through place_runs: any
                 second read of the record (a window slice, a go
                 vector, a sibling block view) made copy-insertion
                 clone the full record every split (~1-2 s/tree at 10M
                 rows, measured both ways).
    hrow_ref   : a [1, Fc, 4, Bp] block of a hists row — the parent
                 slot's chunk s at search step s (its chunk 0 through
                 the tile steps), the new slot's at the write steps
    hists_out  : the left row's chunk at a search step, the right row's
                 at a write step
    comp_ref   : [K, W, 2T] — the step's tiles' compacted blocks
    cnt_ref    : [1, K*128] i32, a 128-lane group a tile — lane 0
                 carries the tile's LEFT count, so the XLA side derives
                 cl/cr/nleft with no go vector (and no record read) at
                 all; lane 1 of every group of the LAST step's block
                 carries how many histogram tile bodies the launch ran
                 (_hist_tiles)
    hacc_ref   : VMEM scratch [NC * Fc, 4, Bp], as the next two — the
                 smaller child's histogram over the
                 last few staged tiles (which child: the one with fewer
                 bagged rows by the search's own exact counts, scal_f[3]
                 and [7], as LightGBM takes the smaller leaf's rows and
                 its sibling by subtraction: a sibling got from the
                 LARGER child keeps the absolute rounding of two large
                 sums in bins a hundredth their size), then the
                 right-child stash, chunk by chunk, for the write steps
    hhi_ref, hlo_ref : VMEM scratch — that histogram's running sum as
                 two floats (_fold_hacc)
    stage_ref  : VMEM scratch [K+1, W, T] — the smaller child's rows,
                 compacted across tiles: a ring (_stage appends a tile's
                 run after the fill, the body below takes the head block
                 and the head moves on; no block is copied).
                 The one-hot body is bound by the VPU and costs the same
                 for a row of the sibling as for one of the child: over
                 every parent tile with the sibling masked it was 2.7
                 times the rows, 856 of the step's 1,141 ms a tree at
                 7.5M x 100 (PERF.md, PR 31)
    fill_ref   : SMEM scratch [2] — rows waiting in ``stage_ref``
                 (< T between steps), and the histogram tiles run
    best_ref   : SMEM scratch [2] f32, last — each child's best raw
                 gain over the chunks searched so far

    With ``exchange`` (the data-parallel grower: the child's histogram is
    summed over the chips before anything subtracts from it) the launch
    stops at the histogram: its ``NC`` tail steps write the smaller
    child's local histogram, a chunk a step, to ``hsmall_ref`` in place
    of ``hrow_ref``, ``meta_ref``, ``hists_out_ref``, ``res_ref`` and
    ``best_ref``, and _split_search_kernel does the rest.
    """
    if exchange:
        hrow_ref = meta_ref = hists_out_ref = res_ref = best_ref = None
        if direct_read:
            (rec_ref, hsmall_ref, comp_ref, cnt_ref, rec_out_ref, hacc_ref,
             hhi_ref, hlo_ref, stage_ref, fill_ref, prev_ref) = refs
        else:
            (win_ref, hsmall_ref, comp_ref, cnt_ref, hacc_ref, hhi_ref,
             hlo_ref, stage_ref, fill_ref) = refs
    elif direct_read:
        (rec_ref, hrow_ref, meta_ref, hists_out_ref,
         comp_ref, res_ref, cnt_ref, rec_out_ref, hacc_ref,
         hhi_ref, hlo_ref, stage_ref, fill_ref, prev_ref, best_ref) = refs
    else:
        (win_ref, hrow_ref, meta_ref, hists_out_ref, comp_ref,
         res_ref, cnt_ref, hacc_ref, hhi_ref, hlo_ref, stage_ref,
         fill_ref, best_ref) = refs

    T = TILE
    K = stage_ref.shape[0] - 1  # parent tiles a tile step
    i = pl.program_id(0)
    do_split = scal_i_ref[3] > 0
    nt = scal_i_ref[10]
    off = 1 if direct_read else 0  # pipeline offset of the tile steps
    search_step = nt + off  # the first of NC
    write_step = search_step + NC  # the first of NC

    small_left_b = scal_f_ref[3] <= scal_f_ref[7]
    sums = (hacc_ref, hhi_ref, hlo_ref)
    tile_args = (scal_i_ref, small_left_b)
    tile_refs = (comp_ref, cnt_ref, stage_ref, fill_ref)

    @pl.when(i == 0)
    def _():
        hacc_ref[...] = jnp.zeros_like(hacc_ref)
        hhi_ref[...] = jnp.zeros_like(hhi_ref)
        hlo_ref[...] = jnp.zeros_like(hlo_ref)
        # zeroed, not left as found: a lane past ``fill`` is masked by a
        # multiplication, which a NaN would survive
        stage_ref[...] = jnp.zeros_like(stage_ref)
        fill_ref[0] = 0
        fill_ref[1] = 0

    if direct_read:
        @pl.when(i <= nt)
        def _():
            # fetch block b0+i and write it back unchanged through the
            # aliased output; tile j = i-1 is merged from LAST step's
            # stashed block (prev) and this fetch BEFORE re-stashing
            cur = rec_ref[...]
            rec_out_ref[...] = cur

            @pl.when(i >= 1)
            def _():
                # (no parent pass-through into hists_out here, as the
                # interpreted branch below needs: Mosaic writes an
                # output block back when its index moves, and the search
                # step fills this one before it does)
                r = scal_i_ref[9]
                # block lanes [0, KT-r) from prev[:, r:], lanes [KT-r,
                # KT) from cur[:, :r): both the same right-rotation by
                # (KT - r) % KT (dynamic shifts are the one dynamic-lane
                # primitive Mosaic supports)
                KT = K * T
                prev = prev_ref[...]
                sh = jax.lax.rem(KT - r, KT)
                ra = pltpu.roll(prev, sh, 1)
                rb = pltpu.roll(cur, sh, 1)
                lane = jax.lax.broadcasted_iota(jnp.int32, (W, KT), 1)
                m = (lane < (KT - r)).astype(jnp.int32)
                tiles = ra * m + rb * (1 - m)
                _split_tiles(tiles, *tile_args, i - 1, *tile_refs, F=F, k=k,
                             missing=missing)

            prev_ref[...] = cur
    else:
        @pl.when(i < nt)
        def _():
            # the output block aliases the PARENT row during tile steps
            # (si[1] == si[0]); pass the parent through so any
            # intermediate writeback (interpret mode flushes every
            # step) is an identity write, never garbage over a row the
            # search still needs
            if not exchange:
                hists_out_ref[0] = hrow_ref[0]
            _split_tiles(win_ref[...], *tile_args, i, *tile_refs, F=F, k=k,
                         missing=missing)

    # The histogram body's ONE call, in a loop of K turns: on a full
    # tile of staged rows for each one a tile step has filled (at most
    # K), and at the search step on whatever is left (``fill < T``
    # there: every tile step that filled a tile has emptied it here).
    def drain(_, carry):
        fill = fill_ref[0]

        @pl.when((fill >= T) | ((i == search_step) & (fill > 0)))
        def _():
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
            head = fill_ref[1] % (K + 1)  # the ring's (_stage)
            _hist_tile_body(
                stage_ref, head, hacc_ref, F=F, k=k, Bp=Bp, fgroup=fgroup,
                live=(lane < fill).astype(jnp.float32))
            fill_ref[0] = jnp.maximum(fill - T, 0)
            ran = fill_ref[1] + 1
            fill_ref[1] = ran

            @pl.when(ran % FOLD_TILES == 0)
            def _():
                _fold_hacc(*sums, Fc, NC)

        return carry

    jax.lax.fori_loop(0, K, drain, 0)

    @pl.when(i >= nt + off)
    def _():
        # tail steps revisit the last tile step's count block: an
        # identity rewrite, so interpret mode never flushes it unwritten,
        # with the histogram tiles run in each group's spare lane 1
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, K * 128), 1)
        cnt_ref[...] = jnp.where(lane % 128 == 1, fill_ref[1], cnt_ref[...])
        if direct_read:
            rec_out_ref[...] = rec_ref[...]

    @pl.when(i == search_step)
    def _():
        _fold_hacc(*sums, Fc, NC)

    if exchange:
        # the child's histogram leaves, a chunk a step, for the exchange
        @pl.when(i >= search_step)
        def _():
            rows = _chunk_rows(i - search_step, Fc)
            hsmall_ref[...] = hhi_ref[rows] + hlo_ref[rows]
        return

    @pl.when((i >= search_step) & (i < write_step))
    def _():
        c = i - search_step
        rows = _chunk_rows(c, Fc)
        parent = hrow_ref[0]  # [Fc, 4, Bp]
        h_small = hhi_ref[rows] + hlo_ref[rows]
        _subtract_and_search(
            c, rows, parent, h_small, small_left_b, do_split,
            hists_out_ref, hacc_ref, scal_f_ref, meta_ref, res_ref,
            best_ref, Fc=Fc, Bp=Bp, missing=missing)

    @pl.when(i >= write_step)
    def _():
        rows = _chunk_rows(i - write_step, Fc)
        hists_out_ref[0] = jnp.where(do_split, hacc_ref[rows], hrow_ref[0])


# SMEM state of a placement launch (_place_kernel): per child run s (0
# left, 1 right) its block, ring half and fill at 3*s .. 3*s+2, then a
# flag a half whose write is in flight, at _PEND + 2*s + half, then
# whether the right run's first block is held in ``mid``.
_PEND = 6
_MID_HELD = 10
# DMA semaphores: one a ring half of each run (2*s + half), then the
# two edge blocks (the reads at the first step, the writes at the last)
_SEM_EDGE = 4


def _place_kernel(cl_ref, sc_ref, comp_ref, rec_in_ref, rec_ref,
                  lbuf, rbuf, mid, edge, st, sem, *, W, leaf_row, nblocks):
    """The placement: ONE grid step a live parent tile, then one closing
    step.  Step ``j`` reads tile ``j``'s ``[W, 2T]`` compacted block once
    and appends its left run (``cl[j]`` lanes of ``comp[:, :T]``) to the
    left child's write buffer and its valid right run (the prefix of
    ``comp[:, T:]`` below ``pcnt``) to the right child's, each the way
    _stage stages the smaller child's rows: a roll to the fill and
    two lane masks, the child's leaf id stamped into ``leaf_row``.

    A write buffer is a RING of two ``[W, T]`` halves.  Lefts land in
    ``[begin, begin + nleft)`` and rights in ``[begin + nleft, begin +
    pcnt)``, so each run starts at a lane of a T-aligned block of the
    record and the ring's current half IS that block: when it fills, one
    DMA writes it to its aligned place in the record (``pl.ANY``,
    aliased in to out) and the run goes on in the other half.  The write
    is waited on only when the run next needs that half, a step later at
    the soonest, so no step waits on a write it issued itself.  Block,
    half and fill of both runs are running sums in SMEM (``st``), from
    the left counts that come as scalar prefetch: no step table.

    Three blocks hold lanes of other data, and each is written ONCE:

    * the first left block, whose lanes below ``begin % T`` belong to the
      leaf before: read into the left ring at the first step;
    * the last right block, whose lanes from ``(begin + pcnt) % T`` on
      belong to the leaf after: read into ``edge`` at the first step;
    * the block where the lefts end and the rights begin: the right
      run's first full block waits in ``mid`` (the lefts below it are
      not all placed before the last tile), and the closing step merges
      the left ring's last lanes into it.

    A window inside one block is the case where all three coincide: the
    closing step writes that block from the left ring, the right ring and
    ``edge``.  ``active`` false (``do_split`` false, or no rows) writes
    nothing, in a grid of one step.

    cl [nt] i32: each tile's left count.  sc [7] i32: (begin, pcnt,
    nleft, active, left leaf, right leaf, live tiles)."""
    T = TILE
    i = pl.program_id(0)
    begin, pcnt, nleft = sc_ref[0], sc_ref[1], sc_ref[2]
    active = sc_ref[3] > 0
    live = sc_ref[6]
    lane = jax.lax.broadcasted_iota(jnp.int32, (W, T), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (W, T), 0)
    bufs = (lbuf, rbuf)

    def block(ref, b):  # the record's T-aligned block ``b``
        return ref.at[:, pl.ds(pl.multiple_of(b * T, T), T)]

    def write(src, b, s):
        return pltpu.make_async_copy(src, block(rec_ref, b), sem.at[s])

    def wait_half(s, h):
        @pl.when(st[_PEND + 2 * s + h] > 0)
        def _():
            write(bufs[s].at[h], 0, 2 * s + h).wait()
            st[_PEND + 2 * s + h] = 0

    @pl.when(active & (i == 0))
    def _():
        e_left, e_right = begin + nleft, begin + pcnt
        st[0], st[1], st[2] = begin // T, 0, begin % T
        st[3], st[4], st[5] = e_left // T, 0, e_left % T
        for x in range(_PEND, _MID_HELD + 1):
            st[x] = 0
        first = pltpu.make_async_copy(block(rec_in_ref, begin // T),
                                      lbuf.at[0], sem.at[_SEM_EDGE])
        last = pltpu.make_async_copy(
            block(rec_in_ref, jnp.minimum(e_right // T, nblocks - 1)),
            edge, sem.at[_SEM_EDGE + 1])
        first.start()
        last.start()
        first.wait()
        last.wait()

    def append(s, half, run, leaf):
        buf = bufs[s]
        blk, h, fill = st[3 * s], st[3 * s + 1], st[3 * s + 2]
        end = fill + run
        rolled = pltpu.roll(half, fill, axis=1)  # lane t -> (t + fill) % T
        if leaf_row >= 0:
            rolled = jnp.where(row == leaf_row, leaf, rolled)
        buf[h] = jnp.where((lane >= fill) & (lane < end), rolled, buf[h])

        @pl.when(end >= T)
        def _():
            o = 1 - h
            wait_half(s, o)  # issued at an earlier step
            buf[o] = jnp.where(lane < end - T, rolled, buf[o])
            if s == 0:
                write(buf.at[h], blk, h).start()
                st[_PEND + h] = 1
            else:
                held = st[_MID_HELD] > 0

                @pl.when(held)
                def _():
                    write(buf.at[h], blk, 2 + h).start()
                    st[_PEND + 2 + h] = 1

                @pl.when(jnp.logical_not(held))
                def _():
                    mid[...] = buf[h]
                    st[_MID_HELD] = 1

            st[3 * s], st[3 * s + 1] = blk + 1, o

        st[3 * s + 2] = jnp.where(end >= T, end - T, end)

    @pl.when(active & (i < live))
    def _():
        comp = comp_ref[0]  # [W, 2T], read once
        nl = cl_ref[i]
        append(0, comp[:, :T], nl, sc_ref[4])
        append(1, comp[:, T:], jnp.clip(pcnt - i * T, 0, T) - nl, sc_ref[5])

    @pl.when(active & (i == live))
    def _():
        for s in range(2):
            for h in range(2):
                wait_half(s, h)
        lblk, lf = st[0], st[2]
        rblk, rf = st[3], st[5]
        lo, ro = lbuf[st[1]], rbuf[st[4]]

        @pl.when(st[_MID_HELD] > 0)
        def _():
            # lefts below ``lf``, the right run's first block above
            mid[...] = jnp.where(lane < lf, lo, mid[...])
            w = write(mid, lblk, _SEM_EDGE)
            w.start()

            @pl.when(rf > 0)
            def _():
                edge[...] = jnp.where(lane < rf, ro, edge[...])
                w2 = write(edge, rblk, _SEM_EDGE + 1)
                w2.start()
                w2.wait()

            w.wait()

        @pl.when((st[_MID_HELD] == 0) & (rf > 0))
        def _():
            # the right run never filled its first block: lefts, rights
            # and the leaf after share it (rblk == lblk)
            edge[...] = jnp.where(lane < lf, lo,
                                  jnp.where(lane < rf, ro, edge[...]))
            w = write(edge, rblk, _SEM_EDGE + 1)
            w.start()
            w.wait()


def _place_call(rec, comp, cl, scal, *, leaf_row: int,
                interpret: bool = False):
    """The placement launch (_place_kernel) on the record ``rec`` [W,
    n_pad], aliased in to out; ``scal`` as the kernel's ``sc``.  One
    grid step a live tile and a closing one, or one step that writes
    nothing."""
    W, n_pad = rec.shape
    T = TILE
    live = scal[6]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(jnp.where(scal[3] > 0, live + 1, 1),),
        in_specs=[
            # the closing step keeps the last tile's block: no fetch
            pl.BlockSpec((1, W, 2 * T),
                         lambda i, cl, sc: (jnp.minimum(i, sc[6] - 1), 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, W, T), jnp.int32),  # left ring
            pltpu.VMEM((2, W, T), jnp.int32),  # right ring
            pltpu.VMEM((W, T), jnp.int32),  # mid
            pltpu.VMEM((W, T), jnp.int32),  # edge
            pltpu.SMEM((16,), jnp.int32),
            pltpu.SemaphoreType.DMA((_SEM_EDGE + 2,)),
        ],
    )
    with phase_scope("partition.place.dyn"):
        return pl.pallas_call(
            functools.partial(_place_kernel, W=W, leaf_row=leaf_row,
                              nblocks=n_pad // T),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((W, n_pad), jnp.int32),
            input_output_aliases={3: 0},  # rec (incl. the prefetch args)
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=max(
                VMEM_DEFAULT_BYTES, place_vmem_bytes(W))),
            interpret=interpret,
        )(cl, scal, comp, rec)


def place_scalars(begin, pcnt, nleft, do_split, left_leaf, right_leaf,
                  live):
    """The placement kernel's ``sc`` operand (see _place_kernel)."""
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    pcnt = i32(pcnt)
    active = (jnp.asarray(do_split) & (pcnt > 0)).astype(jnp.int32)
    return jnp.stack([
        i32(begin), pcnt, i32(nleft), active,
        i32(0 if left_leaf is None else left_leaf),
        i32(0 if right_leaf is None else right_leaf), i32(live)])


@functools.partial(
    jax.jit, static_argnames=("cap", "leaf_row", "interpret"),
    donate_argnums=(0,),
)
@phase_scope("partition")
def place_runs(
    rec,  # [W, n_pad] i32 — DONATED, aliased in place
    comp,  # [nt, W, 2T] i32 — the compacted tiles
    counts,  # (cl [nt], cr [nt]): each tile's left and right run length
    begin, pcnt, nleft, do_split,
    left_leaf, right_leaf,
    cap: int,
    leaf_row: int,
    interpret: bool = False,
    live_tiles=None,  # run-time tile count <= cap // TILE (None = all)
):
    """Place the compacted runs into the record, aliased in place: ONE
    launch a split (_place_call), one grid step a live tile.
    ``live_tiles`` (an operand, as in split_step_window: it must cover
    ``pcnt``, and ``counts`` past it must be zero) is how many tiles the
    launch visits.  The kernel takes the left counts alone: a tile's
    valid rights are what ``pcnt`` leaves of it.  Interpret mode runs
    the (bit-identical, slower) XLA reference placement, so the CPU
    grower stays meaningful; tests/test_place_kernel.py holds the kernel,
    interpreted, to it, and analysis/kernel_parity.py holds the kernel
    to numpy on the chip."""
    nt = cap // TILE
    live = _live_tiles(live_tiles, nt)
    cl, cr = counts

    if interpret:
        # reference placement (the XLA path the kernel replaces)
        loff, roff = _run_offsets(cl, cr)
        return _xla_place(
            rec, comp, loff, roff, begin, pcnt, nleft, do_split, cap,
            leaf_row, left_leaf, right_leaf)
    return _place_call(
        rec, comp, jnp.asarray(cl, jnp.int32),
        place_scalars(begin, pcnt, nleft, do_split, left_leaf, right_leaf,
                      live),
        leaf_row=leaf_row)


def _live_tiles(live_tiles, nt: int):
    """The run-time tile count as an i32 scalar in [1, nt]; ``None`` (a
    caller whose window is static) means every tile.  At least one tile
    always runs, so the kernels' block walks never see an empty range;
    a tile past ``pcnt`` contributes masked zeros."""
    if live_tiles is None:
        return jnp.int32(nt)
    return jnp.clip(jnp.asarray(live_tiles, jnp.int32), 1, nt)


def _tile_counts(cnt, pcnt, live, nt: int):
    """(cl [nt], cr [nt], nleft) from the split kernel's count row
    [1, nt*128] (lane 0 of each 128-lane group = that tile's LEFT
    count): per-tile valid counts come from pcnt alone — no go vector,
    no record read.  Groups past ``live`` were never written by the
    launch and hold whatever the buffer did: masked to zero here, which
    is what a visited tile past pcnt would have counted."""
    tile = jnp.arange(nt, dtype=jnp.int32)
    cl = jnp.where(tile < live, cnt.reshape(nt, 128)[:, 0], 0)
    vt = jnp.clip(pcnt - tile * TILE, 0, TILE)
    cr = vt - cl
    return cl, cr, jnp.sum(cl, dtype=jnp.int32)


def _hist_tiles(cnt, live):
    """How many histogram tile bodies the launch ran, from lane 1 of
    the last live tile's group of the split kernel's count row:
    ``ceil(smaller child's rows / TILE)``, where a launch that summed
    every parent tile with the sibling masked ran ``live`` of them."""
    return jax.lax.dynamic_index_in_dim(
        cnt[0], (live - 1) * 128 + 1, keepdims=False)


def place_vmem_bytes(W: int) -> int:
    """What a ``place_runs`` launch keeps in VMEM on the chip, in
    ``[W, TILE]`` blocks of the record: the ``[1, W, 2 * TILE]`` comp
    block in, double-buffered (4); the two write rings (4); ``mid`` and
    ``edge`` (2); the appends' working tiles, GIVEN (2); and 2 MiB for
    the rest.  Mosaic asked for 12.97 MiB at 512 words (this sum: 14.0)
    and 24.1 at 1,032 (26.2), read off deviceless v5e compiles at
    falling limits (PERF.md, PR 39).  Four blocks under the split step's
    sum at every height, so the grower's gate reads that one
    (learners/fused.py chunking; tests/test_place_kernel.py)."""
    return 12 * W * TILE * 4 + (2 << 20)


def split_step_vmem_bytes(Fp: int, Bp: int, W: int) -> int:
    """What ``split_step_counted`` keeps in VMEM on the chip, in bytes,
    with ``(Fc, NC) = feature_chunk(Fp, Bp)``, ``blk = Fc * 4 * Bp * 4``
    (a ``[Fc, 4, Bp]`` float32 block of a leaf's histogram), ``rec =
    W * TILE * 4`` (a ``[W, TILE]`` block of the record) and ``K =
    split_tiles(W)`` tiles a step:

    * ``3 * NC * blk``: the accumulators ``hacc``, ``hhi``, ``hlo`` of
      every chunk (the tile steps fill them all at once);
    * ``4 * blk``: the parent's block in and the child's block out, both
      double-buffered;
    * ``(10 * K + 1) * rec``: the ``[W, K * TILE]`` record block in and
      out (double-buffered, 4K), the ``[K, W, 2 * TILE]`` ``comp`` block
      out (double-buffered, 4K), the ``[K + 1, W, TILE]`` staging buffer
      (K + 1) and ``prev`` (K);
    * what no spec names, GIVEN and not derived: the search's
      ``[Fc, Bp]`` planes of both children and the fold's, ``8 * blk``;
      the compaction's and the row pick's working tiles, ``5 * K *
      rec``; the search's two ``[Bp, Bp]`` triangular matrices; 2 MiB
      for the small blocks (meta, counts, the one-hot).

    The given terms are held against what Mosaic asks for, read off
    deviceless v5e compiles at falling limits (PERF.md, PR 34; MiB,
    need / this sum): 100 columns of 256 bins 7.0 / 9.6; 264, 18.6 /
    22.8; 1,000, 28.5 / 34.5; 2,000, 46.0 / 54.5; 4,364, 92.9 / 102.8;
    3,000 of 128 bins 48.9 / 55.9; 6,656, 96.1 / 105.4; 300 of 1,024
    bins 32.9 / 42.0; 1,344, 92.0 / 106.2.  Mosaic's need grows with the
    record's height by about 11 ``rec`` up to 512 words and by about 15
    past them.  tests/test_chip_compile.py compiles the step under this limit at
    the widest table the grower's gate admits, a record-bound one and
    one of uint16 bins among them.  A record taller than 64 words
    takes one tile a step (split_tiles), so the gate's edge is where it
    was with one tile a step at every height."""
    from .pallas_histogram import feature_chunk

    Fc, NC = feature_chunk(Fp, Bp)
    blk = Fc * 4 * Bp * 4
    rec = W * TILE * 4
    K = split_tiles(W)
    return ((3 * NC + 12) * blk + (15 * K + 1) * rec + 2 * Bp * Bp * 4
            + (2 << 20))


@functools.partial(
    jax.jit,
    static_argnames=("F", "cap", "k", "fgroup", "interpret",
                     "tiles_per_step", "missing"),
    donate_argnums=(0,),
)
@phase_scope("split_step")
def split_step_counted(
    hists,  # [P, Fp, 4, Bp] f32 — DONATED, rows updated in place
    rec,  # [W, n_pad] i32
    begin, pcnt, do_split,
    f, thr, is_cat,  # split decision scalars
    parent_slot, new_slot,  # hists rows (left child reuses parent's)
    scal_f,  # [16] f32 — pallas_search._pack_scal layout
    meta,  # [Fp, 4] — pallas_search._pack_meta
    F: int, cap: int, k: int,
    fgroup: int = 8,
    interpret: bool = False,
    live_tiles=None,  # run-time tile count <= cap // TILE (None = all)
    tiles_per_step=None,  # K where a test or an audit names it
    missing: bool = False,  # the table has missing values (_tile_go)
    miss_left=None,  # with ``missing``: the bin that goes left, or -1
):
    """One-launch split step over window [begin, begin+cap): compaction
    + smaller-child histogram + subtract + two-child search + in-place
    hists-row updates.  Returns (hists', comp, nleft, res[2, 16], cl,
    cr, rec_pass, hist_tiles): the compacted tiles and their run
    lengths for place_runs, ``rec_pass``, the kernel's aliased record
    pass-through that MUST feed place_runs (feeding the original
    ``rec`` reintroduces the full-record copy this chain eliminates),
    and the number of histogram tile bodies the launch ran (_hist_tiles;
    the grower takes ``split_step_window``, which leaves it out).

    The split decision AND the per-tile left counts live entirely in
    the kernel (_tile_go + the cnt output): the XLA side touches the
    record only through the kernel's block reads (on hardware, two
    aligned [W, K*TILE] blocks roll-merged a grid step — no materialized
    window slice), which is what lets the aliased placement (place_runs)
    update the record in place instead of paying a full-record copy
    per split.

    ``cap`` sizes the buffers (``comp`` [cap // TILE, W, 2T], the
    count row); ``live_tiles`` is how many of those tiles the launch
    visits, an OPERAND: the grid is a dynamic bound, so one compiled
    body serves every leaf size and the grower needs no ``lax.cond``
    over capacities (whose result buffer cost two whole-record copies a
    split, PERF.md PR 26/27).  It must cover ``pcnt``
    (``ceil(pcnt / TILE)``; clamped to [1, cap // TILE] here); a caller
    with a static window passes nothing and gets ``cap // TILE``.
    Tiles past the live count are never written, so their counts are
    masked here before anything reads them.  ``cap`` and the record come
    in whole blocks of ``split_tiles`` of the record's height, the
    parent tiles a grid step, as the grower pads them; a test or an
    audit on smaller buffers names the tiles a step (``tiles_per_step``).

    What is resident in VMEM, as a function of ``(Fc, Bp, W)`` and the
    chunk count: ``split_step_vmem_bytes``.  That sum, or Mosaic's
    default of 16 MiB where it is less, is the call's
    ``vmem_limit_bytes`` at every width (a compile parameter derived
    from the block sizes).
    """
    return _split_step_call(
        hists, rec, begin, pcnt, do_split, f, thr, is_cat, parent_slot,
        new_slot, scal_f, meta, F=F, cap=cap, k=k, fgroup=fgroup,
        interpret=interpret, live_tiles=live_tiles,
        tiles_per_step=tiles_per_step, missing=missing, miss_left=miss_left)


def _split_step_call(hists, rec, begin, pcnt, do_split, f, thr, is_cat,
                     parent_slot, new_slot, scal_f, meta, *, F, cap, k,
                     fgroup, interpret, live_tiles, exchange=False,
                     Fp=None, Bp=None, tiles_per_step=None, missing=False,
                     miss_left=None):
    """The split step's launch (``split_step_counted``); with
    ``exchange`` the launch that stops at the smaller child's histogram
    (``split_hist_counted``: ``hists`` and ``meta`` are None, ``Fp`` and
    ``Bp`` say the histogram's shape).  K, the parent tiles a grid step,
    is ``split_tiles`` of the record's height, or ``tiles_per_step``
    where a test, an audit or a kernel-alone timing names it; ``cap``
    and ``n_pad`` must hold whole blocks of K tiles."""
    from .pallas_histogram import feature_chunk

    W, n_pad = rec.shape
    T = TILE
    assert cap % T == 0, (cap, T)
    assert n_pad % T == 0, (n_pad, T)
    nt = cap // T
    K = tiles_per_step or split_tiles(W)
    assert nt % K == 0 and (n_pad // T) % K == 0, (
        "window and record must hold whole blocks of K tiles",
        nt, n_pad // T, K)
    KT = K * T
    nblocks = n_pad // KT
    if not exchange:
        P, Fp, _, Bp = hists.shape
    Fc, NC = feature_chunk(Fp, Bp)
    if not exchange and meta.shape[0] < NC * Fc:
        # whole chunks of meta: a block past its edge would read
        # padding where a zero feature mask must stand
        meta = jnp.pad(meta, ((0, NC * Fc - meta.shape[0]), (0, 0)))

    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    b0 = i32(begin) // KT
    roff_in = i32(begin) % KT
    live = _live_tiles(live_tiles, nt)
    steps = (live + K - 1) // K  # tile steps, K live tiles each but the last
    scal_i = jnp.stack([
        i32(parent_slot), i32(parent_slot), i32(new_slot), i32(do_split),
        jnp.maximum(i32(f), 0), i32(thr), i32(is_cat), i32(pcnt),
        b0, roff_in, steps] + ([i32(miss_left)] if missing else []))

    direct_read = not interpret
    off = 1 if direct_read else 0  # pipeline offset (see the kernel)
    # block walk of the single aliased record view: b0, b0+1, ..,
    # b0+steps (clamped), parked on the last block for the tail steps
    def _rec_idx(i, si, sf):
        return (0, jnp.minimum(si[8] + jnp.minimum(i, si[10]), nblocks - 1))

    def _tile_idx(i, si):  # comp/cnt block of the tiles done at step i
        return jnp.clip(i - off, 0, si[10] - 1)

    if direct_read:
        data_in = [rec]
        data_specs = [pl.BlockSpec((W, KT), _rec_idx)]
    else:
        # interpret fallback: materialized window slice (pltpu.roll
        # paths are hardware-only; CPU tests keep the reference DS)
        data_in = [jax.lax.dynamic_slice(rec, (0, begin), (W, cap))]
        data_specs = [
            pl.BlockSpec(
                (W, KT),
                lambda i, si, sf: (0, jnp.minimum(i, si[10] - 1))),
        ]

    def _chunk_idx(i, si):
        """The feature chunk of tail step ``i``: 0 .. NC-1 over the
        search steps, again over the write steps (0 through the tile
        steps, so the first search step finds its block fetched)."""
        s = i - (si[10] + off)
        return jnp.clip(jnp.where(s < NC, s, s - NC), 0, NC - 1)

    def _hists_idx(i, si, searched):
        """The hists block of step ``i``: ``searched`` (the parent's
        slot) up to the last search step, the new slot's after."""
        return (jnp.where(i < si[10] + off + NC, searched, si[2]),
                _chunk_idx(i, si), 0, 0)

    tile_specs = [
        pl.BlockSpec((K, W, 2 * T),
                     lambda i, si, sf: (_tile_idx(i, si), 0, 0)),
        # counts ride the LANE axis: a (1, K*128) block on [1, nt*128] is
        # Mosaic-legal (major dim == array dim), a [nt, 128] row-per-tile
        # layout is not (sublane dim 1)
        pl.BlockSpec((1, K * 128), lambda i, si, sf: (0, _tile_idx(i, si))),
    ]
    # aliased identity pass-through of the record (same block walk as the
    # input view): the output VALUE feeds place_runs so every link of the
    # record chain is single-use — see the kernel docstring's copy note
    rec_specs = [pl.BlockSpec((W, KT), _rec_idx)] if direct_read else []
    scratch = [pltpu.VMEM((NC * Fc, 4, Bp), jnp.float32)] * 3 + [
        pltpu.VMEM((K + 1, W, T), jnp.int32),  # staged child rows
        pltpu.SMEM((2,), jnp.int32),  # their count, the tiles run
    ] + ([pltpu.VMEM((W, KT), jnp.int32)] if direct_read else [])
    tile_shapes = [jax.ShapeDtypeStruct((nt, W, 2 * T), jnp.int32),
                   jax.ShapeDtypeStruct((1, nt * 128), jnp.int32)]
    rec_shapes = ([jax.ShapeDtypeStruct((W, n_pad), jnp.int32)]
                  if direct_read else [])
    params = pltpu.CompilerParams(vmem_limit_bytes=max(
        VMEM_DEFAULT_BYTES, split_step_vmem_bytes(Fp, Bp, W)))
    kernel = functools.partial(
        _split_step_kernel, W=W, F=F, k=k, Bp=Bp, Fc=Fc, NC=NC,
        fgroup=fgroup, direct_read=direct_read, exchange=exchange,
        missing=missing)
    if exchange:
        # the tile steps, then one step a chunk that writes the child's
        # histogram: no hists row, no search
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(steps + NC + off,),
            in_specs=data_specs,
            out_specs=[pl.BlockSpec(
                (Fc, 4, Bp), lambda i, si, sf: (_chunk_idx(i, si), 0, 0))]
            + tile_specs + rec_specs,
            scratch_shapes=scratch,
        )
        with phase_scope("split_step.dyn"):
            outs = pl.pallas_call(
                kernel, grid_spec=grid_spec,
                out_shape=[jax.ShapeDtypeStruct((NC * Fc, 4, Bp),
                                                jnp.float32)]
                + tile_shapes + rec_shapes,
                input_output_aliases={2: 3} if direct_read else {},
                compiler_params=params, interpret=interpret,
            )(scal_i, scal_f, *data_in)
        h_small, comp, cnt = outs[:3]
        rec_pass = outs[3] if direct_read else rec
        cl, cr, nleft = _tile_counts(cnt, pcnt, live, nt)
        return (h_small, comp, nleft, cl, cr, rec_pass,
                _hist_tiles(cnt, live))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # a DYNAMIC bound: see the docstring
        grid=(steps + 2 * NC + off,),
        in_specs=data_specs + [
            pl.BlockSpec((1, Fc, 4, Bp),
                         lambda i, si, sf: _hists_idx(i, si, si[0])),
            pl.BlockSpec((Fc, 4), lambda i, si, sf: (_chunk_idx(i, si), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Fc, 4, Bp),
                         lambda i, si, sf: _hists_idx(i, si, si[1])),
            tile_specs[0],
            pl.BlockSpec((2, 16), lambda i, si, sf: (0, 0)),
            tile_specs[1],
        ] + rec_specs,
        # each child's best raw gain over the chunks searched, last
        scratch_shapes=scratch + [pltpu.SMEM((2,), jnp.float32)],
    )
    hists_idx = 2 + len(data_in)  # incl. the 2 prefetch args
    out_shape = [
        jax.ShapeDtypeStruct((P, Fp, 4, Bp), jnp.float32),
        tile_shapes[0],
        jax.ShapeDtypeStruct((2, 16), jnp.float32),
        tile_shapes[1],
    ] + rec_shapes
    aliases = {hists_idx: 0}
    if direct_read:
        aliases[2] = 4  # recA -> rec pass-through
    with phase_scope("split_step.dyn"):
        outs = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            input_output_aliases=aliases,
            compiler_params=params,
            interpret=interpret,
        )(scal_i, scal_f, *data_in, hists, meta)
    if direct_read:
        hists_new, comp, res, cnt, rec_pass = outs
    else:
        hists_new, comp, res, cnt = outs
        rec_pass = rec

    cl, cr, nleft = _tile_counts(cnt, pcnt, live, nt)
    return (hists_new, comp, nleft, res, cl, cr, rec_pass,
            _hist_tiles(cnt, live))


@functools.partial(
    jax.jit, static_argnames=("F", "cap", "k", "Fp", "Bp", "fgroup",
                              "interpret", "tiles_per_step", "missing"))
@phase_scope("split_step")
def split_hist_counted(
    rec, begin, pcnt, do_split, f, thr, is_cat, scal_f,
    F: int, cap: int, k: int, Fp: int, Bp: int,
    fgroup: int = 8,
    interpret: bool = False,
    live_tiles=None,
    tiles_per_step=None,
    missing: bool = False,
    miss_left=None,
):
    """The first half of ``split_step_counted`` for the data-parallel
    grower: the compaction of this chip's window of the parent and this
    chip's histogram of the smaller child, which the search's own counts
    in ``scal_f`` choose (so every chip sums the same child).  Returns
    ``(h_small [NC * Fc, 4, Bp], comp, nleft, cl, cr, rec_pass,
    hist_tiles)``; the caller sums ``h_small`` over the chips and hands
    it to ``split_search``.  One launch, under the split step's own name
    (``lgbm.split_step.dyn``)."""
    return _split_step_call(
        None, rec, begin, pcnt, do_split, f, thr, is_cat, 0, 0, scal_f,
        None, F=F, cap=cap, k=k, fgroup=fgroup, interpret=interpret,
        live_tiles=live_tiles, exchange=True, Fp=Fp, Bp=Bp,
        tiles_per_step=tiles_per_step, missing=missing, miss_left=miss_left)


def _split_search_kernel(scal_i_ref, scal_f_ref, hrow_ref, hsmall_ref,
                         meta_ref, hists_out_ref, res_ref, stash_ref,
                         best_ref, *, Fc, NC, Bp, missing=False):
    """The second half of the split step (_split_step_kernel's tail) on a
    child's histogram summed over the chips: ``NC`` search steps
    (_subtract_and_search), then ``NC`` steps that write the right
    child's blocks.  ``scal_i`` [3]: (parent slot, new slot, do_split)."""
    i = pl.program_id(0)
    do_split = scal_i_ref[2] > 0
    small_left_b = scal_f_ref[3] <= scal_f_ref[7]

    @pl.when(i < NC)
    def _():
        rows = _chunk_rows(i, Fc)
        _subtract_and_search(
            i, rows, hrow_ref[0], hsmall_ref[...], small_left_b, do_split,
            hists_out_ref, stash_ref, scal_f_ref, meta_ref, res_ref,
            best_ref, Fc=Fc, Bp=Bp, missing=missing)

    @pl.when(i >= NC)
    def _():
        rows = _chunk_rows(i - NC, Fc)
        hists_out_ref[0] = jnp.where(do_split, stash_ref[rows], hrow_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret", "missing"),
                   donate_argnums=(0,))
@phase_scope("split_step")
def split_search(hists, h_small, parent_slot, new_slot, do_split, scal_f,
                 meta, interpret: bool = False, missing: bool = False):
    """The second half of ``split_step_counted`` for the data-parallel
    grower: the larger child by subtraction from the parent's ``hists``
    row, both children searched, the two rows written in place, from
    ``h_small`` [NC * Fc, 4, Bp], the smaller child's histogram summed
    over the chips.  One launch of ``2 * NC`` steps, named
    ``lgbm.split_step.search``.  Returns ``(hists', res [2, 16])``."""
    from .pallas_histogram import feature_chunk

    P, Fp, _, Bp = hists.shape
    Fc, NC = feature_chunk(Fp, Bp)
    if meta.shape[0] < NC * Fc:
        meta = jnp.pad(meta, ((0, NC * Fc - meta.shape[0]), (0, 0)))
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    scal_i = jnp.stack([i32(parent_slot), i32(new_slot), i32(do_split)])

    def chunk(i):
        return jnp.where(i < NC, i, i - NC)

    def row(i, si):
        """The hists block of step ``i``: the parent's row through the
        search steps, the new slot's through the write steps."""
        return (jnp.where(i < NC, si[0], si[1]), chunk(i), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(2 * NC,),
        in_specs=[
            pl.BlockSpec((1, Fc, 4, Bp), lambda i, si, sf: row(i, si)),
            pl.BlockSpec((Fc, 4, Bp),
                         lambda i, si, sf: (jnp.minimum(i, NC - 1), 0, 0)),
            pl.BlockSpec((Fc, 4),
                         lambda i, si, sf: (jnp.minimum(i, NC - 1), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Fc, 4, Bp), lambda i, si, sf: row(i, si)),
            pl.BlockSpec((2, 16), lambda i, si, sf: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((NC * Fc, 4, Bp), jnp.float32),
                        pltpu.SMEM((2,), jnp.float32)],
    )
    with phase_scope("split_step.search"):
        hists_new, res = pl.pallas_call(
            functools.partial(_split_search_kernel, Fc=Fc, NC=NC, Bp=Bp,
                              missing=missing),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((P, Fp, 4, Bp), jnp.float32),
                       jax.ShapeDtypeStruct((2, 16), jnp.float32)],
            input_output_aliases={2: 0},
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=max(
                VMEM_DEFAULT_BYTES, split_search_vmem_bytes(Fp, Bp))),
            interpret=interpret,
        )(scal_i, scal_f, hists, h_small, meta)
    return hists_new, res


def split_search_vmem_bytes(Fp: int, Bp: int) -> int:
    """What a ``split_search`` launch keeps in VMEM, with the terms of
    ``split_step_vmem_bytes``: the stash of every chunk (``NC * blk``),
    the parent's block in and the child's out and the summed child's
    block in, double-buffered (``6 * blk``), the search's planes
    (``8 * blk``, given), its two ``[Bp, Bp]`` matrices and 2 MiB: under
    ``split_step_vmem_bytes`` at every width, so the grower's gate reads
    that one (learners/fused.py chunking)."""
    from .pallas_histogram import feature_chunk

    Fc, NC = feature_chunk(Fp, Bp)
    blk = Fc * 4 * Bp * 4
    return (NC + 14) * blk + 2 * Bp * Bp * 4 + (2 << 20)


def split_step_window(*args, **kwargs):
    """``split_step_counted`` without its counter: the grower's call."""
    return split_step_counted(*args, **kwargs)[:7]


@functools.partial(
    jax.jit, static_argnames=("cap", "leaf_row", "interpret"))
@phase_scope("partition")
def partition_window(
    rec: jax.Array,  # [W, n_pad] i32
    go: jax.Array,  # [cap] i32: left-going (valid rows only)
    begin: jax.Array,
    pcnt: jax.Array,
    do_split: jax.Array,
    cap: int,
    left_leaf: jax.Array | None = None,
    right_leaf: jax.Array | None = None,
    leaf_row: int = -1,  # record row to stamp child leaf ids into
    interpret: bool = False,
):
    """Stably partition window [begin, begin+cap) of ``rec``: the
    parent's rows [0, pcnt) become left-rows ++ right-rows (original
    order within each), positions [pcnt, cap) — other leaves' rows
    inside the static tier window, or the n_pad tail — are preserved
    exactly.  Returns (rec', nleft).  DataPartition::Split
    (data_partition.hpp:91-139) re-designed for the TPU memory system.
    With ``leaf_row`` >= 0 the child leaf ids are stamped over the
    parent's kept range (see rec_height's leaf-id row).
    """
    W = rec.shape[0]
    T = TILE
    assert cap % T == 0, (cap, T)
    nt = cap // T

    win = jax.lax.dynamic_slice(rec, (0, begin), (W, cap))
    # i32 from the start: pred (1-bit) arrays at [cap, 1]-ish shapes
    # bounce between bit layouts (measured ~80-100 ms/tree of copies;
    # callers pass go as i32 via serial._go_i32)
    valid = (jnp.arange(cap, dtype=jnp.int32) < pcnt).astype(jnp.int32)
    gov = jnp.asarray(go).astype(jnp.int32) * valid
    nleft = jnp.sum(gov, dtype=jnp.int32)

    kt = gov.reshape(nt, T)
    cl = jnp.sum(kt, axis=1, dtype=jnp.int32)
    # rights per tile INCLUDE the invalid tail: invalids are a SUFFIX of
    # the window, so within any tile valid rights precede invalids and
    # each right-run's valid prefix lands at the right global offset;
    # the garbage beyond total-valid-rights is cut by the final selects
    cr = jnp.sum(valid.reshape(nt, T) - kt, axis=1, dtype=jnp.int32)

    with phase_scope(f"partition.compact.cap{cap}"):
        comp = compact_tiles(win, gov, interpret=interpret)

    # aliased in-kernel placement (the XLA reference under interpret)
    rec2 = place_runs(
        rec, comp, (cl, cr), begin, pcnt, nleft, do_split,
        left_leaf, right_leaf, cap=cap, leaf_row=leaf_row,
        interpret=interpret)
    return rec2, nleft
