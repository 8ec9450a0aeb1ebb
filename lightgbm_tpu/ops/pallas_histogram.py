"""Pallas TPU histogram kernel — MXU one-hot matmuls over one leaf's rows.

The reference's hot loop is a scalar gather-accumulate per row
(DenseBin::ConstructHistogram, src/io/dense_bin.hpp:39-104).  TPUs have
no fast scatter, so `jax.ops.segment_sum` (ops/histogram.py) lowers to a
scatter-add that serializes badly.  This module reformulates the
histogram of one row set (a leaf's, or the root's) as dense MXU work:
every C-row chunk's histogram is a one-hot matmul on the MXU, and the
output block stays resident in VMEM and accumulates across the chunks —
O(n x F x B) MACs, no scatter at all.

One kernel, and one one-hot body (``bin_sums``), the fused split
step's too (ops/record.py _hist_tile_body).  The one-hot is built
TRANSPOSED: a ``[1, C]`` feature row stays in the lanes and its low
seven bits are compared against a SUBLANE iota of 128 rows; the bin's
high bits select which plane's copy of the stat rows a lane keeps, and
``masked stats[16 * H, C]`` and ``onehot[128, C]`` contract the shared
lane axis on the MXU -> ``[16 * H, 128]``, the ``H = B / 128`` planes
of ``[16, B]``.  Reshaping the row to ``[C, 1]`` against a lane iota
instead is a lane->sublane relayout per feature per chunk (PERF.md, PR
29); tests/test_chip_compile.py keeps it out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..device import on_tpu
from ..obs.device_time import phase_scope
from .totals import two_sum

FGROUP = 8  # the feature axis is padded to a multiple of this
# Feature rows per step of the kernel's loop.  Measured alone on a v5e at
# 7.5M x 100 and 8.92M x 81 (PERF.md, PR 29): 8 rows a step 153.1 / 154.2
# ms, 32 rows 138.3 / 138.8, every row unrolled 133.6 / 134.6 (under the
# ``[256, C]`` one-hot of the time: 71.8 ms at 32 rows with bin_sums'
# 128, PR 37; the other steps not timed again).
LOOP_ROWS = 4 * FGROUP
# Rows per grid step of the single-leaf calls.  Same measurement, 32 rows
# a step: 512 138.3 / 138.8 ms, 2048 133.3 / 134.1.
SINGLE_LEAF_CHUNK = 2048
# The one-hot histogram dots carry float32 gradient/hessian sums, and
# Mosaic runs an un-annotated float32 dot as ONE bf16 MXU pass: measured
# on a v5e (jax 0.9.0, libtpu 0.0.34) the kernels then disagreed with a
# float64 numpy histogram by 5.8e-2 on bins of ~100 N(0,1) gradients,
# against 2e-5 for float32 accumulation (analysis/kernel_parity.py).
# precision=HIGHEST repairs that at six passes (0.40 -> 0.75 s/tree at
# 1M rows, PERF.md).  The one-hot side is exact in bf16, so instead each
# stat row is split into three bf16 pieces that sum to it EXACTLY
# (8+8+8 = the 24 significand bits), the pieces ride extra sublanes of
# the same bf16 dot — one pass, float32 accumulation — and the three
# partial histograms are added afterwards.
STAT_ROWS = 16  # 3 pieces x (grad, hess, count, 0), padded to a bf16 tile
# A leaf's histogram is [Fp, 4, Bp] float32.  The kernels walk the
# feature axis in chunks of at most this many bytes a block: 256
# features at 256 bins, the widest block the split step held whole
# before it had a chunk axis (PERF.md, PR 31), so every table it took
# then is one chunk now.
CHUNK_BLOCK_BYTES = 1 << 20


def feature_chunk(Fp: int, Bp: int):
    """``(Fc, NC)``: the features a kernel keeps in VMEM at a time and
    how many such chunks the ``Fp`` features of a table are, from the
    block's size alone.  A table of one chunk has ``Fc == Fp``; wider,
    ``Fc`` is a whole number of the root kernel's LOOP_ROWS steps (a
    packed uint8 tile of the bins block) and the last chunk may be
    short: its block hangs over the arrays' edge, where Pallas reads
    padding and writes nothing."""
    fc = max(
        LOOP_ROWS, CHUNK_BLOCK_BYTES // (16 * Bp) // LOOP_ROWS * LOOP_ROWS)
    if Fp <= fc:
        return Fp, 1
    return fc, -(-Fp // fc)


def _top16(x):
    """float32 with the low 16 bits cleared: exactly bf16-representable.
    Bit masking, not a convert round trip — XLA may elide those."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(-65536)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def split_stats(stats4):
    """[4, n] float32 stat rows -> the [STAT_ROWS, n] bf16 dot operand:
    rows 0-3 / 4-7 / 8-11 are the high, middle and low bf16 pieces
    (hi + mid + lo == x exactly; both subtractions are exact)."""
    hi = _top16(stats4)
    r = stats4 - hi
    mid = _top16(r)
    return jnp.concatenate(
        [hi, mid, r - mid, jnp.zeros_like(hi)], axis=0
    ).astype(jnp.bfloat16)


def merge_stats(o, axis=0):
    """Partial histograms of the three pieces -> the [4, ...] histogram."""
    p = [jax.lax.slice_in_dim(o, 4 * j, 4 * j + 4, axis=axis)
         for j in range(3)]
    return p[0] + p[1] + p[2]


# Rows of the one-hot: a bin's low seven bits.  ``_pad_pow`` makes every
# bin axis a whole number of such planes.
PLANE_BITS = 7
PLANE_BINS = 1 << PLANE_BITS


def onehot_planes(Bp: int) -> int:
    """How many planes of PLANE_BINS bins the one-hot body splits a bin
    axis of ``Bp`` into (1: nothing to split)."""
    return Bp // PLANE_BINS


def bin_sums(stats, Bp: int):
    """The one-hot body of both histogram kernels (_hist_kernel here,
    ops/record.py _hist_tile_body): ``stats`` [STAT_ROWS, T] bf16
    (split_stats) -> the function taking a ``[1, T]`` int32 row of bins
    (in the lanes) to its ``[4, Bp]`` float32 sums.

    A bin is ``b = PLANE_BINS * h + l``.  The one-hot is ``[128, T]``,
    the low bits ``l`` against a sublane iota; the high bits ride the
    dot's other operand, the stat rows where ``h_t == h`` and zero
    elsewhere stacked a plane after another: ``hist[s, 128 h + l] =
    sum_t (stats[s, t] * [h_t == h]) * [l_t == l]``.  The products that
    reach a bin are the rows of that bin, each an exact bf16 piece or an
    exact zero, so the sums are the ``[Bp, T]`` one-hot's, for half the
    compares, selects and packs at 256 bins and a quarter at 512 (the
    VPU building the one-hot bounds both kernels, not the dot: PERF.md,
    PR 29, 31, 37).  The planes' ``[4, 128]`` results lie side by side
    on whole vregs: the ``[4, Bp]`` block keeps its layout.  One plane
    has nothing to split, and the operand is ``stats`` itself (a mask
    that is all true read 7% more bundles in the schedule)."""
    T = stats.shape[1]
    H = onehot_planes(Bp)
    iota_s = jax.lax.broadcasted_iota(jnp.int32, (PLANE_BINS, T), 0)
    zero = jnp.zeros_like(stats) if H > 1 else None
    lane_dot = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    def sums_of(row):
        if H == 1:
            return merge_stats(
                lane_dot(stats, (row == iota_s).astype(jnp.bfloat16)))
        high = row >> PLANE_BITS
        planes = lane_dot(  # [STAT_ROWS * H, 128]
            jax.lax.concatenate([jax.lax.select(
                jax.lax.broadcast_in_dim(high == h, stats.shape, (0, 1)),
                stats, zero) for h in range(H)], 0),
            ((row & (PLANE_BINS - 1)) == iota_s).astype(jnp.bfloat16))
        # side by side on the lanes, [STAT_ROWS, Bp], then the pieces
        return merge_stats(jax.lax.concatenate(
            [jax.lax.slice_in_dim(planes, STAT_ROWS * h, STAT_ROWS * (h + 1))
             for h in range(H)], 1))

    return sums_of


# Rows between two folds of the kernel's small accumulator into the
# output block (see _hist_kernel), whatever the chunk.
FOLD_ROWS = 8192


def _hist_kernel(leaf_of_chunk, bins_ref, stats_ref, out_ref, acc_ref,
                 lo_ref, *, num_f, num_b, chunk):
    """One grid step = one C-row chunk of a single leaf, for one chunk
    of ``num_f`` features (grid: feature chunks, then row chunks: a
    feature chunk walks every row before the next begins).

    bins_ref:  [Fc, C] uint8 (this chunk's bins, feature-major)
    stats_ref: [STAT_ROWS, C] bf16 — split_stats of (g*m, h*m, m, 0)
    out_ref:   [1, Fc, 4, B] f32 block at row ``leaf_of_chunk[c]`` —
               revisited (and therefore VMEM-resident) across all chunks
               of the same leaf.
    acc_ref:   [Fc, 4, B] f32 scratch — the last few chunks' sum
    lo_ref:    [Fc, 4, B] f32 scratch — what the folds' roundings lost

    The chunks do not add into ``out_ref`` one by one: at nine million
    rows that is 17,000 roundings at the size a bin has reached, and a
    bin of four million rows ends tens of ulps off — the error every
    sibling-by-subtraction below inherits (PERF.md, PR 28).  They add
    into ``acc_ref``; every FOLD_ROWS rows that folds into
    ``out_ref`` with the rounding's error kept in ``lo_ref``
    (ops/totals.py two_sum), and the leaf's last chunk adds ``lo_ref``
    back: the bin is the correctly rounded sum of the chunks' partial
    sums.
    """
    c = pl.program_id(1)
    last = pl.num_programs(1) - 1
    leaf = leaf_of_chunk[c]
    is_first = (c == 0) | (leaf != leaf_of_chunk[jnp.maximum(c - 1, 0)])
    is_last = (c == last) | (leaf != leaf_of_chunk[jnp.minimum(c + 1, last)])

    @pl.when(is_first)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        lo_ref[...] = jnp.zeros_like(lo_ref)

    sums_of = bin_sums(stats_ref[...], num_b)  # [1, C] -> [4, B]

    # int8 VMEM rows are 4-packed per sublane, so a dynamically-indexed
    # SINGLE-row vector.load cannot be proven aligned by Mosaic ("index
    # in dimension 0 is a multiple of 4").  Instead the loop walks the
    # feature axis LOOP_ROWS rows at a time (one packed uint8 tile: the
    # dynamic start is provably aligned) and slices rows statically
    # within the step; the rows past the last whole step (num_f is a
    # FGROUP multiple, not a LOOP_ROWS one) follow at a static start.
    # Compiled code size stays O(LOOP_ROWS), not O(num_f).
    def rows(f0, count):
        blk = bins_ref[pl.ds(f0, count), :].astype(jnp.int32)
        for i in range(count):
            # a [1, C] row — stays in the lanes
            acc_ref[f0 + i] = acc_ref[f0 + i] + sums_of(blk[i: i + 1, :])

    steps, rest = divmod(num_f, LOOP_ROWS)

    def step_body(s, _):
        rows(s * LOOP_ROWS, LOOP_ROWS)
        return 0

    if steps:  # (a loop of no steps is traced all the same)
        jax.lax.fori_loop(0, steps, step_body, 0)
    if rest:
        rows(steps * LOOP_ROWS, rest)

    fold = max(1, FOLD_ROWS // chunk)

    @pl.when(is_last | (c % fold == fold - 1))
    def _():
        t, err = two_sum(out_ref[0], acc_ref[...])
        lo = lo_ref[...] + err
        out_ref[0] = jnp.where(is_last, t + lo, t)
        lo_ref[...] = lo
        acc_ref[...] = jnp.zeros_like(acc_ref)


def _pad_pow(b: int) -> int:
    """Bin axis padded up to a lane multiple (128).  Must never round
    DOWN: max_bin > 256 is legal (uint16 bins), and a capped pad would
    silently drop rows whose bin >= cap from the histogram."""
    return ((b + 127) // 128) * 128


def _hist_pallas_call(
    leaf_of_chunk, bins_buf, stats_buf, out_leaves, Fp, B, C, n_chunks,
    interpret, raw=False,
):
    """The one pallas_call: one grid step per feature chunk and C-row
    chunk, output block indexed by the scalar-prefetched chunk->leaf
    map.  Returns hist[out_leaves, Fp, B, 4] in the CANONICAL bin-major
    layout — or, with ``raw=True``, the kernel's NATIVE
    [out_leaves, Fp, 4, B] layout with no relayout at all: the round-3
    profile showed the per-split transpose to the canonical layout
    radiating ~0.5 ms/split of layout-churn fusions through the whole
    split step.

    Resident in VMEM, with ``(Fc, NC) = feature_chunk(Fp, B)``: the
    bins block ``Fc * C`` bytes and the stats block ``STAT_ROWS * C *
    2``, both double-buffered; the output block and the two scratch
    blocks, ``Fc * 4 * B * 4`` each (the output's twice); and the body's
    ``[128, C]`` one-hot and ``[16 * B / 128, C]`` masked stat rows.
    About 5 MiB at Fc = 256, B = 256, C = 2048, whatever ``Fp`` is: no
    width is refused for it."""
    Fc, NC = feature_chunk(Fp, B)
    kernel = functools.partial(_hist_kernel, num_f=Fc, num_b=B, chunk=C)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(NC, n_chunks),
        in_specs=[
            pl.BlockSpec((Fc, C), lambda fc, c, leaf_ref: (fc, c)),
            pl.BlockSpec((STAT_ROWS, C), lambda fc, c, leaf_ref: (0, c)),
        ],
        out_specs=pl.BlockSpec(
            (1, Fc, 4, B), lambda fc, c, leaf_ref: (leaf_ref[c], fc, 0, 0)
        ),
        scratch_shapes=[pltpu.VMEM((Fc, 4, B), jnp.float32)] * 2,
    )
    with phase_scope(f"histogram.cap{n_chunks * C}"):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (out_leaves, Fp, 4, B), jnp.float32),
            interpret=interpret,
        )(leaf_of_chunk, bins_buf, stats_buf)
    if raw:
        return out  # [L, Fp, 4, B] kernel-native
    return out.transpose(0, 1, 3, 2)  # -> [L, Fp, B, 4]


@functools.partial(
    jax.jit, static_argnames=("num_bins", "chunk", "interpret")
)
@phase_scope("histogram")
def histogram_single_leaf(
    bins_T: jax.Array,  # [F, cap] binned rows of ONE leaf (masked)
    grad: jax.Array,  # [cap]
    hess: jax.Array,  # [cap]
    mask: jax.Array,  # [cap] 0/1 validity
    num_bins: int,
    chunk: int = SINGLE_LEAF_CHUNK,
    interpret: bool = False,
) -> jax.Array:
    """hist[F, num_bins, 3] for a single row set — the leaf-wise
    learner's per-split histogram (DenseBin::ConstructHistogram over the
    smaller child's gathered rows, dense_bin.hpp:39-104): every chunk
    accumulates into the one output block, so no sort, no scatter — just
    O(cap x B x F) dense MACs.
    """
    F, cap = bins_T.shape
    bins_T, stats, n_chunks, Fp, B, C = _prep_single_leaf(
        bins_T, grad, hess, mask, num_bins, chunk)
    out = _hist_pallas_call(
        jnp.zeros(n_chunks, jnp.int32), bins_T, stats, 1, Fp, B, C,
        n_chunks, interpret,
    )  # [1, Fp, B, 4]
    return out[0, :F, :num_bins, :3]


def _prep_single_leaf(bins_T, grad, hess, mask, num_bins, chunk):
    """Shared single-leaf padding/stat prep: lane-aligned chunk width
    (an unaligned int8 block is the Mosaic failure class the kernel's
    row loop exists to avoid), features padded to the kernel grouping, and
    the split (g*m, h*m, m, 0) stat rows."""
    F, cap = bins_T.shape
    C = max(128, (chunk // 128) * 128)
    B = _pad_pow(num_bins)
    Fp = ((F + FGROUP - 1) // FGROUP) * FGROUP
    if Fp != F:
        bins_T = jnp.pad(bins_T, ((0, Fp - F), (0, 0)))
    pad = (-cap) % C
    if pad:
        bins_T = jnp.pad(bins_T, ((0, 0), (0, pad)))
        grad = jnp.pad(grad, (0, pad))
        hess = jnp.pad(hess, (0, pad))
        mask = jnp.pad(mask, (0, pad))
    gm = grad * mask
    hm = hess * mask
    stats = split_stats(jnp.stack(
        [gm, hm, mask, jnp.zeros_like(mask)]).astype(jnp.float32))
    return bins_T, stats, (cap + pad) // C, Fp, B, C


@functools.partial(
    jax.jit, static_argnames=("num_bins", "chunk", "interpret")
)
@phase_scope("histogram")
def histogram_single_leaf_raw(
    bins_T: jax.Array,  # [F, cap] binned rows of ONE leaf (masked)
    grad: jax.Array,  # [cap]
    hess: jax.Array,  # [cap]
    mask: jax.Array,  # [cap] 0/1 validity
    num_bins: int,
    chunk: int = SINGLE_LEAF_CHUNK,
    interpret: bool = False,
) -> jax.Array:
    """histogram_single_leaf in the KERNEL-NATIVE [Fp, 4, Bp] layout
    (stat rows g/h/count/zero, bins in lanes, features padded to the
    kernel's grouping) — zero post-processing, so the whole split step
    can stay in one layout (see _hist_pallas_call raw)."""
    bins_T, stats, n_chunks, Fp, B, C = _prep_single_leaf(
        bins_T, grad, hess, mask, num_bins, chunk)
    out = _hist_pallas_call(
        jnp.zeros(n_chunks, jnp.int32), bins_T, stats, 1, Fp, B, C,
        n_chunks, interpret, raw=True,
    )  # [1, Fp, 4, B]
    return out[0]


def make_single_hist_fn_raw(num_bins: int, chunk: int = SINGLE_LEAF_CHUNK):
    """hist_fn for the leaf-wise grower's RAW-layout path (signature:
    bins_T, grad, hess, mask -> [Fp, 4, Bp])."""
    return _single_hist_fn_raw(num_bins, chunk, not on_tpu())


@functools.lru_cache(maxsize=None)
def _single_hist_fn_raw(num_bins: int, chunk: int, interpret: bool):
    def hist_fn(bins_T, grad, hess, mask):
        return histogram_single_leaf_raw(
            bins_T, grad, hess, mask,
            num_bins=num_bins, chunk=chunk, interpret=interpret,
        )

    return hist_fn


def make_single_hist_fn(num_bins: int, chunk: int = SINGLE_LEAF_CHUNK):
    """hist_fn for the leaf-wise grower (signature: bins_T, grad, hess,
    mask -> [F, B, 3]) backed by the single-leaf MXU kernel.  Cached per
    (num_bins, chunk, interpret): the grower jits with hist_fn as a
    static argument, so returning the SAME closure across boosters (cv
    folds, repeated train calls) is what keeps the jit cache warm."""
    return _single_hist_fn(num_bins, chunk, not on_tpu())


@functools.lru_cache(maxsize=None)
def _single_hist_fn(num_bins: int, chunk: int, interpret: bool):
    def hist_fn(bins_T, grad, hess, mask):
        return histogram_single_leaf(
            bins_T, grad, hess, mask,
            num_bins=num_bins, chunk=chunk, interpret=interpret,
        )

    return hist_fn
