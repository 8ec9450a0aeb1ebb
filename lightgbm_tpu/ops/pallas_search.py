"""Pallas TPU kernel for the per-split best-threshold search.

The round-3 on-chip profile (tools/profile_split.py, BASELINE.md) showed
the leaf-wise loop bound by PER-OP overhead, not data volume: the jnp
split search compiles to ~60 small [F, B]-shaped fusions per split
(~1.6 ms), 4x the histogram kernel itself, and no jnp-level
restructuring escapes the per-op cost (batching the two children into
[2, F, B] ops left the steady state unchanged at ~0.95 s/tree).  This
kernel runs the ENTIRE two-child search — suffix sums, gain grid,
validity masking, deterministic (feature asc, bin desc) winner
selection, and winner-stat extraction — as ONE launch.

Design notes:

* Mosaic wants (sublane, lane) register shapes, so the kernel works in
  STRICTLY rank-2 arrays: the two children's [F, B, 3] histograms are
  pre-flattened to one [6F, B] operand (child-major, then stat, then
  feature), the two children unroll as Python iterations, scalars stay
  [1, 1] slices, and feature metadata arrives pre-transposed as [F, 4].
* Suffix sums ride the MXU: tail[t] = sum_{b>t} h[b] is one dot with
  the strict upper-triangular ones matrix at precision=HIGHEST
  (f32-accurate bf16 passes) — no reliance on a Mosaic cumsum lowering.
* The deterministic tie-break reproduces ops/split.py exactly under
  exact float equality: per feature the LARGEST threshold among
  equal-gain maxima, across features the SMALLEST feature index
  (split_info.hpp:98-103 semantics).
* Outputs are a [2, 16] f32 row pair (gain, feature, threshold, six
  stats, two leaf outputs, and with missing values the default
  direction in lane 11); the host-side wrapper casts feature and
  threshold back to int32 and rebuilds the two SplitResults.
* Missing values (``missing``, static): meta's fourth column holds each
  feature's missing bin (-1: none) and every value threshold of such a
  feature is scored twice, its missing rows right and left, under
  ops/split.py's tie rule.  Without it nothing of this is traced.

The jnp path in ops/split.py remains the reference implementation (and
the CPU / float64 path); tests pin this kernel against it in interpret
mode, including crafted exact ties.  Reference scan being replaced:
FeatureHistogram::FindBestThreshold* (feature_histogram.hpp:116-246).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .split import SplitResult, K_EPSILON

NEG = -3.4e38  # "no split" sentinel (python float on purpose: a jnp
# scalar would be a captured constant inside the kernel)
BIG = 2**30


def _tri(B):
    """Strict upper-triangular ones: tri[b, t] = 1.0 iff b > t."""
    bi = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
    ti = jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
    return (bi > ti).astype(jnp.float32)


def _tail_of(x, tri):
    """Exclusive suffix sums along bins: tail[., t] = sum_{b>t} x[., b]
    via one MXU dot at HIGHEST precision (f32-accurate)."""
    return jax.lax.dot_general(
        x, tri, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def _head_of(x, tri):
    """Inclusive prefix sums along bins: head[., t] = sum_{b<=t} x[., b],
    the same dot against the complement of ``tri``.  A numerical left
    side is read from here and not as ``total - tail``: a small left
    child under a large node would keep the absolute rounding of the
    node's totals, and of every ancestor's down a chain of left children
    (ops/split.py does the same with a cumsum)."""
    return _tail_of(x, 1.0 - tri)


def _pack_meta(feature_mask, num_bins_per_feature, is_categorical, Fp,
               missing_bin=None):
    """[F] feature metadata -> the kernels' [Fp, 4] i32 operand (padded
    features get feature_mask 0 and never validate); the fourth column
    is each feature's missing bin where ``missing_bin`` is given."""
    F = feature_mask.shape[0]
    meta = jnp.stack([
        feature_mask.astype(jnp.int32),
        num_bins_per_feature.astype(jnp.int32),
        is_categorical.astype(jnp.int32),
        (jnp.zeros(F, jnp.int32) if missing_bin is None
         else missing_bin.astype(jnp.int32)),
    ], axis=1)
    if Fp != F:
        meta = jnp.pad(meta, ((0, Fp - F), (0, 0)))
    return meta


def _child_search(c, hg, hh, hc, tg, th, tc, pg, ph, scal_ref, meta_ref,
                  out_ref, F, B, f0, best_ref, missing=False):
    """One child's full search given its stat planes [F, B], their
    exclusive suffix sums and the inclusive prefix sums of gradient and
    hessian (the count's prefix is ``cnt_t - tc``, exact either way);
    writes the child's [1, 16] result row.

    Called once a feature chunk (pallas_histogram.feature_chunk; once
    in all for a table of one chunk), in ascending feature order, with
    ``f0`` the chunk's first feature (a run-time scalar) and
    ``best_ref`` an SMEM [2] f32 scratch that keeps each child's best
    RAW gain so far: a later chunk's row replaces the kept one only
    where its raw maximum is strictly greater, so equal maxima keep the
    smaller feature index, which is what one search over every feature
    picks.  (Raw, not minus ``gain_shift``: the subtraction can round
    two distinct maxima into a tie.)  Everything else is a function of
    one (feature, bin) cell or of one feature's row, so the chunked
    search is the whole one bit for bit.

    Mosaic-friendly shapes only: [F, B] / [F, 1] vectors, TRUE scalars
    from the SMEM-prefetched ``scal_ref`` (scalar splats broadcast
    freely; [1,1]->[F,B] tensor broadcasts do not on this stack), and
    scalar full-array reduces for the winner selection.

    With ``missing`` each feature's missing bin (meta's fourth column)
    gives a second set of candidates, the bin's sums moved left; ties
    break by ops/split.py's rule (feature asc; missing-left first,
    threshold desc; then missing-right, threshold ASC on a column with a
    missing bin); lane 11 of the row says whether the winner sends them
    left.
    """
    fmask = meta_ref[:, 0:1] > 0  # [F, 1]
    nb = meta_ref[:, 1:2]  # [F, 1]
    iscat = meta_ref[:, 2:3] > 0  # [F, 1]
    bins = jax.lax.broadcasted_iota(jnp.int32, (F, B), 1)
    # a column's last bin is never a candidate, whatever its kind
    # (ops/split.py: a categorical column's is the others' bin)
    in_range = (bins < nb - 1) & fmask
    # the table's feature index, not the chunk's
    fi = jax.lax.broadcasted_iota(jnp.int32, (F, 1), 0) + f0
    lane16 = jax.lax.broadcasted_iota(jnp.int32, (1, 16), 1)

    min_data = scal_ref[8]
    min_hess = scal_ref[9]
    l1 = scal_ref[10]
    l2 = scal_ref[11]
    min_gain = scal_ref[12]

    def leaf_gain(sg, sh):
        reg = jnp.maximum(jnp.abs(sg) - l1, 0.0)
        return reg * reg / (sh + l2)

    can = scal_ref[4 * c + 0] > 0.0  # scalar bool
    sg_t = scal_ref[4 * c + 1]
    sh_t = scal_ref[4 * c + 2]
    cnt_t = scal_ref[4 * c + 3]

    left_g = jnp.where(iscat, hg, pg)
    left_h = jnp.where(iscat, hh, ph)
    left_c = jnp.where(iscat, hc, cnt_t - tc)
    right_g = jnp.where(iscat, sg_t - hg, tg)
    right_h = jnp.where(iscat, sh_t - hh, th)
    right_c = jnp.where(iscat, cnt_t - hc, tc)

    gain_shift = leaf_gain(sg_t, sh_t)  # scalar

    def scored(left_g, left_h, left_c, right_g, right_h, right_c, allowed):
        gains = leaf_gain(left_g, left_h) + leaf_gain(right_g, right_h)
        valid = (
            allowed
            & (left_c >= min_data) & (right_c >= min_data)
            & (left_h >= min_hess) & (right_h >= min_hess)
            & (gains >= gain_shift + min_gain)
            & can
        )
        return valid, jnp.where(valid, gains, NEG)  # [F, B]

    sides = (left_g, left_h, left_c, right_g, right_h, right_c)
    valid, score = scored(*sides, in_range)

    # deterministic winner: global max; largest t per feature among
    # maxima; smallest such feature
    if not missing:
        maxg = jnp.max(score)  # scalar
        at_max = (score == maxg) & valid
        tbest = jnp.max(jnp.where(at_max, bins, -1), axis=1,
                        keepdims=True)  # [F, 1]
        fbest = jnp.min(jnp.where(tbest >= 0, fi, BIG))  # scalar
        thr = jnp.max(jnp.where(fi == fbest, tbest, -1))  # scalar
    else:
        # missing rows left: the missing bin's sums move from the right
        # side to the left (ops/split.py); a right side keeps a value bin
        mb = meta_ref[:, 3:4]  # [F, 1], -1: no missing bin
        at_mb = bins == mb

        def of_mb(x):
            return jnp.sum(jnp.where(at_mb, x, 0.0), axis=1, keepdims=True)

        mg, mh, mc = of_mb(hg), of_mb(hh), of_mb(hc)
        sides_m = (pg + mg, ph + mh, cnt_t - (tc - mc), tg - mg, th - mh,
                   tc - mc)
        valid_m, score_m = scored(
            *sides_m, (bins < nb - 2) & (mb >= 0) & fmask)
        maxg = jnp.maximum(jnp.max(score_m), jnp.max(score))
        tbest_m = jnp.max(jnp.where((score_m == maxg) & valid_m, bins, -1),
                          axis=1, keepdims=True)
        # missing rows right: the lowest tied threshold on a column with
        # a missing bin (LightGBM's forward scan), the highest elsewhere
        at_r = (score == maxg) & valid
        hi_r = jnp.max(jnp.where(at_r, bins, -1), axis=1, keepdims=True)
        lo_r = jnp.min(jnp.where(at_r, bins, BIG), axis=1, keepdims=True)
        tbest_r = jnp.where((mb >= 0) & (hi_r >= 0), lo_r, hi_r)
        fbest = jnp.min(jnp.where((tbest_m >= 0) | (tbest_r >= 0), fi, BIG))
        at_f = fi == fbest
        dleft = jnp.max(jnp.where(at_f & (tbest_m >= 0), 1, 0)) > 0
        thr = jnp.max(jnp.where(at_f, jnp.where(dleft, tbest_m, tbest_r),
                                -1))
        sides = tuple(jnp.where(dleft, m, x) for m, x in zip(sides_m, sides))
        left_g, left_h, left_c, right_g, right_h, right_c = sides

    sel = (fi == fbest) & (bins == thr)  # [F, B]

    def pick(x):
        return jnp.sum(jnp.where(sel, x, 0.0))  # scalar

    lg, lh, lc = pick(left_g), pick(left_h), pick(left_c)
    rg, rh, rc = pick(right_g), pick(right_h), pick(right_c)

    def leaf_out(sg, sh):
        reg = jnp.maximum(jnp.abs(sg) - l1, 0.0)
        return -jnp.sign(sg) * reg / (sh + l2)

    ok = maxg > NEG  # scalar bool
    vals = [
        jnp.where(ok, maxg - gain_shift, -jnp.inf),
        jnp.where(ok, fbest, -1).astype(jnp.float32),
        jnp.where(ok, thr, 0).astype(jnp.float32),
        lg, lh, lc, rg, rh, rc,
        leaf_out(lg, lh), leaf_out(rg, rh),
    ]
    if missing:
        vals.append(jnp.where(ok & dleft, 1.0, 0.0))
    # assemble the [1, 16] row with lane selects (scalar splats are
    # the one broadcast form this Mosaic supports everywhere)
    row = jnp.zeros((1, 16), jnp.float32)
    for j, v in enumerate(vals):
        row = jnp.where(lane16 == j, v, row)
    better = (f0 == 0) | (maxg > best_ref[c])
    out_ref[c:c + 1, :] = jnp.where(better, row, out_ref[c:c + 1, :])
    best_ref[c] = jnp.where(better, maxg, best_ref[c])


def _search2_kernel(scal_ref, hist_ref, meta_ref, out_ref, best_ref,
                    *, F, B, missing=False):
    """One grid step: both children end-to-end on one feature chunk of
    ``F`` features (the whole table where it is one chunk).

    scal_ref [16]      f32 SMEM  (canL, lsg, lsh, lc, canR, rsg, rsh, rc,
                                  min_data, min_hess, l1, l2, min_gain)
    hist_ref [6, F, B] f32       child-major (c, s) planes: g, h, count
    meta_ref [F, 4]    i32       (feature_mask, nbpf, is_categorical, pad)
    out_ref  [2, 16]   f32
    best_ref [2]       f32 SMEM  (_child_search)
    """
    tri = _tri(B)
    for c in range(2):
        hg, hh, hc = (hist_ref[3 * c + s] for s in range(3))
        # tail[f, t] = sum_{b > t} h[f, b]; the hessian's seeded with
        # kEpsilon
        _child_search(
            c, hg, hh, hc,
            _tail_of(hg, tri), _tail_of(hh, tri) + K_EPSILON,
            _tail_of(hc, tri), _head_of(hg, tri), _head_of(hh, tri),
            scal_ref, meta_ref, out_ref, F, B, pl.program_id(0) * F,
            best_ref, missing,
        )


@functools.partial(jax.jit, static_argnames=("interpret",))
def search2_pallas(
    h_left, h_right,  # [F, B, 3] f32
    lsg, lsh, lc, rsg, rsh, rc,  # scalars
    can,  # scalar bool (shared by both children: same depth)
    feature_mask, num_bins_per_feature, is_categorical,  # [F]
    min_data_in_leaf, min_sum_hessian_in_leaf,
    lambda_l1, lambda_l2, min_gain_to_split,
    missing_bin=None,  # [F] int32, -1: none (ops/split.py)
    interpret: bool = False,
):
    """Both children's best splits in one kernel launch; returns two
    scalar SplitResults matching ops/split.find_best_split bit-for-bit
    up to the suffix-sum accumulation order (MXU triangular dot vs
    sequential cumsum — identical under exact arithmetic).  With
    ``missing_bin`` each is a ``(SplitResult, default_left)`` pair.

    The table is searched a feature chunk a grid step
    (pallas_histogram.feature_chunk; one step for a table of one chunk),
    the best kept across steps (_child_search): one ``[6, Fc, B]`` block
    is resident whatever the width."""
    from .pallas_histogram import feature_chunk

    missing = missing_bin is not None
    if h_left.dtype != jnp.float32 or h_right.dtype != jnp.float32:
        # a silent astype here would hide precision loss from a future
        # float64 hist_dtype caller; the f64 parity mode must stay on
        # the jnp search path (serial.py routes on hl.dtype)
        raise TypeError(
            f"search2_pallas requires float32 histograms, got "
            f"{h_left.dtype}/{h_right.dtype}"
        )
    F, B, _ = h_left.shape
    Fc, NC = feature_chunk(F, B)
    hist = (
        jnp.stack([h_left, h_right])  # [2, F, B, 3]
        .transpose(0, 3, 1, 2)  # [2, 3, F, B] child-major, stat, feature
        .astype(jnp.float32)
        .reshape(6, F, B)
    )
    # whole chunks: padded features get feature_mask 0 (_pack_meta)
    hist = jnp.pad(hist, ((0, 0), (0, NC * Fc - F), (0, 0)))
    meta = _pack_meta(
        feature_mask, num_bins_per_feature, is_categorical, NC * Fc,
        missing_bin)
    scal = _pack_scal(
        jnp.asarray(can, jnp.float32), lsg, lsh, lc, rsg, rsh, rc,
        min_data_in_leaf, min_sum_hessian_in_leaf,
        lambda_l1, lambda_l2, min_gain_to_split)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(NC,),
        in_specs=[
            pl.BlockSpec((6, Fc, B), lambda i, s: (0, i, 0)),
            pl.BlockSpec((Fc, 4), lambda i, s: (i, 0)),
        ],
        out_specs=pl.BlockSpec((2, 16), lambda i, s: (0, 0)),
        scratch_shapes=[pltpu.SMEM((2,), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_search2_kernel, F=Fc, B=B, missing=missing),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((2, 16), jnp.float32),
        interpret=interpret,
    )(scal, hist, meta)

    if not missing:
        return _unpack(out, 0), _unpack(out, 1)
    return tuple((_unpack(out, i), out[i, 11] > 0) for i in range(2))


def _unpack(out, i):
    row = out[i]
    return SplitResult(
        gain=row[0],
        feature=row[1].astype(jnp.int32),
        threshold=row[2].astype(jnp.int32),
        left_sum_grad=row[3],
        left_sum_hess=row[4],
        left_count=row[5],
        right_sum_grad=row[6],
        right_sum_hess=row[7],
        right_count=row[8],
        left_output=row[9],
        right_output=row[10],
    )


def _pack_scal(canf, lsg, lsh, lc, rsg, rsh, rc,
               min_data, min_hess, l1, l2, min_gain):
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    return jnp.stack([
        canf, f32(lsg), f32(lsh), f32(lc),
        canf, f32(rsg), f32(rsh), f32(rc),
        f32(min_data), f32(min_hess), f32(l1), f32(l2), f32(min_gain),
        f32(0), f32(0), f32(0),
    ])  # [16] SMEM scalar-prefetch
