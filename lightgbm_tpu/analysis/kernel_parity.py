"""Kernel-against-reference checks for the hardware-only branches.

The CPU suite pins kernel parity in INTERPRET mode only, and two
branches never run off the chip at all: ``place_runs``' aliased
placement kernel (interpret returns the XLA reference) and the direct
aliased record read of ``split_step_window`` (interpret reads a
materialised slice).  Mosaic compilation is a different program
(layout, MXU accumulation order, select legalisation), so these checks
run the fused grower's launch pair, compiled, against references that
share no code with it, on whatever backend is present:

  search — the split step's in-kernel two-child search vs
           find_best_split_leaves over float64 numpy histograms:
           integer-valued statistics (any summation order exact ->
           bitwise comparable decisions, a crafted tie) plus float
           statistics at tolerance
  split  — the split step's record, counts and both children's
           histogram rows, and the root histogram kernel, vs a numpy
           stable partition and float64 numpy histograms (a demoted MXU
           precision in the un-annotated one-hot dots shows there), with
           the smaller child a third, under 5% (left, then right, at an
           unaligned begin) and half of the parent, then the same at
           2,000 columns (a 512-word record, eight feature chunks) and
           on a leaf whose tiles are all-left, mixed and all-right (the
           compaction's lane gathers at both ends of the heights the
           cells run, and at runs of 0 and TILE lanes); prints how many
           histogram tiles the kernel ran of the parent's
  place  — place_runs (aliased placement) vs the numpy stable
           partition and vs the XLA reference placement on the same
           compacted tiles, at the static tile count and, as the grower
           launches it, over a wider window at a run-time tile count
           (a dynamic Mosaic grid); begins at 0, mid-block and T - 1,
           nleft 0 and pcnt, a window inside one block, a leaf with
           all-left and all-right tiles, and do_split false

Each check prints one summary line through ``log`` and returns
True/False.  ``chip_smoke.py`` is the caller; on a TPU
``interpret=False`` (the default) means Mosaic.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

#: max |kernel - float64 numpy| — float32 accumulation of a few hundred
#: N(0,1) values lands near 1e-5; a one-pass bf16 MXU demotion near 2e-2
HIST_REF_TOL = 1e-3


def _np_hist(bins, g, h, m, num_bins):
    """[F, 3, num_bins] float64 histogram (grad, hess, count rows)."""
    out = np.zeros((bins.shape[0], 3, num_bins), np.float64)
    for fi, row in enumerate(np.asarray(bins)):
        for j, stat in enumerate((g * m, h * m, m)):
            out[fi, j] = np.bincount(
                row, weights=stat.astype(np.float64), minlength=num_bins)
    return out


def _np_partition(rec, go, begin, pcnt, leaf_row, left_leaf, right_leaf):
    """Stable partition of columns [begin, begin + pcnt) of ``rec`` by
    ``go`` (window-relative, 1 = left), child leaf ids stamped into
    ``leaf_row``; everything else untouched.  Returns (rec', nleft)."""
    out = np.array(rec)
    g = np.asarray(go[:pcnt]).astype(bool)
    win = out[:, begin:begin + pcnt]
    out[:, begin:begin + pcnt] = np.concatenate(
        [win[:, g], win[:, ~g]], axis=1)
    nleft = int(g.sum())
    out[leaf_row, begin:begin + nleft] = left_leaf
    out[leaf_row, begin + nleft:begin + pcnt] = right_leaf
    return out, nleft


class Split(NamedTuple):
    """What ``_fused_split`` returns: the step's outputs and the placed
    record."""

    hists: object
    rec: object
    nleft: object
    res: object
    cl: object
    cr: object
    comp: object
    ran: int  # histogram tiles run


def _fused_split(rec, hists, begin, pcnt, f, thr, left_leaf, right_leaf,
                 scal, meta, F, cap, live_tiles, interpret,
                 tiles_per_step=None) -> Split:
    """The fused grower's launch pair on one window (uint8 bins); the
    split step at the parent tiles a grid step the record's height gives,
    or at ``tiles_per_step``, which nothing but tests and timings sets."""
    import jax.numpy as jnp

    from ..ops.record import (
        bins_per_word, num_words, place_runs, split_step_counted)

    k = bins_per_word(jnp.uint8)
    i32 = jnp.int32
    hists2, comp, nleft, res, cl, cr, rec_pass, ran = split_step_counted(
        jnp.array(hists), rec, i32(begin), i32(pcnt), jnp.bool_(True), i32(f),
        i32(thr), jnp.bool_(False), i32(left_leaf), i32(right_leaf),
        scal, meta, F=F, cap=cap, k=k, interpret=interpret,
        live_tiles=live_tiles, tiles_per_step=tiles_per_step)
    rec2 = place_runs(
        jnp.array(rec_pass), comp, (cl, cr), i32(begin), i32(pcnt), nleft,
        jnp.bool_(True), i32(left_leaf), i32(right_leaf), cap=cap,
        leaf_row=num_words(F, k) + 4, interpret=interpret,
        live_tiles=live_tiles)
    return Split(hists2, rec2, nleft, res, cl, cr, comp, int(ran))


def _block(F):
    """Columns of the split step's block at ``F`` columns of uint8 bins:
    the window and the record come in whole blocks, as the grower's do
    (learners/fused.py), so the launch takes the parent tiles a grid step
    the record's height gives."""
    from ..ops.record import TILE, rec_height, split_tiles

    return TILE * split_tiles(rec_height(F, 4))


def _runs(n, num_bins, head):
    """A bins row whose first ``head`` rows hold bin 0 and the rest the
    last bin: split at threshold 0, the tiles before ``head`` are
    all-left, the one it falls in is mixed, the rest all-right."""
    return np.where(np.arange(n) < head, 0, num_bins - 1).astype(np.uint8)


def _split_case(rng, F, n, num_bins, integer, bag_frac, tie=None, begin=0,
                runs=None):
    """One leaf of ``n`` rows, ``begin`` columns into its record, and
    everything the split step needs of it: returns (bins, g, h, bag,
    rec, meta).  ``runs`` = (feature, head) lays that feature out as
    ``_runs`` does."""
    import jax.numpy as jnp

    from ..ops.pallas_search import _pack_meta
    from ..ops.record import build_record, round_up

    bins = rng.randint(0, num_bins, (F, n)).astype(np.uint8)
    if tie is not None:
        bins[tie[1]] = bins[tie[0]]
    if runs is not None:
        bins[runs[0]] = _runs(n, num_bins, runs[1])
    if integer:  # exact under ANY accumulation order
        g = rng.randint(-8, 9, n).astype(np.float32)
        h = rng.randint(1, 5, n).astype(np.float32)
    else:
        g = rng.randn(n).astype(np.float32)
        h = (rng.rand(n) + 0.5).astype(np.float32)
    bag = (rng.rand(n) < bag_frac).astype(np.float32)
    rec = build_record(
        jnp.asarray(np.pad(bins, ((0, 0), (begin, 0)))),
        *(jnp.asarray(np.pad(v, (begin, 0))) for v in (g, h, bag)),
        round_up(n + begin, _block(F)) + _block(F))
    meta = _pack_meta(jnp.ones(F, bool), jnp.full(F, num_bins, jnp.int32),
                      jnp.zeros(F, bool), round_up(F, 8))
    return bins, g, h, bag, rec, meta


def _root_hists(bins, g, h, bag, num_bins, L, interpret):
    import jax.numpy as jnp

    from ..ops.pallas_histogram import histogram_single_leaf_raw

    root = histogram_single_leaf_raw(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(bag),
        num_bins=num_bins, interpret=interpret)
    return jnp.zeros((L,) + root.shape, jnp.float32).at[0].set(root)


def check_search(rng, log=print, interpret=False) -> bool:
    import jax.numpy as jnp

    from ..config import Config
    from ..learners.serial import TreeLearnerParams
    from ..ops.pallas_search import _pack_scal, _unpack
    from ..ops.record import round_up
    from ..ops.split import find_best_split_leaves

    F, n, B = 12, 3000, 64
    f, thr = 4, 30
    prm = TreeLearnerParams.from_config(
        Config(min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3))
    ok = True
    for trial, integer in ((0, True), (1, True), (2, False)):
        # tie case: feature 7 is feature 3 again — the smaller feature
        # index must win (split_info.hpp:98-103)
        bins, g, h, bag, rec, meta = _split_case(
            rng, F, n, B, integer, 0.8, tie=(3, 7))
        left = bins[f] <= thr
        sides = [_np_hist(bins, g, h, bag * m, B) for m in (left, ~left)]
        tot = [s[0].sum(axis=1) for s in sides]  # (Σg, Σh, count) a side
        scal = _pack_scal(
            jnp.float32(1.0), *[jnp.float32(x) for x in (*tot[0], *tot[1])],
            prm.min_data_in_leaf, prm.min_sum_hessian_in_leaf,
            prm.lambda_l1, prm.lambda_l2, prm.min_gain_to_split)
        res = _fused_split(
            rec, _root_hists(bins, g, h, bag, B, 3, interpret), 0, n, f,
            thr, 0, 1, scal, meta, F, round_up(n, _block(F)), None,
            interpret).res
        ref = find_best_split_leaves(
            jnp.asarray(np.stack([s.transpose(0, 2, 1) for s in sides]),
                        jnp.float32),
            jnp.asarray([tot[0][0], tot[1][0]], jnp.float32),
            jnp.asarray([tot[0][1], tot[1][1]], jnp.float32),
            jnp.asarray([tot[0][2], tot[1][2]], jnp.float32),
            jnp.ones(F, bool), jnp.full(F, B, jnp.int32),
            jnp.zeros(F, bool),
            prm.min_data_in_leaf, prm.min_sum_hessian_in_leaf,
            prm.lambda_l1, prm.lambda_l2, prm.min_gain_to_split,
            jnp.asarray([True, True]))
        for c in (0, 1):
            r = _unpack(res, c)
            f_k, t_k = int(r.feature), int(r.threshold)
            f_j, t_j = int(ref.feature[c]), int(ref.threshold[c])
            g_k, g_j = float(r.gain), float(ref.gain[c])
            same = (f_k == f_j and t_k == t_j)
            if not integer:  # float: decisions may differ only at near-ties
                same = same or abs(g_k - g_j) <= 1e-4 * max(1.0, abs(g_j))
            if not same:
                log(f"  search MISMATCH trial {trial} child {c}: "
                    f"kernel (f={f_k}, t={t_k}, g={g_k}) vs "
                    f"jnp (f={f_j}, t={t_j}, g={g_j})")
                ok = False
    log(f"search parity: {'OK' if ok else 'FAIL'}")
    return ok


def check_split(rng, log=print, interpret=False) -> bool:
    import jax.numpy as jnp

    from ..ops.pallas_search import _pack_scal
    from ..ops.record import TILE, bins_per_word, num_words, round_up

    n, num_bins = 5000, 36
    f = 4
    all_ok = True
    # (columns, threshold, begin, head of a runs feature): the smaller
    # child a third of the parent; under 5% of it, left and then right,
    # the window unaligned; half of it; a third at 2,000 columns (512
    # words); two all-left tiles, a mixed one and seven all-right
    for F, thr, begin, head in (
            (11, 11, 0, None), (11, 0, 777, None), (11, 34, 1291, None),
            (11, 17, 0, None), (2000, 11, 777, None),
            (11, 0, 1291, 2 * TILE + 100)):
        lr = num_words(F, bins_per_word(jnp.uint8)) + 4
        bins, g, h, bag, rec, meta = _split_case(
            rng, F, n, num_bins, False, 0.8, begin=begin,
            runs=None if head is None else (f, head))
        hists0 = _root_hists(bins, g, h, bag, num_bins, 7, interpret)
        left = bins[f] <= thr
        # the smaller child by bagged count is the one the kernel sums
        cnt = [float((bag * m).sum()) for m in (left, ~left)]
        small = int((left if cnt[0] <= cnt[1] else ~left).sum())
        scal = _pack_scal(*[jnp.float32(x) for x in (
            1.0, 1., 2., cnt[0], -1., 2., cnt[1], 20., 1e-3, 0., 0., 0.)])
        got = _fused_split(
            rec, hists0, begin, n, f, thr, 0, 1, scal, meta, F,
            round_up(n, _block(F)), None, interpret)
        hists, rec2, nleft, ran = got.hists, got.rec, got.nleft, got.ran

        ok = True
        want_rec, want_nl = _np_partition(rec, left, begin, n, lr, 0, 1)
        if int(nleft) != want_nl:
            log(f"  split nleft mismatch: {int(nleft)} vs {want_nl}")
            ok = False
        if not np.array_equal(np.asarray(rec2), want_rec):
            log("  split record differs from the numpy stable partition")
            ok = False
        # both histogram kernels against float64 numpy: root (single-leaf
        # kernel) and the two children (the smaller from its staged rows,
        # the larger by subtraction)
        want = [_np_hist(bins, g, h, bag * m, num_bins)
                for m in (np.ones(n), left, ~left)]
        got = [np.asarray(hists0[0]), np.asarray(hists[0]),
               np.asarray(hists[1])]
        d_ref = max(float(np.abs(gk[:F, :3, :num_bins] - w).max())
                    for gk, w in zip(got, want))
        if d_ref > HIST_REF_TOL:
            log(f"  split hists vs float64 numpy diff {d_ref} "
                f"(> {HIST_REF_TOL}: MXU precision demoted?)")
            ok = False
        if ran != -(-small // TILE):
            log(f"  split ran {ran} histogram tiles for {small} rows")
            ok = False
        log(f"split parity: {'OK' if ok else 'FAIL'} ({F} columns, "
            f"{_block(F) // TILE} tiles a grid step, "
            f"nleft={int(nleft)} of {n}, smaller child {small / n:.1%}, "
            f"hist tiles / parent tiles = {ran} / {-(-n // TILE)}, "
            f"hist maxdiff kernel-float64={d_ref:.2e})")
        all_ok &= ok
    return all_ok


def check_place(rng, log=print, interpret=False) -> bool:
    """place_runs (the aliased placement kernel) vs a numpy stable
    partition AND vs the XLA reference placement (``_xla_place``, which
    the CPU grower runs) fed the same compacted tiles, bit for bit —
    the hardware-only path (interpret falls back to that reference).
    A launch with ``do_split`` false must leave the record as it was."""
    import jax.numpy as jnp

    from ..ops.pallas_search import _pack_meta, _pack_scal
    from ..ops.record import (
        TILE, bins_per_word, build_record, num_words, place_runs, round_up,
        split_step_counted)

    ok = True
    i32 = jnp.int32
    for trial, (F, n, num_bins, begin, frac) in enumerate((
            (9, 5000, 33, 0, 0.5),
            (9, 5000, 33, 777, 0.2),   # unaligned begin, unbalanced
            (9, 5000, 33, 1291, 0.97),  # nearly-all-left
            (5, 2000, 16, 300, 0.0),   # all-right: nleft = 0
            (7, 3000, 17, TILE - 1, 1.0),  # all-left: nleft = pcnt
            (9, 5000, 33, 777, None),  # all-left and all-right tiles
            (9, 300, 33, 100, 0.5),  # a window inside one block
            # lefts end and rights begin in one block, begin % T = T - 1
            (7, 3000, 17, 2 * TILE - 1, 0.4),
    )):
        bins = rng.randint(0, num_bins, (F, n)).astype(np.uint8)
        if frac is None:
            bins[2], frac = _runs(n, num_bins, 3 * TILE + 57), 0.0
        g = rng.randn(n).astype(np.float32)
        h = (rng.rand(n) + 0.5).astype(np.float32)
        # room for the wider window of the run-time-count launch, in
        # whole blocks of the split step's tiles a grid step
        blk = _block(F)
        total = round_up(n + begin, blk) + 2 * blk
        rec = build_record(
            jnp.asarray(np.pad(bins, ((0, 0), (begin, 0)))),
            jnp.asarray(np.pad(g, (begin, 0))),
            jnp.asarray(np.pad(h, (begin, 0))),
            jnp.ones(n + begin, jnp.float32), total)
        cap = round_up(n, blk)
        thr = min(int(num_bins * frac), num_bins - 1)
        f = 2
        go = bins[f] <= thr
        k = bins_per_word(jnp.uint8)
        lr = num_words(F, k) + 4
        want, want_nl = _np_partition(rec, go, begin, n, lr, 3, 5)
        want_cl = np.pad(go, (0, cap - n)).reshape(-1, TILE).sum(axis=1)

        Fp, Bp = round_up(F, 8), round_up(num_bins, 128)
        meta = _pack_meta(jnp.ones(F, bool),
                          jnp.full(F, num_bins, jnp.int32),
                          jnp.zeros(F, bool), Fp)
        scal = _pack_scal(*[jnp.float32(x) for x in
                            (1., 0., 1., 9., 0., 1., 9., 1., 1e-3,
                             0., 0., 0.)])
        # the static tile count, then the grower's launch pair: a window
        # a block wider than the leaf, visited over the live tiles only
        # (a run-time grid)
        for what, cap_w, live in (
                ("", cap, None),
                (" (live tiles)", cap + blk, i32(cap // TILE))):
            # slots 3 and 5 are written by the kernel's hists index maps
            # — allocate past them (Pallas does not bounds-check them)
            _, comp, nl, _, cl, cr, rec_pass, _ = split_step_counted(
                jnp.zeros((7, Fp, 4, Bp), jnp.float32), rec, i32(begin),
                i32(n), jnp.bool_(True), i32(f), i32(thr), jnp.bool_(False),
                i32(3), i32(5), scal, meta, F=F, cap=cap_w, k=k,
                interpret=interpret, live_tiles=live)
            cl_np = np.asarray(cl)
            if (int(nl) != want_nl
                    or not np.array_equal(cl_np[:cap // TILE], want_cl)
                    or cl_np[cap // TILE:].any()):
                log(f"  place trial {trial}{what}: nleft {int(nl)} vs "
                    f"{want_nl}, or the kernel's tile counts differ")
                ok = False
            before = np.asarray(rec_pass)

            def place(split, reference):
                return np.asarray(place_runs(
                    jnp.array(rec_pass), comp, (cl, cr), i32(begin), i32(n),
                    nl, jnp.bool_(split), i32(3), i32(5), cap=cap_w,
                    leaf_row=lr, interpret=interpret or reference,
                    live_tiles=live))

            got = place(True, False)
            for name, ref in (("numpy", want), ("_xla_place",
                                                place(True, True))):
                if not np.array_equal(got, ref):
                    bad = [r for r in range(ref.shape[0])
                           if not np.array_equal(got[r], ref[r])]
                    log(f"  place trial {trial}{what}: record rows "
                        f"differ from {name}'s {bad}")
                    ok = False
            if not np.array_equal(place(False, False), before):
                log(f"  place trial {trial}{what}: do_split false wrote")
                ok = False
    log(f"place parity: {'OK' if ok else 'FAIL'}")
    return ok


def run_all(log=print, interpret=False) -> dict:
    """All checks from one seed; {name: passed}.  ``interpret``
    rehearses the comparison logic off the chip, where the two
    hardware-only branches fall back to their references."""
    rng = np.random.RandomState(0)
    return {
        "search": check_search(rng, log, interpret),
        "split": check_split(rng, log, interpret),
        "place": check_place(rng, log, interpret),
    }
