"""Kernel-against-reference checks for the hardware-only branches.

The CPU suite pins kernel parity in INTERPRET mode only, and three
branches never run off the chip at all: ``place_runs``' aliased
placement kernel (interpret returns the XLA reference), the direct
aliased record read of ``split_step_window`` (interpret reads a
materialised slice), and ``write_window``'s aliased write-back
(interpret uses a dynamic-update-slice).  Mosaic compilation is a
different program (layout, MXU accumulation order, select
legalisation), so these checks run the compiled kernels against their
``jax.numpy``/numpy references on whatever backend is present:

  search    — search2_pallas_raw vs find_best_split_leaves: integer-
              exact histograms (any summation order exact -> bitwise
              comparable decisions) plus float histograms at tolerance
  split     — split_step_window (the fused split step) vs
              partition_window + histogram_single_leaf_raw +
              search2_update_pallas, and both histogram kernels against
              a float64 numpy histogram (a demoted MXU precision in the
              un-annotated one-hot dots shows there)
  writeback — write_window (aliased) vs a numpy slice assignment
  place     — place_runs (aliased placement) vs partition_window's XLA
              scan-of-DUS placement, at the static tile count and, as
              the grower launches it, over a wider window at a run-time
              tile count (dynamic Mosaic grids, parked chunks)

All run at the import-default routing (``ops.record.ROUTING``).  Each
check prints one summary line through ``log`` and returns True/False.
``chip_smoke.py`` and ``tools/tpu_parity_check.py`` are the callers; on
a TPU ``interpret=False`` (the default) means Mosaic.
"""

from __future__ import annotations

import numpy as np

#: max |kernel - kernel| on float histograms built in different groupings
HIST_TOL = 2e-2
#: max |kernel - float64 numpy| — float32 accumulation of a few hundred
#: N(0,1) values lands near 1e-5; a one-pass bf16 MXU demotion near 2e-2
HIST_REF_TOL = 1e-3


def check_search(rng, log=print, interpret=False) -> bool:
    import jax.numpy as jnp

    from ..config import Config
    from ..learners.serial import TreeLearnerParams
    from ..ops.pallas_search import search2_pallas_raw
    from ..ops.split import find_best_split_leaves

    F, B = 12, 64
    Fp, Bp = 16, 128
    ok = True
    for trial, integer in ((0, True), (1, True), (2, False)):
        if integer:  # exact under ANY accumulation order
            hg = rng.randint(-8, 9, (2, F, B)).astype(np.float32)
            hh = rng.randint(1, 5, (2, F, B)).astype(np.float32)
        else:
            hg = rng.randn(2, F, B).astype(np.float32)
            hh = (rng.rand(2, F, B) + 0.1).astype(np.float32)
        hc = rng.randint(1, 50, (2, F, B)).astype(np.float32)
        # tie case: duplicate the best feature's histogram onto a higher
        # index — the smaller feature must win (split_info.hpp:98-103)
        hg[:, 7] = hg[:, 3]
        hh[:, 7] = hh[:, 3]
        hc[:, 7] = hc[:, 3]
        h2 = np.zeros((2, Fp, 4, Bp), np.float32)
        h2[:, :F, 0, :B] = hg
        h2[:, :F, 1, :B] = hh
        h2[:, :F, 2, :B] = hc
        sums = h2.sum(axis=3)  # [2, Fp, 4]
        lsg, lsh, lc = (sums[0, :F, j].sum() / F for j in range(3))
        rsg, rsh, rc = (sums[1, :F, j].sum() / F for j in range(3))
        prm = TreeLearnerParams.from_config(
            Config(min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3))
        args = (jnp.float32(lsg), jnp.float32(lsh), jnp.float32(lc),
                jnp.float32(rsg), jnp.float32(rsh), jnp.float32(rc))
        fmask = jnp.ones(F, bool)
        nbpf = jnp.full(F, B, jnp.int32)
        iscat = jnp.zeros(F, bool)
        rl, rr = search2_pallas_raw(
            jnp.asarray(h2), *args, jnp.bool_(True), fmask, nbpf, iscat,
            prm.min_data_in_leaf, prm.min_sum_hessian_in_leaf,
            prm.lambda_l1, prm.lambda_l2, prm.min_gain_to_split,
            interpret=interpret)
        hist = jnp.asarray(
            np.stack([np.stack([hg[c], hh[c], hc[c]], -1) for c in (0, 1)]))
        ref = find_best_split_leaves(
            hist, jnp.asarray([lsg, rsg]), jnp.asarray([lsh, rsh]),
            jnp.asarray([lc, rc]), fmask, nbpf, iscat,
            prm.min_data_in_leaf, prm.min_sum_hessian_in_leaf,
            prm.lambda_l1, prm.lambda_l2, prm.min_gain_to_split,
            jnp.asarray([True, True]))
        for c, r in ((0, rl), (1, rr)):
            f_k, t_k = int(r.feature), int(r.threshold)
            f_j, t_j = int(ref.feature[c]), int(ref.threshold[c])
            g_k, g_j = float(r.gain), float(ref.gain[c])
            if integer:
                same = (f_k == f_j and t_k == t_j)
            else:  # float: decisions may differ only at near-ties
                same = (f_k == f_j and t_k == t_j) or abs(
                    g_k - g_j) <= 1e-4 * max(1.0, abs(g_j))
            if not same:
                log(f"  search MISMATCH trial {trial} child {c}: "
                    f"kernel (f={f_k}, t={t_k}, g={g_k}) vs "
                    f"jnp (f={f_j}, t={t_j}, g={g_j})")
                ok = False
    log(f"search parity: {'OK' if ok else 'FAIL'}")
    return ok


def _np_hist(bins, g, h, m, num_bins):
    """[F, 3, num_bins] float64 histogram (grad, hess, count rows)."""
    out = np.zeros((bins.shape[0], 3, num_bins), np.float64)
    for fi, row in enumerate(np.asarray(bins)):
        for j, stat in enumerate((g * m, h * m, m)):
            out[fi, j] = np.bincount(
                row, weights=stat.astype(np.float64), minlength=num_bins)
    return out


def check_split(rng, log=print, interpret=False) -> bool:
    import jax
    import jax.numpy as jnp

    from ..ops.pallas_histogram import histogram_single_leaf_raw
    from ..ops.pallas_search import (
        _pack_meta, _pack_scal, _unpack, search2_update_pallas)
    from ..ops.record import (
        TILE, bins_per_word, build_record, extract_feature, num_words,
        partition_window, round_up, split_step_window, unpack_window)

    F, n, num_bins, L = 11, 5000, 37, 7
    bins = rng.randint(0, num_bins, (F, n)).astype(np.uint8)
    g = rng.randn(n).astype(np.float32)
    h = (rng.rand(n) + 0.5).astype(np.float32)
    bag = (rng.rand(n) > 0.2).astype(np.float32)
    k = bins_per_word(jnp.uint8)
    cap = round_up(n, TILE)
    rec = build_record(jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
                       jnp.asarray(bag), cap + TILE)
    Fp, Bp = round_up(F, 8), round_up(num_bins, 128)
    hists_np = np.zeros((L, Fp, 4, Bp), np.float32)
    hists_np[0] = np.asarray(histogram_single_leaf_raw(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
        jnp.asarray(bag), num_bins=num_bins, interpret=interpret))
    f, thr = 4, 11
    fv = extract_feature(rec, jnp.int32(f), jnp.int32(0), cap, k)
    go = (fv <= thr).astype(jnp.int32)
    meta = _pack_meta(jnp.ones(F, bool), jnp.full(F, num_bins, jnp.int32),
                      jnp.zeros(F, bool), Fp)
    scal_args = [jnp.float32(x) for x in
                 (1.0, 1., 2., 300., -1., 2., 300.)]
    lim_args = [jnp.float32(x) for x in (20., 1e-3, 0., 0., 0.)]
    scal = _pack_scal(*(scal_args + lim_args))

    recA, nlA = partition_window(
        rec, go, jnp.int32(0), jnp.int32(n), jnp.bool_(True), cap,
        interpret=interpret)
    govm = np.asarray(go).astype(bool) & (np.arange(cap) < n)
    win = jax.lax.dynamic_slice(rec, (0, 0), (rec.shape[0], cap))
    bw, gw, hw, mw = unpack_window(win, F, k, jnp.uint8)
    h_left = histogram_single_leaf_raw(
        bw, gw, hw, jnp.asarray(np.asarray(mw) * govm), num_bins=num_bins,
        interpret=interpret)
    histsA, resLA, resRA = search2_update_pallas(
        jnp.asarray(hists_np), h_left, jnp.int32(0), jnp.int32(1),
        jnp.bool_(True), jnp.bool_(True), *scal_args[1:],
        jnp.float32(1.0), jnp.ones(F, bool),
        jnp.full(F, num_bins, jnp.int32), jnp.zeros(F, bool), *lim_args,
        interpret=interpret)

    histsB, recB, nlB, res = split_step_window(
        jnp.asarray(hists_np), rec, jnp.int32(0), jnp.int32(n),
        jnp.bool_(True), jnp.int32(f), jnp.int32(thr), jnp.bool_(False),
        jnp.int32(0), jnp.int32(1), scal, meta, F=F, cap=cap, k=k,
        interpret=interpret)

    ok = True
    if int(nlA) != int(nlB):
        log(f"  split nleft mismatch: {int(nlA)} vs {int(nlB)}")
        ok = False
    # data rows must match exactly; the fused path additionally stamps
    # the leaf-id row, which partition_window (leaf_row=None) left at 0
    lr = num_words(F, k) + 4
    ra, rb = np.asarray(recA), np.asarray(recB)
    rows = [r for r in range(rec.shape[0]) if r != lr]
    if not np.array_equal(ra[rows], rb[rows]):
        log("  split record data rows mismatch")
        ok = False
    d = float(np.abs(np.asarray(histsA) - np.asarray(histsB)).max())
    if d > HIST_TOL:  # different accumulation grouping on real floats
        log(f"  split hists row diff {d}")
        ok = False
    # both histogram kernels against float64 numpy: root (single-leaf
    # kernel) and the two children (in-kernel tile histogram + subtract)
    left = np.asarray(bins[f]) <= thr
    want = [_np_hist(bins, g, h, bag * m, num_bins)
            for m in (np.ones(n), left, ~left)]
    got = [hists_np[0], np.asarray(histsB[0]), np.asarray(histsB[1])]
    d_ref = max(float(np.abs(gk[:F, :3, :num_bins] - w).max())
                for gk, w in zip(got, want))
    if d_ref > HIST_REF_TOL:
        log(f"  split hists vs float64 numpy diff {d_ref} "
            f"(> {HIST_REF_TOL}: MXU precision demoted?)")
        ok = False
    for c, (a, b) in enumerate(
            ((resLA, _unpack(res, 0)), (resRA, _unpack(res, 1)))):
        fa, fb = int(a.feature), int(b.feature)
        if fa != fb:  # float accumulation may flip only exact ties
            log(f"  split child {c} feature mismatch: {fa} vs {fb} "
                f"(gains {float(a.gain):.6g} vs {float(b.gain):.6g})")
            ok = ok and abs(float(a.gain) - float(b.gain)) <= 1e-4 * max(
                1.0, abs(float(a.gain)))
    log(f"split parity: {'OK' if ok else 'FAIL'} (nleft={int(nlB)}, "
        f"hist maxdiff kernel-kernel={d:.2e}, kernel-float64={d_ref:.2e})")
    return ok


def check_writeback(rng, log=print, interpret=False) -> bool:
    import jax.numpy as jnp

    from ..ops.record import TILE, write_window

    rec = jnp.asarray(
        rng.randint(-2**30, 2**30, (16, 8 * TILE)).astype(np.int32))
    out = jnp.asarray(
        rng.randint(-2**30, 2**30, (16, 2 * TILE)).astype(np.int32))
    ok = True
    for begin in (0, 1, 37, 500, TILE - 1):
        got = np.asarray(write_window(
            rec, out, jnp.int32(begin), 2 * TILE, interpret=interpret))
        ref = np.asarray(rec).copy()
        ref[:, begin:begin + 2 * TILE] = np.asarray(out)
        if not np.array_equal(got, ref):
            bad = np.argwhere(got != ref)
            log(f"  writeback MISMATCH at begin={begin}: "
                f"{len(bad)} cells, first {bad[:3].tolist()}")
            ok = False
    log(f"writeback parity: {'OK' if ok else 'FAIL'}")
    return ok


def check_place(rng, log=print, interpret=False) -> bool:
    """place_runs (aliased placement kernel) vs the XLA scan-of-DUS
    reference it replaces — the hardware-only path (interpret falls
    back to the reference)."""
    import jax.numpy as jnp

    from ..ops import record
    from ..ops.pallas_search import _pack_meta, _pack_scal
    from ..ops.record import (
        TILE, bins_per_word, build_record, extract_feature, num_words,
        partition_window, place_runs, round_up, split_step_window)

    # the last trial runs with a tiny step-table chunk so the
    # multi-launch chunk-boundary path (forced adv=1 per launch) is
    # pinned at test size — place_runs reads record.PLACE_CHUNK when it
    # traces, and the trial's unique shape forces a fresh trace
    ok = True
    chunk0 = record.PLACE_CHUNK
    try:
        for trial, (F, n, num_bins, begin_off, frac) in enumerate((
                (9, 5000, 33, 0, 0.5),
                (9, 5000, 33, 777, 0.2),   # unaligned begin, unbalanced
                (9, 5000, 33, 1291, 0.97),  # nearly-all-left
                (5, 2000, 16, 300, 0.0),   # all-right
                (7, 3000, 17, 133, 0.4),   # multi-chunk placement
        )):
            record.PLACE_CHUNK = 8 if trial == 4 else chunk0
            bins = rng.randint(0, num_bins, (F, n)).astype(np.uint8)
            g = rng.randn(n).astype(np.float32)
            h = (rng.rand(n) + 0.5).astype(np.float32)
            bag = np.ones(n, np.float32)
            k = bins_per_word(jnp.uint8)
            # room for the wider window of the run-time-count launch
            total = round_up(n + begin_off, TILE) + 3 * TILE
            rec = build_record(
                jnp.asarray(np.pad(bins, ((0, 0), (begin_off, 0)))),
                jnp.asarray(np.pad(g, (begin_off, 0))),
                jnp.asarray(np.pad(h, (begin_off, 0))),
                jnp.asarray(np.pad(bag, (begin_off, 0))), total)
            cap = round_up(n, TILE)
            thr = int(num_bins * frac)
            f = 2
            begin = jnp.int32(begin_off)
            fv = extract_feature(rec, jnp.int32(f), begin, cap, k)
            go = (fv <= thr).astype(jnp.int32)
            lr = num_words(F, k) + 4

            # reference: partition_window (scan-of-DUS) with leaf stamping
            recA, nlA = partition_window(
                rec, go, begin, jnp.int32(n), jnp.bool_(True), cap,
                left_leaf=jnp.int32(3), right_leaf=jnp.int32(5),
                leaf_row=lr, interpret=interpret)
            # kernel path: compacted tiles -> place_runs
            Fp, Bp = round_up(F, 8), round_up(num_bins, 128)
            # slots 3 and 5 are written by the kernel's hists index maps
            # — allocate past them (Pallas does not bounds-check them)
            hists = jnp.zeros((7, Fp, 4, Bp), jnp.float32)
            meta = _pack_meta(jnp.ones(F, bool),
                              jnp.full(F, num_bins, jnp.int32),
                              jnp.zeros(F, bool), Fp)
            scal = _pack_scal(*[jnp.float32(x) for x in
                                (1., 0., 1., 9., 0., 1., 9., 1., 1e-3,
                                 0., 0., 0.)])
            _, comp, nlB, _, clB, crB, _rp = split_step_window(
                hists, rec, begin, jnp.int32(n), jnp.bool_(True),
                jnp.int32(f), jnp.int32(thr), jnp.bool_(False),
                jnp.int32(3), jnp.int32(5), scal, meta, F=F, cap=cap, k=k,
                return_comp=True, interpret=interpret)
            recB = place_runs(
                jnp.array(rec), comp, go, begin, jnp.int32(n), nlB,
                jnp.bool_(True), jnp.int32(3), jnp.int32(5), cap=cap,
                leaf_row=lr, interpret=interpret)
            # kernel-emitted counts must reproduce the go-derived ones
            govm2 = np.asarray(go).astype(np.int64) * (np.arange(cap) < n)
            want_cl = govm2.reshape(-1, TILE).sum(axis=1)
            if not np.array_equal(np.asarray(clB), want_cl):
                log(f"  place trial {trial}: kernel cl mismatch")
                ok = False
            if int(nlA) != int(nlB):
                log(f"  place trial {trial}: nleft {int(nlA)} vs {int(nlB)}")
                ok = False
            # the grower's launch pair: a window two tiles wider than
            # the leaf, visited over the live tiles only (a run-time
            # grid; chunks past the live steps run one parked step)
            cap2, live = cap + 2 * TILE, jnp.int32(cap // TILE)
            _, comp2, nlC, _, clC, crC, rp2 = split_step_window(
                jnp.zeros((7, Fp, 4, Bp), jnp.float32), rec, begin, jnp.int32(n), jnp.bool_(True),
                jnp.int32(f), jnp.int32(thr), jnp.bool_(False),
                jnp.int32(3), jnp.int32(5), scal, meta, F=F, cap=cap2,
                k=k, return_comp=True, interpret=interpret,
                live_tiles=live)
            recC = place_runs(
                jnp.array(rp2), comp2, None, begin, jnp.int32(n), nlC,
                jnp.bool_(True), jnp.int32(3), jnp.int32(5), cap=cap2,
                leaf_row=lr, interpret=interpret, counts=(clC, crC),
                live_tiles=live)
            ra = np.asarray(recA)
            for what, got in (("", recB), (" (live tiles)", recC)):
                rb = np.asarray(got)
                if not np.array_equal(ra, rb):
                    bad = [r for r in range(ra.shape[0])
                           if not np.array_equal(ra[r], rb[r])]
                    log(f"  place trial {trial}{what}: record rows "
                        f"differ {bad}")
                    ok = False
            if int(nlC) != int(nlA) or np.asarray(clC)[cap // TILE:].any():
                log(f"  place trial {trial} (live tiles): nleft "
                    f"{int(nlC)} vs {int(nlA)}, or counts past the "
                    f"live tiles")
                ok = False
    finally:
        record.PLACE_CHUNK = chunk0
    log(f"place parity: {'OK' if ok else 'FAIL'}")
    return ok


def run_all(log=print, interpret=False) -> dict:
    """All four checks from one seed; {name: passed}.  ``interpret``
    rehearses the comparison logic off the chip, where the three
    hardware-only branches fall back to their references."""
    rng = np.random.RandomState(0)
    return {
        "writeback": check_writeback(rng, log, interpret),
        "search": check_search(rng, log, interpret),
        "split": check_split(rng, log, interpret),
        "place": check_place(rng, log, interpret),
    }
