"""Where a child's sums come from (PR 28): the fused split step takes the
SMALLER child's histogram from its rows and the sibling by subtraction,
its accumulator folds into a two-float running sum, and a numerical left
side's gradient and hessian are the histogram's own prefix.  Each was a
way for a small leaf under a large node to inherit the large node's
absolute rounding once hessians vary (PERF.md, PR 28).  Since PR 31 the
smaller child's rows are STAGED, compacted across the parent's tiles,
and the histogram body runs on full tiles of them: held here to float64
numpy wherever the staging has an edge, with the count of tiles it ran.
Interpret mode, on the CPU.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu.ops.record as R
from lightgbm_tpu.analysis.kernel_parity import _fused_split, _np_partition
from lightgbm_tpu.ops.pallas_search import (
    _pack_meta, _pack_scal, search2_pallas)
from lightgbm_tpu.ops.split import find_best_split

_F, _B = 6, 16
_T = R.TILE
_K = R.bins_per_word(jnp.uint8)
_FP, _BP = R.round_up(_F, 8), R.round_up(_B, 128)


def _window(n, thr, seed=0):
    """A record of ``n`` live rows whose split ``feature 2 <= thr``
    sends few rows one way, with hessians like a second binary tree's."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, _B, (_F, n)).astype(np.uint8)
    g = rng.randn(n).astype(np.float32)
    h = (0.9987 + 1e-3 * rng.randn(n)).astype(np.float32)
    rec = R.build_record(jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
                         jnp.ones(n, jnp.float32), R.round_up(n, _T) + _T)
    left = bins[2] <= thr
    return rec, bins, g, h, left


def _hist64(bins, g, h, rows, m=None):
    """[F, 3, B] float64 histogram of ``rows`` (bool), weighted by the
    bagging mask ``m``."""
    m = np.ones_like(g) if m is None else m
    out = np.zeros((_F, 3, _B))
    for f in range(_F):
        for s, v in enumerate((g * m, h * m, m)):
            out[f, s] = np.bincount(bins[f, rows], v[rows].astype(np.float64),
                                    _B)
    return out


def _step(rec, parent, n, thr, lc, rc):
    return _step_counted(rec, parent, n, thr, lc, rc)[:3]


def _step_counted(rec, parent, n, thr, lc, rc, begin=0):
    """Both children's histograms, ``nleft`` and the histogram tiles the
    kernel ran."""
    hists = np.zeros((3, _FP, 4, _BP), np.float32)
    hists[0, :_F, :3, :_B] = parent
    scal_f = _pack_scal(*[jnp.float32(x) for x in (
        1., 0., 1., lc, 0., 1., rc, 1., 0., 0., 0., 0.)])
    meta = _pack_meta(jnp.ones(_F, bool), jnp.full(_F, _B, jnp.int32),
                      jnp.zeros(_F, bool), _FP)
    cap = R.round_up(n, _T)
    hs, _, nleft, *_, ran = R.split_step_counted(
        jnp.asarray(hists), rec, jnp.int32(begin), jnp.int32(n),
        jnp.bool_(True), jnp.int32(2), jnp.int32(thr), jnp.bool_(False),
        jnp.int32(0), jnp.int32(2), scal_f, meta, F=_F, cap=cap, k=_K,
        interpret=True, tiles_per_step=1)  # a window of whole tiles
    hs = np.asarray(hs)
    return hs[0, :_F, :3, :_B], hs[2, :_F, :3, :_B], int(nleft), int(ran)


@pytest.mark.parametrize("small", ["left", "right"])
def test_the_smaller_child_is_summed_and_the_larger_subtracted(small):
    n = 6 * _T + 17
    thr = 0 if small == "left" else _B - 2
    rec, bins, g, h, left = _window(n, thr)
    parent = _hist64(bins, g, h, np.ones(n, bool)).astype(np.float32)
    hl, hr, nleft = _step(rec, parent, n, thr, left.sum(), (~left).sum())
    assert nleft == left.sum() and min(nleft, n - nleft) < n // 8
    got_small, got_large = (hl, hr) if small == "left" else (hr, hl)
    want = _hist64(bins, g, h, left if small == "left" else ~left)
    # the smaller child to float32's own rounding of ITS bins (against
    # the sum of magnitudes: gradients cancel) ...
    size = _hist64(bins, np.abs(g), h, left if small == "left" else ~left)
    assert (np.abs(got_small - want) <= 3e-7 * size).all()
    # (by subtraction it would carry the parent's: bins 16x the size)
    # ... and the larger by subtraction from the parent, bit for bit
    assert (parent - got_small).tobytes() == got_large.tobytes()


def test_a_bin_of_many_tiles_is_rounded_once():
    """More tiles than one fold holds, every row in one bin of feature
    0: the bin is the float64 sum rounded to float32, not a chain of
    float32 additions."""
    n = (2 * R.FOLD_TILES + 3) * _T
    rec, bins, g, h, left = _window(n, _B // 2, seed=3)
    parent = _hist64(bins, g, h, np.ones(n, bool)).astype(np.float32)
    hl, hr, nleft = _step(rec, parent, n, _B // 2, left.sum(), (~left).sum())
    small, rows = (hl, left) if left.sum() <= (~left).sum() else (hr, ~left)
    want = _hist64(bins, g, h, rows)
    assert np.abs(small[:, 1] - want[:, 1].astype(np.float32)).max() <= \
        np.spacing(np.float32(want[:, 1].max()))
    np.testing.assert_array_equal(small[:, 2], want[:, 2])


# The smaller child's rows in each of the parent's seven tiles (the last
# holds 17 live rows): where the staging buffer has an edge.  At two and
# four tiles a grid step each edge falls inside a step or on its border.
_STAGED = {
    "fills_two_tiles_exactly": (_T, 0, 300, _T - 300, 0, 0, 0),
    "straddles_inside_a_parent_tile": (300, 400, 0, 0, 100, 0, 5),
    "full_tiles_back_to_back": (_T, _T, 500, 0, 0, 0, 0),
    "no_rows_at_all": (0,) * 7,
    "all_in_the_partly_invalid_last_tile": (0, 0, 0, 0, 0, 0, 9),
}
# And on leaves of 1, K-1, K, K+1 and 2K+1 tiles at K tiles a grid step,
# two fifths of every tile's rows: a tile of staged rows fills in the
# third parent tile of each two and a half.
_TWO_FIFTHS = "two_fifths_of_every_tile"
_CASES = [(case, K, len(_STAGED[case])) for case in sorted(_STAGED)
          for K in (1, 2, 4)] + [
    (_TWO_FIFTHS, K, live) for K in (1, 2, 4)
    for live in sorted({1, K - 1, K, K + 1, 2 * K + 1} - {0})]
# Every case's window and record: whole blocks of four tiles, with room
# for ``begin`` and another leaf after the window.
_CAP, _N_PAD = 12 * _T, 16 * _T


def _per_tile(case, live):
    """The smaller child's rows in each of a leaf's ``live`` tiles, the
    last of which holds 17 rows."""
    if case == _TWO_FIFTHS:
        return (2 * _T // 5,) * (live - 1) + (7,)
    return _STAGED[case]


@pytest.mark.parametrize("begin", [0, 77])
@pytest.mark.parametrize("small", ["left", "right"])
@pytest.mark.parametrize("case,K,live", _CASES,
                         ids=[f"{c}-K{K}-live{live}" for c, K, live in _CASES])
def test_the_staged_histogram_is_the_smaller_childs(case, K, live, small,
                                                     begin):
    """The smaller child's histogram from its staged rows, against
    float64 numpy, at K parent tiles a grid step on a leaf of ``live``
    tiles: a bagging mask with zeros, a window that starts ``begin``
    columns into the record and is followed by another leaf's rows
    (which would go left, with gradients of 100), ``small`` on either
    side.  The kernel ran ``ceil(rows / TILE)`` histogram tiles: the full
    ones as they filled, the remainder at the drain.  The record placed
    after it is a numpy stable partition's, and what the step returns is
    K = 1's bit for bit: the compacted tiles the placement reads, the
    counts, the histogram tiles run, the histogram rows."""
    per_tile = _per_tile(case, live)
    n, thr = (live - 1) * _T + 17, 7
    rng = np.random.RandomState(len(case) + live)
    is_small = np.zeros(n, bool)
    for j, c in enumerate(per_tile):
        rows = min(_T, n - j * _T)
        is_small[j * _T + rng.choice(rows, c, replace=False)] = True
    left = is_small if small == "left" else ~is_small
    bins = rng.randint(0, _B, (_F, n)).astype(np.uint8)
    bins[2] = np.where(left, rng.randint(0, thr + 1, n),
                       rng.randint(thr + 1, _B, n))
    g = rng.randn(n).astype(np.float32)
    h = (0.9987 + 1e-3 * rng.randn(n)).astype(np.float32)
    m = (rng.rand(n) < 0.8).astype(np.float32)
    pad = ((0, 0), (begin, _T))  # the columns before, another leaf after
    rec = R.build_record(
        jnp.asarray(np.pad(bins, pad)),
        jnp.asarray(np.pad(g, pad[1], constant_values=100.0)),
        jnp.asarray(np.pad(h, pad[1], constant_values=1.0)),
        jnp.asarray(np.pad(m, pad[1], constant_values=1.0)), _N_PAD)
    parent = _hist64(bins, g, h, np.ones(n, bool), m).astype(np.float32)
    # the search's counts are bagged ones; the kernel sums the side with
    # fewer, which the cases keep the one meant
    lc, rc = (m * left).sum(), (m * ~left).sum()
    assert (lc <= rc) == (small == "left")
    got, one = (_split_at(rec, parent, n, thr, lc, rc, begin, live, tiles)
                for tiles in (K, 1))
    assert np.asarray(got.comp)[:live].tobytes() == \
        np.asarray(one.comp)[:live].tobytes()
    for name in ("cl", "cr", "nleft", "hists", "res"):
        assert np.asarray(getattr(got, name)).tobytes() == \
            np.asarray(getattr(one, name)).tobytes(), name
    assert int(got.nleft) == left.sum()
    want_rec, _ = _np_partition(rec, left, begin, n,
                                R.num_words(_F, _K) + 4, 0, 2)
    assert np.asarray(got.rec).tobytes() == want_rec.tobytes()
    hs = np.asarray(got.hists)
    hl, hr = hs[0, :_F, :3, :_B], hs[2, :_F, :3, :_B]
    got_small, got_large = (hl, hr) if small == "left" else (hr, hl)
    want = _hist64(bins, g, h, is_small, m)
    size = _hist64(bins, np.abs(g), h, is_small, m)
    assert (np.abs(got_small - want) <= 3e-7 * size).all()
    np.testing.assert_array_equal(got_small[:, 2], want[:, 2])
    assert (parent - got_small).tobytes() == got_large.tobytes()
    assert got.ran == one.ran == -(-sum(per_tile) // _T) <= live + 1


def _split_at(rec, parent, n, thr, lc, rc, begin, live, tiles):
    """The launch pair on the leaf at ``tiles`` parent tiles a grid step
    (analysis/kernel_parity.py ``_fused_split``: the smaller child summed,
    the rows placed)."""
    hists = np.zeros((3, _FP, 4, _BP), np.float32)
    hists[0, :_F, :3, :_B] = parent
    scal_f = _pack_scal(*[jnp.float32(x) for x in (
        1., 0., 1., lc, 0., 1., rc, 1., 0., 0., 0., 0.)])
    meta = _pack_meta(jnp.ones(_F, bool), jnp.full(_F, _B, jnp.int32),
                      jnp.zeros(_F, bool), _FP)
    return _fused_split(rec, jnp.asarray(hists), begin, n, 2, thr, 0, 2,
                        scal_f, meta, _F, _CAP, jnp.int32(live), True,
                        tiles_per_step=tiles)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_a_small_left_side_keeps_its_own_digits(impl):
    """Two bins: 1e-3 of hessian left of the threshold, a million right
    of it.  ``total - right`` in float32 would answer 0 or 0.0625."""
    F, B = 8, 16
    hist = np.zeros((F, B, 3), np.float32)
    hist[:, 0] = (-0.25e-3, 1.0e-3, 5.0)
    hist[:, 1] = (3.0e5, 1.0e6, 5.0)
    tot = hist[0].sum(axis=0, dtype=np.float64).astype(np.float32)
    fmask, nbpf, iscat = np.ones(F, bool), np.full(F, 2, np.int32), \
        np.zeros(F, bool)
    z, one = jnp.float32(0.0), jnp.float32(1.0)
    if impl == "jnp":
        res = find_best_split(
            jnp.asarray(hist), *map(jnp.float32, tot), jnp.asarray(fmask),
            jnp.asarray(nbpf), jnp.asarray(iscat), one, z, z, z, z,
            jnp.asarray(True))
    else:
        res = search2_pallas(
            jnp.asarray(hist), jnp.asarray(hist),
            *map(jnp.float32, tot), *map(jnp.float32, tot),
            jnp.asarray(True), jnp.asarray(fmask), jnp.asarray(nbpf),
            jnp.asarray(iscat), one, z, z, z, z, interpret=True)[0]
    assert int(res.feature) == 0 and int(res.threshold) == 0
    assert float(res.left_sum_hess) == np.float32(1.0e-3)
    assert float(res.left_sum_grad) == np.float32(-0.25e-3)
    assert float(res.left_count) == 5.0 and float(res.right_count) == 5.0
