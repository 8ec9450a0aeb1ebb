"""The one-hot body of both histogram kernels (PR 37): a ``[128, lanes]``
one-hot of a bin's low seven bits, the high bits on the stat rows
(ops/pallas_histogram.py bin_sums).  Every bin's sum adds the terms it
added under the ``[Bp, lanes]`` one-hot, so both kernels are held here
to ``==`` with that form (kept below as this file's reference, not in
the package) and to a float64 numpy histogram, at one plane, at a plane's
edge, at two planes and at four, with rows in every plane's first and
last bin, hessians that vary and a bag mask.  Interpret mode, on the CPU.
"""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu.ops.pallas_histogram as PH
import lightgbm_tpu.ops.record as R
from lightgbm_tpu.ops.pallas_search import _pack_meta, _pack_scal

_F, _T = 6, R.TILE  # six columns: two padded features beside them
_FP = R.round_up(_F, 8)
_BINS = [63, 127, 128, 255, 256, 511]
_KERNELS = (PH.histogram_single_leaf_raw, R.split_step_counted)


def _parents_bin_sums(stats, Bp):
    """``bin_sums`` as the parent of PR 37 wrote it into both kernels:
    one ``[Bp, lanes]`` one-hot against the sixteen stat rows."""
    iota_s = jax.lax.broadcasted_iota(jnp.int32, (Bp, stats.shape[1]), 0)

    def sums_of(row):
        onehot = (row == iota_s).astype(jnp.bfloat16)
        return PH.merge_stats(jax.lax.dot_general(
            stats, onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32))

    return sums_of


@contextlib.contextmanager
def _the_parents_body():
    """Both kernels traced with the parent's body, and traced anew
    after."""
    ours, traced = PH.bin_sums, []

    def body(stats, Bp):
        traced.append(stats.shape)
        return _parents_bin_sums(stats, Bp)

    PH.bin_sums = body
    try:
        for fn in _KERNELS:
            fn.clear_cache()
        yield
        assert traced  # the kernels did take this body
    finally:
        PH.bin_sums = ours
        for fn in _KERNELS:
            fn.clear_cache()


def _table(num_bins, n):
    """``n`` rows of ``_F`` binned columns; the first rows sit in every
    plane's first and last bin in every column but the split's (column
    2, where they are bin 0: the summed child's).  Hessians like a
    second binary tree's; three rows in ten out of the bag, none of the
    planted ones."""
    rng = np.random.RandomState(num_bins)
    bins = rng.randint(0, num_bins, (_F, n)).astype(
        np.uint8 if num_bins <= 256 else np.uint16)
    edges = [b for b in (0, 127, 128, 255, 256, 383, 384, num_bins - 1)
             if b < num_bins]
    for j, b in enumerate(edges):
        bins[:, 3 * j: 3 * j + 3] = b
    bins[2, : 3 * len(edges)] = 0
    g = rng.randn(n).astype(np.float32)
    h = (0.25 * rng.rand(n) + 1e-3).astype(np.float32)
    m = (rng.rand(n) > 0.3).astype(np.float32)
    m[: 3 * len(edges)] = 1.0
    return bins, g, h, m, edges


def _hist64(bins, g, h, m, rows, num_bins):
    """[F, 3, num_bins] float64 histogram of ``rows`` (bool)."""
    out = np.zeros((_F, 3, num_bins))
    for f in range(_F):
        for s, v in enumerate((g * m, h * m, m)):
            out[f, s] = np.bincount(
                bins[f, rows], v[rows].astype(np.float64), num_bins)
    return out


def _root(bins, g, h, m, num_bins):
    return np.asarray(PH.histogram_single_leaf_raw(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(m),
        num_bins=num_bins, interpret=True))  # [Fp, 4, Bp]


def _step(bins, g, h, m, num_bins, thr, parent):
    """Both children's ``[Fp, 4, Bp]`` histograms of the split ``column
    2 <= thr`` and the histogram tiles the kernel ran."""
    n, Bp = bins.shape[1], R.round_up(num_bins, 128)
    k = R.bins_per_word(bins.dtype)
    rec = R.build_record(jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
                         jnp.asarray(m), R.round_up(n, _T) + _T)
    hists = np.zeros((3, _FP, 4, Bp), np.float32)
    hists[0] = parent
    left = bins[2] <= thr
    scal_f = _pack_scal(*[jnp.float32(x) for x in (
        1., 0., 1., m[left].sum(), 0., 1., m[~left].sum(),
        1., 0., 0., 0., 0.)])
    meta = _pack_meta(jnp.ones(_F, bool), jnp.full(_F, num_bins, jnp.int32),
                      jnp.zeros(_F, bool), _FP)
    hs, _, nleft, *_, ran = R.split_step_counted(
        jnp.asarray(hists), rec, jnp.int32(0), jnp.int32(n),
        jnp.bool_(True), jnp.int32(2), jnp.int32(thr), jnp.bool_(False),
        jnp.int32(0), jnp.int32(2), scal_f, meta, F=_F, cap=R.round_up(n, _T),
        k=k, interpret=True, tiles_per_step=1)  # a window of whole tiles
    assert int(nleft) == left.sum()
    hs = np.asarray(hs)
    return hs[0], hs[2], int(ran)


@pytest.mark.parametrize("num_bins", _BINS)
def test_planes(num_bins):
    assert PH.onehot_planes(R.round_up(num_bins, 128)) == {
        63: 1, 127: 1, 128: 1, 255: 2, 256: 2, 511: 4}[num_bins]


@pytest.mark.parametrize("num_bins", _BINS)
def test_the_root_kernel_sums_what_the_parents_form_summed(num_bins):
    n = 2 * PH.SINGLE_LEAF_CHUNK + 100  # two chunks and a short third
    bins, g, h, m, edges = _table(num_bins, n)
    got = _root(bins, g, h, m, num_bins)
    with _the_parents_body():
        old = _root(bins, g, h, m, num_bins)
    assert got.shape == (_FP, 4, R.round_up(num_bins, 128))
    np.testing.assert_array_equal(got, old)
    want = _hist64(bins, g, h, m, np.ones(n, bool), num_bins)
    np.testing.assert_allclose(got[:_F, :3, :num_bins], want,
                               rtol=1e-5, atol=1e-4)
    assert not got[:, :, num_bins:].any() and not got[:, 3].any()
    # the planted rows: every plane's first and last bin is counted
    for b in edges:
        assert (got[[0, 1, 3, 4, 5], 2, b] >= 3).all(), b
    # a padded feature is all bin 0
    assert got[_F, 2, 0] == m.sum() and not got[_F, :, 1:].any()


@pytest.mark.parametrize("num_bins", _BINS)
def test_the_split_step_sums_what_the_parents_form_summed(num_bins):
    n = 9 * _T + 17
    bins, g, h, m, edges = _table(num_bins, n)
    thr = num_bins // 4  # the left child the smaller: the summed one
    left = bins[2] <= thr
    assert _T < left.sum() < n // 2  # a full staged tile and a drain
    parent = np.zeros((_FP, 4, R.round_up(num_bins, 128)), np.float32)
    parent[:_F, :3, :num_bins] = _hist64(
        bins, g, h, m, np.ones(n, bool), num_bins)
    parent[_F:, :3, 0] = [(g * m).sum(), (h * m).sum(), m.sum()]
    got = _step(bins, g, h, m, num_bins, thr, parent)
    with _the_parents_body():
        old = _step(bins, g, h, m, num_bins, thr, parent)
    assert got[2] == old[2] == -(-int(left.sum()) // _T)
    for ours, theirs in zip(got[:2], old[:2]):
        np.testing.assert_array_equal(ours, theirs)
    for child, rows in ((got[0], left), (got[1], ~left)):
        np.testing.assert_allclose(
            child[:_F, :3, :num_bins],
            _hist64(bins, g, h, m, rows, num_bins), rtol=1e-5, atol=1e-4)
        assert not child[:, :, num_bins:].any()
    for b in edges:
        assert (got[0][[0, 1, 3, 4, 5], 2, b] >= 3).all(), b
