"""The kernels' feature-chunk axis (PR 34): a table wider than one
``[Fc, 4, Bp]`` block of a leaf's histogram is walked in chunks by the
root histogram, by the split step's subtraction, search and ``hists`` row
traffic, and by the standalone search.  Each chunked kernel is held here
to its one-chunk self on the same rows, bit for bit, at widths on both
sides of a chunk boundary and one that is no multiple of the chunk; a tie
across chunks goes to the feature one search over every feature picks.
Interpret mode, on the CPU, with a short chunk (``CHUNK_BLOCK_BYTES``,
all that ``feature_chunk`` reads, made small and the kernels traced anew)
so the widths stay cheap.
"""

import contextlib

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu.ops.record as R
from lightgbm_tpu.ops import pallas_histogram as PH
from lightgbm_tpu.ops.pallas_search import (
    _pack_meta, _pack_scal, search2_pallas)
from lightgbm_tpu.ops.split import find_best_split

_B, _T, _K = 16, R.TILE, 4
_BP = R.round_up(_B, 128)
_CHUNK = 32  # one LOOP_ROWS step: the shortest chunk there is
# one chunk exactly; two, the second short; two whole; three, the last
# short and no multiple of FGROUP before padding
_WIDTHS = [32, 40, 64, 75]


@contextlib.contextmanager
def _chunks_of(features, bins=_BP):
    """The kernels traced with feature chunks of ``features`` features
    at ``bins`` lanes of bins, and traced anew after."""
    kernels = (PH.histogram_single_leaf_raw, R.split_step_counted,
               search2_pallas)
    whole = PH.CHUNK_BLOCK_BYTES
    PH.CHUNK_BLOCK_BYTES = features * 16 * bins
    try:
        for fn in kernels:
            fn.clear_cache()
        yield
    finally:
        PH.CHUNK_BLOCK_BYTES = whole
        for fn in kernels:
            fn.clear_cache()


def _table(F, n, seed=0, dup=()):
    """``n`` rows of ``F`` binned columns; ``dup`` pairs ``(a, b)`` make
    column ``b`` a copy of column ``a``."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, _B, (F, n)).astype(np.uint8)
    for a, b in dup:
        bins[b] = bins[a]
    g = rng.randn(n).astype(np.float32)
    h = (0.9987 + 1e-3 * rng.randn(n)).astype(np.float32)
    return bins, g, h


def test_the_chunk_is_a_function_of_the_block():
    assert PH.feature_chunk(104, 256) == (104, 1)  # synthetic-100
    assert PH.feature_chunk(224, 256) == (224, 1)  # istella-s-220
    assert PH.feature_chunk(256, 256) == (256, 1)
    assert PH.feature_chunk(264, 256) == (256, 2)
    assert PH.feature_chunk(2000, 256) == (256, 8)  # epsilon-2000
    assert PH.feature_chunk(512, 128) == (512, 1)  # fewer bins, more features
    assert PH.feature_chunk(1000, 512) == (128, 8)  # uint16 bins
    with _chunks_of(_CHUNK):
        assert PH.feature_chunk(40, _BP) == (32, 2)


@pytest.mark.parametrize("F", _WIDTHS)
def test_the_chunked_root_histogram_is_its_one_chunk_self(F):
    n = 3 * PH.SINGLE_LEAF_CHUNK + 100  # the fold and a short last chunk
    bins, g, h = _table(F, n, seed=F)
    m = (np.arange(n) % 7 > 0).astype(np.float32)
    args = [jnp.asarray(a) for a in (bins, g, h, m)]
    whole = PH.histogram_single_leaf_raw(*args, num_bins=_B, interpret=True)
    with _chunks_of(_CHUNK):
        chunked = PH.histogram_single_leaf_raw(
            *args, num_bins=_B, interpret=True)
        assert PH.feature_chunk(R.round_up(F, 8), _BP)[1] == -(-F // 32)
    assert whole.shape == chunked.shape == (R.round_up(F, 8), 4, _BP)
    assert np.asarray(whole).tobytes() == np.asarray(chunked).tobytes()
    # and it is the histogram: counts exact, feature 0 against numpy
    want = np.bincount(bins[0], m, _B)
    np.testing.assert_array_equal(np.asarray(whole)[0, 2, :_B], want)


def _split(F, bins, g, h, thr, f_split=2, begin=0):
    """One split step over the whole table: ``(hists, res, comp, nleft,
    hist tiles)`` with the root's histogram in row 0."""
    n = bins.shape[1]
    Fp = R.round_up(F, 8)
    ones = jnp.ones(n, jnp.float32)
    root = PH.histogram_single_leaf_raw(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), ones,
        num_bins=_B, interpret=True)
    hists = jnp.zeros((3, Fp, 4, _BP), jnp.float32).at[0].set(root)
    rec = R.build_record(jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
                         ones, R.round_up(n, _T) + _T)
    left = bins[f_split] <= thr
    lc, rc = float(left.sum()), float((~left).sum())
    scal_f = _pack_scal(*[jnp.float32(x) for x in (
        1., g[left].sum(), h[left].sum(), lc,
        g[~left].sum(), h[~left].sum(), rc, 5., 0., 0., 0., 0.)])
    meta = _pack_meta(jnp.ones(F, bool), jnp.full(F, _B, jnp.int32),
                      jnp.zeros(F, bool), Fp)
    hs, comp, nleft, res, *_, ran = R.split_step_counted(
        hists, rec, jnp.int32(begin), jnp.int32(n), jnp.bool_(True),
        jnp.int32(f_split), jnp.int32(thr), jnp.bool_(False), jnp.int32(0),
        jnp.int32(2), scal_f, meta, F=F, cap=R.round_up(n, _T), k=_K,
        interpret=True, tiles_per_step=1)  # a window of whole tiles
    return (np.asarray(hs), np.asarray(res), np.asarray(comp), int(nleft),
            int(ran))


@pytest.mark.parametrize("small", ["left", "right"])
@pytest.mark.parametrize("F", _WIDTHS)
def test_the_chunked_split_step_is_its_one_chunk_self(F, small):
    """Both children's ``hists`` rows, both search rows, the compacted
    tiles and the counts: the chunk steps change none of them."""
    n = 3 * _T + 17
    bins, g, h = _table(F, n, seed=F)
    thr = 2 if small == "left" else _B - 4
    whole = _split(F, bins, g, h, thr)
    with _chunks_of(_CHUNK):
        chunked = _split(F, bins, g, h, thr)
    assert whole[3] == chunked[3] == int((bins[2] <= thr).sum())
    assert whole[4] == chunked[4] == -(-min(whole[3], n - whole[3]) // _T)
    for a, b in zip(whole[:3], chunked[:3]):
        assert a.tobytes() == b.tobytes()
    # the search found something, and the rows are the children's
    assert whole[1][0, 0] > 0 and whole[1][1, 0] > 0
    assert whole[0][0, :F, 2, :_B].sum() == whole[3] * F
    assert whole[0][2, :F, 2, :_B].sum() == (n - whole[3]) * F


def test_a_wide_record_sums_by_word_groups():
    """The histogram body walks the record's whole groups of
    ``LOOP_WORDS`` words in a loop and unrolls the words after the last,
    and the go flags come from one masked sum down the record's height.
    300 columns: 75 words, four whole groups of 16 and eleven words
    after, split on a column of the last word; counts are exact in
    float32, so every column's are numpy's."""
    F, n, thr, f_split = 300, _T + 200, 6, 297
    bins, g, h = _table(F, n, seed=5)
    assert PH.feature_chunk(R.round_up(F, 8), _BP)[1] == 1  # 16 bins
    assert R.num_words(F, _K) // R.LOOP_WORDS == 4
    hists, _, _, nleft, _ = _split(F, bins, g, h, thr, f_split=f_split)
    left = bins[f_split] <= thr
    assert nleft == int(left.sum())
    for row, rows in ((0, left), (2, ~left)):
        want = np.stack([np.bincount(b[rows], minlength=_B) for b in bins])
        np.testing.assert_array_equal(hists[row, :F, 2, :_B], want)
        for f in (0, 63, 64, 255, 256, 299):  # group edges, the tail
            np.testing.assert_allclose(
                hists[row, f, 0, :_B],
                np.bincount(bins[f, rows], g[rows], _B), atol=1e-4)


def _search(hl, hr, sums, F):
    return search2_pallas(
        jnp.asarray(hl), jnp.asarray(hr), *sums, jnp.bool_(True),
        jnp.ones(F, bool), jnp.full(F, _B, jnp.int32), jnp.zeros(F, bool),
        5.0, 0.0, 0.0, 0.0, 0.0, interpret=True)


def _hist3(bins, g, h, rows):
    """[F, B, 3] float32 histogram of ``rows``."""
    out = np.zeros((bins.shape[0], _B, 3), np.float32)
    for f in range(bins.shape[0]):
        for s, v in enumerate((g, h, np.ones_like(g))):
            out[f, :, s] = np.bincount(bins[f, rows], v[rows], _B)
    return out


@pytest.mark.parametrize("F", _WIDTHS)
def test_the_chunked_search_is_its_one_chunk_self(F):
    n = 4000
    bins, g, h = _table(F, n, seed=F + 1)
    left = bins[1] <= 7
    hl, hr = _hist3(bins, g, h, left), _hist3(bins, g, h, ~left)
    sums = [jnp.float32(x) for x in (
        g[left].sum(), h[left].sum(), left.sum(),
        g[~left].sum(), h[~left].sum(), (~left).sum())]
    whole = _search(hl, hr, sums, F)
    with _chunks_of(_CHUNK, _B):
        chunked = _search(hl, hr, sums, F)
    for a, b in zip(whole, chunked):
        assert int(a.feature) >= 0
        for x, y in zip(a, b):
            assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


# (the column copied, its copy): a copy in a LATER chunk ties the best
# split exactly; one search over every feature takes the smaller index
@pytest.mark.parametrize("a,b", [(3, 35), (3, 67), (40, 70), (31, 32)])
def test_a_tie_across_chunks_goes_to_the_smaller_feature(a, b):
    F, n = 75, 4000
    rng = np.random.RandomState(a)
    bins, g, h = _table(F, n, seed=a)
    # column ``a`` carries the signal; so does its copy, in another chunk
    bins[a] = np.clip((g > 0) * 8 + rng.randint(0, 8, n), 0, _B - 1)
    bins[b] = bins[a]
    assert a // _CHUNK != b // _CHUNK
    rows = np.ones(n, bool)
    hl = _hist3(bins, g, h, rows)
    sums = [jnp.float32(x) for x in (g.sum(), h.sum(), n)] * 2
    whole = _search(hl, hl, sums, F)
    with _chunks_of(_CHUNK, _B):
        chunked = _search(hl, hl, sums, F)
    ref = find_best_split(
        jnp.asarray(hl), *sums[:3], jnp.ones(F, bool),
        jnp.full(F, _B, jnp.int32), jnp.zeros(F, bool),
        5.0, 0.0, 0.0, 0.0, 0.0, jnp.bool_(True))
    for got in (*whole, *chunked):
        assert int(got.feature) == a == int(ref.feature)
        assert int(got.threshold) == int(ref.threshold)
        assert float(got.gain) == float(whole[0].gain) > 0
    # the split step's own search, over the same duplicated table
    one = _split(F, bins, g, h, 7, f_split=1)[1]
    with _chunks_of(_CHUNK):
        cut = _split(F, bins, g, h, 7, f_split=1)[1]
    assert one.tobytes() == cut.tobytes()
    assert one[0, 1] == one[1, 1] == a


def test_a_better_split_in_a_later_chunk_replaces_the_kept_one():
    F, n = 75, 4000
    bins, g, h = _table(F, n, seed=9)
    bins[5] = (g > 0.5) * 8  # a fair split in chunk 0
    bins[70] = (g > 0) * 8  # a better one in chunk 2
    hl = _hist3(bins, g, h, np.ones(n, bool))
    sums = [jnp.float32(x) for x in (g.sum(), h.sum(), n)] * 2
    whole = _search(hl, hl, sums, F)
    with _chunks_of(_CHUNK, _B):
        chunked = _search(hl, hl, sums, F)
    for got in (*whole, *chunked):
        assert int(got.feature) == 70
        assert float(got.gain) == float(whole[0].gain)
