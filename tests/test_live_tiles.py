"""The run-time tile count of the fused split step and the placement
(ISSUE 27): ``split_step_window`` and ``place_runs`` take how many of
their window's tiles to visit as an OPERAND, so one compiled body
serves every leaf size and the grower launches it outside any
``lax.cond``.  Pinned here, in interpret mode on the CPU:

* a launch over the live tiles gives bitwise the record, ``hists``,
  ``nleft`` and ``res`` of a launch over every tile of the window;
* what the launch never wrote (``comp`` tiles and count groups past the
  live count) is masked before anything reads it;
* a 3-tree model grown by the fused grower equals the canonical
  grower's.

The hardware-only halves (the dynamic Mosaic grids) are compiled by
tests/test_chip_compile.py and executed by analysis/kernel_parity.py;
the placement kernel runs interpreted in tests/test_place_kernel.py.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu.ops.record as R
from lightgbm_tpu.ops.pallas_search import _pack_meta, _pack_scal

_F, _B = 6, 16
_T = R.TILE
_NT = 4
_CAP = _NT * _T
_LEAF_ROW = R.num_words(_F, R.bins_per_word(jnp.uint8)) + 4

_PCNTS = {"0": 0, "1": 1, "T-1": _T - 1, "T": _T, "T+1": _T + 1,
          "cap/2+3": _CAP // 2 + 3, "cap": _CAP}
_BEGINS = {"0": 0, "7": _T + 7, "T-1": 2 * _T - 1}  # begin % T


def _inputs(seed=0):
    n = 3 * _T + _CAP  # rows on both sides of every window used here
    rng = np.random.RandomState(seed)
    rec = R.build_record(
        jnp.asarray(rng.randint(0, _B, (_F, n)).astype(np.uint8)),
        jnp.asarray(rng.randn(n).astype(np.float32)),
        jnp.asarray((np.abs(rng.randn(n)) + 0.1).astype(np.float32)),
        jnp.asarray((rng.rand(n) < 0.8).astype(np.float32)),
        n + _CAP)
    Fp, Bp = R.round_up(_F, 8), R.round_up(_B, 128)
    hists = jnp.asarray(rng.rand(3, Fp, 4, Bp).astype(np.float32))
    scal_f = _pack_scal(*[jnp.float32(x) for x in (
        1., 0., 1., 9., 0., 1., 9., 1., 1e-3, 0., 0., 0.)])
    meta = _pack_meta(jnp.ones(_F, bool), jnp.full(_F, _B, jnp.int32),
                      jnp.zeros(_F, bool), Fp)
    return rec, hists, scal_f, meta


def _split(rec, hists, scal_f, meta, begin, pcnt, do_split, live):
    """The grower's launch pair; ``live`` None = every tile."""
    k = R.bins_per_word(jnp.uint8)
    hs, comp, nleft, res, cl, cr, rec_pass = R.split_step_window(
        jnp.array(hists), rec, jnp.int32(begin), jnp.int32(pcnt),
        jnp.bool_(do_split), jnp.int32(2), jnp.int32(7), jnp.bool_(False),
        jnp.int32(0), jnp.int32(2), scal_f, meta, F=_F, cap=_CAP, k=k,
        interpret=True, live_tiles=live, tiles_per_step=1)
    return hs, comp, nleft, res, cl, cr, rec_pass


def _place(rec_pass, comp, cl, cr, begin, pcnt, nleft, do_split, live):
    return R.place_runs(
        jnp.array(rec_pass), comp, (cl, cr), jnp.int32(begin),
        jnp.int32(pcnt), nleft, jnp.bool_(do_split), jnp.int32(0),
        jnp.int32(2), cap=_CAP, leaf_row=_LEAF_ROW, interpret=True,
        live_tiles=live)


def _live_of(pcnt):
    return jnp.int32(-(-pcnt // _T))  # what the grower passes


@pytest.fixture(scope="module")
def inputs():
    return _inputs()


@pytest.mark.parametrize("do_split", [0, 1])
@pytest.mark.parametrize("begin", list(_BEGINS), ids=lambda b: f"r{b}")
@pytest.mark.parametrize("pcnt", list(_PCNTS), ids=lambda p: f"pcnt{p}")
def test_live_tile_count_matches_full_count(inputs, pcnt, begin, do_split):
    """Tiles past ``pcnt`` add masked zeros to the left child's
    histogram and nothing to the counts: not running them changes no
    bit of the record, ``hists``, ``nleft`` or ``res``."""
    rec, hists, scal_f, meta = inputs
    b, p = _BEGINS[begin], _PCNTS[pcnt]
    out = {}
    for name, live in (("full", None), ("live", _live_of(p))):
        hs, comp, nleft, res, cl, cr, rec_pass = _split(
            rec, hists, scal_f, meta, b, p, do_split, live)
        rec2 = _place(rec_pass, comp, cl, cr, b, p, nleft, do_split, live)
        out[name] = [np.asarray(x) for x in (rec2, hs, nleft, res, cl, cr)]
    for what, a, c in zip(("record", "hists", "nleft", "res", "cl", "cr"),
                          out["full"], out["live"]):
        assert a.tobytes() == c.tobytes(), what
    if do_split and p:
        assert 0 <= int(out["live"][2]) <= p
        # the partition did something: the leaf-id row carries both ids
        row = out["live"][0][_LEAF_ROW, b:b + p]
        assert set(np.unique(row)) <= {0, 2}
        assert int((row == 0).sum()) == int(out["live"][2])


@pytest.mark.parametrize("do_split", [0, 1])
@pytest.mark.parametrize("begin", list(_BEGINS), ids=lambda b: f"r{b}")
@pytest.mark.parametrize("pcnt", list(_PCNTS), ids=lambda p: f"pcnt{p}")
def test_unwritten_tiles_are_masked(inputs, pcnt, begin, do_split):
    """A launch over the live tiles leaves the ``comp`` tiles and the
    count groups past them unwritten.  Filled with garbage, they change
    neither the counts nor the placed record."""
    rec, hists, scal_f, meta = inputs
    b, p = _BEGINS[begin], _PCNTS[pcnt]
    live = _live_of(p)
    hs, comp, nleft, res, cl, cr, rec_pass = _split(
        rec, hists, scal_f, meta, b, p, do_split, live)
    want = np.asarray(_place(rec_pass, comp, cl, cr, b, p, nleft,
                             do_split, live))
    rng = np.random.RandomState(1)
    lt = max(int(live), 1)  # _live_tiles clamps to [1, nt]

    # counts: lane 0 of each 128-lane group holds a tile's left count
    cnt = rng.randint(-2**30, 2**30, (1, _NT * 128)).astype(np.int32)
    cnt[0, :lt * 128:128] = np.asarray(cl)[:lt]
    cl2, cr2, nleft2 = R._tile_counts(
        jnp.asarray(cnt), jnp.int32(p), jnp.int32(lt), _NT)
    for a, c in ((cl, cl2), (cr, cr2), (nleft, nleft2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

    comp_g = np.asarray(comp).copy()
    comp_g[lt:] = rng.randint(-2**30, 2**30, comp_g[lt:].shape)
    got = np.asarray(_place(rec_pass, jnp.asarray(comp_g), cl2, cr2, b, p,
                            nleft2, do_split, live))
    assert got.tobytes() == want.tobytes()


def _grow3(raw):
    """Three boosting rounds of a grower (``raw``: the fused one, else
    the canonical one) on integer-valued gradients
    (exact in float32 under any accumulation order, as in
    tests/test_opt_layout.py): each round's gradients come from the
    leaves of the round before."""
    from test_opt_layout import _grow, _mk

    bins, grad, hess = _mk(n=3000, F=7, num_bins=23, seed=5)
    trees = []
    for _ in range(3):
        tree, leaf_id = _grow(bins, grad, hess, 23, raw=raw, max_leaves=12)
        trees.append((tree, np.asarray(leaf_id)))
        step = np.rint(4 * np.asarray(tree.leaf_value))[trees[-1][1]]
        grad = (grad - step).astype(np.float32)
    return trees


def test_three_tree_model_fused_equals_canonical():
    """The fused grower (kernels in interpret mode: one split-step
    launch and one placement a split, at the run-time tile count) grows
    the canonical grower's three trees."""
    for (t0, l0), (t1, l1) in zip(_grow3(raw=False), _grow3(raw=True)):
        assert int(t0.num_leaves) == int(t1.num_leaves) > 4
        np.testing.assert_array_equal(
            np.asarray(t0.split_feature), np.asarray(t1.split_feature))
        np.testing.assert_array_equal(
            np.asarray(t0.threshold_bin), np.asarray(t1.threshold_bin))
        np.testing.assert_array_equal(l0, l1)
        np.testing.assert_allclose(
            np.asarray(t0.leaf_value), np.asarray(t1.leaf_value),
            rtol=2e-5, atol=2e-5)
