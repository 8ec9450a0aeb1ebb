"""The record's stable compaction against a numpy stable partition.

``partition_window`` (prefix-sum routing: lane-cumsum destination
offsets + the staged-shift compress network, then the placement) must
give BYTE for byte the record a numpy stable partition of the same
window gives: random go patterns across TILE in {128, 256, 512}, ragged
window caps, all-left / all-right / empty-leaf edges, an interior
window, and with the bagging-mask word populated; and so must the fused
split step's launch pair, whose histograms are held to float64 numpy.
(Until PR 30 these cases pinned the prefix routing to a one-hot MXU
routing: a path against a path.)

The tests call ``partition_window.__wrapped__`` (the un-jitted body):
the jit cache keys on shapes/static args but NOT on the module TILE
global, so a monkeypatched TILE would silently hit a stale trace.
"""

import os
import re

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu
import lightgbm_tpu.ops.record as R
from lightgbm_tpu.analysis.kernel_parity import (
    _fused_split, _np_hist, _np_partition)

_F, _B = 6, 16
_LEAF_ROW = R.num_words(_F, R.bins_per_word(jnp.uint8)) + 4


def _mkrec(n, n_pad, seed=0, bag_frac=None):
    """A populated record: packed bins + grad/hess + bagging-mask word
    (routed as data like every other word-row) + row/leaf-id rows."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, _B, (_F, n)).astype(np.uint8)
    bag = (np.ones(n, np.float32) if bag_frac is None
           else (rng.rand(n) < bag_frac).astype(np.float32))
    rec = R.build_record(
        jnp.asarray(bins),
        jnp.asarray(rng.randn(n).astype(np.float32)),
        jnp.asarray((np.abs(rng.randn(n)) + 0.1).astype(np.float32)),
        jnp.asarray(bag),
        n_pad,
    )
    return rec


def _partition(rec, go, begin, pcnt, cap, do_split=True):
    """(record bytes, nleft) of partition_window on one window."""
    out, nleft = R.partition_window.__wrapped__(
        jnp.array(rec),  # called eagerly, place_runs donates its record
        jnp.asarray(go, jnp.int32), jnp.int32(begin),
        jnp.int32(pcnt), jnp.bool_(do_split), cap,
        left_leaf=jnp.int32(0), right_leaf=jnp.int32(1),
        leaf_row=_LEAF_ROW, interpret=True)
    return np.asarray(out).tobytes(), int(nleft)


def _numpy(rec, go, begin, pcnt, do_split=True):
    """The same, from a numpy stable partition."""
    if not do_split:
        return np.asarray(rec).tobytes(), int(np.sum(go[:pcnt]))
    out, nleft = _np_partition(rec, go, begin, pcnt, _LEAF_ROW, 0, 1)
    return out.tobytes(), nleft


@pytest.fixture(autouse=True)
def _restore_tile(monkeypatch):
    # every test in this module may monkeypatch R.TILE; ensure the
    # import-time value is back afterwards no matter what
    tile = R.TILE
    yield
    R.TILE = tile


@pytest.mark.parametrize("tile", [128, 256, 512])
def test_compaction_matches_numpy_random_windows(tile, monkeypatch):
    """Random go patterns over multi-tile windows, ragged pcnt."""
    monkeypatch.setattr(R, "TILE", tile)
    rng = np.random.RandomState(tile)
    n = 3 * tile - 57  # ragged: the window's invalid tail is nonempty
    cap = 3 * tile
    rec = _mkrec(n, cap + tile, seed=tile, bag_frac=0.7)
    for trial in range(3):
        go = (rng.rand(cap) < rng.choice([0.1, 0.5, 0.9])).astype(np.int32)
        assert _partition(rec, go, 0, n, cap) == _numpy(rec, go, 0, n), (
            tile, trial)


@pytest.mark.parametrize("tile", [128, 512])
def test_compaction_matches_numpy_edges(tile, monkeypatch):
    """All-left, all-right, empty leaf, and a no-op split."""
    monkeypatch.setattr(R, "TILE", tile)
    cap = 2 * tile
    n = cap - 13
    rec = _mkrec(n, cap + tile, seed=1, bag_frac=0.5)
    cases = [
        (np.ones(cap, np.int32), n, True),    # all-left
        (np.zeros(cap, np.int32), n, True),   # all-right
        (np.ones(cap, np.int32), 0, True),    # empty leaf (pcnt = 0)
        (np.random.RandomState(2).randint(0, 2, cap).astype(np.int32),
         n, False),                            # do_split = False no-op
    ]
    for go, pcnt, do_split in cases:
        assert (_partition(rec, go, 0, pcnt, cap, do_split=do_split)
                == _numpy(rec, go, 0, pcnt, do_split=do_split)), (
            tile, pcnt, do_split)
    # the all-left case really moved every valid row left
    _, nleft = _partition(rec, np.ones(cap, np.int32), 0, n, cap)
    assert nleft == n


def test_compaction_matches_numpy_interior_window():
    """A window that does not start at the record origin (begin > 0,
    tile-aligned as in the tier chain): the placement's offsets."""
    tile = R.TILE
    cap = 2 * tile
    n = 3 * tile
    rec = _mkrec(n, n + cap, seed=3, bag_frac=0.6)
    rng = np.random.RandomState(4)
    go = rng.randint(0, 2, cap).astype(np.int32)
    assert (_partition(rec, go, tile, cap - 100, cap)
            == _numpy(rec, go, tile, cap - 100))


# (parent tiles a grid step, the leaf's tiles): 1, K-1, K, K+1 and 2K+1
_K_LIVE = [(K, live) for K in (1, 2, 4)
           for live in sorted({1, K - 1, K, K + 1, 2 * K + 1} - {0})]


@pytest.mark.parametrize("K,live", _K_LIVE,
                         ids=[f"K{K}-live{live}" for K, live in _K_LIVE])
def test_fused_split_step_matches_numpy(K, live):
    """The fused grower's launch pair at the hlo_audit table, on a leaf
    of ``live`` tiles (the last short by 57 rows) at K parent tiles a
    grid step: record and ``nleft`` equal a numpy stable partition's,
    both children's histogram rows a float64 numpy histogram's, the
    histogram tiles run ``ceil(rows / TILE)``, and everything the step
    returns is K = 1's bit for bit (the compacted tiles the placement
    reads, the counts, the histogram rows)."""
    from lightgbm_tpu.analysis.hlo_audit import _B as B, _F as F
    from lightgbm_tpu.analysis.hlo_audit import _split_step_inputs

    rec, hists, scal_f, meta, s, cap, k = _split_step_inputs(
        tiles=live, tail=57, blocks_of=4)
    n, f, thr = int(s["pcnt"]), int(s["f"]), int(s["thr"])
    bins, g, h, m = (np.asarray(x) for x in R.unpack_window(
        rec[:, :n], F, k, jnp.uint8))
    left = bins[f] <= thr
    parent = _np_hist(bins, g, h, m, B)
    hists = hists.at[0, :F, :3, :B].set(jnp.asarray(parent, jnp.float32))
    got, one = (_fused_split(
        rec, hists, 0, n, f, thr, 0, 1, scal_f, meta, F, cap,
        s["live_tiles"], True, tiles_per_step=tiles) for tiles in (K, 1))
    assert np.asarray(got.comp)[:live].tobytes() == \
        np.asarray(one.comp)[:live].tobytes()
    for name in ("cl", "cr", "nleft", "hists", "res"):
        assert np.asarray(getattr(got, name)).tobytes() == \
            np.asarray(getattr(one, name)).tobytes(), name
    want_rec, want_nl = _np_partition(
        rec, left, 0, n, R.num_words(F, k) + 4, 0, 1)
    assert int(got.nleft) == want_nl == int(np.asarray(got.cl).sum())
    assert np.asarray(got.rec).tobytes() == want_rec.tobytes()
    # the search's counts tie, so the kernel sums the left child
    assert got.ran == one.ran == -(-want_nl // R.TILE)
    for row, side in ((0, left), (1, ~left)):
        np.testing.assert_allclose(
            np.asarray(got.hists[row, :F, :3, :B]),
            _np_hist(bins, g, h, m * side, B), rtol=0, atol=1e-4)


_ENV_NAMES_LEFT = {
    # read in models/gbdt.py; the cell that can judge each is named in
    # ROADMAP.md queue 3, item 5
    "LGBM_TPU_STOP_LAG", "LGBM_TPU_PREDICT_MATMUL",
    "LGBM_TPU_PREDICT_ROW_CHUNK", "LGBM_TPU_FOREST_MAX_ROWS",
    # the chaos hook (resilience/faults.py), named in gbdt.py's comments
    "LGBM_TPU_FAULT",
}


def test_no_environment_selector_on_the_growers_path():
    """No module under ops/ or learners/ reads (or names) an
    ``LGBM_TPU_*`` environment variable, and models/ only the four
    that wait for a cell to judge them and the chaos hook."""
    root = os.path.dirname(lightgbm_tpu.__file__)
    found = {}
    for sub in ("ops", "learners", "models"):
        for dirpath, _, files in os.walk(os.path.join(root, sub)):
            for name in files:
                if name.endswith(".py"):
                    with open(os.path.join(dirpath, name)) as fh:
                        for env in re.findall(r"LGBM_TPU_[A-Z0-9_]+",
                                              fh.read()):
                            found.setdefault(sub, set()).add(env)
    assert not found.get("ops") and not found.get("learners"), found
    assert found.get("models", set()) <= _ENV_NAMES_LEFT, found


def test_prefix_lane_cumsum_matches_numpy():
    """The in-kernel Hillis-Steele scan is exactly an inclusive cumsum
    (pltpu.roll only evaluates inside a kernel, so run it through a
    one-block interpret pallas_call)."""
    import jax
    from jax.experimental import pallas as pl

    def kern(g_ref, o_ref):
        o_ref[...] = R._lane_cumsum(g_ref[...])

    rng = np.random.RandomState(0)
    for T in (128, 256, 512):
        g = rng.randint(0, 2, (1, T)).astype(np.int32)
        got = np.asarray(pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((1, T), jnp.int32),
            interpret=True)(jnp.asarray(g)))
        np.testing.assert_array_equal(got, np.cumsum(g[0])[None])


# go share of the tile: none, one lane, 3%, half, 97%, all
_GO_SHARES = (0.0, "one", 0.03, 0.5, 0.97, 1.0)


@pytest.mark.parametrize("tail", [0, 57], ids=["whole", "invalid_tail"])
@pytest.mark.parametrize("share", _GO_SHARES, ids=[str(s) for s in _GO_SHARES])
@pytest.mark.parametrize("W", [8, 32, 64, 136])
def test_compact_body_matches_a_numpy_stable_partition(W, share, tail):
    """``_compact_tiles`` on one tile (the permutation computed on one two-row
    operand, applied by lane gathers) through an interpreted one-tile
    call: the lefts, in order, fill the left half from lane 0 and
    everything else, the invalid tail last, the right half, word for
    word; the lanes past each run are garbage and not compared.  Record
    heights of the narrow fixtures, of the cells (32, 64 words) and one
    that is no power of two (512 columns of 128 bins)."""
    T = R.TILE
    rng = np.random.RandomState(W * 7 + tail)
    tile = rng.randint(-2**31, 2**31, (W, T), dtype=np.int64).astype(np.int32)
    valid = np.arange(T) < T - tail
    if share == "one":
        go = np.arange(T) == rng.randint(T - tail)
    else:
        go = rng.rand(T) < share
    go = (go & valid).astype(np.int32)  # 1 = left AND valid, as _tile_go's
    comp = np.asarray(R.compact_tiles(
        jnp.asarray(tile), jnp.asarray(go), interpret=True))[0]
    nleft = int(go.sum())
    assert comp.shape == (W, 2 * T)
    np.testing.assert_array_equal(comp[:, :nleft], tile[:, go == 1])
    np.testing.assert_array_equal(comp[:, T: 2 * T - nleft], tile[:, go == 0])
