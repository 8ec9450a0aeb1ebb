"""Missing values: a numerical column whose bin sample holds a NaN gets a
missing bin (its last), every split on it learns a default direction for
those rows, and training, the model text and prediction route a NaN by it
(io/binner.py, ops/split.py, ops/pallas_search.py, ops/record.py,
models/tree.py).  Tables here are small and seeded, with NaN in blocks of
columns as a production line's stations leave them."""

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import native
from lightgbm_tpu.io.binner import MISSING_NAN, MISSING_NONE, BinMapper
from lightgbm_tpu.learners import fused
from lightgbm_tpu.learners.serial import TreeLearnerParams, grow_tree
from lightgbm_tpu.ops.pallas_search import search2_pallas
from lightgbm_tpu.ops.split import find_best_split


def station_table(n=3000, F=8, seed=7):
    """Rows of a line whose parts skip stations: every column pair is a
    station, present or absent together; the label depends on a value
    and on whether a station was skipped."""
    rng = np.random.default_rng(seed)
    X = np.round(rng.standard_normal((n, F)), 3)
    absent = rng.random((n, F // 2)) < np.linspace(0.2, 0.8, F // 2)
    X[np.repeat(absent, 2, axis=1)] = np.nan
    y = ((np.nan_to_num(X[:, 0]) + 1.2 * absent[:, 1]
          + 0.5 * rng.standard_normal(n)) > 0.6).astype(np.float64)
    return X, y


PARAMS = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 20,
          "verbose": -1}


# ------------------------------------------------------------------ binning
@pytest.mark.parametrize("case", ["nan_column", "no_nan", "use_missing_off",
                                  "max_bin_counts_it", "all_nan_sample"])
def test_the_binner_gives_a_missing_bin_to_columns_with_nan(case):
    rng = np.random.default_rng(1)
    x = np.round(rng.standard_normal(2000), 2)
    x[rng.random(2000) < 0.3] = np.nan
    if case == "nan_column":
        m = BinMapper.find(x, max_bin=16)
        assert m.missing_type == MISSING_NAN and m.missing_bin == 15
        assert np.isnan(m.bin_upper_bound[-1])
        assert np.isposinf(m.bin_upper_bound[-2])
        b = m.value_to_bin(x)
        assert (b[np.isnan(x)] == 15).all() and (b[~np.isnan(x)] < 15).all()
        again = BinMapper.from_dict(m.to_dict())
        np.testing.assert_array_equal(again.value_to_bin(x), b)
        assert again.missing_bin == 15
    elif case == "no_nan":
        m = BinMapper.find(np.nan_to_num(x), max_bin=16)
        assert m.missing_type == MISSING_NONE and m.missing_bin == -1
        assert m.value_to_bin(np.array([np.nan]))[0] == \
            m.value_to_bin(np.array([0.0]))[0]
    elif case == "use_missing_off":
        m = BinMapper.find(x, max_bin=16, use_missing=False)
        assert m.missing_bin == -1
        np.testing.assert_array_equal(m.value_to_bin(x),
                                      m.value_to_bin(np.nan_to_num(x)))
    elif case == "max_bin_counts_it":
        # value bins plus the missing bin within max_bin: uint8 stays
        m = BinMapper.find(rng.standard_normal(5000).tolist() + [np.nan],
                           max_bin=255)
        assert m.num_bin == 255 and m.missing_bin == 254
    else:
        # a sample of NaN and elided zeros: one value bin and the
        # missing bin, a split of present against missing
        m = BinMapper.find(np.full(10, np.nan), total_sample_cnt=50,
                           max_bin=16)
        assert m.num_bin == 2 and not m.is_trivial
        np.testing.assert_array_equal(
            m.value_to_bin(np.array([0.0, np.nan])), [0, 1])


def test_the_native_encoder_sends_nan_to_the_missing_bin():
    X, _ = station_table()
    X[:, 7] = np.nan_to_num(X[:, 7])  # one column without a gap
    mappers = [BinMapper.find(X[:, j], max_bin=63) for j in range(8)]
    assert [m.missing_bin >= 0 for m in mappers] == [True] * 7 + [False]
    out = np.empty(X.shape, np.uint8)
    if not native.value_to_bin_numerical(
            np.ascontiguousarray(X), np.arange(8),
            [m.bin_upper_bound for m in mappers], out):
        pytest.skip("native encoder unavailable")
    for j, m in enumerate(mappers):
        np.testing.assert_array_equal(out[:, j], m.value_to_bin(X[:, j]))


def test_a_dataset_counts_its_missing_columns():
    from lightgbm_tpu.obs import telemetry

    tel = telemetry.get_telemetry()
    X, y = station_table()
    cols, values = (tel.counter("ingest.missing_columns"),
                    tel.counter("ingest.missing_sample_values"))
    ds = lgb.Dataset(X, label=y).construct()
    assert (ds.missing_bins >= 0).all()
    assert tel.counter("ingest.missing_columns") - cols == 8
    assert tel.counter("ingest.missing_sample_values") - values == int(
        np.isnan(X).sum())


# ------------------------------------------------------------------- search
def brute_force(hist, tot, nbpf, mbin, min_data=1.0, l2=1.0):
    """Every (feature, side, threshold) candidate in float64, ranked by
    gain desc, feature asc, then LightGBM 2.x's scans: missing-left,
    threshold desc; missing-right, threshold asc on a column with a
    missing bin, desc on one without."""
    def gain(g, h):
        return g * g / (h + l2)
    G, H, C = tot
    best = None
    for f in range(hist.shape[0]):
        nb, mb = int(nbpf[f]), int(mbin[f])
        sides = [(True, nb - 3), (False, nb - 2)] if mb >= 0 else [
            (False, nb - 2)]
        for dl, last in sides:
            for t in (range(last + 1) if mb >= 0 and not dl
                      else range(last, -1, -1)):
                left = hist[f, :t + 1].sum(axis=0).astype(np.float64)
                if dl:
                    left = left + hist[f, mb]
                right = np.array(tot, np.float64) - left
                if min(left[2], right[2]) < min_data:
                    continue
                g = gain(left[0], left[1]) + gain(right[0], right[1])
                if best is None or g > best[0]:
                    best = (g, f, t, dl)
    return best


def _search(hist, tot, nbpf, mbin):
    F = hist.shape[0]
    one = jnp.float32(1.0)
    return find_best_split(
        jnp.asarray(hist), jnp.float32(tot[0]), jnp.float32(tot[1]),
        jnp.float32(tot[2]), jnp.ones(F, bool), jnp.asarray(nbpf),
        jnp.zeros(F, bool), one, jnp.float32(0.0), jnp.float32(0.0), one,
        jnp.float32(0.0), jnp.asarray(True),
        jnp.asarray(mbin, jnp.int32))


def _kernel(hist, tot, nbpf, mbin):
    F = hist.shape[0]
    return search2_pallas(
        jnp.asarray(hist), jnp.asarray(hist), *[jnp.float32(v) for v in tot],
        *[jnp.float32(v) for v in tot], jnp.asarray(True),
        jnp.ones(F, bool), jnp.asarray(nbpf), jnp.zeros(F, bool),
        jnp.float32(1.0), jnp.float32(0.0), jnp.float32(0.0),
        jnp.float32(1.0), jnp.float32(0.0),
        missing_bin=jnp.asarray(mbin, jnp.int32), interpret=True)[0]


def _hist(seed, F=6, B=16, n=400):
    """Histograms of ``n`` rows with integer gradients (exact sums in
    float32 in any order); the even features have a missing bin, their
    last, which holds about a fifth of the rows."""
    rng = np.random.RandomState(seed)
    g = rng.randint(-9, 10, n).astype(np.float32)
    h = rng.randint(1, 4, n).astype(np.float32)
    bins = rng.randint(0, B - 1, (F, n))
    mbin = np.where(np.arange(F) % 2 == 0, B - 1, -1).astype(np.int32)
    bins[(mbin[:, None] >= 0) & (rng.rand(F, n) < 0.2)] = B - 1
    return _of_rows(bins, g, h, B), mbin


def _of_rows(bins, g, h, B):
    hist = np.stack([np.stack([np.bincount(b, w, B) for w in (
        g, h, np.ones_like(g))], axis=-1) for b in bins]).astype(np.float32)
    tot = tuple(float(v) for v in hist[0].sum(axis=0))
    return hist, tot, np.full(len(bins), B, np.int32)


@pytest.mark.parametrize("search", ["jnp", "pallas"])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_both_searches_try_both_sides_for_missing_rows(search, seed):
    (hist, tot, nbpf), mbin = _hist(seed)
    want = brute_force(hist, tot, nbpf, mbin)
    sr, dl = (_search if search == "jnp" else _kernel)(hist, tot, nbpf, mbin)
    assert (int(sr.feature), int(sr.threshold), bool(dl)) == want[1:]
    parent = tot[0] ** 2 / (tot[1] + 1.0)
    np.testing.assert_allclose(float(sr.gain), want[0] - parent, rtol=1e-5)


@pytest.mark.parametrize("search", ["jnp", "pallas"])
def test_the_tie_rule_is_feature_then_missing_left_then_threshold(search):
    """Features 2 and 3 bin the rows alike and their missing bins hold
    none of them, so each threshold of either scores the same on both
    sides: the rule takes feature 2, its missing rows left.  Features 0
    and 1 hold every row in one bin and offer nothing."""
    B, n = 16, 300
    rng = np.random.RandomState(4)
    g = rng.randint(-9, 10, n).astype(np.float32)
    h = rng.randint(1, 4, n).astype(np.float32)
    col = rng.randint(0, B - 1, n)
    bins = np.stack([np.zeros(n, int), np.zeros(n, int), col, col])
    mbin = np.array([-1, -1, B - 1, B - 1], np.int32)
    hist, tot, nbpf = _of_rows(bins, g, h, B)
    run = _search if search == "jnp" else _kernel
    for want_f in (2, 3):
        _, f, t, dl = brute_force(hist, tot, nbpf, mbin)
        assert (f, dl) == (want_f, True)
        sr, left = run(hist, tot, nbpf, mbin)
        assert (int(sr.feature), int(sr.threshold), bool(left)) == (f, t, dl)
        hist[2] = hist[0]  # feature 2 offers nothing now: feature 3 wins


@pytest.mark.parametrize("search", ["jnp", "pallas"])
def test_missing_right_ties_take_the_lowest_threshold(search):
    """Values in bins 0-3 and 10-14 only, so thresholds 3 to 9 split the
    rows alike; the missing rows (feature 0's last bin) belong with the
    high ones, so missing-right wins.  Feature 0, with a missing bin,
    takes the LOWEST tied threshold (LightGBM's forward scan); feature
    1, without one, the highest (its reverse scan)."""
    B, n = 16, 300
    rng = np.random.RandomState(5)
    high = rng.rand(n) < 0.5
    absent = high & (rng.rand(n) < 0.4)
    value = np.where(high, rng.randint(10, 15, n), rng.randint(0, 4, n))
    g = np.where(high, 5.0, -5.0).astype(np.float32)
    h = np.ones(n, np.float32)
    bins = np.stack([np.where(absent, B - 1, value), value])
    mbin = np.array([B - 1, -1], np.int32)
    hist, tot, nbpf = _of_rows(bins, g, h, B)
    run = _search if search == "jnp" else _kernel
    for want in ((0, 3, False), (1, 9, False)):
        assert brute_force(hist, tot, nbpf, mbin)[1:] == want
        sr, left = run(hist, tot, nbpf, mbin)
        assert (int(sr.feature), int(sr.threshold), bool(left)) == want
        hist[0] = _of_rows(np.zeros((1, n), int), g, h, B)[0][0]


# ------------------------------------------------------------------ growers
def _params():
    return TreeLearnerParams(
        jnp.float32(20), jnp.float32(1e-3), jnp.float32(0.0),
        jnp.float32(0.0), jnp.float32(0.0), jnp.int32(-1))


@pytest.mark.parametrize("grower", ["canonical", "fused"])
def test_the_growers_agree_node_for_node(grower):
    """Both growers on integer gradients (exact sums in any order): the
    same splits, sides, leaves and rows, and a row's leaf is where the
    tree's own binned walk sends it."""
    from lightgbm_tpu.models.tree import predict_leaf_binned

    X, _ = station_table(n=4000)
    ds = lgb.Dataset(X, label=np.zeros(len(X))).construct()
    bins = np.asarray(ds.X_bin)
    rng = np.random.RandomState(0)
    grad = jnp.asarray(rng.randint(-8, 9, len(X)).astype(np.float32))
    hess = jnp.asarray(rng.randint(1, 5, len(X)).astype(np.float32))
    mb = jnp.asarray(ds.missing_bins)
    args = (jnp.asarray(bins.T), grad, hess, jnp.ones(len(X), jnp.float32),
            jnp.ones(bins.shape[1], bool),
            jnp.asarray(ds.num_bins_per_feature),
            jnp.zeros(bins.shape[1], bool), _params())
    kw = dict(num_bins=int(ds.max_num_bin), max_leaves=16, missing_bin=mb)
    t0, l0 = grow_tree(*args, **kw)
    t1, l1 = (grow_tree if grower == "canonical" else fused.grow_tree)(
        *args, **kw)
    nl = int(t0.num_leaves)
    assert nl == int(t1.num_leaves) > 8
    dt = np.asarray(t1.decision_type)[:nl - 1]
    assert set(dt) <= {8, 10} and 10 in dt and 8 in dt
    for f in ("split_feature", "threshold_bin", "decision_type"):
        np.testing.assert_array_equal(np.asarray(getattr(t0, f))[:nl - 1],
                                      np.asarray(getattr(t1, f))[:nl - 1])
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))
    np.testing.assert_array_equal(
        np.asarray(predict_leaf_binned(t1, jnp.asarray(bins), mb)),
        np.asarray(l1))


def test_prediction_on_the_raw_matrix_is_the_training_score():
    X, y = station_table()
    b = lgb.train(PARAMS, lgb.Dataset(X, label=y), num_boost_round=6)
    score = np.asarray(b._gbdt._scores)[0]
    dt = np.concatenate([np.asarray(t.decision_type)[:int(t.num_leaves) - 1]
                         for t in b._gbdt.models])
    assert (dt & 8).all() and (dt & 2).any() and not (dt & 2).all()
    np.testing.assert_allclose(b.predict(X, raw_score=True), score,
                               atol=1e-6)
    # the matmul path and the walk route a NaN alike
    from lightgbm_tpu.models.tree import ensemble_leaves_raw, stack_trees
    from lightgbm_tpu.ops.predict_matmul import (
        build_path_tables, ensemble_leaves_matmul)

    stacked = stack_trees(b._gbdt.models)
    Xj = jnp.asarray(X, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(ensemble_leaves_matmul(build_path_tables(stacked),
                                          stacked, Xj)),
        np.asarray(ensemble_leaves_raw(stacked, Xj)))


@pytest.mark.parametrize("how", ["model_text", "checkpoint"])
def test_decision_type_round_trips(how, tmp_path):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.io.metadata import Metadata
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.resilience import checkpoint as ck

    X, y = station_table()
    cfg = Config.from_dict(PARAMS)

    def booster():
        ds = BinnedDataset.from_matrix(X, Metadata(label=y), config=cfg)
        return GBDT(cfg, ds, create_objective(cfg, ds.metadata, ds.num_data))

    b = booster()
    for _ in range(3):
        b.train_one_iter()
    if how == "model_text":
        again = lgb.Booster(model_str=b.save_model_to_string())._gbdt
    else:
        path = str(tmp_path / "ckpt_00000003.json")
        ck.save_checkpoint(path, b, cfg, iteration=3)
        again = booster()
        assert ck.restore_training_state(again, ck.load_checkpoint(path)) == 3
    for t0, t1 in zip(b.models, again.models):
        nl = int(t0.num_leaves)
        np.testing.assert_array_equal(
            np.asarray(t0.decision_type)[:nl - 1],
            np.asarray(t1.decision_type)[:nl - 1])
    np.testing.assert_array_equal(again.predict_raw_score(X),
                                  b.predict_raw_score(X))
    dumped = b.dump_model()["tree_info"][0]["tree_structure"]
    assert dumped["missing_type"] == "NaN"


def test_without_a_missing_bin_nan_reads_zero():
    """``use_missing=false``: the trees of the table with every NaN
    written as 0.0, and prediction reads a NaN as 0.0 too."""
    X, y = station_table()
    off = lgb.train({**PARAMS, "use_missing": False},
                    lgb.Dataset(X, label=y), num_boost_round=3)
    zero = lgb.train(PARAMS, lgb.Dataset(np.nan_to_num(X), label=y),
                     num_boost_round=3)
    assert off._gbdt._missing_bin is None
    for t0, t1 in zip(off._gbdt.models, zero._gbdt.models):
        for f in ("split_feature", "threshold_bin", "decision_type",
                  "leaf_value"):
            np.testing.assert_array_equal(np.asarray(getattr(t0, f)),
                                          np.asarray(getattr(t1, f)))
    np.testing.assert_array_equal(off.predict(X, raw_score=True),
                                  zero.predict(np.nan_to_num(X),
                                               raw_score=True))


def test_a_learner_that_cannot_route_missing_values_refuses():
    X, y = station_table()
    with pytest.raises(ValueError, match="missing bin"):
        lgb.train({**PARAMS, "tree_learner": "voting"},
                  lgb.Dataset(X, label=y), num_boost_round=1)
