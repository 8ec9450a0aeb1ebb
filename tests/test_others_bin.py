"""Categorical columns through the normal entry points.

Two things the library got wrong until PR 38 (ISSUE 38's findings 1 and
2): ``categorical_column`` in ``params`` was ignored for an in-memory
matrix, and a categorical column with more categories than ``max_bin``
trained on rows that prediction routed differently (every category past
the kept ones was binned with the most frequent one).  Now a value that
is no kept category has the column's last bin, the others' bin, which
the search never offers as a left side (io/binner.py): training and
prediction route every raw value alike.
"""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models.gbdt import GBDT

PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 255,
          "min_data_in_leaf": 20, "verbose": -1}


def table(n, categories=400, seed=5):
    """A categorical column of ``categories`` values, heavy-tailed so the
    rare ones are past the kept bins, one with few values, one number."""
    rng = np.random.default_rng(seed)
    big = np.minimum(rng.geometric(0.012, n) - 1, categories - 1)
    code = np.random.default_rng(1).permutation(categories)
    X = np.c_[code[big], rng.integers(0, 12, n),
              rng.standard_normal(n)].astype(np.float32)
    effect = np.random.default_rng(2).standard_normal(categories)
    y = (effect[big] + 0.5 * (X[:, 1] == 3) + 0.8 * X[:, 2]
         + 0.5 * rng.standard_normal(n) > 0).astype(np.float32)
    return X, y


def as_text(booster):
    return booster.model_to_string().split("feature importances")[0]


def split_kinds(text):
    """Every split's ``decision_type`` (1: one category goes left)."""
    return [int(v) for line in text.splitlines()
            if line.startswith("decision_type=")
            for v in line.split("=")[1].split()]


@pytest.mark.parametrize("spec", ["0,1", [0, 1], "name:big,small"])
def test_categorical_column_in_params_is_honoured_for_a_matrix(spec):
    """``params={"categorical_column": ...}`` (and its aliases) on an
    in-memory matrix declares what ``categorical_feature=[...]`` does:
    the same ``is_categorical``, the same trees."""
    X, y = table(4000, categories=40)
    names = ["big", "small", "x"]
    by_arg = lgb.Dataset(X, label=y, categorical_feature=[0, 1],
                         feature_name=names, params=dict(PARAMS))
    by_params = lgb.Dataset(
        X, label=y, feature_name=names,
        params={**PARAMS, "categorical_feature": spec})
    assert (by_params.construct().is_categorical.tolist()
            == by_arg.construct().is_categorical.tolist()
            == [True, True, False])
    grown = [lgb.train(dict(PARAMS), ds, num_boost_round=3)
             for ds in (by_arg, by_params)]
    assert as_text(grown[0]) == as_text(grown[1])
    assert sum(split_kinds(as_text(grown[0]))) > 0


def test_params_and_the_argument_are_merged_and_checked():
    X, y = table(2000, categories=40)
    ds = lgb.Dataset(X, label=y, categorical_feature=[1],
                     params={**PARAMS, "categorical_column": "0"})
    assert ds.construct().is_categorical.tolist() == [True, True, False]
    with pytest.raises(ValueError, match="out of range"):
        lgb.Dataset(X, label=y,
                    params={**PARAMS, "cat_column": "7"}).construct()


@pytest.mark.parametrize("feature_name", [None, "auto", ["a", "b", "c"]])
def test_a_name_with_no_such_feature_name_is_refused_in_words(feature_name):
    X, y = table(500, categories=40)
    with pytest.raises(lgb.basic.LightGBMError, match="feature_name"):
        lgb.Dataset(X, label=y, feature_name=feature_name,
                    params={**PARAMS, "categorical_column": "name:big"}
                    ).construct()


@pytest.mark.parametrize("grower", ["canonical", "fused"])
def test_prediction_routes_every_training_row_as_training_did(
        grower, monkeypatch):
    """A column of 400 categories under ``max_bin`` 255: 254 are kept,
    the rest and the values the bin sample never met share the others'
    bin.  ``Booster.predict`` on the training matrix, which routes by
    ``x == c`` on the raw values, must reproduce the training scores,
    which were routed by bin, on EVERY row (259 of 20,000 rows differed
    by up to 0.086 before PR 38).  ``fused``: the grower a chip runs,
    its kernels interpreted."""
    n = 20_000 if grower == "canonical" else 6_000
    if grower == "fused":
        monkeypatch.setattr(
            GBDT, "select_grower", lambda self, row_mask=False: ("fused", ""))
    X, y = table(n)
    params = {**PARAMS, "bin_construct_sample_cnt": n // 2}
    ds = lgb.Dataset(X, label=y, params={**params, "cat_column": "0,1"})
    booster = lgb.train(params, ds, num_boost_round=4)
    inner = ds.construct()
    mapper = inner.bin_mappers[0]
    assert len(mapper.bin_to_category) == 254 and mapper.num_bin == 255
    others = np.asarray(inner.X_bin)[:, 0] == 254
    kept = np.isin(X[:, 0], mapper.bin_to_category)
    assert others.sum() > 50 and (others == ~kept).all()
    # some of them belong to categories the half-table sample never met
    assert len(np.unique(X[others, 0])) > 400 - 254 - 60
    text = booster.model_to_string()
    assert sum(split_kinds(text)) >= 4  # the trees do split one-vs-rest
    trained = np.asarray(booster._gbdt._scores, np.float64)[0]
    predicted = booster.predict(X, raw_score=True)
    np.testing.assert_allclose(predicted, trained, rtol=0, atol=1e-6)
    # and a reloaded model routes them the same way (no new field)
    again = lgb.Booster(model_str=text).predict(X, raw_score=True)
    np.testing.assert_allclose(again, trained, rtol=0, atol=1e-6)
    # rows of the others' bin never go left at a split of their column:
    # a value no training row held goes where they went
    strange = X[others][:50].copy()
    strange[:, 0] = 10_000 + np.arange(len(strange))
    np.testing.assert_allclose(
        booster.predict(strange, raw_score=True),
        booster.predict(X[others][:50], raw_score=True), rtol=0, atol=0)
