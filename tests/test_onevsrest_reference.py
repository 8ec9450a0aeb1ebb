"""The one-vs-rest plain reference against hand-made nodes, and its
controls: a planted fault must be able to answer with a categorical
column, and the bindings it lends ``gbdt_replay`` must be given back."""

import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")

P = {"lambda_l2": 0.0, "min_data_in_leaf": 5, "min_sum_hessian_in_leaf": 1.0}


@pytest.fixture(scope="module", autouse=True)
def references():
    """The benchmark's reference modules, imported the way ``run.py``
    does; the names below are theirs."""
    sys.path.append(BENCH)
    from references import gbdt_replay, onevsrest_replay

    globals().update(gbdt_replay=gbdt_replay,
                     onevsrest_replay=onevsrest_replay)
    yield
    sys.path.remove(BENCH)


def node(n=600, seed=3):
    """Rows whose gradient follows category 7 of column 0 most, a step
    in column 1 less, category 2 of column 2 least."""
    rng = np.random.default_rng(seed)
    X = np.c_[rng.integers(0, 12, n), rng.standard_normal(n),
              rng.integers(0, 4, n)].astype(np.float32)
    g = (3.0 * (X[:, 0] == 7) + 1.0 * (X[:, 1] > 0.3) + 0.3 * (X[:, 2] == 2)
         + 0.05 * rng.standard_normal(n)).astype(np.float32)
    g -= g.mean()
    h = np.ones(n, np.float32)
    bounds = [(0, np.arange(12.0)), (1, np.array([-0.5, 0.3, np.inf])),
              (2, np.arange(4.0))]
    return X, np.arange(n), g, h, bounds


def by_hand(X, rows, g, left):
    gl, gr = g[rows][left].sum(dtype=np.float64), g[rows][~left].sum(
        dtype=np.float64)
    nl, nr = left.sum(), (~left).sum()
    return gl * gl / nl + gr * gr / nr - (gl + gr) ** 2 / (nl + nr)


def test_the_search_finds_the_category_and_the_bound():
    X, rows, g, h, bounds = node()
    cats = frozenset({0, 2})
    gain, col, value = onevsrest_replay.best_split(
        X, rows, g, h, bounds, P, categorical=cats)
    assert (col, value) == (0, 7.0)
    assert gain == pytest.approx(by_hand(X, rows, g, X[:, 0] == 7))
    assert gain == pytest.approx(onevsrest_replay.gain_of(
        X, rows, g, h, 0, 7.0, 0.0, categorical=cats))
    # the runner-up is the best split of the second-best COLUMN
    gain2, col2, value2 = onevsrest_replay.best_split(
        X, rows, g, h, bounds, P, runner_up=True, categorical=cats)
    assert (col2, value2) == (1, 0.3) and gain2 < gain
    assert gain2 == pytest.approx(by_hand(X, rows, g, X[:, 1] <= 0.3))
    # ... and can be a categorical one: the fault ``altered_split`` then
    # answers with a category
    weak = [(0, np.arange(12.0)), (1, np.array([np.inf])),
            (2, np.arange(4.0))]
    _, col3, value3 = onevsrest_replay.best_split(
        X, rows, g, h, weak, P, runner_up=True, categorical=cats)
    assert (col3, value3) == (2, 2.0)


def test_a_value_that_is_no_kept_category_is_never_a_candidate():
    """Category 7 is not among the kept ones: its rows go with the rest,
    and the search answers with another column."""
    X, rows, g, h, bounds = node()
    kept = [(0, np.array([0.0, 1.0, 2.0, 3.0])), bounds[1], bounds[2]]
    _, col, value = onevsrest_replay.best_split(
        X, rows, g, h, kept, P, categorical=frozenset({0, 2}))
    assert (col, value) == (1, 0.3)


def test_min_data_holds_on_both_sides():
    X, rows, g, h, bounds = node()
    few = int((X[:, 0] == 7).sum())
    p = {**P, "min_data_in_leaf": few + 1}
    _, col, value = onevsrest_replay.best_split(
        X, rows, g, h, bounds, p, categorical=frozenset({0, 2}))
    assert (col, value) != (0, 7.0)


def test_routing_is_by_the_nodes_kind():
    X = np.array([[3, 0.5], [4, 0.5], [3, 2.5], [9, 0.1]], np.float32)
    tree = {"num_leaves": 3, "split_feature_real": np.array([0, 1]),
            "threshold_real": np.array([3.0, 1.0]),
            "decision_type": np.array([1, 0]),
            "left_child": np.array([1, ~0]), "right_child": np.array([~1, ~2])}
    node_rows, leaf_of = onevsrest_replay.route(X, tree)
    assert node_rows[1].tolist() == [0, 2]  # category 3 went left
    assert leaf_of.tolist() == [0, 1, 2, 1]


def test_bound_lends_and_gives_back():
    before = (gbdt_replay.route, gbdt_replay.best_split, gbdt_replay.gain_of)
    with onevsrest_replay.bound([0, 2]):
        assert gbdt_replay.route is onevsrest_replay.route
        X, rows, g, h, bounds = node()
        assert gbdt_replay.best_split(X, rows, g, h, bounds, P)[1:] == (0, 7.0)
    assert (gbdt_replay.route, gbdt_replay.best_split,
            gbdt_replay.gain_of) == before
    with pytest.raises(RuntimeError):
        with onevsrest_replay.bound([0]):
            raise RuntimeError
    assert gbdt_replay.route is before[0]


def skewed_column(n=200_000, categories=400, seed=11):
    """A heavy-tailed categorical column beside a number, and what a
    program that keeps the ``keep`` busiest of ``sample`` rows keeps."""
    rng = np.random.default_rng(seed)
    rank = np.minimum(rng.geometric(0.012, n) - 1, categories - 1)
    code = np.random.default_rng(1).permutation(categories)
    X = np.c_[code[rank], rng.standard_normal(n)].astype(np.float32)
    return X


def kept_by_a_sample(X, keep, sample, seed=5):
    rows = np.random.default_rng(seed).choice(len(X), sample, replace=False)
    values, counts = np.unique(X[rows, 0].astype(np.int64),
                               return_counts=True)
    order = np.lexsort((values, -counts))
    return values[order][:keep].astype(np.float64)


def kept_off(X, kept, keep, sample):
    return onevsrest_replay.kept_off(
        X, [(0, kept), (1, np.array([0.0, np.inf]))], frozenset({0}),
        keep, sample)


@pytest.mark.parametrize("keep,sample", [(254, 50_000), (40, 50_000),
                                         (254, 2_000), (254, 10**6)])
def test_the_lists_a_sample_keeps_read_zero(keep, sample):
    """Whatever the sample drew: more categories than bins, fewer, a
    sample that meets few of them, a sample that is the whole column."""
    X = skewed_column()
    for seed in range(4):
        kept = kept_by_a_sample(X, keep, min(sample, len(X)), seed)
        assert kept_off(X, kept, keep, sample) == 0


def swapped(X, kept):
    """The busiest kept category gives its place to a rare one left out."""
    values = np.unique(X[:, 0])
    return np.r_[np.setdiff1d(values, kept)[:1], kept[1:]]


@pytest.mark.parametrize("wrong", [
    swapped,
    lambda X, kept: kept[:-1],                        # one short
    lambda X, kept: kept[:100],                       # fewer than max_bin - 1
    lambda X, kept: np.r_[kept[:-1], kept[0]],        # an entry twice
    lambda X, kept: np.r_[kept[:-1], 12345.0],        # no value of the column
    lambda X, kept: np.r_[kept, np.setdiff1d(np.unique(X[:, 0]), kept)[:1]],
], ids=["swapped", "one_short", "too_few", "twice", "unknown", "too_many"])
def test_a_list_no_sample_explains_is_counted(wrong):
    """The reference searches the kept categories the program hands it:
    a program that keeps the wrong ones, or too few, must not come out
    ``correct`` for it."""
    X = skewed_column()
    kept = kept_by_a_sample(X, 254, 50_000)
    assert kept_off(X, kept, 254, 50_000) == 0
    assert kept_off(X, wrong(X, kept), 254, 50_000) >= 1
