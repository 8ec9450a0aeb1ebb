"""The ranking configuration's generator and plain reference objective.

What the program compiles depends on the query lengths alone, so they are
the configuration's and not the run's; what a run's seed changes is the
features and which rows carry the grades.  The reference's lambdas cancel
within every query and vanish where a query's labels are equal."""

import numpy as np
import pytest

import cells
from generators import ltr_queries
from references import lambdarank

CONFIG = cells.read_json(cells.HERE, "configs", "istella-s-220.json")
PARAMS = CONFIG["generator"]["params"]
REHEARSAL = CONFIG["rehearsal"]["rows"]


def buckets(sizes) -> dict:
    """Queries in each of the program's power-of-two length buckets."""
    of = np.maximum(16, 1 << np.ceil(np.log2(np.maximum(sizes, 1))
                                     ).astype(np.int64))
    return {int(b): int((of == b).sum()) for b in np.unique(of)}


@pytest.fixture(scope="module")
def two_runs():
    small = {**PARAMS, "rows": REHEARSAL}
    return (ltr_queries.generate(small, 2_147_483_659),
            ltr_queries.generate(small, 2_147_491_578))


def test_two_run_seeds_share_lengths_and_buckets_and_not_features(two_runs):
    a, b = two_runs
    np.testing.assert_array_equal(a["group"], b["group"])
    assert buckets(a["group"]) == buckets(b["group"])
    assert a["X"].shape == b["X"].shape == (REHEARSAL, PARAMS["features"])
    assert np.mean(a["X"] != b["X"]) > 0.9
    assert np.mean(a["y"] != b["y"]) > 0.05
    # each query holds the same number of rows of each grade in both
    edges = np.concatenate([[0], np.cumsum(a["group"])])[:-1]
    for grade in range(1, 5):
        np.testing.assert_array_equal(
            np.add.reduceat(a["y"] >= grade, edges),
            np.add.reduceat(b["y"] >= grade, edges))


@pytest.mark.parametrize("rows", [PARAMS["rows"], REHEARSAL])
def test_lengths_sum_to_the_rows(rows):
    sizes = ltr_queries.query_lengths(PARAMS, rows)
    assert sizes.sum() == rows
    assert sizes.min() >= 1 and sizes.max() <= PARAMS["length_max"]
    assert len(sizes) == round(rows / PARAMS["mean_length"])
    if rows == PARAMS["rows"]:  # the published shape, and every bucket
        assert len(sizes) == CONFIG["published"]["train_queries"]
        assert rows == CONFIG["published"]["train_rows"]
        assert sorted(buckets(sizes)) == [16, 32, 64, 128, 256, 512, 1024]
        assert 78 <= np.median(sizes) <= 88
        assert 0.005 <= np.mean(sizes > 400) <= 0.02


def test_labels_are_graded_mostly_zero_and_some_queries_barren(two_runs):
    data = two_runs[0]
    share = np.bincount(data["y"].astype(np.int64), minlength=5) / REHEARSAL
    assert share[0] > 0.8 and np.all(share[1:] > 0.005), share
    edges = np.concatenate([[0], np.cumsum(data["group"])])[:-1]
    barren = np.add.reduceat(data["y"], edges) == 0
    assert 0 < barren.mean() < 0.2


def test_reference_lambdas_cancel_within_every_query(two_runs):
    data = two_runs[0]
    params = {**CONFIG["defaults_relied_on"], **CONFIG["params"]}
    objective = lambdarank.Objective(data, params)
    rng = np.random.default_rng(3)
    scores = (rng.integers(-4, 5, REHEARSAL) * np.float32(0.05)
              ).astype(np.float32)  # ties, as leaf values make them
    grad, hess = objective.gradients(scores)
    assert grad.dtype == hess.dtype == np.float32
    assert np.all(hess >= 0)
    equal = 0
    for _, a, b in objective.queries():
        g = grad[a:b].astype(np.float64)
        # each row's float32 rounding, 6e-8 of it, is all that is left
        assert abs(g.sum()) <= 1e-6 * max(np.abs(g).sum(), 1e-30)
        if data["y"][a:b].min() == data["y"][a:b].max():
            equal += 1
            assert not grad[a:b].any() and not hess[a:b].any()
        else:
            assert np.abs(g).max() > 0 and hess[a:b].max() > 0
    assert equal > 0
    # the smooth cost falls along the lambdas, and NDCG is only printed
    step = np.float32(0.1) * grad / np.maximum(hess, np.float32(1e-3))
    assert objective.loss(scores - step) < objective.loss(scores)
    assert 0 < objective.ndcg(scores) < 1
