"""A learning-to-rank table: query-document rows in whole queries of
heavy-tailed length, graded relevance 0 to ``len(grade_shares)``, as the
LETOR sets have them.

What decides the shapes of the program's compiled code is the
configuration's and the same on every run: the query lengths
(``length_seed``: log-normal of the given ``mean_length`` and
``length_sigma``, rounded, kept within 1 .. ``length_max``, and as many
queries as ``rows / mean_length`` rounds to), how many rows of each grade
every query holds, and the rule that scores a row (``label_seed``).  The
run's ``--seed`` gives the features and the noise on that score, so WHICH
rows of a query carry its grades differs from run to run.

Grades are cut query by query: a query of n rows and richness r (a fixed
gamma draw; a ``barren_share`` of queries has r = 0, so all its labels are
0 and every gradient and hessian in it is exactly 0) holds about
``r * share_g * n`` rows of grade g or better, rounded by a fixed draw so
that short queries get a graded row now and then, and they are its rows of
highest noisy score.  Rows keep the order they were made in, which has
nothing to do with their labels: the order of ties is arbitrary, as in a
crawl.
"""

from __future__ import annotations

import numpy as np

from . import blocks


def query_lengths(p: dict, rows: int) -> np.ndarray:
    """Whole queries that use up ``rows`` exactly: the law's draws, then
    single rows given to or taken from randomly chosen queries until the
    sum is right (a percent or two of a row a query at the cell's size)."""
    rng = np.random.default_rng([int(p["length_seed"]), 0])
    sigma, cap = float(p["length_sigma"]), int(p["length_max"])
    nq = max(1, int(round(rows / float(p["mean_length"]))))
    mu = np.log(float(p["mean_length"])) - sigma * sigma / 2
    sizes = np.clip(np.rint(rng.lognormal(mu, sigma, nq)), 1, cap
                    ).astype(np.int64)
    if not nq <= rows <= nq * cap:
        raise ValueError(f"{rows} rows cannot make {nq} queries of 1..{cap}")
    while (short := rows - int(sizes.sum())) != 0:
        room = np.flatnonzero(sizes < cap if short > 0 else sizes > 1)
        sizes[rng.choice(room, size=min(abs(short), len(room)),
                         replace=False)] += np.sign(short)
    return sizes


def grade_counts(p: dict, sizes: np.ndarray) -> np.ndarray:
    """[grades, queries]: how many rows of each query have grade g + 1 or
    better.  Fixed by the configuration."""
    rng = np.random.default_rng([int(p["length_seed"]), 1])
    nq = len(sizes)
    shape = float(p["richness_shape"])
    rich = rng.gamma(shape, 1.0 / shape, nq)
    rich[rng.random(nq) < float(p["barren_share"])] = 0.0
    at_least = np.cumsum(np.asarray(p["grade_shares"], np.float64)[::-1]
                         )[::-1]  # share of grade g or better
    want = rich[None, :] * at_least[:, None] * sizes[None, :]
    counts = np.floor(want + rng.random(nq)[None, :]).astype(np.int64)
    return np.minimum(counts, sizes[None, :])


def generate(p: dict, seed: int) -> dict:
    rows, cols = int(p["rows"]), int(p["features"])
    sizes = query_lengths(p, rows)
    counts = grade_counts(p, sizes)
    kinds = blocks.column_kinds(cols, p["column_shares"])
    fixed = np.random.default_rng(int(p["label_seed"]))
    cards = np.exp(fixed.uniform(np.log(2), np.log(p["count_cardinality_max"]),
                                 size=cols)).astype(np.float32)
    label = blocks.LabelScore(fixed, cols, int(p["label_features"]), kinds,
                              cards, pair=0.8, bend=0.5, square=True)
    X, score = blocks.normal_matrix(
        rows, cols, seed,
        lambda block, rng: blocks.shape_columns(block, kinds, cards), label)
    rng = np.random.default_rng([int(seed), 1])
    score += np.float32(p["label_noise"]) * score.std() \
        * rng.standard_normal(rows, dtype=np.float32)
    # every row's place in its query by noisy score, best first
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    query = np.repeat(np.arange(len(sizes)), sizes)
    by_score = np.lexsort((-score, query))
    place = np.empty(rows, np.int64)
    place[by_score] = np.arange(rows) - starts[query]
    y = (place[None, :] < counts[:, query]).sum(axis=0)
    return {"X": X, "y": y.astype(np.float32), "group": sizes}
