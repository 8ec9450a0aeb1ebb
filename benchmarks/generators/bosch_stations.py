"""The Bosch production line table: one row a part, 968 numeric
measurements in the order of the Kaggle table's ``train_numeric.csv``
(columns ``L{line}_S{station}_F{n}``: 4 lines, 52 stations), and a binary
label: the part failed its quality check.

A part passes only some stations, so a station's columns are present or
absent together, and most cells of a row are missing (NaN).  What is
fixed by the configuration's ``label_seed``: how many columns each
station measures, the routes (each a set of chances to visit each
station, and how many parts take it), each column's spread, and what the
route and a few measurements add to the chance of a failure.  What the
run's seed draws: the parts, their stations and their measurements.
Every value lies on the configuration's grid (0.001): the table holds
readings rounded as the real one does.  The label is cut at the
quantile that leaves the configuration's share of failures.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import blocks


class Line:
    """Everything ``label_seed`` fixes: stations, routes, spreads and the
    label's score."""

    def __init__(self, p: dict):
        fixed = np.random.default_rng(int(p["label_seed"]))
        stations = [int(s) for s in p["line_stations"]]
        columns = [int(c) for c in p["line_columns"]]
        # columns of each station, each station at least one, in order
        per_station = []
        for s, c in zip(stations, columns):
            share = fixed.dirichlet(np.full(s, 2.0))
            k = 1 + np.floor(share * (c - s)).astype(np.int64)
            k[: c - int(k.sum())] += 1
            per_station.append(k)
        self.station_columns = np.concatenate(per_station)
        self.station_of = np.repeat(np.arange(len(self.station_columns)),
                                    self.station_columns)
        line_of_station = np.repeat(np.arange(len(stations)), stations)
        # routes: a route visits a line with its own chance, and within
        # a visited line each station it takes often or rarely
        R = int(p["routes"])
        visit = fixed.random((R, len(stations))) < np.asarray(
            p["line_visit"], np.float64)
        often = fixed.random((R, len(self.station_columns))) < float(
            p["station_often"])
        chance = np.where(often, float(p["often_chance"]),
                          float(p["rare_chance"]))
        self.visit = (chance * visit[:, line_of_station]).astype(np.float32)
        w = fixed.pareto(1.5, R) + 0.05
        self.route_cdf = np.cumsum(w / w.sum())
        self.spread = np.exp(fixed.uniform(
            np.log(p["spread_min"]), np.log(p["spread_max"]),
            len(self.station_of))).astype(np.float32)
        self.centre = (fixed.standard_normal(len(self.station_of))
                       * 0.05).astype(np.float32)
        self.route_effect = (fixed.standard_normal(R)
                             * float(p["route_effect"])).astype(np.float32)
        k = int(p["label_features"])
        self.label_cols = np.sort(fixed.choice(len(self.station_of), k,
                                               replace=False))
        self.label_w = (fixed.standard_normal(k)
                        / self.spread[self.label_cols]).astype(np.float32)
        self.grid = np.float32(p["grid"])

    def present_share(self) -> float:
        """The share of cells a row of this line holds, in expectation."""
        pr = np.diff(np.concatenate([[0.0], self.route_cdf]))
        return float(pr @ (self.visit[:, self.station_of]).mean(axis=1))

    def fill(self, block: np.ndarray, rng) -> np.ndarray:
        """Make a block of parts in place; returns their label scores."""
        rows = block.shape[0]
        route = np.minimum(np.searchsorted(self.route_cdf, rng.random(rows),
                                           side="right"),
                           len(self.route_cdf) - 1)
        rng.standard_normal(block.shape, dtype=np.float32, out=block)
        np.multiply(block, self.spread, out=block)
        np.add(block, self.centre, out=block)
        np.multiply(block, np.float32(1) / self.grid, out=block)
        np.rint(block, out=block)
        np.multiply(block, self.grid, out=block)
        z = block[:, self.label_cols] - self.centre[self.label_cols]
        visited = rng.random((rows, self.visit.shape[1]),
                             dtype=np.float32) < self.visit[route]
        absent = ~visited[:, self.station_of]
        block[absent] = np.nan
        z[absent[:, self.label_cols]] = 0.0
        return z @ self.label_w + self.route_effect[route]


def generate(p: dict, seed: int) -> dict:
    rows, cols = int(p["rows"]), int(p["features"])
    line = Line(p)
    assert len(line.station_of) == cols, (len(line.station_of), cols)
    X = np.empty((rows, cols), np.float32)
    score = np.empty(rows, np.float32)
    seeds = np.random.SeedSequence(int(seed)).spawn(blocks.BLOCKS)
    edges = np.linspace(0, rows, blocks.BLOCKS + 1).astype(np.int64)

    def fill(b: int) -> None:
        lo, hi = edges[b], edges[b + 1]
        score[lo:hi] = line.fill(X[lo:hi], np.random.default_rng(seeds[b]))

    with ThreadPoolExecutor(blocks.THREADS) as pool:
        list(pool.map(fill, range(blocks.BLOCKS)))
    rng = np.random.default_rng([int(seed), 1])
    score += np.float32(p["label_noise"]) * score.std() \
        * rng.standard_normal(rows, dtype=np.float32)
    cut = np.quantile(score, 1.0 - float(p["positive_share"]))
    return {"X": X, "y": (score > cut).astype(np.float32), "group": None}
