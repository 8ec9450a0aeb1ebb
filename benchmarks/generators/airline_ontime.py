"""The Airline on-time table: one row a scheduled flight, thirteen columns
in the order of arXiv:1806.11248's "Airline" set, six of them identifiers
(month, day, weekday, carrier, origin, destination) that LightGBM's own
experiment on this data declares categorical, and a binary label: the
flight arrived late.

What is fixed by the configuration's ``label_seed``: how popular each
airport and carrier is (heavy-tailed: a few hubs hold several per cent of
the rows each), where each airport lies (a flight's distance is its
pair's), and what each identifier adds to the chance of a late arrival.
What the run's seed draws: the flights.  Identifiers are label-encoded in
an order that says nothing about popularity, so a column's bins are not
its codes.  Every value is a whole number that float32 holds exactly.
"""

from __future__ import annotations

import numpy as np

from . import blocks

COLUMNS = ("Year", "Month", "DayofMonth", "DayOfWeek", "CRSDepTime",
           "CRSArrTime", "UniqueCarrier", "FlightNum", "ActualElapsedTime",
           "Origin", "Dest", "Distance", "Diverted")
(YEAR, MONTH, DAY, WEEKDAY, DEP, ARR, CARRIER, FLIGHT, ELAPSED, ORIGIN, DEST,
 DISTANCE, DIVERTED) = range(13)
MONTH_DAYS = np.array([0, 31, 29, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31],
                      np.float32)
MILES_A_MINUTE, GROUND_MINUTES = 8.4, 32.0


def hhmm(minutes: np.ndarray) -> np.ndarray:
    """Minutes after midnight as the table writes a time: 1735."""
    m = np.mod(minutes, 1440)
    return np.floor(m / 60) * 100 + np.mod(m, 60)


class Popularity:
    """Identifiers drawn by rank, ``(rank + shift) ** -power * exp(-rank
    / cutoff)``: a power law among the busy ones and a thin tail; the
    code of a rank is a fixed permutation's."""

    def __init__(self, fixed, count: int, shift: float, power: float,
                 cutoff: float):
        rank = np.arange(count)
        w = (rank + shift) ** -float(power) * np.exp(-rank / cutoff)
        self.cdf = np.cumsum(w / w.sum())
        self.code = fixed.permutation(count).astype(np.float32)

    def draw(self, rng, rows: int) -> np.ndarray:
        rank = np.searchsorted(self.cdf, rng.random(rows), side="right")
        return self.code[np.minimum(rank, len(self.code) - 1)]


class Table:
    """Everything ``label_seed`` fixes, and the two passes over a block."""

    def __init__(self, p: dict):
        fixed = np.random.default_rng(int(p["label_seed"]))
        A, C = int(p["airports"]), int(p["carriers"])
        self.p = p
        self.airport = Popularity(fixed, A, *p["airport_popularity"])
        self.carrier = Popularity(fixed, C, *p["carrier_popularity"])
        self.place = fixed.uniform((0, 0), (2500, 1200), (A, 2))
        years = np.arange(p["first_year"], p["last_year"] + 1)
        self.years = years.astype(np.float32)
        self.year_cdf = np.cumsum((years - years[0] + 20.0)
                                  / (years - years[0] + 20.0).sum())
        f32 = np.float32
        self.late = {
            ORIGIN: (0.6 * fixed.standard_normal(A)).astype(f32),
            DEST: (0.4 * fixed.standard_normal(A)).astype(f32),
            CARRIER: (0.5 * fixed.standard_normal(C)).astype(f32),
            MONTH: (0.35 * fixed.standard_normal(13)).astype(f32),
            WEEKDAY: (0.2 * fixed.standard_normal(8)).astype(f32),
        }

    def fill(self, block: np.ndarray, rng) -> None:
        """A block of standard normals becomes a block of flights."""
        p, n = self.p, len(block)
        z_dep, z_air, z_gate = (block[:, c].copy()
                                for c in (DEP, ELAPSED, ARR))
        block[:, YEAR] = self.years[np.minimum(
            np.searchsorted(self.year_cdf, rng.random(n), side="right"),
            len(self.years) - 1)]
        block[:, MONTH] = rng.integers(1, 13, n)
        block[:, DAY] = np.minimum(
            rng.integers(1, 32, n), MONTH_DAYS[block[:, MONTH].astype(int)])
        block[:, WEEKDAY] = rng.integers(1, 8, n)
        block[:, CARRIER] = self.carrier.draw(rng, n)
        block[:, FLIGHT] = np.floor(
            p["flight_numbers"] * rng.random(n) ** 2) + 1
        block[:, ORIGIN] = o = self.airport.draw(rng, n)
        block[:, DEST] = d = self.airport.draw(rng, n)
        gap = self.place[o.astype(int)] - self.place[d.astype(int)]
        block[:, DISTANCE] = dist = np.maximum(
            np.rint(np.hypot(gap[:, 0], gap[:, 1])), 31)
        # two waves of departures, morning and late afternoon
        morning = rng.random(n) < 0.5
        dep = np.clip(np.rint(np.where(
            morning, 510 + 130 * z_dep, 1030 + 155 * z_dep)), 300, 1439)
        planned = np.rint(dist / MILES_A_MINUTE + GROUND_MINUTES + 7)
        block[:, ELAPSED] = np.maximum(np.rint(
            dist / MILES_A_MINUTE + GROUND_MINUTES + 9 * np.abs(z_air)
            + 6 * z_gate), 20)
        block[:, DEP] = hhmm(dep)
        block[:, ARR] = hhmm(dep + planned)
        block[:, DIVERTED] = rng.random(n) < p["diverted_share"]

    def late_score(self, block: np.ndarray) -> np.ndarray:
        """What the identifiers, the hour, the distance and the time in
        the air add up to, before the run's noise."""
        score = np.zeros(len(block), np.float32)
        for col, effect in self.late.items():
            score += effect[block[:, col].astype(int)]
        dist = block[:, DISTANCE]
        over = block[:, ELAPSED] - (dist / MILES_A_MINUTE + GROUND_MINUTES)
        score += np.float32(0.07) * (np.floor(block[:, DEP] / 100) - 12)
        score += np.float32(0.06) * over + np.float32(2e-4) * dist
        score += np.float32(0.02) * (block[:, YEAR] - self.years[0])
        return score + np.float32(2.0) * block[:, DIVERTED]


def generate(p: dict, seed: int) -> dict:
    rows, cols = int(p["rows"]), int(p["features"])
    assert cols == len(COLUMNS), (cols, COLUMNS)
    table = Table(p)
    X, score = blocks.normal_matrix(rows, cols, seed, table.fill,
                                    table.late_score)
    rng = np.random.default_rng([int(seed), 1])
    score += np.float32(p["label_noise"]) * score.std() \
        * rng.standard_normal(rows, dtype=np.float32)
    cut = np.quantile(score, 1.0 - float(p["positive_share"]))
    return {"X": X, "y": (score > cut).astype(np.float32), "group": None}
