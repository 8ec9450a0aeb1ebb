"""The production-line configuration's generator.

A part passes only some of the 52 stations, so a station's columns are
present or absent together and about 81% of the cells are missing; which
stations a route takes, and how the columns divide among the stations,
are the configuration's (``label_seed``), and the parts the run's seed."""

import numpy as np
import pytest

import cells
from generators import bosch_stations

CONFIG = cells.read_json(cells.HERE, "configs", "bosch-968.json")
PARAMS = CONFIG["generator"]["params"]
ROWS = 20_000


@pytest.fixture(scope="module")
def two_runs():
    small = {**PARAMS, "rows": ROWS}
    return (bosch_stations.generate(small, 2_147_483_659),
            bosch_stations.generate(small, 2_147_491_578))


def test_the_layout_is_the_configurations(two_runs):
    line = bosch_stations.Line(PARAMS)
    assert len(line.station_columns) == sum(PARAMS["line_stations"]) == 52
    assert line.station_columns.sum() == PARAMS["features"] == 968
    assert (line.station_columns >= 1).all()
    a, _ = two_runs
    assert a["X"].shape == (ROWS, 968) and a["X"].dtype == np.float32


def test_about_81_percent_of_cells_are_missing_in_station_blocks(two_runs):
    line = bosch_stations.Line(PARAMS)
    for run in two_runs:
        X = run["X"]
        nan = np.isnan(X)
        assert 0.79 < nan.mean() < 0.84
        assert abs(nan.mean() - (1 - line.present_share())) < 0.01
        # a station's columns are present or absent together
        first = np.concatenate([[0], np.cumsum(line.station_columns)[:-1]])
        np.testing.assert_array_equal(nan, nan[:, first][:, line.station_of])
        # and every column has gaps: drivers/train_steady_missing.py
        # requires a missing bin of each
        assert nan.any(axis=0).all() and (~nan).any(axis=0).all()


def test_values_sit_on_the_grid_and_the_label_on_its_share(two_runs):
    for run in two_runs:
        X = run["X"]
        v = X[~np.isnan(X)].astype(np.float64)
        assert np.abs(v * 1000 - np.rint(v * 1000)).max() < 1e-3
        assert abs(run["y"].mean() - PARAMS["positive_share"]) < 1e-3


def test_the_seed_draws_the_parts_and_not_the_layout(two_runs):
    a, b = two_runs
    assert np.mean(np.isnan(a["X"]) != np.isnan(b["X"])) > 0.1
    again = bosch_stations.generate({**PARAMS, "rows": ROWS}, 2_147_483_659)
    np.testing.assert_array_equal(again["X"], a["X"])
    np.testing.assert_array_equal(again["y"], a["y"])
