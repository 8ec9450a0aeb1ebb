"""Standard-normal columns and a linear target of a few of them, as
``sklearn.datasets.make_regression`` makes them (the "Synthetic" set of
the GPU tree-boosting papers): ``y = X[:, informative] @ coef``
with ``coef = 100 U(0, 1)`` and, as by its defaults, no noise.  The
informative columns and their coefficients are the configuration's
(``label_seed``); the rows are the run's seed.  Values lie on the 2**-10
grid of ``blocks.to_grid``."""

from __future__ import annotations

import numpy as np

from . import blocks


def generate(p: dict, seed: int) -> dict:
    rows, cols = int(p["rows"]), int(p["features"])
    fixed = np.random.default_rng(int(p["label_seed"]))
    informative = np.sort(fixed.choice(cols, size=int(p["informative"]),
                                       replace=False))
    coef = (100.0 * fixed.uniform(size=len(informative))).astype(np.float32)
    X, y = blocks.normal_matrix(
        rows, cols, seed, lambda block, rng: blocks.to_grid(block),
        lambda block: block[:, informative] @ coef)
    return {"X": X, "y": y, "group": None}
