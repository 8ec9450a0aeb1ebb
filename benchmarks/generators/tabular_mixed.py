"""A wide mixed table with a binary label: label-encoded integer columns
of low cardinality beside continuous ones, as the public LightGBM kernels
fed Microsoft Malware Prediction.  Column kinds and the label rule are
the configuration's (``label_seed``); rows and noise the run's seed."""

from __future__ import annotations

import numpy as np

from . import blocks


def generate(p: dict, seed: int) -> dict:
    rows, cols = int(p["rows"]), int(p["features"])
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
    cut = np.quantile(score, 1.0 - float(p["positive_share"]))
    return {"X": X, "y": (score > cut).astype(np.float32), "group": None}
