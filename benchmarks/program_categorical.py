"""What ``program.py`` leaves unread of a program that holds categorical
columns: the kind of every split (``decision_type``: 1 where one category
goes left, 0 where ``x <= threshold`` does) and the categories each
categorical column KEPT a bin for.  Like ``program.py``, nothing here
computes a number that is compared.
"""

from __future__ import annotations

import numpy as np

import program


def trees(booster, first: int, count: int | None = None) -> list:
    """``program.trees`` with every split's ``decision_type`` beside it."""
    out = program.trees(booster, first, count)
    for d, t in zip(out, booster._gbdt.models[first:]):
        d["decision_type"] = np.asarray(
            t.decision_type)[:max(d["num_leaves"] - 1, 0)]
    return out


def bounds(ds) -> list:
    """``program.bin_bounds`` for the numerical columns; for a categorical
    one, in the same place, the categories it kept (every other value
    shares the column's last bin: lightgbm_tpu/io/binner.py)."""
    inner = ds.construct()
    return [(col, np.asarray(m.bin_to_category, np.float64)
             if is_cat else ub)
            for (col, ub), m, is_cat in zip(
                program.bin_bounds(ds), inner.bin_mappers,
                inner.is_categorical)]


def categorical_columns(ds) -> list:
    """The columns of the raw matrix that the program binned as
    categorical."""
    inner = ds.construct()
    return sorted(int(c) for c in np.asarray(
        inner.real_feature_indices)[np.asarray(inner.is_categorical)])
