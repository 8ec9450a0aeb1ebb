"""Rows at every split of a grown tree, from its node counts."""

from __future__ import annotations

import numpy as np


def splits(internal_count, leaf_count, left_child, right_child):
    """``(parent_rows, smaller_child_rows)`` for every split of one tree."""
    def rows(child):
        return internal_count[child] if child >= 0 else leaf_count[~child]
    parents, smaller = [], []
    for i in range(len(internal_count)):
        parents.append(int(internal_count[i]))
        smaller.append(int(min(rows(int(left_child[i])),
                               rows(int(right_child[i])))))
    return np.asarray(parents, np.int64), np.asarray(smaller, np.int64)
