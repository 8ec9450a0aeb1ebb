"""The work a leaf-wise tree requires, whatever implements it.

Histogram subtraction is the algorithm's own saving, so a tree needs the
histogram of its root and of the smaller child of every split, over every
feature.  Each histogram cell read is one bin (1 byte at up to 256 bins)
and feeds three accumulations (gradient, hessian, count); each row of a
histogram also reads its gradient and hessian (8 bytes).  Partitioning,
the search over bins and the score update are not counted: this is a floor
on the work, so the share read against it cannot be flattered by them.
"""

from __future__ import annotations

from . import tree_shape

ACCUMULATIONS = 3
BIN_BYTES = 1
GRADIENT_BYTES = 8


def of_rows(rows: int, features: int) -> dict:
    return {"flops": float(rows) * features * ACCUMULATIONS,
            "bytes": float(rows) * (features * BIN_BYTES + GRADIENT_BYTES)}


def required(trees: list, features: int) -> dict:
    """Summed over ``trees`` (tuples of internal_count, leaf_count,
    left_child, right_child)."""
    rows = 0
    for ic, lc, left, right in trees:
        if len(ic) == 0:
            continue
        _, smaller = tree_shape.splits(ic, lc, left, right)
        rows += int(ic[0]) + int(smaller.sum())
    return of_rows(rows, features)
