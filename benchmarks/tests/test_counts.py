"""The count functions against a tree counted by hand.

        root: 10 rows
        /          \\
    leaf 0: 4     node 1: 6 rows
                  /         \\
             leaf 1: 5     leaf 2: 1

Two splits: (parent 10, smaller child 4) and (parent 6, smaller child 1).
"""

import numpy as np

from counts import gbdt_tree, root_histogram, split_step, tree_shape

TREE = (np.array([10, 6]), np.array([4, 5, 1]),
        np.array([~0, ~1]), np.array([1, ~2]))
FEATURES = 3


def test_rows_at_every_split():
    parents, smaller = tree_shape.splits(*TREE)
    assert parents.tolist() == [10, 6]
    assert smaller.tolist() == [4, 1]


def test_required_work_of_a_tree():
    # histograms of the root and of each smaller child: 10 + 4 + 1 rows
    work = gbdt_tree.required([TREE], FEATURES)
    assert work == {"flops": 15 * 3 * 3, "bytes": 15 * (3 * 1 + 8)}


def test_split_step_work():
    # partition reads and writes 16 parent rows of 3 + 8 + 4 bytes, and the
    # smaller children (5 rows) are accumulated
    work = split_step.required([TREE], FEATURES)
    assert work["bytes"] == 2 * 16 * 15 + 5 * 11
    assert work["flops"] == 5 * 3 * 3


def test_root_histogram_work():
    assert root_histogram.required([TREE, TREE], FEATURES) == {
        "flops": 20 * 3 * 3, "bytes": 20 * 11}


def test_a_stump_requires_nothing():
    stump = (np.array([], int), np.array([7]), np.array([], int),
             np.array([], int))
    assert gbdt_tree.required([stump], FEATURES) == {"flops": 0, "bytes": 0}
