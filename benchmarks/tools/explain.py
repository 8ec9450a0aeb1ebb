"""Where a leaf's value comes from, three ways, beside the program's.

For every leaf of the trees the reference followed:

* ``ref``: the reference's value, float64 sums of the leaf's own rows;
* ``direct32``: the same rows summed in float32, one after the other;
* ``book32``: float32 sums as LightGBM's bookkeeping hands them down (the
  program's ``ops/split.py`` does the same): the root's totals are summed
  directly; at every split the right child's sums are the suffix sum over
  the split column's histogram bins above the threshold, and the left
  child's are the parent's totals less those.  Every bin here is the
  float64 sum rounded once to float32, the best a float32 histogram can
  be, so this reads the least that the arithmetic itself costs;
* ``program``: what the program answered.

``tools/limits.py --explain`` writes the table of each seed to an ``.npz``;
PERF.md (PR 25) reads the cause of the worst leaf's gap from it.
"""

from __future__ import annotations

import numpy as np

from references import gbdt_replay


def direct32(leaf_of: np.ndarray, a: np.ndarray, leaves: int) -> np.ndarray:
    """Plain float32 sums of every leaf's rows, in row order."""
    order = np.argsort(leaf_of, kind="stable")
    starts = np.searchsorted(leaf_of[order], np.arange(leaves))
    return np.add.reduceat(a.astype(np.float32)[order], starts,
                           dtype=np.float32)


def book32(X, tree: dict, node_rows: dict, g, h, bounds: dict) -> np.ndarray:
    """[leaves, 2] float32 (G, H) by parent-less-sibling bookkeeping."""
    nl = tree["num_leaves"]
    leaf = np.zeros((nl, 2), np.float32)
    total = {0: np.array([g.sum(dtype=np.float32), h.sum(dtype=np.float32)],
                         np.float32)}
    for i in range(nl - 1):
        rows = node_rows[i]
        col = int(tree["split_feature_real"][i])
        ub = bounds[col]
        b = np.searchsorted(ub, X[rows, col].astype(np.float64), side="left")
        hist = np.stack([np.bincount(b, g[rows], len(ub)),
                         np.bincount(b, h[rows], len(ub))]).astype(np.float32)
        t = int(np.searchsorted(ub, float(tree["threshold_real"][i]),
                                side="left"))
        suffix = np.cumsum(hist[:, ::-1], axis=1, dtype=np.float32)[:, ::-1]
        right = suffix[:, t + 1] if t + 1 < len(ub) else np.zeros(2, np.float32)
        left = total[i] - right
        for child, sums in ((int(tree["left_child"][i]), left),
                            (int(tree["right_child"][i]), right)):
            if child >= 0:
                total[child] = sums
            else:
                leaf[~child] = sums
    return leaf


def leaf_tables(state: dict, ref: dict, three_ways: bool) -> dict:
    """Arrays for one seed, ``t<tree>_<name>``: every leaf's and node's
    reading, and with ``three_ways`` the two float32 sums beside them."""
    X = state["data"]["X"]
    bounds = dict(state["bounds"])
    lam2, rate = ref["lambda_l2"], ref["rate"]
    out = {}
    for t, (tree, want) in enumerate(zip(state["trees"], ref["trees"])):
        g, h = want["grad"], want["hess"]
        nl = tree["num_leaves"]

        def value(sums):
            return -sums[:, 0].astype(np.float64) / (sums[:, 1] + lam2) * rate

        table = [
            ("count", want["leaf_sums"][:, 2]),
            ("G", want["leaf_sums"][:, 0]), ("H", want["leaf_sums"][:, 1]),
            ("abs_grad", want["abs_grad"]),
            ("sum_hess", float(h.sum(dtype=np.float64))),
            ("ref", want["leaf_value"]), ("program", tree["leaf_value"]),
            ("gain_ref", want["split_gain"]),
            ("gain_program", tree["split_gain"]),
            ("node_count", want["internal_count"])]
        if three_ways:
            d32 = np.stack([direct32(want["leaf_of"], a, nl)
                            for a in (g, h)], 1)
            b32 = book32(X, tree, want["node_rows"], g, h, bounds)
            table += [("direct32", value(d32)), ("book32", value(b32))]
        for name, a in table:
            out[f"t{t}_{name}"] = np.asarray(a)
    return out
