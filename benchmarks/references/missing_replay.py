"""The plain replay for a table with missing values.

``gbdt_replay`` follows the program's trees through a table with no gaps:
rows go left by ``x <= threshold`` and a search tries every bin bound.  A
column with a missing bin (the program's last bound of it is NaN) holds
NaN cells, and a node on it keeps a DEFAULT DIRECTION for them.  This
module says what that means on the raw matrix, in plain numpy, with
nothing of the program's bins but their bounds:

* a node whose ``decision_type`` has missing type NaN (bits 2-3 read 2)
  sends a NaN row left iff its default-left bit (2) is set; any other
  node reads a NaN as 0.0; every other value goes by ``x <= threshold``
  (``==`` at a categorical node, bit 1);
* the search at a sampled node builds each column's full histogram over
  its bin bounds, a NaN row in the column's missing bin, in float64 from
  float32 gradients, and tries every value threshold of a column with a
  missing bin twice: its missing rows right (every value bin up to the
  last) and left (every value bin but the last), under the same
  ``min_data_in_leaf`` and ``min_sum_hessian_in_leaf`` on both sides.
  Ties break as LightGBM 2.x's two scans do, each taking a later
  candidate only on a strictly greater gain: column asc; then its
  missing-left candidates, threshold desc (the reverse scan, run
  first); then its missing-right ones, threshold ASC (the forward
  scan); a column without a missing bin, threshold desc.  The answer's
  threshold is ``(bound, default_left)`` on such a column;
* the gain of a chosen split is read with the same routing.

``bound(..., altered=True)`` plants the fault of this mechanism: the first
node of each tree whose rows hold a NaN of its column sends them the
other way, in the routing alone.  ``bound(..., withheld=True)`` plants
the search's: no missing-left candidate is offered, so a node whose best
split sends its missing rows left answers with another.  The comparison
must fail on both.

The replay itself (gradients, float64 sums, leaf values, the sampled
nodes, the bfloat16 control, the other planted faults) is
``gbdt_replay``'s, by import: ``bound`` gives it these functions for the
length of a call, as ``onevsrest_replay.bound`` does.
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import gbdt_replay, onevsrest_replay

CATEGORICAL, DEFAULT_LEFT, MISSING_MASK, MISSING_NAN = 1, 2, 12, 8


def has_missing_bin(ub: np.ndarray) -> bool:
    return len(ub) > 1 and bool(np.isnan(ub[-1]))


def nan_left(dt: int, threshold: float) -> bool:
    """Where a NaN goes at a numerical node of ``decision_type`` ``dt``."""
    if dt & MISSING_MASK == MISSING_NAN:
        return bool(dt & DEFAULT_LEFT)
    return 0.0 <= threshold


def goes_left(x: np.ndarray, dt: int, threshold: float,
              flip: bool = False) -> np.ndarray:
    if dt & CATEGORICAL:
        return onevsrest_replay.category_of(np.nan_to_num(x)) == int(
            threshold)
    return np.where(np.isnan(x), nan_left(dt, threshold) != flip,
                    x <= threshold)


def route(X: np.ndarray, tree: dict, altered: bool = False):
    """``gbdt_replay.route`` with each node's default direction: rows of
    every node and the leaf of every row, by the raw values; ``altered``:
    the planted fault (the module docstring)."""
    n = X.shape[0]
    nl = tree["num_leaves"]
    rows = {0: np.arange(n, dtype=np.int32)} if nl > 1 else {}
    leaf_of = np.zeros(n, np.int32)
    node_rows = {}
    done = not altered
    for i in range(nl - 1):
        r = rows.pop(i)
        node_rows[i] = r
        x = X[r, int(tree["split_feature_real"][i])]
        dt = int(tree["decision_type"][i])
        flip = not done and dt & MISSING_MASK == MISSING_NAN \
            and bool(np.isnan(x).any())
        done = done or flip
        left = goes_left(x, dt, tree["threshold_real"][i], flip)
        for child, part in ((int(tree["left_child"][i]), r[left]),
                            (int(tree["right_child"][i]), r[~left])):
            if child >= 0:
                rows[child] = part
            else:
                leaf_of[part] = ~child
    return node_rows, leaf_of


def binned(X: np.ndarray, bounds: list) -> list:
    """Every column of ``bounds`` binned by its bounds, a NaN in the
    column's missing bin (one past its value bins) or as 0.0 where it has
    none: the table every search of a state reads, a column a thread."""
    def one(entry):
        col, ub = entry
        nv = len(ub) - has_missing_bin(ub)
        x = X[:, col]
        nan = np.isnan(x)
        b = np.searchsorted(ub[:nv], np.where(nan, 0.0, x).astype(
            np.float64), side="left").astype(np.uint16)
        if nv < len(ub):
            b[nan] = nv
        return b

    with ThreadPoolExecutor(gbdt_replay.THREADS) as pool:
        return list(pool.map(one, bounds))


def best_split(X, rows, g, h, bounds, p, runner_up: bool = False, *,
               table: list, withheld: bool = False):
    """Best gain over every column and every bin bound of it, each
    threshold of a column with a missing bin twice (the module
    docstring), with the column and threshold that give it; ``table``
    is ``binned(X, bounds)``.  ``runner_up``: the best split of the
    second-best column; ``withheld``: no missing-left candidate."""
    gr, hr = g[rows].astype(np.float64), h[rows].astype(np.float64)
    G, H, C = gr.sum(), hr.sum(), len(rows)
    lam2, min_c = p["lambda_l2"], max(p["min_data_in_leaf"], 1)
    min_h = p["min_sum_hessian_in_leaf"] * (1 + gbdt_replay.FEASIBLE_SLACK)
    parent = G * G / (H + lam2)

    def gains(gl, hl, cl):
        ok = (cl >= min_c) & (C - cl >= min_c) & (hl >= min_h) \
            & (H - hl >= min_h)
        with np.errstate(divide="ignore", invalid="ignore"):
            return np.where(ok, gl * gl / (hl + lam2)
                            + (G - gl) ** 2 / (H - hl + lam2) - parent,
                            -np.inf)

    def one(k):
        col, ub = bounds[k]
        missing = has_missing_bin(ub)
        nv = len(ub) - missing
        b = table[k][rows]
        sums = [np.bincount(b, w, nv + 1) for w in (gr, hr, None)]
        head = [np.cumsum(s[:nv]) for s in sums]
        right_of_missing = gains(*head)  # t = 0 .. nv-1
        left_of_missing = (gains(*[hd[:-1] + s[nv] for hd, s in
                                   zip(head, sums)])
                           if missing and not withheld else np.zeros(0))
        # missing-left, threshold desc; then missing-right, threshold
        # asc where the column has a missing bin, else desc
        right = right_of_missing if missing else right_of_missing[::-1]
        order = np.concatenate([left_of_missing[::-1], right])
        if not len(order) or not np.isfinite(order.max()):
            return (-np.inf, col, 0.0)
        i = int(np.argmax(order))
        dl = i < len(left_of_missing)
        j = i - len(left_of_missing)
        t = (len(left_of_missing) - 1 - i if dl
             else j if missing else nv - 1 - j)
        thr = float(ub[t])
        return (float(order[i]), col, (thr, dl) if missing else thr)

    with ThreadPoolExecutor(gbdt_replay.THREADS) as pool:
        ranked = sorted(pool.map(one, range(len(bounds))),
                        key=lambda t: -t[0])
    return ranked[1] if runner_up else ranked[0]


def gain_of(X, rows, g, h, col: int, threshold, lam2: float) -> float:
    """Gain of splitting ``rows`` on ``col`` at ``threshold``: ``(bound,
    default_left)`` on a column with a missing bin, a bound elsewhere
    (a NaN then reads 0.0)."""
    if isinstance(threshold, tuple):
        thr, dl = threshold
        dt = MISSING_NAN | (DEFAULT_LEFT if dl else 0)
    else:
        thr, dt = threshold, 0
    left = goes_left(X[rows, col], dt, np.float32(thr))
    gr, hr = g[rows].astype(np.float64), h[rows].astype(np.float64)
    return gbdt_replay.split_gain((gr[left].sum(), hr[left].sum()),
                                  (gr[~left].sum(), hr[~left].sum()), lam2)


def chosen_with_sides(side: list, trees: list) -> list:
    """``check.side_of_program``'s answers with each node's side for its
    missing rows beside the threshold, where its column has a missing
    bin, as ``best_split`` answers."""
    for got, tree in zip(side[1:], trees):
        dt = tree["decision_type"]
        got["chosen"] = {
            i: (col, (thr, bool(dt[i] & DEFAULT_LEFT))
                if dt[i] & MISSING_MASK == MISSING_NAN else thr)
            for i, (col, thr) in got["chosen"].items()}
    return side


def bound(table: list, altered: bool = False, withheld: bool = False):
    """``gbdt_replay`` with this module's routing, search (over ``table``,
    ``binned``'s) and gain; ``altered`` and ``withheld`` plant the
    mechanism's faults (the module docstring)."""
    return onevsrest_replay.rebound(
        gbdt_replay, route=functools.partial(route, altered=altered),
        best_split=functools.partial(best_split, table=table,
                                     withheld=withheld),
        gain_of=gain_of)
