"""The plain replay for a table with categorical columns.

``gbdt_replay`` follows the program's trees through a table whose every
column is a number: rows go left by ``x <= threshold`` and a search tries
every bin bound.  A categorical column splits ONE-VS-REST: one category
goes left, every other value right.  This module says what that means on
the raw matrix, in plain numpy, with nothing of the program's bins:

* a node the program marks categorical (``decision_type`` 1) sends a row
  left iff the row's category IS the node's;
* the search at a sampled node tries, beside every bin bound of every
  numerical column, every KEPT category of every categorical column as
  the one that goes left, under the same ``min_data_in_leaf`` and
  ``min_sum_hessian_in_leaf`` on both sides.  The kept categories come
  from the program as the bin bounds do (it chose them from a sample);
  a value that is none of them is never a candidate and goes right at
  every split of its column, which is where the configuration's file
  says such values go (``categorical.other_values``);
* the gain of a chosen split is read by equality on a categorical column
  and by ``<=`` on a numerical one;
* the kept lists are the program's, so they are looked at too
  (``kept_off``): each against the column's counts on the raw matrix, for
  what no sample of the program's size would have kept or left out.

The replay itself (gradients, float64 sums, leaf values, the sampled
nodes, the bfloat16 control, the planted faults) is ``gbdt_replay``'s,
by import: it looks ``route``, ``best_split`` and ``gain_of`` up by name
when it runs, and ``bound`` gives it these for the length of a call.
"""

from __future__ import annotations

import contextlib
import functools
import math

import numpy as np

from . import gbdt_replay

# the numerical column's search and gain, as they are before any rebinding
bound_search, less_equal_gain = gbdt_replay.best_split, gbdt_replay.gain_of
gammaln = np.vectorize(math.lgamma, otypes=[np.float64])


def category_of(x: np.ndarray) -> np.ndarray:
    """A categorical column's values as whole numbers, as the library
    reads them (the reference's ``static_cast<int>``)."""
    return x.astype(np.int64)


def route(X: np.ndarray, tree: dict):
    """``gbdt_replay.route`` with the node's kind: rows of every node and
    the leaf of every row, by the raw values."""
    n = X.shape[0]
    nl = tree["num_leaves"]
    rows = {0: np.arange(n, dtype=np.int32)} if nl > 1 else {}
    leaf_of = np.zeros(n, np.int32)
    node_rows = {}
    for i in range(nl - 1):
        r = rows.pop(i)
        node_rows[i] = r
        x = X[r, int(tree["split_feature_real"][i])]
        if tree["decision_type"][i] == 1:
            left = category_of(x) == int(tree["threshold_real"][i])
        else:
            left = x <= tree["threshold_real"][i]
        for child, part in ((int(tree["left_child"][i]), r[left]),
                            (int(tree["right_child"][i]), r[~left])):
            if child >= 0:
                rows[child] = part
            else:
                leaf_of[part] = ~child
    return node_rows, leaf_of


def one_vs_rest(X, rows, g, h, col: int, kept: np.ndarray, p: dict):
    """``(gain, col, category)`` of the best kept category of one
    categorical column to send left alone, as ``gbdt_replay.best_split``
    reads a numerical column's best bound."""
    gr, hr = g[rows].astype(np.float64), h[rows].astype(np.float64)
    G, H, C = gr.sum(), hr.sum(), len(rows)
    lam2, min_c = p["lambda_l2"], max(p["min_data_in_leaf"], 1)
    min_h = p["min_sum_hessian_in_leaf"] * (1 + gbdt_replay.FEASIBLE_SLACK)
    cats = np.sort(category_of(np.asarray(kept)))
    x = category_of(X[rows, col])
    at = np.minimum(np.searchsorted(cats, x), len(cats) - 1)
    b = np.where(cats[at] == x, at, len(cats))  # the rest: no candidate
    k = len(cats)
    gl = np.bincount(b, gr, k + 1)[:k]
    hl = np.bincount(b, hr, k + 1)[:k]
    cl = np.bincount(b, minlength=k + 1)[:k]
    ok = (cl >= min_c) & (C - cl >= min_c) & (hl >= min_h) & (H - hl >= min_h)
    if not ok.any():
        return (-np.inf, col, 0.0)
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = np.where(ok, gl * gl / (hl + lam2)
                        + (G - gl) ** 2 / (H - hl + lam2)
                        - G * G / (H + lam2), -np.inf)
    best = int(np.argmax(gain))
    return (float(gain[best]), col, float(cats[best]))


def best_split(X, rows, g, h, bounds, p, runner_up: bool = False, *,
               categorical: frozenset):
    """Best gain over every column: every bin bound of a numerical one
    (``gbdt_replay.best_split`` on those columns alone) and every kept
    category of a categorical one.  ``runner_up``: the best split of the
    second-best column, whatever its kind."""
    numerical = [e for e in bounds if e[0] not in categorical]
    ranked = [one_vs_rest(X, rows, g, h, col, kept, p)
              for col, kept in bounds if col in categorical]
    if numerical:
        ranked.append(bound_search(X, rows, g, h, numerical, p))
    if runner_up and len(numerical) > 1:
        ranked.append(bound_search(X, rows, g, h, numerical, p,
                                   runner_up=True))
    ranked.sort(key=lambda t: -t[0])
    return ranked[1] if runner_up else ranked[0]


def gain_of(X, rows, g, h, col: int, threshold: float, lam2: float, *,
            categorical: frozenset) -> float:
    """Gain of splitting ``rows`` on ``col``: its category ``threshold``
    against the rest, or ``<= threshold``, by the column's kind."""
    if col not in categorical:
        return less_equal_gain(X, rows, g, h, col, threshold, lam2)
    left = category_of(X[rows, col]) == int(threshold)
    gr, hr = g[rows].astype(np.float64), h[rows].astype(np.float64)
    return gbdt_replay.split_gain((gr[left].sum(), hr[left].sum()),
                                  (gr[~left].sum(), hr[~left].sum()), lam2)


def kept_off(X, bounds, categorical, keep: int, sample_rows: int) -> int:
    """How much of the program's kept lists its sample cannot explain,
    counted on the raw matrix.  The program keeps, of each categorical
    column, the ``keep`` categories most frequent in ``sample_rows``
    rows it draws itself (all a column has, where it has fewer), and the
    search above tries those alone: a list that is short, or holds the
    wrong categories, would hide splits from both sides.  So, with every
    category's count over the whole column: a kept entry that is no
    value of the column or is listed twice counts; a list longer than
    ``keep`` or outside what a sample of that size meets (the expected
    number of distinct categories, six deviations and one either way)
    counts as one; and so does every category left out whose count
    stands above a kept one's by more than six deviations of their
    difference in such a sample.  0 says the lists are the sample's."""
    n = X.shape[0]
    m = min(int(sample_rows), n)
    off = 0
    for col, kept in bounds:
        if col not in categorical:
            continue
        kept = category_of(np.asarray(kept))
        values, counts = np.unique(category_of(X[:, col]),
                                   return_counts=True)
        at = np.minimum(np.searchsorted(values, kept), len(values) - 1)
        known = values[at] == kept
        off += int((~known).sum()) + len(kept) - len(np.unique(kept))
        # a category of c rows misses a sample of m of the n rows with
        # probability C(n - c, m) / C(n, m)
        log_miss = (gammaln(n - counts + 1.0) + gammaln(n - m + 1.0)
                    - gammaln(n + 1.0)
                    - gammaln(np.maximum(n - m - counts, 0) + 1.0))
        miss = np.where(counts <= n - m, np.exp(log_miss), 0.0)
        met = float((1.0 - miss).sum())
        slack = 6.0 * float(np.sqrt((miss * (1.0 - miss)).sum())) + 1.0
        if not (min(np.floor(met - slack), keep) <= len(kept)
                <= min(np.ceil(met + slack), keep)):
            off += 1
        is_kept = np.zeros(len(values), bool)
        is_kept[at[known]] = True
        share = counts / float(n)
        out, held = share[~is_kept], share[is_kept]
        if len(out) and len(held):
            ahead = m * (out[:, None] - held[None, :])
            spread = np.sqrt(m * (out[:, None] + held[None, :])
                             * (n - m) / max(n - 1, 1))
            off += int((ahead > 6.0 * spread).any(axis=1).sum())
    return off


@contextlib.contextmanager
def rebound(module, **names):
    """Give ``module`` other functions under ``names`` for the length of
    a call."""
    kept = {k: getattr(module, k) for k in names}
    for k, f in names.items():
        setattr(module, k, f)
    try:
        yield
    finally:
        for k, f in kept.items():
            setattr(module, k, f)


def bound(categorical_columns):
    """``gbdt_replay`` with this module's routing, search and gain for a
    table whose ``categorical_columns`` (indices in the raw matrix) are
    categorical."""
    cats = frozenset(int(c) for c in categorical_columns)
    return rebound(
        gbdt_replay, route=route,
        best_split=functools.partial(best_split, categorical=cats),
        gain_of=functools.partial(gain_of, categorical=cats))
