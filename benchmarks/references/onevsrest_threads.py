"""``onevsrest_replay`` on threads, for a table four chips hold.

The same plain reference, routing and search, on the raw matrix: a row goes
left at a categorical node iff its category IS the node's, at a numerical
one iff ``x <= bound``; a search tries every kept category and every bin
bound.  Only the order of the work differs: rows are routed a block at a
time on a pool of threads and the blocks' node rows laid end to end (each
block keeps its rows in order, so every node's rows are the ones the
one-thread route gives, in the same order), a search's categorical
columns run one a thread beside the numerical columns' pool, and the kept
lists are held to the raw matrix a column a thread.  numpy lets go
of the GIL in the indexing, sorts and sums that do the work.  At 2**26 rows
the one-thread replay follows three trees in minutes; on the 30 cores of a
four-chip host this is several times as fast.
"""

from __future__ import annotations

import contextlib
import functools
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import gbdt_replay, onevsrest_replay

BLOCK_ROWS = 1 << 19
# the one-thread function, whatever stands under its name later
one_thread_kept_off = onevsrest_replay.kept_off


def workers() -> int:
    return max(1, min(32, os.cpu_count() or 1))


def route(X: np.ndarray, tree: dict):
    """``onevsrest_replay.route``, a block of rows a task."""
    n = X.shape[0]
    starts = list(range(0, n, BLOCK_ROWS))
    if len(starts) < 2:
        return onevsrest_replay.route(X, tree)

    def block(lo):
        node_rows, leaf_of = onevsrest_replay.route(
            X[lo:lo + BLOCK_ROWS], tree)
        return {i: r + np.int32(lo) for i, r in node_rows.items()}, leaf_of

    with ThreadPoolExecutor(min(workers(), len(starts))) as pool:
        parts = list(pool.map(block, starts))
    node_rows = {i: np.concatenate([p[0][i] for p in parts])
                 for i in parts[0][0]}
    return node_rows, np.concatenate([p[1] for p in parts])


def best_split(X, rows, g, h, bounds, p, runner_up: bool = False, *,
               categorical: frozenset):
    """``onevsrest_replay.best_split`` with the categorical columns on a
    pool of threads: the same candidates, the same ranking."""
    numerical = [e for e in bounds if e[0] not in categorical]
    cats = [(col, kept) for col, kept in bounds if col in categorical]
    with ThreadPoolExecutor(max(1, min(workers(), len(cats)))) as pool:
        ranked = list(pool.map(
            lambda e: onevsrest_replay.one_vs_rest(X, rows, g, h, *e, p),
            cats))
    if numerical:
        ranked.append(onevsrest_replay.bound_search(
            X, rows, g, h, numerical, p))
    if runner_up and len(numerical) > 1:
        ranked.append(onevsrest_replay.bound_search(
            X, rows, g, h, numerical, p, runner_up=True))
    ranked.sort(key=lambda t: -t[0])
    return ranked[1] if runner_up else ranked[0]


def bound(categorical_columns):
    """``onevsrest_replay.bound`` with this module's route and search."""
    cats = frozenset(int(c) for c in categorical_columns)
    return onevsrest_replay.rebound(
        gbdt_replay, route=route,
        best_split=functools.partial(best_split, categorical=cats),
        gain_of=functools.partial(onevsrest_replay.gain_of, categorical=cats))


def kept_off(X, bounds, categorical, keep: int, sample_rows: int) -> int:
    """``onevsrest_replay.kept_off``, a column a thread: it is a sum over
    the columns."""
    with ThreadPoolExecutor(max(1, min(workers(), len(bounds)))) as pool:
        return sum(pool.map(lambda e: one_thread_kept_off(
            X, [e], categorical, keep, sample_rows), bounds))


@contextlib.contextmanager
def in_place_of_one_thread():
    """Every ``onevsrest_replay.bound`` and ``kept_off`` of a call gives
    this module's."""
    with onevsrest_replay.rebound(onevsrest_replay, bound=bound,
                                  kept_off=kept_off):
        yield
