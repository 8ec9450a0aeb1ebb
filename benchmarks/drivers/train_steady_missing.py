"""Steady training on a table with missing values.

The traffic, the set-up, the window and every reading are
``train_steady``'s, by import.  What differs:

* before anything is timed, the program must have given a missing bin to
  every column the table leaves with gaps; a program that bins NaN as
  0.0 grows other trees on another table, cannot run this configuration,
  and exits here (``build_dataset``);
* the plain reference the first trees are held to is
  ``references/missing_replay.py``: a NaN row follows its node's default
  direction, and a search tries both sides for a column's missing rows.
  The trees are read with their ``decision_type`` (program_categorical.py
  reads it for any table), and the program's answers keep each node's
  side beside its threshold;
* two faults more for tools/limits.py: ``altered_default``, one node of
  each tree sending its missing rows the other way, and ``withheld_left``,
  a search that offers no missing-left candidate;
* one number more, ``resolved_node_argmax_gap``: ``node_argmax_gap`` over
  the sampled nodes whose best gain float32 can tell from the others';
* three readings of what the mechanism did to the window's trees.

``train_steady.run`` finds ``first_trees``, ``reference`` and ``compared``
by name when it runs; ``run`` here gives it this module's for the length
of the call.
"""

from __future__ import annotations

import contextlib
import sys

import numpy as np

import check
import program
import program_categorical
from drivers import train_steady
from references import missing_replay, onevsrest_replay

setup = train_steady.setup
# train_steady's own, whatever stands under their names later (``bound``)
plain_first_trees, plain_build_dataset = (
    train_steady.first_trees, program.build_dataset)
followed, numbers_of = train_steady.reference, train_steady.compared
plain_side = check.side_of_program
FAULTS = (("fault", "float32", "altered_default"),
          ("fault", "float32", "withheld_left"))
# missing type NaN as lightgbm_tpu/io/binner.py numbers a column's
BINNER_MISSING_NAN = 2
BLOCK_ROWS = 1 << 16
# a best gain under this share of its node's own G^2 / (H + lambda_l2) is
# below what float32 gains resolve: 16 units in the last place of that
# term, which every candidate's float32 score carries
RESOLVE = 2.0 ** -19


def missing_bin_columns(ds) -> list:
    """The columns of the raw matrix that the program gave a missing bin
    (none, for a program that has no such bin)."""
    inner = ds.construct()
    real = np.asarray(inner.real_feature_indices)
    return sorted(int(real[i]) for i, m in enumerate(inner.bin_mappers)
                  if getattr(m, "missing_type", 0) == BINNER_MISSING_NAN)


def gap_columns(X: np.ndarray) -> list:
    """The columns of the raw matrix that hold a NaN."""
    gaps = np.zeros(X.shape[1], bool)
    for lo in range(0, X.shape[0], BLOCK_ROWS):
        gaps |= np.isnan(X[lo:lo + BLOCK_ROWS]).any(axis=0)
    return np.flatnonzero(gaps).tolist()


def build_dataset(config: dict, data: dict):
    """``program.build_dataset``, once the program is seen to have given a
    missing bin to every column with a NaN in the raw matrix."""
    ds = plain_build_dataset(config, data)
    gaps, found = gap_columns(data["X"]), missing_bin_columns(ds)
    if found != gaps:
        sys.exit(f"benchmark: {len(gaps)} columns of the table have "
                 f"missing values and the program gave {len(found)} a "
                 "missing bin; nothing was run")
    return ds


def first_trees(run, s: dict) -> dict:
    """``train_steady.first_trees`` with every split's ``decision_type``."""
    k = int(run.traffic["checked_trees"])
    return {**plain_first_trees(run, s),
            "trees": program_categorical.trees(s["booster"], 0, k)}


def binned(state: dict) -> list:
    """The raw matrix binned by the program's bounds, once a state."""
    if "binned" not in state:
        state["binned"] = missing_replay.binned(state["data"]["X"],
                                                state["bounds"])
    return state["binned"]


def reference(run, state: dict, forced: bool = True,
              keep_rows: bool = False) -> dict:
    with missing_replay.bound(binned(state)):
        return followed(run, state, forced, keep_rows)


def resolved_node_argmax_gap(ref: dict, detail: list) -> tuple:
    """``node_argmax_gap`` over the sampled nodes below a root whose best
    gain is at least ``RESOLVE`` of G^2 / (H + lambda_l2) of the node's
    rows.  Below that every candidate's float32 score is the same number
    to within its rounding: at the rehearsal's size, where its 35
    failures are soon split off, sampled nodes read 1e-8 to 7e-8 of
    their term and the program's choice there up to 0.99 under the best,
    while every node a split can help reads 1e-4 or more (PERF.md).
    Returns it and how many sampled nodes it left out."""
    lam2 = ref["lambda_l2"]
    worst, left_out = 0.0, 0
    for d in detail:
        if not d["node"]:
            continue
        tree = ref["trees"][d["tree"]]
        rows = tree["node_rows"][d["node"]]
        g = tree["grad"][rows].sum(dtype=np.float64)
        h = tree["hess"][rows].sum(dtype=np.float64)
        if d["best_gain"] >= RESOLVE * g * g / (h + lam2):
            worst = max(worst, (d["best_gain"] - d["chosen"])
                        / d["best_gain"])
        else:
            left_out += 1
    return worst, left_out


def compared(run, state: dict, ref: dict, precision: str = "float32",
             fault: str | None = None, forced: bool = True,
             detail: list | None = None) -> dict:
    """``train_steady.compared`` against the missing-value reference, and
    ``resolved_node_argmax_gap`` with the count of the nodes it left out
    (``unresolved_nodes``, which no limit reads); the program's answers
    carry each node's side for its missing rows, and ``altered_default``
    and ``withheld_left`` are planted in the replay that stands in the
    program's place."""
    def side(trees, snapshots, loss_of):
        return missing_replay.chosen_with_sides(
            plain_side(trees, snapshots, loss_of), trees)

    detail = [] if detail is None else detail
    start = len(detail)
    with missing_replay.bound(
            binned(state), altered=fault == "altered_default",
            withheld=fault == "withheld_left"), \
            onevsrest_replay.rebound(check, side_of_program=side):
        numbers = numbers_of(run, state, ref, precision, fault, forced,
                             detail)
    gap, left_out = resolved_node_argmax_gap(ref, detail[start:])
    return {**numbers, "resolved_node_argmax_gap": gap,
            "unresolved_nodes": float(left_out)}


@contextlib.contextmanager
def bound(first=first_trees):
    """``train_steady`` (and what reads a cell through it:
    tools/limits.py, whose sides gain this module's ``FAULTS``) with this
    module's reference, and its set-up with the look at what the program
    binned."""
    from tools import limits

    with onevsrest_replay.rebound(
            train_steady, first_trees=first, reference=reference,
            compared=compared), onevsrest_replay.rebound(
            program, build_dataset=build_dataset), onevsrest_replay.rebound(
            limits, SIDES=limits.SIDES + FAULTS):
        yield


def run(run) -> dict:
    kinds = []

    def first_trees_and_kinds(run, s: dict) -> dict:
        # the window's trees are the program's until it is freed
        kinds.extend(t["decision_type"] for t in program_categorical.trees(
            s["booster"], s["warmup_trees"]))
        return first_trees(run, s)

    with bound(first_trees_and_kinds):
        out = train_steady.run(run)
    dt = (np.concatenate(kinds).astype(np.int64) if kinds
          else np.zeros(0, np.int64))
    on_missing = (dt & missing_replay.MISSING_MASK
                  == missing_replay.MISSING_NAN)
    moved = [ic for ic, _, _, _ in out["readings"]["tree_counts"] if len(ic)]
    out["readings"].update(
        missing_split_share=(
            100.0 * float(np.mean(on_missing)) if len(dt) else None),
        default_left_share=(
            100.0 * float(np.mean(
                dt[on_missing] & missing_replay.DEFAULT_LEFT > 0))
            if on_missing.any() else None),
        # how many times a tree moves a row: every split moves its
        # parent's rows once
        moved_rows_per_tree=(
            float(sum(ic.sum() / ic[0] for ic in moved)) / len(moved)
            if moved else None))
    return out
