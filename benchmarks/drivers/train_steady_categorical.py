"""Steady training on a table with categorical columns.

The traffic, the set-up, the window and every reading are
``train_steady``'s, by import.  What differs is the plain reference the
program's first trees are held to: ``references/onevsrest_replay.py``
routes a categorical node by equality and searches every kept category
beside every bin bound, and the trees are read with their
``decision_type`` and the columns' kept categories
(``program_categorical.py``).  ``train_steady.run`` finds ``first_trees``,
``reference`` and ``compared`` by name when it runs; ``run`` here gives it
this module's for the length of the call, and adds two readings of what
the mechanism did to the window's trees.  The kept categories are the
program's, as bin bounds are, so ``compared`` holds them to the raw
matrix's counts beside the trees (``kept_categories_off``).
"""

from __future__ import annotations

import contextlib
import sys

import numpy as np

import program
import program_categorical
from drivers import train_steady
from references import onevsrest_replay

setup = train_steady.setup
# train_steady's own, whatever stands under their names later (``bound``)
plain_first_trees, plain_build_booster = (
    train_steady.first_trees, program.build_booster)
followed, numbers_of = train_steady.reference, train_steady.compared


def categorical_columns(run) -> list:
    return run.config["categorical"]["columns"]


def build_booster(config: dict, ds):
    """``program.build_booster``, once the program is seen to have binned
    as categorical exactly the columns the configuration declares.  A
    program that ignores the declaration grows other trees on another
    table: it cannot run this configuration, and is not timed as it."""
    declared = sorted(config["categorical"]["columns"])
    found = program_categorical.categorical_columns(ds)
    if found != declared:
        sys.exit(f"benchmark: the configuration declares columns {declared} "
                 f"categorical and the program binned {found} so; nothing "
                 "was run")
    return plain_build_booster(config, ds)


def first_trees(run, s: dict) -> dict:
    """``train_steady.first_trees`` with the kinds of the splits and the
    kept categories of the categorical columns."""
    k = int(run.traffic["checked_trees"])
    return {**plain_first_trees(run, s),
            "trees": program_categorical.trees(s["booster"], 0, k),
            "bounds": program_categorical.bounds(s["ds"])}


def reference(run, state: dict, forced: bool = True,
              keep_rows: bool = False) -> dict:
    with onevsrest_replay.bound(categorical_columns(run)):
        return followed(run, state, forced, keep_rows)


def compared(run, state: dict, ref: dict, precision: str = "float32",
             fault: str | None = None, forced: bool = True,
             detail: list | None = None) -> dict:
    """``train_steady.compared`` against the one-vs-rest reference, and
    one number more that no side's arithmetic moves: what the raw matrix
    says of the kept lists the search was given (counted once a state)."""
    if "kept_categories_off" not in state:
        kinds = run.config["categorical"]
        state["kept_categories_off"] = onevsrest_replay.kept_off(
            state["data"]["X"], state["bounds"],
            frozenset(kinds["columns"]),
            int(run.config["params"]["max_bin"]) - 1,
            int(kinds["bin_sample_rows"]))
    with onevsrest_replay.bound(categorical_columns(run)):
        numbers = numbers_of(
            run, state, ref, precision, fault, forced, detail)
    return {**numbers, "kept_categories_off": state["kept_categories_off"]}


@contextlib.contextmanager
def bound(first=first_trees):
    """``train_steady`` (and what reads a cell through it:
    tools/limits.py) with this module's reference, and its set-up with
    the look at what the program binned."""
    with onevsrest_replay.rebound(
            train_steady, first_trees=first, reference=reference,
            compared=compared), onevsrest_replay.rebound(
            program, build_booster=build_booster):
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
    splits = np.concatenate(kinds) if kinds else np.zeros(0)
    moved = [ic for ic, _, _, _ in out["readings"]["tree_counts"] if len(ic)]
    out["readings"].update(
        categorical_split_share=(
            100.0 * float(np.mean(splits == 1)) if len(splits) else None),
        # how many times a tree moves a row: every split moves its
        # parent's rows once
        moved_rows_per_tree=(
            float(sum(ic.sum() / ic[0] for ic in moved)) / len(moved)
            if moved else None))
    return out
