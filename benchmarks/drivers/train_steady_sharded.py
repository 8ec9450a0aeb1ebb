"""Steady training on a table that the chips of one host share by rows.

The traffic, the set-up, the window and every reading are
``train_steady_categorical``'s, by import, and so is the plain reference:
the one-vs-rest replay over the WHOLE table, unsharded (data-parallel
trees are the serial trees up to float32 summation order, so they are held
to what one device holding every row would grow).  At four chips' rows
that replay runs on threads (``references/onevsrest_threads.py``: the same
routing and search, a block of rows or a column a thread), given to every
``onevsrest_replay.bound`` of the call.  The readings add the program's
``dp.*`` counters, once a booster: the shards, the rows of the fullest,
the bytes one split sums over the chips, and its collectives; a program
without them leaves them out.
"""

from __future__ import annotations

import contextlib

from drivers import train_steady_categorical
from references import onevsrest_threads

setup = train_steady_categorical.setup
first_trees = train_steady_categorical.first_trees

# reading -> the program's counter
COUNTERS = {"dp_shards": "dp.shards", "rows_per_shard": "dp.rows_per_shard",
            "exchange_bytes_per_split": "dp.exchange_bytes_per_split",
            "collectives_per_split": "dp.collectives_per_split"}


@contextlib.contextmanager
def bound(*first):
    """``train_steady_categorical.bound`` with the replay on threads."""
    with onevsrest_threads.in_place_of_one_thread(), \
            train_steady_categorical.bound(*first):
        yield


def reference(run, state: dict, forced: bool = True,
              keep_rows: bool = False) -> dict:
    with onevsrest_threads.in_place_of_one_thread():
        return train_steady_categorical.reference(run, state, forced,
                                                  keep_rows)


def compared(run, state: dict, ref: dict, precision: str = "float32",
             fault: str | None = None, forced: bool = True,
             detail: list | None = None) -> dict:
    with onevsrest_threads.in_place_of_one_thread():
        return train_steady_categorical.compared(
            run, state, ref, precision, fault, forced, detail)


def counters() -> dict:
    """The program's ``dp.*`` counters, where it keeps them."""
    from lightgbm_tpu.obs import telemetry

    kept = telemetry.get_telemetry().snapshot(
        include_compiles=False)["counters"]
    return {reading: kept.get(name) for reading, name in COUNTERS.items()}


def run(run) -> dict:
    with onevsrest_threads.in_place_of_one_thread():
        out = train_steady_categorical.run(run)
    out["readings"].update(counters())
    return out
