"""One chip's share of what a tree requires, where ``CHIPS`` chips share
the rows evenly: every chip histograms and partitions its own rows, so each
does a ``CHIPS``-th of the tree's required work (``gbdt_tree``,
``root_histogram``, ``split_step``).  The count modules of a four-chip cell
are named ``<count>_of4``."""

from __future__ import annotations

CHIPS = 4


def share(work: dict, chips: int = CHIPS) -> dict:
    return {k: v / chips for k, v in work.items()}
