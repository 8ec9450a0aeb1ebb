"""What the standalone histogram requires: the root of every tree."""

from __future__ import annotations

from . import gbdt_tree


def required(trees: list, features: int) -> dict:
    rows = sum(int(ic[0]) for ic, _, _, _ in trees if len(ic))
    return gbdt_tree.of_rows(rows, features)
