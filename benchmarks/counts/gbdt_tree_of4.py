"""One chip's share of ``gbdt_tree`` where four chips share the rows
(``chip_share``)."""

from __future__ import annotations

from . import chip_share, gbdt_tree


def required(trees: list, features: int) -> dict:
    return chip_share.share(gbdt_tree.required(trees, features))
