"""One chip's share of ``split_step`` where four chips share the rows
(``chip_share``)."""

from __future__ import annotations

from . import chip_share, split_step


def required(trees: list, features: int) -> dict:
    return chip_share.share(split_step.required(trees, features))
