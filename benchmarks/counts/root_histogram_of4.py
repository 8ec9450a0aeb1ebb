"""One chip's share of ``root_histogram`` where four chips share the rows
(``chip_share``)."""

from __future__ import annotations

from . import chip_share, root_histogram


def required(trees: list, features: int) -> dict:
    return chip_share.share(root_histogram.required(trees, features))
