"""What the fused split step requires for one tree's splits: partition the
parent's rows (read and write each row's bins, gradient, hessian and row
id once) and accumulate the smaller child's histogram."""

from __future__ import annotations

from . import gbdt_tree, tree_shape

ROW_ID_BYTES = 4


def required(trees: list, features: int) -> dict:
    flops = bytes_ = 0.0
    for ic, lc, left, right in trees:
        if len(ic) == 0:
            continue
        parents, smaller = tree_shape.splits(ic, lc, left, right)
        row_bytes = (features * gbdt_tree.BIN_BYTES
                     + gbdt_tree.GRADIENT_BYTES + ROW_ID_BYTES)
        bytes_ += 2.0 * float(parents.sum()) * row_bytes
        child = gbdt_tree.of_rows(int(smaller.sum()), features)
        flops += child["flops"]
        bytes_ += child["bytes"]
    return {"flops": flops, "bytes": bytes_}
