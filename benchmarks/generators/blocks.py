"""Seeded float32 matrices made in row blocks on a few threads.

The block count is fixed, so the matrix depends on the seed alone and not
on how many threads ran.  numpy releases the interpreter lock inside the
generator, so the threads overlap.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCKS = 64
THREADS = 12


def normal_matrix(rows: int, cols: int, seed: int, transform=None,
                  per_row=None):
    """[rows, cols] float32 standard normals; ``transform(block, rng)``
    then edits each row block in place, and ``per_row(block)`` gives one
    float32 a row (a label score), worked out on the same threads."""
    out = np.empty((rows, cols), np.float32)
    extra = np.empty(rows, np.float32) if per_row is not None else None
    seeds = np.random.SeedSequence(int(seed)).spawn(BLOCKS)
    edges = np.linspace(0, rows, BLOCKS + 1).astype(np.int64)

    def fill(b: int) -> None:
        rng = np.random.default_rng(seeds[b])
        block = out[edges[b]:edges[b + 1]]
        rng.standard_normal(block.shape, dtype=np.float32, out=block)
        if transform is not None:
            transform(block, rng)
        if per_row is not None:
            extra[edges[b]:edges[b + 1]] = per_row(block)

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(BLOCKS)))
    return (out, extra) if per_row is not None else out


class LabelScore:
    """A fixed nonlinear score of a few columns, from the configuration's
    ``label_seed``: ``z w + a z0 z1 + b f(z2)`` with every column scaled
    by its kind's nominal spread, so that no pass over the whole matrix
    is needed."""

    def __init__(self, fixed, cols: int, k: int, kinds: dict,
                 cards: np.ndarray, pair: float, bend: float, square: bool):
        self.cols = np.sort(fixed.choice(cols, size=k, replace=False))
        self.w = fixed.standard_normal(k).astype(np.float32)
        spread = np.ones(cols, np.float32)
        a, b = kinds.get("count", (0, 0))
        spread[a:b] = cards[a:b] * np.float32(0.4 * 0.6)
        a, b = kinds.get("sparse", (0, 0))
        spread[a:b] = np.float32(0.4)
        self.scale = (np.float32(1) / spread[self.cols]).astype(np.float32)
        self.pair, self.bend = np.float32(pair), np.float32(bend)
        self.square = square

    def __call__(self, block: np.ndarray) -> np.ndarray:
        z = block[:, self.cols] * self.scale
        bent = z[:, 2] ** 2 - 1 if self.square else np.abs(z[:, 2])
        return z @ self.w + self.pair * z[:, 0] * z[:, 1] + self.bend * bent


def to_grid(a: np.ndarray, step_log2: int = 10) -> None:
    """Round in place to multiples of 2**-step_log2: feature logs keep a
    few digits, and every midpoint between two grid values is exact in
    float32 and float64 alike, so ``x <= threshold`` means one thing."""
    scale = np.float32(2.0 ** step_log2)
    np.multiply(a, scale, out=a)
    np.rint(a, out=a)
    np.multiply(a, np.float32(1.0) / scale, out=a)


def column_kinds(cols: int, shares: dict) -> dict:
    """``{kind: (first, last)}``: each kind's columns lie side by side, so
    that a block's columns of one kind are a view and are shaped in
    place.  The shares are the configuration's."""
    names = list(shares)
    counts = [int(round(shares[k] * cols)) for k in names]
    counts[0] += cols - sum(counts)
    edges = np.concatenate([[0], np.cumsum(counts)])
    return {k: (int(edges[i]), int(edges[i + 1]))
            for i, k in enumerate(names)}


def shape_columns(block: np.ndarray, kinds: dict, cards: np.ndarray):
    """Give each column its marginal, in place, from its normal draw."""
    for kind, (a, b) in kinds.items():
        sub = block[:, a:b]
        if a == b:
            continue
        if kind == "continuous":
            to_grid(sub)
        elif kind == "count":  # skewed small integers, 0 .. card-1
            np.abs(sub, out=sub)
            np.multiply(sub, cards[a:b] * np.float32(0.4), out=sub)
            np.floor(sub, out=sub)
            np.minimum(sub, cards[a:b] - 1, out=sub)
        elif kind == "sparse":  # zero for about four rows in five
            np.subtract(sub, np.float32(0.84), out=sub)
            np.maximum(sub, 0, out=sub)
            to_grid(sub)
        else:
            raise ValueError(f"unknown column kind {kind!r}")
