"""A tree's root totals (``ops/totals.py``): accurate, and the same bits
whatever masked-out rows ride along.

Every leaf's sums descend from these two numbers, so the one-segment
``segment_sum`` they used to be taken with (a float32 accumulation row
by row) made the first leaf wrong by tens of percent at nine million
rows (PERF.md, PR 25/28).  The gradients are the ones
``benchmarks/tools/root_totals.py`` reads that construct on: binary
log-loss like a cell's first tree (|g| = h = 1, every sum exact) and
like its second (hessians about 0.9987).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.totals import root_totals

ROWS = (60_000, 1_000_000, 8_921_483)
TOL = 1e-6


def gradients(n: int, kind: str, seed: int = 1):
    rng = np.random.default_rng([n, seed])
    sign = np.where(rng.random(n) < 0.5, 1, -1).astype(np.float32)
    s = np.zeros(n, np.float32) if kind == "tree0" else (
        -0.03 * sign + 0.02 * rng.standard_normal(n)).astype(np.float32)
    r = (-2 * sign / (1 + np.exp(2 * sign * s))).astype(np.float32)
    a = np.abs(r)
    return r, (a * (2 - a)).astype(np.float32)


def errors(out, g, h):
    """(Σg's error over Σ|g|, Σh's relative error), against float64."""
    G, H = g.sum(dtype=np.float64), h.sum(dtype=np.float64)
    return (abs(float(out[0]) - G) / np.abs(g).sum(dtype=np.float64),
            abs(float(out[1]) - H) / H)


@pytest.mark.parametrize("kind", ["tree0", "tree1"])
@pytest.mark.parametrize("n", ROWS)
def test_accurate_against_float64(n, kind):
    g, h = gradients(n, kind)
    out = jax.jit(root_totals)(
        jnp.asarray(g), jnp.asarray(h), jnp.ones(n, jnp.float32))
    eg, eh = errors(out, g, h)
    assert eg <= TOL and eh <= TOL, (eg, eh)
    # more than the tolerance asks: each is the float64 sum rounded once
    assert np.float32(g.sum(dtype=np.float64)) == np.asarray(out[0])
    assert np.float32(h.sum(dtype=np.float64)) == np.asarray(out[1])


@pytest.mark.parametrize("n", ROWS[1:])
def test_the_old_construct_fails_the_same_comparison(n):
    """The comparison has teeth: the one-segment segment_sum this
    replaces reads the second tree's Σh over a thousandth high."""
    g, h = gradients(n, "tree1")
    old = jax.ops.segment_sum(
        jnp.stack([jnp.asarray(g), jnp.asarray(h)], axis=-1),
        jnp.zeros(n, jnp.int32), num_segments=1)[0]
    assert errors(old, g, h)[1] > 100 * TOL


@pytest.mark.parametrize("kind", ["tree0", "tree1"])
@pytest.mark.parametrize("n", ROWS[:2])
def test_same_bits_with_masked_rows_at_seeded_positions(n, kind):
    """The fixed-order contract of learners/serial.py's root: the same
    live rows, with masked-out rows of any value inserted at seeded
    positions (a base-row mask, a forest lane's padding), or in another
    order altogether, give the same bits."""
    g, h = gradients(n, kind)
    alone = root_totals(jnp.asarray(g), jnp.asarray(h),
                        jnp.ones(n, jnp.float32))
    rng = np.random.default_rng([n, 7])
    for extra in (1, n // 3, 2 * n):
        at = np.sort(rng.choice(n + extra, size=n, replace=False))
        g2 = (100 * rng.standard_normal(n + extra)).astype(np.float32)
        h2 = np.abs(g2)
        m2 = np.zeros(n + extra, np.float32)
        g2[at], h2[at], m2[at] = g, h, 1
        mixed = root_totals(jnp.asarray(g2), jnp.asarray(h2),
                            jnp.asarray(m2))
        for a, b in zip(alone, mixed):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    order = rng.permutation(n)
    moved = root_totals(jnp.asarray(g[order]), jnp.asarray(h[order]),
                        jnp.ones(n, jnp.float32))
    for a, b in zip(alone, moved):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_lanes_and_edge_values():
    """A stack of lanes sums lane by lane (learners/forest.py); all-zero
    and all-masked inputs give exact zeros; a heavy tail does not cost
    the small values their digits."""
    rng = np.random.default_rng(3)
    g = rng.standard_normal((3, 5000)).astype(np.float32)
    g[1] *= 1e-20
    g[2, 17] = 3e7  # one value a hundred million times the rest
    h = np.abs(g)
    m = (rng.random((3, 5000)) < 0.7).astype(np.float32)
    sg, sh = root_totals(jnp.asarray(g), jnp.asarray(h), jnp.asarray(m))
    for lane in range(3):
        one = root_totals(jnp.asarray(g[lane]), jnp.asarray(h[lane]),
                          jnp.asarray(m[lane]))
        assert np.asarray(one[0]) == np.asarray(sg[lane])
        assert np.asarray(one[1]) == np.asarray(sh[lane])
        want = (g[lane] * m[lane]).sum(dtype=np.float64)
        # what lies under the last digit is dropped: 2^-33 of the power
        # of two above the largest magnitude, a row
        grid = 5000 * 2.0 ** -33 * 2 * np.abs(g[lane] * m[lane]).max()
        assert abs(float(sg[lane]) - want) <= grid + abs(want) * 2 ** -23
    zero = root_totals(jnp.zeros(100), jnp.zeros(100), jnp.zeros(100))
    assert float(zero[0]) == 0.0 and float(zero[1]) == 0.0
