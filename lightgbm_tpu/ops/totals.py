"""A tree's root totals: Σg and Σh over the bagged rows.

The root's search reads its gain against them, a categorical split takes
``total - bin`` from them, and until PR 28 every left child's sums were
``total - right`` (``ops/split.py``), so what they were wrong by the
first leaf's chain inherited whole.  A float32 accumulation row by row
reads a varying hessian 0.13% high at nine million rows (PERF.md, PR
25/28).

The sum here is *exact up to a fixed grid*, which makes it both accurate
and independent of order.  Each value is cut into three signed integer
digits (five for float64) of ``DIGIT_BITS`` bits below the power of two
above the largest magnitude; what lies under the last digit (2^-33 of
that power for float32, 2^-55 for float64) is dropped, the same for a
value wherever it stands.  Digits are summed in int32, blocks first and the blocks'
sums in two halves, so no partial sum rounds or overflows, and the few
integers left are put together in a two-float accumulator.  The result
is therefore a function of the *set* of live values: rows that carry an
exact 0.0 (bagged out, held out by a base-row mask, a lane's padding)
change nothing at any position, and neither does the order of the rows.
``tests/test_root_totals.py`` holds both: accuracy against float64 and
the same bits with dead rows riding along.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..obs.device_time import phase_scope

DIGIT_BITS = 11  # |digit| <= 2^11
BLOCK = 1 << 12  # rows a block: |block sum| <= 2^23
HALF_BITS = 12  # a block sum is split at this bit for the second sum


def _pow2_above(m: jax.Array) -> jax.Array:
    """The power of two above ``m`` (float, finite, >= 0), at least
    2^-60: digits below that would leave the float32 range."""
    _, e = jnp.frexp(jnp.maximum(m, jnp.asarray(2.0 ** -61, m.dtype)))
    return jnp.ldexp(jnp.ones_like(m), e)


def two_sum(a, b):
    """Knuth's two-sum: ``(s, e)`` with ``s = fl(a + b)`` and ``a + b ==
    s + e`` exactly.  Plain arithmetic, so it also runs inside the
    histogram kernels, whose accumulators fold with it."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def exact_sums(x: jax.Array, axis_name: str | None = None) -> jax.Array:
    """Sums over the last axis of ``x`` [..., n] in ``x``'s float dtype,
    exact up to the grid the module's docstring states.  With
    ``axis_name`` the rows are sharded over that mesh axis and the sum is
    over every chip's: the grid is the largest magnitude of all of them
    (``pmax``) and the integer digit sums are added across the chips
    (``psum``, exact in int32), so the result is the one a single device
    holding every row reads, bit for bit."""
    dt = x.dtype
    levels = 3 if dt == jnp.float32 else 5
    n = x.shape[-1]
    pad = -n % BLOCK
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    top = _pow2_above(jnp.max(jnp.abs(x), axis=-1, keepdims=True))
    if axis_name is not None:
        with phase_scope("grow.exchange"):
            top = jax.lax.pmax(top, axis_name)
    rest = x
    hi = jnp.zeros(x.shape[:-1], dt)
    lo = jnp.zeros(x.shape[:-1], dt)
    terms = []
    for level in range(levels):
        q = top * (2.0 ** -(DIGIT_BITS * (level + 1)))
        digit = jnp.round(rest / q)  # exact: q is a power of two
        rest = rest - digit * q  # exact: |rest| <= q / 2
        blocks = jnp.sum(
            digit.astype(jnp.int32).reshape(x.shape[:-1] + (-1, BLOCK)),
            axis=-1)
        upper = blocks >> HALF_BITS  # floor: upper * 2^12 + lower == blocks
        lower = blocks - (upper << HALF_BITS)
        for part, weight in ((lower, 1.0), (upper, float(1 << HALF_BITS))):
            s = jnp.sum(part, axis=-1)  # int32: fits for n < 2^31
            if axis_name is not None:
                with phase_scope("grow.exchange"):
                    s = jax.lax.psum(s, axis_name)
            # two exact float pieces of an int32
            top16 = s >> 16
            terms.append(((s - (top16 << 16)).astype(dt), q[..., 0] * weight))
            terms.append((top16.astype(dt), q[..., 0] * (weight * 65536.0)))
    # smallest weights first; every product is exact (a 16-bit integer
    # times a power of two)
    for v, w in reversed(terms):
        hi, err = two_sum(hi, v * w)
        lo = lo + err
    return hi + lo


def root_totals(grad: jax.Array, hess: jax.Array, mask: jax.Array,
                axis_name: str | None = None):
    """``(Σ grad·mask, Σ hess·mask)`` over the last axis (and over the
    chips of ``axis_name``: ``exact_sums``)."""
    with phase_scope("root_totals"):
        dt = jnp.promote_types(grad.dtype, jnp.float32)
        s = exact_sums(jnp.stack(
            [(grad * mask).astype(dt), (hess * mask).astype(dt)], axis=-2),
            axis_name)
        return s[..., 0], s[..., 1]
