"""The construct the program takes a tree's root totals with, alone.

    python benchmarks/tools/root_totals.py      # on the machine's devices

``lightgbm_tpu/learners/serial.py`` (line 586 at PR 25) sums the root's
gradients and hessians by a one-segment ``jax.ops.segment_sum`` over all
rows.  This sets that call beside ``jnp.sum`` and float64 on binary
log-loss gradients like a cell's first tree (|g| = h = 1: exact) and its
second (hessians about 0.9987), at several row counts.  PERF.md, PR 25,
reads the cause of leaf 0's fault from it; once the program is mended the
first error column has to read like the second.
"""
import jax
import jax.numpy as jnp
import numpy as np

print(jax.devices())

@jax.jit
def seg(g, h):
    return jax.ops.segment_sum(jnp.stack([g, h], axis=-1),
                               jnp.zeros(g.shape[0], jnp.int32),
                               num_segments=1)[0]

@jax.jit
def plain(g, h):
    return jnp.sum(jnp.stack([g, h], axis=-1), axis=0)

for n in (60_000, 1_000_000, 2_200_000, 8_921_483, 10_500_000):
    for kind in ("tree0", "tree1"):
        for seed in (1, 2, 3):
            rng = np.random.default_rng([n, seed])
            sign = np.where(rng.random(n) < 0.5, 1, -1).astype(np.float32)
            s = np.zeros(n, np.float32) if kind == "tree0" else (
                -0.03 * sign + 0.02 * rng.standard_normal(n)).astype(np.float32)
            r = (-2 * sign / (1 + np.exp(2 * sign * s))).astype(np.float32)
            a = np.abs(r)
            g, h = r, (a * (2 - a)).astype(np.float32)
            exact = np.array([g.sum(dtype=np.float64), h.sum(dtype=np.float64)])
            A = np.abs(g).sum(dtype=np.float64)
            x = np.asarray(seg(jnp.asarray(g), jnp.asarray(h)), np.float64)
            y = np.asarray(plain(jnp.asarray(g), jnp.asarray(h)), np.float64)
            print(f"n={n} {kind} seed={seed} float64 G={exact[0]:.3f} H={exact[1]:.3f} "
                  f"sum|g|={A:.0f} | segment_sum err G={x[0]-exact[0]:+.3f} "
                  f"H={x[1]-exact[1]:+.3f} | jnp.sum err G={y[0]-exact[0]:+.3f} "
                  f"H={y[1]-exact[1]:+.3f}", flush=True)
