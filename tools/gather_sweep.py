"""Gather/scatter strategy micro-sweep for the per-split hot path.

Round-3 TPU evidence (tools/kernel_ab.py + BENCH 1M): the leafwise tree
loop is bound by per-index gather/scatter overhead (~30 ns/element), not
by the histogram kernels (contiguous Pallas streams are ~10x faster per
row).  Per split the loop pays: partition feature-row gather (cap) +
order scatter (cap) + smaller-child bins/grad/hess takes (3 x cap_small)
~= 42M indexed elements per 1M-row 255-leaf tree ~= the whole measured
1.23 s/tree.  This sweep times the candidate replacements so the rewrite
chases measured wins, not guesses:

  A  col-take of [F, n] i8 bins (current hist gather)        baseline
  B  3 separate takes: bins cols + grad + hess               current total
  C  packed-record single take: [R, n] i32 (bins 4/word + g + h)
  D  packed-record ROW take: [n, R] i32 (+transpose)
  E  packed-record row take, 128B-padded rows [n, 32] i32
  F  sorted-index compaction take (indices ascending, both runs)
  G  order scatter (current partition write)  vs  H inverse-perm gather
  I  record-wide partition: scatter [R, cap] i32 columns in one op
  J  lax.sort stable partition of (key, order) — no descriptors
  K  lax.sort stable partition carrying the full [R] record
  L  block-compaction partition: per-512-tile MXU one-hot compaction +
     sequential dynamic_update_slice merge (no per-index descriptors;
     the pure-JAX prototype of the Pallas partition design)

Run on the chip:  python tools/gather_sweep.py [rows]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

ROWS = int(float(sys.argv[1])) if len(sys.argv) > 1 else 1_000_000
F = 28


def t(fn, reps=20):
    """Enqueue all reps asynchronously, block once: a per-rep
    block_until_ready pays the host round trip per rep and times the
    dispatch, not the op."""
    import jax

    out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def main():
    import jax
    import jax.numpy as jnp

    print("devices:", jax.devices(), flush=True)
    assert jax.devices()[0].platform == "tpu", "TPU op sweep: no chip found"

    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, 255, (F, ROWS)).astype(np.uint8))
    g = jnp.asarray(rng.randn(ROWS).astype(np.float32))
    h = jnp.asarray(np.abs(rng.randn(ROWS)).astype(np.float32))

    # packed record: ceil(F/4) words of 4 bins + g + h, column-major [R, n]
    words = (F + 3) // 4
    bins_np = np.asarray(bins)
    packed = np.zeros((words, ROWS), np.int32)
    for w in range(words):
        for b in range(4):
            f = w * 4 + b
            if f < F:
                packed[w] |= bins_np[f].astype(np.int32) << (8 * b)
    rec = jnp.asarray(
        np.concatenate(
            [packed,
             np.asarray(g)[None].view(np.int32),
             np.asarray(h)[None].view(np.int32)], axis=0))  # [R, n]
    R = rec.shape[0]
    rec_rm = jnp.asarray(np.ascontiguousarray(np.asarray(rec).T))  # [n, R]
    rec_pad = jnp.asarray(
        np.ascontiguousarray(
            np.pad(np.asarray(rec).T, ((0, 0), (0, 32 - R)))))  # [n, 32]

    for cap in (max(512, ROWS // 2 // 512 * 512),
                max(512, ROWS // 8 // 512 * 512),
                max(512, ROWS // 32 // 512 * 512)):
        idx = jnp.asarray(rng.randint(0, ROWS, cap).astype(np.int32))
        idx_sorted = jnp.sort(idx)

        res = {}
        res["A  col-take bins i8"] = t(jax.jit(
            lambda i=idx: jnp.take(bins, i, axis=1)))
        res["B  3 takes bins+g+h"] = t(jax.jit(
            lambda i=idx: (jnp.take(bins, i, axis=1), g[i], h[i])))
        res["C  packed col-take [R,n]"] = t(jax.jit(
            lambda i=idx: jnp.take(rec, i, axis=1)))
        res["D  packed row-take+T [n,R]"] = t(jax.jit(
            lambda i=idx: rec_rm[i].T))
        res["E  padded row-take [n,32]"] = t(jax.jit(
            lambda i=idx: rec_pad[i]))
        res["F  sorted col-take [R,n]"] = t(jax.jit(
            lambda i=idx_sorted: jnp.take(
                rec, i, axis=1, indices_are_sorted=True)))
        res["F' sorted row-take [n,32]"] = t(jax.jit(
            lambda i=idx_sorted: jnp.take(
                rec_pad, i, axis=0, indices_are_sorted=True)))

        # partition-shaped ops over a cap window
        order = jnp.asarray(rng.permutation(ROWS)[:cap].astype(np.int32))
        go = jnp.asarray(rng.rand(cap) < 0.45)
        nleft = jnp.sum(go, dtype=jnp.int32)
        lpos = jnp.cumsum(go.astype(jnp.int32)) - 1
        rpos = nleft + jnp.cumsum((~go).astype(jnp.int32)) - 1
        newpos = jnp.where(go, lpos, rpos)

        res["G  order scatter (cap)"] = t(jax.jit(
            lambda o=order, p=newpos: o.at[p].set(o, unique_indices=True)))
        res["H  inverse-perm gather"] = t(jax.jit(
            lambda o=order, p=newpos: o[jnp.argsort(p)]))
        win = rec[:, :cap]
        res["I  record scatter [R,cap]"] = t(jax.jit(
            lambda w=win, p=newpos: w.at[:, p].set(w, unique_indices=True)))
        res["I' record 2-run take"] = t(jax.jit(
            lambda w=win, k=go: jnp.take(
                w,
                jnp.argsort(~k, stable=True),
                axis=1)))
        res["J  sort (key, order)"] = t(jax.jit(
            lambda o=order, k=go: jax.lax.sort(
                ((~k).astype(jnp.int32), o), num_keys=1)))
        res["K  sort (key, order, R rec)"] = t(jax.jit(
            lambda o=order, k=go, w=win: jax.lax.sort(
                ((~k).astype(jnp.int32), o) + tuple(w), num_keys=1)))

        T = 512
        if cap % T == 0:
            win_rm = rec_rm[:cap]  # [cap, R] row-major record window

            @jax.jit
            def block_compact(wrm, k):
                nt = cap // T
                kt = k.reshape(nt, T)
                cl = jnp.sum(kt, axis=1, dtype=jnp.int32)
                loff = jnp.concatenate(
                    [jnp.zeros(1, jnp.int32), jnp.cumsum(cl)])[:-1]
                roff = jnp.concatenate(
                    [jnp.zeros(1, jnp.int32),
                     jnp.cumsum(T - cl)])[:-1]
                nl = jnp.sum(cl)
                tiles = wrm.reshape(nt, T, R)
                lpos = jnp.cumsum(kt, axis=1) - 1
                rpos = jnp.cumsum(~kt, axis=1) - 1
                pos = jnp.where(kt, lpos, T + rpos)  # [nt, T] in [0, 2T)

                def body(carry, x):
                    lbuf, rbuf = carry
                    tile, p, lo_, ro_ = x
                    # stable compaction of the tile through the MXU:
                    # one-hot destination matrix applied to the four i32
                    # BYTES separately — MXU rounds multiplicands to
                    # bf16 (8-bit mantissa), so bytes (<=255) are the
                    # widest exactly-representable split
                    P = (p[:, None]
                         == jnp.arange(2 * T, dtype=jnp.int32)[None, :]
                         ).astype(jnp.float32)
                    comp = jnp.zeros((2 * T, R), jnp.int32)
                    for b in range(4):
                        byte = ((tile >> (8 * b)) & 0xFF).astype(
                            jnp.float32)
                        m = jax.lax.dot_general(
                            P, byte, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
                        comp = comp | (m.astype(jnp.int32) << (8 * b))
                    lbuf = jax.lax.dynamic_update_slice(
                        lbuf, comp[:T], (lo_, 0))
                    rbuf = jax.lax.dynamic_update_slice(
                        rbuf, comp[T:], (ro_, 0))
                    return (lbuf, rbuf), None

                buf0 = jnp.zeros((cap + T, R), jnp.int32)
                (lbuf, rbuf), _ = jax.lax.scan(
                    body, (buf0, buf0), (tiles, pos, loff, roff))
                merged = jnp.where(
                    jnp.arange(cap, dtype=jnp.int32)[:, None] < nl,
                    lbuf[:cap],
                    jnp.roll(rbuf, nl, axis=0)[:cap])
                return merged

            res["L  block-compact scan+MXU"] = t(
                lambda: block_compact(win_rm, go))

        print(f"\n== cap={cap} ({cap / ROWS:.3f} n) ==", flush=True)
        for k, v in res.items():
            print(f"  {k:28s} {v:8.2f} ms  "
                  f"({v * 1e6 / cap:6.1f} ns/idx)", flush=True)


if __name__ == "__main__":
    main()
