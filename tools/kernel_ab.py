"""A/B the partition-routing strategies, the smaller-child gather
layouts and the end-to-end growth modes.

Routing A/B (runs first, works on CPU AND TPU — the parity and FLOP
halves of ISSUE 12's acceptance):

  python tools/kernel_ab.py --routing-only [rows]

asserts the ``onehot`` and ``prefix`` partition compactions produce
BITWISE-IDENTICAL records (partition_window + the fused split step,
in one process via the kernels' ``routing=`` static arg — this is why
the knob is an argument and not only the LGBM_TPU_REC_ROUTING env),
reports the HLO-cost-analysis FLOP ratio and wall-clock per routing,
and writes the artifact to ``.bench/kernel_ab_routing.json``
(atomic writer, PR 11 conventions).

Gather/e2e A/B (TPU; the original tool):  python tools/kernel_ab.py [rows]

Times, at bench shapes (F=28, B=255, L=255):
  1. the smaller-child gather: column take against row take + transpose
  2. leafwise + depthwise end-to-end s/tree
(The histogram kernel has one body since PR 29, so the stages that set
two against each other are gone: BASELINE.md.)
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

_ARGS = [a for a in sys.argv[1:] if not a.startswith("-")]
_FLAGS = {a for a in sys.argv[1:] if a.startswith("-")}
ROWS = int(float(_ARGS[0])) if _ARGS else 1_000_000


def t(fn, reps=5):
    import jax

    out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1000


def routing_ab(rows):
    """A/B the two partition-routing strategies in ONE process: bitwise
    parity of partition_window and the fused split step, HLO FLOPs per
    routing (cost analysis of the interpret lowering — the dots vs the
    compress network as real XLA ops), and wall-clock per routing on
    the current backend.  Writes .bench/kernel_ab_routing.json."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops import record as R
    from lightgbm_tpu.resilience import atomic_write_json

    interpret = jax.default_backend() != "tpu"
    T = R.TILE
    F, B = 28, 255
    k = R.bins_per_word(jnp.uint8)
    n = R.round_up(min(rows, 262_144) if interpret else rows, T)
    rng = np.random.RandomState(0)
    bins = rng.randint(0, B, (F, n)).astype(np.uint8)
    rec = R.build_record(
        jnp.asarray(bins), jnp.asarray(rng.randn(n).astype(np.float32)),
        jnp.ones(n, jnp.float32),
        jnp.asarray((rng.rand(n) < 0.8).astype(np.float32)),  # bag word
        n + T)
    leaf_row = R.num_words(F, k) + 4
    cap = n
    fv = R.extract_feature(rec, jnp.int32(2), jnp.int32(0), cap, k)
    go = (fv <= 100).astype(jnp.int32)
    pcnt = jnp.int32(n - 37)  # ragged: invalid tail rides the window
    args = (rec, go, jnp.int32(0), pcnt, jnp.bool_(True))
    kw = dict(cap=cap, left_leaf=jnp.int32(0), right_leaf=jnp.int32(1),
              leaf_row=leaf_row, interpret=interpret)

    out = {"tool": "kernel_ab.routing_ab", "rows": int(n),
           "tile": int(T), "backend": jax.default_backend(),
           "default_routing": R.ROUTING,
           "parity": {}, "flops": {}, "wall_ms": {}}

    recs = {}
    for routing in ("onehot", "prefix"):
        r2, nl = R.partition_window(*args, routing=routing, **kw)
        jax.block_until_ready(r2)
        recs[routing] = (np.asarray(r2).tobytes(), int(nl))
        t0 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            r2, nl = R.partition_window(*args, routing=routing, **kw)
        jax.block_until_ready(r2)
        out["wall_ms"][routing] = round(
            (time.perf_counter() - t0) / reps * 1000, 3)

        def _flops(lowered):
            ca = lowered.compile().cost_analysis()
            if isinstance(ca, list):
                ca = ca[0]
            return float(ca.get("flops", 0.0))

        # whole-program FLOPs at the A/B window (context: the interpret
        # grid is a while loop, so the kernel body counts ONCE and the
        # surrounding O(n) work dilutes the ratio as n grows) ...
        out["flops"].setdefault("program", {})[routing] = _flops(
            R.partition_window.lower(
                *args, routing=routing, **dict(kw, interpret=True)))
        # ... and the ROUTING-KERNEL FLOPs at a one-TILE window (the
        # hlo_audit pinned shape): the acceptance-criterion number —
        # per-tile routing work is what the strategies differ in
        out["flops"].setdefault("kernel_one_tile", {})[routing] = _flops(
            R.partition_window.lower(
                rec, go[:T], jnp.int32(0), jnp.int32(T),
                jnp.bool_(True), routing=routing,
                **dict(kw, cap=T, interpret=True)))
    bitwise = (recs["onehot"][0] == recs["prefix"][0]
               and recs["onehot"][1] == recs["prefix"][1])
    out["parity"]["partition_window_bitwise"] = bitwise
    for key in ("program", "kernel_one_tile"):
        d = out["flops"][key]
        d["onehot_over_prefix"] = round(
            d["onehot"] / max(d["prefix"], 1.0), 2)

    # fused split step: all four outputs must agree byte-for-byte
    # (fresh inputs per routing — hists is donated)
    from lightgbm_tpu.analysis.hlo_audit import _split_step_inputs

    ss = {}
    for routing in ("onehot", "prefix"):
        srec, hists, scal_f, meta, s, scap, sk = _split_step_inputs()
        o = R.split_step_window(
            hists, srec, s["begin"], s["pcnt"], s["do_split"], s["f"],
            s["thr"], s["is_cat"], s["parent_slot"], s["new_slot"],
            scal_f, meta, F=4, cap=scap, k=sk, interpret=interpret,
            routing=routing)
        ss[routing] = b"".join(np.asarray(x).tobytes() for x in o)
    out["parity"]["split_step_window_bitwise"] = ss["onehot"] == ss["prefix"]

    print(f"routing A/B (n={n}, TILE={T}, backend="
          f"{out['backend']}):", flush=True)
    print(f"  partition_window bitwise-identical: "
          f"{out['parity']['partition_window_bitwise']}", flush=True)
    print(f"  split_step_window bitwise-identical: "
          f"{out['parity']['split_step_window_bitwise']}", flush=True)
    for key in ("kernel_one_tile", "program"):
        d = out["flops"][key]
        print(f"  HLO flops [{key}]: onehot {d['onehot']:.3e}, prefix "
              f"{d['prefix']:.3e} ({d['onehot_over_prefix']}x)",
              flush=True)
    print(f"  wall ms/partition: {out['wall_ms']}", flush=True)

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".bench", "kernel_ab_routing.json")
    atomic_write_json(path, out)
    print(f"  wrote {path}", flush=True)
    assert bitwise and out["parity"]["split_step_window_bitwise"], (
        "routing parity FAILED — do not ship")
    return out


def main():
    plat = os.environ.get("BENCH_PLATFORM")

    import jax

    if plat:
        jax.config.update("jax_platforms", plat)
    import jax.numpy as jnp

    print("devices:", jax.devices(), flush=True)
    if jax.default_backend() != "tpu" and not plat:
        sys.exit(f"backend is {jax.default_backend()!r}, not tpu; set "
                 "BENCH_PLATFORM to name another platform explicitly")

    # partition-routing A/B first: cheap, runs on any backend, and its
    # parity assert is the thing that must never regress silently.
    # Guarded like every other section — if Mosaic rejects the prefix
    # kernel on a real chip (the documented risk; routing="prefix" is
    # explicit here, so the LGBM_TPU_REC_ROUTING=onehot escape hatch
    # cannot skip it), the gather/e2e A/B below must still get its
    # chip window.  --routing-only keeps the loud failure.
    try:
        routing_ab(ROWS)
        routing_ok = True
    except Exception as e:
        print(f"routing A/B FAILED: {type(e).__name__}: {str(e)[:300]}",
              flush=True)
        routing_ok = False
    if "--routing-only" in _FLAGS:
        if not routing_ok:
            sys.exit(1)
        return

    rng = np.random.RandomState(0)
    F, B = 28, 255
    bins = jnp.asarray(rng.randint(0, B, (F, ROWS)).astype(np.uint8))

    # gather-layout A/B: the leafwise smaller-child gather is currently a
    # minor-dim column take of [F, n]; the alternative keeps a row-major
    # copy and gathers rows (then relayouts [cap, F] -> [F, cap]).
    bins_rm = jnp.asarray(np.ascontiguousarray(np.asarray(bins).T))  # [n, F]
    for cap in (ROWS // 4, ROWS // 16):
        idx = jnp.asarray(rng.randint(0, ROWS, cap).astype(np.int32))

        @jax.jit
        def take_cols(i):
            return jnp.take(bins, i, axis=1)

        @jax.jit
        def take_rows_T(i):
            return bins_rm[i].T

        try:
            ms_c = t(lambda: take_cols(idx))
            ms_r = t(lambda: take_rows_T(idx))
            print(f"gather cap={cap}: col-take {ms_c:.2f} ms, "
                  f"row-take+T {ms_r:.2f} ms", flush=True)
        except Exception as e:
            print(f"gather cap={cap} FAILED: {type(e).__name__}: "
                  f"{str(e)[:200]}", flush=True)

    # end-to-end growth modes.
    # KERNEL_AB_SKIP_E2E=1 stops here: the end-to-end leafwise compile is
    # the giant one (~9 tier bodies), and bench.py covers end-to-end —
    # the micro numbers above are this tool's unique output.
    if os.environ.get("KERNEL_AB_SKIP_E2E", "0") != "0":
        return
    import bench
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.io.metadata import Metadata
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective

    X, y = bench.make_data(ROWS)
    for growth in ("leafwise", "depthwise"):
        cfg = Config(objective="binary", num_leaves=255, max_bin=255,
                     learning_rate=0.1, min_data_in_leaf=100,
                     metric=["auc"], tree_growth=growth)
        ds = BinnedDataset.from_matrix(
            X, Metadata(label=y.astype(np.float32)), config=cfg)
        booster = GBDT(cfg, ds, create_objective(cfg, ds.metadata, ds.num_data))
        t0 = time.perf_counter()
        booster.train_one_iter()
        _ = np.asarray(booster._scores[0, :1])
        t_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        trees = 10
        for _ in range(trees):
            booster.train_one_iter()
        _ = np.asarray(booster._scores)
        t_tree = (time.perf_counter() - t0) / trees
        auc = booster.eval_at(0).get("auc", float("nan"))
        print(f"{growth}: compile+1st {t_compile:.1f}s, "
              f"{t_tree*1000:.0f} ms/tree, AUC {auc:.4f}", flush=True)


if __name__ == "__main__":
    main()
