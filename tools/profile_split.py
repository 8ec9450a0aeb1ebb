"""Profile the compiled leaf-wise training loop and attribute device
time by HLO op — ground truth for what the ~ms/split is spent on.

The micro-sweeps (kernel_ab.py, gather_sweep.py) time ops as separate
dispatches, which adds a per-launch floor (not measured on this
machine) and hides the in-loop cost structure.  This tool instead traces
the REAL fori_loop program with jax.profiler, parses the TensorBoard
trace, and prints device time aggregated by op name/category.

    python tools/profile_split.py [rows] [trees]

Output: top ops by total device-time plus a category rollup
(gather / scatter / dynamic-slice / dynamic-update-slice / fusion /
custom-call(pallas) / sort / convert / other).
"""

import glob
import gzip
import json
import os
import re
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

ROWS = int(float(sys.argv[1])) if len(sys.argv) > 1 else 1_000_000
TREES = int(sys.argv[2]) if len(sys.argv) > 2 else 3


def main():
    import jax

    plat = os.environ.get("BENCH_PLATFORM")
    if plat:
        jax.config.update("jax_platforms", plat)

    import bench
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.io.metadata import Metadata
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective

    print("devices:", jax.devices(), flush=True)
    leaves = int(os.environ.get("BENCH_LEAVES", 255))
    cat_cols = ()
    if os.environ.get("BENCH_CAT"):
        # the bench_categorical.py 100k Expo shape: 4 numeric + 4
        # categorical columns, 63 leaves — the small-shape floor case
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import bench_categorical as bc
        Xn, Xc, y = bc.make_data(ROWS)
        X = np.column_stack([Xn, Xc])
        cat_cols = tuple(range(Xn.shape[1], X.shape[1]))
        leaves = int(os.environ.get("BENCH_LEAVES", bc.LEAVES))
    else:
        X, y = bench.make_data(ROWS)
    cfg = Config(objective="binary", num_leaves=leaves, max_bin=255,
                 learning_rate=0.1, min_data_in_leaf=100, metric=["auc"],
                 categorical_column=",".join(map(str, cat_cols)),
                 tree_growth=os.environ.get("BENCH_GROWTH", "leafwise"))
    ds = BinnedDataset.from_matrix(
        X, Metadata(label=y.astype(np.float32)), config=cfg)
    booster = GBDT(cfg, ds, create_objective(cfg, ds.metadata, ds.num_data))

    # BENCH_LEARNER=dp_record|dp_canonical traces the data-parallel
    # grower's per-shard program on however many devices exist (a
    # 1-device mesh on the real chip exposes the DP loop structure)
    learner = os.environ.get("BENCH_LEARNER", "serial")
    if learner.startswith("dp_"):
        from lightgbm_tpu.parallel import data_mesh, make_data_parallel_grower

        booster._grow = make_data_parallel_grower(
            data_mesh(num_devices=len(jax.devices())),
            num_bins=booster._num_bins, max_leaves=booster.max_leaves,
            sorted_hist=booster._use_pallas_hist(),
            record=(learner == "dp_record"))
        print("learner:", learner, flush=True)

    t0 = time.perf_counter()
    booster.train_one_iter()  # compile + warm
    np.asarray(booster._scores[0, :1])
    print(f"compile+first: {time.perf_counter() - t0:.1f}s", flush=True)

    outdir = tempfile.mkdtemp(prefix="jaxprof_")
    with jax.profiler.trace(outdir):
        t0 = time.perf_counter()
        for _ in range(TREES):
            booster.train_one_iter()
        np.asarray(booster._scores[0, :1])
        wall = time.perf_counter() - t0
    print(f"steady: {wall / TREES:.3f} s/tree over {TREES} trees", flush=True)

    traces = glob.glob(
        os.path.join(outdir, "**", "*.trace.json.gz"), recursive=True)
    if not traces:
        print("NO TRACE FILES under", outdir)
        return
    by_name = {}
    device_total = 0.0
    for path in traces:
        with gzip.open(path, "rt") as fh:
            data = json.load(fh)
        events = data.get("traceEvents", [])
        # device lanes: pid whose process_name mentions TPU/device; the
        # robust filter is events carrying a "run_id"/"correlation" arg
        # — instead aggregate complete events on threads whose name is
        # not python/host.
        pid_names = {}
        tid_names = {}
        for e in events:
            if e.get("ph") == "M" and e.get("name") == "process_name":
                pid_names[e["pid"]] = e["args"].get("name", "")
            if e.get("ph") == "M" and e.get("name") == "thread_name":
                tid_names[(e["pid"], e["tid"])] = e["args"].get("name", "")
        # SELF-time attribution: events on one thread nest by interval;
        # self = dur - sum(direct children).  Without this, while/cond
        # wrappers absorb their bodies and dominate the report.
        lanes = {}
        for e in events:
            if e.get("ph") != "X":
                continue
            pname = pid_names.get(e.get("pid"), "")
            if not re.search(r"TPU|/device|XLA Op|Chip", pname, re.I):
                continue
            tname = tid_names.get((e.get("pid"), e.get("tid")), "")
            if re.search(r"step|launch|infeed|outfeed", tname, re.I):
                continue
            lanes.setdefault((e["pid"], e["tid"]), []).append(e)
        for evs in lanes.values():
            evs.sort(key=lambda e: (e["ts"], -e.get("dur", 0)))
            stack = []  # (end_ts, entry) entries currently open
            for e in evs:
                ts, dur = e["ts"], e.get("dur", 0)
                while stack and stack[-1][0] <= ts:
                    stack.pop()
                entry = {"child": 0.0}
                if stack:
                    stack[-1][1]["child"] += dur
                stack.append((ts + dur, entry))
                args = e.get("args", {}) or {}
                e["_entry"] = entry
                e["_long"] = (args.get("long_name")
                              or args.get("hlo_op") or "")
            for e in evs:
                dur = e.get("dur", 0)
                self_ms = max(0.0, dur - e["_entry"]["child"]) / 1e3
                name = e.get("name", "?")
                key = re.sub(r"[.\d]+$", "", name) or name
                if key in ("fusion", "copy") and e["_long"]:
                    # split the fusion/copy buckets by output-shape
                    # signature (one 'copy' group hid which layouts pay)
                    sig = re.search(r"= ([^)]{0,70})", e["_long"])
                    if sig:
                        key = key + " " + re.sub(
                            r"\{[^}]*\}", "", sig.group(1))[:60]
                rec = by_name.setdefault(
                    key, {"ms": 0.0, "n": 0, "ex": "", "long": ""})
                rec["ms"] += self_ms
                rec["n"] += 1
                if not rec["ex"]:
                    rec["ex"] = name
                if e["_long"] and len(e["_long"]) > len(rec["long"]):
                    rec["long"] = e["_long"]
                device_total += self_ms
    if not by_name:
        print("trace parsed but no device events matched; pids seen:")
        print(sorted(set(pid_names.values()))[:20])
        return

    def cat(name):
        n = name.lower()
        for pat, c in (
            ("gather", "gather"),
            ("scatter", "scatter"),
            ("dynamic-update-slice", "dyn-update-slice"),
            ("dynamic_update_slice", "dyn-update-slice"),
            ("dynamic-slice", "dyn-slice"),
            ("dynamic_slice", "dyn-slice"),
            ("custom-call", "custom-call(pallas)"),
            ("sort", "sort"),
            ("cumsum", "cumsum"),
            ("reduce", "reduce"),
            ("fusion", "fusion"),
            ("convert", "convert"),
            ("copy", "copy"),
            ("select", "select"),
            ("while", "while-overhead"),
        ):
            if pat in n:
                return c
        return "other"

    print(f"\ndevice SELF-time total: {device_total:.1f} ms "
          f"({device_total / TREES:.1f} ms/tree)")
    cats = {}
    for name, rec in by_name.items():
        cats[cat(name)] = cats.get(cat(name), 0.0) + rec["ms"]
    print("\n-- by category (self time) --")
    for c, ms in sorted(cats.items(), key=lambda kv: -kv[1]):
        print(f"  {c:22s} {ms:9.1f} ms  ({100 * ms / device_total:5.1f}%)")
    print("\n-- top 30 op groups (self time; name stripped of ids) --")
    for name, rec in sorted(by_name.items(), key=lambda kv: -kv[1]["ms"])[:30]:
        print(f"  {rec['ms']:9.1f} ms  n={rec['n']:6d}  {name[:60]}"
              f"   [{rec['ex'][:40]}]")
        if rec["long"]:
            print(f"             {rec['long'][:150]}")


if __name__ == "__main__":
    main()
