"""Read, in one process, what the limits are set from.

    python benchmarks/tools/limits.py --workload <config>.<suffix> --seeds 12
        [--traffic train_steady] [--first 7000] [--control-seeds 3]
        [--explain 3] [--faults half_batch,altered_split]
        [--out chiprun_out/limits_<cell>.jsonl]

The workload need not be in BENCHMARK.json yet: a cell is read here before
it is admitted.

For every seed: the data, the program's ``Dataset`` and ``Booster`` at the
cell's own size, its first trees through ``Booster.update()``, then the
plain reference and the comparison's numbers (the lower readings).  On the
first ``--control-seeds`` seeds also the control (the reference in the
program's place, in bfloat16) and the planted faults, at the same size (the
upper readings).  Training's readings need no measured window.  One JSON
line a reading, with every searched node's gains; with ``--out`` every
seed's leaves and nodes go to an ``.npz`` beside it, and on the first
``--explain`` seeds each leaf is also summed in float32 two ways
(tools/explain.py): where a worst leaf reads far off, that tells the
arithmetic's cost from a fault.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

SIDES = (("control", "bfloat16", None), ("fault", "float32", "half_batch"),
         ("fault", "float32", "state_unchanged"),
         ("fault", "float32", "altered_split"),
         ("fault", "float32", "altered_leaf"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--traffic", default="train_steady")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first", type=int, default=2_200_000_000)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--own-seeds", type=int, default=4,
                    help="seeds on which the reference also follows its "
                         "own scores, for PERF.md's second reading")
    ap.add_argument("--explain", type=int, default=0,
                    help="seeds whose leaves are also summed in float32 "
                         "two ways (tools/explain.py); every seed's leaf "
                         "and node readings go to <out>.<seed>.npz")
    ap.add_argument("--faults", default=",".join(
        f for _, _, f in SIDES if f), help="the planted faults to read")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out")
    a = ap.parse_args()
    sides = tuple(s for s in SIDES
                  if s[2] is None or s[2] in a.faults.split(","))

    import numpy as np

    import cells
    import program
    import run as entry
    from drivers import train_steady
    from tools import explain

    cell = cells.assemble(
        {"name": a.workload, "config": a.workload.rsplit(".", 1)[0],
         "traffic": a.traffic, "chips": 1}, cells.benchmark())
    out = open(a.out, "a") if a.out else None
    for i in range(a.seeds):
        args = entry.parse(["--workload", a.workload, "--seed",
                            str(a.first + 7919 * i), "--seconds", "0"]
                           + (["--rehearsal"] * a.rehearsal))
        if a.rehearsal:
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
        run = entry.Run(args, cell)
        if i == 0:
            entry.find_devices(int(cell["workload"]["chips"]), a.rehearsal)
            if not a.rehearsal:
                entry.keep_compile_cache()
        run.traffic = {**run.traffic, "min_warmup_trees":
                       run.traffic["checked_trees"], "quiet_trees": 0}
        t = time.time()
        s = train_steady.setup(run)
        state = train_steady.first_trees(run, s)
        program.free(s.pop("booster"), s.pop("ds"))
        rows = [("program", "float32", None)]
        rows += sides if i < a.control_seeds else ()
        todo = [(True, r) for r in rows]
        todo += [(False, rows[0])] if i < a.own_seeds else []
        refs = {}
        for forced, (kind, precision, fault) in todo:
            if forced not in refs:
                refs[forced] = train_steady.reference(
                    run, state, forced, keep_rows=forced and i < a.explain)
                if forced and a.out:
                    np.savez(f"{a.out}.{run.seed}.npz", **explain.leaf_tables(
                        state, refs[forced], i < a.explain))
            searched = []
            numbers = train_steady.compared(
                run, state, refs[forced], precision, fault, forced,
                detail=searched)
            line = json.dumps({"workload": a.workload, "seed": run.seed,
                               "side": kind, "precision": precision,
                               "fault": fault, "forced": forced,
                               "numbers": numbers, "searched": searched})
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
        run.say(f"seed {run.seed} read in {time.time() - t:.0f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
