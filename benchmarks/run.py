"""The benchmark's one entry.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Every run is a new process: it finds the chips the cell asks for or exits
non-zero with no result, makes its data from the seed, warms up, measures
for ``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON line last.  ``--rehearsal`` runs the
same code at the configuration's tiny rehearsal size, on the CPU unless
``JAX_PLATFORMS`` says otherwise, and prints NO result line.
"""

import time

T0 = time.time()  # set-up counts from the process's start

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


class Run:
    """What a driver needs of one run."""

    def __init__(self, a, cell: dict):
        self.t0 = T0
        self.seed, self.seconds, self.trace = a.seed, a.seconds, bool(a.trace)
        self.keep_trace = a.keep_trace
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.generator_params = dict(self.config["generator"]["params"])
        if a.rehearsal:
            small = self.config["rehearsal"]
            self.generator_params["rows"] = small["rows"]
            self.config = {**self.config, "params": {
                **self.config["params"], "num_leaves": small["num_leaves"]}}
        self._compiles = 0

    def say(self, msg: str) -> None:
        print(f"[{time.time() - self.t0:7.1f}s] {msg}", file=sys.stderr,
              flush=True)

    def count_compiles(self) -> None:
        import jax.monitoring

        def on(event: str, duration: float, **_) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self._compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on)

    def compiles(self) -> int:
        return self._compiles


def find_devices(chips: int, rehearsal: bool) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if not rehearsal and (dev["platform"] != "tpu" or len(devs) < chips):
        sys.exit(f"benchmark: the cell needs {chips} TPU chip(s) and JAX "
                 f"found {dev}; nothing was run")
    return dev


def keep_compile_cache() -> str:
    """A fixed directory inside the checkout, unless the environment
    names one: the path is part of the cache's key."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(HERE), ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def reduce_trace(out: dict) -> dict | None:
    """The traced window: operations that did the work, busy time."""
    import xplane as trace

    tr = out.get("trace")
    if not tr or not tr["devices"]:
        return None
    span = [h for h in tr["host"] if h[2] == "bench.window"]
    lo, hi = (span[0][0], span[0][1]) if span else (
        min(e[0] for d in tr["devices"].values() for e in d["ops"]),
        max(e[1] for d in tr["devices"].values() for e in d["ops"]))
    per_chip = {p: trace.clip(d["ops"], lo, hi)
                for p, d in tr["devices"].items()}
    fullest = max(per_chip, key=lambda p: trace.busy_ns(per_chip[p]))
    ops = per_chip[fullest]
    busy = sum(trace.busy_ns(e) for e in per_chip.values()) / len(per_chip)
    return {"leaf_ops": trace.leaves_only(ops), "ops": ops,
            "modules": trace.clip(tr["devices"][fullest]["modules"], lo, hi),
            "busy_ns": busy, "window_ns": float(hi - lo), "lo": lo, "hi": hi,
            "host": [h for h in tr["host"] if h[2] != "bench.window"],
            **out["traced"]}


def parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--keep-trace", help="copy the .xplane.pb here")
    return ap.parse_args(argv)


def execute(a) -> dict:
    """One run, from the look for a chip to the result's object."""
    if a.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import cells
    import check
    import peaks
    import readers
    import xplane as trace

    cell = cells.cell(a.workload)
    run = Run(a, cell)
    dev = find_devices(int(cell["workload"]["chips"]), a.rehearsal)
    if not a.rehearsal:
        run.say(f"compile cache: {keep_compile_cache()}")
    run.count_compiles()
    driver = cells.plugin("drivers", cell["traffic"]["driver"])
    out = driver.run(run)

    correct, table = check.verdict(
        out["numbers"], check.limits_of(cell["config"]["name"]))
    ctx = {"readings": out["readings"], "trace": reduce_trace(out),
           "peak": peaks.of(dev["kind"]) if dev["platform"] == "tpu" else None}
    section = "per_layer" if a.trace else "end_to_end"
    metrics = {}
    for m in cells.metrics_of(cell["bench"], section, a.workload):
        if section == "end_to_end":
            v = out["readings"].get(m["name"])
        else:
            v = readers.value(m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {**dev, "memory_peak_bytes": int(out["memory_peak_bytes"])}
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if ctx["trace"]:
        tr = ctx["trace"]
        kernels = sorted({ev[2] for ev in tr["leaf_ops"] if "lgbm." in ev[2]})
        run.say(f"{len(kernels)} distinct lgbm.* kernels ran in the traced "
                f"window: {' '.join(kernels)}")
        device["busy_s"] = tr["busy_ns"] / 1e9
        device["window_s"] = tr["window_ns"] / 1e9
        result["breakdown"] = {
            "device_ops": trace.top_ops(tr["leaf_ops"]),
            "idle_gaps": trace.attribute_gaps(
                trace.gaps(tr["ops"], tr["lo"], tr["hi"]), tr["host"])}
    result["checked"] = table
    for name, row in table.items():
        print(f"checked {name}: {row['value']:.6g} (limit {row['limit']:g})",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    a = parse(argv)
    with contextlib.redirect_stdout(sys.stderr):  # the program logs there
        result = execute(a)
    if a.rehearsal:
        print("rehearsal: " + json.dumps(
            {**result, "rehearsal": True}), file=sys.stderr)
    else:
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
