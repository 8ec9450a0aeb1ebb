"""Steady training: ``Booster.update()`` back to back.

Set-up makes the data from the seed, builds the program's ``Dataset`` and
``Booster`` (the calls ``engine.train`` makes) and drives that one object
through its first trees until no program compiles any more; the window
then calls the same ``update()`` on the same object until its seconds
have passed.  Once it has closed, the peak is read, the program's device
state is freed, and the plain reference follows the first trees.
"""

from __future__ import annotations

import shutil
import tempfile
import time

import cells
import check
import program
import xplane as trace
from references import gbdt_replay

HOST_SPANS = ("bench.window", "Booster.update", "final sync")


def setup(run) -> dict:
    """Everything before the window; its clocks are per-layer readings."""
    config, traffic = run.config, run.traffic
    gen = cells.plugin("generators", config["generator"]["name"])
    t = time.perf_counter()
    data = gen.generate(run.generator_params, run.seed)
    generate_s = time.perf_counter() - t
    t = time.perf_counter()
    ds = program.build_dataset(config, data)
    binning_s = time.perf_counter() - t
    booster = program.build_booster(config, ds)
    snapshots = [program.scores(booster)]
    tree_s, quiet = [], 0
    for i in range(int(traffic["max_warmup_trees"])):
        before = run.compiles()
        t = time.perf_counter()
        booster.update()
        program.sync(booster)
        tree_s.append(time.perf_counter() - t)
        quiet = quiet + 1 if run.compiles() == before else 0
        if i < int(traffic["checked_trees"]):
            snapshots.append(program.scores(booster))
        run.say(f"warm-up tree {i}: {tree_s[-1]:.3f}s, "
                f"{run.compiles() - before} compile(s)")
        if (i + 1 >= int(traffic["min_warmup_trees"])
                and quiet >= int(traffic["quiet_trees"])):
            break
    steady = min(tree_s[1:]) if len(tree_s) > 1 else tree_s[0]
    return {"data": data, "ds": ds, "booster": booster,
            "snapshots": snapshots, "generate_s": generate_s,
            "binning_s": binning_s,
            "compile_s": max(tree_s[0] - steady, 0.0),
            "warmup_trees": len(tree_s)}


def window(run, booster, seconds: float, traced_trees: int = 0) -> dict:
    """``update()`` until ``seconds`` have passed.  With ``traced_trees``
    the profiler records the first trees of it and is stopped again."""
    import jax

    first = program.num_trees(booster)
    compiles = run.compiles()
    attempted = 0
    traced = None
    start = time.perf_counter()
    if traced_trees:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(traced_trees):
                with jax.profiler.TraceAnnotation("Booster.update"):
                    attempted += 1
                    booster.update()
            with jax.profiler.TraceAnnotation("final sync"):
                program.sync(booster)
        traced = {"seconds": time.perf_counter() - t, "trees": traced_trees,
                  "first_tree": first}
        jax.profiler.stop_trace()
        traced["log_dir"] = log_dir
    while time.perf_counter() - start < seconds:
        attempted += 1
        booster.update()
    program.sync(booster)
    elapsed = time.perf_counter() - start
    trees = program.num_trees(booster) - first
    return {"elapsed_s": elapsed, "trees": trees, "first_tree": first,
            "attempted": attempted, "failed": attempted - trees,
            "compiles": run.compiles() - compiles, "traced": traced}


def reference(run, state: dict, forced: bool = True,
              keep_rows: bool = False) -> dict:
    """The plain reference's replay of the program's first trees, each
    from the scores the program had before it (``forced``) or from the
    reference's own."""
    k = int(run.traffic["checked_trees"])
    return gbdt_replay.replay(
        state["data"], state["trees"][:k], state["bounds"], run.config,
        run.seed, starts=state["snapshots"][:k] if forced else None,
        keep_rows=keep_rows)


def compared(run, state: dict, ref: dict, precision: str = "float32",
             fault: str | None = None, forced: bool = True,
             detail: list | None = None) -> dict:
    """The numbers that decide ``correct``: what stands in the program's
    place against the reference.  With neither ``precision`` lowered nor a
    ``fault`` planted that is the program itself; otherwise the reference
    again, lowered or broken (the control and the faults of tools/limits.py
    and tests/test_check.py)."""
    k = int(run.traffic["checked_trees"])
    if precision == "float32" and fault is None:
        side = check.side_of_program(
            state["trees"][:k], state["snapshots"], ref["objective"].loss)
    else:
        side = check.side_of_replay(gbdt_replay.replay(
            state["data"], state["trees"][:k], state["bounds"], run.config,
            run.seed, precision=precision, fault=fault,
            starts=state["snapshots"][:k] if forced else None,
            picks=[[s["node"] for s in t["searched"]]
                   for t in ref["trees"]]))
    return check.compare(side, ref, state["data"]["X"], detail)


def first_trees(run, s: dict) -> dict:
    """What the check needs of the program once the window has closed."""
    k = int(run.traffic["checked_trees"])
    return {"data": s["data"], "snapshots": s["snapshots"],
            "trees": program.trees(s["booster"], 0, k),
            "bounds": program.bin_bounds(s["ds"])}


def device_memory(devices) -> tuple:
    """``(live, reserved, both)`` of the fullest chip.  The allocator
    counts live arrays under ``peak_bytes_in_use`` and the scratch that
    loaded programs hold for their temporaries under
    ``peak_bytes_reserved`` (the grow program's 12.8 GB; PERF.md, PR 25):
    the chip's memory is full by the sum."""
    best = (0, 0, 0)
    for d in devices:
        stats = d.memory_stats() or {}
        live = int(stats.get("peak_bytes_in_use", 0))
        reserved = int(stats.get("peak_bytes_reserved", 0))
        best = max(best, (live, reserved, live + reserved),
                   key=lambda t: t[2])
    return best


def run(run) -> dict:
    import jax

    s = setup(run)
    setup_s = time.time() - run.t0
    booster = s["booster"]
    w = window(run, booster, run.seconds,
               int(run.traffic["traced_trees"]) if run.trace else 0)
    live, reserved, peak = device_memory(jax.local_devices())
    model = program.memory_model(run.config)
    run.say(f"peak_bytes_in_use {live} + peak_bytes_reserved {reserved} = "
            f"{peak} ({peak / 2**30:.2f} GiB); obs/memmodel predicts "
            f"{'n/a' if model is None else f'{model / 2**30:.2f} GiB'}")
    state = first_trees(run, s)
    tree_counts = program.tree_counts(booster, w["first_tree"])
    program.free(booster, s["ds"])
    del booster

    t = time.perf_counter()
    numbers = compared(run, state, reference(run, state))
    run.say(f"reference followed its trees in {time.perf_counter() - t:.1f}s")

    readings = {
        "train_s_per_tree": w["elapsed_s"] / max(w["trees"], 1),
        "setup_s": setup_s, "binning_s": s["binning_s"],
        "generate_s": s["generate_s"], "compile_s": s["compile_s"],
        "window_compiles": w["compiles"], "peak_bytes": peak,
        "live_peak_bytes": live, "reserved_peak_bytes": reserved,
        "window_trees": w["trees"], "warmup_trees": s["warmup_trees"],
        "features": s["data"]["X"].shape[1], "tree_counts": tree_counts,
    }
    out = {"numbers": numbers, "readings": readings,
           "attempted": w["attempted"], "failed": w["failed"],
           "memory_peak_bytes": peak}
    if w["traced"]:
        tr = w["traced"]
        path = trace.find_xplane(tr["log_dir"])
        out["trace"] = trace.read(path, HOST_SPANS)
        out["traced"] = {"trees": tr["trees"], "seconds": tr["seconds"],
                         "tree_counts": tree_counts[:tr["trees"]]}
        if run.keep_trace:
            shutil.copy(path, run.keep_trace)
        shutil.rmtree(tr["log_dir"], ignore_errors=True)
    return out
