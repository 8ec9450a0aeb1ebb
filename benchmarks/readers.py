"""Per-layer metrics: one small file each under ``layers/``, read here.

A layer's file names its ``reader`` and that reader's arguments.  A reader
takes the run's readings (host clocks, counters, the reduced trace) and
returns a number, or ``None`` where it finds nothing to read: the harness
then leaves the metric out of the line.  It never returns 0 for a share.
"""

from __future__ import annotations

import cells
import peaks
import xplane as trace


def load(name: str) -> dict:
    return cells.read_json(cells.HERE, "layers", name + ".json")


def value(name: str, ctx: dict):
    spec = load(name)
    return READERS[spec["reader"]](spec, ctx)


def _scope_ns(spec: dict, ctx: dict):
    tr = ctx.get("trace")
    if not tr:
        return None
    found = trace.matching(tr["leaf_ops"], spec["scopes"])
    return trace.busy_ns(found) if found else None


def _least_seconds(spec: dict, ctx: dict) -> float:
    """The least time the chip could take for what the traced trees
    require, by the count function the layer's file names."""
    work = cells.plugin("counts", spec["count"]).required(
        ctx["trace"]["tree_counts"], ctx["readings"]["features"])
    return peaks.least_seconds(work, ctx["peak"])[0]


def scope_ms_per_tree(spec, ctx):
    ns = _scope_ns(spec, ctx)
    return None if ns is None else ns / 1e6 / ctx["trace"]["trees"]


def scope_roofline_pct(spec, ctx):
    ns = _scope_ns(spec, ctx)
    if not ns or not ctx["peak"]:
        return None
    return 100.0 * _least_seconds(spec, ctx) / (ns / 1e9)


def _modules(spec: dict, tr: dict) -> list:
    """The runs of the jitted programs that ``modules`` names, or of all
    but those that ``not_modules`` names."""
    found = tr["modules"]
    if "modules" in spec:
        found = trace.matching(found, spec["modules"])
    for pattern in spec.get("not_modules", []):
        found = [ev for ev in found if pattern not in ev[2]]
    return found


def module_ms_per_tree(spec, ctx):
    """Device time of whole jitted programs, by their names."""
    tr = ctx.get("trace")
    if not tr:
        return None
    found = _modules(spec, tr)
    return trace.busy_ns(found) / 1e6 / tr["trees"] if found else None


def unscoped_ms_per_tree(spec, ctx):
    """Device time, inside the named programs, of operations under none
    of ``scopes``."""
    tr = ctx.get("trace")
    if not tr:
        return None
    ops = trace.inside(tr["leaf_ops"], _modules(spec, tr))
    named = {id(ev) for ev in trace.matching(ops, spec["scopes"])}
    rest = [ev for ev in ops if id(ev) not in named]
    return trace.busy_ns(rest) / 1e6 / tr["trees"] if rest else None


def step_mfu_pct(spec, ctx):
    """The least time the chip could take for the trees' required work,
    over the time the traced trees took."""
    tr = ctx.get("trace")
    if not tr or not ctx["peak"]:
        return None
    return 100.0 * _least_seconds(spec, ctx) / tr["seconds"]


def idle_ms_per_tree(spec, ctx):
    tr = ctx.get("trace")
    return None if not tr else (
        (tr["window_ns"] - tr["busy_ns"]) / 1e6 / tr["trees"])


def idle_pct(spec, ctx):
    tr = ctx.get("trace")
    return None if not tr else (
        100.0 * (tr["window_ns"] - tr["busy_ns"]) / tr["window_ns"])


def reading(spec, ctx):
    v = ctx["readings"].get(spec["reading"])
    return None if v is None else float(v) * float(spec.get("scale", 1.0))


READERS = {f.__name__: f for f in (
    scope_ms_per_tree, scope_roofline_pct, module_ms_per_tree,
    unscoped_ms_per_tree,
    step_mfu_pct, idle_ms_per_tree, idle_pct, reading)}
