"""From a profiler trace (``.xplane.pb``) to intervals and sums.

Read with ``jax.profiler.ProfileData`` and nothing else.  A device plane
is one chip; its "XLA Ops" line holds one event for every operation that
ran, named by its HLO instruction, and the program's ``jax.named_scope``s
(``lgbm.split_step`` ...) are found in the event's name or statistics.
Host spans (``jax.profiler.TraceAnnotation``) are on the host plane's
thread lines.  Everything here works on plain lists, so a recorded trace
checks it (tests/test_trace.py).
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {log_dir}")
    return found[-1]


def read(path: str, host_names: tuple = ()) -> dict:
    """``{"devices": {plane: {"ops": [(start_ns, end_ns, name, where)],
    "modules": [...]}}, "host": [(start_ns, end_ns, name)]}``.  An
    operation's ``name`` is its HLO instruction's (``%lgbm.split_step.13``;
    the instruction's text is cut off), and ``where`` is the text of the
    event's statistics, in which a named scope shows where the backend
    records one.  A module is one run of a jitted program
    (``jit_grow_tree(...)``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            found = {OPS_LINE: [], MODULES_LINE: []}
            for line in plane.lines:
                if line.name not in found:
                    continue
                for ev in line.events:
                    where = " ".join(str(v) for _, v in ev.stats
                                     if isinstance(v, str))
                    found[line.name].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         ev.name.split(" = ")[0], where))
            if found[OPS_LINE]:
                devices[plane.name] = {"ops": found[OPS_LINE],
                                       "modules": found[MODULES_LINE]}
        elif plane.name == HOST_PLANE and host_names:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in host_names:
                        host.append((ev.start_ns,
                                     ev.start_ns + ev.duration_ns, ev.name))
    return {"devices": devices, "host": sorted(host)}


def clip(events: list, lo: float, hi: float) -> list:
    """Events cut to the window ``[lo, hi]``."""
    out = []
    for ev in events:
        s, e = max(ev[0], lo), min(ev[1], hi)
        if e > s:
            out.append((s, e) + tuple(ev[2:]))
    return out


def union(events: list) -> list:
    """Merged ``(start, end)`` intervals: nested and overlapping events
    count once."""
    merged = []
    for s, e in sorted((ev[0], ev[1]) for ev in events):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(events: list) -> float:
    return float(sum(e - s for s, e in union(events)))


def gaps(events: list, lo: float, hi: float) -> list:
    """Idle ``(start, end)`` stretches of ``[lo, hi]``."""
    out, at = [], lo
    for s, e in union(events):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def matching(events: list, patterns: list) -> list:
    """Events whose name or statistics hold one of ``patterns``."""
    return [ev for ev in events
            if any(p in ev[2] or p in ev[3] for p in patterns)]


def inside(events: list, spans: list) -> list:
    """Events that start inside one of ``spans`` (merged intervals)."""
    import bisect

    merged = union(spans)
    starts = [s for s, _ in merged]
    out = []
    for ev in events:
        k = bisect.bisect_right(starts, ev[0]) - 1
        if k >= 0 and ev[0] < merged[k][1]:
            out.append(ev)
    return out


def leaves_only(events: list) -> list:
    """Drop events that wholly contain another one (a ``while`` or a
    ``call`` spans the operations of its body): what is left are the
    operations that did the work, each counted once."""
    ordered = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    out, stack = [], []
    for ev in ordered:
        while stack and stack[-1][1] <= ev[0]:
            out.append(stack.pop())
        if stack and ev[1] <= stack[-1][1]:
            stack.pop()  # the enclosing event is not a leaf
        stack.append(ev)
    out.extend(stack)
    return out


def top_ops(events: list, how_many: int = 10) -> list:
    """``[[name, seconds]]`` of the operations that took most time."""
    total = {}
    for s, e, name, _ in events:
        total[name] = total.get(name, 0.0) + (e - s)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:how_many]
    return [[name, ns / 1e9] for name, ns in ranked]


def attribute_gaps(idle: list, host: list, how_many: int = 10) -> list:
    """``[[host span name, seconds]]``: every idle stretch is given to the
    host spans that overlap it, by the overlap; the rest is ``(no span)``."""
    total = {}
    for gs, ge in idle:
        covered = 0.0
        for hs, he, name in host:
            o = min(ge, he) - max(gs, hs)
            if o > 0:
                total[name] = total.get(name, 0.0) + o
                covered += o
        rest = (ge - gs) - covered
        if rest > 0:
            total["(no span)"] = total.get("(no span)", 0.0) + rest
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:how_many]
    return [[name, ns / 1e9] for name, ns in ranked]
