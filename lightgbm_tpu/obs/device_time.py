"""Device time by program scope and cause, read from a profiler trace.

One system in two halves.  :func:`phase_scope` *writes* names: the
grower, its kernels and the boosting driver wrap what they trace in
``jax.named_scope("lgbm.<scope>")``, which costs a name-stack push at
trace time and nothing at run time, and lands in every XLA op's
``op_name``.  :func:`read` and :func:`attribute` *read them back* from
the ``.xplane.pb`` that ``jax.profiler`` writes (jax 0.9.0 writes no
chrome trace): the device planes' "XLA Ops" / "XLA Modules" events,
the per-instruction event metadata (``tf_op`` = JAX's ``op_name`` path,
``source``, ``bytes_accessed``, ``program_id`` ...), the host plane's
``TraceMe`` events, and the optimized ``HloProto`` of every program that
ran (plane ``/host:metadata``).  ``jax.profiler.ProfileData`` exposes an
event's own statistics only, and the names live in the event *metadata*,
so the file is read as protobuf wire format here: seven ``XSpace``
messages and four of ``HloProto``, no TensorFlow, xprof or protobuf
import.

Every leaf op gets a *scope* (the innermost ``lgbm.*`` component of its
``op_name``; failing that, of the ``while`` / ``conditional`` / ``call``
that encloses it, walking the HLO upward; failing that, of its consumer;
failing that ``unattributed``) and a *cause*: ``program`` for an op the
program wrote, else ``from > into`` read off the HLO, for the copies and
bitcast fusions XLA inserts.  *from*: ``kernel`` (a Mosaic call's
output), ``cond`` / ``loop`` (a get-tuple-element of a conditional / a
while), ``carry`` (the while body's parameter), ``arg`` (an entry
parameter), ``op``.  *into*: ``branch_result`` (root tuple of a
conditional's branch), ``carry`` (root tuple of the while body, or the
while's operand), ``result`` (the entry's root), ``kernel`` (only Mosaic
calls use it), ``cond_arg``, ``op``.  `` layout`` marks a copy whose
dimensions are its operand's and whose layout or memory space is not.

    python -m lightgbm_tpu.obs.device_time <file.xplane.pb>
        [--program jit_grow_tree] [--window <host span>] [--top N] [--json]

prints, per program, time by scope x cause; the copy ledger (every op
the program did not write that takes 1% of its program or more, with
bytes, shape, producer, consumer and the chain of enclosing control
flow); and the device's idle gaps by the innermost ``lgbm.*`` host span
(``telemetry.span`` opens a ``TraceAnnotation`` beside its timer).
``benchmarks/run.py --keep-trace`` writes the file this takes.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import struct
import sys
from typing import NamedTuple

# The one table of scope names: (scope, what it covers).  A component
# ``lgbm.partition.compact.cap4096`` belongs to the scope
# ``lgbm.partition`` and carries the tail ``compact.cap4096`` (a Mosaic
# call's kernel and, where each capacity tier has a body of its own, its
# capacity; ``dyn`` where the tile count is a run-time operand: the
# instruction takes its name from the innermost scope, so the tail is
# what makes ``%lgbm.split_step.dyn.N`` say which call it is).
SCOPES = (
    ("lgbm.histogram", "histogram kernels (on the fused path: the root's) "
     "and the XLA ops that pad and split their operands"),
    ("lgbm.split_search", "jax.numpy split search (ops/split.py)"),
    ("lgbm.split_step", "fused split step: routing, compaction, child "
     "histogram and both searches in one Mosaic call (ops/record.py)"),
    ("lgbm.partition", "build_record, place_runs (tail place.dyn) and, "
     "in the canonical grower's record mode, partition_window's "
     "compaction (tail compact.cap<N>): Mosaic calls and the XLA ops "
     "around them"),
    ("lgbm.leaf_update", "_post_grow_step: shrinkage, score update, "
     "thresholds (models/gbdt.py)"),
    ("lgbm.gradients", "the objective's jitted gradient programs"),
    ("lgbm.rank.sort", "lambdarank's pair-gradient program "
     "(objectives_rank.py), under lgbm.gradients: the gather of scores "
     "into [queries, Q] and the two lax.sort calls that carry what they "
     "reorder as operands (scores, slots, labels, gains out by score; "
     "the row sums back by slot)"),
    ("lgbm.rank.pairs", "the same program's [C, Q, Q] pair arithmetic "
     "and row sums, and the lax.map that carries the chunks"),
    ("lgbm.rank.scatter", "the same program's way back to rows, in a "
     "tree's last launch: the buckets' [queries, Q] sums laid end to end "
     "and gathered by the row -> slot map (scatter-adds until PR 33)"),
    ("lgbm.predict", "matmul prediction (ops/predict_matmul.py)"),
    ("lgbm.grow.root", "grow_tree before the loop: root histogram, first "
     "search, initial state"),
    ("lgbm.root_totals", "the root's sum of gradients and hessians "
     "(ops/totals.py), inside lgbm.grow.root"),
    ("lgbm.grow.loop", "the fori_loop itself: its carry and whatever of "
     "the body no inner scope names"),
    ("lgbm.grow.select", "body's argmax and a split's column reads "
     "and scalar packing"),
    ("lgbm.grow.tier", "a _tier_chain call of the canonical grower "
     "(learners/serial.py): the cond nest and what XLA puts at its "
     "boundaries (tails: part, hist; the fused grower has none)"),
    ("lgbm.grow.book", "a split after the kernels: best_mat, pos_mat, "
     "tree_i, tree_f column updates (learners/tables.py), pool "
     "bookkeeping"),
    ("lgbm.grow.unpack", "grow_tree after the loop: Tree unpack, leaf_id"),
    ("lgbm.grow.exchange", "the data-parallel fused grower's collectives "
     "(learners/fused.py exchange, ops/totals.py): the psum of the root's "
     "and of every split's smaller-child [Fp, 4, Bp] histogram, the "
     "root totals' pmax and psums; none on one device"),
)
SCOPE_NAMES = tuple(s for s, _ in SCOPES)
UNATTRIBUTED = "unattributed"

_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"
_DEVICE_PREFIX, _HOST_PLANE, _HLO_PLANE = (
    "/device:TPU:", "/host:CPU", "/host:metadata")
_MOSAIC = "tpu_custom_call"


def phase_scope(phase: str):
    """Trace-time scope ``lgbm.<phase>`` (dashes become underscores), as
    a context manager or a decorator under ``jax.jit``.  An ambient
    scope does not reach into a jitted callee: put it inside the jit."""
    import jax

    return jax.named_scope("lgbm." + phase.replace("-", "_"))


# ----------------------------------------------------------- wire format

def _varint(buf, i):
    r = s = 0
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << s
        if b < 0x80:
            return r, i
        s += 7


def _fields(buf, i, end):
    """``(field, wire_type, value)`` of one message: an int for a varint,
    ``(start, end)`` for a length-delimited field, raw bytes for fixed."""
    while i < end:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wt in (1, 5):
            n = 8 if wt == 1 else 4
            v = buf[i:i + n]
            i += n
        else:
            raise ValueError(f"wire type {wt} at byte {i}: not a protobuf")
        yield key >> 3, wt, v


def _text(buf, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _ints(buf, wt, v) -> list:
    """A repeated int64 field, packed or not."""
    if wt == 0:
        return [v]
    out, i = [], v[0]
    while i < v[1]:
        x, i = _varint(buf, i)
        out.append(x)
    return out


def _map_entry(buf, span):
    key = val = None
    for f, _, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _stat(buf, span, stat_names: dict):
    """One ``XStat`` as ``(name, value)``; a ``ref_value`` is the string
    that ``stat_metadata`` holds once under that id."""
    name = value = None
    for f, wt, v in _fields(buf, *span):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            value = v - (1 << 64) if f == 4 and v >> 63 else v
        elif f == 5:
            value = _text(buf, v)
        elif f == 6:
            value = buf[v[0]:v[1]]
        elif f == 7:
            value = stat_names.get(v, "")
    return name, value


# ---------------------------------------------------------------- XSpace

class Op(NamedTuple):
    """What the event metadata holds of one HLO instruction."""

    name: str  # "%copy.618"
    opcode: str
    operands: tuple  # operand names, from the instruction's text
    shape: str  # result shape with layout
    operand_shape: str  # first operand's, "" if the text has none
    tf_op: str  # JAX's op_name path, "" if none
    source: str  # file:line, "" if none
    category: str
    bytes: int
    program_id: int


_INSTR = re.compile(r"^(%\S+) = (.*?) ([\w\-]+)\(")
_NAME = re.compile(r"%[\w.\-]+")


def _parse_op(text: str, stats: dict) -> Op:
    m = _INSTR.match(text)
    if not m:  # a module's or a step's metadata: no instruction text
        return Op(text, "", (), "", "", "", "", "", 0,
                  int(stats.get("program_id", 0)))
    depth, j = 1, m.end()
    while j < len(text) and depth:
        depth += {"(": 1, ")": -1}.get(text[j], 0)
        j += 1
    inner = text[m.end():j - 1]
    first = _NAME.search(inner)
    return Op(
        m.group(1), m.group(3), tuple(_NAME.findall(inner)), m.group(2),
        inner[:first.start()].strip() if first else "",
        str(stats.get("tf_op", "")), str(stats.get("source", "")),
        str(stats.get("hlo_category", "")),
        int(stats.get("bytes_accessed", 0)),
        int(stats.get("program_id", 0)))


def _plane(buf, span) -> dict:
    out = {"name": "", "lines": [], "meta": {}, "stat_names": {}}
    for f, _, v in _fields(buf, *span):
        if f == 2:
            out["name"] = _text(buf, v)
        elif f == 3:
            out["lines"].append(v)
        elif f == 4:
            key, val = _map_entry(buf, v)
            out["meta"][key] = val
        elif f == 5:
            key, val = _map_entry(buf, v)
            for f2, _, v2 in _fields(buf, *val):
                if f2 == 2:
                    out["stat_names"][key] = _text(buf, v2)
    return out


def _event_metadata(buf, span, stat_names: dict):
    """``(name, stats)`` of one ``XEventMetadata``."""
    name, stats = "", {}
    for f, _, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 5:
            k, val = _stat(buf, v, stat_names)
            stats[k] = val
    return name, stats


def _line(buf, span):
    """``(name, [(start_ns, end_ns, metadata_id)])`` on the clock
    ``ProfileData`` gives, whole nanoseconds cut from picoseconds, so
    that this reader and ``benchmarks/xplane.py`` see the same nesting."""
    name, t0, events = "", 0, []
    for f, _, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            t0 = v
        elif f == 4:
            events.append(v)
    out = []
    for ev in events:
        mid = off = dur = 0
        for f, _, v in _fields(buf, *ev):
            if f == 1:
                mid = v
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
        start = float(t0 + off // 1000)
        out.append((start, start + dur // 1000, mid))
    return name, out


# -------------------------------------------------------------- HloProto

class Instr(NamedTuple):
    name: str
    opcode: str
    operands: tuple  # instruction ids
    called: tuple  # computation ids
    op_name: str
    source: str
    comp: int
    target: str  # custom_call_target
    index: int  # tuple_index of a get-tuple-element
    shape: str  # "s32[32,15000576]", a tuple's in parentheses; no layout


class Program(NamedTuple):
    name: str
    instrs: dict  # id -> Instr
    by_name: dict  # name -> id
    roots: dict  # computation id -> root instruction id
    entry: int  # entry computation id
    users: dict  # id -> [ids of its users]
    caller: dict  # computation id -> id of the instruction that calls it
    flow_names: frozenset  # op_names of while / conditional / call

    def of(self, op_name: str):
        return self.by_name.get(op_name.lstrip("%"))


# xla_data.proto PrimitiveType, as HLO text spells it
_PRIMITIVE = {1: "pred", 2: "s8", 3: "s16", 4: "s32", 5: "s64", 6: "u8",
              7: "u16", 8: "u32", 9: "u64", 10: "f16", 11: "f32", 12: "f64",
              15: "c64", 16: "bf16", 17: "token", 18: "c128"}


def _shape(buf, span) -> str:
    """A ``ShapeProto`` as HLO text writes it, without layout."""
    etype, dims, parts = 0, [], []
    for f, wt, v in _fields(buf, *span):
        if f == 2:
            etype = v
        elif f == 3:
            dims += _ints(buf, wt, v)
        elif f == 4:
            parts.append(_shape(buf, v))
    if etype == 13:
        return "(" + ", ".join(parts) + ")"
    return (_PRIMITIVE.get(etype, f"type{etype}")
            + "[" + ",".join(map(str, dims)) + "]")


def _instruction(buf, span, comp: int):
    name = opcode = op_name = src_file = target = shape = ""
    src_line = iid = index = 0
    operands, called = [], []
    for f, wt, v in _fields(buf, *span):
        if f == 1:
            name = _text(buf, v)
        elif f == 2:
            opcode = _text(buf, v)
        elif f == 3:
            shape = _shape(buf, v)
        elif f == 7:
            for f2, _, v2 in _fields(buf, *v):
                if f2 == 2:
                    op_name = _text(buf, v2)
                elif f2 == 3:
                    src_file = _text(buf, v2)
                elif f2 == 4:
                    src_line = v2
        elif f == 13:
            index = v
        elif f == 28:
            target = _text(buf, v)
        elif f == 35:
            iid = v
        elif f == 36:
            operands += _ints(buf, wt, v)
        elif f == 38:
            called += _ints(buf, wt, v)
    source = f"{src_file}:{src_line}" if src_file else ""
    return iid, Instr(name, opcode, tuple(operands), tuple(called), op_name,
                      source, comp, target, index, shape)


def _program(buf, span) -> Program:
    """Of an ``HloProto``: its ``hlo_module``, read by :func:`_module`."""
    empty = (span[0], span[0])
    return _module(buf, next(
        (v for f, _, v in _fields(buf, *span) if f == 1), empty))


def program_of_module(module_proto: bytes) -> Program:
    """The :class:`Program` of a serialized ``HloModuleProto``, which is
    what a compiled executable gives with no trace at all
    (``compiled.runtime_executable().hlo_modules()[0]
    .as_serialized_hlo_module_proto()``): tests/test_chip_compile.py
    reads the deviceless compile's while body with it."""
    return _module(module_proto, (0, len(module_proto)))


def _module(buf, span) -> Program:
    """Of an ``HloModuleProto``: the computations and, per instruction,
    name, opcode, shape, operands, called computations and metadata."""
    name, entry, comps = "", 0, []
    for f, _, v in _fields(buf, *span):
        if f == 1:
            name = _text(buf, v)
        elif f == 3:
            comps.append(v)
        elif f == 6:
            entry = v
    instrs, roots = {}, {}
    for comp in comps:
        cid, root, spans = 0, 0, []
        for f, _, v in _fields(buf, *comp):
            if f == 2:
                spans.append(v)
            elif f == 5:
                cid = v
            elif f == 6:
                root = v
        roots[cid] = root
        for sp in spans:
            iid, ins = _instruction(buf, sp, cid)
            instrs[iid] = ins
    users, caller = {}, {}
    for iid, ins in instrs.items():
        for o in ins.operands:
            users.setdefault(o, []).append(iid)
        for c in ins.called:
            caller[c] = iid
    return Program(name, instrs, {i.name: k for k, i in instrs.items()},
                   roots, entry, users, caller, frozenset(
                       i.op_name for i in instrs.values()
                       if i.opcode in ("while", "conditional", "call")))


# ------------------------------------------------------------------ read

def read(path: str) -> dict:
    """``{"devices": {plane: {"ops": [(start_ns, end_ns, Op)], "modules":
    [(start_ns, end_ns, name, program_id)]}}, "host": [(start_ns, end_ns,
    name)], "programs": {program_id: Program}}`` of one ``.xplane.pb``
    (or ``.gz`` of one)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as fh:
        buf = fh.read()
    devices, host, programs = {}, [], {}
    for f, _, v in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        plane = _plane(buf, v)
        names = plane["stat_names"]
        if plane["name"].startswith(_DEVICE_PREFIX):
            ops_of = {}
            found = {_OPS_LINE: [], _MODULES_LINE: []}
            for span in plane["lines"]:
                name, events = _line(buf, span)
                if name not in found:
                    continue
                for s, e, mid in events:
                    if mid not in ops_of:
                        ops_of[mid] = _parse_op(*_event_metadata(
                            buf, plane["meta"][mid], names))
                    found[name].append((s, e, ops_of[mid]))
            if found[_OPS_LINE]:
                devices[plane["name"]] = {
                    "ops": found[_OPS_LINE],
                    "modules": [(s, e, op.name, op.program_id)
                                for s, e, op in found[_MODULES_LINE]]}
        elif plane["name"] == _HOST_PLANE:
            texts = {}
            for span in plane["lines"]:
                for s, e, mid in _line(buf, span)[1]:
                    if mid not in texts:
                        texts[mid] = _event_metadata(
                            buf, plane["meta"][mid], names)[0]
                    host.append((s, e, texts[mid]))
        elif plane["name"] == _HLO_PLANE:
            for pid, meta in plane["meta"].items():
                for f2, _, v2 in _fields(buf, *meta):
                    if f2 != 5:
                        continue
                    for f3, _, v3 in _fields(buf, *v2):
                        if f3 == 6:  # bytes_value: the HloProto
                            programs[pid] = _program(buf, v3)
    return {"devices": devices, "host": sorted(host), "programs": programs}


# ------------------------------------------------------------- intervals

def union(events: list) -> list:
    merged = []
    for s, e in sorted((ev[0], ev[1]) for ev in events):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(events: list) -> float:
    return float(sum(e - s for s, e in union(events)))


def leaves_only(events: list) -> list:
    """Drop events that wholly contain another: a ``while`` or a ``call``
    spans the ops of its body (the rule of ``benchmarks/xplane.py``; a
    tier-1 test holds the two readers to the same numbers)."""
    out, stack = [], []
    for ev in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][1] <= ev[0]:
            out.append(stack.pop())
        if stack and ev[1] <= stack[-1][1]:
            stack.pop()
        stack.append(ev)
    return out + stack


def _span_of(trace: dict, name: str):
    found = [h for h in trace["host"] if h[2] == name]
    if not found:
        raise ValueError(f"no host span named {name!r} in the trace")
    return found[0][0], found[0][1]


def _clip(events: list, lo: float, hi: float) -> list:
    out = []
    for ev in events:
        s, e = max(ev[0], lo), min(ev[1], hi)
        if e > s:
            out.append((s, e) + tuple(ev[2:]))
    return out


# ------------------------------------------------------------- attribute

class Row(NamedTuple):
    start_ns: float
    end_ns: float
    program: str
    instruction: str
    opcode: str
    scope: str
    tail: str  # "dyn" of lgbm.split_step.dyn
    cause: str  # "program", or "from > into[ layout]"
    bytes: int
    source: str


def scope_of(op_name: str):
    """``(scope, tail)`` of the innermost ``lgbm.*`` component, or None.
    A component under no name of :data:`SCOPES` is its own scope."""
    for comp in reversed(op_name.rstrip(":").split("/")):
        if comp.startswith("lgbm."):
            fits = [s for s in SCOPE_NAMES
                    if comp == s or comp.startswith(s + ".")]
            if not fits:
                return comp, ""
            best = max(fits, key=len)
            return best, comp[len(best) + 1:]
    return None


def _wrote(prog: Program, op_name: str) -> bool:
    """Did the program write the op of this ``op_name``?  XLA gives what
    it inserts no name, or that of the control flow it serves (a copy
    of the loop's carry reads ``jit(grow_tree)/while:``, as the
    ``while`` itself does)."""
    name = op_name.rstrip(":")
    return bool(name) and name not in prog.flow_names


def _enclosing_scope(prog: Program, iid: int):
    """Scope of the nearest ``while`` / ``conditional`` / ``call`` above
    the instruction whose ``op_name`` holds one."""
    at = prog.caller.get(prog.instrs[iid].comp)
    while at is not None:
        found = scope_of(prog.instrs[at].op_name)
        if found:
            return found
        at = prog.caller.get(prog.instrs[at].comp)
    return None


def _resolve_scope(prog, iid, op_name: str):
    found = scope_of(op_name)
    if found:
        return found
    if iid is None:
        return UNATTRIBUTED, ""
    found = _enclosing_scope(prog, iid)
    if found:
        return found
    for user in prog.users.get(iid, ()):
        found = (scope_of(prog.instrs[user].op_name)
                 or _enclosing_scope(prog, user))
        if found:
            return found
    return UNATTRIBUTED, ""


def _kind_of_comp(prog: Program, comp: int):
    """``("entry" | "while" | "branch" | "call", caller id, branch no)``."""
    if comp == prog.entry:
        return "entry", None, 0
    at = prog.caller.get(comp)
    if at is None:
        return "call", None, 0
    ins = prog.instrs[at]
    kind = {"while": "while", "conditional": "branch"}.get(ins.opcode, "call")
    return kind, at, ins.called.index(comp)


def _from(prog: Program, iid: int, hops: int = 16, path: tuple = ()):
    """``(kind, id)`` of where the value of instruction ``iid`` comes
    from, through bitcasts, async starts, tuples and branch parameters;
    ``path`` holds the tuple indices still to be taken of it."""
    ins = prog.instrs[iid]
    if hops == 0:
        return "op", iid
    if ins.opcode == "get-tuple-element":
        return _from(prog, ins.operands[0], hops - 1, (ins.index,) + path)
    if ins.opcode == "tuple" and path:
        return _from(prog, ins.operands[path[0]], hops - 1, path[1:])
    if ins.operands and (ins.opcode == "bitcast"
                         or ins.opcode.endswith("-start")):
        return _from(prog, ins.operands[0], hops - 1, path)
    if ins.opcode == "custom-call" and ins.target == _MOSAIC:
        return "kernel", iid
    if ins.opcode in ("conditional", "while"):
        return {"conditional": "cond", "while": "loop"}[ins.opcode], iid
    if ins.opcode == "parameter":
        kind, at, no = _kind_of_comp(prog, ins.comp)
        if kind == "entry":
            return "arg", iid
        if kind == "while":
            return "carry", at
        if kind == "branch":  # operand 0 is the predicate / the index
            return _from(prog, prog.instrs[at].operands[no + 1], hops - 1,
                         path)
    return "op", iid


def _into(prog: Program, iid: int, hops: int = 8):
    """``(kind, id)`` of what consumes the value of instruction ``iid``."""
    users = prog.users.get(iid, [])
    found = []
    for u in users:
        ins = prog.instrs[u]
        if hops and (ins.opcode == "bitcast" or ins.opcode.endswith("-done")):
            found.append(_into(prog, u, hops - 1))
        elif ins.opcode == "tuple" and prog.roots.get(ins.comp) == u:
            kind, at, _ = _kind_of_comp(prog, ins.comp)
            found.append(({"entry": "result", "while": "carry",
                           "branch": "branch_result"}.get(kind, "op"),
                          u if at is None else at))
        elif hops and ins.opcode == "tuple":
            found.append(_into(prog, u, hops - 1))
        elif ins.opcode == "while":
            found.append(("carry", u))
        elif ins.opcode == "conditional":
            found.append(("cond_arg", u))
        elif ins.opcode == "custom-call" and ins.target == _MOSAIC:
            found.append(("kernel", u))
        else:
            found.append(("op", u))
    for want in ("branch_result", "carry", "result", "cond_arg"):
        for kind, u in found:
            if kind == want:
                return kind, u
    if found and all(kind == "kernel" for kind, _ in found):
        return found[0]
    return ("op", found[0][1]) if found else ("op", None)


def _dims(shape: str) -> str:
    return shape.split("{", 1)[0]


def cause_of(prog: Program, iid: int, op: Op):
    """``(cause, producer id, consumer id)`` of an op the program did not
    write."""
    ins = prog.instrs[iid]
    src, producer = (_from(prog, ins.operands[0]) if ins.operands
                     else ("op", None))
    dst, consumer = _into(prog, iid)
    layout = (op.opcode == "copy" and op.operand_shape
              and _dims(op.shape) == _dims(op.operand_shape)
              and op.shape != op.operand_shape)
    return (f"{src} > {dst}" + (" layout" if layout else ""),
            producer, consumer)


def attribute(trace: dict, window: str | None = None) -> list:
    """One :class:`Row` for every leaf op of every device plane, inside
    the host span ``window`` if one is named."""
    rows = []
    lo, hi = _span_of(trace, window) if window else (-1e30, 1e30)
    resolved = {}
    for dev in trace["devices"].values():
        for s, e, op in leaves_only(_clip(dev["ops"], lo, hi)):
            key = (op.program_id, op.name)
            if key not in resolved:
                prog = trace["programs"].get(op.program_id)
                iid = prog.of(op.name) if prog else None
                name = op.tf_op or (
                    prog.instrs[iid].op_name if iid is not None else "")
                scope, tail = _resolve_scope(prog, iid, name)
                cause = ("program" if iid is None or _wrote(prog, name)
                         or prog.instrs[iid].target == _MOSAIC
                         else cause_of(prog, iid, op)[0])
                source = op.source or (
                    prog.instrs[iid].source if iid is not None else "")
                resolved[key] = (prog.name if prog else str(op.program_id),
                                 scope, tail, cause, source)
            program, scope, tail, cause, source = resolved[key]
            rows.append(Row(s, e, program, op.name, op.opcode, scope, tail,
                            cause, op.bytes, source))
    return rows


def newest_xplane(log_dir: str):
    """The ``.xplane.pb`` of the newest capture under a
    ``jax.profiler.start_trace`` directory (each capture gets a
    timestamped directory of its own), or None."""
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def seconds_by_scope(path: str) -> dict:
    """``{scope: device seconds}`` of a trace, ``unattributed`` among
    them: what the CLI's manifest records under ``phases``."""
    out = {}
    for r in attribute(read(path)):
        out[r.scope] = out.get(r.scope, 0.0) + (r.end_ns - r.start_ns) / 1e9
    return {k: round(v, 6) for k, v in sorted(out.items())}


# ---------------------------------------------------------------- report

def _runs(trace: dict, lo: float, hi: float) -> dict:
    """Program name -> how many of its runs start inside the window."""
    runs = {}
    for dev in trace["devices"].values():
        for s, _, name, pid in dev["modules"]:
            if lo <= s < hi:
                prog = trace["programs"].get(pid)
                key = prog.name if prog else name.split("(")[0]
                runs[key] = runs.get(key, 0) + 1
    return runs


def _chain(prog: Program, iid: int) -> str:
    """``conditional.100[1] < ... < while.57`` above an instruction."""
    out, comp = [], prog.instrs[iid].comp
    while True:
        kind, at, no = _kind_of_comp(prog, comp)
        if at is None:
            return " < ".join(out) or "(entry)"
        out.append(prog.instrs[at].name
                   + (f"[{no}]" if kind == "branch" else ""))
        comp = prog.instrs[at].comp


def copy_ledger(trace: dict, rows: list, program: str,
                share: float = 0.01) -> list:
    """Every op of ``program`` the program did not write that takes
    ``share`` of the program's leaf-op time or more, longest first."""
    mine = [r for r in rows if r.program == program]
    total = sum(r.end_ns - r.start_ns for r in mine)
    by = {}
    for r in mine:
        if r.cause != "program":
            t = by.setdefault(r.instruction, [0.0, 0, r])
            t[0] += r.end_ns - r.start_ns
            t[1] += 1
    prog = next((p for p in trace["programs"].values()
                 if p.name == program), None)
    ops = {op.name: op for dev in trace["devices"].values()
           for _, _, op in dev["ops"] if prog and
           trace["programs"].get(op.program_id) is prog}
    out = []
    for name, (ns, count, r) in sorted(by.items(), key=lambda kv: -kv[1][0]):
        if not total or ns / total < share:
            break
        line = {"instruction": name, "ns": ns, "share": ns / total,
                "count": count, "scope": r.scope, "tail": r.tail,
                "cause": r.cause, "bytes": r.bytes}
        iid = prog.of(name) if prog else None
        if iid is not None:
            _, producer, consumer = cause_of(prog, iid, ops[name])
            named = ["" if i is None else prog.instrs[i].name
                     for i in (producer, consumer)]
            line.update(
                shape=ops[name].shape, operand_shape=ops[name].operand_shape,
                producer=named[0], consumer=named[1],
                chain=_chain(prog, iid),
                # the HloProto of this XLA keeps stack-frame ids, not
                # files: the neighbour's own event has the line
                source=next((ops["%" + n].source for n in named
                             if "%" + n in ops and ops["%" + n].source),
                            r.source))
        out.append(line)
    return out


def idle_by_host_span(trace: dict, lo: float, hi: float) -> dict:
    """Idle time of the device inside ``[lo, hi]`` by the innermost
    ``lgbm.*`` host span that overlaps it; the rest is ``(no span)``."""
    ops = [ev for dev in trace["devices"].values()
           for ev in _clip(dev["ops"], lo, hi)]
    spans = [h for h in trace["host"] if h[2].startswith("lgbm.")]
    out, at = {}, lo
    for s, e in union(ops) + [[hi, hi]]:
        if s > at:
            cuts = sorted({at, s} | {t for h in spans for t in h[:2]
                                     if at < t < s})
            for a, b in zip(cuts, cuts[1:]):
                over = [h for h in spans if h[0] <= a and h[1] >= b]
                name = max(over)[2] if over else "(no span)"
                out[name] = out.get(name, 0.0) + (b - a)
        at = max(at, e)
    return out


def report(trace: dict, program: str | None = None,
           window: str | None = None, top: int = 40) -> dict:
    rows = attribute(trace, window)
    if not rows:
        raise ValueError("no device plane with an 'XLA Ops' line: only a "
                         "trace taken on a TPU can be attributed")
    lo, hi = _span_of(trace, window) if window else (
        min(r.start_ns for r in rows), max(r.end_ns for r in rows))
    runs = _runs(trace, lo, hi)
    programs = {}
    for r in rows:
        if program and r.program != program:
            continue
        p = programs.setdefault(r.program, {"ns": 0.0, "by": {}, "un": {}})
        ns = r.end_ns - r.start_ns
        p["ns"] += ns
        key = (r.scope, r.cause)
        p["by"][key] = p["by"].get(key, 0.0) + ns
        if r.scope == UNATTRIBUTED:
            p["un"][r.instruction] = p["un"].get(r.instruction, 0.0) + ns
    out = {"window_ns": hi - lo, "programs": {}, "ledger": {},
           "idle_ns": idle_by_host_span(trace, lo, hi)}
    for name, p in sorted(programs.items(), key=lambda kv: -kv[1]["ns"]):
        n = max(runs.get(name, 1), 1)
        out["programs"][name] = {
            "runs": n, "ms_per_run": p["ns"] / 1e6 / n,
            "attributed_share": 1.0 - sum(p["un"].values()) / p["ns"],
            "scope_cause": [
                [scope, cause, ns / 1e6 / n, ns / p["ns"]]
                for (scope, cause), ns in sorted(
                    p["by"].items(), key=lambda kv: -kv[1])[:top]],
            "unattributed": [
                [instr, ns / 1e6 / n] for instr, ns in sorted(
                    p["un"].items(), key=lambda kv: -kv[1])[:top]]}
        out["ledger"][name] = copy_ledger(trace, rows, name)[:top]
    return out


def _print(rep: dict) -> None:
    for name, p in rep["programs"].items():
        print(f"\n== {name}: {p['ms_per_run']:.3f} ms/run over {p['runs']} "
              f"run(s), {100 * p['attributed_share']:.2f}% attributed")
        print(f"{'scope':<20}{'cause':<36}{'ms/run':>12}{'share':>9}")
        for scope, cause, ms, share in p["scope_cause"]:
            print(f"{scope:<20}{cause:<36}{ms:>12.3f}{100 * share:>8.2f}%")
        shown = [(i, ms) for i, ms in p["unattributed"] if ms >= 0.0005]
        for instr, ms in shown:
            print(f"  unattributed {instr}: {ms:.3f} ms/run")
        if len(shown) < len(p["unattributed"]):
            print(f"  unattributed: {len(p['unattributed']) - len(shown)} "
                  "more of the top under 0.0005 ms/run each (--json)")
        if rep["ledger"][name]:
            print(f"-- copy ledger of {name} (ops the program did not "
                  "write, 1% of it or more)")
        for c in rep["ledger"][name]:
            n = p["runs"]
            print(f"{c['instruction']}  {c['ns'] / 1e6 / n:.3f} ms/run "
                  f"({100 * c['share']:.2f}%) x{c['count'] / n:g}/run  "
                  f"{c['cause']}  [{c['scope']}"
                  f"{'.' + c['tail'] if c['tail'] else ''}]")
            if "shape" in c:
                print(f"    {c['bytes']} B  {c['shape']} <- "
                      f"{c['operand_shape'] or '?'}")
                print(f"    from {c['producer'] or '?'}  into "
                      f"{c['consumer'] or '?'}  near {c['source'] or '?'}")
                print(f"    in {c['chain']}")
    print(f"\n== device idle in the window ({rep['window_ns'] / 1e6:.3f} ms)")
    for name, ns in sorted(rep["idle_ns"].items(), key=lambda kv: -kv[1]):
        print(f"{name:<28}{ns / 1e6:>12.3f} ms")


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu.obs.device_time",
        description="device time by program scope and cause")
    ap.add_argument("xplane", help="a .xplane.pb (or .gz of one)")
    ap.add_argument("--program", help="only this program, e.g. jit_grow_tree")
    ap.add_argument("--window", help="only inside this host span")
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--json", action="store_true")
    a = ap.parse_args(argv)
    rep = report(read(a.xplane), a.program, a.window, a.top)
    if a.json:
        json.dump(rep, sys.stdout, indent=1)
    else:
        _print(rep)
    return 0


if __name__ == "__main__":
    sys.exit(main())
