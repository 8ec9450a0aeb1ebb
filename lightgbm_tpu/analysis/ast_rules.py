"""jaxlint stage 1: AST rules over the package source.

Scope model
-----------
A function is **traced** when its body runs under ``jax.jit`` tracing:

* decorated with ``jax.jit`` / ``functools.partial(jax.jit, ...)``,
* wrapped at module level (``f = jax.jit(g)``) or lazily
  (``self._jfn = jax.jit(self.eval_jax)`` marks same-file methods named
  ``eval_jax``),
* lexically nested inside a traced function, or
* called (by simple name, including through ``functools.partial``)
  from a traced function in the same module — a fixpoint over the
  module-local call graph, so helpers like the tier-chain builders in
  ``learners/serial.py`` are correctly treated as trace-time code.

A function is **hot** when its module lives under ``learners/``,
``ops/``, ``parallel/``, or is ``models/gbdt.py`` / ``engine.py`` —
the per-iteration training path where a host sync inside a Python loop
drains the dispatch pipeline every tree (the class of regression the
round-3 lagged-stop work was built against; its cost on this machine is
not measured).

Suppression: append ``# jaxlint: disable=<rule>[,<rule>]`` to the
flagged line, or put ``# jaxlint: disable-file=<rule>`` on any line to
suppress a rule for the whole file.  Suppressions are for sites where
the flagged behavior is INTENTIONAL and documented (e.g. the f64
reference-parity accumulation in metrics.py) — not a way to mute real
findings.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

# ---------------------------------------------------------------- findings

@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    message: str

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


class _Loc:
    """Synthetic location carrier for findings computed after the walk
    (only ``lineno`` is read by :meth:`_RuleWalker.flag`)."""

    __slots__ = ("lineno",)

    def __init__(self, lineno: int) -> None:
        self.lineno = lineno


# rule id -> one-line description (the CLI prints this table)
AST_RULES: Dict[str, str] = {
    "host-sync-in-jit": (
        "host transfer/materialization (np.asarray/np.array, .item(), "
        ".tolist(), jax.device_get, .block_until_ready()) inside a "
        "jit-traced function: executes at trace time on tracers (error "
        "or silent constant-folding) and defeats async dispatch"
    ),
    "python-loop-over-device-array": (
        "Python for-loop iterating a device array inside a jit-traced "
        "function: unrolls the trace per element and syncs per element "
        "when leaked to eager code"
    ),
    "env-read-at-trace": (
        "os.environ read inside a jit-traced function: the value is "
        "baked at trace time but the jit cache keys only on shapes/"
        "statics, so a mid-process env flip silently does not apply — "
        "read once at module import instead (ADVICE r3 convention)"
    ),
    "f64-literal-in-traced": (
        "explicit float64 dtype in jit-traced code: under default "
        "x64-disabled semantics this silently truncates to f32, and "
        "under enable_x64 it doubles histogram/score bandwidth — gate "
        "deliberate f64 paths behind a file-level suppression with the "
        "justification in a comment"
    ),
    "jit-cache-miss-risk": (
        "jax.jit of a lambda inside a function body, or any jax.jit "
        "call inside a loop: every evaluation builds a fresh callable "
        "with an empty jit cache, retracing and recompiling per call"
    ),
    "host-sync-in-loop": (
        "host materialization (float(f(...)), int(f(...)), np.asarray, "
        "np.array, .item(), .tolist()) inside a Python loop in a hot "
        "module: one device sync per iteration drains the dispatch "
        "pipeline (cost on this machine: not measured)"
    ),
    "wallclock-without-sync": (
        "time.time()/perf_counter() stop timestamp around jax/jnp "
        "device computation with no block_until_ready/device_get/"
        "np.asarray sync before the stop: async dispatch returns "
        "before the device finishes, so the elapsed time measures "
        "dispatch, not compute (the mis-timing hazard behind every "
        "too-good-to-be-true bench number)"
    ),
    "raw-artifact-write": (
        "open(path, 'w'/'x') or json.dump(obj, open(...)) writes an "
        "artifact non-atomically: a preemption mid-write leaves half a "
        "file under the real name (a truncated model silently LOADS, "
        "with fewer trees).  Route result artifacts through "
        "resilience.atomic_write / atomic_write_json / atomic_writer "
        "(tmp + fsync + rename); append-mode logs are exempt"
    ),
    "device-buffer-retention": (
        "module-global or class-attribute assignment of a jax/jnp "
        "device value from runtime code in a hot/serving/obs module: "
        "the buffer is pinned in device memory for the process "
        "lifetime, invisible to owner-attributed census accounting "
        "(obs/memory.py) and to hot-swap reclamation.  Keep device "
        "buffers on instances registered via obs.memory.register_owner "
        "(docs/memory.md), or suppress with the justification inline"
    ),
    "unbounded-event-buffer": (
        "append/extend to a module-level list from function code in a "
        "hot/serving/obs module with no maxlen/ring discipline: a "
        "long-lived serving replica grows it without bound until the "
        "host OOMs (per-request event logs are the classic case).  Use "
        "collections.deque(maxlen=N) — append+evict is one atomic, "
        "capped operation (obs/flightrec.py's ring is the pattern)"
    ),
}

_HOT_DIR_PARTS = ("learners", "ops", "parallel")
_HOT_FILES = ("gbdt.py", "engine.py")
# unbounded-event-buffer scope: the hot modules PLUS the long-lived
# server/observability tiers, where an uncapped event list outlives
# every request that fed it
_EVENT_SCOPE_DIR_PARTS = ("serving", "obs")

_NP_NAMES = {"np", "numpy", "onp"}
# numpy calls that pull data to (or materialize on) the host; pure
# host-side allocation (zeros/ones/empty/arange/...) is NOT flagged
_NP_SYNC_FUNCS = {"asarray", "array", "ascontiguousarray"}
_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
# iteration wrappers that never yield a device array element-by-element
_SAFE_ITER_CALLS = {
    "range", "enumerate", "zip", "reversed", "sorted", "len", "list",
    "tuple", "dict", "set", "items", "keys", "values", "split",
    "splitlines", "product", "combinations", "chain",
}

_PRAGMA_LINE = re.compile(r"#\s*jaxlint:\s*disable=([\w,\-]+)")
_PRAGMA_FILE = re.compile(r"#\s*jaxlint:\s*disable-file=([\w,\-]+)")

# wallclock-without-sync machinery: wall-clock sources, device-compute
# roots, and the sync calls that make a stop timestamp honest
_TIME_CALLS = {"time.time", "time.perf_counter", "time.monotonic",
               "perf_counter", "monotonic"}
_DEVICE_ROOTS = {"jax", "jnp"}
_SYNC_LEAVES = {"block_until_ready", "device_get", "item", "tolist"}


def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_jax_jit(node: ast.AST) -> bool:
    return _dotted(node) in ("jax.jit", "jit", "pjit", "jax.pjit")


def _is_partial_of_jit(call: ast.Call) -> bool:
    if _dotted(call.func) not in ("functools.partial", "partial"):
        return False
    return bool(call.args) and _is_jax_jit(call.args[0])


def _jit_decorated(fn: ast.AST) -> bool:
    for dec in getattr(fn, "decorator_list", []):
        if _is_jax_jit(dec):
            return True
        if isinstance(dec, ast.Call):
            if _is_jax_jit(dec.func) or _is_partial_of_jit(dec):
                return True
    return False


class _ModuleIndex(ast.NodeVisitor):
    """Collect module functions, jit roots, and the name-level call
    graph in one pass."""

    def __init__(self) -> None:
        self.functions: Dict[str, List[ast.AST]] = {}
        self.jit_roots: Set[str] = set()
        self.calls: Dict[str, Set[str]] = {}
        self._stack: List[str] = []

    def _add_fn(self, node: ast.AST) -> None:
        name = node.name  # type: ignore[attr-defined]
        self.functions.setdefault(name, []).append(node)
        if _jit_decorated(node):
            self.jit_roots.add(name)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._add_fn(node)
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def visit_Call(self, node: ast.Call) -> None:
        callee: Optional[str] = None
        func_name = _dotted(node.func)
        if _is_jax_jit(node.func) and node.args:
            # f = jax.jit(g) / self._jfn = jax.jit(self.eval_jax):
            # mark the wrapped function (by trailing name) as a root
            target = _dotted(node.args[0])
            if target is not None:
                self.jit_roots.add(target.split(".")[-1])
        if func_name is not None:
            if func_name in ("functools.partial", "partial") and node.args:
                inner = _dotted(node.args[0])
                if inner is not None:
                    callee = inner.split(".")[-1]
            else:
                callee = func_name.split(".")[-1]
        if callee and self._stack:
            self.calls.setdefault(self._stack[-1], set()).add(callee)
        self.generic_visit(node)


def _traced_functions(index: _ModuleIndex) -> Set[str]:
    """Fixpoint: jit roots + same-module functions they (transitively)
    call by name."""
    traced = set(index.jit_roots) & set(index.functions)
    changed = True
    while changed:
        changed = False
        for name in list(traced):
            for callee in index.calls.get(name, ()):
                if callee in index.functions and callee not in traced:
                    traced.add(callee)
                    changed = True
    return traced


class _RuleWalker(ast.NodeVisitor):
    """Walk one function body with (traced, hot, loop-depth) context."""

    def __init__(self, path: str, traced: bool, hot: bool,
                 findings: List[Finding],
                 jit_roots: Optional[Set[str]] = None,
                 module_lists: Optional[Set[str]] = None,
                 event_scope: bool = False,
                 module_classes: Optional[Set[str]] = None) -> None:
        self.path = path
        self.traced = traced
        self.hot = hot
        self.findings = findings
        self.loop_depth = 0
        self.jit_roots = jit_roots or set()
        # unbounded-event-buffer context: module-level bare-list names
        # (no maxlen discipline possible) + whether this module is a
        # hot/serving/obs scope the rule applies to
        self.module_lists = module_lists or set()
        self.event_scope = event_scope
        # device-buffer-retention context: module-level class names
        # (a ClassName.attr store is process-lifetime retention) and
        # names this function declared ``global``
        self.module_classes = module_classes or set()
        self._global_names: Set[str] = set()
        # wallclock-without-sync event streams (line-ordered within the
        # walked function; nested defs are walked separately)
        self._time_marks: Dict[str, List[int]] = {}
        self._device_lines: List[int] = []
        self._sync_lines: List[int] = []
        self._stops: List[Tuple[int, str]] = []

    def flag(self, rule: str, node: ast.AST, msg: str) -> None:
        self.findings.append(
            Finding(rule, self.path, getattr(node, "lineno", 0), msg))

    # nested defs are visited separately (lint_source's visit_scope)
    # with their own traced context — do not descend into them here
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        pass

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def visit_For(self, node: ast.For) -> None:
        if self.traced and not self._safe_iterable(node.iter):
            desc = _dotted(node.iter) or type(node.iter).__name__
            self.flag(
                "python-loop-over-device-array", node,
                f"for-loop iterates '{desc}' directly inside traced "
                "code; iterate range()/static containers or use "
                "lax.fori_loop/scan",
            )
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    def visit_While(self, node: ast.While) -> None:
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    @staticmethod
    def _is_host_numpy_call(call: ast.Call) -> bool:
        """float(np.searchsorted(...))-style conversions of host-numpy
        results are host compute, not a device sync."""
        name = _dotted(call.func)
        return name is not None and name.split(".")[0] in _NP_NAMES

    @staticmethod
    def _safe_iterable(it: ast.AST) -> bool:
        if isinstance(it, (ast.List, ast.Tuple, ast.Dict, ast.Set,
                           ast.Constant, ast.GeneratorExp, ast.ListComp)):
            return True
        if isinstance(it, ast.Call):
            name = _dotted(it.func)
            if name is None:
                return False
            leaf = name.split(".")[-1]
            if leaf in _SAFE_ITER_CALLS:
                return True
            # sorted(x)/reversed(x)/zip(...) handled above by leaf name
            return False
        return False

    def visit_Subscript(self, node: ast.Subscript) -> None:
        self._check_environ(node, node.value)
        self.generic_visit(node)

    # -------------------------------------------- wallclock-without-sync
    @staticmethod
    def _is_time_call(node: ast.AST) -> bool:
        return (isinstance(node, ast.Call)
                and _dotted(node.func) in _TIME_CALLS)

    def visit_Assign(self, node: ast.Assign) -> None:
        if self._is_time_call(node.value):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    self._time_marks.setdefault(tgt.id, []).append(
                        node.lineno)
        self._check_buffer_retention(node, node.targets, node.value)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self._check_buffer_retention(node, [node.target], node.value)
        self.generic_visit(node)

    def visit_Global(self, node: ast.Global) -> None:
        self._global_names.update(node.names)

    # ------------------------------------------ device-buffer-retention
    def _is_device_value(self, value: ast.AST) -> bool:
        """A jax/jnp-rooted call (or a call into one of this module's
        jit roots) — the expressions whose results live in device
        memory.  Host numpy and plain Python values are not flagged."""
        if not isinstance(value, ast.Call):
            return False
        if _is_jax_jit(value.func) or _is_partial_of_jit(value):
            # a cached jitted CALLABLE retains compiled code, not a
            # device buffer — the idiomatic module-level dispatch cache
            return False
        name = _dotted(value.func)
        if name is None:
            return False
        root, leaf = name.split(".")[0], name.split(".")[-1]
        return root in _DEVICE_ROOTS or leaf in self.jit_roots

    def _check_buffer_retention(self, node: ast.AST,
                                targets: List[ast.AST],
                                value: ast.AST) -> None:
        """device-buffer-retention: ``global NAME; NAME = jnp.f(...)``
        or ``ClassName.attr = jnp.f(...)`` from runtime code in an
        event-scope module parks a device buffer where no census owner
        can see it and no teardown frees it.  Instance attributes
        (``self.x = ...``) stay legal — they die with their owner."""
        if not self.event_scope or not self._is_device_value(value):
            return
        for tgt in targets:
            if isinstance(tgt, ast.Name) and tgt.id in self._global_names:
                self.flag(
                    "device-buffer-retention", node,
                    f"global '{tgt.id}' is bound to a device value from "
                    "runtime code: the buffer outlives every request and "
                    "is invisible to owner-attributed census accounting "
                    "— keep it on an instance registered via "
                    "obs.memory.register_owner (docs/memory.md)",
                )
            elif isinstance(tgt, ast.Attribute):
                root = tgt.value
                if (isinstance(root, ast.Name)
                        and root.id in self.module_classes):
                    self.flag(
                        "device-buffer-retention", node,
                        f"class attribute '{root.id}.{tgt.attr}' is bound "
                        "to a device value from runtime code: a "
                        "process-lifetime pin shared across instances, "
                        "invisible to census owner attribution — keep "
                        "device buffers on instances registered via "
                        "obs.memory.register_owner (docs/memory.md)",
                    )

    def visit_BinOp(self, node: ast.BinOp) -> None:
        # stop timestamp: `time.perf_counter() - t0` (t0 a recorded mark)
        if (isinstance(node.op, ast.Sub) and self._is_time_call(node.left)
                and isinstance(node.right, ast.Name)):
            self._stops.append((node.lineno, node.right.id))
        self.generic_visit(node)

    def _note_wallclock_call(self, node: ast.Call, name: Optional[str],
                             leaf: Optional[str]) -> None:
        """Record device-compute and sync events for the linear
        wallclock scan.  Device compute = a jax/jnp-rooted call (minus
        the sync API) or a call into one of this module's jit roots;
        sync = anything that blocks on device results."""
        line = getattr(node, "lineno", 0)
        if name is not None:
            root = name.split(".")[0]
            if leaf in _SYNC_LEAVES or (root in _NP_NAMES
                                        and leaf in _NP_SYNC_FUNCS):
                self._sync_lines.append(line)
                return
            if leaf in ("float", "int") and name == leaf:
                # float(x)/int(x) of a device scalar is a sync; of host
                # data it is harmless — treating it as a sync errs on
                # the quiet side for THIS rule (host-sync-in-loop owns
                # the opposite direction)
                self._sync_lines.append(line)
                return
            if name.startswith(("jax.profiler.", "jax.config.",
                                "jax.monitoring.")):
                return  # harness/profiler API, not device compute
            if root in _DEVICE_ROOTS or leaf in self.jit_roots:
                self._device_lines.append(line)
        elif isinstance(node.func, ast.Attribute) \
                and node.func.attr in _SYNC_LEAVES:
            self._sync_lines.append(line)

    def finish(self) -> None:
        """Evaluate collected wallclock stop timestamps (called once
        after the whole function body is visited).  Traced code is
        exempt: a wall-clock read there is trace-time Python with its
        own failure mode (it would be constant-folded), not an async
        mis-timing."""
        if self.traced:
            return
        for stop_line, mark in self._stops:
            starts = [ln for ln in self._time_marks.get(mark, ())
                      if ln < stop_line]
            if not starts:
                continue
            start_line = max(starts)
            devs = [ln for ln in self._device_lines
                    if start_line < ln <= stop_line]
            syncs = [ln for ln in self._sync_lines
                     if start_line < ln <= stop_line]
            if devs and not syncs:
                self.flag(
                    "wallclock-without-sync",
                    _Loc(stop_line),
                    f"elapsed-time stop at line {stop_line} times device "
                    f"work dispatched at line(s) {devs} with no "
                    "block_until_ready()/device_get/np.asarray before "
                    "the stop: async dispatch makes this measure launch "
                    "cost, not compute",
                )

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if self.traced and _dotted(node) in ("jnp.float64", "np.float64",
                                             "numpy.float64",
                                             "jax.numpy.float64"):
            self.flag(
                "f64-literal-in-traced", node,
                f"explicit {_dotted(node)} in traced code",
            )
        self.generic_visit(node)

    def _check_environ(self, node: ast.AST, value: ast.AST) -> None:
        if self.traced and _dotted(value) in ("os.environ", "environ"):
            self.flag(
                "env-read-at-trace", node,
                "os.environ read at trace time: hoist to a module-level "
                "read (jit caches do not key on env)",
            )

    # --------------------------------------------- raw-artifact-write
    @staticmethod
    def _write_mode_of(call: ast.Call) -> Optional[str]:
        """The constant mode string of an ``open()`` call when it is a
        WRITE mode ('w'/'x' family; 'a' append and 'r+' update are
        exempt — logs and in-place patching are not artifact writes)."""
        mode = None
        if len(call.args) >= 2:
            mode = call.args[1]
        for kw in call.keywords:
            if kw.arg == "mode":
                mode = kw.value
        if isinstance(mode, ast.Constant) and isinstance(mode.value, str):
            if mode.value and mode.value[0] in ("w", "x"):
                return mode.value
        return None

    def _check_raw_write(self, node: ast.Call, name: Optional[str]) -> None:
        if name == "open" and self._write_mode_of(node) is not None:
            self.flag(
                "raw-artifact-write", node,
                f"open(..., {self._write_mode_of(node)!r}) writes "
                "non-atomically: a crash mid-write leaves a truncated "
                "file under the real name — use resilience.atomic_write"
                "/atomic_writer (tmp + fsync + rename)",
            )
        elif name in ("json.dump",) and len(node.args) >= 2:
            f = node.args[1]
            if (isinstance(f, ast.Call) and _dotted(f.func) == "open"
                    and self._write_mode_of(f) is not None):
                self.flag(
                    "raw-artifact-write", node,
                    "json.dump(obj, open(..., 'w')) writes an artifact "
                    "non-atomically — use resilience.atomic_write_json",
                )

    def _check_event_buffer(self, node: ast.Call,
                            name: Optional[str]) -> None:
        """unbounded-event-buffer: ``MODLIST.append(...)`` / ``.extend``
        where MODLIST is a module-level bare list and this module is a
        hot/serving/obs scope.  Module-import-time appends never reach
        here (the walker only visits function bodies), so one-shot
        registry building at import stays legal."""
        if not self.event_scope or name is None:
            return
        parts = name.split(".")
        if (len(parts) == 2 and parts[1] in ("append", "extend")
                and parts[0] in self.module_lists):
            self.flag(
                "unbounded-event-buffer", node,
                f"{parts[0]}.{parts[1]}() grows the module-level list "
                f"'{parts[0]}' from request/runtime code with no "
                "maxlen/ring discipline — a long-lived server "
                "accumulates it forever; use collections.deque("
                "maxlen=N) (obs/flightrec.py's ring is the pattern)",
            )

    def visit_Call(self, node: ast.Call) -> None:
        name = _dotted(node.func)
        leaf = name.split(".")[-1] if name else None

        self._note_wallclock_call(node, name, leaf)
        self._check_raw_write(node, name)
        self._check_event_buffer(node, name)

        # env-read-at-trace: os.environ.get(...) / os.getenv(...)
        if self.traced and name in ("os.environ.get", "os.getenv",
                                    "environ.get", "getenv"):
            self.flag(
                "env-read-at-trace", node,
                "os.environ read at trace time: hoist to a module-level "
                "read (jit caches do not key on env)",
            )

        # host-sync-in-jit
        if self.traced:
            if (name is not None
                    and name.split(".")[0] in _NP_NAMES
                    and leaf in _NP_SYNC_FUNCS):
                self.flag(
                    "host-sync-in-jit", node,
                    f"{name}() materializes on host inside traced code "
                    "(use jnp, or move the host work outside the jit)",
                )
            elif name in ("jax.device_get", "device_get"):
                self.flag(
                    "host-sync-in-jit", node,
                    "jax.device_get inside traced code",
                )
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _SYNC_METHODS):
                self.flag(
                    "host-sync-in-jit", node,
                    f".{node.func.attr}() forces a host sync inside "
                    "traced code",
                )

        # jit-cache-miss-risk
        if _is_jax_jit(node.func) and node.args:
            if isinstance(node.args[0], ast.Lambda):
                self.flag(
                    "jit-cache-miss-risk", node,
                    "jax.jit(lambda ...) builds a fresh callable (empty "
                    "jit cache) at every evaluation of this expression",
                )
            elif self.loop_depth > 0:
                self.flag(
                    "jit-cache-miss-risk", node,
                    "jax.jit called inside a loop: one retrace+compile "
                    "per iteration",
                )

        # host-sync-in-loop (hot, non-traced host code)
        if self.hot and not self.traced and self.loop_depth > 0:
            if (name is not None
                    and name.split(".")[0] in _NP_NAMES
                    and leaf in _NP_SYNC_FUNCS):
                self.flag(
                    "host-sync-in-loop", node,
                    f"{name}() inside a hot loop: one device->host "
                    "sync per iteration",
                )
            elif (leaf in ("float", "int") and name == leaf
                    and node.args
                    and isinstance(node.args[0], ast.Call)
                    and not self._is_host_numpy_call(node.args[0])):
                self.flag(
                    "host-sync-in-loop", node,
                    f"{leaf}(<call>) inside a hot loop materializes a "
                    "computed device value per iteration: batch the "
                    "fetches (one jax.device_get of all values) or park "
                    "the device scalar and materialize it lagged",
                )
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("item", "tolist")):
                self.flag(
                    "host-sync-in-loop", node,
                    f".{node.func.attr}() inside a hot loop: one device "
                    "sync per iteration",
                )

        self.generic_visit(node)


def _suppressions(source: str) -> Tuple[Set[str], Dict[int, Set[str]]]:
    file_rules: Set[str] = set()
    line_rules: Dict[int, Set[str]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _PRAGMA_FILE.search(line)
        if m:
            file_rules.update(r.strip() for r in m.group(1).split(","))
            continue
        m = _PRAGMA_LINE.search(line)
        if m:
            line_rules.setdefault(i, set()).update(
                r.strip() for r in m.group(1).split(","))
    return file_rules, line_rules


def _is_hot(path: str) -> bool:
    norm = path.replace(os.sep, "/")
    parts = norm.split("/")
    if any(p in _HOT_DIR_PARTS for p in parts[:-1]):
        return True
    return parts[-1] in _HOT_FILES


def _is_event_scope(path: str) -> bool:
    """Where unbounded-event-buffer applies: the hot modules plus the
    long-lived serving/obs tiers."""
    if _is_hot(path):
        return True
    parts = path.replace(os.sep, "/").split("/")
    return any(p in _EVENT_SCOPE_DIR_PARTS for p in parts[:-1])


def _module_level_lists(tree: ast.Module) -> Set[str]:
    """Names bound to a bare ``[]`` / ``list()`` at module top level —
    the buffers with no possible maxlen discipline.  deque(maxlen=...)
    and any other construction are not collected."""
    names: Set[str] = set()
    for stmt in tree.body:
        targets: List[ast.AST] = []
        value: Optional[ast.AST] = None
        if isinstance(stmt, ast.Assign):
            targets, value = stmt.targets, stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets, value = [stmt.target], stmt.value
        if value is None:
            continue
        is_bare_list = isinstance(value, ast.List) or (
            isinstance(value, ast.Call) and _dotted(value.func) == "list"
            and not value.args and not value.keywords)
        if not is_bare_list:
            continue
        for tgt in targets:
            if isinstance(tgt, ast.Name):
                names.add(tgt.id)
    return names


def lint_source(source: str, path: str = "<string>",
                hot: Optional[bool] = None,
                rules: Optional[Iterable[str]] = None) -> List[Finding]:
    """Lint one module's source; returns surviving findings."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding("syntax-error", path, e.lineno or 0, str(e.msg))]
    index = _ModuleIndex()
    index.visit(tree)
    traced = _traced_functions(index)
    hot = _is_hot(path) if hot is None else hot
    module_lists = _module_level_lists(tree)
    event_scope = _is_event_scope(path)
    module_classes = {n.name for n in tree.body
                      if isinstance(n, ast.ClassDef)}

    findings: List[Finding] = []

    def walk_fn(fn: ast.AST, is_traced: bool) -> None:
        walker = _RuleWalker(path, is_traced, hot, findings,
                             jit_roots=index.jit_roots,
                             module_lists=module_lists,
                             event_scope=event_scope,
                             module_classes=module_classes)
        for stmt in fn.body:  # type: ignore[attr-defined]
            walker.visit(stmt)
        walker.finish()

    seen: Set[int] = set()

    def visit_scope(node: ast.AST, enclosing_traced: bool) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if id(child) in seen:
                    continue
                seen.add(id(child))
                is_traced = enclosing_traced or child.name in traced
                walk_fn(child, is_traced)
                visit_scope(child, is_traced)
            else:
                visit_scope(child, enclosing_traced)

    visit_scope(tree, False)

    file_sup, line_sup = _suppressions(source)
    active = set(rules) if rules is not None else set(AST_RULES)
    out = []
    for f in findings:
        if f.rule not in active:
            continue
        if f.rule in file_sup or f.rule in line_sup.get(f.line, ()):
            continue
        out.append(f)
    out.sort(key=lambda f: (f.path, f.line, f.rule))
    return out


def lint_paths(paths: Iterable[str],
               rules: Optional[Iterable[str]] = None) -> List[Finding]:
    """Lint .py files (recursing into directories)."""
    findings: List[Finding] = []
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, _dirs, names in os.walk(p):
                if "__pycache__" in root:
                    continue
                for n in sorted(names):
                    if n.endswith(".py"):
                        files.append(os.path.join(root, n))
        elif p.endswith(".py"):
            files.append(p)
    for fp in sorted(files):
        with open(fp, encoding="utf-8") as fh:
            src = fh.read()
        findings.extend(lint_source(src, path=fp, rules=rules))
    return findings
