"""The package's one ``jax.monitoring`` listener: the backend-compile
count, and the seconds of every jitted program by phase.

JAX emits a ``/jax/core/compile/backend_compile_duration`` monitoring
event once per actual backend compile (a hit of the jit's in-memory
cache emits nothing — verified on this jaxlib: two same-shape calls add
zero events, a new shape adds one).  Counting these events gives the
recompile signal the bench warm-up and the steady-loop tier-1 gate
need: a timed loop is only honest once an iteration adds no new
compiles.

The same events carry a duration and the program's ``fun_name``, and
two more precede them: ``jaxpr_trace_duration`` (Python tracing; jax
names the function there, ``grow_tree``, and ``jit(grow_tree)`` after
it: the listener says ``jit_grow_tree``, the module's name, for both) and
``jaxpr_to_mlir_module_duration`` (lowering, Pallas kernels to Mosaic
included).  The backend event spans ``compile_or_get_cached``: the
cache key, then XLA's and Mosaic's compile and the write on a miss of
the persistent cache, or the retrieval on a hit, which the four
``/jax/compilation_cache/`` events tell apart.  While telemetry is on
the listener adds all of them to its counters (``compile.*``,
obs/telemetry.py's list), so a program that recompiles at tree 40 is a
counter that moved, with its name.

The listener registry in jax.monitoring has no targeted unregister, so
the listeners install once per process and stay; the count is read
by delta (``CompileCounter.delta()`` snapshots).

Caveat: lazily-compiled Mosaic kernels inside an already-compiled XLA
program (the per-tier TPU kernels) compile in the TPU runtime and do
NOT emit this event — callers that warm real-chip loops should combine
the counter with an iteration-time stability check (bench.py does).
"""

from __future__ import annotations

import re
import threading

from ..obs import telemetry

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace_s.jit_",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower_s.",
    _COMPILE_EVENT: "compile.backend_s.",
}
_CACHE = {
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "compile.cache_retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "compile.time_saved_s",
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}

_NOT_NAME = re.compile(r"[^0-9A-Za-z_.]+")  # "jit(grow_tree)" -> jit_grow_tree

_lock = threading.Lock()
_installed = False
_count = 0


def _on_duration(event: str, duration: float, **kwargs) -> None:
    global _count
    if event in _PHASES:
        fun = _NOT_NAME.sub("_", str(kwargs.get("fun_name"))).rstrip("_")
        adds = {_PHASES[event] + fun: duration}
        if event == _COMPILE_EVENT:
            with _lock:
                _count += 1
            adds["compile.programs"] = 1
        telemetry.count_many(adds)
    elif event in _CACHE:
        telemetry.count(_CACHE[event], duration)


def _on_event(event: str, **kwargs) -> None:  # noqa: ARG001
    if event in _CACHE:  # jax records hits and misses as plain events
        telemetry.count(_CACHE[event])


def install() -> None:
    """Start listening (once a process; a flag test afterwards): the
    count for its first reader, the ``compile.*`` seconds from the
    first booster on (models/gbdt.py ``GBDT.__init__``)."""
    global _installed
    with _lock:
        if _installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        # flag is set only AFTER successful registration: a failure
        # must surface on the next call too, not leave a permanently-
        # zero counter that makes every compile-stability gate pass
        # vacuously (registration never fires the listener, so holding
        # _lock across it cannot deadlock)
        _installed = True


class CompileCounter:
    """Snapshot view over the process-wide compile count."""

    def __init__(self) -> None:
        self._mark = backend_compile_count()

    @property
    def count(self) -> int:
        """Total backend compiles this process has performed."""
        return backend_compile_count()

    def delta(self) -> int:
        """Compiles since construction or the last ``reset()``."""
        return backend_compile_count() - self._mark

    def reset(self) -> None:
        self._mark = backend_compile_count()


def backend_compile_count() -> int:
    install()
    with _lock:
        return _count


def compile_counter() -> CompileCounter:
    """A fresh zeroed snapshot counter (installs the listener)."""
    return CompileCounter()
