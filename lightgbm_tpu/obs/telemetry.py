"""Always-on runtime telemetry: spans, counters, per-tree reservoirs.

The round-5 regression (BENCH_r05 vs_baseline 0.71) shipped unnoticed
because no training run records where its time goes.  This module is
the runtime half of the fix (jaxlint is the static half): every process
carries a near-zero-overhead telemetry singleton that any entry point
can snapshot into a :class:`~lightgbm_tpu.obs.manifest.RunManifest`.

Design constraints, in order:

* **Near-zero overhead on the hot path.**  A span is two
  ``time.perf_counter()`` calls and two dict operations; a counter is
  one uncontended-lock acquisition and one dict add (the lock arrived
  with the multi-threaded serving tier — see the :class:`Telemetry`
  docstring).  Nothing here touches a device array, forces a sync, or
  allocates per-iteration beyond a float append.  What it costs is
  measured where it counts: on the chip, parent against change against
  ``LGBM_TPU_TELEMETRY=off`` in one call (PERF.md section 6).
* **Honesty about async dispatch.**  Host-side span times measure
  *dispatch* wall time, not device time — ``train_one_iter`` returns
  before the chip finishes (the same hazard the jaxlint
  ``wallclock-without-sync`` rule flags).  Spans are therefore labeled
  host-wall; phase-attributed *device* time comes from the profiler
  trace (:mod:`lightgbm_tpu.obs.device_time`), never from host timers.
  No span holds a ``block_until_ready``: an asynchronous ``device_put``
  under ``lgbm.setup.booster.upload`` is what it is.
* **A timeline, not only totals.**  Every span keeps its first start as
  seconds since the package's import began (``first_start_s``), so the
  ``lgbm.setup.*`` spans of ingest, booster construction and the first
  iteration read in order (:func:`setup_timeline`; docs/observability.md
  "Set-up timeline").
* **No jax import at module import.**  Tools (benchdiff, jaxlint) read
  telemetry data structures without paying a jax import; the compile
  counter bridges to :mod:`lightgbm_tpu.analysis.recompile` lazily.

Counters maintained by the library itself:

* ``backend_compiles`` — XLA backend compiles (snapshot-time bridge to
  ``analysis/recompile.py``'s process-wide listener; cache hits are 0).
* ``compile.trace_s.<fun>`` / ``compile.lower_s.<fun>`` /
  ``compile.backend_s.<fun>`` — seconds jax spent tracing ``<fun>`` to
  a jaxpr, lowering it to MLIR, and in ``compile_or_get_cached`` (XLA's
  and Mosaic's compile on a miss of the persistent cache, the retrieval
  on a hit), by jitted program (``jit_grow_tree``); ``compile.programs``
  counts the last.  ``compile.cache_hits`` / ``.cache_misses`` /
  ``.cache_retrieval_s`` / ``.time_saved_s`` are the persistent cache's
  own events, over all programs.  Written by the same listener from
  ``jax.monitoring`` while telemetry is on; a function traced inside
  another's trace is inside the outer ``trace_s`` too, so sum over the
  programs that have a ``backend_s``.
* ``setup.import_s`` / ``setup.import_unix_s`` — what importing the
  package took (jax included when the package is what loads it) and
  ``time.time()`` at its start: the anchor of every ``first_start_s``
  on a harness's own clock.
* ``ingest.rows`` / ``.columns`` / ``.used_columns`` / ``.sample_rows``
  / ``.float64_bytes`` / ``.bin_bytes`` — once a dataset binned
  (io/dataset.py); ``setup.upload_bytes`` — host bytes a booster sent
  to the device (models/gbdt.py ``reset_training_data``).
* ``grow_traces`` / ``dp_grow_traces`` — retraces of the serial /
  data-parallel grow program (incremented at Python trace time inside
  the traced body, so each retrace counts exactly once).
* ``grow.feature_chunks`` / ``grow.chunk_features`` /
  ``grow.hist_block_bytes`` / ``grow.record_words`` /
  ``grow.onehot_planes`` — how the fused grower's kernels walk the
  feature axis and split the bin axis (learners/fused.py ``chunking``;
  ops/pallas_histogram.py ``bin_sums``), added once a booster that
  takes the fused grower; beside them ``grow.split_tiles_per_step``
  (the parent tiles a grid step of the split step takes, from the
  record's height alone: 4 up to 64 words, 1 past them; ops/record.py
  ``split_tiles``), ``grow.place_steps_per_tile`` and
  ``grow.place_launches_per_split`` (1 and 1: ops/record.py
  ``PLACE_STEPS_PER_TILE``; the step table before PR 39 took 4 and up to
  8).
* ``host_syncs`` — deliberate device->host materialization points the
  library performs (eval fetches, lagged-stop drains, bench syncs).
* ``collective_ops`` / ``collective_bytes`` — cross-device collectives
  in compiled parallel programs, recorded via :func:`record_collectives`
  (static count from the optimized HLO, promoted from the old
  ``tools/collective_count.py``).

Env: ``LGBM_TPU_TELEMETRY`` = ``on`` (default) | ``off`` | ``json``
(``json`` additionally emits one structured JSON line to stderr at
process exit, whatever embeds the package).  Read once at import (jit
caches do not key on env — same convention the env-read-at-trace rule
enforces); :func:`set_enabled` is the runtime override tests use.
"""

from __future__ import annotations

import atexit
import bisect
import json
import re
import sys
import time
from os import environ as _environ
from typing import Dict, List, Optional

from .. import _IMPORT_T0
from ..analysis import lockcheck

# read once at import — see module docstring
TELEMETRY_MODE = _environ.get("LGBM_TPU_TELEMETRY", "on").strip().lower()

_RESERVOIR_CAP = 4096

# fixed latency buckets (seconds) for Prometheus-style histograms: the
# serving stage clocks span ~0.1 ms (pad on a warm bucket) to seconds
# (a cold dispatch); log-ish spacing keeps the tail resolvable without
# per-request allocation.  STABLE — these boundaries are part of the
# /metrics contract (docs/observability.md), change = new metric name.
DEFAULT_LATENCY_BOUNDS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


class SpanStat:
    """Accumulated wall time of one named span (host-wall, see module
    docstring for the async-dispatch caveat).  ``first_start_s`` is the
    span's first start, in seconds since the package's import began."""

    __slots__ = ("total_s", "count", "min_s", "max_s", "first_start_s")

    def __init__(self) -> None:
        self.total_s = 0.0
        self.count = 0
        self.min_s = float("inf")
        self.max_s = 0.0
        self.first_start_s = 0.0

    def add(self, dt: float, t0: float) -> None:
        """``t0``: the ``time.perf_counter()`` at which the span began."""
        if not self.count:
            self.first_start_s = t0 - _IMPORT_T0
        self.total_s += dt
        self.count += 1
        if dt < self.min_s:
            self.min_s = dt
        if dt > self.max_s:
            self.max_s = dt

    def as_dict(self) -> dict:
        return {
            "total_s": round(self.total_s, 6),
            "count": self.count,
            "min_s": round(self.min_s, 6) if self.count else 0.0,
            "max_s": round(self.max_s, 6),
            "first_start_s": round(self.first_start_s, 6),
        }


class Reservoir:
    """Sliding window of the most recent ``cap`` samples with p50/p99.

    A ring buffer, not a probabilistic reservoir: per-tree times drift
    (lazy Mosaic compiles early, steady state later), and the question
    the manifest answers is "what does a tree cost NOW", so the window
    deliberately reports the most recent ``cap`` trees.  The total
    sample count is kept so a reader can see how much was windowed out.
    """

    __slots__ = ("cap", "_buf", "_n")

    def __init__(self, cap: int = _RESERVOIR_CAP) -> None:
        self.cap = cap
        self._buf: List[float] = []
        self._n = 0

    def add(self, v: float) -> None:
        if len(self._buf) < self.cap:
            self._buf.append(v)
        else:
            self._buf[self._n % self.cap] = v
        self._n += 1

    def __len__(self) -> int:
        return self._n

    def percentile(self, p: float) -> float:
        """Nearest-rank percentile over the current window (0 if empty)."""
        if not self._buf:
            return 0.0
        s = sorted(self._buf)
        k = max(0, min(len(s) - 1, int(round(p / 100.0 * (len(s) - 1)))))
        return s[k]

    def clone(self) -> "Reservoir":
        """Cheap copy (one list copy) so percentile sorting can happen
        OUTSIDE the telemetry store lock — a /metrics scrape must not
        stall request-path writers for the duration of ~18 sorts."""
        c = Reservoir(self.cap)
        c._buf = list(self._buf)
        c._n = self._n
        return c

    def as_dict(self, include_samples: bool = False) -> dict:
        window = len(self._buf)
        mean = sum(self._buf) / window if window else 0.0
        out = {
            "count": self._n,
            "window": window,
            "mean_s": round(mean, 6),
            "p50_s": round(self.percentile(50), 6),
            "p99_s": round(self.percentile(99), 6),
            "max_s": round(max(self._buf), 6) if window else 0.0,
        }
        if include_samples:
            # the raw window, in insertion order: cross-rank merging
            # (obs/dist.py) concatenates windows and recomputes exact
            # quantiles — averaging per-rank percentiles would be wrong
            # for any skewed distribution
            start = self._n % self.cap if self._n > self.cap else 0
            ordered = self._buf[start:] + self._buf[:start]
            out["samples"] = [round(v, 6) for v in ordered]
        return out


class Histogram:
    """Fixed-bucket histogram (the Prometheus exposition shape).

    Complements :class:`Reservoir`: the reservoir answers "what do the
    most recent requests cost" (sliding window, exact quantiles); the
    histogram is cumulative over the process lifetime and exports as
    ``_bucket{le=...}/_sum/_count`` series a scraper can rate() and
    aggregate across replicas — which windowed quantiles cannot.
    ``observe`` is one bisect + three adds.
    """

    __slots__ = ("bounds", "counts", "total", "sum")

    def __init__(self, bounds=DEFAULT_LATENCY_BOUNDS) -> None:
        self.bounds = tuple(float(b) for b in bounds)
        if list(self.bounds) != sorted(self.bounds) or not self.bounds:
            raise ValueError(f"histogram bounds must be sorted and "
                             f"non-empty, got {bounds!r}")
        self.counts = [0] * (len(self.bounds) + 1)  # last = +Inf bucket
        self.total = 0
        self.sum = 0.0

    def observe(self, v: float) -> None:
        self.counts[bisect.bisect_left(self.bounds, v)] += 1
        self.total += 1
        self.sum += v

    def as_dict(self) -> dict:
        return {"bounds": list(self.bounds), "counts": list(self.counts),
                "count": self.total, "sum": round(self.sum, 9)}


class _Span:
    """Context manager recording one timed region into a Telemetry, and
    the same region as a ``jax.profiler.TraceAnnotation``: with a
    profiler session open the span stands on the trace's host plane,
    on the device's clock (obs/device_time.py reads it back); with none
    the annotation is a flag test.  jax is never imported here: a
    process that has not loaded it has nothing to profile."""

    __slots__ = ("_tel", "_name", "_t0", "_note")

    def __init__(self, tel: "Telemetry", name: str) -> None:
        self._tel = tel
        self._name = name

    def __enter__(self) -> "_Span":
        jax = sys.modules.get("jax")
        self._note = jax and jax.profiler.TraceAnnotation(self._name)
        if self._note:
            self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        if self._note:
            self._note.__exit__(*exc)
        self._tel._record_span(self._name, dt, self._t0)


class _NullSpan:
    """Telemetry-off span: enter/exit do nothing at all."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL_SPAN = _NullSpan()


class Telemetry:
    """Process-wide telemetry store (counters, spans, reservoirs,
    histograms).

    Every mutation takes the one store lock.  This changed with the
    serving observability PR: the training loop is single-threaded (the
    GIL made torn counts a non-issue), but the serving tier increments
    from many request threads at once, where ``d[k] = d.get(k) + n``
    LOSES increments and a ``/v1/stats`` snapshot could see the rows
    counter ahead of the requests counter it rode in with.  An
    uncontended ``threading.Lock`` is tens of nanoseconds, and in
    exchange :meth:`snapshot` is one consistent cut: everything it
    returns was simultaneously true.  Related adds that must move
    together go through :meth:`count_many` (one acquisition).
    """

    def __init__(self, enabled: bool = True) -> None:
        self.enabled = enabled
        # RLock, not Lock: the preemption path runs flightrec.dump()
        # (which counts) from a SIGNAL HANDLER on the main thread — if
        # the signal interrupted a frame that already holds the store
        # lock, a non-reentrant lock would deadlock the "Ctrl-C twice"
        # abort.  Re-entry can at worst lose the interrupted frame's
        # single increment; a hang needs SIGKILL.
        self._lock = lockcheck.make_rlock("telemetry.store")
        self._counters: Dict[str, float] = {}
        self._spans: Dict[str, SpanStat] = {}
        self._reservoirs: Dict[str, Reservoir] = {}
        self._histograms: Dict[str, Histogram] = {}

    # ------------------------------------------------------------- record
    def span(self, name: str):
        """``with tel.span("bench.timed_loop"): ...`` — host-wall timer."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name)

    def _record_span(self, name: str, dt: float, t0: float) -> None:
        with self._lock:
            st = self._spans.get(name)
            if st is None:
                st = self._spans.setdefault(name, SpanStat())
            st.add(dt, t0)

    def count(self, name: str, n: float = 1) -> None:
        """Monotonic counter add (no-op when disabled)."""
        if self.enabled:
            with self._lock:
                self._counters[name] = self._counters.get(name, 0) + n

    def count_many(self, adds: Dict[str, float]) -> None:
        """Several counter adds under ONE lock acquisition — for pairs
        that must never be observed half-applied (``serving.requests``
        and ``serving.rows``: a snapshot between two separate adds
        would report traffic whose row count belongs to no request
        count)."""
        if not self.enabled:
            return
        with self._lock:
            for name, n in adds.items():
                self._counters[name] = self._counters.get(name, 0) + n

    def record_value(self, name: str, v: float) -> None:
        """Append one sample to the named reservoir (e.g. per-tree s)."""
        if not self.enabled:
            return
        with self._lock:
            r = self._reservoirs.get(name)
            if r is None:
                r = self._reservoirs.setdefault(name, Reservoir())
            r.add(v)

    def observe(self, name: str, v: float, bounds=None) -> None:
        """One sample into the named fixed-bucket histogram (the
        ``/metrics`` exposition shape; see :class:`Histogram` for why
        this exists next to the reservoirs).  ``bounds`` applies only
        on first touch of a name."""
        if not self.enabled:
            return
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms.setdefault(
                    name, Histogram(bounds or DEFAULT_LATENCY_BOUNDS))
            h.observe(v)

    def _sample_sinks(self, name: str):
        """Get-or-create the (reservoir, histogram) pair a latency
        series feeds.  Caller holds the store lock."""
        r = self._reservoirs.get(name)
        if r is None:
            r = self._reservoirs.setdefault(name, Reservoir())
        h = self._histograms.get(name)
        if h is None:
            h = self._histograms.setdefault(name, Histogram())
        return r, h

    def record_samples(self, samples: Dict[str, float]) -> None:
        """Several latency samples under ONE lock acquisition, each
        feeding its reservoir AND its histogram — the serving scatter
        path records five series per request (four stages + the
        end-to-end), and five-times-two separate acquisitions were the
        dominant tracing cost on the 1-core container."""
        if not self.enabled:
            return
        with self._lock:
            for name, v in samples.items():
                r, h = self._sample_sinks(name)
                r.add(v)
                h.observe(v)

    def record_sample_lists(self, samples: Dict[str, List[float]]) -> None:
        """Batch form of :meth:`record_samples`: one lock acquisition
        for a whole coalesced batch's worth of per-request samples —
        the serving dispatcher records once per BATCH, keeping the
        tracing cost on its critical path independent of how many
        requests coalesced."""
        if not self.enabled:
            return
        with self._lock:
            for name, vals in samples.items():
                r, h = self._sample_sinks(name)
                for v in vals:
                    r.add(v)
                    h.observe(v)

    def host_sync(self, n: int = 1) -> None:
        """Record a deliberate device->host materialization point."""
        self.count("host_syncs", n)

    # ------------------------------------------------------------ inspect
    def counter(self, name: str) -> float:
        return self._counters.get(name, 0)

    def reservoir(self, name: str) -> Optional[Reservoir]:
        return self._reservoirs.get(name)

    def span_stat(self, name: str) -> Optional[SpanStat]:
        return self._spans.get(name)

    def histogram(self, name: str) -> Optional[Histogram]:
        return self._histograms.get(name)

    def snapshot(self, include_compiles: bool = True,
                 include_samples: bool = False) -> dict:
        """ONE consistent cut of everything, as plain JSON-able dicts:
        the store lock is held across the whole copy and every writer
        takes the same lock, so no snapshot can observe one counter of
        a related pair updated and the other not (``/v1/stats`` and
        ``/metrics`` both read through here).

        ``backend_compiles`` is bridged in from the analysis subsystem's
        process-wide listener at snapshot time (importing jax only if
        the process already did — the listener installs on first use by
        whoever counts compiles, and a process that never imported jax
        has by definition compiled nothing).  The ``compile.*`` seconds
        by program need no bridge: the same listener adds them here as
        jax reports them.
        """
        with self._lock:
            counters = dict(self._counters)
            spans = {k: v.as_dict() for k, v in self._spans.items()}
            # clone, don't as_dict: percentile sorting over up-to-4096
            # samples per reservoir happens outside the lock, so a
            # scrape can't stall every request-path writer meanwhile
            res_clones = {k: v.clone() for k, v in self._reservoirs.items()}
            histograms = {k: v.as_dict() for k, v in self._histograms.items()}
        reservoirs = {k: v.as_dict(include_samples=include_samples)
                      for k, v in res_clones.items()}
        if include_compiles and "jax" in sys.modules:
            try:
                from lightgbm_tpu.analysis.recompile import (
                    backend_compile_count)

                counters["backend_compiles"] = backend_compile_count()
            except Exception:
                pass
        return {"counters": counters, "spans": spans,
                "reservoirs": reservoirs, "histograms": histograms}

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._spans.clear()
            self._reservoirs.clear()
            self._histograms.clear()

    def emit(self, stream=None) -> None:
        """One JSON line of the full snapshot with the set-up timeline
        as its ``setup`` key (what ``LGBM_TPU_TELEMETRY=json`` prints to
        stderr at process exit)."""
        stream = sys.stderr if stream is None else stream
        snap = self.snapshot()
        snap["setup"] = setup_timeline(snap)
        print(json.dumps({"lgbm_tpu_telemetry": snap}, sort_keys=True),
              file=stream, flush=True)


def setup_timeline(snapshot: dict) -> List[dict]:
    """The ``lgbm.setup.*`` spans of a snapshot in order of first start:
    ``name``, ``start_s`` (since the package's import began; add the
    counter ``setup.import_unix_s`` for the wall clock), ``seconds`` and
    ``count``, ``parent`` (the longest dotted prefix that is itself a
    recorded span, else None) and ``uncovered_s``: the seconds of the
    span that none of its children covers, so a leaf's own seconds and
    a parent's remainder with no name.  Host wall time, like every
    span: the sum of ``uncovered_s`` is the top-level spans' total."""
    spans = {k: v for k, v in snapshot["spans"].items()
             if k.startswith("lgbm.setup.")}
    rows = {}
    for name, st in spans.items():
        parent = name.rpartition(".")[0]
        while parent and parent not in spans:
            parent = parent.rpartition(".")[0]
        rows[name] = {"name": name, "start_s": st["first_start_s"],
                      "seconds": st["total_s"], "count": st["count"],
                      "parent": parent or None,
                      "uncovered_s": st["total_s"]}
    for row in rows.values():
        if row["parent"]:
            rows[row["parent"]]["uncovered_s"] -= row["seconds"]
    for row in rows.values():  # children round to 1 us each
        row["uncovered_s"] = max(round(row["uncovered_s"], 6), 0.0)
    return sorted(rows.values(), key=lambda r: (r["start_s"], r["name"]))


_TELEMETRY = Telemetry(enabled=TELEMETRY_MODE != "off")
if TELEMETRY_MODE == "json":
    atexit.register(_TELEMETRY.emit)


def get_telemetry() -> Telemetry:
    """The process-wide singleton every entry point snapshots."""
    return _TELEMETRY


def set_enabled(flag: bool) -> None:
    """Runtime enable/disable."""
    _TELEMETRY.enabled = bool(flag)


def enabled() -> bool:
    return _TELEMETRY.enabled


# module-level conveniences bound to the singleton
def span(name: str):
    return _TELEMETRY.span(name)


def count(name: str, n: float = 1) -> None:
    _TELEMETRY.count(name, n)


def count_many(adds: Dict[str, float]) -> None:
    _TELEMETRY.count_many(adds)


def record_value(name: str, v: float) -> None:
    _TELEMETRY.record_value(name, v)


def observe(name: str, v: float, bounds=None) -> None:
    _TELEMETRY.observe(name, v, bounds=bounds)


def record_samples(samples: Dict[str, float]) -> None:
    _TELEMETRY.record_samples(samples)


def record_sample_lists(samples: Dict[str, List[float]]) -> None:
    _TELEMETRY.record_sample_lists(samples)


def host_sync(n: int = 1) -> None:
    _TELEMETRY.host_sync(n)


# ------------------------------------------------------- collectives (HLO)
# Promoted from tools/collective_count.py: static collective count +
# payload bytes of a compiled program's optimized HLO.  The count is per
# compiled module; the while-body computation (executed num_leaves-1
# times per tree) is the per-split budget documented in
# parallel/data_parallel.py.

COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)\b"
)
_SHAPE_RE = re.compile(r"([a-z]+[0-9]+)\[([0-9,]*)\]")
_DT_BYTES = {"f32": 4, "f64": 8, "s32": 4, "u32": 4, "pred": 1, "bf16": 2,
             "s8": 1, "u8": 1, "f16": 2, "s64": 8, "u64": 8, "u16": 2,
             "s16": 2}


def _collective_bytes_of(line: str) -> int:
    """Sum ALL result-shape components: variadic (combined) collectives
    have tuple results like ``(f32[64,32], s32[4]) all-reduce(...)``."""
    lhs = line.split("=", 1)[-1]
    m_op = COLLECTIVE_RE.search(lhs)
    head = lhs[: m_op.start()] if m_op else lhs
    total = 0
    for dt, dims in _SHAPE_RE.findall(head):
        num = 1
        for d in dims.split(","):
            if d:
                num *= int(d)
        total += num * _DT_BYTES.get(dt, 4)
    return total


def collective_stats(hlo_text: str) -> dict:
    """Collective ops in an optimized-HLO dump, per computation.

    Returns ``{"total": N, "payload_bytes": B, "by_op": {...},
    "by_computation": {name: {"ops": {...}, "payload_bytes": B}}}``.
    ``-done`` halves of async pairs are not double-counted.
    """
    blocks: Dict[str, List[str]] = {}
    cur = None
    for line in hlo_text.splitlines():
        if line and not line.startswith(" ") and "{" in line:
            cur = line.split("{")[0].strip().split(" ")[0]
            blocks[cur] = []
        elif cur is not None:
            blocks[cur].append(line)
    by_comp: Dict[str, dict] = {}
    by_op: Dict[str, int] = {}
    total = 0
    payload = 0
    for name, lines in blocks.items():
        counts: Dict[str, int] = {}
        nbytes = 0
        for ln in lines:
            m = COLLECTIVE_RE.search(ln)
            if m and "=" in ln and "-done" not in ln.split("=", 1)[-1][:40]:
                counts[m.group(1)] = counts.get(m.group(1), 0) + 1
                nbytes += _collective_bytes_of(ln)
        if counts:
            by_comp[name] = {"ops": counts, "payload_bytes": nbytes}
            for op, c in counts.items():
                by_op[op] = by_op.get(op, 0) + c
            total += sum(counts.values())
            payload += nbytes
    return {"total": total, "payload_bytes": payload, "by_op": by_op,
            "by_computation": by_comp}


def record_collectives(tag: str, compiled) -> dict:
    """Count collectives in a compiled program (``jax.jit(f).lower(*a)
    .compile()``) and fold them into the telemetry counters
    (``collective_ops`` / ``collective_bytes``).  Returns the stats."""
    stats = collective_stats(compiled.as_text())
    adds = {
        "collective_ops": stats["total"],
        "collective_bytes": stats["payload_bytes"],
        f"collective_ops.{tag}": stats["total"],
    }
    # per-op-kind fold (obs/dist.py convention: the 3-collectives/split
    # contract is checkable per-op, not just as a total)
    for op, c in stats["by_op"].items():
        adds[f"collective_ops.op.{op}"] = \
            adds.get(f"collective_ops.op.{op}", 0) + c
    _TELEMETRY.count_many(adds)
    return stats
