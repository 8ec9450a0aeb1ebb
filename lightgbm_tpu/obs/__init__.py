"""Runtime observability: telemetry, phase-attributed device time,
self-describing run manifests.

The runtime half of ROADMAP item 1's "make perf un-regressable"
(jaxlint in ``analysis/`` is the static half):

* :mod:`~lightgbm_tpu.obs.telemetry` — always-on spans / counters /
  per-tree reservoirs (near-zero overhead; no jax import).
* :mod:`~lightgbm_tpu.obs.device_time` — ``phase_scope`` writes the
  ``lgbm.*`` names onto the grower, its kernels and the boosting driver;
  ``read`` / ``attribute`` read them back from a profiler trace
  (``.xplane.pb``): every device op by scope and cause, the copy ledger,
  idle gaps by host span.  ``python -m lightgbm_tpu.obs.device_time``.
* :mod:`~lightgbm_tpu.obs.manifest` — ``RunManifest`` written next to
  every bench result artifact; diffed by ``tools/benchdiff.py``.
* :mod:`~lightgbm_tpu.obs.tracing` — per-request ``TraceContext``
  (trace id + stage clock) threaded through the serving tier; every
  served response carries a per-stage latency breakdown.
* :mod:`~lightgbm_tpu.obs.export` — Prometheus text exposition of the
  telemetry snapshot (``GET /metrics`` on the serving server).
* :mod:`~lightgbm_tpu.obs.flightrec` — lock-cheap last-N event ring,
  dumped atomically (checksum sidecar, rank-tagged filename) on
  preemption / guard trips / serving failures for post-mortem.
* :mod:`~lightgbm_tpu.obs.dist` — the cross-rank layer: rank-scoped
  snapshots, merge + skew attribution, host-side snapshot exchange,
  per-collective tracing (barrier-wait vs transfer), desync sentinels.
* :mod:`~lightgbm_tpu.obs.memory` — device-memory accounting: the
  shared ``memory_stats()`` reader, owner-tagged live-buffer census,
  host-boundary watermarks, ``lgbm_memory_*`` gauges, OOM post-mortems.
* :mod:`~lightgbm_tpu.obs.memmodel` — analytic HBM footprint model
  (expected live-set per phase from first principles); the planning
  artifact behind ``tools/hbm_budget.py``.

See docs/observability.md for the schemas and the reading guide.
"""

from __future__ import annotations

from . import (  # noqa: F401
    dist,
    export,
    flightrec,
    memmodel,
    memory,
    telemetry,
    tracing,
)
from .manifest import (  # noqa: F401
    RunManifest,
    config_fingerprint,
    manifest_path,
    validate,
)
from .telemetry import (  # noqa: F401
    Histogram,
    Reservoir,
    SpanStat,
    Telemetry,
    collective_stats,
    count,
    count_many,
    enabled,
    get_telemetry,
    host_sync,
    observe,
    record_collectives,
    record_value,
    set_enabled,
    span,
)
from .tracing import TraceContext  # noqa: F401

_LAZY = ("phase_scope", "SCOPES")


def __getattr__(name):
    # loaded on first use, like every reader of a trace: manifest and
    # telemetry consumers (benchdiff, lint tooling) never need it
    if name in _LAZY or name == "device_time":
        from . import device_time

        if name == "device_time":
            return device_time
        return getattr(device_time, name)
    raise AttributeError(name)
