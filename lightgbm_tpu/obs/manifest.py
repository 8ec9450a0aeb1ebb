"""Self-describing run manifests.

Every BENCH/`.bench/*.json` number becomes evidence instead of prose:
each bench entry point (``bench.py``, the ``cli.py`` train task,
``tools/northstar_run.py``) writes a ``RunManifest`` next to its result
artifact recording *what ran* (git sha, dirty flag, jax/backend/device,
config fingerprint, env knobs), *how it warmed up* (warm-up iteration
count, discarded warm trees, compile-stability), *what it counted*
(telemetry counters incl. backend compiles, collectives), and *where
the time went* (host-wall spans, phase breakdown, per-tree p50/p99).

The round-5 failure this kills: a 2x regression shipped because the
committed bench row said only "0.4442 s/tree" — nothing recorded that
the run carried lazy compiles, which commit it measured, or which phase
grew.  A manifest makes the next BENCH row diffable by
``tools/benchdiff.py`` instead of by archaeology.

Schema versioned as ``lightgbm-tpu/run-manifest/v1``; `validate`
pins the required keys so the round-trip is a tier-1 contract.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import platform as _platform
import subprocess
import sys
import time
from typing import Any, Dict, Optional

from .telemetry import get_telemetry, setup_timeline

SCHEMA = "lightgbm-tpu/run-manifest/v1"

# env knobs worth recording: anything that changes what gets traced,
# compiled, or measured
_KNOB_PREFIXES = ("LGBM_TPU_", "BENCH_", "NS_", "JAX_PLATFORMS",
                  "XLA_FLAGS", "JAX_ENABLE_X64")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

REQUIRED_KEYS = ("schema", "entry", "created_unix", "git", "runtime",
                 "config_fingerprint", "knobs", "warmup", "telemetry",
                 "phases", "per_tree", "result")


def _git_info() -> dict:
    """Best-effort git sha + dirty flag (a manifest from an exported
    tarball still validates — sha is then null)."""
    out = {"sha": None, "dirty": None}
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=_REPO_ROOT, timeout=10,
            capture_output=True, text=True)
        if sha.returncode == 0:
            out["sha"] = sha.stdout.strip()
        st = subprocess.run(
            ["git", "status", "--porcelain"], cwd=_REPO_ROOT, timeout=10,
            capture_output=True, text=True)
        if st.returncode == 0:
            out["dirty"] = bool(st.stdout.strip())
    except Exception:
        pass
    return out


def _runtime_info() -> dict:
    """jax / backend / device identity.  Lazy and guarded: collecting a
    manifest must never initialize a backend the run didn't already use
    (a supervisor that has touched JAX holds the chip its children
    need), so devices are read only when jax is already imported."""
    info: Dict[str, Any] = {
        "python": sys.version.split()[0],
        "platform": _platform.platform(),
    }
    if "jax" not in sys.modules:
        return info
    try:
        import jax

        info["jax"] = jax.__version__
        try:
            import jaxlib

            info["jaxlib"] = jaxlib.__version__
        except Exception:
            pass
        devs = jax.devices()
        info["backend"] = devs[0].platform
        info["device_kind"] = getattr(devs[0], "device_kind", None)
        info["device_count"] = len(devs)
    except Exception as e:
        info["jax_error"] = f"{type(e).__name__}: {str(e)[:120]}"
    return info


def _knobs() -> dict:
    return {k: v for k, v in sorted(os.environ.items())
            if k.startswith(_KNOB_PREFIXES)}


def config_fingerprint(config: Any) -> Optional[str]:
    """Stable sha256 over the run configuration (a Config object, a
    dict, or anything with ``__dict__``).  Two runs with the same
    fingerprint trained the same program shape — the precondition for a
    benchdiff comparison to be apples-to-apples."""
    if config is None:
        return None
    if dataclasses.is_dataclass(config) and not isinstance(config, type):
        d = dataclasses.asdict(config)
    elif isinstance(config, dict):
        d = config
    elif hasattr(config, "__dict__"):
        d = vars(config)
    else:
        d = {"repr": repr(config)}
    blob = json.dumps(
        {str(k): repr(v) for k, v in sorted(d.items(), key=lambda kv: str(kv[0]))},
        sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


@dataclasses.dataclass
class RunManifest:
    """One run's self-description; see module docstring for the fields'
    purpose.  ``telemetry`` is a full snapshot (counters/spans/
    reservoirs); ``phases`` is phase -> seconds; ``per_tree`` is the
    p50/p99 reservoir summary of the timed trees."""

    entry: str
    created_unix: float
    git: dict
    runtime: dict
    config_fingerprint: Optional[str]
    knobs: dict
    warmup: dict
    telemetry: dict
    phases: dict
    per_tree: dict
    result: dict
    extra: dict = dataclasses.field(default_factory=dict)
    # multi-rank runs (obs/dist.py): rank 0 writes the ONE manifest,
    # carrying every rank's identity + load-bearing numbers (device,
    # compiles, span seconds, collective wait/transfer).  Empty on
    # single-process runs; optional in v1 (validate does not require
    # it), so every existing manifest still loads.
    ranks: list = dataclasses.field(default_factory=list)
    # device-memory section beside phases{} (obs/memory.py:
    # manifest_memory_section()): hbm gauges, boundary watermarks,
    # owner-tagged census summary.  Optional in v1 like ``ranks``.
    memory: dict = dataclasses.field(default_factory=dict)
    # the ``lgbm.setup.*`` spans in order of start (telemetry.
    # setup_timeline): ingest, booster construction, the first
    # iteration.  Optional in v1 like ``ranks``.
    setup: list = dataclasses.field(default_factory=list)
    schema: str = SCHEMA

    @classmethod
    def collect(cls, entry: str, config: Any = None,
                result: Optional[dict] = None,
                phases: Optional[dict] = None,
                warmup: Optional[dict] = None,
                per_tree_reservoir: str = "tree_s",
                extra: Optional[dict] = None,
                ranks: Optional[list] = None,
                memory: Optional[dict] = None) -> "RunManifest":
        """Gather everything the process knows right now.  ``entry`` is
        the entry point name ("bench.py", "cli.train", "northstar")."""
        tel = get_telemetry()
        snap = tel.snapshot()
        res = tel.reservoir(per_tree_reservoir)
        return cls(
            entry=entry,
            created_unix=round(time.time(), 3),
            git=_git_info(),
            runtime=_runtime_info(),
            config_fingerprint=config_fingerprint(config),
            knobs=_knobs(),
            warmup=dict(warmup or {}),
            telemetry=snap,
            phases=dict(phases or {}),
            per_tree=res.as_dict() if res is not None else {},
            result=dict(result or {}),
            extra=dict(extra or {}),
            ranks=list(ranks or []),
            memory=dict(memory or {}),
            setup=setup_timeline(snap),
        )

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "RunManifest":
        validate(d)
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    def write(self, path: str) -> str:
        # shared crash-safe writer (resilience/atomic.py): tmp + fsync +
        # rename — a crash mid-write must not leave a half manifest
        # shadowing a real result artifact
        from ..resilience.atomic import atomic_write_json

        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        return atomic_write_json(path, self.to_dict())

    @classmethod
    def load(cls, path: str) -> "RunManifest":
        with open(path) as fh:
            return cls.from_dict(json.load(fh))


def validate(d: dict) -> None:
    """Raise ValueError when a manifest dict is not v1-shaped."""
    missing = [k for k in REQUIRED_KEYS if k not in d]
    if missing:
        raise ValueError(f"manifest missing keys: {missing}")
    if d["schema"] != SCHEMA:
        raise ValueError(f"unknown manifest schema {d['schema']!r}")


def manifest_path(artifact_path: str) -> str:
    """Canonical manifest location for a result artifact:
    ``foo.json`` -> ``foo.manifest.json`` (sibling, self-pairing)."""
    base, ext = os.path.splitext(artifact_path)
    if ext == ".json":
        return base + ".manifest.json"
    return artifact_path + ".manifest.json"
