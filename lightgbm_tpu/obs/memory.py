"""Device-memory observability: the memory half of the obs stack.

``device_time.py``/``telemetry.py`` answer *where the time went*; this
module answers *where the bytes live*.  Four surfaces:

* ``hbm_stats()`` — the one shared reader over
  ``device.memory_stats()`` (bytes_in_use / peak / limit), normalized
  to ``hbm_*`` keys.  Backends without allocator stats (the CPU
  backend returns ``None``) degrade to ``hbm_stats_supported: false``
  with zeroed gauges instead of raising — tier-1 runs on CPU.
* ``live_buffer_census()`` — groups ``jax.live_arrays()`` by owner tag
  (dataset / scores / histograms / routing / serving) x dtype x shape.
  Owners self-register via ``register_owner``; the registry holds only
  weakrefs + getter callables, never the buffers themselves, so it can
  never *cause* the retention it is built to detect.
* host-side phase watermarks — ``phase_boundary(name)`` samples the
  allocator at the boundaries the host can see (binning / train / eval
  / serve / swap).  NOTE this is deliberately not ``phase_scope``: the
  trace-time phases (histogram / split-search / ...) live *inside* one
  jitted dispatch where the host cannot observe the allocator; their
  in-program peaks come from the static side instead
  (``analysis/hlo_audit.py`` memory budgets + ``obs/memmodel.py``).
* OOM post-mortems — ``classify_dispatch_error`` turns a
  RESOURCE_EXHAUSTED escaping a train/serve dispatch into a flight
  recorder dump (tail kind ``oom``) carrying the last census and the
  analytic model's prediction for the failing shape.

No jax import at module import time (jax is imported lazily inside
functions) so manifest/lint consumers stay jax-free, matching the rest
of ``obs/``.  See docs/memory.md for the gauge-name contract.
"""

from __future__ import annotations

import itertools
import weakref
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

from ..analysis import lockcheck

GAUGE_PREFIX = "lgbm_memory_"

# owner tags with a registered meaning (docs/memory.md); census rows
# from unregistered buffers fall under "other"
OWNER_TAGS = ("dataset", "scores", "histograms", "routing", "serving")

# host-visible sampling boundaries (NOT the trace-time PHASES — see
# module docstring)
BOUNDARIES = ("binning", "train", "eval", "serve", "swap")

# substrings that identify an out-of-device-memory failure in the
# message of a jax/XLA runtime error (XlaRuntimeError carries the grpc
# status name in-text; older paths say "Out of memory")
OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
               "OOM when allocating")

_lock = lockcheck.make_lock("memory.census")
_enabled = True

# token -> (tag, weakref-to-owner, getter).  getter(owner) returns a
# pytree / iterable of (possibly) jax arrays.
_owners: Dict[int, Tuple[str, "weakref.ref", Callable[[Any], Any]]] = {}
_owner_counter = itertools.count(1)

# phase -> {"last_bytes", "peak_bytes", "samples", "source"}
_watermarks: Dict[str, Dict[str, Any]] = {}
_last_census: Optional[dict] = None


def set_enabled(on: bool) -> None:
    """Runtime switch for the sampling half (watermark sampling and
    census-on-boundary).  Explicit census / stats calls still work
    while disabled."""
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


# ---------------------------------------------------------------------------
# allocator stats (the shared reader northstar_run/bench adopt)

def device_memory_stats(device: Any = None) -> dict:
    """Raw ``memory_stats()`` for one device ({} when unsupported —
    the CPU backend returns None)."""
    try:
        import jax

        dev = device if device is not None else jax.local_devices()[0]
        return dict(dev.memory_stats() or {})
    except Exception:
        return {}


def hbm_stats(device: Any = None) -> dict:
    """Normalized device-memory gauges.  Keys are stable contract
    (docs/memory.md): ``hbm_bytes_in_use``, ``hbm_peak_bytes``,
    ``hbm_limit_bytes``, ``hbm_stats_supported``.  Never raises; a
    backend probe failure comes back as ``hbm_stats_error``."""
    try:
        import jax

        dev = device if device is not None else jax.local_devices()[0]
        ms = dev.memory_stats()
    except Exception as e:  # backend failed to start, no local device
        return {"hbm_bytes_in_use": 0, "hbm_peak_bytes": 0,
                "hbm_limit_bytes": 0, "hbm_stats_supported": False,
                "hbm_stats_error": f"{type(e).__name__}: {str(e)[:120]}"}
    if not ms:
        return {"hbm_bytes_in_use": 0, "hbm_peak_bytes": 0,
                "hbm_limit_bytes": 0, "hbm_stats_supported": False}
    return {
        "hbm_bytes_in_use": int(ms.get("bytes_in_use", 0)),
        "hbm_peak_bytes": int(ms.get("peak_bytes_in_use", 0)),
        "hbm_limit_bytes": int(ms.get("bytes_limit", 0)),
        "hbm_stats_supported": True,
    }


# ---------------------------------------------------------------------------
# owner registry + live-buffer census

def register_owner(tag: str, owner: Any,
                   getter: Callable[[Any], Any]) -> int:
    """Register ``owner`` as holding device buffers under ``tag``.
    ``getter(owner)`` must return the buffers (a pytree or iterable);
    it is called at census time against the *live* owner.  Only a
    weakref to ``owner`` is kept — registration never extends a
    buffer's lifetime.  Returns a token for ``unregister_owner``."""
    token = next(_owner_counter)
    with _lock:
        _owners[token] = (str(tag), weakref.ref(owner), getter)
    return token


def unregister_owner(token: int) -> None:
    with _lock:
        _owners.pop(token, None)


def _iter_owner_arrays() -> Iterable[Tuple[str, Any]]:
    """(tag, array) pairs from live registered owners; drops dead
    weakrefs as it goes."""
    import jax

    with _lock:
        items = list(_owners.items())
    dead = []
    for token, (tag, ref, getter) in items:
        owner = ref()
        if owner is None:
            dead.append(token)
            continue
        try:
            leaves = jax.tree_util.tree_leaves(getter(owner))
        except Exception:
            continue
        for leaf in leaves:
            if isinstance(leaf, jax.Array):
                yield tag, leaf
    if dead:
        with _lock:
            for token in dead:
                _owners.pop(token, None)


def live_buffer_census(top: int = 16) -> dict:
    """Group every live device array by (owner tag, dtype, shape).

    Built on ``jax.live_arrays()`` so it sees *all* buffers, not just
    registered ones — unregistered buffers land under ``other``, which
    is exactly where a leak shows up.  O(live arrays) host walk; cheap
    at the scales this repo runs, and gated off the hot path (only at
    explicit call sites: /metrics scrape, manifest collection, OOM
    post-mortem, leak tests)."""
    global _last_census
    try:
        import jax
    except Exception:
        return {"total_bytes": 0, "buffers": 0, "by_owner": {},
                "groups": [], "supported": False}

    tag_of: Dict[int, str] = {}
    for tag, arr in _iter_owner_arrays():
        tag_of[id(arr)] = tag

    groups: Dict[Tuple[str, str, tuple], Dict[str, int]] = {}
    by_owner: Dict[str, Dict[str, int]] = {}
    total = 0
    count = 0
    for arr in jax.live_arrays():
        try:
            if arr.is_deleted():
                continue
            nbytes = int(arr.nbytes)
            key = (tag_of.get(id(arr), "other"), str(arr.dtype),
                   tuple(arr.shape))
        except Exception:
            continue
        total += nbytes
        count += 1
        g = groups.setdefault(key, {"bytes": 0, "count": 0})
        g["bytes"] += nbytes
        g["count"] += 1
        o = by_owner.setdefault(key[0], {"bytes": 0, "buffers": 0})
        o["bytes"] += nbytes
        o["buffers"] += 1

    rows = sorted(
        ({"owner": k[0], "dtype": k[1], "shape": list(k[2]),
          "count": v["count"], "bytes": v["bytes"]}
         for k, v in groups.items()),
        key=lambda r: (-r["bytes"], r["owner"], r["dtype"]))
    census = {
        "total_bytes": int(total),
        "buffers": int(count),
        "by_owner": {k: dict(v) for k, v in sorted(by_owner.items())},
        "groups": rows[:max(0, int(top))],
        "supported": True,
    }
    _last_census = census
    return census


def last_census() -> Optional[dict]:
    """Most recent census (post-mortems attach it when a fresh walk is
    impossible); None before the first census."""
    return _last_census


# ---------------------------------------------------------------------------
# host-side phase watermarks

def _live_bytes_fast() -> int:
    """Cheap total over live arrays — the CPU fallback signal when the
    allocator exposes no stats (keeps watermarks meaningful in tier-1)."""
    try:
        import jax

        return sum(int(a.nbytes) for a in jax.live_arrays()
                   if not a.is_deleted())
    except Exception:
        return 0


def phase_boundary(phase: str) -> None:
    """Sample device memory at a host-visible boundary (one of
    BOUNDARIES, though unknown names are accepted).  No-op while
    the layer is disabled."""
    if not _enabled:
        return
    st = hbm_stats()
    if st.get("hbm_stats_supported"):
        bytes_now = st["hbm_bytes_in_use"]
        peak_seen = st["hbm_peak_bytes"]
        source = "device"
    else:
        bytes_now = _live_bytes_fast()
        peak_seen = bytes_now
        source = "census"
    with _lock:
        w = _watermarks.setdefault(
            phase, {"last_bytes": 0, "peak_bytes": 0, "samples": 0,
                    "source": source})
        w["last_bytes"] = int(bytes_now)
        w["peak_bytes"] = max(int(w["peak_bytes"]), int(peak_seen),
                              int(bytes_now))
        w["samples"] += 1
        w["source"] = source


def watermarks() -> dict:
    with _lock:
        return {k: dict(v) for k, v in sorted(_watermarks.items())}


def reset_watermarks() -> None:
    with _lock:
        _watermarks.clear()


def peak_bytes() -> int:
    """Best available peak: allocator peak when supported, else the
    high-water mark over every boundary sample."""
    st = hbm_stats()
    if st.get("hbm_stats_supported"):
        return st["hbm_peak_bytes"]
    with _lock:
        return max((int(v["peak_bytes"]) for v in _watermarks.values()),
                   default=0)


# ---------------------------------------------------------------------------
# gauges / manifest section

def memory_gauges(census: Optional[dict] = None) -> dict:
    """Flat ``lgbm_memory_*`` gauge dict for
    :func:`obs.export.render_prometheus` (value or (value, help)
    entries).  Runs a fresh census unless one is passed in."""
    st = hbm_stats()
    c = census if census is not None else live_buffer_census()
    gauges: Dict[str, Any] = {
        GAUGE_PREFIX + "bytes_in_use": (
            st["hbm_bytes_in_use"],
            "Device allocator bytes currently in use"),
        GAUGE_PREFIX + "peak_bytes": (
            max(st["hbm_peak_bytes"], 0) or peak_bytes(),
            "Device allocator peak bytes (census high-water on CPU)"),
        GAUGE_PREFIX + "limit_bytes": (
            st["hbm_limit_bytes"], "Device allocator capacity"),
        GAUGE_PREFIX + "stats_supported": (
            1 if st.get("hbm_stats_supported") else 0,
            "1 when the backend exposes allocator stats"),
        GAUGE_PREFIX + "live_buffer_bytes": (
            c.get("total_bytes", 0),
            "Total bytes across jax.live_arrays()"),
        GAUGE_PREFIX + "live_buffers": (
            c.get("buffers", 0), "Number of live device arrays"),
    }
    for tag, row in (c.get("by_owner") or {}).items():
        gauges[GAUGE_PREFIX + "owner_bytes_" + str(tag)] = (
            row.get("bytes", 0),
            f"Live bytes owned by census tag '{tag}'")
    return gauges


def manifest_memory_section(census: Optional[dict] = None) -> dict:
    """The ``memory{}`` manifest section beside ``phases{}``: hbm
    gauges + boundary watermarks + a census summary."""
    c = census if census is not None else live_buffer_census()
    return {
        "hbm": hbm_stats(),
        "watermarks": watermarks(),
        "census": {
            "total_bytes": c.get("total_bytes", 0),
            "buffers": c.get("buffers", 0),
            "by_owner": c.get("by_owner", {}),
            "top": (c.get("groups") or [])[:8],
        },
    }


# ---------------------------------------------------------------------------
# OOM classification + post-mortem

def is_oom_error(exc: BaseException) -> bool:
    msg = f"{type(exc).__name__}: {exc}"
    return any(marker in msg for marker in OOM_MARKERS)


def oom_postmortem(exc: BaseException, where: str,
                   shape: Optional[dict] = None,
                   predict_params: Optional[dict] = None) -> dict:
    """Record + dump the post-mortem for an OOM at a dispatch boundary.

    Flight-recorder tail kind is ``oom`` and the event carries the last
    live-buffer census plus ``obs/memmodel``'s prediction for the
    failing shape (when the caller knows it) — so the dump answers both
    "what was resident" and "what did the model expect".  Never raises:
    a post-mortem that throws inside an OOM handler would mask the real
    failure."""
    from . import flightrec, telemetry

    try:
        census = live_buffer_census()
    except Exception:
        census = last_census() or {"total_bytes": 0, "buffers": 0,
                                   "by_owner": {}, "groups": []}
    predicted = None
    if predict_params:
        try:
            from . import memmodel

            predicted = memmodel.predict(**predict_params)
        except Exception:
            predicted = None
    event = {
        "where": where,
        "error": f"{type(exc).__name__}: {str(exc)[:400]}",
        "shape": dict(shape or {}),
        "hbm": hbm_stats(),
        "census": {
            "total_bytes": census.get("total_bytes", 0),
            "buffers": census.get("buffers", 0),
            "by_owner": census.get("by_owner", {}),
            "top": (census.get("groups") or [])[:8],
        },
        "predicted_peak_bytes": (
            predicted.get("peak_bytes") if predicted else None),
        "predicted_phases": (
            predicted.get("phases") if predicted else None),
    }
    try:
        telemetry.count("oom." + where.split(".")[0])
        flightrec.record("oom", **event)
        event["dump_path"] = flightrec.dump("oom")
    except Exception:
        event.setdefault("dump_path", None)
    return event


def classify_dispatch_error(exc: BaseException, where: str,
                            shape: Optional[dict] = None,
                            predict_params: Optional[dict] = None,
                            ) -> Optional[dict]:
    """Dispatch-boundary hook: post-mortem iff ``exc`` is an OOM.
    Returns the post-mortem event (or None); callers re-raise ``exc``
    either way."""
    if not is_oom_error(exc):
        return None
    return oom_postmortem(exc, where, shape=shape,
                          predict_params=predict_params)
