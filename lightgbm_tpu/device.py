"""Which device this process computes on, and where compiled code is kept.

Two ways the program runs: on a TPU (Mosaic-compiled Pallas kernels,
the raw-layout split step, the matmul predictor) and on the CPU for
tests (segment-sum histograms, the ``jax.numpy`` search, kernels in
interpret mode).  Every platform-dependent choice in the library asks
:func:`on_tpu`, so a log line can say which program ran and a test can
select the chip program without a chip (``assume_platform``).

Importing this module does not initialize a JAX backend.
"""

from __future__ import annotations

import contextlib
import glob
import os

_assumed: str | None = None  # assume_platform() override, tests only
_cache_checked = False

#: default compile-cache directory: ``<checkout>/.jax_cache`` (git-ignored)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def platform() -> str:
    """The platform the library selects code paths for: JAX's default
    backend unless a test has assumed another."""
    if _assumed is not None:
        return _assumed
    import jax

    return jax.default_backend()


def on_tpu() -> bool:
    return platform() == "tpu"


def vmem_bytes() -> int:
    """VMEM of one TensorCore of the chip this process computes on, by
    Pallas's table of device kinds; a v5e's 128 MiB where no TPU is
    attached (the chip the deviceless compiles describe)."""
    from jax.experimental.pallas import tpu as pltpu

    try:
        return int(pltpu.get_tpu_info().vmem_capacity_bytes)
    except ValueError:  # "Unsupported TPU device kind: cpu"
        return 128 << 20


@contextlib.contextmanager
def assume_platform(name: str):
    """Select the code paths of platform ``name`` regardless of the
    backend present.  For deviceless compiles of the chip program
    (tests/test_chip_compile.py); nothing in the library calls this."""
    global _assumed
    prev, _assumed = _assumed, name
    try:
        yield
    finally:
        _assumed = prev


def compile_cache_dir() -> str | None:
    """Where JAX persists compiled programs in this process, or None
    (the config option is where JAX keeps ``JAX_COMPILATION_CACHE_DIR``)."""
    import jax

    return jax.config.jax_compilation_cache_dir or None


def enable_compile_cache() -> str | None:
    """The one setter of the persistent compile cache.  A cold 1M-row
    training loop carries ~20 Mosaic kernel compiles; caching them makes
    every process after the first start warm.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise, on a TPU only, the cache goes to
    ``<checkout>/.jax_cache`` — a fixed path, because the path is part of
    the cache key.  The XLA:CPU cache stays off: its machine-feature
    keying risks replaying code built for another host.  Called lazily
    from the first booster, when the backend is being initialized
    anyway.  Returns the directory in use."""
    global _cache_checked
    if not _cache_checked:
        _cache_checked = True
        import jax

        if not compile_cache_dir() and jax.default_backend() == "tpu":
            os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 1.0)
    return compile_cache_dir()


# ------------------------------------------------- one process for each chip
def local_tpu_chips() -> int:
    """TPU chips attached to this host, counted from their device files
    so that a supervisor can ask without initializing JAX — a parent
    that has touched JAX holds the chips its children need.  0 where
    ``JAX_PLATFORMS`` keeps the children off the TPU anyway."""
    if "tpu" not in (os.environ.get("JAX_PLATFORMS") or "tpu"):
        return 0
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def chip_env(slot: int) -> dict:
    """Environment that shows a child process chip ``slot`` and no
    other, as a one-chip topology of its own."""
    port = 8476 + slot
    return {
        "TPU_VISIBLE_CHIPS": str(slot),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{port}",
        "TPU_MESH_CONTROLLER_PORT": str(port),
    }


def require_chips(processes: int, what: str) -> int:
    """Refuse, before anything starts, a fleet of more chip-holding
    processes than this host has chips: the surplus children would die
    in backend start-up and burn the restart budget.  Returns the chip
    count (0 = not a TPU host, nothing to assign)."""
    chips = local_tpu_chips()
    if chips and processes > chips:
        raise ValueError(
            f"{what}: {processes} processes need one TPU chip each and "
            f"this host has {chips}")
    return chips
