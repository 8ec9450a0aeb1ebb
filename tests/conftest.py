"""Test configuration: force an 8-device virtual CPU platform so the
multi-device (mesh) code paths run without TPU hardware."""

import os

# Must be set before jax is imported anywhere.  Force CPU whatever the
# outer environment says: the suite's multi-device tests need 8 virtual
# devices, kernels run in interpret mode, and chip_smoke.py — not the
# test suite — is what exercises the real chip.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Pin the embedded C API interpreter too (capi_impl import-time platform
# selection reads this, not JAX_PLATFORMS).
os.environ.setdefault("LGBM_CAPI_PLATFORM", "cpu")

import jax  # noqa: E402

import pytest  # noqa: E402

REFERENCE_DIR = "/root/reference"


@pytest.fixture(scope="session")
def reference_examples():
    """Path to the reference's bundled example datasets (skip if absent)."""
    path = os.path.join(REFERENCE_DIR, "examples")
    if not os.path.isdir(path):
        pytest.skip("reference examples not available")
    return path


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Bound in-process compiled-executable accumulation.

    A full-suite run compiles hundreds of XLA:CPU programs in one
    process; on this VM (compile/host CPU-feature mismatch — XLA warns
    'could lead to execution errors such as SIGILL') the accumulation
    has produced rare late-suite segfaults inside backend_compile.
    Dropping compiled caches between modules keeps the process small;
    within-module caching (the expensive tier-chain compiles reused
    across a module's tests) is unaffected."""
    yield
    jax.clear_caches()
