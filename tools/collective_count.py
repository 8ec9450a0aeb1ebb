"""Count cross-device collectives in the compiled data-parallel tree.

Compiles the leaf-wise data-parallel grower over an 8-device virtual CPU
mesh and counts collective ops in the optimized HLO — the evidence for
the per-split collective budget documented in parallel/data_parallel.py.

The counting itself lives in the library now
(``lightgbm_tpu.obs.telemetry.collective_stats`` /
``record_collectives`` — promoted from this tool so parallel runs can
fold collective counts into their telemetry); this CLI keeps the
human-readable per-computation report.  The ops sit inside the
fori_loop body (executed num_leaves-1 times per tree), so the per-split
budget is the count within the while body.

Usage:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
            python tools/collective_count.py
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# a count over virtual CPU devices, whatever the outer environment says
jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from lightgbm_tpu.config import Config  # noqa: E402
from lightgbm_tpu.learners.serial import TreeLearnerParams  # noqa: E402
from lightgbm_tpu.obs import record_collectives  # noqa: E402
from lightgbm_tpu.parallel import data_mesh, make_data_parallel_grower  # noqa: E402


def report(tag: str, compiled) -> None:
    """Per-computation collective counts + payload bytes.  The while body
    (executed num_leaves-1 times) is the per-split budget."""
    stats = record_collectives(tag, compiled)
    for name, entry in stats["by_computation"].items():
        where = "ENTRY (per-tree setup)" if name.startswith("ENTRY") \
            else f"{name} (per-split while body)"
        print(f"[{tag}] {where}: {entry['ops']}  "
              f"payload={entry['payload_bytes']}B")


def main() -> None:
    n, F, B, L = 4096, 64, 32, 15  # small L: the while BODY is what we count
    rng = np.random.RandomState(0)
    args = (
        jnp.asarray(rng.randint(0, B, size=(F, n)).astype(np.uint8)),
        jnp.asarray(rng.randn(n).astype(np.float32)),
        jnp.asarray(np.abs(rng.randn(n)).astype(np.float32) + 0.1),
        jnp.ones(n, jnp.float32),
        jnp.ones(F, bool),
        jnp.full(F, B, jnp.int32),
        jnp.zeros(F, bool),
        TreeLearnerParams.from_config(Config(min_data_in_leaf=20)),
    )
    mesh = data_mesh()
    grow = make_data_parallel_grower(mesh, num_bins=B, max_leaves=L)
    report("data-parallel F=64", jax.jit(grow).lower(*args).compile())

    # voting-parallel (PV-Tree): the vote restricts the reduced histogram
    # payload from O(F*B) to O(2*top_k*B)
    # (voting_parallel_tree_learner.cpp:137-166, 260-265)
    from lightgbm_tpu.parallel import make_voting_parallel_grower

    for top_k in (5, 20):
        grow_v = make_voting_parallel_grower(
            mesh, num_bins=B, max_leaves=L, top_k=top_k)
        report(f"voting top_k={top_k} F=64",
               jax.jit(grow_v).lower(*args).compile())


if __name__ == "__main__":
    main()
