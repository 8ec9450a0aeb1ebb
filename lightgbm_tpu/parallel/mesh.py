"""Device-mesh helpers.

The reference bootstraps its cluster from a machine-list file + TCP
handshakes (src/network/linkers_socket.cpp:20-61) or MPI_COMM_WORLD.
On TPU the runtime already knows the topology: a 1-D mesh over all
addressable devices is the analog of `num_machines` ranks, and rank
assignment / connection retry logic disappears.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

ROW_AXIS = "row"  # data-parallel axis (rows sharded)


def default_device_count() -> int:
    return len(jax.devices())


def data_mesh(
    num_devices: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    axis_name: str = ROW_AXIS,
) -> Mesh:
    """A 1-D mesh whose single axis shards the row dimension — the
    mesh-shaped analog of the reference's `num_machines` world
    (network.cpp:20-38)."""
    if devices is None:
        devices = jax.devices()
        if num_devices is not None:
            devices = devices[:num_devices]
    return Mesh(np.asarray(devices), (axis_name,))


def row_padded_grower(sharded_fn, num_shards: int):
    """Wrap a shard-mapped grow fn with row padding so n need not divide
    the mesh evenly.  Padded rows carry bag_mask 0, making them invisible
    to histograms and sums; the leaf partition is trimmed on return."""
    import jax
    import jax.numpy as jnp

    # named as the one-device growers are: every grow program is
    # ``jit_grow_tree`` to the profiler and the compile counters
    @jax.jit
    def grow_tree(bins_T, grad, hess, bag_mask, feature_mask, nbpf, is_cat,
                  params):
        n = bins_T.shape[1]
        pad = (-n) % num_shards
        if pad:
            bins_T = jnp.pad(bins_T, ((0, 0), (0, pad)))
            grad = jnp.pad(grad, (0, pad))
            hess = jnp.pad(hess, (0, pad))
            bag_mask = jnp.pad(bag_mask, (0, pad))
        tree, leaf_id = sharded_fn(
            bins_T, grad, hess, bag_mask, feature_mask, nbpf, is_cat, params
        )
        return tree, leaf_id[:n]

    return grow_tree
