"""Distributed training over a TPU device mesh.

TPU-native replacement for the reference's entire network layer
(src/network/: Bruck allgather, recursive-halving reduce-scatter, socket
and MPI linkers — network.cpp:40-185) and its parallel tree learners
(src/treelearner/parallel_tree_learner.h).  Sockets, topology maps, and
byte-level reducers collapse into XLA collectives (`psum`,
`psum_scatter`, `all_gather`, argmax reductions) over a
`jax.sharding.Mesh`, executing on ICI within a slice and DCN across
hosts with no framework code changes.
"""

from .mesh import data_mesh, default_device_count  # noqa: F401
from .data_parallel import make_data_parallel_grower  # noqa: F401
from .feature_parallel import make_feature_parallel_grower  # noqa: F401
from .voting_parallel import make_voting_parallel_grower  # noqa: F401

__all__ = [
    "data_mesh",
    "default_device_count",
    "make_data_parallel_grower",
    "make_feature_parallel_grower",
    "make_voting_parallel_grower",
]
