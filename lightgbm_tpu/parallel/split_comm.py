"""SplitInfo exchange shared by the sharded-search learners.

The reference ships one fixed-size SplitInfo byte buffer through
Network::Allreduce with a deterministic MaxReducer (split_info.hpp:58-104,
feature_parallel_tree_learner.cpp:64-77).  The mesh analog: pack the
11-field SplitResult into ONE float matrix (a pytree all_gather would
emit 11 collectives, one per leaf array), all_gather it, and reduce with
the reference's ordering — max gain, ties broken toward the smaller
feature index.  feature/threshold values are < 2^24, exactly
representable in f32 for transport.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.split import SplitResult

# Plain Python int (weakly typed in jnp ops): a module-level jnp constant
# would initialize the default JAX backend at import time, before the
# caller has chosen a platform — and take the chip from whoever needs it.
_INT_MAX = 2**31 - 1

_F_FEATURE = SplitResult._fields.index("feature")
_F_THRESH = SplitResult._fields.index("threshold")


def pack_split(r: SplitResult) -> jax.Array:
    """[..., 11] float transport form (int fields cast, exact)."""
    ft = r.gain.dtype
    return jnp.stack([jnp.asarray(f).astype(ft) for f in r], axis=-1)


def unpack_split(a: jax.Array) -> SplitResult:
    fields = [a[..., i] for i in range(len(SplitResult._fields))]
    fields[_F_FEATURE] = fields[_F_FEATURE].astype(jnp.int32)
    fields[_F_THRESH] = fields[_F_THRESH].astype(jnp.int32)
    return SplitResult(*fields)


def combine_gathered_split_infos(g: SplitResult) -> SplitResult:
    """Reduce an all_gathered SplitResult (leading device axis, arbitrary
    trailing batch axes) with the reference's deterministic ordering
    (split_info.hpp:98-103)."""
    feats = jnp.where(g.feature < 0, _INT_MAX, g.feature)
    tied = g.gain == jnp.max(g.gain, axis=0, keepdims=True)
    winner = jnp.argmin(jnp.where(tied, feats, _INT_MAX), axis=0)
    return SplitResult(
        *[jnp.take_along_axis(f, winner[None], axis=0)[0] for f in g]
    )


def gather_and_combine(r: SplitResult, axis: str,
                       site: str = None) -> SplitResult:
    """One packed all_gather over ``axis`` + deterministic max.

    ``site`` opts into the trace-time collective census (obs/dist.py):
    callers on an audited path name their site so the per-op
    collectives-per-split contract stays checkable."""
    g = jax.lax.all_gather(pack_split(r), axis)  # [D, 11]
    if site:
        from ..obs.dist import record_collective_site

        record_collective_site(site, "all-gather",
                               g.size * g.dtype.itemsize)
    return combine_gathered_split_infos(unpack_split(g))
