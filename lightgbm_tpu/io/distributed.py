"""Distributed data loading: per-rank row partition + parallel bin finding.

Mirrors the reference's distributed loader (src/io/dataset_loader.cpp):

* **Row partition at load** (dataset_loader.cpp:500-605, is_pre_partition
  = false): every rank reads the same file and keeps the rows a shared-
  seed RNG assigns to it — query-granular for ranking data so no query is
  split across ranks.
* **Parallel bin finding** (dataset_loader.cpp:692-755): features are
  sharded across ranks, each rank fits BinMappers for its shard from its
  LOCAL sample, and the mappers are allgathered so every rank ends with
  the full set.  The reference moves serialized BinMapper buffers through
  its Bruck allgather (network.cpp:99-131); here the payload is the same
  idea (BinMapper.to_dict JSON) moved by a pluggable gather function —
  `jax.experimental.multihost_utils.process_allgather` in a real
  multi-host run, identity in tests.

These are host-side (numpy) by design: binning happens once at ingest,
the TPU only ever sees the binned matrix.
"""

from __future__ import annotations

import json
from typing import Callable, List, Optional, Sequence

import numpy as np

from .binner import BinMapper, find_bin_mappers

GatherFn = Callable[[str], List[str]]


# --------------------------------------------------------------- partition
def partition_rows(
    num_rows: int,
    rank: int,
    num_machines: int,
    seed: int,
    query_boundaries: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Row indices this rank keeps (dataset_loader.cpp:500-605).

    Every rank runs the same RNG stream, so the rank assignment is
    consistent without communication.  With ``query_boundaries`` the
    assignment is per-query (query-granular partition for lambdarank,
    dataset_loader.cpp:560-605)."""
    rng = np.random.RandomState(seed)
    if query_boundaries is not None:
        qb = np.asarray(query_boundaries)
        nq = len(qb) - 1
        owner = rng.randint(0, num_machines, size=nq)
        keep_q = np.nonzero(owner == rank)[0]
        return np.concatenate(
            [np.arange(qb[q], qb[q + 1]) for q in keep_q]
        ).astype(np.int64) if len(keep_q) else np.empty(0, np.int64)
    owner = rng.randint(0, num_machines, size=num_rows)
    return np.nonzero(owner == rank)[0].astype(np.int64)


# ------------------------------------------------------------- bin finding
def shard_features(num_features: int, num_machines: int) -> List[np.ndarray]:
    """Contiguous feature shards, one per rank (the reference balances by
    bin count after a first pass, dataset_loader.cpp:697-716; contiguous
    even split is the same comm volume and simpler)."""
    bounds = np.linspace(0, num_features, num_machines + 1).astype(np.int64)
    return [np.arange(bounds[r], bounds[r + 1]) for r in range(num_machines)]


def _identity_gather(payload: str) -> List[str]:
    return [payload]


def _jax_process_gather(payload: str) -> List[str]:
    """Allgather JSON payloads across jax processes (multi-host)."""
    import jax
    from jax.experimental import multihost_utils

    data = np.frombuffer(payload.encode(), dtype=np.uint8)
    # pad to the max length across processes
    n = np.asarray([len(data)], np.int32)
    all_n = multihost_utils.process_allgather(n).reshape(-1)
    maxlen = int(all_n.max())
    padded = np.zeros(maxlen, np.uint8)
    padded[: len(data)] = data
    gathered = multihost_utils.process_allgather(padded)
    return [
        bytes(gathered[r][: int(all_n[r])]).decode()
        for r in range(gathered.shape[0])
    ]


def distributed_find_bin_mappers(
    sample_local: np.ndarray,
    rank: int,
    num_machines: int,
    max_bin: int = 256,
    categorical_features: Sequence[int] = (),
    total_sample_cnt: Optional[int] = None,
    gather_fn: Optional[GatherFn] = None,
    use_missing: bool = True,
) -> List[BinMapper]:
    """Feature-sharded bin finding + mapper allgather
    (dataset_loader.cpp:692-755).

    Each rank fits mappers only for its feature shard (from its local
    sample) and broadcasts them; the returned list covers ALL features on
    every rank.  ``gather_fn(payload) -> [payload_rank0, ...]`` abstracts
    the transport; the default uses jax multihost allgather when more
    than one process is attached, else runs single-rank."""
    F = sample_local.shape[1]
    shards = shard_features(F, num_machines)
    mine = shards[rank]
    cats = set(int(c) for c in categorical_features)

    local = find_bin_mappers(
        sample_local[:, mine] if len(mine) else sample_local[:, :0],
        total_sample_cnt=total_sample_cnt or len(sample_local),
        max_bin=max_bin,
        categorical_features=[i for i, j in enumerate(mine) if int(j) in cats],
        use_missing=use_missing,
    )
    payload = json.dumps(
        {"rank": rank, "mappers": [m.to_dict() for m in local]}
    )

    if gather_fn is None:
        import jax

        gather_fn = (
            _jax_process_gather if jax.process_count() > 1 else _identity_gather
        )
    gathered = [json.loads(s) for s in gather_fn(payload)]
    if len(gathered) == 1 and num_machines == 1:
        return local

    by_rank = {g["rank"]: g["mappers"] for g in gathered}
    if len(by_rank) != num_machines:
        raise RuntimeError(
            f"distributed bin finding expected {num_machines} payloads, "
            f"got ranks {sorted(by_rank)}"
        )
    out: List[Optional[BinMapper]] = [None] * F
    for r in range(num_machines):
        for i, j in enumerate(shards[r]):
            out[int(j)] = BinMapper.from_dict(by_rank[r][i])
    return out  # type: ignore[return-value]
