"""Multi-host training: jax.distributed wiring + cross-process growers.

TPU-native replacement for the reference's Network::Init cluster
bootstrap (src/application/application.cpp:187-198) and its TCP/MPI
linker mesh (src/network/linkers_socket.cpp:20-61): one
``jax.distributed.initialize`` call attaches this process to the JAX
coordination service, after which ``jax.devices()`` spans every host and
the same XLA collectives (psum over the row axis) that power the
single-host data-parallel learner run over DCN/ICI across machines —
no sockets, no Bruck/recursive-halving topologies, no retry loops.

Process bootstrap accepts either

* the standard coordinator env/args (``LGBM_TPU_COORDINATOR``,
  ``LGBM_TPU_NUM_PROCESSES``, ``LGBM_TPU_PROCESS_ID``), or
* the reference's ``machine_list_file`` ("ip port" lines,
  linkers_socket.cpp:73-109): the first line is the coordinator and this
  process's rank is the position of a local interface address in the
  list (linkers_socket.cpp:31-44), overridable by env.
"""

from __future__ import annotations

import os
import socket
from typing import List, Optional, Tuple

import jax
import numpy as np

from ..log import Log
from .data_parallel import data_parallel_sharded
from .mesh import ROW_AXIS


def _parse_machine_list(path: str) -> List[Tuple[str, int]]:
    machines: List[Tuple[str, int]] = []
    with open(path) as fh:
        for line in fh:
            parts = line.split()
            if len(parts) >= 2:
                machines.append((parts[0], int(parts[1])))
    return machines


def _local_addresses() -> set:
    """Best-effort local interface addresses (GetLocalIpList,
    socket_wrapper.hpp:157-197)."""
    addrs = {"127.0.0.1", "localhost", "0.0.0.0"}
    try:
        hostname = socket.gethostname()
        addrs.add(hostname)
        for info in socket.getaddrinfo(hostname, None):
            addrs.add(info[4][0])
    except OSError:
        pass
    return addrs


def _already_distributed() -> bool:
    """Whether jax.distributed.initialize already ran in this process.

    Checked WITHOUT jax.process_count(): that call initializes the XLA
    backend as a side effect, after which jax.distributed.initialize
    refuses to run ("must be called before any JAX calls") — probing via
    process_count would permanently break the machine_list_file bootstrap
    it is guarding."""
    return jax.distributed.is_initialized()


def initialize_from_config(cfg=None) -> bool:
    """Attach to (or bootstrap) the multi-process JAX runtime when the
    config/env asks for more than one machine.  Returns True when this
    process is part of a >1-process world.  Idempotent."""
    if _already_distributed():
        return jax.process_count() > 1

    coord = os.environ.get("LGBM_TPU_COORDINATOR", "")
    nproc = int(os.environ.get("LGBM_TPU_NUM_PROCESSES", "0") or 0)
    pid = int(os.environ.get("LGBM_TPU_PROCESS_ID", "-1") or -1)

    mlist = getattr(cfg, "machine_list_file", "") if cfg is not None else ""
    want = getattr(cfg, "num_machines", 1) if cfg is not None else nproc
    if not coord and mlist and want > 1:
        machines = _parse_machine_list(mlist)
        if len(machines) < want:
            Log.fatal(
                f"machine_list_file lists {len(machines)} machines, "
                f"num_machines={want}"
            )
        coord = f"{machines[0][0]}:{machines[0][1]}"
        nproc = want
        if pid < 0:
            local = _local_addresses()
            ranks = [i for i, (ip, _) in enumerate(machines) if ip in local]
            if len(ranks) == 1:
                pid = ranks[0]
            else:
                Log.fatal(
                    "cannot determine this machine's rank from "
                    f"machine_list_file (matches: {ranks}); set "
                    "LGBM_TPU_PROCESS_ID"
                )

    if coord and nproc > 1 and 0 <= pid < nproc:
        Log.info(
            f"Initializing distributed runtime: coordinator={coord}, "
            f"num_processes={nproc}, process_id={pid}"
        )
        # failure handling mirrors the reference's socket bootstrap: a
        # bounded retry loop (20 x 10s connect retries,
        # linkers_socket.cpp:182-197) under the config's time_out budget
        # (minutes, config.h:227).  jax.distributed's own
        # initialization_timeout covers the coordinator barrier.
        import time as _time

        timeout_s = 60 * int(getattr(cfg, "time_out", 120) or 120)
        attempts = 20
        deadline = _time.monotonic() + timeout_s
        for attempt in range(1, attempts + 1):
            try:
                jax.distributed.initialize(
                    coordinator_address=coord,
                    num_processes=nproc,
                    process_id=pid,
                    initialization_timeout=max(
                        10, min(timeout_s // attempts,
                                int(deadline - _time.monotonic()) or 1),
                    ),
                )
                break
            except Exception as e:  # noqa: BLE001 — retry any init failure
                try:  # a failed initialize leaves jax's global client set;
                    # without a shutdown every retry would instantly raise
                    # "should only be called once"
                    jax.distributed.shutdown()
                except Exception:
                    pass
                if attempt == attempts or _time.monotonic() >= deadline:
                    Log.fatal(
                        f"distributed init failed (attempt {attempt}/"
                        f"{attempts}, time_out={timeout_s // 60}min): "
                        f"{type(e).__name__}: {e}"
                    )
                Log.warning(
                    f"distributed init attempt {attempt}/{attempts} failed "
                    f"({type(e).__name__}); retrying"
                )
                # pace fast-failing errors (bad DNS, port still held by a
                # restarting coordinator) like the reference's 10s-spaced
                # connect retries, without overshooting the deadline
                _time.sleep(min(10.0, max(0.0, deadline - _time.monotonic())))
        return jax.process_count() > 1
    return False


def describe_topology() -> dict:
    """This process's rank-topology block, for checkpoint manifests and
    rank telemetry (obs/dist.py): who am I, how wide is the world, and
    which devices are local.  Resolution mirrors obs/dist.py — the live
    jax runtime when one is attached, else the launcher env
    (``LGBM_TPU_PROCESS_ID``/``LGBM_TPU_NUM_PROCESSES``), so a gang
    supervisor's CPU-only rank children report the same shape a real
    multihost world would."""
    topo = {
        "process_id": int(os.environ.get("LGBM_TPU_PROCESS_ID", "0") or 0),
        "num_processes": int(
            os.environ.get("LGBM_TPU_NUM_PROCESSES", "1") or 1),
        "local_devices": 0,
        "global_devices": 0,
        "platform": "",
    }
    # only query the live runtime when a backend already exists — the
    # probe must never initialize XLA as a side effect (that would
    # break the machine_list_file bootstrap _already_distributed guards)
    # private import: jax has no public "is a backend initialized yet?"
    # (jax.extend.backend.backends() initializes one by asking)
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        try:
            topo["process_id"] = jax.process_index()
            topo["num_processes"] = jax.process_count()
            topo["local_devices"] = jax.local_device_count()
            topo["global_devices"] = jax.device_count()
            topo["platform"] = jax.devices()[0].platform
        except Exception:  # noqa: BLE001 — env fallback already filled in
            pass
    gang_dir = os.environ.get("LGBM_TPU_GANG_DIR", "")
    if gang_dir:
        topo["gang_id"] = os.environ.get("LGBM_TPU_GANG_ID", "gang")
        topo["gang_slot"] = int(
            os.environ.get("LGBM_TPU_GANG_SLOT", "0") or 0)
    return topo


def sync_config_across_processes(cfg) -> None:
    """Cross-process config agreement — the reference's GlobalSyncUpByMin
    (application.cpp:110-127, 190-198, 259-270): randomized-behavior
    seeds/fractions take the MIN across ranks so every machine samples
    identically, and the load-bearing training params are fingerprinted
    and verified equal (the reference trusts operators to ship the same
    conf file; we fail fast instead of silently training a mixed world).
    No-op single-process.  Mutates ``cfg`` in place."""
    if jax.process_count() <= 1 or cfg is None:
        return
    from jax.experimental import multihost_utils

    # Exchange VALUES losslessly: under the default x64-disabled mode,
    # process_allgather downcasts f64->f32 / i64->i32 on the way through
    # the device, which would corrupt seeds >= 2^24 and add f32 drift to
    # fractions even when every rank already agrees.  Seeds ride as
    # int32 (config ints); fractions ride as their f64 BIT PATTERN in
    # two int32 lanes and are reassembled host-side before the min.
    seed_names = ("data_random_seed", "feature_fraction_seed", "bagging_seed")
    frac_names = ("feature_fraction", "bagging_fraction")
    seeds = np.asarray(
        [int(getattr(cfg, k, 0)) for k in seed_names], np.int32
    )
    fracs = np.asarray(
        [float(getattr(cfg, k, 1.0)) for k in frac_names], np.float64
    )
    payload = np.concatenate([seeds, fracs.view(np.int32)])  # [3 + 4] i32
    # traced + guarded collective (obs/dist.py over resilience/retry.py):
    # a peer that died before joining this allgather would otherwise hang
    # EVERY rank forever — collective_deadline_s (or
    # LGBM_TPU_COLLECTIVE_DEADLINE_S) bounds the wait and fails loudly,
    # transient UNAVAILABLE errors retry with backoff attributed to this
    # site (and the fail_collective_once chaos fault injects here).  The
    # tracing wrapper splits barrier wait (straggler time) from the
    # transfer and feeds the per-op collective counters.
    from ..obs import dist
    from ..resilience.retry import collective_deadline_s

    world = jax.process_count()
    gathered = dist.traced_collective(
        lambda: multihost_utils.process_allgather(payload),
        op="all-gather", label="config_sync",
        payload_bytes=int(payload.size) * 4 * world,
        barrier_fn=lambda: multihost_utils.sync_global_devices(
            "lgbm_config_sync"),
        deadline_s=collective_deadline_s(cfg))  # [P, 7] i32
    gathered = np.ascontiguousarray(np.asarray(gathered))
    seed_min = gathered[:, :3].min(axis=0)
    frac_all = gathered[:, 3:].view(np.float64)  # [P, 2]
    frac_min = frac_all.min(axis=0)
    for k, v in zip(seed_names, seed_min):
        if hasattr(cfg, k):
            setattr(cfg, k, int(v))
    for k, v in zip(frac_names, frac_min):
        if hasattr(cfg, k):
            setattr(cfg, k, float(v))

    # structural params must MATCH, not reconcile: a rank training with a
    # different tree shape would diverge at the first collective
    import zlib

    fp_src = "|".join(
        f"{k}={getattr(cfg, k, None)}" for k in (
            "objective", "num_iterations", "learning_rate", "num_leaves_",
            "max_bin", "min_data_in_leaf", "min_sum_hessian_in_leaf",
            "lambda_l1", "lambda_l2", "max_depth", "tree_learner",
            "boosting_type", "num_class",
        )
    )
    # crc32 is uint32; mask to int31 so the int32 transport is lossless
    fp = np.asarray([zlib.crc32(fp_src.encode()) & 0x7FFFFFFF], np.int32)
    fps = np.asarray(dist.traced_collective(
        lambda: multihost_utils.process_allgather(fp),
        op="all-gather", label="config_fingerprint",
        payload_bytes=4 * world,
        deadline_s=collective_deadline_s(cfg))).ravel()
    if len(set(int(x) for x in fps)) > 1:
        Log.fatal(
            "training config differs across processes "
            f"(fingerprints {sorted(set(int(x) for x in fps))}); every "
            "rank must run with identical structural parameters"
        )


def make_multihost_data_parallel_grower(
    mesh, num_bins: int, max_leaves: int, axis: str = ROW_AXIS,
    sorted_hist: bool = False, hist_pool: int = 0, record: bool = True,
    collective_deadline: Optional[float] = None,
):
    """Data-parallel grower across processes: each process feeds its
    LOCAL row partition (the per-rank ingest split, io/distributed.py);
    the shard-mapped growth program runs SPMD over the global mesh with
    psum collectives crossing hosts.

    Contract (mirrors the reference's balanced per-rank partition,
    dataset_loader.cpp:500-605): every process must pass the same number
    of LOCAL rows, padded here to a multiple of the local device count
    with bag_mask-0 rows.  Returns the (replicated) tree as host numpy
    and this process's local leaf partition.

    Observability (obs/dist.py): each call times its dispatch and its
    host fetch as ``dist.grow.dispatch`` / ``dist.grow.fetch`` spans
    (host-wall — the fetch span ends AFTER the np.asarray sync, so it
    is real device+transfer time; the dispatch span is trace+enqueue
    wall), and — in a >1-process world — piggybacks a desync sentinel
    on the fetch sync point: a cheap int32[3] fingerprint allgather of
    (step, crc32 of the grown tree's bytes).  Ranks whose trees diverge
    are NAMED within the iteration (`DesyncError`) instead of shipping
    bitwise-divergent models.  ``LGBM_TPU_DESYNC_CHECK=0`` disables,
    ``=N`` checks every N trees.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..obs import dist, telemetry
    from ..resilience.retry import collective_deadline_s

    # caller passes the config's deadline (gbdt does); None falls back
    # to the env override alone
    sentinel = dist.DesyncSentinel(
        deadline_s=collective_deadline_s(None)
        if collective_deadline is None else collective_deadline)
    step_box = [0]  # grow() calls on this rank (the boosting iteration)
    cfg_crc_box = [None]  # config half of the sentinel fingerprint

    sharded = jax.jit(
        data_parallel_sharded(
            mesh, num_bins, max_leaves, axis=axis, sorted_hist=sorted_hist,
            hist_pool=hist_pool, record=record,
        )
    )
    col_s = NamedSharding(mesh, P(None, axis))
    row_s = NamedSharding(mesh, P(axis))

    def grow(bins_T, grad, hess, bag_mask, fmask, nbpf, is_cat, params):
        with telemetry.span("dist.grow.dispatch"):
            bins_T = np.asarray(bins_T)
            grad = np.asarray(grad)
            hess = np.asarray(hess)
            bag_mask = np.asarray(bag_mask)
            n_local = bins_T.shape[1]
            pad = (-n_local) % jax.local_device_count()
            if pad:
                bins_T = np.pad(bins_T, ((0, 0), (0, pad)))
                grad = np.pad(grad, (0, pad))
                hess = np.pad(hess, (0, pad))
                bag_mask = np.pad(bag_mask, (0, pad))  # invisible rows

            mk = jax.make_array_from_process_local_data
            g_bins = mk(col_s, bins_T)
            g_grad = mk(row_s, grad)
            g_hess = mk(row_s, hess)
            g_bag = mk(row_s, bag_mask)
            # replicated small inputs go in as host numpy (identical on
            # every process; jit replicates them without communication)
            tree, leaf_id = sharded(
                g_bins, g_grad, g_hess, g_bag,
                np.asarray(fmask), np.asarray(nbpf), np.asarray(is_cat),
                jax.tree.map(np.asarray, params),
            )
        with telemetry.span("dist.grow.fetch"):
            # tree is replicated -> each process holds a full copy; the
            # np.asarray here is the per-iteration sync point the desync
            # sentinel piggybacks on
            tree = jax.tree.map(
                lambda a: np.asarray(a.addressable_data(0)), tree)
            # leaf_id is row-sharded; stitch this process's shards in order
            shards = sorted(
                leaf_id.addressable_shards,
                key=lambda s: s.index[0].start or 0
            )
            local = np.concatenate(
                [np.asarray(s.data) for s in shards])[:n_local]
        step_box[0] += 1
        if sentinel.should_check(step_box[0]):
            # fingerprint = (structural params crc, crc32 over every
            # tree field's bytes): bitwise tree divergence (the thing
            # the serial-equality dryrun pins offline) AND a rank
            # training under different params are both caught HERE,
            # named, within one iteration
            if cfg_crc_box[0] is None:
                cfg_crc_box[0] = dist.config_crc(
                    jax.tree.map(lambda a: np.asarray(a).tolist(), params))
            fp = dist.state_fingerprint(
                step_box[0], cfg_crc_box[0],
                *(np.asarray(f).tobytes() for f in tree))
            sentinel.verify(step_box[0], fp)
        return tree, local

    return grow
