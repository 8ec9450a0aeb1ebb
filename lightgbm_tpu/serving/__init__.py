"""Online serving layer: micro-batched inference + hot-swap (ROADMAP 4).

The layer on top of ops/models that turns the fast matmul predictor
into a *service*:

* :mod:`engine`  — persistent on-device ensemble, padded-shape
  power-of-two bucketing, pre-warmed (recompile-free steady state by
  construction).
* :mod:`queue`   — micro-batching request queue: concurrent ``submit``s
  coalesce into one bucketed dispatch under a max-latency / max-batch
  policy; results scatter back to futures.
* :mod:`hotswap` — checksum-verified adoption of a new boosting round
  under load: verify ``.sha256`` sidecar, pack + prewarm off-path,
  atomic flip; corrupt candidates are refused loudly.
* :mod:`server`  — stdlib HTTP/JSON front end (``task=serve``) plus the
  in-process client tier-1 tests use.
* :mod:`batch`   — the batch tier: overlapped parse -> predict -> write
  file prediction (byte-identical to the sequential path, crash-safe
  via ``atomic_writer``).
* :mod:`supervisor` — the fleet layer (``task=serve_fleet``): N
  supervised replica subprocesses, health-checked restarts with
  jittered backoff and a hard budget, round-robin routing with one
  bounded retry on a different replica, queue-depth autoscaling.

See docs/serving.md for the architecture, the bucketing policy, the
hot-swap contract, and the fault matrix.
"""

from .batch import (format_block, pipelined_predict_file,
                    predict_chunk_stream)
from .engine import PackedModel, ServingEngine, power_of_two_buckets
from .hotswap import adopt_model, load_packed_model
from .queue import (DeadlineExpired, MicroBatchQueue, PredictionResult,
                    QueueDraining, QueueFull, RequestShed)
from .server import (InProcessClient, ServingServer, serve_from_config,
                     write_serving_manifest)
from .supervisor import (FleetBudgetExhausted, FleetFrontEnd,
                         FleetRequestFailed, ReplicaSupervisor,
                         SubprocessReplica, ThreadReplica,
                         serve_fleet_from_config)

__all__ = [
    "format_block", "pipelined_predict_file", "predict_chunk_stream",
    "PackedModel", "ServingEngine", "power_of_two_buckets",
    "adopt_model", "load_packed_model",
    "MicroBatchQueue", "PredictionResult",
    "RequestShed", "QueueFull", "DeadlineExpired", "QueueDraining",
    "InProcessClient", "ServingServer", "serve_from_config",
    "write_serving_manifest",
    "ReplicaSupervisor", "SubprocessReplica", "ThreadReplica",
    "FleetFrontEnd", "FleetRequestFailed", "FleetBudgetExhausted",
    "serve_fleet_from_config",
]
