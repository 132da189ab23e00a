"""Dynamic-batching inference: snapshot -> frozen engine -> dispatcher.

The serving subsystem (doc/serving.md). Pieces:

- :mod:`~cxxnet_tpu.serve.bucketing` — the batch-size bucket ladder
  every padded dispatch shape comes from
- :mod:`~cxxnet_tpu.serve.engine` — frozen eval-mode engine with AOT
  executables per bucket (zero compile events after warmup)
- :mod:`~cxxnet_tpu.serve.batcher` — coalescing micro-batch dispatcher:
  bounded queue, reject-with-busy backpressure, per-request deadlines,
  exception propagation, graceful drain, pipelined H2D hand-off
- :mod:`~cxxnet_tpu.serve.server` — config-driven ``ServeSession`` and
  the closed-loop client drive behind ``task = serve``

The fleet layer (``task = serve_fleet``, doc/serving.md):

- :mod:`~cxxnet_tpu.serve.router` — multi-model routing: N engines
  behind one front end, atomic hot-swap flip
- :mod:`~cxxnet_tpu.serve.quota` — per-tenant token-bucket quotas and
  typed over-quota shedding
- :mod:`~cxxnet_tpu.serve.swap` — checkpoint-driven zero-downtime
  hot-swap (verified-snapshot watcher, shadow warmup, flip + drain)
- :mod:`~cxxnet_tpu.serve.frontend` — the network front end: HTTP/JSON
  + length-prefixed binary protocols over one shared request core
"""

from .batcher import (DynamicBatcher, ServeBusyError, ServeClosedError,
                      ServeTimeoutError)
from .bucketing import (bucket_ladder, mesh_align, pad_to_bucket,
                        parse_buckets, pick_bucket)
from .engine import InferenceEngine, StagedBatch, build_engine
from .frontend import (BinaryClient, FailoverBinaryClient,
                       FailoverHttpClient, FleetConfig, FleetServer,
                       registry_endpoints)
from .quota import QuotaManager, TenantQuotaError, TokenBucket
from .router import ModelRouter, UnknownModelError
from .server import ServeConfig, ServeSession, run_closed_loop
from .swap import SnapshotWatcher, latest_verified

__all__ = [
    "DynamicBatcher", "ServeBusyError", "ServeClosedError",
    "ServeTimeoutError", "bucket_ladder", "mesh_align", "pad_to_bucket",
    "parse_buckets", "pick_bucket", "InferenceEngine", "StagedBatch",
    "build_engine", "ServeConfig", "ServeSession", "run_closed_loop",
    "BinaryClient", "FailoverBinaryClient", "FailoverHttpClient",
    "registry_endpoints", "FleetConfig", "FleetServer", "QuotaManager",
    "TenantQuotaError", "TokenBucket", "ModelRouter",
    "UnknownModelError", "SnapshotWatcher", "latest_verified",
]
