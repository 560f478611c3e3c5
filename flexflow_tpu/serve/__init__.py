"""Production serving subsystem (docs/SERVING.md).

The reference ships a Legion inference backend (``triton/``, ~18k LoC)
because an auto-parallelizing training framework is only half a
production story.  This package is the TPU-native analog over the
compiled decode path (:mod:`flexflow_tpu.models.gpt_decode`):

* :mod:`flexflow_tpu.serve.kvcache` — paged/block KV-cache allocator:
  the (L, B, H, S, D) cache becomes fixed-size blocks with a free list
  and per-request block tables, so long and short conversations share
  HBM instead of each reserving max-S.
* :mod:`flexflow_tpu.serve.scheduler` — continuous-batching scheduler:
  variable-length requests admitted FIFO into a shared fixed-slot
  decode step; finished sequences free their slot mid-flight and a
  queued request takes it without recompiling.
* :mod:`flexflow_tpu.serve.programs` — the four jitted serve programs
  (decode, chunked prefill, speculative draft and verify): one decoder
  trunk over the paged pools, built outside the engine.
* :mod:`flexflow_tpu.serve.engine` — the zero-per-step-sync serve loop
  over them (device-chained tokens, one host sync per flush window —
  the async-fit machinery applied to serving).
* :mod:`flexflow_tpu.serve.traffic` — synthetic open-loop traffic
  generator for CPU-smoke A/Bs (`bench.py serve_continuous_ab`).
* :mod:`flexflow_tpu.serve.objective` — ``ServeObjective``: prices
  steady-state decode tokens/s subject to a p99 per-token latency SLO,
  so ``unity_search --objective serve`` emits placements for inference.
* :mod:`flexflow_tpu.serve.driver` — the ``python -m flexflow_tpu
  --serve`` entry point.
* :mod:`flexflow_tpu.serve.disagg` / :mod:`flexflow_tpu.serve.wire` /
  :mod:`flexflow_tpu.serve.transport` — disaggregated prefill/decode:
  a split-pool cluster whose prefill and decode engines run on
  disjoint submeshes, handing KV across a priced, digest-checked
  ``ffkv/1`` transport.
* :mod:`flexflow_tpu.serve.fleet` — the fleet tier: a
  prefix-cache-aware router over N replica engines with session
  affinity, live replica→replica KV migration, SLO-tiered spillover,
  and a closed-loop autoscaler driven by the fleet's own ``ffmetrics``
  rollup (decisions on the ``fffleet/1`` stream).
"""

from flexflow_tpu.serve.disagg import DisaggregatedCluster, DisaggReport
from flexflow_tpu.serve.engine import ServeEngine, ServeReport
from flexflow_tpu.serve.fleet import (
    FleetAutoscaler,
    FleetReport,
    FleetRouter,
    read_fleet,
)
from flexflow_tpu.serve.kvcache import KVCacheOOM, PagedKVCache
from flexflow_tpu.serve.objective import ServeObjective, ServeSpec
from flexflow_tpu.serve.scheduler import (
    ContinuousBatchingScheduler,
    Request,
    RequestState,
)
from flexflow_tpu.serve.traffic import (
    TrafficSpec,
    multi_tenant_requests,
    synthetic_requests,
)
from flexflow_tpu.serve.transport import (
    InProcessTransport,
    Transport,
    TransportFull,
)
from flexflow_tpu.serve.wire import (
    KV_SCHEMA,
    HandoffError,
    decode_handoff,
    encode_handoff,
)

__all__ = [
    "PagedKVCache",
    "KVCacheOOM",
    "Request",
    "RequestState",
    "ContinuousBatchingScheduler",
    "ServeEngine",
    "ServeReport",
    "ServeSpec",
    "ServeObjective",
    "TrafficSpec",
    "synthetic_requests",
    "multi_tenant_requests",
    "DisaggregatedCluster",
    "DisaggReport",
    "FleetRouter",
    "FleetAutoscaler",
    "FleetReport",
    "read_fleet",
    "Transport",
    "InProcessTransport",
    "TransportFull",
    "KV_SCHEMA",
    "HandoffError",
    "encode_handoff",
    "decode_handoff",
]
