"""Serving: batched HTTP inference frontends + multi-host coordination.

Capability parity with Spark Serving (`src/io/http` serving sources/sinks)
rebuilt for the TPU execution model — see :mod:`mmlspark_tpu.serving.server`.

The socket edge is selectable: the default event-loop frontend
(:mod:`mmlspark_tpu.serving.frontend` — keep-alive connection reuse,
zero-copy framing, ``SO_REUSEPORT`` acceptors) or the threaded
``http.server`` baseline (``frontend="threaded"``). See
``docs/serving.md`` "The socket edge".

Observability: every worker serves ``GET /metrics`` (Prometheus text
format) and carries ``X-Trace-Id`` through its whole data plane; the
:class:`ServingCoordinator` aggregates the fleet — ``GET /fleet`` merges
every worker's ``/stats`` (naming the slowest stage fleet-wide) and
``GET /fleet/metrics`` merges their scrapes into one exposition. See
``docs/observability.md``.
"""

from mmlspark_tpu.serving.server import (
    ServingClient, ServingCoordinator, ServingServer,
)
from mmlspark_tpu.serving.capture import TrafficCapture
from mmlspark_tpu.serving.consolidator import PartitionConsolidator
from mmlspark_tpu.serving.decode import (
    DecodeOverloaded, DecodeScheduler, PagePool, PrefixCache, Sampler,
    SlotPool, TransformerDecoder, decoder_for,
)
from mmlspark_tpu.serving.frontend import EventLoopFrontend
from mmlspark_tpu.serving.incident import FanoutNotifier, IncidentManager
from mmlspark_tpu.serving.policy import (
    AdaptiveBatchPolicy, PriorityShedPolicy, SpeculationPolicy,
)
from mmlspark_tpu.serving.quant import QuantizationConfig
from mmlspark_tpu.serving.rollout import (
    ModelVersionManager, RolloutError, RolloutOrchestrator,
)
from mmlspark_tpu.serving.tenancy import (
    FairCycle, Tenant, TenantRegistry, TokenBucket, extract_api_key,
)

__all__ = ["ServingServer", "ServingCoordinator", "ServingClient",
           "PartitionConsolidator", "EventLoopFrontend",
           "ModelVersionManager", "RolloutError", "RolloutOrchestrator",
           "DecodeScheduler", "DecodeOverloaded", "SlotPool", "PagePool",
           "PrefixCache",
           "TransformerDecoder", "decoder_for", "AdaptiveBatchPolicy",
           "QuantizationConfig",
           "SpeculationPolicy", "Sampler", "TrafficCapture",
           "Tenant", "TenantRegistry", "TokenBucket", "FairCycle",
           "PriorityShedPolicy", "extract_api_key",
           "IncidentManager", "FanoutNotifier"]
