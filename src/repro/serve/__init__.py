"""repro.serve — the unified multi-tenant sensor-serving stack.

One package now holds every serving layer: the batched execution engine
(`engine.py`, formerly `repro.serving.circuit_engine`), per-tenant engine
**replica pools** with least-loaded routing and per-replica device pins
(`replicas.py`), the fleet router with deadline-driven micro-batching,
queue-depth **admission control** and manifest **hot-reload**
(`fleet.py` + `batcher.py`), the fleet controller — QoS classes,
per-tenant token-bucket rate limits, and a hysteresis replica
autoscaler (`autoscale.py`) — and a real network front: a
length-prefixed binary wire protocol with version-negotiated batch
frames (`protocol.py`), a sharded asyncio socket server with optional
connectionless UDP ingest (`server.py`) and a blocking client library
with batched submits and client-side coalescing (`client.py`).

In-process:

    from repro.serve import ClassifierFleet
    fleet = ClassifierFleet.from_emit_dir("artifacts", backends="swar",
                                          replicas=2, max_queue=2048)
    req = fleet.submit("tnn_cardio", reading)      # returns immediately
    label = req.result(timeout=1.0)                # blocks until served
    reqs, shed, retry_ms = fleet.submit_many("tnn_cardio", plane)  # batched
    fleet.shutdown(drain=True)

Over the wire:

    python -m repro.serve serve --emit-dir artifacts --port 7341 \
        --shards 2 --udp-port 7342                                 # server
    python -m repro.serve replay --emit-dir artifacts \
        --connect 127.0.0.1:7341 --batch 256                       # client

    from repro.serve.client import FleetClient
    with FleetClient("127.0.0.1", 7341) as c:
        label = c.submit("tnn_cardio", reading).result(timeout=1.0)
        labels = c.classify("tnn_cardio", plane)   # SUBMIT_BATCH frames
"""
from repro.serve.autoscale import (
    QOS_CLASSES,
    Autoscaler,
    AutoscaleConfig,
    TenantSignals,
    TokenBucket,
)
from repro.serve.batcher import MicroBatcher, QueuedItem
from repro.serve.engine import (
    STATS_WINDOW,
    CircuitServingEngine,
    SensorRequest,
    ServeStats,
)
from repro.serve.fleet import (
    DEFAULT_DEADLINE_MS,
    DEFAULT_MAX_BATCH,
    FLEET_BACKENDS,
    ClassifierFleet,
    FleetOverloadError,
    FleetRequest,
    TenantSpec,
)
from repro.serve.replicas import EngineReplica, ReplicaPool

__all__ = [
    "DEFAULT_DEADLINE_MS",
    "DEFAULT_MAX_BATCH",
    "FLEET_BACKENDS",
    "QOS_CLASSES",
    "STATS_WINDOW",
    "Autoscaler",
    "AutoscaleConfig",
    "CircuitServingEngine",
    "ClassifierFleet",
    "EngineReplica",
    "FleetOverloadError",
    "FleetRequest",
    "MicroBatcher",
    "QueuedItem",
    "ReplicaPool",
    "SensorRequest",
    "ServeStats",
    "TenantSignals",
    "TenantSpec",
    "TokenBucket",
]
