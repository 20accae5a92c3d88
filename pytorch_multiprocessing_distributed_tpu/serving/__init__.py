"""Serving: continuous-batching request engine over the KV-cache decode.

The inference half of the north star ("serve heavy traffic"): a
slot-based engine (``engine``) whose jitted decode keeps a SMALL
FIXED compiled-program set — one per (length bucket, horizon rung),
never per batch composition — with per-step attention cost tracking
the longest ACTIVE sequence instead of the cache capacity, over ONE
paged KV pool (``kv_pages``), steady-state decode fused H steps per
dispatch with
ONE token-block readback per horizon (``decode_horizon`` — host
syncs/token = 1/H, on-device EOS/budget freezing keeps it
token-exact), the step pipelined one block deep at every horizon (the
next block is dispatched, admissions included, before this one is read
back), prompts admitted whole or in fixed-size chunks
interleaved with decode (``scheduler.PrefillPlan``), fed by a FIFO
scheduler with admission control and the adaptive horizon policy
(``scheduler``), loading trained checkpoints param-only (``params``).
graftroute (``router``/``replica``) composes N engines into ONE
fleet: cache- and load-aware placement, AIMD admission windows +
work stealing, prefill/decode disaggregation over a host
``PageTransfer`` seam, and journal redelivery across replica death.
graftscale (``autoscale``) closes the loop: traffic decides the
fleet size (supervised spawn/drain from the router's own signals,
per-role, hysteresis + cooldown) and ``RollingRollout`` upgrades
weights under continuous load with zero failed requests.
CLI: repo-root ``serve_lm.py`` (``--replicas N`` for the fleet,
``--autoscale MIN,MAX`` / ``--rollout PATH`` for graftscale).
"""

from .autoscale import (AutoscaleError, EngineReplicaSpawner,
                        FleetAutoscaler, ProcessReplicaSpawner,
                        RollingRollout, ScaleEvent, SpawnFailed)
from .engine import ServingEngine
from .kv_pages import PagePool, PagePoolExhausted, PrefixCache
from .params import init_params, load_params
from .remote import (RemoteReplica, ReplicaServer,
                     fleet_from_directory)
from .replica import PageTransfer, ServingReplica
from .router import (FleetDead, FleetSaturated, PrefixCacheDirectory,
                     Router)
from .scheduler import (DONE, FAILED, FIFOScheduler, PrefillPlan,
                        QueueFull, Request, bucket_length, pick_draft_k,
                        pick_horizon)
from .spec import NgramDrafter, ngram_bucket

__all__ = [
    "ServingEngine", "PagePool", "PagePoolExhausted", "PrefixCache",
    "FIFOScheduler", "PrefillPlan", "NgramDrafter",
    "QueueFull", "Request", "bucket_length", "init_params",
    "load_params", "ngram_bucket", "pick_draft_k", "pick_horizon",
    "DONE", "FAILED",
    # graftroute: fleet serving
    "Router", "ServingReplica", "PageTransfer",
    "PrefixCacheDirectory", "FleetSaturated", "FleetDead",
    # graftwire: the socket transport behind the replica seam
    "ReplicaServer", "RemoteReplica", "fleet_from_directory",
    # graftscale: traffic-driven autoscaling + rolling rollout
    "FleetAutoscaler", "RollingRollout", "EngineReplicaSpawner",
    "ProcessReplicaSpawner", "ScaleEvent", "AutoscaleError",
    "SpawnFailed",
]
