"""Continuous-batching generation service (DESIGN.md §11, fleet tier §17).

``engine.SlotArena`` is the device half: a fixed-shape slot-structured KV
arena where admission/retirement are ``dynamic_update_slice``s and one
jitted tick decodes every occupied slot under a per-slot active mask —
never a shape change, never a retrace (gated by graftspmd's S3 serve
check).  ``scheduler.GenerationServer`` is the host half: thread-safe
request queue, iteration-level admission, SLO-aware scheduling
(latency-class requests preempt throughput-class fills), and the
per-request latency / aggregate throughput accounting ``bench_serve``
reports.

The fleet tier sits on top: ``replica.Replica`` wraps one server with a
JOINING→SERVING→DRAINING→DEAD lifecycle + driver thread, and
``router.FleetRouter`` routes over N replicas — consistent-hash
affinity with queue-depth spill, SLO-aware shedding (typed
``ShedError``), bounded retries with exponential backoff, drain/join
riding the rc-74 preemption contract, and an exactly-once future
resolution audit (zero dropped futures under replica loss).

graftwire (§21) pushes the same seam across a process boundary:
``wire`` is the stdlib framed-JSON RPC transport (typed failure
classes, deadline + bounded retry + jittered backoff, ``rpc_send`` /
``rpc_recv`` fault sites), and ``remote`` pairs a subprocess-side
``ReplicaServer`` with a router-side ``RemoteReplica`` that presents
the exact ``Replica`` surface — the router needs no remote-aware code.
"""
from .autoscale import (AutoScaler, Decision, DegradeLevel, ScalePolicy,
                        Signals)
from .engine import ArenaGeometry, SlotArena
from .prefix import RadixPrefixCache
from .remote import (RemoteReplica, ReplicaServer, SpawnFailed,
                     spawn_replica)
from .replica import (DEAD, DRAINING, JOINING, SERVING, Replica,
                      ReplicaDown)
from .router import (FleetRouter, NoHealthyReplica, RequestFailed,
                     RetriesExhausted, RouterError, RouterHandle,
                     ShedError)
from .scheduler import (LATENCY, SLO_CLASSES, THROUGHPUT, GenerationServer,
                        ServeHandle, ServerStopped)
from .wire import (WireClient, WireError, WireProtocolError,
                   WireRemoteError, WireReset, WireServer, WireTimeout,
                   WireUnavailable)

__all__ = [
    "ArenaGeometry", "SlotArena", "RadixPrefixCache", "GenerationServer",
    "ServeHandle",
    "ServerStopped", "LATENCY", "THROUGHPUT", "SLO_CLASSES",
    "Replica", "ReplicaDown", "JOINING", "SERVING", "DRAINING", "DEAD",
    "FleetRouter", "RouterHandle", "RouterError", "ShedError",
    "RetriesExhausted", "RequestFailed", "NoHealthyReplica",
    "WireClient", "WireServer", "WireError", "WireTimeout",
    "WireUnavailable", "WireReset", "WireProtocolError", "WireRemoteError",
    "RemoteReplica", "ReplicaServer", "spawn_replica", "SpawnFailed",
    "AutoScaler", "Decision", "DegradeLevel", "ScalePolicy", "Signals",
]
