"""Fault-tolerant multi-replica serving fleet.

A host-side layer over N ServingEngine replicas: health-routed load
balancing, crash/wedge failover with completed-prefix dedup,
tail-latency hedging, graceful drain/rejoin through the resilience
preemption seam, and priority load shedding — all chaos-testable on
CPU via resilience.faults (replica_crash / replica_wedge /
replica_slow / scrape_timeout / flaky_transport) and all host-side
bookkeeping, so every replica's zero-recompile contract survives the
whole failure model.

- InprocReplica:  one engine + worker thread behind a transport seam
                  (replica.py; a subprocess replica speaks the same
                  verbs over a wire). The response plane is
                  at-least-once with explicit acks: results are
                  retained until the router durably processed them,
                  so a router crash cannot lose a finished request
- ProcReplica:    the same verbs across a REAL process boundary
                  (proc.py + proc_child.py): one ServingEngine per OS
                  subprocess, length-prefixed checksummed JSONL over
                  pipes (the journal's framing), streamed partial
                  tokens for SIGKILL-grade failover, per-incarnation
                  result stamping, warm-boot respawn
- FleetSupervisor: self-healing replica lifecycle (supervisor.py):
                  OS-level crash detection, seeded-backoff respawn,
                  health-gated warm-boot rejoin, crash-loop circuit
                  breaker with quarantine + cooldown, `retiring`
                  exemption for autoscaler-owned scale-ins
- FleetAutoscaler: SLO-driven elastic capacity (autoscaler.py):
                  scale out on multi-window burn alerts / standing
                  overload, scale in on recovered budget + idle trend
                  with hysteresis + cooldowns, warm-boot-gated
                  adoption, drain->remove retirement, every decision
                  journaled + flight-dumped (fleet_autoscale_*)
- ReplicaClient:  idempotent-by-rid transport with seeded-jitter
                  retry (client.py)
- Journal:        the router's write-ahead request journal
                  (journal.py): append-only checksummed JSONL
                  segments, atomic COMPLETE-marker rotation, torn-
                  tail-tolerant replay, journal_* disk-fault seams —
                  FleetRouter.recover() replays it to re-adopt a
                  still-live fleet after a router crash/preemption
                  with token-exact, exactly-once continuation
- FleetRouter:    global queue, scrape-scored placement, failover/
                  hedging/drain/shed + its own MetricsRegistry,
                  distributed tracing (one causally-linked span tree
                  per request across router/transport/replicas, with
                  per-hop latency attribution via trace_report), SLO
                  burn-rate accounting (fleet_slo_* gauges), and a
                  full /metrics+/healthz+/report+/traces endpoint
                  (router.py)

See docs/robustness.md ("Fleet serving") for the contracts and
docs/observability.md for the fleet_* metric catalogue and the
"Distributed tracing & SLOs" guide. Chaos suites:
tests/test_fleet_serving.py + tests/test_fleet_tracing.py (pytest -m
chaos).
"""
from .autoscaler import FleetAutoscaler  # noqa: F401
from .client import ReplicaClient  # noqa: F401
from .journal import Journal, JournalCrash, JournalError  # noqa: F401
from .proc import FrameReader, ProcReplica  # noqa: F401
from .replica import InprocReplica, ReplicaCrash  # noqa: F401
from .router import FleetRouter, RouterCrash  # noqa: F401
from .supervisor import FleetSupervisor  # noqa: F401

__all__ = ["FleetAutoscaler", "FleetRouter", "FleetSupervisor",
           "FrameReader", "InprocReplica", "Journal", "JournalCrash",
           "JournalError", "ProcReplica", "ReplicaClient",
           "ReplicaCrash", "RouterCrash"]
