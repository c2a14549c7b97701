from repro.serve.autotune import (AUTOTUNE_MODES, GridDecision, GridPlanner,
                                  default_candidates)
from repro.serve.engine import (ContinuousEngine, EngineMetrics,
                                GenerateResult, ServeEngine)
from repro.serve.faults import (ENGINE_FAULT_KINDS, FAULT_KINDS, FAULT_REQ,
                                FLEET_FAULT_KINDS, FaultInjector, FaultPlan,
                                FaultSpec, TransientFault, canned_fleet_plan,
                                canned_plan)
from repro.serve.frontend import (AsyncFrontend, AsyncStream, RequestResult,
                                  RequestTracker, TrackedRequest)
from repro.serve.guard import (GUARD_STATES, EngineGuard, EngineSheddingError,
                               GuardConfig, GuardSignals)
from repro.serve.invariants import (InvariantViolation, check_invariants,
                                    leaked_blocks)
from repro.serve.journal import (FSYNC_POLICIES, Journal, JournalCorrupt,
                                 ReplayedRequest, ReplayState, replay,
                                 state_digest)
from repro.serve.kernel_costs import (CostParams, LaunchCost,
                                      decode_launch_cost, estimate_seconds,
                                      prefill_launch_cost)
from repro.serve.kv_pool import PagedKVCache, PoolExhausted, PoolStats
from repro.serve.metrics import (Counter, Gauge, Histogram, MetricRegistry,
                                 parse_prometheus_text)
from repro.serve.radix_cache import CacheStats, RadixCache
from repro.serve.router import ROUTING_POLICIES, PlacementDecision, Router
from repro.serve.scheduler import (FINISH_CANCELLED, FINISH_DEADLINE,
                                   FINISH_FAILOVER, FINISH_LENGTH,
                                   FINISH_QUARANTINED,
                                   CapacityExceededError,
                                   DuplicateRequestError, EmptyPromptError,
                                   Request, Scheduler, SubmitError)
from repro.serve.snapshot import (Snapshot, SnapshotCorrupt, apply_snapshot,
                                  engine_fingerprint, requeue_inflight,
                                  restore_engine, snapshot_state,
                                  write_snapshot)
from repro.serve.supervisor import (FleetSupervisor, ReplicaHandle,
                                    replica_device, snapshot_path)
from repro.serve.telemetry import (ManualClock, RequestTrace, StepTimeline,
                                   Telemetry)

__all__ = ["ContinuousEngine", "EngineMetrics", "GenerateResult",
           "ServeEngine", "PagedKVCache", "PoolExhausted", "PoolStats",
           "RadixCache", "CacheStats", "Request", "Scheduler",
           "Counter", "Gauge", "Histogram", "MetricRegistry",
           "parse_prometheus_text", "ManualClock", "RequestTrace",
           "StepTimeline", "Telemetry",
           "AUTOTUNE_MODES", "GridDecision", "GridPlanner",
           "default_candidates", "CostParams", "LaunchCost",
           "decode_launch_cost", "prefill_launch_cost",
           "estimate_seconds",
           # resilience layer (PR 8)
           "FAULT_KINDS", "FAULT_REQ", "FaultInjector", "FaultPlan",
           "FaultSpec", "TransientFault", "canned_plan",
           "GUARD_STATES", "EngineGuard", "EngineSheddingError",
           "GuardConfig", "GuardSignals",
           "InvariantViolation", "check_invariants", "leaked_blocks",
           "SubmitError", "EmptyPromptError", "DuplicateRequestError",
           "CapacityExceededError", "FINISH_LENGTH", "FINISH_CANCELLED",
           "FINISH_DEADLINE", "FINISH_QUARANTINED",
           # fleet serving layer (PR 9)
           "ENGINE_FAULT_KINDS", "FLEET_FAULT_KINDS", "canned_fleet_plan",
           "FINISH_FAILOVER", "AsyncFrontend", "AsyncStream",
           "RequestResult", "RequestTracker", "TrackedRequest",
           "Journal", "JournalCorrupt", "ReplayState", "ReplayedRequest",
           "replay", "ROUTING_POLICIES", "PlacementDecision", "Router",
           "FleetSupervisor", "ReplicaHandle", "replica_device",
           # durability layer (PR 10)
           "FSYNC_POLICIES", "state_digest", "Snapshot", "SnapshotCorrupt",
           "apply_snapshot", "engine_fingerprint", "requeue_inflight",
           "restore_engine", "snapshot_state", "write_snapshot",
           "snapshot_path"]
