"""Deterministic fault-injection harness (``docs/resilience.md``).

Test/bench tooling, never wired by ``PlatformConfig`` — production
assemblies carry no chaos code path. Three parts:

- ``injector``   — seeded ``FaultInjector`` + wrappers for the HTTP hop
  (error status / connection-refused / latency / dropped response) and
  the queue publish surface (duplicate delivery);
- ``harness``    — kill/restart helpers: ``RestartableBackend`` (a
  worker that dies and comes back on the same port),
  ``kill_dispatcher``/``restart_dispatcher``;
- ``invariants`` — ``InvariantChecker`` riding the store's change feed:
  every accepted task terminates, no task is lost, no duplicate
  client-visible completion — plus chain-verified replica convergence
  per shard (``assert_replicas_converged``);
- ``disk``       — seeded filesystem fault injection on the journal's
  write path (torn/short write, ENOSPC, EIO-on-fsync, lost page cache)
  — the storage-layer analogue of the network injector;
- ``crashpoint`` — the crash-point sweep: kill/restart a journaled
  store at every record boundary and seeded mid-record offsets, assert
  0 acknowledged-task loss / no conflicting state / replica
  convergence per reboot (docs/durability.md).
"""

from .crashpoint import check_reboot, crash_offsets, drive_workload, sweep
from .disk import (DiskFaultInjector, DiskFaultRule, FaultyFile,
                   attach_journal_faults, lose_page_cache)
from .harness import (RestartableBackend, kill_dispatcher, kill_shard_primary,
                      kill_worker, rebalance_slot, restart_dispatcher,
                      restart_worker)
from .injector import (ChaosSession, ChaosSessionHolder, Decision,
                       FaultInjector, FaultRule, wrap_platform_http,
                       wrap_publish_duplicates)
from .invariants import InvariantChecker

__all__ = [
    "FaultInjector", "FaultRule", "Decision", "ChaosSession",
    "ChaosSessionHolder", "wrap_platform_http", "wrap_publish_duplicates",
    "RestartableBackend", "kill_dispatcher", "restart_dispatcher",
    "kill_worker", "restart_worker", "kill_shard_primary", "rebalance_slot",
    "InvariantChecker",
    "DiskFaultInjector", "DiskFaultRule", "FaultyFile",
    "attach_journal_faults", "lose_page_cache",
    "sweep", "drive_workload", "crash_offsets", "check_reboot",
]
