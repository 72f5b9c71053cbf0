"""repro.fleet — fault-tolerant sweep orchestration.

Shards any `repro.scenarios` suite (or dataset build) across a pool of
supervised worker processes, with the blobstore as the only coordination
spine: atomic lease files claim work, results land in the existing
content-addressed caches (so a re-launched fleet resumes from whatever
completed), and a supervisor handles retry/backoff, poison quarantine,
dead-worker reaping, and straggler deadlines. `repro.fleet.chaos`
injects deterministic fault plans so tests and CI can prove a disturbed
run converges to the bitwise-same cache as a clean one.

    from repro.fleet import FleetConfig, run_fleet, sweep_job_for, sweep_tasks

    runner = SweepRunner(backend, cache_dir="results/cache",
                         fleet=FleetConfig(workers=4))
    report = runner.run(get_suite("smoke16"))       # fleet-sharded

CLI: `python -m repro.fleet --suite smoke16 [--chaos "kill:worker=0,after=2"]`
Docs: docs/FLEET.md. Design: DESIGN.md §12.
"""
from .chaos import ChaosMonkey, Fault, FaultPlan, parse_plan
from .coord import Coordinator
from .jobs import (DatasetJob, FleetJob, SweepJob, dataset_tasks,
                   sweep_job_for, sweep_tasks)
from .metrics import FleetMetrics
from .supervisor import (DeviceBusyError, FleetConfig, default_coord_dir,
                         run_fleet, task_set_digest)

__all__ = [
    "ChaosMonkey", "Fault", "FaultPlan", "parse_plan",
    "Coordinator",
    "DatasetJob", "FleetJob", "SweepJob",
    "dataset_tasks", "sweep_job_for", "sweep_tasks",
    "FleetMetrics",
    "DeviceBusyError", "FleetConfig", "default_coord_dir", "run_fleet",
    "task_set_digest",
]
