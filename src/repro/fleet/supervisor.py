"""Fleet supervisor (`repro.fleet.supervisor`).

`run_fleet(tasks, job, config)` drives a pool of spawned worker
processes to a terminal state where **every task is accounted for**:
done (results verified on disk) or poisoned (quarantined with a
traceback manifest). The supervisor owns every policy decision — workers
only compute and report:

- **Resume** — on startup, leases whose owner pid is gone are broken and
  done markers are re-verified against the blobstore (a marker whose
  results went missing or corrupt is retracted and the chunk requeued).
  Tasks completed by a previous launch count as `already_done` and are
  never recomputed.
- **Retry vs poison** — a worker's err marker carries the
  `classify_error` verdict. Retryable failures requeue with
  `Backoff.delay(attempt, task_id)` — capped exponential, deterministic
  per-task jitter — up to `max_attempts`; deterministic failures (or
  retryable ones that exhaust attempts) move to `poison/` and stop
  consuming workers.
- **Reaping** — a lease whose heartbeat goes stale (`lease_timeout_s`)
  marks a dead or wedged owner: the supervisor SIGKILLs the pid (only
  its own children), breaks the lease, and requeues through the same
  retry path. Workers that exit nonzero holding a lease get the same
  treatment; the pool is topped back up to `workers` while work remains.
- **Stragglers** — completed-chunk wall times feed a `StepDeadline`
  (median + k*MAD); running chunks past the deadline are counted as
  stragglers, and past `straggler_kill_factor x` deadline (or the hard
  `chunk_timeout_s`) their worker is reaped and the chunk requeued.
- **Verification** — after the pool drains, every done task is
  re-verified through the integrity-checked blobstore; failures retract
  the marker and re-enter the loop (bounded by `verify_rounds`).

Correctness never rests on the supervisor's bookkeeping: results are
content-addressed atomic blobs, so the worst a wrong decision (broken
lease, double spawn) can cause is duplicate compute writing identical
bytes.

Workers are fresh interpreters started as `python -m repro.fleet.worker`
(see `_worker_env` for what they inherit), so how the parent itself was
launched (a script, `-m`, stdin, a pytest-xdist worker) never matters.
An accelerator belongs to one process at a time: a job that needs it
(`FleetJob.needs_device`) runs in one worker, and only when this
process holds no device; every other worker runs with
`JAX_PLATFORMS=cpu`.
"""
from __future__ import annotations

import dataclasses
import hashlib
import logging
import os
import pickle
import signal
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from ..obs.trace import TRACE_PARENT_ENV, configure as obs_configure, \
    get_tracer
from ..runtime.resilience import Backoff, StepDeadline
from .chaos import FaultPlan
from .coord import Coordinator
from .jobs import FleetJob, Task
from .metrics import FleetMetrics

logger = logging.getLogger("repro.fleet")


@dataclass(frozen=True)
class FleetConfig:
    """Knobs for one fleet run. Defaults suit real sweeps; tests shrink
    every timeout by ~10x."""
    workers: int = 2
    coord_dir: Optional[str] = None   # None: dispatcher derives one from
    #                                   its store root + the task-set digest
    heartbeat_s: float = 0.5          # worker lease-touch interval
    lease_timeout_s: float = 5.0      # heartbeat silence -> reap owner
    poll_s: float = 0.1               # supervisor/worker scan interval
    max_attempts: int = 3             # per-task tries before poison
    backoff: Backoff = field(default_factory=Backoff)
    chaos: Optional[FaultPlan] = None
    chunk_timeout_s: Optional[float] = None   # hard per-chunk wall cap
    straggler_kill_factor: float = 4.0        # x deadline -> reap
    deadline_k: float = 6.0                   # StepDeadline MAD multiplier
    verify_rounds: int = 2            # post-drain verify/requeue passes
    trace_dir: Optional[str] = None   # repro.obs span JSONL dir; None
    #                                   falls back to $REPRO_TRACE_DIR

    def with_coord_dir(self, coord_dir: str) -> "FleetConfig":
        return dataclasses.replace(self, coord_dir=coord_dir)


def task_set_digest(tasks: List[Task]) -> str:
    """Stable id of a work set — the default coord-dir name, so a
    relaunch of the same work lands on the same markers and leases."""
    ids = sorted(tid for tid, _ in tasks)
    return hashlib.sha256("|".join(ids).encode()).hexdigest()[:16]


def default_coord_dir(base_root: str, tasks: List[Task]) -> str:
    return os.path.join(base_root, "fleet", task_set_digest(tasks))


# <src>/repro/fleet/supervisor.py -> <src>: put on each worker's PYTHONPATH
_SRC_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# bound on the wait for a freshly launched pool to reach its start line
START_TIMEOUT_S = 60.0


class DeviceBusyError(RuntimeError):
    """A job that needs the accelerator cannot get it in a worker: this
    process already holds it, or more than one worker was asked for."""


def _held_platform() -> Optional[str]:
    """Platform of the JAX backend this process has initialized, or None
    if it has not touched one (and so holds no device)."""
    if "jax" not in sys.modules:
        return None
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return None
    import jax
    return jax.default_backend()


def _worker_env(job: FleetJob, workers: int) -> Dict[str, str]:
    """What every worker process of one run gets on top of this
    process's environment at spawn time (which carries the tracing
    variables).

    Workers import `repro` from this checkout's `src`. A job that needs
    no device runs its workers with `JAX_PLATFORMS=cpu`, so any number
    of them can import JAX beside a process that owns the chip. A job
    that needs the device keeps the CPU when the run is pinned to it
    (`JAX_PLATFORMS=cpu`) or when this process's JAX found nothing but
    the CPU; otherwise it gets the device in a single worker, and only
    if this process holds none — else `DeviceBusyError`, before any
    worker starts, instead of workers that hang on a taken chip."""
    env = {"PYTHONPATH": os.pathsep.join(
        [_SRC_ROOT] + [p for p in os.environ.get("PYTHONPATH", "").split(
            os.pathsep) if p])}
    if not job.needs_device:
        env["JAX_PLATFORMS"] = "cpu"
        return env
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return env
    held = _held_platform()
    if held == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        return env
    if held is not None:
        raise DeviceBusyError(
            f"this process already holds the {held} device, so no fleet "
            "worker can use it; run the sweep in-process (fleet=None) or "
            "start the fleet from a process that has not touched JAX")
    if workers > 1:
        raise DeviceBusyError(
            f"{workers} workers asked for a job that needs the "
            "accelerator, which one process at a time can own; use "
            "workers=1, or JAX_PLATFORMS=cpu to run them all on the CPU")
    return env


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except (OSError, TypeError):
        return False


def run_fleet(tasks: List[Task], job: FleetJob, config: FleetConfig,
              log=None) -> FleetMetrics:
    """Drive `tasks` through `job` under `config` until every task is
    done or poisoned; returns the run's `FleetMetrics` (also written to
    `<coord_dir>/metrics.json`)."""
    if config.coord_dir is None:
        raise ValueError("FleetConfig.coord_dir is unset — dispatchers "
                         "must derive one (see default_coord_dir)")

    def say(msg: str):
        logger.info(msg)
        if log:
            log(f"[fleet] {msg}")

    env = _worker_env(job, config.workers)
    coord = Coordinator(config.coord_dir)
    payloads: Dict[str, dict] = dict(tasks)
    task_ids = [tid for tid, _ in tasks]
    metrics = FleetMetrics(
        total=len(tasks),
        chaos=config.chaos.spec if config.chaos else "")
    deadline = StepDeadline(k=config.deadline_k,
                            floor_s=config.lease_timeout_s)
    # tracing: configure() also exports REPRO_TRACE_DIR, and the run
    # span's ids go out via REPRO_TRACE_PARENT, so spawned workers both
    # trace into the same directory and parent their lifetime spans here
    tracer = (obs_configure(config.trace_dir, proc="fleet-supervisor")
              if config.trace_dir else get_tracer())
    run_span = tracer.span(
        "fleet.run", attrs={"tasks": len(tasks), "workers": config.workers,
                            "chaos": config.chaos.spec if config.chaos
                            else ""})
    trace_parent_set = False
    if tracer.enabled:
        os.environ[TRACE_PARENT_ENV] = \
            f"{run_span.trace_id}:{run_span.span_id}"
        trace_parent_set = True
    t0 = time.perf_counter()

    # ------------------------------------------------- startup recovery
    for tid in coord.leases.active():
        info = coord.leases.owner(tid) or {}
        if not _pid_alive(info.get("pid")):
            coord.leases.release(tid)
            metrics.lease_breaks += 1
            say(f"broke stale lease {tid[:12]} "
                f"(owner {info.get('owner', '?')} gone)")
    pending: Set[str] = set()
    for tid in task_ids:
        if coord.is_poisoned(tid):
            continue
        if coord.is_done(tid):
            missing = job.verify(payloads[tid])
            if not missing:
                metrics.already_done += 1
                continue
            coord.clear_done(tid)
            metrics.verify_requeues += 1
            say(f"done marker {tid[:12]} had unreadable results — requeued")
        coord.clear_error(tid)   # stale park from a dead launch
        pending.add(tid)
    if metrics.already_done:
        say(f"resuming: {metrics.already_done}/{len(tasks)} task(s) "
            "already complete")

    # --------------------------------------------------- worker pool
    # what every worker needs, pickled once (this process wrote it, the
    # workers read it); each worker gets the path and its own index
    args_path = os.path.join(coord.root, "worker_args.pkl")
    tmp = f"{args_path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        pickle.dump((coord.root, job, tasks, config.chaos,
                     config.heartbeat_s, config.poll_s), f)
    os.replace(tmp, args_path)
    procs: Dict[int, subprocess.Popen] = {}   # worker index -> process
    next_index = 0

    def spawn():
        nonlocal next_index
        idx = next_index
        p = subprocess.Popen(
            [sys.executable, "-m", "repro.fleet.worker", args_path,
             str(idx)], env={**os.environ, **env})
        procs[idx] = p
        next_index += 1
        metrics.workers_spawned += 1
        if metrics.workers_spawned > config.workers:
            metrics.worker_restarts += 1

    def reap(tid: str, owner: str, pid, why: str):
        """Break a lease and requeue its task through the retry path."""
        if pid in {p.pid for p in procs.values()} and _pid_alive(pid):
            os.kill(pid, signal.SIGKILL)
            metrics.kills += 1
        # park the task (err marker) before freeing its lease: a free
        # lease with no marker is claimable, and a worker that claims and
        # finishes it first would hide the retry from this loop
        coord.synthetic_error(tid, owner, why)
        coord.leases.release(tid)
        metrics.lease_breaks += 1
        say(f"reaped {tid[:12]} ({why})")

    attempts: Dict[str, int] = {}
    requeue_at: Dict[str, float] = {}
    flagged: Set[Tuple[str, int]] = set()   # straggler (task, attempt)

    def start_pool():
        """Launch a whole pool at once, then open the start line when
        every worker is ready (or has died, or START_TIMEOUT_S passed)."""
        coord.hold_start()
        try:
            for _ in range(min(config.workers, max(len(pending), 1))):
                spawn()
            t_end = time.monotonic() + START_TIMEOUT_S
            while time.monotonic() < t_end and any(
                    p.poll() is None and not coord.is_ready(f"w{idx}")
                    for idx, p in procs.items()):
                time.sleep(config.poll_s)
        finally:
            coord.release_start()

    try:
        while pending:
            if not procs and pending:
                start_pool()
            now = time.monotonic()

            # ---- completions / poisons / errors
            for tid in sorted(pending):
                if coord.is_done(tid):
                    rec = coord.done_record(tid) or {}
                    wall = rec.get("wall_s")
                    if wall is not None:
                        wall = float(wall)
                        deadline.observe(wall)
                        metrics.chunk_wall.observe(wall)
                    pending.discard(tid)
                    metrics.computed += 1
                    requeue_at.pop(tid, None)
                    continue
                if coord.is_poisoned(tid):
                    pending.discard(tid)
                    continue
                err = coord.error_record(tid)
                if err is not None and tid not in requeue_at:
                    n = attempts[tid] = attempts.get(tid, 0) + 1
                    if err.get("retryable") and n < config.max_attempts:
                        delay = config.backoff.delay(n, token=tid)
                        requeue_at[tid] = now + delay
                        metrics.retried += 1
                        say(f"retry {tid[:12]} attempt {n + 1} in "
                            f"{delay:.2f}s ({err.get('exc_type')}: "
                            f"{err.get('exc', '')[:80]})")
                    else:
                        why = ("deterministic failure"
                               if not err.get("retryable")
                               else f"exhausted {n} attempts")
                        coord.mark_poison(tid, {**err, "attempts": n,
                                                "why": why})
                        coord.clear_error(tid)
                        metrics.poisoned += 1
                        pending.discard(tid)
                        say(f"poisoned {tid[:12]} ({why}: "
                            f"{err.get('exc_type')})")
                elif tid in requeue_at and now >= requeue_at[tid]:
                    coord.clear_error(tid)      # open for claiming again
                    del requeue_at[tid]

            # ---- lease health: stale heartbeats + stragglers
            for tid in coord.leases.active():
                if tid not in pending:
                    coord.leases.release(tid)   # lease outlived its task
                    continue
                age = coord.leases.age(tid)
                if age is None:
                    continue
                info = coord.leases.owner(tid) or {}
                owner = info.get("owner", "?")
                if age > config.lease_timeout_s:
                    reap(tid, owner, info.get("pid"),
                         f"no heartbeat for {age:.1f}s")
                    continue
                runtime = time.time() - info.get("t_claim", time.time())
                dl = deadline.deadline
                n = attempts.get(tid, 0)
                if runtime > dl and (tid, n) not in flagged:
                    flagged.add((tid, n))
                    metrics.stragglers += 1
                    say(f"straggler {tid[:12]}: {runtime:.1f}s "
                        f"(deadline {dl:.1f}s)")
                hard = config.chunk_timeout_s
                if (runtime > dl * config.straggler_kill_factor
                        or (hard is not None and runtime > hard)):
                    reap(tid, owner, info.get("pid"),
                         f"chunk overdue after {runtime:.1f}s")

            # ---- worker health: collect exits, requeue orphaned leases
            for idx, p in list(procs.items()):
                code = p.poll()
                if code is None:
                    continue
                del procs[idx]
                if code != 0:
                    say(f"worker w{idx} exited {code}")
                    for tid in coord.leases.active():
                        info = coord.leases.owner(tid) or {}
                        if (info.get("owner") == f"w{idx}"
                                and tid in pending):
                            coord.synthetic_error(
                                tid, f"w{idx}",
                                f"worker exited {code} mid-chunk")
                            coord.leases.release(tid)
                            metrics.lease_breaks += 1

            # ---- keep the pool full while work remains
            while pending and len(procs) < min(config.workers,
                                               max(len(pending), 1)):
                spawn()

            if pending:
                time.sleep(config.poll_s)

            # ---- drained: verify completions, requeue what fails
            # (bounded: at most verify_rounds retractions per task)
            if not pending:
                bad = [tid for tid in task_ids
                       if coord.is_done(tid) and job.verify(payloads[tid])]
                if bad and metrics.verify_requeues < \
                        config.verify_rounds * len(tasks):
                    for tid in bad:
                        coord.clear_done(tid)
                        metrics.verify_requeues += 1
                        pending.add(tid)
                    say(f"verify pass retracted {len(bad)} done "
                        "marker(s) with unreadable results")
    finally:
        # workers exit 0 on their own once everything is terminal;
        # anything still running after a grace period gets killed
        grace = 2 * config.poll_s + config.heartbeat_s
        for p in procs.values():
            try:
                p.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=5)

    metrics.done = sum(coord.is_done(tid) for tid in task_ids)
    metrics.poisoned = sum(coord.is_poisoned(tid) for tid in task_ids)
    metrics.poison = [rec for rec in coord.poison_manifest()
                      if rec.get("task") in payloads]
    metrics.stragglers = max(metrics.stragglers, deadline.stragglers)
    metrics.wall_s = time.perf_counter() - t0
    coord.write_metrics(metrics.as_dict())
    coord.write_obs(metrics.obs_snapshot())
    run_span.end(done=metrics.done, poisoned=metrics.poisoned,
                 computed=metrics.computed)
    if trace_parent_set:
        os.environ.pop(TRACE_PARENT_ENV, None)
    say(f"fleet done: {metrics.done}/{metrics.total} complete "
        f"({metrics.already_done} resumed, {metrics.computed} computed), "
        f"{metrics.poisoned} poisoned, {metrics.retried} retried, "
        f"{metrics.kills} kill(s), {metrics.wall_s:.1f}s")
    return metrics
