"""repro.fleet: fault-tolerant orchestration + chaos harness suite.

The fleet's contract, asserted deterministically via seeded FaultPlans:

- a clean fleet run fills the result cache bitwise-identically to an
  in-process SweepRunner run of the same sweep;
- kill/stall/corrupt/transient-raise plans all converge: every chunk
  accounted for (done + poisoned == total) and the surviving cache is
  bitwise-identical to an undisturbed run's;
- a re-launched fleet resumes from completed work (0 recomputed chunks),
  including after a hard SIGKILL of the whole fleet process;
- deterministic failures are quarantined to the poison manifest with
  their traceback instead of blocking the sweep.

Comparisons exclude `wall_time` (nondeterministic by nature) and pin
chunk_size so padding decisions match; the flowsim backend used here is
chunking-independent anyway.
"""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.fleet import (FleetConfig, parse_plan, run_fleet, sweep_job_for,
                         sweep_tasks)
from repro.runtime.resilience import Backoff
from repro.scenarios import SweepRunner, get_suite
from repro.scenarios.cache import ResultCache, result_key
from repro.sim import get_backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fast_config(**kw):
    """Test-speed supervision knobs (~10x tighter than the defaults)."""
    base = dict(workers=2, heartbeat_s=0.05, lease_timeout_s=0.6,
                poll_s=0.02, max_attempts=3,
                backoff=Backoff(base_s=0.05, factor=2.0, cap_s=0.3))
    base.update(kw)
    return FleetConfig(**base)


def sweep_fixture(n=6, num_flows=8):
    """(backend, specs, requests, keys) for a small flowsim sweep."""
    backend = get_backend("flowsim")
    specs = list(get_suite("smoke16", num_flows=num_flows).limit(n))
    reqs = [s.to_request() for s in specs]
    keys = [result_key(r, backend) for r in reqs]
    return backend, specs, reqs, keys


def cache_payload_bytes(cache_dir, keys):
    """fcts/slowdowns bytes per key — the bitwise-identity comparison
    (wall_time is honest timing, so it differs run to run)."""
    store = ResultCache(cache_dir)
    out = {}
    for k in keys:
        res = store.get(k)
        assert res is not None, f"missing cache entry {k[:12]}"
        out[k] = (res.fcts.tobytes(), res.slowdowns.tobytes())
    return out


def fleet_once(tmp_path, tag, chaos=None, n=6, **cfg_kw):
    """One fleet run in a fresh cache+coord pair; returns (metrics, keys,
    cache_dir)."""
    backend, specs, reqs, keys = sweep_fixture(n=n)
    cache = str(tmp_path / f"cache_{tag}")
    job = sweep_job_for(backend, cache)
    tasks = sweep_tasks(specs, reqs, keys, 1)
    cfg = fast_config(coord_dir=str(tmp_path / f"coord_{tag}"),
                      chaos=chaos, **cfg_kw)
    return run_fleet(tasks, job, cfg), keys, cache


# ------------------------------------------------------------- fault plans
def test_parse_plan_dsl():
    plan = parse_plan("kill:worker=0,after=2;corrupt:task=5;"
                      "raise:task=3,exc=oserror,times=2;"
                      "stall:worker=1,after=1", seed=7)
    kinds = [f.kind for f in plan.faults]
    assert kinds == ["kill", "corrupt", "raise", "stall"]
    assert plan.faults[0].worker == 0 and plan.faults[0].after == 2
    assert plan.faults[2].times == 2 and plan.faults[2].exc == "oserror"
    assert plan.seed == 7 and plan.spec.startswith("kill:")
    assert not parse_plan("")
    with pytest.raises(ValueError):
        parse_plan("explode:worker=0")
    with pytest.raises(ValueError):
        parse_plan("kill:after=2")              # kill needs worker=
    with pytest.raises(ValueError):
        parse_plan("raise:task=0,exc=nonsense")


def test_chaos_fire_markers_are_one_shot(tmp_path):
    from repro.fleet import ChaosMonkey
    plan = parse_plan("raise:task=0,exc=oserror,times=2")
    monkey = ChaosMonkey(plan, 0, str(tmp_path / "chaos"), ["t0", "t1"])
    with pytest.raises(OSError):
        monkey.on_run("t0")
    with pytest.raises(OSError):
        monkey.on_run("t0")
    monkey.on_run("t0")     # both slots consumed -> inert
    monkey.on_run("t1")     # untargeted task -> always inert


# ----------------------------------------------------------- clean convoys
def test_clean_fleet_matches_inprocess_sweep(tmp_path):
    backend, specs, reqs, keys = sweep_fixture()
    direct = SweepRunner(backend, cache_dir=str(tmp_path / "direct"),
                         chunk_size=1)
    direct.run(get_suite("smoke16", num_flows=8).limit(6))
    metrics, fkeys, fleet_cache = fleet_once(tmp_path, "clean")
    assert metrics.total == 6 and metrics.done == 6
    assert metrics.accounted == metrics.total
    assert metrics.poisoned == 0 and metrics.computed == 6
    assert cache_payload_bytes(str(tmp_path / "direct"), keys) == \
        cache_payload_bytes(fleet_cache, fkeys)


def test_fleet_relaunch_resumes_without_recompute(tmp_path):
    backend, specs, reqs, keys = sweep_fixture()
    cache = str(tmp_path / "cache")
    job = sweep_job_for(backend, cache)
    tasks = sweep_tasks(specs, reqs, keys, 1)
    cfg = fast_config(coord_dir=str(tmp_path / "coord"))
    first = run_fleet(tasks, job, cfg)
    assert first.computed == 6 and first.already_done == 0
    second = run_fleet(tasks, job, cfg)
    assert second.already_done == 6 and second.computed == 0
    assert second.workers_spawned == 0      # no work -> no processes


def test_sweeprunner_fleet_mode_report(tmp_path):
    backend = get_backend("flowsim")
    runner = SweepRunner(backend, cache_dir=str(tmp_path / "cache"),
                         chunk_size=1, fleet=fast_config())
    report = runner.run(get_suite("smoke16", num_flows=8).limit(4))
    assert report.fleet is not None
    assert report.fleet["done"] == 4 and report.fleet["accounted"] == 4
    assert report.misses == 4 and all(e.result is not None
                                      for e in report.entries)
    # second run: pure cache hits, no fleet dispatch at all
    report2 = runner.run(get_suite("smoke16", num_flows=8).limit(4))
    assert report2.hits == 4 and report2.fleet is None
    for e1, e2 in zip(report.entries, report2.entries):
        np.testing.assert_array_equal(e1.result.fcts, e2.result.fcts)


def test_sweeprunner_fleet_requires_cache():
    with pytest.raises(ValueError, match="cache_dir"):
        SweepRunner(get_backend("flowsim"), fleet=fast_config())


# ------------------------------------------------------------ chaos convoys
def test_kill_and_corrupt_plan_converges_bitwise(tmp_path):
    """The tentpole acceptance plan at test scale: two worker kills plus
    a corrupted result blob still end with every chunk done and the
    cache bitwise-equal to an undisturbed run."""
    clean, keys, clean_cache = fleet_once(tmp_path, "clean")
    plan = parse_plan("kill:worker=0,after=2;kill:worker=1,after=1;"
                      "corrupt:task=3")
    chaos, ckeys, chaos_cache = fleet_once(tmp_path, "chaos", chaos=plan,
                                           workers=3)
    assert chaos.done == chaos.total == 6
    assert chaos.poisoned == 0
    assert chaos.worker_restarts >= 2       # both kills respawned
    assert chaos.retried >= 1               # corrupt blob healed via retry
    assert cache_payload_bytes(clean_cache, keys) == \
        cache_payload_bytes(chaos_cache, ckeys)
    # the corrupted blob was quarantined aside, not deleted
    corrupt_files = [f for _, _, fs in os.walk(chaos_cache) for f in fs
                     if f.endswith(".corrupt")]
    assert len(corrupt_files) == 1


def test_stalled_worker_is_reaped(tmp_path):
    """A worker whose heartbeat goes silent mid-chunk gets SIGKILLed and
    its chunk requeued — the fleet still finishes everything."""
    plan = parse_plan("stall:worker=0,after=1")
    metrics, keys, cache = fleet_once(tmp_path, "stall", chaos=plan)
    assert metrics.done == metrics.total == 6
    assert metrics.kills >= 1 and metrics.lease_breaks >= 1
    assert metrics.retried >= 1
    cache_payload_bytes(cache, keys)        # everything readable


def test_transient_errors_retry_then_succeed(tmp_path):
    plan = parse_plan("raise:task=2,exc=oserror,times=2")
    metrics, keys, cache = fleet_once(tmp_path, "transient", chaos=plan)
    assert metrics.done == metrics.total == 6
    assert metrics.retried == 2 and metrics.poisoned == 0
    cache_payload_bytes(cache, keys)


def test_deterministic_failure_is_poisoned(tmp_path):
    """A ValueError is deterministic: no retries, quarantined with its
    traceback, and the rest of the sweep completes around it."""
    plan = parse_plan("raise:task=2,exc=valueerror")
    metrics, keys, cache = fleet_once(tmp_path, "poison", chaos=plan)
    assert metrics.done == metrics.total - 1
    assert metrics.poisoned == 1
    assert metrics.accounted == metrics.total       # the CI gate
    assert metrics.retried == 0                     # poison never retries
    (rec,) = metrics.poison
    assert rec["exc_type"] == "ValueError"
    assert "chaos-injected" in rec["exc"]
    assert "ValueError" in rec["traceback"]
    assert rec["why"] == "deterministic failure"


def test_poisoned_chunk_surfaces_as_none_entry(tmp_path):
    backend = get_backend("flowsim")
    runner = SweepRunner(
        backend, cache_dir=str(tmp_path / "cache"), chunk_size=1,
        fleet=fast_config(chaos=parse_plan("raise:task=1,exc=valueerror")))
    report = runner.run(get_suite("smoke16", num_flows=8).limit(4))
    assert report.fleet["poisoned"] == 1
    holes = [e for e in report.entries if e.result is None]
    assert len(holes) == 1
    rows = report.rows()                    # poisoned row renders as NaN
    assert sum(np.isnan(r["wall_s"]) for r in rows) == 1
    report.table()                          # and the table still formats


def test_exhausted_retries_poison(tmp_path):
    """A transient error that never stops (times >= max_attempts) ends
    in the poison manifest too — nothing retries forever."""
    plan = parse_plan("raise:task=0,exc=oserror,times=99")
    metrics, keys, cache = fleet_once(tmp_path, "exhaust", chaos=plan)
    assert metrics.poisoned == 1
    assert metrics.accounted == metrics.total
    (rec,) = metrics.poison
    assert rec["attempts"] == 3 and "exhausted" in rec["why"]


# ------------------------------------------------------- device ownership
@pytest.mark.parametrize("backend, env, held, workers, want", [
    # numpy backends never touch a device: any number of CPU workers
    ("flowsim", None, "tpu", 4, "cpu"),
    ("packet", "tpu", None, 4, "cpu"),
    # jax backends follow the run onto the CPU when it is pinned there
    # or when this process's JAX found nothing but the CPU ...
    ("flowsim_fast", "cpu", "tpu", 4, "cpu"),
    ("m4", None, "cpu", 4, "cpu"),
    # ... and otherwise get the accelerator in one worker, inherited
    ("m4", None, None, 1, None),
    ("flowsim_fast", "tpu", None, 1, "tpu"),
    # never beside a parent that holds the chip, never in two workers
    ("m4", None, "tpu", 1, "held"),
    ("flowsim_fast", None, None, 2, "workers"),
])
def test_worker_env_device_ownership(monkeypatch, backend, env, held,
                                     workers, want):
    from repro.fleet import DeviceBusyError, SweepJob
    from repro.fleet import supervisor
    if env is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", env)
    monkeypatch.setattr(supervisor, "_held_platform", lambda: held)
    job = SweepJob(backend_name=backend, cache_dir="unused")
    if want in ("held", "workers"):
        with pytest.raises(DeviceBusyError,
                           match="already holds" if want == "held"
                           else "workers asked"):
            supervisor._worker_env(job, workers)
        return
    out = {**os.environ, **supervisor._worker_env(job, workers)}
    assert out.get("JAX_PLATFORMS") == want
    assert out["PYTHONPATH"].split(os.pathsep)[0] == \
        os.path.join(REPO, "src")


def test_dataset_job_workers_run_on_cpu(monkeypatch):
    from repro.core.model import M4Config
    from repro.fleet import DatasetJob
    from repro.fleet import supervisor
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(supervisor, "_held_platform", lambda: "tpu")
    job = DatasetJob(root="unused", m4cfg=M4Config())
    assert not job.needs_device
    assert supervisor._worker_env(job, 8)["JAX_PLATFORMS"] == "cpu"


def test_busy_device_fails_before_any_worker_starts(monkeypatch, tmp_path):
    """A device job beside a parent that holds the chip raises at once:
    no worker is started, no task is poisoned."""
    from repro.fleet import DeviceBusyError
    from repro.fleet import supervisor
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(supervisor, "_held_platform", lambda: "tpu")
    backend = get_backend("flowsim_fast")
    specs = list(get_suite("smoke16", num_flows=8).limit(2))
    reqs = [s.to_request() for s in specs]
    keys = [result_key(r, backend) for r in reqs]
    job = sweep_job_for(backend, str(tmp_path / "cache"))
    cfg = fast_config(coord_dir=str(tmp_path / "coord"), workers=1)
    with pytest.raises(DeviceBusyError):
        run_fleet(sweep_tasks(specs, reqs, keys, 1), job, cfg)
    assert not os.path.exists(tmp_path / "coord")


# --------------------------------------------------------- CLI + acceptance
def cli_cmd(cache_dir, extra, num_flows=8, workers=3):
    return [sys.executable, "-m", "repro.fleet", "--suite", "smoke16",
            "--num-flows", str(num_flows), "--backend", "flowsim",
            "--workers", str(workers), "--chunk", "1",
            "--cache-dir", cache_dir, "--lease-timeout", "1.0",
            "--heartbeat", "0.1"] + extra


def cli_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("REPRO_FLEET_CHAOS", None)
    return env


def test_cli_smoke16_chaos_acceptance(tmp_path):
    """ISSUE 8 acceptance: a smoke16 fleet run under a plan that kills
    two workers and corrupts a blob completes 16/16 with a cache
    bitwise-identical to an undisturbed run."""
    clean_cache = str(tmp_path / "clean")
    chaos_cache = str(tmp_path / "chaos")
    out = subprocess.run(cli_cmd(clean_cache, []), env=cli_env(),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    metrics_path = str(tmp_path / "metrics.json")
    out = subprocess.run(
        cli_cmd(chaos_cache,
                ["--chaos", "kill:worker=0,after=1;kill:worker=1,after=2;"
                 "corrupt:task=5",
                 "--expect-clean", "--metrics-out", metrics_path]),
        env=cli_env(), capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    m = json.load(open(metrics_path))
    assert m["done"] == m["total"] == 16 and m["poisoned"] == 0
    assert m["accounted"] == 16
    assert m["worker_restarts"] >= 2 and m["retried"] >= 1
    backend, specs, reqs, keys = sweep_fixture(n=16)
    assert cache_payload_bytes(clean_cache, keys) == \
        cache_payload_bytes(chaos_cache, keys)


def test_cli_hard_kill_resumes_without_recompute(tmp_path):
    """SIGKILL the whole fleet mid-run; the relaunch must recompute only
    the chunks that never reached the cache."""
    cache = str(tmp_path / "cache")
    # one worker + heavier scenarios (~0.15s each) so the SIGKILL lands
    # reliably mid-run rather than after everything finished
    proc = subprocess.Popen(cli_cmd(cache, [], num_flows=400, workers=1),
                            env=cli_env(), stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL,
                            start_new_session=True)
    deadline = time.time() + 120
    try:
        # wait for some (not all) results to land, then hard-kill
        while time.time() < deadline:
            blobs = [f for _, _, fs in os.walk(cache)
                     for f in fs if f.endswith(".msgpack.z")]
            if len(blobs) >= 3:
                break
            time.sleep(0.02)
        else:
            pytest.fail("fleet never produced 3 results")
    finally:
        # SIGKILL the whole session: supervisor AND its spawned workers
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait(timeout=30)
    done_before = len([f for _, _, fs in os.walk(cache)
                       for f in fs if f.endswith(".msgpack.z")])
    assert 0 < done_before < 16, f"kill raced: {done_before} blobs"
    metrics_path = str(tmp_path / "metrics.json")
    out = subprocess.run(
        cli_cmd(cache, ["--metrics-out", metrics_path], num_flows=400),
        env=cli_env(), capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    m = json.load(open(metrics_path))
    # completed chunks were served from the cache, not recomputed
    assert m["total"] <= 16 - done_before
    assert m["computed"] == m["total"] and m["accounted"] == m["total"]
    assert f"{16 - m['total']} cached" in out.stdout
