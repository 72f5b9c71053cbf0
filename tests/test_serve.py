"""repro.serve: the concurrency/load and fault-injection suite.

The service's contract, asserted:

- N threads submitting shape-diverse requests concurrently all resolve,
  bitwise-equal to the direct `run_many` path;
- duplicate in-flight requests coalesce to one simulation;
- repeat submission after warm-up is 100% cache hits with zero new
  compiles (TRACE_COUNTS + `no_retrace` asserted);
- deadline flushes are driven by an injectable `ManualClock` — no
  wall-clock sleeps anywhere in this file;
- a backend failing or producing NaN mid-batch fails only the affected
  futures (healthy flush-mates resolve);
- a full queue raises clean backpressure, never deadlocks;
- shutdown drains in-flight work then rejects new submissions;
- random submit/cancel/shutdown interleavings never wedge or drop a
  future (property test via tests/_hypothesis_compat).

Pure-concurrency tests run against a jax-free `StubBackend` so they
exercise the dispatcher, not XLA; compile-count and bitwise tests use
the real flowsim_fast/m4 backends.
"""
import json
import os
import threading
from concurrent.futures import CancelledError, Future

import numpy as np
import pytest

from repro.runtime.guards import NonFiniteError, no_retrace
from repro.scenarios import ScenarioSpec
from repro.sim import Backend, SimRequest, SimResult, get_backend
from repro.serve import (ManualClock, RequestTimeout, ServeConfig,
                         ServiceClosed, ServiceOverloaded, SimService)

from _hypothesis_compat import given, settings, st

WAIT = 120          # future.result backstop (never reached when healthy)


def spec_request(seed, num_flows=10, topo="ft-4x2x2"):
    """A fixed-topology request: bucket identity == (num_flows, links)."""
    return ScenarioSpec(topo=topo, num_flows=num_flows, seed=seed,
                        max_load=0.4).to_request()


class StubBackend(Backend):
    """Deterministic jax-free backend for pure-concurrency tests.

    `fail_batch_seeds`: run_many raises when the batch contains one of
    these seeds (the whole flush fails, like a poisoned XLA batch), and
    `run` raises only for the poisoned request itself. `nan_seeds`: the
    result for that request comes back all-NaN.
    """

    name = "stub"

    def __init__(self, fail_batch_seeds=(), nan_seeds=()):
        self.fail_batch_seeds = set(fail_batch_seeds)
        self.nan_seeds = set(nan_seeds)
        self.run_many_calls = []         # batch sizes, in dispatch order
        self.run_calls = 0
        self.lock = threading.Lock()

    def run(self, request):
        with self.lock:
            self.run_calls += 1
        if request.seed in self.fail_batch_seeds:
            raise RuntimeError(f"poisoned request seed={request.seed}")
        n = request.num_flows
        fill = np.nan if request.seed in self.nan_seeds else float(n)
        return SimResult(
            fcts=np.full(n, fill + request.seed, dtype=np.float64),
            slowdowns=np.full(n, fill, dtype=np.float64),
            wall_time=0.0, backend=self.name)

    def run_many(self, requests):
        with self.lock:
            self.run_many_calls.append(len(requests))
        if any(r.seed in self.fail_batch_seeds for r in requests):
            raise RuntimeError("batch poisoned")
        return [self.run(r) for r in requests]

    def fingerprint(self):
        return "stub-v1"


def stub_request(seed, num_flows=4):
    """Tiny fixed-shape request; the seed rides on `SimRequest.seed` so
    StubBackend's fault injection can key off it."""
    return ScenarioSpec(topo="ft-4x2x2", num_flows=num_flows, seed=seed,
                        max_load=0.4).to_request(seed=seed)


@pytest.fixture()
def manual_service():
    """StubBackend service on a ManualClock; yields (service, backend,
    clock); closes in teardown so a failing test can't leak threads."""
    clock = ManualClock()
    backend = StubBackend()
    service = SimService(backend, clock=clock,
                         config=ServeConfig(batch_size=4,
                                            flush_interval_s=0.05,
                                            max_queue=32))
    yield service, backend, clock
    service.close(drain=False)


def wait_idle(service, name="stub", timeout=10.0):
    """Block until the lane's dispatcher has evaluated the *current*
    queue state and gone back to waiting — the deterministic sync point
    that replaces wall-clock sleeps. Forces one fresh dispatcher pass
    (a spurious wakeup the loop tolerates) so a stale `idle` from before
    the caller's submit can't satisfy the wait."""
    lane = service._lanes[name]
    with lane.cond:
        w0 = lane.waits
        lane.cond.notify_all()
        assert lane.cond.wait_for(
            lambda: lane.idle and lane.waits > w0,
            timeout), "dispatcher never settled"


def _fast_compiles():
    from repro.core.flowsim_fast import TRACE_COUNTS
    return sum(TRACE_COUNTS.values())


def _m4_compiles():
    from repro.core.simulate import TRACE_COUNTS
    return sum(TRACE_COUNTS.values())


# --------------------------------------------------------------- the basics
def test_single_request_roundtrip():
    backend = get_backend("flowsim")
    with SimService(backend) as service:
        req = spec_request(0, num_flows=8)
        res = service.submit(req).result(timeout=WAIT)
        np.testing.assert_array_equal(res.fcts, backend.run(req).fcts)
        assert res.backend == "flowsim"
        m = service.metrics()
        assert m["submitted"] == m["completed"] == 1


def test_submit_validates_backend_name():
    with SimService(StubBackend()) as service:
        with pytest.raises(KeyError, match="unknown backend"):
            service.submit(stub_request(0), backend="m4")


def test_multi_backend_lanes_route_independently():
    a, b = StubBackend(), StubBackend()
    with SimService({"a": a, "b": b},
                    config=ServeConfig(batch_size=1)) as service:
        with pytest.raises(ValueError, match="pass backend="):
            service.submit(stub_request(0))
        fa = service.submit(stub_request(0), backend="a")
        fb = service.submit(stub_request(1), backend="b")
        fa.result(timeout=WAIT), fb.result(timeout=WAIT)
        assert a.run_many_calls and b.run_many_calls
        assert service.metrics(backend="a")["completed"] == 1
        assert service.metrics()["completed"] == 2     # aggregate sums


def test_serve_config_validation():
    with pytest.raises(ValueError, match="batch_size"):
        ServeConfig(batch_size=0)
    with pytest.raises(ValueError, match="max_queue"):
        ServeConfig(max_queue=0)
    with pytest.raises(ValueError, match="flush_interval_s"):
        ServeConfig(flush_interval_s=-1.0)
    with pytest.raises(ValueError, match="at least one backend"):
        SimService({})


# ------------------------------------------------- concurrent load (real jax)
def test_concurrent_shape_diverse_matches_run_many():
    """16 threads, 2 shape buckets: every future resolves bitwise-equal
    to the direct run_many path."""
    backend = get_backend("flowsim_fast")
    reqs = [spec_request(s, num_flows=10 + 4 * (s % 2)) for s in range(16)]
    direct = {id(r): res for r, res in zip(reqs, backend.run_many(reqs))}
    with SimService(backend, config=ServeConfig(batch_size=8,
                                                flush_interval_s=0.02)) \
            as service:
        futures = {}
        def submit(r):
            futures[id(r)] = service.submit(r)
        threads = [threading.Thread(target=submit, args=(r,)) for r in reqs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for r in reqs:
            res = futures[id(r)].result(timeout=WAIT)
            np.testing.assert_array_equal(res.fcts, direct[id(r)].fcts)
            np.testing.assert_array_equal(res.slowdowns,
                                          direct[id(r)].slowdowns)
        m = service.metrics()
        assert m["completed"] == 16 and m["failed"] == 0


def test_warm_resubmission_all_hits_zero_compiles(tmp_path):
    """After warm-up, resubmission is 100% cache hits and compiles
    nothing (no_retrace + TRACE_COUNTS asserted)."""
    backend = get_backend("flowsim_fast")
    reqs = [spec_request(s, num_flows=10) for s in range(8)]
    with SimService(backend, cache_dir=str(tmp_path),
                    config=ServeConfig(batch_size=4,
                                       flush_interval_s=0.02)) as service:
        for f in [service.submit(r) for r in reqs]:
            f.result(timeout=WAIT)
        c0 = _fast_compiles()
        with no_retrace(allowed=0, label="warm resubmission"):
            warm = [service.submit(r) for r in reqs]
            results = [f.result(timeout=WAIT) for f in warm]
        assert _fast_compiles() == c0
        m = service.metrics()
        assert m["cache_hits"] == 8                     # the whole 2nd pass
        assert all(len(r.fcts) == 10 for r in results)


def test_duplicate_inflight_requests_coalesce(manual_service):
    """Same request submitted twice before any flush: one simulation,
    both futures resolve with it."""
    service, backend, clock = manual_service
    req = stub_request(3)
    f1 = service.submit(req)
    f2 = service.submit(req)
    assert service.metrics()["coalesced"] == 1
    clock.advance(0.06)                                 # deadline flush
    r1, r2 = f1.result(timeout=WAIT), f2.result(timeout=WAIT)
    np.testing.assert_array_equal(r1.fcts, r2.fcts)
    assert backend.run_many_calls == [4]                # one padded flush
    assert backend.run_calls == 4                       # 1 live + 3 pads


def test_coalesced_requests_count_one_queue_slot(manual_service):
    service, backend, clock = manual_service
    req = stub_request(1)
    for _ in range(5):
        service.submit(req)
    assert service._lanes["stub"].queued == 1
    assert service.metrics()["coalesced"] == 4


# --------------------------------------------- deadline flush (manual clock)
def test_deadline_flush_fires_at_interval_not_before(manual_service):
    """A lone request flushes exactly when the 50ms deadline passes on
    the injected clock — asserted on both sides, no wall sleeps."""
    service, backend, clock = manual_service
    fut = service.submit(stub_request(0))
    wait_idle(service)
    assert not fut.done() and backend.run_many_calls == []
    clock.advance(0.04)                                 # 10ms early
    wait_idle(service)
    assert not fut.done() and backend.run_many_calls == []
    clock.advance(0.02)                                 # now past 50ms
    assert fut.result(timeout=WAIT).backend == "stub"
    assert backend.run_many_calls == [4]                # padded to capacity


def test_full_bucket_flushes_without_clock(manual_service):
    """batch_size requests of one shape flush immediately — the deadline
    never has to arrive."""
    service, backend, clock = manual_service
    futs = [service.submit(stub_request(s)) for s in range(4)]
    for f in futs:
        assert f.result(timeout=WAIT) is not None
    assert backend.run_many_calls == [4]


def test_shape_buckets_flush_independently(manual_service):
    """Requests of two shapes never share a batch: the full bucket
    flushes now, the lone other-shape request waits for its deadline."""
    service, backend, clock = manual_service
    small = [service.submit(stub_request(s, num_flows=4)) for s in range(4)]
    big = service.submit(stub_request(9, num_flows=6))
    for f in small:
        f.result(timeout=WAIT)
    wait_idle(service)
    assert not big.done()
    clock.advance(0.06)
    assert len(big.result(timeout=WAIT).fcts) == 6
    assert backend.run_many_calls == [4, 4]


def test_oversize_burst_drains_in_capacity_chunks(manual_service):
    """9 same-shape requests, capacity 4: two full flushes immediately,
    the remainder on its deadline."""
    service, backend, clock = manual_service
    futs = [service.submit(stub_request(s)) for s in range(9)]
    for f in futs[:8]:
        f.result(timeout=WAIT)
    wait_idle(service)
    assert not futs[8].done()
    clock.advance(0.06)
    futs[8].result(timeout=WAIT)
    assert sorted(backend.run_many_calls) == [4, 4, 4]  # tail padded


def test_batch_padding_can_be_disabled():
    backend = StubBackend()
    clock = ManualClock()
    service = SimService(backend, clock=clock,
                         config=ServeConfig(batch_size=4,
                                            flush_interval_s=0.05,
                                            pad_batches=False,
                                            guard_retrace=False))
    try:
        fut = service.submit(stub_request(0))
        clock.advance(0.06)
        fut.result(timeout=WAIT)
        assert backend.run_many_calls == [1]            # no pad copies
    finally:
        service.close(drain=False)


# ------------------------------------------------------- deadlines / cancel
def test_request_timeout_expires_in_queue(manual_service):
    """A queued request past its deadline fails with RequestTimeout;
    a patient flush-mate still resolves."""
    service, backend, clock = manual_service
    hasty = service.submit(stub_request(0), timeout=0.01)
    patient = service.submit(stub_request(1))
    clock.advance(0.02)                    # past hasty's deadline only
    with pytest.raises(RequestTimeout):
        hasty.result(timeout=WAIT)
    wait_idle(service)
    assert not patient.done()
    clock.advance(0.04)                    # past the flush interval
    assert patient.result(timeout=WAIT) is not None
    m = service.metrics()
    assert m["timed_out"] == 1 and m["completed"] == 1
    assert backend.run_many_calls == [4]   # hasty was never simulated


def test_cancelled_future_is_skipped(manual_service):
    service, backend, clock = manual_service
    doomed = service.submit(stub_request(0))
    kept = service.submit(stub_request(1))
    assert doomed.cancel()
    clock.advance(0.06)
    kept.result(timeout=WAIT)
    with pytest.raises(CancelledError):
        doomed.result(timeout=WAIT)
    assert backend.run_many_calls == [4]   # kept's flush (padded)
    assert service.metrics()["cancelled"] >= 1


def test_cancel_one_coalesced_future_keeps_the_other(manual_service):
    service, backend, clock = manual_service
    req = stub_request(5)
    f1, f2 = service.submit(req), service.submit(req)
    assert f1.cancel()
    clock.advance(0.06)
    assert f2.result(timeout=WAIT) is not None
    assert f1.cancelled()


# ---------------------------------------------------- backpressure / limits
def test_full_queue_rejects_with_backpressure():
    """max_queue pendings: the next submit raises ServiceOverloaded with
    a retry hint — and the queue drains normally afterwards."""
    clock = ManualClock()
    backend = StubBackend()
    service = SimService(backend, clock=clock,
                         config=ServeConfig(batch_size=99, max_queue=2,
                                            flush_interval_s=0.05))
    try:
        f1 = service.submit(stub_request(0))
        f2 = service.submit(stub_request(1))
        with pytest.raises(ServiceOverloaded) as exc_info:
            service.submit(stub_request(2))
        # base flush interval plus deterministic per-request jitter
        assert 0.05 <= exc_info.value.retry_after_s < 0.10
        with pytest.raises(ServiceOverloaded) as exc_info2:
            service.submit(stub_request(2))
        assert exc_info2.value.retry_after_s == exc_info.value.retry_after_s
        with pytest.raises(ServiceOverloaded) as exc_other:
            service.submit(stub_request(3))
        assert exc_other.value.retry_after_s != exc_info.value.retry_after_s
        assert service.metrics()["rejected"] == 3
        clock.advance(0.06)                       # deadline flush drains
        f1.result(timeout=WAIT), f2.result(timeout=WAIT)
        # space opened up: admission works again
        f3 = service.submit(stub_request(2))
        clock.advance(0.06)
        assert f3.result(timeout=WAIT) is not None
    finally:
        service.close(drain=False)


def test_coalesced_duplicates_bypass_admission():
    """Duplicates of an in-flight request don't consume queue slots, so
    they are admitted even at the bound."""
    clock = ManualClock()
    backend = StubBackend()
    service = SimService(backend, clock=clock,
                         config=ServeConfig(batch_size=99, max_queue=1,
                                            flush_interval_s=0.05))
    try:
        req = stub_request(0)
        f1 = service.submit(req)
        f2 = service.submit(req)               # duplicate: no new slot
        with pytest.raises(ServiceOverloaded):
            service.submit(stub_request(1))
        clock.advance(0.06)
        assert f1.result(timeout=WAIT) and f2.result(timeout=WAIT)
    finally:
        service.close(drain=False)


# ----------------------------------------------------------- fault injection
def test_batch_failure_isolates_poisoned_request():
    """run_many raising for a flush fails only the poisoned request
    (with the original error); healthy flush-mates resolve via the
    per-request fallback."""
    clock = ManualClock()
    backend = StubBackend(fail_batch_seeds={2})
    service = SimService(backend, clock=clock,
                         config=ServeConfig(batch_size=4,
                                            flush_interval_s=0.05))
    try:
        futs = [service.submit(stub_request(s)) for s in range(4)]
        for s, f in enumerate(futs):
            if s == 2:
                with pytest.raises(RuntimeError, match="seed=2"):
                    f.result(timeout=WAIT)
            else:
                assert f.result(timeout=WAIT).fcts[0] == 4.0 + s
        m = service.metrics()
        assert m["failed"] == 1 and m["completed"] == 3
        assert m["isolated_retries"] == 4
    finally:
        service.close(drain=False)


def test_batch_failure_is_logged_with_cause(caplog):
    """A failed batch is logged with its exception and traceback, not
    only counted: the lone re-runs that follow can all succeed."""
    clock = ManualClock()
    service = SimService(StubBackend(fail_batch_seeds={1}), clock=clock,
                         config=ServeConfig(batch_size=2,
                                            flush_interval_s=0.05))
    try:
        with caplog.at_level("ERROR", logger="repro.serve"):
            futs = [service.submit(stub_request(s)) for s in (0, 1)]
            for f in futs:
                f.exception(timeout=WAIT)
        (rec,) = [r for r in caplog.records if r.name == "repro.serve"]
        assert "re-running each request alone" in rec.getMessage()
        assert "RuntimeError: batch poisoned" in rec.getMessage()
        assert rec.exc_info is not None
        assert service.metrics()["isolated_retries"] == 2
    finally:
        service.close(drain=False)


def test_real_backend_batch_failure_isolates(monkeypatch):
    """Same contract on a real jax backend: monkeypatched run_many
    raises mid-batch, healthy requests still resolve via run()."""
    backend = get_backend("flowsim_fast")
    reqs = [spec_request(s, num_flows=8) for s in range(3)]
    expected = [backend.run(r).fcts for r in reqs]
    boom = RuntimeError("XLA batch exploded")
    monkeypatch.setattr(type(backend), "run_many",
                        lambda self, requests: (_ for _ in ()).throw(boom))
    with SimService(backend, config=ServeConfig(batch_size=4,
                                                flush_interval_s=0.01,
                                                guard_retrace=False)) \
            as service:
        futs = [service.submit(r) for r in reqs]
        for f, exp in zip(futs, expected):
            np.testing.assert_array_equal(f.result(timeout=WAIT).fcts, exp)
        assert service.metrics()["isolated_retries"] == 3


def test_nan_result_fails_only_affected_future(monkeypatch):
    """REPRO_CHECK_FINITE=1: an all-NaN result fails its own future with
    NonFiniteError; healthy results in the same flush are unaffected and
    the poisoned result is never cached."""
    monkeypatch.setenv("REPRO_CHECK_FINITE", "1")
    clock = ManualClock()
    backend = StubBackend(nan_seeds={1})
    service = SimService(backend, clock=clock,
                         config=ServeConfig(batch_size=4,
                                            flush_interval_s=0.05))
    try:
        futs = [service.submit(stub_request(s)) for s in range(4)]
        for s, f in enumerate(futs):
            if s == 1:
                with pytest.raises(NonFiniteError, match="all-NaN"):
                    f.result(timeout=WAIT)
            else:
                assert np.isfinite(f.result(timeout=WAIT).fcts).all()
        assert service.metrics()["failed"] == 1
    finally:
        service.close(drain=False)


def test_nan_checks_off_by_default(monkeypatch):
    """Without REPRO_CHECK_FINITE, NaN results flow through — NaN is the
    documented 'flow never finished' value."""
    monkeypatch.delenv("REPRO_CHECK_FINITE", raising=False)
    clock = ManualClock()
    backend = StubBackend(nan_seeds={0})
    service = SimService(backend, clock=clock,
                         config=ServeConfig(batch_size=1))
    try:
        res = service.submit(stub_request(0)).result(timeout=WAIT)
        assert np.isnan(res.fcts).all()
    finally:
        service.close(drain=False)


# ------------------------------------------------------------------ shutdown
def test_close_drains_inflight_then_rejects(manual_service):
    """Queued work survives shutdown (drain flushes ignore deadlines);
    post-close submissions raise ServiceClosed."""
    service, backend, clock = manual_service
    futs = [service.submit(stub_request(s)) for s in range(3)]
    service.close(drain=True)               # no clock advance needed
    for f in futs:
        assert f.result(timeout=WAIT) is not None
    with pytest.raises(ServiceClosed):
        service.submit(stub_request(9))
    assert not any(l.thread.is_alive() for l in service._lanes.values())


def test_close_without_drain_fails_pending(manual_service):
    service, backend, clock = manual_service
    futs = [service.submit(stub_request(s)) for s in range(3)]
    service.close(drain=False)
    for f in futs:
        with pytest.raises(ServiceClosed):
            f.result(timeout=WAIT)
    assert backend.run_many_calls == []     # nothing was simulated
    assert service.metrics()["failed"] == 3


def test_close_is_idempotent(manual_service):
    service, _, _ = manual_service
    service.close()
    service.close(drain=False)              # second close: no-op, no raise


def test_shutdown_during_inflight_batch_drains():
    """close() lands while the backend is mid-batch: the batch finishes,
    queued work flushes, nothing hangs."""
    release = threading.Event()
    entered = threading.Event()

    class SlowBackend(StubBackend):
        def run_many(self, requests):
            entered.set()
            assert release.wait(WAIT), "close() should not block the batch"
            return super().run_many(requests)

    backend = SlowBackend()
    service = SimService(backend, config=ServeConfig(batch_size=2,
                                                     flush_interval_s=0.01))
    f1 = service.submit(stub_request(0))
    f2 = service.submit(stub_request(1))    # full bucket -> flush starts
    assert entered.wait(WAIT)
    f3 = service.submit(stub_request(7))    # queued behind the batch
    closer = threading.Thread(target=service.close)
    closer.start()
    release.set()
    closer.join(WAIT)
    assert not closer.is_alive()
    for f in (f1, f2, f3):
        assert f.result(timeout=WAIT) is not None
    with pytest.raises(ServiceClosed):
        service.submit(stub_request(9))


def test_context_manager_closes():
    with SimService(StubBackend(),
                    config=ServeConfig(batch_size=1)) as service:
        res = service.submit(stub_request(0)).result(timeout=WAIT)
        assert res is not None
    assert service.closed
    with pytest.raises(ServiceClosed):
        service.submit(stub_request(1))


# ------------------------------------------------------------- property test
@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_random_interleavings_never_wedge_or_drop(seed):
    """Random submit/duplicate/cancel/advance/shutdown interleavings:
    every future ends resolved, failed, or cancelled — none pending,
    no dispatcher thread left alive."""
    import random
    rng = random.Random(seed)
    clock = ManualClock()
    backend = StubBackend(fail_batch_seeds={13}, nan_seeds={7})
    service = SimService(backend, clock=clock,
                         config=ServeConfig(batch_size=rng.choice([1, 2, 4]),
                                            flush_interval_s=0.05,
                                            max_queue=rng.choice([2, 8])))
    futures = []
    requests = [stub_request(s, num_flows=rng.choice([3, 5]))
                for s in (0, 3, 7, 13)]    # 13 poisons batches, 7 is NaN
    try:
        for _ in range(rng.randint(3, 12)):
            op = rng.random()
            if op < 0.55:
                try:
                    futures.append(service.submit(rng.choice(requests)))
                except ServiceOverloaded:
                    pass                   # legal under backpressure
            elif op < 0.7 and futures:
                rng.choice(futures).cancel()
            elif op < 0.9:
                clock.advance(rng.choice([0.01, 0.06]))
            else:
                clock.advance(0.06)
    finally:
        service.close(drain=rng.random() < 0.7)
    for f in futures:
        assert f.done(), "future dropped by the service"
        if not f.cancelled():
            f.exception(timeout=0)         # resolved or failed — not stuck
    assert not any(l.thread.is_alive() for l in service._lanes.values())


# ------------------------------------------ acceptance: 64-request workload
def test_acceptance_64_requests_2_buckets_half_warm(tmp_path):
    """The ISSUE acceptance criterion: a 64-request shape-diverse
    concurrent workload (2 shape buckets, 50% cache-warm) completes with
    <= 2 run_many compiles, resubmission is a 100% hit rate with zero
    compiles, and every result is bitwise-identical to direct run_many."""
    backend = get_backend("flowsim_fast")
    reqs = [spec_request(s, num_flows=10 + 4 * (s % 2)) for s in range(64)]
    direct = backend.run_many(reqs)                  # reference, uncounted

    c0 = _fast_compiles()
    with SimService(backend, cache_dir=str(tmp_path),
                    config=ServeConfig(batch_size=8,
                                       flush_interval_s=0.02)) as service:
        # warm half the working set through the service itself
        for f in [service.submit(r) for r in reqs[:32]]:
            f.result(timeout=WAIT)
        # full 64-request burst from 8 concurrent client threads
        futures = [None] * len(reqs)
        def client(lo):
            for i in range(lo, len(reqs), 8):
                futures[i] = service.submit(reqs[i])
        threads = [threading.Thread(target=client, args=(lo,))
                   for lo in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        results = [f.result(timeout=WAIT) for f in futures]
        assert _fast_compiles() - c0 <= 2            # one per shape bucket
        m = service.metrics()
        assert m["cache_hits"] >= 32                 # the warm half
        assert m["failed"] == m["rejected"] == 0
        for res, ref in zip(results, direct):
            np.testing.assert_array_equal(res.fcts, ref.fcts)
            np.testing.assert_array_equal(res.slowdowns, ref.slowdowns)

        # resubmission: pure cache, zero compiles
        with no_retrace(allowed=0, label="acceptance resubmission"):
            again = [service.submit(r).result(timeout=WAIT) for r in reqs]
        hits_before = m["cache_hits"]
        assert service.metrics()["cache_hits"] - hits_before == 64
        for res, ref in zip(again, direct):
            np.testing.assert_array_equal(res.fcts, ref.fcts)
        assert m2_occupancy_sane(service.metrics())


def m2_occupancy_sane(m):
    assert 0.0 < m["batch_occupancy"] <= 1.0
    assert m["queue_delay_p99_ms"] >= m["queue_delay_p50_ms"] >= 0.0
    assert np.isfinite(m["queue_delay_p99_ms"])
    return True


def test_m4_service_matches_direct_run_many(tmp_path):
    """The learned backend through the service: batched flushes bitwise-
    match direct run_many, warm pass is all hits, <= 1 compile."""
    import jax
    from repro.core.model import M4Config, init_m4
    cfg = M4Config(hidden=16, gnn_dim=12, mlp_hidden=8, gnn_layers=2,
                   snap_flows=8, snap_links=24)
    backend = get_backend("m4", params=init_m4(jax.random.PRNGKey(0), cfg),
                          cfg=cfg)
    reqs = [spec_request(s, num_flows=10) for s in range(8)]
    direct = backend.run_many(reqs)                  # B=8 reference
    c0 = _m4_compiles()
    with SimService(backend, cache_dir=str(tmp_path),
                    config=ServeConfig(batch_size=8,
                                       flush_interval_s=0.02)) as service:
        results = [f.result(timeout=WAIT)
                   for f in [service.submit(r) for r in reqs]]
        assert _m4_compiles() - c0 <= 1
        for res, ref in zip(results, direct):
            np.testing.assert_array_equal(res.fcts, ref.fcts)
        warm = [service.submit(r).result(timeout=WAIT) for r in reqs]
        assert service.metrics()["cache_hits"] == 8
        for res, ref in zip(warm, direct):
            np.testing.assert_array_equal(res.fcts, ref.fcts)


# ------------------------------------------------------------ HTTP front-end
@pytest.fixture()
def http_service():
    """flowsim service behind a real ephemeral-port HTTP server."""
    from repro.serve import ServeClient, start_http_server
    service = SimService(get_backend("flowsim"),
                         config=ServeConfig(batch_size=4,
                                            flush_interval_s=0.01))
    server = start_http_server(service, port=0)
    client = ServeClient(f"http://127.0.0.1:{server.server_address[1]}")
    yield service, server, client
    server.shutdown()
    server.server_close()
    service.close(drain=False)


SPEC = {"topo": "ft-4x2x2", "num_flows": 8, "max_load": 0.4, "seed": 0}


def http_status(client, method, path, body=None):
    """Raw status + JSON body (urllib raises on >= 400; unwrap it)."""
    from urllib.error import HTTPError
    try:
        if method == "GET":
            reply = client._call(path)
        else:
            reply = client._call(path, body or {})
        return 200, reply
    except HTTPError as exc:
        return exc.code, json.loads(exc.read() or b"{}"), dict(exc.headers)


def test_http_simulate_roundtrip(http_service):
    service, server, client = http_service
    reply = client.simulate(SPEC, backend="flowsim")
    expected = get_backend("flowsim").run(
        ScenarioSpec(**SPEC).to_request())
    np.testing.assert_array_equal(np.asarray(reply["fcts"]), expected.fcts)
    np.testing.assert_array_equal(np.asarray(reply["slowdowns"]),
                                  expected.slowdowns)
    assert reply["backend"] == "flowsim"


def test_http_metrics_and_healthz(http_service):
    service, server, client = http_service
    client.simulate(SPEC)
    m = client.metrics()
    assert m["submitted"] >= 1 and m["completed"] >= 1
    assert "queue_delay_p99_ms" in m and "flowsim" in m["lanes"]
    h = client.health()
    assert h == {"ok": True, "status": "ok", "backends": ["flowsim"],
                 "dead_lanes": []}


def test_health_reports_dead_dispatcher_lane():
    """A lane whose dispatcher thread died must flip health to degraded
    (not ok): that backend's queue will never drain again, so LB checks
    have to route traffic elsewhere."""
    service = SimService(StubBackend(), clock=ManualClock())
    try:
        assert service.health()["status"] == "ok"
        dead = threading.Thread(target=lambda: None)
        dead.start()
        dead.join()
        service._lanes["stub"].thread = dead
        h = service.health()
        assert h == {"ok": False, "status": "degraded",
                     "backends": ["stub"], "dead_lanes": ["stub"]}
    finally:
        service.close(drain=False)


def test_http_healthz_degraded_is_503(http_service):
    service, server, client = http_service
    dead = threading.Thread(target=lambda: None)
    dead.start()
    dead.join()
    live = service._lanes["flowsim"].thread
    try:
        service._lanes["flowsim"].thread = dead
        code, body, *_ = http_status(client, "GET", "/healthz")
        assert code == 503 and body["status"] == "degraded"
        assert body["dead_lanes"] == ["flowsim"]
        # ServeClient.health returns the 503 body instead of raising
        assert client.health()["ok"] is False
    finally:
        service._lanes["flowsim"].thread = live


def test_retry_after_jitter_deterministic_spread():
    """The retry hint is a pure function of the cache key, spread over
    [base, 2*base): same request -> same hint, a cohort of distinct
    requests -> distinct hints (no synchronized re-stampede)."""
    from repro.serve.service import retry_after_jitter
    hints = [retry_after_jitter(0.05, f"key-{i}") for i in range(32)]
    assert all(0.05 <= h < 0.10 for h in hints)
    assert len(set(hints)) == len(hints)
    assert hints == [retry_after_jitter(0.05, f"key-{i}")
                     for i in range(32)]


def test_http_404_unknown_route(http_service):
    _, _, client = http_service
    code, body, *_ = http_status(client, "GET", "/nope")
    assert code == 404 and "no route" in body["error"]
    code, body, *_ = http_status(client, "POST", "/nope", {"spec": SPEC})
    assert code == 404


def test_http_400_malformed_requests(http_service):
    _, _, client = http_service
    code, body, *_ = http_status(client, "POST", "/simulate", {})
    assert code == 400 and '"spec"' in body["error"]
    code, body, *_ = http_status(client, "POST", "/simulate",
                                 {"spec": {"no_such_field": 1}})
    assert code == 400 and "bad spec" in body["error"]
    code, body, *_ = http_status(
        client, "POST", "/simulate",
        {"spec": SPEC, "options": {"record_events": True}})
    assert code == 400 and "unsupported options" in body["error"]
    code, body, *_ = http_status(client, "POST", "/simulate",
                                 {"spec": SPEC, "backend": "m4"})
    assert code == 400 and "unknown backend" in body["error"]


def test_http_504_on_expired_deadline():
    """timeout=0 expires in the queue before any flush -> HTTP 504."""
    from repro.serve import ServeClient, start_http_server
    clock = ManualClock()
    service = SimService(StubBackend(), clock=clock,
                         config=ServeConfig(batch_size=8,
                                            flush_interval_s=0.05))
    server = start_http_server(service, port=0)
    client = ServeClient(f"http://127.0.0.1:{server.server_address[1]}")
    try:
        code, body, *_ = http_status(
            client, "POST", "/simulate",
            {"spec": dict(SPEC, num_flows=4), "backend": "stub",
             "timeout": 0.0})
        assert code == 504 and "deadline" in body["error"]
    finally:
        server.shutdown()
        server.server_close()
        service.close(drain=False)


def test_http_503_backpressure_with_retry_after():
    """A full lane maps to 503 + a Retry-After header on the wire."""
    from repro.serve import ServeClient, start_http_server
    clock = ManualClock()
    service = SimService(StubBackend(), clock=clock,
                         config=ServeConfig(batch_size=99, max_queue=1,
                                            flush_interval_s=0.05))
    server = start_http_server(service, port=0)
    client = ServeClient(f"http://127.0.0.1:{server.server_address[1]}")
    try:
        service.submit(stub_request(0))          # fill the only slot
        code, body, headers = http_status(
            client, "POST", "/simulate",
            {"spec": dict(SPEC, seed=99, num_flows=4), "backend": "stub"})
        assert code == 503
        assert 0.05 <= body["retry_after_s"] < 0.10
        assert float(headers["Retry-After"]) == \
            pytest.approx(body["retry_after_s"], abs=1e-3)
    finally:
        server.shutdown()
        server.server_close()
        service.close(drain=False)


def test_http_503_after_close(http_service):
    service, server, client = http_service
    service.close()
    code, body, *_ = http_status(client, "POST", "/simulate",
                                 {"spec": SPEC})
    assert code == 503 and "closed" in body["error"]
    h = client.health()
    assert h["ok"] is False and h["status"] == "closed"


def test_request_from_wire_net_tuples():
    """JSON lists for the `net` overrides land as the spec's tuples, and
    the materialized request round-trips the content hash."""
    from repro.serve import request_from_wire
    body = {"spec": dict(SPEC, net=[["dctcp_k", 25000]])}
    req = request_from_wire(body)
    assert req.num_flows == SPEC["num_flows"]
    spec = ScenarioSpec(**dict(SPEC, net=(("dctcp_k", 25000.0),)))
    assert req.content_hash() == spec.to_request().content_hash()


# --------------------------------------------------------------- CLI + stub
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cli_smoke_passes():
    """`python -m repro.serve --smoke` is the CI serve-smoke entrypoint:
    real HTTP, mixed hit/miss workload, metrics assertions, exit 0."""
    import subprocess, os, sys
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.serve", "--smoke",
         "--backend", "flowsim", "--flush-ms", "10"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "cache hits >= 1" in proc.stdout


def test_launch_serve_is_deprecated_stub():
    """The old LM serving scaffold is gone: the module carries no model
    code and its CLI exits nonzero pointing at repro.serve."""
    import subprocess, os, sys
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-m", "repro.launch.serve"],
                          cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode == 2
    assert "repro.serve" in proc.stderr
    import repro.launch.serve as stub
    assert not any(hasattr(stub, name) for name in ("serve", "lm"))
