"""repro.obs: the unified telemetry spine, asserted end to end.

- the metrics registry: counters/gauges/log-bucket histograms, snapshot
  JSON round-trips, exact cross-process merges, bounded quantile error;
- the Prometheus text exporter round-trips through its own strict
  parser, which rejects malformed input (names, labels, duplicates);
- the tracer: thread-local nesting, JSONL persistence, idempotent end,
  cross-process parent propagation via env, and a shared no-op span
  when tracing is off (the warm serve path does zero telemetry work);
- spans on the profiler's clock: under a `jax.profiler` session m4's
  `m4.run` > `m4.build`/`m4.scan`/`m4.result` spans are host events of
  the trace, with or without a trace dir; with a trace dir they also
  reach the JSONL and pass `--check`;
- serve integration: one cache-miss request reconstructs as a single
  trace (admit -> queue -> flush -> compile/run), `/metrics` exposes
  per-lane queue gauges in both JSON and Prometheus form;
- fleet integration: a chaos `kill` plan still yields one complete,
  stitchable trace per task (the killed attempt writes no root span;
  the retry writes the closed one), validated through the same
  `python -m repro.obs --check --coord` gate CI runs;
- the train loop's compile-vs-steady wall split lands in history
  entries and the process registry.
"""
import json
import os
import random

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.obs import (MetricsRegistry, Histogram, NULL_SPAN, Tracer,
                       labeled, lookup, merge_snapshots, parse_prometheus,
                       read_spans, spans_by_trace, split_labels,
                       task_trace_id, to_prometheus)
from repro.obs import __main__ as obs_cli
from repro.obs.trace import configure, get_tracer
from repro.scenarios import ScenarioSpec
from repro.sim import Backend, SimResult

WAIT = 120


# ----------------------------------------------------------------- registry
def test_registry_snapshot_schema_and_roundtrip():
    reg = MetricsRegistry(proc="t")
    reg.inc("a.count", 3)
    reg.inc(labeled("a.by_lane", lane="x"), 2)
    reg.set_gauge("a.depth", 7.5)
    for v in (0.001, 0.01, 0.25):
        reg.observe("a.wall_s", v)
    snap = reg.snapshot()
    assert snap["schema"] == "repro.obs/1"
    assert snap["proc"] == "t"
    assert snap["counters"]["a.count"] == 3
    assert snap["counters"]['a.by_lane{lane="x"}'] == 2
    assert snap["gauges"]["a.depth"] == 7.5
    # snapshots are plain JSON and histograms reload losslessly
    reloaded = json.loads(json.dumps(snap))
    h = Histogram.from_dict(reloaded["histograms"]["a.wall_s"], "a.wall_s")
    h0 = reg.histogram("a.wall_s")
    assert h.count == h0.count and h.buckets == h0.buckets
    assert h.quantile(0.5) == h0.quantile(0.5)


def test_labeled_split_roundtrip():
    name = labeled("serve.completed", lane="flowsim_fast", zone="a")
    base, labels = split_labels(name)
    assert base == "serve.completed"
    assert labels == {"lane": "flowsim_fast", "zone": "a"}
    assert split_labels("plain") == ("plain", {})


def test_histogram_quantile_error_is_bounded():
    rng = random.Random(7)
    h = Histogram("w")
    samples = [rng.lognormvariate(0.0, 1.5) for _ in range(20000)]
    for s in samples:
        h.observe(s)
    samples.sort()
    for q in (0.5, 0.9, 0.99):
        exact = samples[int(q * len(samples))]
        rel = abs(h.quantile(q) - exact) / exact
        # log-bucket growth 2**0.25 bounds relative error at ~9%
        assert rel < 0.09, (q, rel)
    assert abs(h.mean - np.mean(samples)) / np.mean(samples) < 1e-6


def test_histogram_merge_is_exact():
    a, b, whole = Histogram("x"), Histogram("x"), Histogram("x")
    rng = random.Random(3)
    for i in range(5000):
        v = rng.expovariate(1.0)
        (a if i % 2 else b).observe(v)
        whole.observe(v)
    a.merge(b)
    assert a.buckets == whole.buckets
    assert a.count == whole.count
    assert a.quantile(0.99) == whole.quantile(0.99)
    assert a.min == whole.min and a.max == whole.max


def test_merge_snapshots_adds_counters_and_histograms():
    regs = [MetricsRegistry(proc=f"p{i}") for i in range(3)]
    for i, reg in enumerate(regs):
        reg.inc("n.tasks", i + 1)
        reg.set_gauge("n.depth", float(i))
        reg.observe("n.wall_s", 0.1 * (i + 1))
    merged = merge_snapshots([r.snapshot() for r in regs])
    assert merged["counters"]["n.tasks"] == 6
    assert merged["gauges"]["n.depth"] == 2.0     # max wins for gauges
    h = Histogram.from_dict(merged["histograms"]["n.wall_s"])
    assert h.count == 3


def _rand_snapshot(seed: int) -> dict:
    """A small random registry snapshot (histograms + counters)."""
    rng = random.Random(seed)
    reg = MetricsRegistry(proc=f"p{seed}")
    reg.inc("m.count", rng.randint(0, 5))
    # integer-valued samples: float addition over them is exact, so the
    # merged `sum` is associative bit-for-bit (buckets/counts always are)
    for _ in range(rng.randint(1, 20)):
        reg.observe("m.wall_s", float(rng.randint(1, 1_000_000)))
    if rng.random() < 0.5:                 # partially-overlapping keys
        reg.observe("m.other", float(rng.randint(1, 100)))
        reg.inc("m.extra")
    return reg.snapshot()


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000), st.integers(0, 10_000), st.integers(0, 10_000))
def test_merge_snapshots_is_associative_and_order_invariant(sa, sb, sc):
    """Counters and histograms merge like a commutative monoid: any
    grouping and any ordering of the same snapshots yields the same
    totals and the same buckets. (Gauges are last-write and `proc` is a
    concatenation — both order-dependent by design, so excluded.)"""
    a, b, c = (_rand_snapshot(s) for s in (sa, sb, sc))

    def core(s):
        return (s["counters"], s["histograms"])

    left = merge_snapshots([merge_snapshots([a, b]), c])
    right = merge_snapshots([a, merge_snapshots([b, c])])
    flat = merge_snapshots([a, b, c])
    perm = merge_snapshots([c, a, b])
    assert core(left) == core(right) == core(flat) == core(perm)


# --------------------------------------------------------------- prometheus
def test_prometheus_roundtrip():
    reg = MetricsRegistry(proc="svc")
    reg.inc("serve.completed", 42)
    reg.inc(labeled("serve.completed_by", lane="fast"), 7)
    reg.set_gauge("serve.queue_depth", 3)
    for v in (0.002, 0.004, 0.008):
        reg.observe("serve.queue_delay_s", v)
    text = to_prometheus(reg.snapshot())
    parsed = parse_prometheus(text)
    assert lookup(parsed, "repro_serve_completed_total") == 42
    assert lookup(parsed, "repro_serve_completed_by_total", lane="fast") == 7
    assert lookup(parsed, "repro_serve_queue_depth") == 3
    assert lookup(parsed, "repro_serve_queue_delay_s_count") == 3
    p50 = lookup(parsed, "repro_serve_queue_delay_s", quantile="0.5")
    assert p50 == pytest.approx(0.004, rel=0.1)


def test_prometheus_help_text_roundtrips_descriptions():
    reg = MetricsRegistry(proc="svc")
    reg.inc("diff.scenarios", 2)
    reg.describe("diff.scenarios", "scenarios compared, m4 vs oracle")
    reg.observe("probe.link_queue", 1.5)
    reg.describe("probe.link_queue", "probe channel link_queue (bytes)")
    reg.set_gauge("diff.mean_rel_err", 0.13)
    text = to_prometheus(reg.snapshot())
    parsed, heads = parse_prometheus(text, meta=True)
    assert heads["repro_diff_scenarios_total"] == {
        "help": "scenarios compared, m4 vs oracle", "type": "counter"}
    assert heads["repro_probe_link_queue"] == {
        "help": "probe channel link_queue (bytes)", "type": "summary"}
    # undescribed metrics still get the generic HELP line
    assert heads["repro_diff_mean_rel_err"]["help"] == "repro.obs metric"
    assert lookup(parsed, "repro_diff_scenarios_total") == 2


@pytest.mark.parametrize("bad", [
    "repro_x_total 1\nrepro_x_total 2\n",            # duplicate sample
    "9bad_name 1\n",                                  # invalid metric name
    'repro_x{lane=unquoted} 1\n',                     # unquoted label value
    "# TYPE repro_x sometype\nrepro_x 1\n",           # unknown TYPE
    "repro_x notanumber\n",                           # non-numeric value
])
def test_prometheus_parser_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_prometheus(bad)


# ------------------------------------------------------------------- tracer
@pytest.fixture()
def trace_dir(tmp_path, monkeypatch):
    """Enable the global tracer into a temp dir; restore the disabled
    tracer (and env) afterwards so other tests stay telemetry-free."""
    monkeypatch.delenv("REPRO_TRACE_DIR", raising=False)
    monkeypatch.delenv("REPRO_TRACE_PARENT", raising=False)
    d = str(tmp_path / "spans")
    configure(d, proc="test")
    yield d
    configure(None)


def test_disabled_tracer_hands_out_the_shared_null_span(tmp_path):
    t = Tracer(None)
    assert not t.enabled
    sp = t.span("anything", attrs={"k": 1})
    assert sp is NULL_SPAN                # no allocation, no clock read
    with sp:
        pass
    sp.end()                              # all no-ops
    assert read_spans(str(tmp_path)) == []


def test_tracer_nesting_jsonl_and_idempotent_end(trace_dir):
    tracer = get_tracer()
    with tracer.span("root", attrs={"run": 1}) as root:
        with tracer.span("child_a"):
            pass
        free = tracer.start("child_b", parent=root)   # cross-thread style
        free.end(status="done")
        free.end(status="overwritten-never")          # idempotent
    recs = read_spans(trace_dir)
    assert len(recs) == 3
    by_trace = spans_by_trace(recs)
    assert len(by_trace) == 1
    (recs,) = by_trace.values()
    names = {r["name"]: r for r in recs}
    assert names["root"]["parent_id"] is None
    assert names["child_a"]["parent_id"] == names["root"]["span_id"]
    assert names["child_b"]["parent_id"] == names["root"]["span_id"]
    assert names["child_b"]["status"] == "done"
    for r in recs:
        assert r["t_end"] >= r["t_start"]


def test_trace_parent_env_propagates(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TRACE_PARENT", "cafecafecafecafe:1234")
    t = Tracer(str(tmp_path), proc="child")
    sp = t.span("worker")
    assert sp.trace_id == "cafecafecafecafe"
    assert sp.parent_id == "1234"
    sp.end()


def test_span_exit_records_exception_status(trace_dir):
    tracer = get_tracer()
    with pytest.raises(RuntimeError):
        with tracer.span("doomed"):
            raise RuntimeError("boom")
    (rec,) = read_spans(trace_dir)
    assert rec["status"] == "error:RuntimeError"


def test_torn_trailing_line_is_skipped(trace_dir):
    tracer = get_tracer()
    tracer.span("ok").end()
    tracer.close()
    path = next(os.path.join(trace_dir, f) for f in os.listdir(trace_dir))
    with open(path, "a", encoding="utf-8") as fh:
        fh.write('{"trace_id": "deadbeef", "name": "torn')  # killed writer
    assert [r["name"] for r in read_spans(trace_dir)] == ["ok"]


# ------------------------------------------- spans on the profiler's clock
M4_CHILDREN = ("m4.build", "m4.scan", "m4.result")


def _host_events(trace_root):
    """(name, start_ns, end_ns) of the host events of a profiler trace."""
    import glob
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_root, "**", "*.xplane.pb"),
                        recursive=True)
    return [(ev.name, ev.start_ns, ev.end_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def _profiled(tmp_path, fn):
    import jax
    root = str(tmp_path / "profile")
    jax.profiler.start_trace(root)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _host_events(root)


@pytest.fixture(scope="module")
def tiny_m4():
    """A tiny m4 and two small scenarios; `run(batched)` simulates them."""
    import jax
    from repro.core import simulate as sim
    from repro.core.model import M4Config, init_m4
    cfg = M4Config(hidden=16, gnn_dim=12, mlp_hidden=8, gnn_layers=2,
                   snap_flows=8, snap_links=24, kernel_mode="xla")
    params = init_m4(jax.random.PRNGKey(0), cfg)
    scens = [ScenarioSpec(topo="ft-4x2x2", num_flows=10, seed=s)
             .to_scenario() for s in (3, 4)]
    scens = [(sc.topo, sc.config, sc.generate()) for sc in scens]

    def run(batched):
        if batched:
            return sim.simulate_open_loop_batch(params, cfg, scens)
        return sim.simulate_open_loop(params, cfg, *scens[0])

    run(False), run(True)                   # compile outside the traces
    return run


@pytest.mark.parametrize("batched", [False, True])
def test_m4_spans_nest_in_the_profiler_trace(tmp_path, monkeypatch, tiny_m4,
                                             batched):
    monkeypatch.delenv("REPRO_TRACE_DIR", raising=False)
    configure(None)
    events = _profiled(tmp_path, lambda: tiny_m4(batched))
    root = "m4.run_many" if batched else "m4.run"
    (run,) = [e for e in events if e[0] == root]
    kids = {e[0]: e for e in events if e[0] in M4_CHILDREN}
    assert sorted(kids) == sorted(M4_CHILDREN)
    for _, start, end in kids.values():
        assert run[1] <= start <= end <= run[2]
    assert kids["m4.build"][2] <= kids["m4.scan"][1]
    assert kids["m4.scan"][2] <= kids["m4.result"][1]
    assert read_spans(str(tmp_path)) == []  # no trace dir: no JSONL


def test_m4_spans_reach_the_jsonl_and_pass_check(trace_dir, tmp_path,
                                                 tiny_m4, capsys):
    events = _profiled(tmp_path, lambda: tiny_m4(False))
    recs = read_spans(trace_dir)
    by_name = {r["name"]: r for r in recs}
    assert sorted(by_name) == sorted(("m4.run",) + M4_CHILDREN)
    for name in M4_CHILDREN:
        assert by_name[name]["parent_id"] == by_name["m4.run"]["span_id"]
    # the same spans are host events of the profiler's trace
    assert {e[0] for e in events} >= set(by_name)
    assert obs_cli.main(["--dir", trace_dir, "--check"]) == 0
    assert "obs check: OK" in capsys.readouterr().out


def test_span_is_null_without_profiler_or_trace_dir(tmp_path, monkeypatch):
    from repro.obs import phase
    monkeypatch.delenv("REPRO_TRACE_DIR", raising=False)
    tracer = configure(None)
    assert tracer.span("m4.run") is NULL_SPAN
    seen = []

    def work():
        sp = tracer.span("m4.run")       # a profiler session collects
        seen.append(sp)
        with sp:
            with phase("demo", registry=MetricsRegistry()):
                pass

    events = _profiled(tmp_path, work)
    assert seen[0] is not NULL_SPAN
    assert {"m4.run", "phase:demo"} <= {e[0] for e in events}
    assert tracer.span("m4.run") is NULL_SPAN       # the session is over
    assert read_spans(str(tmp_path)) == []


# ------------------------------------------------------------------- serve
class _Stub(Backend):
    """Tiny deterministic jax-free backend (mirrors test_serve's stub)."""
    name = "stub"

    def run(self, request):
        n = request.num_flows
        return SimResult(fcts=np.full(n, 1.0 + request.seed, np.float64),
                         slowdowns=np.ones(n, np.float64),
                         wall_time=0.0, backend=self.name)

    def run_many(self, requests):
        return [self.run(r) for r in requests]

    def fingerprint(self):
        return "stub-v1"


def _stub_request(seed):
    return ScenarioSpec(topo="ft-4x2x2", num_flows=4, seed=seed,
                        max_load=0.4).to_request(seed=seed)


def test_serve_request_reconstructs_as_one_trace(trace_dir, tmp_path):
    from repro.serve import ServeConfig, SimService
    with SimService(_Stub(), cache_dir=str(tmp_path / "cache"),
                    config=ServeConfig(batch_size=2,
                                       flush_interval_s=0.01)) as svc:
        f0 = svc.submit(_stub_request(0))
        f1 = svc.submit(_stub_request(1))
        f0.result(timeout=WAIT)
        f1.result(timeout=WAIT)
        svc.submit(_stub_request(0)).result(timeout=WAIT)   # cache hit
    traces = spans_by_trace(read_spans(trace_dir))
    roots = {tid: recs for tid, recs in traces.items()
             if any(r["name"] == "serve.request" and r["parent_id"] is None
                    for r in recs)}
    assert len(roots) == 3
    full = [recs for recs in roots.values() if len(recs) > 2]
    assert len(full) == 2                 # two misses, one cache-hit root
    for recs in full:
        names = [r["name"] for r in recs]
        for expected in ("serve.request", "serve.admit", "serve.queue",
                         "serve.flush"):
            assert expected in names, names
        assert "serve.compile" in names or "serve.run" in names
        root = next(r for r in recs if r["parent_id"] is None)
        for r in recs:
            assert r["t_start"] >= root["t_start"] - 2e-3
            assert r["t_end"] <= root["t_end"] + 2e-3
    hit = next(recs for recs in roots.values() if len(recs) <= 2)
    assert any(r["status"] == "cache-hit" for r in hit)
    # the CI gate accepts the same structure
    assert obs_cli.main(["--dir", trace_dir, "--check"]) == 0


def test_metrics_expose_per_lane_queue_gauges_in_both_formats():
    from repro.serve import ServeConfig, SimService
    from repro.serve.metrics import prometheus_text
    with SimService(_Stub(), config=ServeConfig(batch_size=2,
                                                flush_interval_s=0.01)) as svc:
        for seed in range(3):
            svc.submit(_stub_request(seed)).result(timeout=WAIT)
        agg = svc.metrics()
        assert agg["completed"] == 3
        assert "queue_depth" in agg       # summed across lanes
        lane = agg["lanes"]["stub"]
        assert lane["queue_depth"] == 0 and lane["dispatcher_alive"]
        parsed = parse_prometheus(prometheus_text(agg))
    assert lookup(parsed, "repro_serve_completed_total") == 3
    assert lookup(parsed, "repro_serve_queue_depth", lane="stub") == 0
    assert lookup(parsed, "repro_serve_dispatcher_alive", lane="stub") == 1
    assert lookup(parsed,
                  "repro_serve_queue_delay_s_count", lane="stub") == 3


def test_tracing_off_leaves_no_span_files(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_TRACE_DIR", raising=False)
    configure(None)
    from repro.serve import ServeConfig, SimService
    with SimService(_Stub(), config=ServeConfig(batch_size=2,
                                                flush_interval_s=0.01)) as svc:
        assert svc.submit(_stub_request(5)).result(timeout=WAIT) is not None
    assert read_spans(str(tmp_path)) == []


# -------------------------------------------------------------------- fleet
def test_fleet_chaos_kill_still_stitches_every_task(tmp_path, monkeypatch):
    from repro.fleet import (FleetConfig, parse_plan, run_fleet,
                             sweep_job_for, sweep_tasks)
    from repro.runtime.resilience import Backoff
    from repro.scenarios import get_suite
    from repro.scenarios.cache import result_key
    from repro.sim import get_backend

    monkeypatch.delenv("REPRO_TRACE_DIR", raising=False)
    monkeypatch.delenv("REPRO_TRACE_PARENT", raising=False)
    backend = get_backend("flowsim")
    specs = list(get_suite("smoke16", num_flows=8).limit(5))
    reqs = [s.to_request() for s in specs]
    keys = [result_key(r, backend) for r in reqs]
    cache = str(tmp_path / "cache")
    coord = str(tmp_path / "coord")
    trace = str(tmp_path / "trace")
    tasks = sweep_tasks(specs, reqs, keys, 1)
    cfg = FleetConfig(workers=2, coord_dir=coord, heartbeat_s=0.05,
                      lease_timeout_s=0.6, poll_s=0.02, max_attempts=3,
                      backoff=Backoff(base_s=0.05, factor=2.0, cap_s=0.3),
                      chaos=parse_plan("kill:worker=0,after=1", seed=0),
                      trace_dir=trace)
    try:
        metrics = run_fleet(tasks, sweep_job_for(backend, cache), cfg)
    finally:
        configure(None)
    assert metrics.done == len(tasks)
    # the killed worker shows up as a broken lease + a respawn
    assert metrics.lease_breaks + metrics.kills >= 1
    assert metrics.worker_restarts >= 1

    traces = spans_by_trace(read_spans(trace))
    for task_id, _payload in tasks:
        recs = traces.get(task_trace_id(task_id))
        assert recs, f"no trace for task {task_id[:16]}"
        root = next(r for r in recs if r["parent_id"] is None
                    and r["name"] == "fleet.task")
        assert root["status"] == "done"
        kid_names = {r["name"] for r in recs
                     if r["parent_id"] == root["span_id"]}
        assert {"fleet.claim", "fleet.build", "fleet.cache-write",
                "fleet.verify", "fleet.done"} <= kid_names
    # worker lifetimes hang off the supervisor's fleet.run root: the
    # env-propagated parent crossed the spawn boundary
    run_trace = next(recs for recs in traces.values()
                     if any(r["name"] == "fleet.run" for r in recs))
    assert any(r["name"] == "fleet.worker" and r["parent_id"] is not None
               for r in run_trace)
    # the CI gate: structural validity + every done task stitched
    assert obs_cli.main(["--dir", trace, "--check", "--coord", coord]) == 0
    # the supervisor's obs snapshot landed next to metrics.json
    snap_paths = [os.path.join(coord, "obs_snapshot.json")]
    assert os.path.exists(snap_paths[0])
    merged = merge_snapshots([json.load(open(p)) for p in snap_paths])
    assert merged["counters"]["fleet.done"] == len(tasks)
    assert merged["counters"]["fleet.worker_restarts"] >= 1
    assert merged["histograms"]["fleet.chunk_wall_s"]["count"] >= len(tasks)


# ---------------------------------------------------------------------- CLI
def test_cli_merge_and_prom(tmp_path, capsys):
    snaps = []
    for i in range(2):
        reg = MetricsRegistry(proc=f"w{i}")
        reg.inc("fleet.done", 4)
        reg.observe("fleet.chunk_wall_s", 0.5)
        path = tmp_path / f"snap{i}.json"
        path.write_text(json.dumps(reg.snapshot()))
        snaps.append(str(path))
    # a report carrying the snapshot under "obs" is accepted as-is
    wrapped = tmp_path / "train_log.json"
    wrapped.write_text(json.dumps(
        {"suite": "x", "obs": {"schema": "repro.obs/1", "proc": "t",
                               "counters": {"fleet.done": 1}, "gauges": {},
                               "histograms": {}}}))
    assert obs_cli.main(["--merge", *snaps, str(wrapped)]) == 0
    merged = json.loads(capsys.readouterr().out)
    assert merged["counters"]["fleet.done"] == 9
    assert obs_cli.main(["--merge", *snaps, "--prom"]) == 0
    parsed = parse_prometheus(capsys.readouterr().out)
    assert lookup(parsed, "repro_fleet_done_total") == 8


def test_cli_check_fails_on_unclosed_root(tmp_path, capsys):
    d = tmp_path / "spans"
    d.mkdir()
    rec = {"trace_id": "t1", "span_id": "c1", "parent_id": "gone",
           "name": "fleet.claim", "t_start": 1.0, "t_end": 2.0,
           "status": "ok", "proc": "w", "pid": 1, "attrs": {}}
    (d / "spans-w-1.jsonl").write_text(json.dumps(rec) + "\n")
    assert obs_cli.main(["--dir", str(d), "--check"]) == 1
    assert "no closed root span" in capsys.readouterr().out


def test_cli_check_fails_on_child_outside_root_window(tmp_path, capsys):
    d = tmp_path / "spans"
    d.mkdir()
    root = {"trace_id": "t1", "span_id": "r", "parent_id": None,
            "name": "job", "t_start": 10.0, "t_end": 11.0,
            "status": "ok", "proc": "w", "pid": 1, "attrs": {}}
    kid = dict(root, span_id="k", parent_id="r", name="step",
               t_start=11.5, t_end=12.0)
    (d / "spans-w-1.jsonl").write_text(
        json.dumps(root) + "\n" + json.dumps(kid) + "\n")
    assert obs_cli.main(["--dir", str(d), "--check"]) == 1
    assert "outside" in capsys.readouterr().out


def test_cli_trace_render_and_flame(trace_dir, capsys):
    tracer = get_tracer()
    with tracer.span("outer") as sp:
        tid = sp.trace_id
        with tracer.span("inner"):
            pass
    assert obs_cli.main(["--dir", trace_dir, "--trace", tid[:8]]) == 0
    out = capsys.readouterr().out
    assert "outer" in out and "inner" in out
    assert obs_cli.main(["--dir", trace_dir, "--flame"]) == 0
    assert "outer" in capsys.readouterr().out


# ------------------------------------------------------------------- probes
def test_probes_off_is_the_identical_program():
    """`probes=None` is a trace-time branch, not a runtime one: the
    unprobed call after a probed compile reuses the executable compiled
    *before* any probe existed (TRACE_COUNTS unchanged), so probes-off
    events/sec is the pre-probe program's by construction — there is no
    second unprobed program to regress (the perf gate's BENCH files gate
    the absolute rate)."""
    from repro.core.flowsim_fast import TRACE_COUNTS
    from repro.core.probes import ProbeConfig
    from repro.sim import get_backend

    backend = get_backend("flowsim_fast")
    spec = ScenarioSpec(topo="ft-4x2x2", num_flows=6, max_load=0.4)
    r0 = backend.run(spec.to_request())
    c0 = sum(TRACE_COUNTS.values())
    r1 = backend.run(spec.to_request())               # warm: no retrace
    assert sum(TRACE_COUNTS.values()) == c0
    rp = backend.run(spec.to_request(
        probes=ProbeConfig(stride=2, max_samples=8)))
    cp = sum(TRACE_COUNTS.values())
    assert cp == c0 + 1                               # probes-on: one program
    r2 = backend.run(spec.to_request())               # off again: still warm
    assert sum(TRACE_COUNTS.values()) == cp
    assert r2.probes is None and rp.probes is not None
    assert np.array_equal(r0.fcts, r1.fcts)
    assert np.array_equal(r0.fcts, r2.fcts)           # bitwise-identical


# -------------------------------------------------------------------- train
def test_fit_history_carries_compile_step_split(tmp_path):
    from repro.core.model import M4Config
    from repro.scenarios import get_suite
    from repro.train.data import build_dataset
    from repro.train.loop import TrainConfig, fit

    cfg = M4Config(hidden=8, gnn_dim=8, mlp_hidden=8, gnn_layers=1,
                   snap_flows=8, snap_links=16)
    suite = get_suite("smoke16", num_flows=10).limit(2)
    batches, _ = build_dataset(list(suite), cfg, str(tmp_path / "data"),
                               max_events=48)
    _, history = fit(batches, cfg, TrainConfig(epochs=2, bucket_size=2),
                     log=lambda *a, **k: None)
    ep0, ep1 = history
    assert ep0["compiles"] >= 1 and ep0["compile_s"] > 0
    assert ep1["compiles"] == 0 and ep1["compile_s"] == 0
    assert ep1["step_s"] > 0
    for e in history:
        assert e["compile_s"] + e["step_s"] == pytest.approx(
            e["wall_s"], rel=0.25, abs=0.05)
    from repro.obs.registry import get_registry
    snap = get_registry().snapshot()
    assert snap["counters"]["train.steps"] >= 2
    assert snap["counters"]["train.compiles"] >= 1
    assert "train.step_wall_s" in snap["histograms"]
