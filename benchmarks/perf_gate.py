"""Perf gate: steady-state events/sec of the jax hot paths, as data.

Measures the m4 open-loop event scan (production incremental path AND the
seed program preserved behind ``snapshot_impl="dense"`` — the
"current-main" baseline the speedup is claimed against) and the
flowsim_fast event scan, at arena sizes N in {256, 1024, 4096} on
proportionally grown fat-trees, plus the end-to-end throughput of the
`repro.serve` dynamic-batching service (``measure_serve``), plus the
deterministic m4-vs-flowSim *accuracy* profile (``measure_accuracy``,
via `repro.obs.diff`). Results land in ``BENCH_m4.json``,
``BENCH_flowsim_fast.json``, ``BENCH_serve.json``, and
``BENCH_accuracy.json`` at the repo root; committing them gives the repo
a perf + accuracy trajectory, and the CI jobs replay ``--check`` against
the committed files (``--only serve`` / ``--only accuracy`` run just
that benchmark, as the serve-smoke / accuracy-gate jobs do).

Methodology
-----------
- **Steady state only.** Every (shape, impl) gets a warmup call first; the
  cold call (XLA trace + compile + run) is reported separately as
  ``first_call_s``. Without the split, fresh-shape timings are dominated
  by compilation (tens of seconds vs sub-second execution).
- **Event-capped scans.** Per-event cost is flat across the trace, so the
  scan is capped at ``--events`` events instead of the full 2N — a 4096-
  flow legacy trace would otherwise take minutes per repetition on CPU.
- **Interleaved reps, max events/sec.** Impls alternate inside each
  repetition and the best rate per impl wins: robust against host load
  spikes (shared CI runners routinely wobble 30%+).
- **Untrained CI-scale model.** Event-step cost does not depend on weight
  values, and the deliberately small model keeps the gate sensitive to
  the *simulator machinery* (snapshot building, arena updates, event
  selection) rather than GEMM throughput.

Gate semantics (``--check``)
----------------------------
Absolute events/sec are not comparable across machines, so the gated
quantity is the **incremental/legacy speedup ratio**, geometric-mean
across arena sizes (fails on >20% regression vs the committed file,
``--tolerance``; per-N ratios stay in the report as data).
Absolute events/sec are additionally gated when the committed file was
measured on the same host (hostname match), at 2x the tolerance — even
same-host reruns on small shared boxes see scheduler-level variance well
beyond what best-of-reps cancels. Cross-host absolute comparisons only
warn.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import socket
import sys
import time

import numpy as np

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(REPO_ROOT, "src"))

# CI-scale gate model: paper structure, small dims (see module docstring)
GATE_SIZES = ((256, "ft-8x4x2"), (1024, "ft-16x8x4"), (4096, "ft-32x16x8"))


def _rate(run, events, reps):
    """Best observed events/sec over `reps` repetitions; each repetition
    loops the scan enough times to fill a ~0.25s window, so sub-50ms
    measurements aren't at the mercy of one scheduler tick."""
    t0 = time.perf_counter()
    run()
    dt = max(time.perf_counter() - t0, 1e-4)
    best = events / dt
    loops = max(1, int(0.5 / dt))
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(loops):
            run()
        best = max(best, events * loops / (time.perf_counter() - t0))
    return best


def _gate_cfg():
    from repro.core.model import M4Config
    return M4Config(hidden=16, gnn_dim=16, mlp_hidden=16, gnn_layers=2,
                    snap_flows=16, snap_links=32)


def _scenario(n, topo):
    from repro.scenarios.spec import ScenarioSpec
    sc = ScenarioSpec(topo=topo, num_flows=n, seed=1,
                      max_load=0.5).to_scenario()
    return sc, sc.generate()


def measure_m4(sizes=GATE_SIZES, events=512, reps=3, log=print):
    """events/sec of the m4 event scan, incremental vs legacy, per N."""
    import jax
    import jax.numpy as jnp
    from repro.core import simulate as sim
    from repro.core.model import init_m4

    cfg = sim.canonicalize_cfg(_gate_cfg())
    params = init_m4(jax.random.PRNGKey(0), cfg)
    entries = []
    for n, topo in sizes:
        sc, flows = _scenario(n, topo)
        static, num_links, _ = sim.make_static(sc.topo, flows, sc.config, cfg)
        order, times = sim._arrival_order(static)
        args = (params, cfg, num_links, static, jnp.asarray(order),
                jnp.asarray(times))
        first, best = {}, {}
        for impl in ("incremental", "dense"):
            t0 = time.perf_counter()
            jax.block_until_ready(sim._open_loop_scan(
                *args, snapshot_impl=impl, num_events=events))
            first[impl] = time.perf_counter() - t0

            def run(impl=impl):
                jax.block_until_ready(sim._open_loop_scan(
                    *args, snapshot_impl=impl, num_events=events))
            best[impl] = _rate(run, events, reps)
        e = {
            "n": n, "topo": topo, "events": events,
            "events_per_sec": round(best["incremental"], 1),
            "legacy_events_per_sec": round(best["dense"], 1),
            "speedup_vs_legacy": round(best["incremental"] / best["dense"], 3),
            "first_call_s": round(first["incremental"], 3),
            "steady_s": round(events / best["incremental"], 4),
        }
        entries.append(e)
        log(f"[m4] N={n:5d} {topo:11s} inc={e['events_per_sec']:9.0f} ev/s  "
            f"legacy={e['legacy_events_per_sec']:8.0f} ev/s  "
            f"speedup={e['speedup_vs_legacy']:.2f}x  "
            f"(first call {e['first_call_s']:.1f}s)")
    return {"benchmark": "m4", "config": _cfg_dict(cfg),
            "kernel_mode": cfg.kernel_mode, "entries": entries}


def measure_flowsim_fast(sizes=GATE_SIZES, events=256, reps=3, log=print):
    """events/sec of the flowsim_fast event scan per N (one impl; the gate
    tracks absolute same-host rate + its trajectory)."""
    import jax
    import jax.numpy as jnp
    from repro.core import flowsim_fast as ff
    from repro.kernels.dispatch import resolve_mode

    mode = resolve_mode()
    entries = []
    for n, topo in sizes:
        # flowsim_fast per-event cost is O(N·L) waterfill rounds (~30ms at
        # N=4096 on CPU): scale the cap down so one rep stays in seconds
        ev = max(32, min(events, (512 * 256) // n))
        sc, flows = _scenario(n, topo)
        a, cap, szs, times, order = ff._pack(sc.topo, flows)
        args = tuple(jnp.asarray(x) for x in (a, cap, szs, times, order))
        t0 = time.perf_counter()
        jax.block_until_ready(ff._event_scan(*args, mode=mode,
                                             num_events=ev))
        first = time.perf_counter() - t0

        def run():
            jax.block_until_ready(ff._event_scan(*args, mode=mode,
                                                 num_events=ev))
        best = _rate(run, ev, reps)
        e = {"n": n, "topo": topo, "events": ev,
             "events_per_sec": round(best, 1),
             "first_call_s": round(first, 3),
             "steady_s": round(ev / best, 4)}
        entries.append(e)
        log(f"[flowsim_fast] N={n:5d} {topo:11s} {e['events_per_sec']:9.0f} "
            f"ev/s (first call {e['first_call_s']:.1f}s)")
    return {"benchmark": "flowsim_fast", "kernel_mode": mode,
            "entries": entries}


def measure_serve(reps=3, log=print):
    """End-to-end SimService throughput: a 32-request shape-diverse
    concurrent workload (2 shape buckets, 4 client threads) through the
    dynamic-batching service, cold then warm.

    The cold pass pays simulation + up to one XLA compile per shape
    bucket; warm passes are pure content-hash cache hits. Structural
    facts (compiles, hit rate, failures) gate cross-host; requests/sec
    gates same-host only, like the other benchmarks."""
    import shutil
    import tempfile
    import threading

    from repro.scenarios.spec import ScenarioSpec
    from repro.serve import ServeConfig, SimService
    from repro.sim import get_backend

    n_reqs, n_threads, batch = 32, 4, 8
    reqs = [ScenarioSpec(topo="ft-8x4x2", num_flows=192 + 64 * (i % 2),
                         seed=i, max_load=0.5).to_request()
            for i in range(n_reqs)]
    cache_dir = tempfile.mkdtemp(prefix="bench_serve_")
    try:
        with SimService(get_backend("flowsim_fast"), cache_dir=cache_dir,
                        config=ServeConfig(batch_size=batch,
                                           flush_interval_s=0.02)) as svc:

            def drive():
                futs = []

                def client(lo):
                    for i in range(lo, n_reqs, n_threads):
                        futs.append(svc.submit(reqs[i]))
                threads = [threading.Thread(target=client, args=(lo,))
                           for lo in range(n_threads)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                for f in futs:
                    f.result(timeout=600)

            t0 = time.perf_counter()
            drive()
            cold_rps = n_reqs / (time.perf_counter() - t0)
            warm_rps = 0.0
            for _ in range(reps):
                t0 = time.perf_counter()
                drive()
                warm_rps = max(warm_rps, n_reqs / (time.perf_counter() - t0))
            m = svc.metrics()
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)

    e = {"n": n_reqs,
         "cold_requests_per_sec": round(cold_rps, 1),
         "warm_requests_per_sec": round(warm_rps, 1),
         "compiles": m["compiles"],
         "batch_occupancy": m["batch_occupancy"],
         "queue_delay_p50_ms": m["queue_delay_p50_ms"],
         "queue_delay_p99_ms": m["queue_delay_p99_ms"],
         "warm_hit_rate": round(m["cache_hits"] / (reps * n_reqs), 4),
         "failed": m["failed"] + m["rejected"] + m["timed_out"]}
    log(f"[serve] {n_reqs} reqs x {n_threads} threads: "
        f"cold={e['cold_requests_per_sec']:.1f} rps  "
        f"warm={e['warm_requests_per_sec']:.0f} rps  "
        f"compiles={e['compiles']}  "
        f"p99 delay={e['queue_delay_p99_ms']:.1f}ms")
    return {"benchmark": "serve",
            "workload": {"requests": n_reqs, "threads": n_threads,
                         "shape_buckets": 2, "batch_size": batch,
                         "warm_passes": reps},
            "entries": [e]}


def measure_accuracy(scenarios=6, num_flows=24, log=print):
    """m4-vs-flowSim per-flow accuracy on fixed smoke scenarios, as data.

    Runs the deterministic gate-scale m4 (untrained, PRNGKey(0) — the
    committed numbers are a *fixture*, not a quality claim) and the
    flowsim_fast baseline through `repro.obs.diff.diff_sweep` on the
    first `scenarios` smoke16 specs, with probes on both sides so the
    report also carries intermediate-state series distances. Unlike the
    timing benchmarks, every number here is a simulation output: it
    reproduces bit-for-bit on any host, so `check_accuracy` gates
    cross-host with no hostname escape hatch."""
    import jax
    from repro.core.model import init_m4
    from repro.core.probes import ProbeConfig
    from repro.obs.diff import diff_sweep
    from repro.scenarios.suites import get_suite
    from repro.sim import get_backend

    cfg = _gate_cfg()
    m4 = get_backend("m4", params=init_m4(jax.random.PRNGKey(0), cfg),
                     cfg=cfg)
    base = get_backend("flowsim_fast")
    suite = get_suite("smoke16", num_flows=num_flows).limit(scenarios)
    report = diff_sweep(suite, m4, base, cache_dir=None, chunk_size=None,
                        probes=ProbeConfig(stride=4, max_samples=64))
    entries = []
    for p in sorted(report["profiles"], key=lambda p: p["label"]):
        e = {"scenario": p["label"], "flows": p["num_flows"],
             "mean_rel_err": round(p["mean_rel_err"], 4),
             "p90_rel_err": round(p["p90_rel_err"], 4),
             "sldn_p99_delta": round(p["sldn_delta"]["p99"], 4),
             "probe_distance": {k: round(v, 4)
                                for k, v in sorted(
                                    p["probe_distance"].items())}}
        entries.append(e)
        log(f"[accuracy] {e['scenario']:<12} flows={e['flows']:3d}  "
            f"mean={e['mean_rel_err']:.4f}  p90={e['p90_rel_err']:.4f}  "
            f"sldn_p99_d={e['sldn_p99_delta']:+.3f}")
    s = report["summary"]
    log(f"[accuracy] pooled over {s['flows']} flows: "
        f"mean={s['mean_rel_err']:.4f}  p90={s['p90_rel_err']:.4f}")
    return {"benchmark": "accuracy",
            "config": _cfg_dict(cfg), "oracle": "flowsim_fast",
            "suite": {"name": "smoke16", "scenarios": scenarios,
                      "num_flows": num_flows},
            "summary": {"mean_rel_err": s["mean_rel_err"],
                        "p90_rel_err": s["p90_rel_err"],
                        "flows": s["flows"]},
            "entries": entries}


def check_accuracy(report, baseline, tolerance=0.2, log=print):
    """Accuracy gate: structure everywhere, error levels with tolerance.

    Structural (exact): same scenario set and per-scenario flow counts —
    a changed suite silently invalidates the comparison. Gated: the
    flow-pooled mean and p90 relative error may not exceed the committed
    baseline by more than `tolerance` (cross-host — these are
    deterministic simulation outputs, not timings)."""
    failures = []
    base_by = {e["scenario"]: e for e in baseline.get("entries", [])}
    new_by = {e["scenario"]: e for e in report.get("entries", [])}
    if sorted(base_by) != sorted(new_by):
        failures.append(
            f"accuracy: scenario set changed — baseline {sorted(base_by)} "
            f"vs {sorted(new_by)} (re-commit BENCH_accuracy.json)")
    for label in sorted(set(base_by) & set(new_by)):
        if new_by[label]["flows"] != base_by[label]["flows"]:
            failures.append(
                f"accuracy {label}: {new_by[label]['flows']} flows != "
                f"baseline {base_by[label]['flows']}")
    s, bs = report.get("summary") or {}, baseline.get("summary") or {}
    for k in ("mean_rel_err", "p90_rel_err"):
        if k not in s or k not in bs:
            failures.append(f"accuracy: summary missing {k!r}")
            continue
        lim = bs[k] * (1 + tolerance) + 1e-9
        if s[k] > lim:
            failures.append(
                f"accuracy {k}: {s[k]:.4f} > {lim:.4f} "
                f"(baseline {bs[k]:.4f} + {tolerance:.0%})")
    return failures


def check_serve(report, baseline, tolerance=0.2, log=print):
    """Serve gate: structural facts everywhere, throughput same-host.

    Cross-host gates — more XLA compiles than the committed run (a
    retrace crept into the batching path), a warm pass that is not 100%
    cache hits, or any failed/rejected/timed-out request. Requests/sec
    is gated at 2x tolerance only on hostname match, like the absolute
    rates in the other benchmarks."""
    failures = []
    same_host = baseline.get("host", {}).get("hostname") == \
        socket.gethostname()
    e = report["entries"][0]
    b = baseline["entries"][0]
    if e["compiles"] > b["compiles"]:
        failures.append(f"serve: {e['compiles']} compiles > baseline "
                        f"{b['compiles']} (retrace in the batching path)")
    if e["warm_hit_rate"] < 1.0:
        failures.append(f"serve: warm hit rate {e['warm_hit_rate']:.2%} "
                        "< 100%")
    if e["failed"] > 0:
        failures.append(f"serve: {e['failed']} requests "
                        "failed/rejected/timed out")
    abs_tol = min(1.0, 2 * tolerance)
    for k in ("cold_requests_per_sec", "warm_requests_per_sec"):
        lim = b[k] * (1 - abs_tol)
        if e[k] < lim:
            msg = (f"serve {k}: {e[k]:.1f} < {lim:.1f} "
                   f"(baseline {b[k]:.1f} - {abs_tol:.0%})")
            if same_host:
                failures.append(msg)
            else:
                log(f"[warn, different host — not gated] {msg}")
    return failures


def _cfg_dict(cfg):
    import dataclasses
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def _obs_snapshot(report):
    """The report's numeric facts as a `repro.obs/1` snapshot: gauges
    named ``bench.<benchmark>.<field>{n="..."}``, mergeable with the
    serve/fleet/train snapshots via ``python -m repro.obs --merge``.
    Pure addition to the report — ``check()``/``check_serve()`` read only
    ``entries``, so committed baselines without an ``obs`` key still
    compare cleanly."""
    from repro.obs.registry import MetricsRegistry, labeled
    reg = MetricsRegistry(proc="perf_gate")
    bench = report["benchmark"]
    for e in report.get("entries", []):
        for k, v in e.items():
            if k == "n" or isinstance(v, bool) \
                    or not isinstance(v, (int, float)):
                continue
            reg.set_gauge(labeled(f"bench.{bench}.{k}", n=str(e.get("n"))),
                          float(v))
    return reg.snapshot()


def _host_info():
    import jax
    return {"hostname": socket.gethostname(),
            "platform": platform.platform(),
            "machine": platform.machine(),
            "cpu_count": os.cpu_count(),
            "python": platform.python_version(),
            "jax": jax.__version__,
            "jax_backend": jax.default_backend()}


def check(report, baseline, tolerance=0.2, log=print):
    """Compare a fresh report against the committed baseline.

    Returns a list of failure strings (empty = pass). Gated: the
    incremental/legacy speedup ratio per N; absolute events/sec only when
    the baseline was measured on this host."""
    failures = []
    same_host = baseline.get("host", {}).get("hostname") == \
        socket.gethostname()
    base_by_n = {e["n"]: e for e in baseline.get("entries", [])}
    # speedup ratio gated on the geometric mean across arena sizes: per-N
    # ratios on a loaded 2-core box wobble ~30% run-to-run, the mean does
    # not; per-N values stay in the report as data
    pairs = [(e["speedup_vs_legacy"], base_by_n[e["n"]]["speedup_vs_legacy"])
             for e in report["entries"]
             if "speedup_vs_legacy" in e and e["n"] in base_by_n
             and "speedup_vs_legacy" in base_by_n[e["n"]]]
    if pairs:
        gm_new = float(np.exp(np.mean([np.log(p[0]) for p in pairs])))
        gm_base = float(np.exp(np.mean([np.log(p[1]) for p in pairs])))
        if gm_new < gm_base * (1 - tolerance):
            failures.append(
                f"{report['benchmark']}: mean speedup {gm_new:.2f}x < "
                f"{gm_base * (1 - tolerance):.2f}x (baseline "
                f"{gm_base:.2f}x - {tolerance:.0%})")
    for e in report["entries"]:
        b = base_by_n.get(e["n"])
        if b is None:
            continue
        abs_tol = min(1.0, 2 * tolerance)
        lim = b["events_per_sec"] * (1 - abs_tol)
        if e["events_per_sec"] < lim:
            msg = (f"{report['benchmark']} N={e['n']}: "
                   f"{e['events_per_sec']:.0f} ev/s < {lim:.0f} ev/s "
                   f"(baseline {b['events_per_sec']:.0f} - {abs_tol:.0%})")
            if same_host:
                failures.append(msg)
            else:
                log(f"[warn, different host — not gated] {msg}")
    return failures


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="compare against the committed BENCH files and "
                         "exit non-zero on regression")
    ap.add_argument("--events", type=int, default=512,
                    help="events per measured scan (m4; flowsim_fast uses "
                         "half — its per-event cost is ~10x higher)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--tolerance", type=float, default=0.2,
                    help="allowed fractional regression (default 0.2)")
    ap.add_argument("--out-dir", default=REPO_ROOT,
                    help="where BENCH_*.json live")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of benchmarks to run "
                         "(m4, flowsim_fast, serve, accuracy; default: all)")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import use_persistent_cache
    use_persistent_cache()

    benches = {
        "BENCH_m4.json": ("m4", lambda: measure_m4(
            events=args.events, reps=args.reps)),
        "BENCH_flowsim_fast.json": ("flowsim_fast", lambda:
            measure_flowsim_fast(events=max(32, args.events // 2),
                                 reps=args.reps)),
        "BENCH_serve.json": ("serve", lambda: measure_serve(reps=args.reps)),
        "BENCH_accuracy.json": ("accuracy", lambda: measure_accuracy()),
    }
    only = {s for s in args.only.split(",") if s}
    unknown = only - {name for name, _ in benches.values()}
    if unknown:
        ap.error(f"unknown benchmark(s) {sorted(unknown)}")
    reports = {fname: fn() for fname, (name, fn) in benches.items()
               if not only or name in only}
    failures = []
    for fname, report in reports.items():
        report["host"] = _host_info()
        report["measured_unix_time"] = int(time.time())
        report["obs"] = _obs_snapshot(report)
        path = os.path.join(args.out_dir, fname)
        if args.check:
            if not os.path.exists(path):
                failures.append(f"missing committed baseline {fname}")
                continue
            with open(path) as fh:
                baseline = json.load(fh)
            checker = {"serve": check_serve,
                       "accuracy": check_accuracy}.get(
                report["benchmark"], check)
            failures += checker(report, baseline, args.tolerance)
        else:
            with open(path, "w") as fh:
                json.dump(report, fh, indent=2, sort_keys=True)
                fh.write("\n")
            print(f"wrote {path}")
    if args.check:
        if failures:
            for f in failures:
                print(f"PERF GATE FAIL: {f}", file=sys.stderr)
            return 1
        print("perf gate OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
