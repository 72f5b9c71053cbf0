#!/usr/bin/env python3
"""Run m4 end to end on a TPU at the paper's model widths.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: only the sharded paths

One chip (no arguments), each phase through the public API, with random
weights from `init_m4(PRNGKey(SEED), M4Config())` (hidden 400, GNN 300,
MLP 200, 3 GNN layers, 64/128 snapshot slots) and scenarios built from
`repro.scenarios` specs and seeds:

  m4_single     one ft-32x16x8 scenario of 4096 flows through
                `get_backend("m4").run`: the kernels must resolve to
                compiled Pallas (never the interpreter), every flow must
                finish with a finite FCT, and the same request at
                `kernel_mode="xla"` must agree.
  m4_batched    `run_many` on 8 Table-2 scenarios of 1500 flows against
                a per-scenario `run` of each.
  flowsim_fast  the device flowSim on one of them against numpy flowSim.
  serve         a `SimService` m4 lane answers 8 concurrent requests of
                2 shapes, twice: nothing fails, no request is retried
                alone, the second round compiles nothing, and every
                answer equals a direct `run_many`.
  train         three data-parallel `fit` steps on a 2-scenario corpus
                built inline: finite losses, parameters that move.

Four chips (`--chips 4`): the sharded m4 and flowsim_fast sweeps
(`jax.pmap` over scenarios) and the data-parallel training step, each
against the single-device vmapped program on the same batch.

The walls printed are smoke timings, not benchmark numbers. The script
exits non-zero, printing no result, when it runs outside a checkout, when
JAX finds no TPU, or when any check fails. Otherwise the last line of
standard output is
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import threading
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
SEED = 0
# scenario sizes: the single m4 request, the batched sweep, the two serve
# shapes, and the training corpus (flows per scenario, events per sim)
SINGLE = dict(topo="ft-32x16x8", num_flows=4096, seed=1, max_load=0.5)
BATCH_FLOWS = 1500
SERVE_FLOWS = (1024, 1536)
TRAIN_FLOWS, TRAIN_EVENTS = 200, 256

# Tolerances. Two FCT vectors agree when, flow by flow,
#     |a - b| <= rtol * |b| + CLOCK_ULPS * eps32 * t_end
# FCTs are differences of float32 absolute times, so each carries an
# absolute error of a few ulps of the simulated clock (t_end: the
# scenario's last completion): about 1e-10 s at 1 ms into a trace, which
# is 1e-3 of a 100 ns flow. The rtol of each pair is the bound the CPU
# suite holds it to:
# m4 pallas vs xla (tests/test_simulate_incremental.py, interpret vs xla)
M4_MODES_RTOL = 1e-4
# m4 run_many (padded, vmapped) vs run (tests/test_sim_api.py); also the
# sharded sweep vs the one-chip vmapped program
M4_BATCH_RTOL = 2e-4
# flowsim_fast vs numpy flowSim (tests/test_flowsim_fast.py): float32
# device rates vs float64 numpy; also sharded vs one-chip
FLOWSIM_RTOL = 1e-4
CLOCK_ULPS = 4
# data-parallel training step vs the one-chip step, on loss and gradient
# (relative L2): f32 sums added in another order differ by about 1e-6; a
# sharding fault (a padded lane weighted, a sum not normalized) moves them
# by 1/B or more
TRAIN_SHARDED_RTOL = 1e-3


def log(msg: str):
    print(f"[smoke] {msg}", flush=True)


def rel_diff(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.abs(a - b) / np.maximum(np.abs(b), 1e-30)


class Smoke:
    """Phase runner: records failed checks and keeps going, so one run
    reports every phase."""

    def __init__(self):
        self.failures = []

    def check(self, ok, what: str):
        if not ok:
            self.failures.append(what)
            log(f"CHECK FAILED: {what}")

    def close(self, name: str, a, b, rtol: float, req):
        """Compare two FCT vectors of request `req` (see Tolerances)."""
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        t_end = float(np.max(b + [f.t_arrival for f in req.flows]))
        atol = CLOCK_ULPS * float(np.finfo(np.float32).eps) * t_end
        d = rel_diff(a, b)
        bad = np.abs(a - b) > rtol * np.abs(b) + atol
        w = int(np.argmax(d))
        log(f"{name}: max_rel={d.max():.3e} p50_rel={np.median(d):.3e} "
            f"p99_rel={np.percentile(d, 99):.3e} worst: fct={b[w]:.4e}s "
            f"|diff|={abs(a[w] - b[w]):.3e}s; {int(bad.sum())}/{d.size} "
            f"outside rtol {rtol:g} + atol {atol:.2e}s")
        self.check(not bad.any(), f"{name}: {int(bad.sum())} flow(s) "
                                  f"outside rtol {rtol:g} + atol {atol:.2e}s")

    def phase(self, name: str, fn, *args):
        log(f"phase {name}")
        t0 = time.perf_counter()
        try:
            fn(self, *args)
        except Exception:
            self.failures.append(f"{name} raised")
            log(f"phase {name} raised:\n{traceback.format_exc()}")
        log(f"phase {name} wall_s={time.perf_counter() - t0:.3f}")


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def cold_warm(s: Smoke, name: str, fn):
    """Call twice: the first call compiles (or reads the persistent
    cache), the second is steady. Returns the steady result."""
    first, cold = timed(fn)
    second, warm = timed(fn)
    log(f"{name}: cold_wall_s={cold:.3f} warm_wall_s={warm:.3f} "
        "(smoke timings, not benchmark numbers)")
    return first, second


def dispatch_counts():
    from repro.kernels import dispatch
    from repro.obs.registry import get_registry, labeled
    counters = get_registry().snapshot()["counters"]
    return {m: counters.get(labeled("kernels.dispatch", mode=m), 0)
            for m in dispatch.MODES}


def check_dispatch(s: Smoke, name: str, before: dict):
    after = dispatch_counts()
    delta = {m: after[m] - before[m] for m in after}
    log(f"{name}: kernels.dispatch resolutions {delta}")
    s.check(delta["pallas"] > 0, f"{name}: no pallas resolution")
    s.check(delta["interpret"] == 0,
            f"{name}: {delta['interpret']} interpret resolution(s)")


def table2_requests(n=8):
    from repro.scenarios import get_suite
    return [spec.to_request()
            for spec in get_suite("table2_train_space", n=n,
                                  num_flows=BATCH_FLOWS)]


# ---------------------------------------------------------------- one chip
def phase_m4_single(s: Smoke, params, cfg):
    from repro.scenarios import ScenarioSpec
    from repro.sim import get_backend
    req = ScenarioSpec(**SINGLE).to_request()
    before = dispatch_counts()
    m4 = get_backend("m4", params=params, cfg=cfg)
    s.check(m4.cfg.kernel_mode == "pallas",
            f"m4 resolved kernel_mode {m4.cfg.kernel_mode!r}, not pallas")
    first, res = cold_warm(s, "m4_single pallas", lambda: m4.run(req))
    check_dispatch(s, "m4_single", before)
    log(f"m4_single: {req.num_flows} flows, {req.topo.num_links} links, "
        f"{2 * req.num_flows} events")
    s.check(np.isfinite(res.fcts).all(), "m4_single: non-finite FCT")
    s.check((res.fcts > 0).all(),
            f"m4_single: {(res.fcts <= 0).sum()} flow(s) never finished")
    s.check(np.array_equal(first.fcts, res.fcts),
            "m4_single: two runs of one request differ")
    xla = get_backend("m4", params=params,
                      cfg=dataclasses.replace(cfg, kernel_mode="xla"))
    _, res_x = cold_warm(s, "m4_single xla", lambda: xla.run(req))
    s.close("m4_single pallas vs xla", res.fcts, res_x.fcts, M4_MODES_RTOL,
            req)


def phase_m4_batched(s: Smoke, params, cfg):
    from repro.sim import get_backend
    reqs = table2_requests()
    m4 = get_backend("m4", params=params, cfg=cfg)
    _, batched = cold_warm(s, "m4_batched run_many B=8",
                           lambda: m4.run_many(reqs))
    looped, wall = timed(lambda: [m4.run(r) for r in reqs])
    log(f"m4_batched: looped run of 8 (compiles each shape) "
        f"wall_s={wall:.3f} (smoke timings, not benchmark numbers)")
    for i, (r, b, l) in enumerate(zip(reqs, batched, looped)):
        s.check(np.isfinite(b.fcts).all() and (b.fcts > 0).all(),
                f"m4_batched[{i}]: unfinished or non-finite FCT")
        s.close(f"m4_batched[{i}] run_many vs run", b.fcts, l.fcts,
                M4_BATCH_RTOL, r)


def phase_flowsim_fast(s: Smoke):
    from repro.sim import get_backend
    req = table2_requests(n=1)[0]
    before = dispatch_counts()
    fast = get_backend("flowsim_fast")
    _, res = cold_warm(s, "flowsim_fast", lambda: fast.run(req))
    check_dispatch(s, "flowsim_fast", before)
    ref, wall = timed(lambda: get_backend("flowsim").run(req))
    log(f"flowsim (numpy, host) wall_s={wall:.3f}")
    s.close("flowsim_fast vs numpy flowsim", res.fcts, ref.fcts,
            FLOWSIM_RTOL, req)


def phase_serve(s: Smoke, params, cfg):
    from repro.scenarios import ScenarioSpec
    from repro.serve import ServeConfig, SimService
    from repro.sim import get_backend
    # two shapes (two flow counts on the 2-to-1 paper topology), four
    # requests each: every bucket fills exactly one batch of 4
    reqs = [ScenarioSpec(name=f"serve-{n}-{i}", oversub="2-to-1",
                         num_flows=n, seed=900 + i).to_request()
            for n in SERVE_FLOWS for i in range(4)]
    m4 = get_backend("m4", params=params, cfg=cfg)
    # a long flush interval: buckets flush full, so both rounds present
    # the same batches (and the same padded arenas) to run_many
    config = ServeConfig(batch_size=4, flush_interval_s=30.0)

    def round_trip(service):
        futures = [None] * len(reqs)
        go = threading.Barrier(len(reqs))

        def client(i):
            go.wait()
            futures[i] = service.submit(reqs[i])
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(reqs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return [f.result(timeout=600) for f in futures]

    with SimService(m4, config=config) as service:
        first, cold = timed(lambda: round_trip(service))
        c1 = service.metrics()["compiles"]
        second, warm = timed(lambda: round_trip(service))
        m = service.metrics()
    log(f"serve: 8 requests x 2 rounds, cold_wall_s={cold:.3f} "
        f"warm_wall_s={warm:.3f} (smoke timings, not benchmark numbers)")
    log(f"serve: compiles first round={c1} second round="
        f"{m['compiles'] - c1} batches={m['batches']} "
        f"failed={m['failed']} isolated_retries={m['isolated_retries']}")
    s.check(m["failed"] == 0, f"serve: {m['failed']} failed request(s)")
    s.check(m["isolated_retries"] == 0,
            f"serve: {m['isolated_retries']} request(s) re-run alone after "
            "a batch failed")
    s.check(m["compiles"] - c1 == 0,
            f"serve: {m['compiles'] - c1} compile(s) after the first flush")
    s.check(m["completed"] == 2 * len(reqs),
            f"serve: {m['completed']} of {2 * len(reqs)} completed")
    direct = m4.run_many(reqs[:4]) + m4.run_many(reqs[4:])
    for i, (a, b, d) in enumerate(zip(first, second, direct)):
        s.check(np.array_equal(a.fcts, d.fcts)
                and np.array_equal(b.fcts, d.fcts),
                f"serve[{i}]: answer differs from direct run_many "
                f"(max_rel {rel_diff(a.fcts, d.fcts).max():.3e})")


def train_corpus(cfg, n: int):
    from repro.scenarios import ScenarioSpec
    from repro.train import build_dataset
    specs = [ScenarioSpec(name=f"train-{i}", num_flows=TRAIN_FLOWS,
                          seed=800 + i) for i in range(n)]
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        batches, _ = build_dataset(specs, cfg, root,
                                   max_events=TRAIN_EVENTS)
    return batches


def phase_train(s: Smoke, cfg):
    from repro.train import TrainConfig, fit, init_state
    batches = train_corpus(cfg, 2)
    tc = TrainConfig(epochs=3, step_mode="batch", bucket_size=2,
                     schedule="const", lr=1e-3, shuffle=False)
    h0 = init_state(cfg, tc.seed).weights_hash()
    state, hist = fit(batches, cfg, tc, log=log)
    losses = [e["loss"] for e in hist]
    log(f"train: steps={state.step} losses={losses} "
        f"compile_wall_s={hist[0]['compile_s']:.3f} steady_step_wall_s="
        f"{sum(e['step_s'] for e in hist):.3f} "
        "(smoke timings, not benchmark numbers)")
    s.check(state.step == 3, f"train: {state.step} steps, not 3")
    s.check(all(np.isfinite(losses)), f"train: non-finite loss {losses}")
    s.check(state.weights_hash() != h0, "train: parameters did not change")


# -------------------------------------------------------------- four chips
def phase_m4_sharded(s: Smoke, params, cfg):
    import jax
    from repro.core import simulate as sim
    from repro.sim import get_backend
    reqs = table2_requests()
    m4 = get_backend("m4", params=params, cfg=cfg)
    before = sim.TRACE_COUNTS["open_loop_sharded"]
    _, sharded = cold_warm(s, f"m4 run_many B=8 over "
                              f"{jax.local_device_count()} chips",
                           lambda: m4.run_many(reqs))
    s.check(sim.TRACE_COUNTS["open_loop_sharded"] > before,
            "m4_sharded: run_many did not take the sharded path")
    scen = [(r.topo, r.config, list(r.flows)) for r in reqs]
    static, order, times, l_max, _ = sim.stack_scenarios(m4.cfg, scen)
    fct, _ = sim._open_loop_scan_batched(params, m4.cfg, l_max, static,
                                         order, times)
    fct = np.asarray(fct)
    for i, (r, res) in enumerate(zip(reqs, sharded)):
        s.close(f"m4 sharded[{i}] vs one-chip vmap", res.fcts,
                fct[i, :r.num_flows], M4_BATCH_RTOL, r)


def phase_flowsim_fast_sharded(s: Smoke):
    from repro.core import flowsim_fast as ff
    from repro.kernels import dispatch
    from repro.sim import get_backend
    reqs = table2_requests()
    before = ff.TRACE_COUNTS["event_scan_sharded"]
    _, sharded = cold_warm(s, "flowsim_fast run_many B=8 sharded",
                           lambda: get_backend("flowsim_fast")
                           .run_many(reqs))
    s.check(ff.TRACE_COUNTS["event_scan_sharded"] > before,
            "flowsim_fast_sharded: run_many did not take the sharded path")
    stacked = ff.stack_scenarios([(r.topo, list(r.flows)) for r in reqs])
    fct_abs = np.asarray(ff._event_scan_batched(
        *stacked, mode=dispatch.resolve_mode()))
    for i, (r, res) in enumerate(zip(reqs, sharded)):
        arr = np.array([f.t_arrival for f in r.flows])
        s.close(f"flowsim_fast sharded[{i}] vs one-chip vmap", res.fcts,
                fct_abs[i, :r.num_flows] - arr, FLOWSIM_RTOL, r)


def phase_train_sharded(s: Smoke, cfg):
    import jax
    import jax.numpy as jnp
    from repro.train import TrainConfig, init_state
    from repro.train.batching import make_buckets
    from repro.train.loop import TRACE_COUNTS, make_bucket_step
    D = jax.local_device_count()
    tc = TrainConfig(step_mode="batch", bucket_size=D)
    (bucket,) = make_buckets(train_corpus(cfg, D), D)
    step = make_bucket_step(cfg, tc, lambda _: jnp.asarray(1e-3))
    state = init_state(cfg, tc.seed)
    before = TRACE_COUNTS["train_step_sharded"]
    (p_s, o_s, out_s), wall = timed(lambda: jax.block_until_ready(
        step(state.params, state.opt, bucket.arrays)))
    s.check(TRACE_COUNTS["train_step_sharded"] > before,
            "train_sharded: the step did not take the sharded path")
    p_1, o_1, out_1 = step.single_device(state.params, state.opt,
                                         bucket.arrays)
    log(f"train data-parallel step over {D} chips: cold_wall_s={wall:.3f} "
        f"loss={float(out_s[0, 0]):.6f} vs one-chip "
        f"{float(out_1[0, 0]):.6f} (smoke timings, not benchmark numbers)")
    d = rel_diff(np.asarray(out_s)[0, [0, 1, 2, 3, 5]],
                 np.asarray(out_1)[0, [0, 1, 2, 3, 5]])
    log(f"train sharded vs one-chip loss, heads, grad norm: max_rel="
        f"{d.max():.3e} (rtol {TRAIN_SHARDED_RTOL:g})")
    s.check(d.max() <= TRAIN_SHARDED_RTOL,
            f"train sharded loss/grad-norm max_rel {d.max():.3e}")
    # AdamW's first moment after one update is 0.1 x the clipped gradient
    m_s = np.concatenate([np.ravel(x) for x in jax.tree.leaves(o_s["m"])])
    m_1 = np.concatenate([np.ravel(x) for x in jax.tree.leaves(o_1["m"])])
    err = np.linalg.norm(m_s - m_1) / np.linalg.norm(m_1)
    log(f"train sharded vs one-chip gradient: rel_l2={err:.3e} "
        f"(rtol {TRAIN_SHARDED_RTOL:g})")
    s.check(err <= TRAIN_SHARDED_RTOL,
            f"train sharded gradient rel_l2 {err:.3e}")
    del p_s, p_1


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every phase on one chip; 4: only the "
                         "sharded paths, across four chips")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}: run this script "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.runtime.compile_cache import use_persistent_cache
    cache_dir = use_persistent_cache()

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU found (JAX runs on {backend!r}); this "
              "script runs only on the chip", file=sys.stderr)
        return 1
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 1
    dev = devices[0]
    log(f"jax {jax.__version__} on {len(devices)} x {dev.device_kind} "
        f"({dev.platform}); compile cache {cache_dir}")

    from repro.core.model import M4Config, init_m4
    cfg = M4Config()
    params = init_m4(jax.random.PRNGKey(SEED), cfg)
    s = Smoke()
    t0 = time.perf_counter()
    if args.chips == 1:
        s.phase("m4_single", phase_m4_single, params, cfg)
        s.phase("m4_batched", phase_m4_batched, params, cfg)
        s.phase("flowsim_fast", phase_flowsim_fast)
        s.phase("serve", phase_serve, params, cfg)
        s.phase("train", phase_train, cfg)
    else:
        s.phase("m4_sharded", phase_m4_sharded, params, cfg)
        s.phase("flowsim_fast_sharded", phase_flowsim_fast_sharded)
        s.phase("train_sharded", phase_train_sharded, cfg)
    log(f"total wall_s={time.perf_counter() - t0:.3f} "
        "(smoke timings, not benchmark numbers)")
    if s.failures:
        print("chip_smoke: FAILED:\n  " + "\n  ".join(s.failures),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
