#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from `BENCHMARK.json`: the cell (`workloads`)
names a configuration (`bench/configs/<config>.json`, whose `"system"`
names the module `bench/systems/<system>.py`) and a traffic mix
(`bench/traffic/<traffic>.json`); each per-layer metric is read by
`bench/metrics/<metric>.py`. Adding a cell adds files and entries only.

Set-up (process start to the window: JAX and the chip, weights and
scenarios from the seed, one call of the timed path to compile or read
the persistent compile cache kept in `<checkout>/.jax_cache`) is
`setup_s`. The window then calls the timed path until `--seconds` have
passed; its rate is all the events of all its calls over all its time.
Compiles inside the window are counted. After the window the peak device
memory is read, and one of the window's answers, drawn by the seed, is
compared with the plain reference (`correct`). With `--trace 1` the first
call of the window runs under the profiler and the per-layer metrics and
`breakdown` come from that trace.

The last line of standard output is one JSON object (`correct`,
`attempted`, `failed`, `metrics`, `device`, with `--trace 1` also
`breakdown`, and `checks`: each number compared, with its limit). Exits
non-zero, printing no result, without a TPU, with fewer chips than the
cell asks for, or outside a checkout of the program.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return 1


def profile_options(jax):
    """Device ops and the benchmark's own host spans only: no Python
    tracer, host annotations at level 1, no HLO protos."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def cell_metrics(bench: dict, section: str, cell: str) -> list:
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def main(argv=None, root: str = ROOT, require_tpu: bool = True,
         t0: float = T0) -> int:
    args = parse_args(argv)
    bench = read_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        return fail(f"no workload {args.workload!r} in BENCHMARK.json")
    cell = cells[args.workload]
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        return fail(f"no program under {root}/src: run from a checkout")
    for p in (os.path.join(root, "src"), root):
        if p not in sys.path:
            sys.path.insert(0, p)
    # the compile cache lives in the checkout, at a fixed path (the path is
    # part of the cache key); the program reads the same variable
    cache = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.makedirs(cache, exist_ok=True)

    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        return fail(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < cell["chips"]:
        return fail(f"{args.workload} needs {cell['chips']} chips, JAX sees "
                    f"{len(devices)}")

    from bench import traffic
    config = read_json(root, "bench", "configs", cell["config"] + ".json")
    mix = traffic.load_mix(root, cell["traffic"])
    system = load_module(os.path.join(root, "bench", "systems",
                                      config["system"] + ".py"),
                         "bench.systems." + config["system"])
    sut = system.Cell(config, mix, args.seed)
    sut.setup()
    setup_s = time.perf_counter() - t0

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if name == COMPILE_EVENT else None)
    outputs, trace_dir, traced = [], None, None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    start = time.perf_counter()
    while True:
        if trace_dir and not outputs:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=profile_options(jax))
        with jax.profiler.TraceAnnotation("bench.run"):
            outputs.append(sut.call())
        if trace_dir and len(outputs) == 1:
            jax.profiler.stop_trace()
            traced = sut.events_per_call
        if time.perf_counter() - start >= args.seconds:
            break
    elapsed = time.perf_counter() - start
    compiles_in_window = len(compiles)

    stats = devices[0].memory_stats() or {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}

    result = {"correct": None, "attempted": len(outputs),
              "failed": sum(int(sut.unfinished(o) > 0) for o in outputs)}
    if args.trace:
        metrics, breakdown = traced_metrics(root, bench, cell, config,
                                            trace_dir, traced, device)
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["breakdown"] = breakdown
    else:
        values = {sut.rate_metric: len(outputs) * sut.events_per_call
                  / elapsed, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(bench, "end_to_end", cell["name"])}
    result["metrics"] = metrics
    result["device"] = device

    pick = int(np.random.default_rng([args.seed, 7]).integers(len(outputs)))
    checks = sut.check(outputs[pick], outputs)
    checks["compiles_in_window"] = compiles_in_window
    result["correct"], result["checks"] = judge(checks, config)
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


def judge(checks: dict, config: dict) -> tuple:
    """`correct`, and each number compared beside its limit: the
    configuration's limits, and no compile inside the window."""
    limits = dict(config["correct"]["limits"], compiles_in_window=0)
    return (all(checks[k] <= limits[k] for k in limits),
            {k: {"value": checks[k], "limit": limits[k]} for k in limits})


def traced_metrics(root, bench, cell, config, trace_dir, events, device):
    from bench import flops, trace
    t = trace.load(trace.find_xplane(trace_dir))
    busy = trace.busy_s(t)
    device["busy_s"] = busy
    device["window_s"] = t.window_s
    peaks = read_json(root, "bench", "peaks.json")["devices"]
    if device["kind"] not in peaks and device["platform"] == "tpu":
        raise KeyError(f"no peaks for device kind {device['kind']!r} in "
                       "bench/peaks.json")
    ctx = {"trace": t, "events": events, "busy_s": busy,
           "window_s": t.window_s, "config": config,
           "peak": peaks.get(device["kind"]), "flops": flops}
    metrics = {}
    for m in cell_metrics(bench, "per_layer", cell["name"]):
        reader = load_module(os.path.join(root, "bench", "metrics",
                                          m["name"] + ".py"),
                             "bench.metrics." + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, {"device_ops": trace.top_ops(t),
                     "idle_gaps": trace.idle_gaps(t)}


if __name__ == "__main__":
    sys.exit(main())
