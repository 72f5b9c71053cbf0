#!/usr/bin/env python3
"""Readings behind a cell's correctness limits, for many seeds in one
process (the runs of the benchmark itself never run this).

    python bench/control.py --workload <cell> --seeds 1 2 3 ...

For each seed the cell is set up as a run sets it up (weights and
scenario from the seed) and the timed path is called once. Its answer is
compared with the plain reference at the configuration's precision by the
cell's own `check`: the program's readings. The control is the reference
computed one precision lower (`correct.control` in the configuration:
`high` for m4's `highest`), put in the program's place and compared the
same way: the control's readings. Both are judged by the harness's own
comparison (`bench.run.judge`) against the configuration's limits.

One JSON line per seed, then a summary line with the largest program
reading and the smallest control reading of each number, and the limits. Exits non-zero when
a program reading is not correct or a control reading is; and without a
TPU, unless `--cpu` is given.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(root: str, workload: str, seeds, log=print) -> dict:
    """Per seed the program's and the control's numbers compared, as the
    cell's own `check` gives them, and whether each side is correct; and
    the configuration's limits."""
    for p in (os.path.join(root, "src"), root):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import traffic
    from bench.run import judge, load_module, read_json
    bench = read_json(root, "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[workload]
    config = read_json(root, "bench", "configs", cell["config"] + ".json")
    mix = traffic.load_mix(root, cell["traffic"])
    system = load_module(os.path.join(root, "bench", "systems",
                                      config["system"] + ".py"),
                         "bench.systems." + config["system"])
    out = {"program": [], "control": [], "program_correct": [],
           "control_correct": [], "limits": config["correct"]["limits"]}
    for seed in seeds:
        t0 = time.perf_counter()
        sut = system.Cell(config, mix, seed)
        sut.setup()
        t1 = time.perf_counter()
        answer = sut.call()
        t2 = time.perf_counter()
        low = sut.reference(config["correct"]["control"])
        t3 = time.perf_counter()
        row = {"workload": workload, "seed": seed}
        for side, fcts in (("program", answer), ("control", low)):
            checks = dict(sut.check(fcts, [fcts]), compiles_in_window=0)
            ok, _ = judge(checks, config)
            out[side].append(checks)
            out[side + "_correct"].append(ok)
            row[side], row[side + "_correct"] = checks, ok
        log(json.dumps(dict(row, reference=dict(sut.ref_stats),
                            setup_s=t1 - t0, call_s=t2 - t1,
                            control_s=t3 - t2)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--cpu", action="store_true",
                    help="allow a run without a TPU (rehearsal only)")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax
    if not args.cpu and jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    r = readings(ROOT, args.workload, args.seeds,
                 log=lambda s: print(s, flush=True))
    print(json.dumps({"workload": args.workload, "limits": r["limits"],
                      "program_max": {k: max(c[k] for c in r["program"])
                                      for k in r["limits"]},
                      "control_min": {k: min(c[k] for c in r["control"])
                                      for k in r["limits"]},
                      "device": jax.devices()[0].device_kind}))
    return int(not all(r["program_correct"]) or any(r["control_correct"]))


if __name__ == "__main__":
    sys.exit(main())
