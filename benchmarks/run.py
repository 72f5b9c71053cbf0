"""Benchmark driver: one function per paper table + roofline summary.
Prints `name,us_per_call,derived` CSV lines at the end for harness parsing.

  PYTHONPATH=src python -m benchmarks.run [--fast]
"""
from __future__ import annotations

import argparse
import glob
import json
import time

import numpy as np


def _timeit(fn, *a, **kw):
    t0 = time.perf_counter()
    out = fn(*a, **kw)
    return out, (time.perf_counter() - t0) * 1e6


def roofline_summary(log=print):
    """Render §Roofline table from results/roofline/*.json."""
    rows = []
    for p in sorted(glob.glob("results/roofline/*.json")):
        r = json.load(open(p))
        if r.get("skipped"):
            continue
        rows.append(r)
    if not rows:
        log("(roofline results not generated yet — run "
            "`python -m repro.launch.roofline --all`)")
        return rows
    log("arch, shape, t_compute_s, t_memory_s, t_collective_s, dominant, "
        "useful_ratio, roofline_fraction")
    for r in rows:
        log(f"{r['arch']}, {r['shape']}, {r['t_compute_s']:.3e}, "
            f"{r['t_memory_s']:.3e}, {r['t_collective_s']:.3e}, "
            f"{r['dominant']}, {r['useful_ratio']:.2f}, "
            f"{r['roofline_fraction']:.3f}")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller flow counts (CI)")
    args = ap.parse_args()
    from repro.runtime.compile_cache import use_persistent_cache
    use_persistent_cache()
    n = 150 if args.fast else 300

    from . import (table1_flowsim_vs_ns3, table3_accuracy, table4_scaling,
                   table5_ablation)

    csv = []
    print("\n========== Table 1: flowSim vs packet-level ==========")
    rows, us = _timeit(table1_flowsim_vs_ns3.run, num_flows=n)
    csv.append(("table1_flowsim_vs_ns3", us,
                f"mean_err={np.mean([r['err_mean'] for r in rows]):.3f}"))

    print("\n========== Table 3: m4 vs flowSim accuracy ==========")
    rows, us = _timeit(table3_accuracy.run, num_flows=n)
    m4m = np.mean([r["m4_mean"] for r in rows])
    fsm = np.mean([r["flowsim_mean"] for r in rows])
    csv.append(("table3_accuracy", us,
                f"m4={m4m:.3f}_flowsim={fsm:.3f}_red={(1-m4m/fsm):.0%}"))

    print("\n========== Table 4: runtime scaling ==========")
    rows, us = _timeit(table4_scaling.run,
                       sizes=((8, 4), (16, 8), (32, 8)) if args.fast
                       else ((8, 4), (16, 8), (32, 8), (64, 16)))
    csv.append(("table4_scaling", us, f"largest_hosts={rows[-1]['hosts']}"))

    print("\n========== Table 5: dense-supervision ablation ==========")
    rows, us = _timeit(table5_ablation.run,
                       n_train=6 if args.fast else 12, n_eval=2)
    csv.append(("table5_ablation", us,
                f"full={rows[0]['mean']:.3f}_wo_size={rows[1]['mean']:.3f}"
                f"_wo_queue={rows[2]['mean']:.3f}"))

    print("\n========== Roofline (from dry-run artifacts) ==========")
    rows, us = _timeit(roofline_summary)
    csv.append(("roofline_summary", us, f"cells={len(rows)}"))

    print("\nname,us_per_call,derived")
    for name, us, derived in csv:
        print(f"{name},{us:.0f},{derived}")


if __name__ == "__main__":
    main()
