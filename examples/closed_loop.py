"""Closed-loop interactive application (paper §5.4).

Client racks keep at most N requests inflight to storage racks; each
completion releases the next request. Throughput (completed flows/sec) is
compared across the packet-level ground truth, flowSim, and m4 — the
regime where flowSim's missing queueing/CC dynamics compound, because
errors feed back into arrival times. All three run through the same
`repro.sim` closed-loop session protocol:

    run_closed_loop(get_backend("m4", params=p, cfg=c), topo, cfg, backlog, N)

  PYTHONPATH=src python examples/closed_loop.py [--racks 8] [--limits 1 3 5]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from benchmarks.common import trained_m4
from repro.core.closedloop import make_backlog
from repro.net.packetsim import NetConfig
from repro.net.topology import FatTree
from repro.sim import get_backend, run_closed_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--racks", type=int, default=8)
    ap.add_argument("--flows-per-rack", type=int, default=30)
    ap.add_argument("--limits", type=int, nargs="+", default=[1, 3, 5])
    args = ap.parse_args()
    from repro.runtime.compile_cache import use_persistent_cache
    use_persistent_cache()

    topo = FatTree(num_racks=args.racks, hosts_per_rack=4, num_spines=2)
    config = NetConfig(cc="dctcp")
    params, m4cfg = trained_m4()
    backlog = make_backlog(topo, client_racks=max(args.racks // 4, 1),
                           flows_per_rack=args.flows_per_rack,
                           size_dist="WebServer", seed=7)

    backends = [get_backend("packet"), get_backend("flowsim"),
                get_backend("m4", params=params, cfg=m4cfg)]

    print("N, thr_ns3(f/s), thr_flowsim, thr_m4, err_flowsim, err_m4")
    errs_fs, errs_m4 = [], []
    for N in args.limits:
        gt, fs, m4 = (run_closed_loop(b, topo, config, backlog, N)
                      for b in backends)
        e_fs = abs(fs.throughput - gt.throughput) / gt.throughput
        e_m4 = abs(m4.throughput - gt.throughput) / gt.throughput
        errs_fs.append(e_fs)
        errs_m4.append(e_m4)
        print(f"{N}, {gt.throughput:.0f}, {fs.throughput:.0f}, "
              f"{m4.throughput:.0f}, {e_fs:.1%}, {e_m4:.1%}")
    print(f"\nmean throughput error: flowSim {np.mean(errs_fs):.1%}, "
          f"m4 {np.mean(errs_m4):.1%} (paper: 28.1% -> 11.5%)")


if __name__ == "__main__":
    main()
