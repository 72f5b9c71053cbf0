"""ASTRA-sim-style integration (paper §2.1): estimate the communication
time of a compiled LM training step by converting its collective schedule
into network flows and simulating them with flowSim and m4.

Pipeline: dry-run JSON (collective bytes by kind, parsed from the compiled
HLO of an assigned arch) -> ring-schedule flows on a fat-tree hosting the
data-parallel ranks -> flow-level simulation -> per-collective completion
time, vs. the analytic alpha-beta lower bound.

  PYTHONPATH=src python examples/simulate_collectives.py \
      --cell results/dryrun/gemma2-9b_train_4k_16x16.json --ranks 16
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import glob
import json

import numpy as np

from benchmarks.common import trained_m4
from repro.net.packetsim import Flow, NetConfig
from repro.net.topology import FatTree
from repro.sim import SimRequest, get_backend


def ring_flows(topo, ranks, bytes_per_rank, start=0.0):
    """One ring pass: rank i -> rank i+1, `bytes_per_rank` each."""
    hosts = np.linspace(0, topo.num_hosts - 1, ranks).astype(int)
    flows = []
    for i in range(ranks):
        src, dst = int(hosts[i]), int(hosts[(i + 1) % ranks])
        flows.append(Flow(fid=i, src=src, dst=dst,
                          size=max(int(bytes_per_rank), 1000),
                          t_arrival=start, path=topo.path(src, dst, i)))
    return flows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default=None,
                    help="dry-run JSON (default: first train cell found)")
    ap.add_argument("--ranks", type=int, default=16)
    args = ap.parse_args()
    from repro.runtime.compile_cache import use_persistent_cache
    use_persistent_cache()

    cell = args.cell or sorted(
        glob.glob("results/dryrun/*train_4k_16x16.json"))[0]
    rec = json.load(open(cell))
    print(f"[collectives] {rec['arch']} {rec['shape']}: "
          f"{rec['collective_ops']} collective ops in compiled HLO")

    topo = FatTree(num_racks=8, hosts_per_rack=4, num_spines=4,
                   link_gbps=100.0)  # ICI-class links
    config = NetConfig(cc="dctcp")
    params, m4cfg = trained_m4()

    print("collective, bytes_dev, t_alpha_beta_us, t_flowsim_us, t_m4_us")
    n = args.ranks
    for kind, nbytes in rec["collective_kinds"].items():
        # ring schedule: all-reduce moves 2(n-1)/n per rank, others (n-1)/n
        factor = 2.0 if kind == "all-reduce" else 1.0
        per_rank = factor * (n - 1) / n * nbytes
        steps = factor * (n - 1)
        chunk = nbytes / n
        flows = ring_flows(topo, n, per_rank)
        # alpha-beta: steps * (alpha + chunk/bw)
        bw = topo.link_gbps * 1e9 / 8
        t_ab = steps * (2e-6 + chunk / bw)
        req = SimRequest(topo=topo, config=config, flows=tuple(flows))
        fs = get_backend("flowsim").run(req)
        m4 = get_backend("m4", params=params, cfg=m4cfg).run(req)
        print(f"{kind}, {nbytes/1e6:.1f}MB, {t_ab*1e6:.0f}, "
              f"{np.nanmax(fs.fcts)*1e6:.0f}, {np.nanmax(m4.fcts)*1e6:.0f}")
    print("[collectives] flowSim models contention the alpha-beta bound "
          "misses; m4 adds learned queueing/CC effects on top.")


if __name__ == "__main__":
    main()
