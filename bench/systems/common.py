"""What cells of the program's `repro.sim` backends share: turning the
benchmark's scenario into the program's request, and comparing per-flow
completion times with a reference.
"""
from __future__ import annotations

import numpy as np

from bench import traffic


def to_request(scen: traffic.Scenario):
    """The program's `SimRequest` for one generated scenario."""
    from repro.net.packetsim import Flow, NetConfig
    from repro.net.topology import FatTree
    from repro.sim import SimRequest
    topo = FatTree(num_racks=scen.racks, hosts_per_rack=scen.hosts_per_rack,
                   num_spines=scen.spines, link_gbps=scen.link_gbps,
                   prop_delay_s=scen.prop_delay_s)
    net = dict(scen.net)
    config = NetConfig(cc=net.pop("cc"), **net)
    flows = tuple(Flow(fid=i, src=int(scen.src[i]), dst=int(scen.dst[i]),
                       size=int(scen.size[i]),
                       t_arrival=float(scen.t_arrival[i]),
                       path=list(scen.paths[i]))
                  for i in range(scen.num_flows))
    return SimRequest(topo=topo, config=config, flows=flows)


def unfinished(fcts: np.ndarray) -> int:
    return int(np.sum(~np.isfinite(fcts) | (fcts <= 0)))


def fct_gap_mean(prog: np.ndarray, ref: np.ndarray) -> float:
    """Mean over flows of the relative FCT gap |prog - ref| / ref.

    Both sides keep time as float32 instants, so an FCT is known to one
    spacing of its completion instant: some 2.5e-6 of the shortest flows'
    FCTs. The widest gap over flows reads that rounding, on the program
    and on a control one precision lower alike; the mean reads how many
    flows moved, and by how much."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    gap = np.abs(prog - ref) / np.abs(ref)
    return float(np.mean(np.where(np.isfinite(gap), gap, np.inf)))
