"""flowSim cells: `repro.sim.get_backend("flowsim_fast").run` on the mix's
scenario, checked against `flowsim_ref`, the plain reference.

flowSim's work per event is the number of water-filling rounds times a
fixed dense cost, and the round count follows the order in which flows
of each size arrive. So a run seed that drew a new trajectory, as
`bench.traffic.scenario` does for m4's cells, would draw new work. Here
one trajectory is fixed by the mix's `perm_seed` (sizes over flows and
the order of the gaps, as `bench.traffic` permutes them), and the run
seed draws a relabelling of it:

- an automorphism of the fat-tree: a permutation of the racks, of the
  hosts in each rack and of the spines, applied to every flow's source,
  destination and path;
- a permutation of the flow ids.

Arrival times, sizes and path shapes stay. Every seed then takes the same
events with the same rounds, while the seed decides which rows and
columns of the program's incidence hold which flow and link. The
reference runs on the same relabelled arrays.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import traffic
from bench.systems import common, flowsim_ref


def link_map(racks: int, hpr: int, spines: int, rng) -> tuple:
    """(host map, link map) of a random automorphism of the fat-tree, in
    `bench.traffic.ecmp_path`'s link numbering: host uplinks [0, H),
    host downlinks [H, 2H), ToR-to-spine links, then spine-to-ToR links,
    each block rack-major."""
    H = racks * hpr
    rack = rng.permutation(racks)
    in_rack = np.stack([rng.permutation(hpr) for _ in range(racks)])
    spine = rng.permutation(spines)
    host = (rack[:, None] * hpr + in_rack).reshape(-1)
    tor = (rack[:, None] * spines + spine[None, :]).reshape(-1)
    links = np.concatenate([host, H + host, 2 * H + tor,
                            2 * H + racks * spines + tor])
    return host, links


def relabel(scen: traffic.Scenario, rng) -> traffic.Scenario:
    """The scenario under a random fat-tree automorphism and flow-id
    permutation drawn from `rng`. Flow i becomes flow `perm[i]`, so flows
    are no longer numbered in arrival order."""
    host, links = link_map(scen.racks, scen.hosts_per_rack, scen.spines, rng)
    perm = rng.permutation(scen.num_flows)
    inv = np.argsort(perm)                    # new id -> old id
    return dataclasses.replace(
        scen, src=host[scen.src][inv], dst=host[scen.dst][inv],
        size=scen.size[inv], t_arrival=scen.t_arrival[inv],
        paths=[[int(links[l]) for l in scen.paths[i]] for i in inv])


class Cell:
    """One flowSim cell: each call runs the relabelled scenario through
    the backend."""

    rate_metric = "events_per_s"

    def __init__(self, config: dict, mix: dict, seed: int):
        self.config, self.seed = config, seed
        base = traffic.scenario(mix, int(mix["perm_seed"]))
        self.scen = relabel(base, np.random.default_rng([seed, 20]))
        self.events_per_call = 2 * self.scen.num_flows

    def setup(self):
        from repro.sim import get_backend
        self.backend = get_backend("flowsim_fast")
        self.request = common.to_request(self.scen)
        self.call()                       # compiles or reads the cache

    def call(self) -> np.ndarray:
        """One call of the timed path: the scenario's FCTs (numpy)."""
        return np.asarray(self.backend.run(self.request).fcts)

    def reference(self, precision: str) -> np.ndarray:
        fcts, self.ref_stats = flowsim_ref.simulate(self.scen, precision)
        return fcts

    unfinished = staticmethod(common.unfinished)

    def check(self, sample: np.ndarray, outputs: list) -> dict:
        """Numbers compared for `correct`: the mean relative FCT gap of the
        sampled call against the reference at the configuration's
        precision, and the flows left unfinished in any call of the
        window."""
        ref = self.reference(self.config["correct"]["precision"])
        return {"fct_gap_mean": common.fct_gap_mean(sample, ref),
                "unfinished": sum(map(common.unfinished, outputs))
                + common.unfinished(ref)}
