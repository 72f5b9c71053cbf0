"""m4 cells: `repro.sim.get_backend("m4").run` on the mix's scenario, at
the configuration's model sizes and with the benchmark's own weights from
the seed; checked against `m4_ref`, the plain reference.

A system module (`bench/systems/<system>.py`, named by a configuration's
`"system"`) defines `Cell(config, mix, seed)` with `setup()`, `call()`,
`check(sample, outputs)`, `unfinished(answer)`, `rate_metric` and
`events_per_call`.
"""
from __future__ import annotations

import numpy as np

from bench import traffic
from bench.systems import common, m4_ref


class Cell:
    """One m4 cell: each call runs the scenario through the backend."""

    rate_metric = "events_per_s"

    def __init__(self, config: dict, mix: dict, seed: int):
        self.config, self.seed = config, seed
        self.scen = traffic.scenario(mix, seed)
        self.events_per_call = 2 * self.scen.num_flows

    def setup(self):
        from repro.core.model import M4Config
        from repro.sim import get_backend
        m = self.config["model"]
        self.params = m4_ref.make_params(self.seed, m)
        self.backend = get_backend("m4", params=self.params,
                                   cfg=M4Config(**m))
        self.request = common.to_request(self.scen)
        self.call()                       # compiles or reads the cache

    def call(self) -> np.ndarray:
        """One call of the timed path: the scenario's FCTs (numpy)."""
        return np.asarray(self.backend.run(self.request).fcts)

    def reference(self, precision: str) -> np.ndarray:
        fcts, self.ref_stats = m4_ref.simulate(
            self.params, self.scen, self.config["model"], precision)
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
