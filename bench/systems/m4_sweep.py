"""m4 sweep cells: one chunk of a parameter sweep per call, the mix's
scenarios through `repro.sim.get_backend("m4").run_many`, at the
configuration's model sizes and with the benchmark's own weights from the
seed; each scenario checked against `m4_ref`, the plain reference, run on
that scenario alone (unpadded, nothing vmapped).

A sweep mix holds a `"scenarios"` list instead of one `"scenario"`.
Point i's structure comes from `[base_seed, 1000, i]` and the run seed
permutes its sizes and gaps through `[seed, i]` (`bench.traffic.generate`),
so every seed pads the batch to the same shapes.

The batch is held to the first chip where the program lets a caller hold
it (`run_many`'s `devices`), so the cell runs the vmapped scan on one chip
whatever the host exposes; a program without that argument runs the
vmapped scan on a one-chip host all the same.
"""
from __future__ import annotations

import inspect

import numpy as np

from bench import traffic
from bench.systems import common, m4_ref


def scenarios(mix: dict, seed: int) -> list:
    """The mix's scenarios for run seed `seed`."""
    base = int(mix["base_seed"])
    return [traffic.generate(p, np.random.default_rng([base, 1000, i]),
                             np.random.default_rng([seed, i]))
            for i, p in enumerate(mix["scenarios"])]


class Cell:
    """One m4 sweep cell: each call runs the batch through `run_many`."""

    rate_metric = "events_per_s"

    def __init__(self, config: dict, mix: dict, seed: int):
        self.config, self.seed = config, seed
        self.scens = scenarios(mix, seed)
        if len(self.scens) != config["batch"]:
            raise ValueError(f"the mix has {len(self.scens)} scenarios, the "
                             f"configuration's batch is {config['batch']}")
        self.events_per_call = 2 * sum(s.num_flows for s in self.scens)

    def setup(self):
        import jax
        from repro.core.model import M4Config
        from repro.sim import get_backend
        m = self.config["model"]
        self.params = m4_ref.make_params(self.seed, m)
        self.backend = get_backend("m4", params=self.params,
                                   cfg=M4Config(**m))
        self.requests = [common.to_request(s) for s in self.scens]
        self.hold = {}
        if "devices" in inspect.signature(self.backend.run_many).parameters:
            self.hold = {"devices": jax.devices()[:1]}
        self.call()                       # compiles or reads the cache

    def call(self) -> list:
        """One call of the timed path: each scenario's FCTs (numpy)."""
        return [np.asarray(r.fcts)
                for r in self.backend.run_many(self.requests, **self.hold)]

    def reference(self, precision: str) -> list:
        out, self.ref_stats = [], {}
        for scen in self.scens:
            fcts, stats = m4_ref.simulate(self.params, scen,
                                          self.config["model"], precision)
            out.append(fcts)
            for k, v in stats.items():
                self.ref_stats[k] = self.ref_stats.get(k, 0) + v
        return out

    @staticmethod
    def unfinished(answer: list) -> int:
        return sum(map(common.unfinished, answer))

    def check(self, sample: list, outputs: list) -> dict:
        """Numbers compared for `correct`: the worst scenario's mean
        relative FCT gap of the sampled call against the reference at the
        configuration's precision (one bad lane cannot hide in an average
        over the batch), and the flows left unfinished in any call of the
        window."""
        ref = self.reference(self.config["correct"]["precision"])
        return {"fct_gap_worst": max(common.fct_gap_mean(p, r)
                                     for p, r in zip(sample, ref)),
                "unfinished": sum(map(self.unfinished, outputs))
                + self.unfinished(ref)}
