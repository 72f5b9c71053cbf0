"""TPU-resident flowSim (lax.scan event loop) vs numpy event-driven
reference: identical FCTs on random Table-2 scenarios; the water-filling
rounds counter against the benchmark's plain reference."""
import os
import sys

import numpy as np
import pytest

from repro.core.flowsim import run_flowsim
from repro.core.flowsim_fast import run_flowsim_fast
from repro.data.traffic import sample_scenario


@pytest.mark.parametrize("seed", [0, 3, 7])
def test_fast_flowsim_matches_reference(seed):
    sc = sample_scenario(seed, num_flows=60)
    flows = sc.generate()
    ref = run_flowsim(sc.topo, sc.generate())
    fast = run_flowsim_fast(sc.topo, flows)
    # same event semantics -> same completion times (fp tolerance)
    np.testing.assert_allclose(fast.fcts, ref.fcts, rtol=1e-4)


def test_fast_flowsim_single_link():
    from repro.net.packetsim import Flow
    from repro.net.topology import FatTree
    topo = FatTree(num_racks=2, hosts_per_rack=2, num_spines=1)
    n, size = 4, 100_000
    flows = [Flow(fid=i, src=0, dst=1, size=size, t_arrival=0.0,
                  path=topo.path(0, 1, 0)) for i in range(n)]
    res = run_flowsim_fast(topo, flows)
    np.testing.assert_allclose(res.fcts, n * size * 8.0 / 10e9, rtol=1e-5)


# ------------------------------------------- rounds counter and bit-identity
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import traffic  # noqa: E402
from bench.systems import common, flowsim_ref  # noqa: E402

SMALL = {"racks": 8, "hosts_per_rack": 4, "spines": 2, "link_gbps": 10.0,
         "prop_delay_s": 1e-6, "net": {"cc": "dctcp"},
         "size_dist": "WebServer", "theta": 2e4, "sigma": 1.0,
         "max_load": 0.7, "matrix": "A", "num_flows": 300}


def _small_request(seed):
    scen = traffic.generate(SMALL, np.random.default_rng([seed, 1000]),
                            np.random.default_rng([seed, 0]))
    return scen, common.to_request(scen)


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_rounds_counter_matches_reference(seed):
    """The scan's water-filling rounds, summed over its 2N events, are the
    plain reference's rounds over the same events; the call publishes
    them as the `flowsim.waterfill.*` counter and gauge.

    The reference holds shares within a millionth of each other as one
    level (in exact arithmetic they are equal). The program compares its
    float32 shares exactly, so a level that float32 rounding splits costs
    it one more round: seed 5 has one such event (two shares of 10/3
    Gb/s, two float32 spacings apart). Never fewer rounds, and at most one
    in 500 more."""
    from repro.obs import get_registry
    scen, req = _small_request(seed)
    _, stats = flowsim_ref.simulate(scen, "float32")
    registry = get_registry()
    before = registry.snapshot()["counters"].get("flowsim.waterfill.rounds", 0)
    run_flowsim_fast(req.topo, list(req.flows))
    snap = registry.snapshot()
    rounds = snap["counters"]["flowsim.waterfill.rounds"] - before
    assert stats["events"] == 2 * scen.num_flows
    assert 0 <= rounds - stats["rounds"] <= stats["rounds"] // 500
    assert snap["gauges"]["flowsim.waterfill.rounds_per_event"] == \
        rounds / (2 * scen.num_flows)


@pytest.mark.parametrize("seed", [1, 2])
def test_rounds_output_changes_no_fct(seed):
    """The single-scenario scan's added output moves no FCT: it equals the
    probed scan (which returns no count) and its lane of a `run_many`
    batch (whose scan drops the count) bit for bit."""
    from repro.core.probes import ProbeConfig
    from repro.sim import get_backend
    _, req = _small_request(seed)
    plain = run_flowsim_fast(req.topo, list(req.flows))
    probed = run_flowsim_fast(req.topo, list(req.flows),
                              probes=ProbeConfig(stride=4, max_samples=16))
    _, other = _small_request(seed + 10)
    lanes = get_backend("flowsim_fast").run_many([req, other])
    np.testing.assert_array_equal(plain.fcts, probed.fcts)
    np.testing.assert_array_equal(plain.fcts, lanes[0].fcts)
