"""m4's batched sweep path (`run_many` -> `simulate_open_loop_batch`) on
Table-2 scenarios that differ in everything the batch pads over: CC
scheme and knobs, spine count (links), flow count (events) and link
degree (K).

- each lane agrees with that scenario's own `run`, and both agree with
  the plain reference (`bench/systems/m4_ref.py`, dense jax.numpy at
  `highest`), run on the scenario alone, unpadded;
- the batch gauges (`m4.batch.*`) equal the padding counted by hand;
- `devices` holds a batch to one device (the vmapped scan there) on a
  host with two, and the default still shards.
"""
import os
import subprocess
import sys
from collections import Counter

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import traffic  # noqa: E402
from bench.systems import common, m4_ref  # noqa: E402

# small widths; a snapshot of 128 links holds every link of these fabrics
MODEL = {"hidden": 16, "gnn_dim": 12, "mlp_hidden": 8, "gnn_layers": 2,
         "snap_flows": 64, "snap_links": 128, "max_path": 8, "cfg_dim": 9,
         "dense_sldn": True}


def _point(spines, net, dist, sigma, load, matrix, n):
    return {"racks": 8, "hosts_per_rack": 4, "spines": spines,
            "link_gbps": 10.0, "prop_delay_s": 1e-6, "net": net,
            "size_dist": dist, "theta": 2e4, "sigma": sigma,
            "max_load": load, "matrix": matrix, "num_flows": n}


POINTS = [
    _point(1, {"cc": "dcqcn", "dcqcn_kmin": 26e3, "dcqcn_kmax": 48e3},
           "gaussian", 2.0, 0.7, "C", 120),
    _point(2, {"cc": "dctcp", "dctcp_k": 12e3, "init_window": 8e3},
           "pareto", 1.0, 0.5, "A", 80),
    _point(4, {"cc": "timely", "timely_thigh": 1.1e-4}, "lognormal", 1.0,
           0.6, "B", 100),
]


def _scenarios(seed=7):
    return [traffic.generate(p, np.random.default_rng([3, 1000, i]),
                             np.random.default_rng([seed, i]))
            for i, p in enumerate(POINTS)]


def _degree(scen):
    return max(Counter(l for p in scen.paths for l in p).values())


@pytest.fixture(scope="module")
def batch():
    import jax
    from repro.core.model import M4Config
    from repro.sim import get_backend
    scens = _scenarios()
    params = m4_ref.make_params(11, MODEL)
    backend = get_backend("m4", params=params, cfg=M4Config(**MODEL))
    reqs = [common.to_request(s) for s in scens]
    return {"scens": scens, "params": params, "backend": backend,
            "reqs": reqs, "batched": backend.run_many(
                reqs, devices=jax.devices()[:1]),
            "run": [backend.run(r) for r in reqs]}


def test_points_differ_in_every_padded_axis():
    scens = _scenarios()
    for axis in (lambda s: s.net["cc"], lambda s: s.num_links,
                 lambda s: s.num_flows, _degree):
        assert len({axis(s) for s in scens}) == 3


@pytest.mark.parametrize("lane", range(len(POINTS)))
def test_lane_matches_its_own_run(batch, lane):
    got = batch["batched"][lane].fcts
    want = batch["run"][lane].fcts
    assert got.shape == (POINTS[lane]["num_flows"],)
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("lane", range(len(POINTS)))
@pytest.mark.parametrize("path", ["batched", "run"])
def test_lane_matches_the_plain_reference(batch, path, lane):
    """Each lane of `run_many` ("batched") and each scenario's own `run`
    against the reference, which runs the scenario alone, unpadded. The
    limit is the tiny sweep cell's (bench/tests/test_sweep.py): on CPU
    seeds 1-16 its worst lane read at most 3.0e-8, the control one
    precision lower at least 9.6e-8."""
    ref, full = m4_ref.simulate(batch["params"], batch["scens"][lane], MODEL,
                                "highest")
    got = batch[path][lane].fcts
    assert common.unfinished(got) == 0
    assert full == {"flows_overflow": 0, "links_overflow": 0}
    assert common.fct_gap_mean(got, ref) < 5e-8


def test_batch_gauges_are_the_padding_by_hand(batch, tmp_path):
    from repro.obs import configure, get_registry, read_spans
    configure(str(tmp_path), proc="t")
    try:
        batch["backend"].run_many(batch["reqs"])
    finally:
        configure(None)
    scens = batch["scens"]
    n = [s.num_flows for s in scens]
    links = [s.num_links for s in scens]
    k = [_degree(s) for s in scens]
    want = {"m4.batch.size": 3,
            "m4.batch.k_pad_share": 1 - sum(k) / (3 * max(k)),
            "m4.batch.event_pad_share": 1 - sum(2 * x for x in n)
            / (3 * 2 * max(n)),
            "m4.batch.link_pad_share": 1 - sum(links) / (3 * max(links))}
    assert min(want.values()) > 0.1
    gauges = get_registry().snapshot()["gauges"]
    (span,) = [s for s in read_spans(str(tmp_path))
               if s["name"] == "m4.run_many"]
    for name, value in want.items():
        assert gauges[name] == pytest.approx(value, abs=1e-12)
        assert span["attrs"][name] == pytest.approx(value, abs=1e-12)


def test_devices_hold_the_batch_to_one_of_two_subprocess():
    """On a host with two (forced CPU) devices: `devices=[one]` runs the
    vmapped scan on that device; no `devices` still shards the batch
    across both; `devices=[both]` shards across the two given."""
    code = f"""
import sys
sys.path[:0] = [{ROOT!r}, {os.path.join(ROOT, "src")!r}]
import jax, numpy as np
assert jax.local_device_count() == 2, jax.devices()
from tests.test_sweep_batch import MODEL, _scenarios
from bench.systems import common, m4_ref
from repro.core.model import M4Config
from repro.core import simulate
from repro.core.simulate import TRACE_COUNTS
from repro.sim import get_backend
sharded_over = []
scan_for = simulate._sharded_scan
simulate._sharded_scan = lambda d=None: sharded_over.append(d) or scan_for(d)
reqs = [common.to_request(s) for s in _scenarios()]
m4 = get_backend("m4", params=m4_ref.make_params(11, MODEL),
                 cfg=M4Config(**MODEL))
held = m4.run_many(reqs, devices=jax.devices()[1:])
counts = dict(TRACE_COUNTS)
assert counts.get("open_loop_batched") == 1, counts
assert "open_loop_sharded" not in counts, counts
shard = m4.run_many(reqs)
assert TRACE_COUNTS["open_loop_sharded"] == 1, dict(TRACE_COUNTS)
given = m4.run_many(reqs, devices=jax.devices())
assert sharded_over == [None, tuple(jax.devices())], sharded_over
assert TRACE_COUNTS["open_loop_batched"] == 1, dict(TRACE_COUNTS)
for r, a, b, c in zip(reqs, held, shard, given):
    want = m4.run(r).fcts
    for x in (a, b, c):
        np.testing.assert_allclose(x.fcts, want, rtol=1e-5)
print("held-ok")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "held-ok" in out.stdout
