"""The O(degree) incremental event step vs the dense seed program.

Three layers of equivalence evidence:
- property test: on >= 50 random scenarios across all workload families
  and arbitrary active sets, the incremental snapshot builder emits
  bitwise-identical (snap_f, mask, snap_l, edges) to the dense reference;
- end-to-end: FCTs of the incremental scan match the legacy scan (the
  seed program preserved behind snapshot_impl="dense") on the smoke16
  suite, batched, within rtol 1e-5;
- kernel modes: the same FCTs under REPRO_KERNELS-style mode overrides
  ("xla" vs "interpret"), plus closed-loop/next_departure behavior and
  the compile-vs-steady wallclock split.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import simulate as sim
from repro.core.model import M4Config, init_m4
from repro.kernels import dispatch
from repro.scenarios import get_suite
from repro.scenarios.spec import ScenarioSpec

TINY = M4Config(hidden=16, gnn_dim=12, mlp_hidden=8, gnn_layers=2,
                snap_flows=8, snap_links=24)


@pytest.fixture(scope="module")
def tiny_params():
    return init_m4(jax.random.PRNGKey(0), TINY)


def _spec(seed):
    """Scenario #seed: cycles workload families, topologies and sizes."""
    workloads = ["table2", "incast", "permutation", "all_to_all"]
    topos = ["paper", "ft-4x2x2", "ft-8x2x2", "ft-4x4x2"]
    return ScenarioSpec(
        name=f"prop-{seed}", topo=topos[seed % 4],
        workload=workloads[seed % 4], size_dist="WebServer",
        max_load=0.3 + 0.04 * (seed % 6), num_flows=20 + 3 * (seed % 7),
        seed=1000 + seed, fan_in=4, participants=4)


# ---------------------------------------------------- builder equivalence
@pytest.mark.parametrize("seed", range(50))
def test_incremental_builder_matches_dense(seed):
    """For arbitrary active sets, incremental == dense snapshot builder,
    including the downstream link set and edge list."""
    sc = _spec(seed).to_scenario()
    flows = sc.generate()
    # pad some scenarios to exercise the batch-shaped tables
    pad = seed % 3 == 0
    n_total = len(flows) + 7 if pad else None
    k_total = (sim.max_link_degree(flows, TINY.max_path) + 3) if pad else None
    static, L, _ = sim.make_static(sc.topo, flows, sc.config, TINY,
                                   n_total=n_total, l_total=None,
                                   k_total=k_total)
    N = static["flow_links"].shape[0]
    rng = np.random.default_rng(seed)
    members = np.asarray(static["link_members"])          # (L+1, K)
    for case in range(4):
        frac = [0.0, 0.3, 0.7, 1.0][case]
        active = rng.random(len(flows)) < frac
        active = np.concatenate([active, np.zeros(N - len(flows), bool)])
        # occupancy consistent with the active set: occ[l,k] iff the
        # member flow is active (padding members have id N -> inactive)
        act_ext = np.concatenate([active, [False]])
        occ = jnp.asarray(act_ext[members])
        fid = int(rng.integers(0, len(flows)))
        active_d = jnp.asarray(active).at[fid].set(True)

        snap_i, sfm_i = sim._build_snapshot(TINY, static, occ,
                                            jnp.int32(fid))
        snap_d, sfm_d = sim._build_snapshot_dense(
            TINY, static["flow_links"], jnp.int32(fid), active_d)
        np.testing.assert_array_equal(np.asarray(snap_i), np.asarray(snap_d))
        np.testing.assert_array_equal(np.asarray(sfm_i), np.asarray(sfm_d))

        fg = jnp.minimum(snap_i, N - 1)
        out_new = sim._build_links(TINY, static["flow_links"], fg, sfm_i, L)
        out_leg = sim._build_links(TINY, static["flow_links"], fg, sfm_i, L,
                                   legacy=True)
        for a, b in zip(out_new, out_leg):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dedupe_ascending_matches_unique():
    rng = np.random.default_rng(0)
    for k in (8, 15, 32, 48):           # both regimes of the dedupe
        for _ in range(10):
            vals = jnp.asarray(rng.integers(0, 40, size=96), jnp.int32)
            got = sim._dedupe_ascending(vals, k, 99)
            want = jnp.unique(jnp.where(vals < 99, vals, 99), size=k,
                              fill_value=99)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------- end-to-end parity
def test_smoke16_fct_parity_incremental_vs_legacy(tiny_params):
    """Acceptance: FCTs match the pre-change dense program (rtol 1e-5) on
    the smoke16 suite — run batched, 2 compiles total."""
    suite = get_suite("smoke16", num_flows=10)
    scenarios = []
    for spec in suite:
        sc = spec.to_scenario()
        scenarios.append((sc.topo, sc.config, sc.generate()))
    inc = sim.simulate_open_loop_batch(tiny_params, TINY, scenarios)
    leg = sim.simulate_open_loop_batch(tiny_params, TINY, scenarios,
                                       snapshot_impl="dense")
    for a, b in zip(inc, leg):
        np.testing.assert_allclose(a.fcts, b.fcts, rtol=1e-5)


def test_smoke16_fct_parity_kernel_modes(tiny_params):
    """Same FCTs whether the GRU/GNN run as jnp ("xla") or as the Pallas
    kernels under the interpreter ("interpret") — both batched compiles."""
    suite = get_suite("smoke16", num_flows=8).limit(8)
    scenarios = []
    for spec in suite:
        sc = spec.to_scenario()
        scenarios.append((sc.topo, sc.config, sc.generate()))
    import dataclasses
    cfg_x = dataclasses.replace(TINY, kernel_mode="xla")
    cfg_i = dataclasses.replace(TINY, kernel_mode="interpret")
    rx = sim.simulate_open_loop_batch(tiny_params, cfg_x, scenarios)
    ri = sim.simulate_open_loop_batch(tiny_params, cfg_i, scenarios)
    for a, b in zip(rx, ri):
        np.testing.assert_allclose(a.fcts, b.fcts, rtol=1e-4)


def test_flowsim_fast_kernel_mode_parity():
    from repro.core import flowsim_fast as ff
    sc = _spec(3).to_scenario()
    flows = sc.generate()
    a, cap, sizes, times, order = ff._pack(sc.topo, flows)
    args = tuple(jnp.asarray(x) for x in (a, cap, sizes, times, order))
    fx = np.asarray(ff._event_scan(*args, mode="xla"))
    fi = np.asarray(ff._event_scan(*args, mode="interpret"))
    np.testing.assert_allclose(fx, fi, rtol=1e-5)


# ------------------------------------------------------------ closed loop
def test_next_departure_scalars_and_idle(tiny_params):
    sc = _spec(1).to_scenario()
    flows = sc.generate()
    s = sim.M4Simulator(tiny_params, TINY, sc.topo, sc.config, flows)
    assert s.next_departure() == (None, None)          # idle arena
    s.inject_arrival(0, 0.0)
    t, i = s.next_departure()
    assert isinstance(t, float) and t > 0 and i == 0
    s.commit_departure(i, t)
    assert s.next_departure() == (None, None)
    assert np.isfinite(s.fcts[0])


def test_closed_loop_occupancy_tracks_active(tiny_params):
    """After arrival the flow occupies its links' slots; after departure
    the slots clear again."""
    sc = _spec(2).to_scenario()
    flows = sc.generate()
    s = sim.M4Simulator(tiny_params, TINY, sc.topo, sc.config, flows)
    rows = np.asarray(s.static["occ_rows"])[0]
    slots = np.asarray(s.static["occ_slots"])[0]
    live = rows < s.num_links
    s.inject_arrival(0, 0.0)
    occ = np.asarray(s.state["link_occ"])
    assert occ[rows[live], slots[live]].all()
    t, i = s.next_departure()
    s.commit_departure(0, t)
    occ = np.asarray(s.state["link_occ"])
    assert not occ[rows[live], slots[live]].any()


# ------------------------------------------------------- wallclock / modes
def test_warmup_splits_compile_from_steady(tiny_params):
    import dataclasses
    sc = dataclasses.replace(_spec(4), num_flows=23).to_scenario()
    flows = sc.generate()        # distinctive arena shape -> fresh compile
    r = sim.simulate_open_loop(tiny_params, TINY, sc.topo, sc.config,
                               flows, warmup=True)
    assert r.compile_wall > 0 and r.wallclock > 0
    # the cold call includes trace+compile+run: it must dominate steady
    assert r.compile_wall > r.wallclock


def test_resolve_mode_and_canonicalize(monkeypatch):
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    assert dispatch.resolve_mode() in dispatch.MODES
    assert dispatch.resolve_mode("xla") == "xla"
    # requesting compiled pallas off-TPU raises: no silent interpret
    if jax.default_backend() != "tpu":
        with pytest.raises(ValueError, match="needs a TPU"):
            dispatch.resolve_mode("pallas")
    monkeypatch.setenv(dispatch.ENV_VAR, "interpret")
    # env fills in the default (None) but never re-routes a pinned mode —
    # a backend's construction-time pin must match what later executes
    assert dispatch.resolve_mode("xla") == "xla"
    assert dispatch.resolve_mode(None) == "interpret"
    cfg = dispatch.canonicalize_cfg(TINY)
    assert cfg.kernel_mode == "interpret"
    assert dispatch.canonicalize_cfg(cfg).kernel_mode == "interpret"
    monkeypatch.setenv(dispatch.ENV_VAR, "bogus")
    with pytest.raises(ValueError):
        dispatch.resolve_mode()


@pytest.mark.parametrize("platform", ["cpu", "gpu"])
@pytest.mark.parametrize("via", ["request", "env", "config"])
def test_pallas_off_tpu_raises(monkeypatch, platform, via):
    """Compiled Pallas requested on any non-TPU platform is an error —
    whether pinned in code, through REPRO_KERNELS or on an M4Config — and
    nothing is counted as dispatched."""
    from repro.obs.registry import get_registry, labeled
    monkeypatch.setattr(dispatch, "_platform", lambda: platform)
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    reg = get_registry()
    before = reg.snapshot()["counters"]
    with pytest.raises(ValueError, match="needs a TPU"):
        if via == "request":
            dispatch.resolve_mode("pallas")
        elif via == "env":
            monkeypatch.setenv(dispatch.ENV_VAR, "pallas")
            dispatch.resolve_mode()
        else:
            dispatch.canonicalize_cfg(M4Config(kernel_mode="pallas"))
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    after = reg.snapshot()["counters"]
    for mode in dispatch.MODES:
        name = labeled("kernels.dispatch", mode=mode)
        assert after.get(name, 0) == before.get(name, 0)
    # the platform probe itself still picks the right default
    assert dispatch.resolve_mode() == "xla"
    monkeypatch.setattr(dispatch, "_platform", lambda: "tpu")
    assert dispatch.resolve_mode() == "pallas"
    assert dispatch.resolve_mode("pallas") == "pallas"


def test_fingerprints_include_kernel_mode(tiny_params, monkeypatch):
    from repro.sim import get_backend
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    mode = dispatch.resolve_mode()
    assert get_backend("flowsim_fast").fingerprint() == \
        f"flowsim_fast-k{mode}"
    fp = get_backend("m4", params=tiny_params, cfg=TINY).fingerprint()
    assert fp.endswith(f"-k{mode}")
    monkeypatch.setenv(dispatch.ENV_VAR, "interpret")
    fp2 = get_backend("m4", params=tiny_params, cfg=TINY).fingerprint()
    assert fp2.endswith("-kinterpret") and fp2 != fp


# --------------------------------------------------------------- perf gate
def test_perf_gate_check_logic():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    import perf_gate
    base = {"benchmark": "m4", "host": {"hostname": "elsewhere"},
            "entries": [{"n": 256, "events_per_sec": 1000.0,
                         "legacy_events_per_sec": 500.0,
                         "speedup_vs_legacy": 2.0}]}
    good = {"benchmark": "m4",
            "entries": [{"n": 256, "events_per_sec": 10.0,   # other host:
                         "legacy_events_per_sec": 5.0,       # abs ignored
                         "speedup_vs_legacy": 1.9}]}
    assert perf_gate.check(good, base, log=lambda *a: None) == []
    bad = {"benchmark": "m4",
           "entries": [{"n": 256, "events_per_sec": 900.0,
                        "legacy_events_per_sec": 900.0,
                        "speedup_vs_legacy": 1.0}]}          # ratio lost
    fails = perf_gate.check(bad, base, log=lambda *a: None)
    assert len(fails) == 1 and "speedup" in fails[0]
    # same host: absolute regression (beyond 2x tolerance) is gated too
    import socket
    base["host"]["hostname"] = socket.gethostname()
    slow = {"benchmark": "m4",
            "entries": [{"n": 256, "events_per_sec": 100.0,
                         "legacy_events_per_sec": 50.0,
                         "speedup_vs_legacy": 2.0}]}
    fails = perf_gate.check(slow, base, log=lambda *a: None)
    assert len(fails) == 1 and "ev/s" in fails[0]
