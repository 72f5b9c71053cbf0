"""The O(degree) incremental event step against dense oracles.

- property test: on >= 50 random scenarios across all workload families
  and arbitrary active sets, the incremental snapshot builder emits
  bitwise-identical (snap_f, mask) to a dense (N, P, P) search over the
  whole arena (`_build_snapshot_dense`, below), and the snapshot's link
  set equals `jnp.unique` of its links;
- the edge slots equal a binary search's, and the dedupe equals
  `jnp.unique`;
- kernel modes: the same FCTs under REPRO_KERNELS-style mode overrides
  ("xla" vs "interpret"), plus closed-loop/next_departure behavior and
  the named scopes that mark the event step's layers in the compiled
  program.
"""
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
def _build_snapshot_dense(cfg: M4Config, flow_links, fid, active_mask):
    """Oracle: affected flows = active flows sharing >= 1 link with the
    event flow, found by a dense (N, P, P) comparison + top-k over the
    whole arena."""
    SF = cfg.snap_flows
    ev_links = flow_links[fid]                               # (P,)
    share = (flow_links[:, :, None] == ev_links[None, None, :]) \
        & (flow_links[:, :, None] >= 0)
    shares = share.any((1, 2))                               # (N,)
    score = jnp.where(shares & active_mask, 1.0, 0.0).at[fid].set(-1.0)
    # stable top-(SF-1) by score (ties -> lower index)
    N = flow_links.shape[0]
    key = score * N - jnp.arange(N, dtype=jnp.int32)
    k = min(SF - 1, N)
    _, idx = jax.lax.top_k(key, k)
    others_valid = score[idx] > 0
    pad = SF - 1 - k
    if pad:
        idx = jnp.concatenate([idx, jnp.zeros((pad,), idx.dtype)])
        others_valid = jnp.concatenate([others_valid, jnp.zeros((pad,), bool)])
    # masked slots scatter to the dump row N, never aliasing a live row
    idx = jnp.where(others_valid, idx, N)
    snap_f = jnp.concatenate([fid[None], idx])
    snap_mask = jnp.concatenate([jnp.ones((1,), jnp.float32),
                                 others_valid.astype(jnp.float32)])
    return snap_f, snap_mask


@pytest.mark.parametrize("seed", range(50))
def test_incremental_builder_matches_dense(seed):
    """For arbitrary active sets, incremental == dense snapshot builder,
    and the snapshot's link set is `jnp.unique` of its masked links."""
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
        snap_d, sfm_d = _build_snapshot_dense(
            TINY, static["flow_links"], jnp.int32(fid), active_d)
        np.testing.assert_array_equal(np.asarray(snap_i), np.asarray(snap_d))
        np.testing.assert_array_equal(np.asarray(sfm_i), np.asarray(sfm_d))

        fg = jnp.minimum(snap_i, N - 1)
        snap_l, slm, _, _ = sim._build_links(TINY, static["flow_links"], fg,
                                             sfm_i, L)
        gl = static["flow_links"][fg]
        gl = jnp.where((gl >= 0) & (sfm_i[:, None] > 0), gl, L)
        want = jnp.unique(gl.reshape(-1), size=TINY.snap_links, fill_value=L)
        np.testing.assert_array_equal(np.asarray(snap_l), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(slm),
                                      np.asarray(want < L, np.float32))


PAPER_SNAP = M4Config()      # SF 64, P 8, SL 128
SLOT_CASES = ("random", "empty", "full", "dup", "masked", "overflow")


def _snapshot_links(cfg, case, num_links, rng):
    """(flow_links, snap_f, snap_f_mask) of one snapshot whose edges hit
    the link set as `case` says."""
    SF, P, SL = cfg.snap_flows, cfg.max_path, cfg.snap_links
    N = 2 * SF
    pool = {"dup": rng.choice(num_links, size=3, replace=False),
            "full": rng.choice(num_links, size=SL, replace=False)}.get(
                case, np.arange(num_links))
    links = rng.choice(pool, size=(N, P))
    links[rng.random((N, P)) < 0.2] = -1          # short paths
    snap_f = rng.permutation(N)[:SF]
    if case in ("full", "overflow"):              # every hop a link
        links[snap_f] = rng.choice(pool, size=(SF, P))
    if case == "full":                            # each of the SL once
        flat = links[snap_f].reshape(-1)
        flat[:SL] = pool
        links[snap_f] = flat.reshape(SF, P)
    mask = np.ones(SF, np.float32)
    if case == "empty":
        mask[:] = 0
    elif case == "masked":
        mask[rng.random(SF) < 0.5] = 0
    return (jnp.asarray(links, jnp.int32), jnp.asarray(snap_f, jnp.int32),
            jnp.asarray(mask))


def _slots_by_binary_search(cfg, flow_links, snap_f, snap_f_mask, uniq,
                            num_links):
    """The edge slots as the seed program found them: `searchsorted`'s
    binary search, then the same masking and clamping."""
    gl = flow_links[snap_f]
    gl = jnp.where((gl >= 0) & (snap_f_mask[:, None] > 0), gl, num_links)
    el = jnp.searchsorted(uniq, gl.reshape(-1), method="scan")
    return jnp.where(gl.reshape(-1) < num_links,
                     jnp.minimum(el, cfg.snap_links - 1), 0)


@pytest.mark.parametrize("case", SLOT_CASES)
@pytest.mark.parametrize("cfg, num_links", [(TINY, 60), (PAPER_SNAP, 1536)],
                         ids=["tiny", "paper"])
def test_build_links_slots_match_binary_search(cfg, num_links, case):
    """The comparison-rank edge slots equal a binary search's, one snapshot
    at a time and vmapped over 8 lanes."""
    rng = np.random.default_rng([num_links, SLOT_CASES.index(case)])
    snaps = [_snapshot_links(cfg, case, num_links, rng) for _ in range(8)]

    def both(flow_links, snap_f, snap_f_mask):
        snap_l, slm, el, _ = sim._build_links(cfg, flow_links, snap_f,
                                              snap_f_mask, num_links)
        want = _slots_by_binary_search(cfg, flow_links, snap_f, snap_f_mask,
                                       snap_l, num_links)
        return el, want, slm.sum()

    for snap in snaps[:2]:
        got, want, _ = both(*snap)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got, want, n_links = jax.vmap(both)(*(jnp.stack(a) for a in zip(*snaps)))
    assert got.shape == (8, cfg.snap_flows * cfg.max_path)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # each case reaches the link set it names
    SL = cfg.snap_links
    n_links = np.asarray(n_links).tolist()
    want_links = {"empty": 0, "full": SL, "overflow": SL, "dup": 3}
    if case in want_links:
        assert n_links == [want_links[case]] * 8
    else:
        assert all(0 < n <= SL for n in n_links)


def test_dedupe_ascending_matches_unique():
    rng = np.random.default_rng(0)
    for k in (8, 15, 32, 48):           # both regimes of the dedupe
        for _ in range(10):
            vals = jnp.asarray(rng.integers(0, 40, size=96), jnp.int32)
            got = sim._dedupe_ascending(vals, k, 99)
            want = jnp.unique(jnp.where(vals < 99, vals, 99), size=k,
                              fill_value=99)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------------ kernel modes
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


# ----------------------------------------------------- named scopes / modes
SCOPES = ("m4.departure", "m4.snapshot", "m4.temporal", "m4.spatial",
          "m4.heads", "m4.scatter")


@pytest.mark.parametrize("mode", ["xla", "interpret"])
@pytest.mark.parametrize("batched", [False, True])
def test_event_step_scopes_in_compiled_program(tiny_params, mode, batched):
    """Every layer of the event step is a named scope: the CPU-compiled
    scan carries each scope in its ops' `op_name` metadata, which the
    profiler reports as each device op's name stack."""
    import dataclasses
    import re
    cfg = dataclasses.replace(TINY, kernel_mode=mode)
    scens = [_spec(s).to_scenario() for s in (1, 5)]
    if batched:
        static, order, times, L, _ = sim.stack_scenarios(
            cfg, [(sc.topo, sc.config, sc.generate()) for sc in scens])
        fn = sim._open_loop_scan_batched
    else:
        sc = scens[0]
        static, L, _ = sim.make_static(sc.topo, sc.generate(), sc.config,
                                       cfg)
        order, times = sim._arrival_order(static)
        fn = sim._open_loop_scan
    hlo = fn.lower(tiny_params, cfg, L, static, order, times) \
        .compile().as_text()
    stacks = re.findall(r'op_name="([^"]*)"', hlo)
    found = {c for st in stacks for c in st.split("/") if c in SCOPES}
    assert found == set(SCOPES)
    # the layers do not nest: an op belongs to one of them at most
    assert not any(len(set(st.split("/")) & set(SCOPES)) > 1
                   for st in stacks)


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

