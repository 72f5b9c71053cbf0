"""Per-kernel allclose sweeps (Pallas interpret=True vs pure-jnp oracle)
plus hypothesis property tests on the water-filling invariants."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.flowsim import waterfill as waterfill_np
from repro.kernels.bipartite.ops import bipartite_round
from repro.kernels.bipartite.ref import bipartite_round_ref
from repro.kernels.fused_gru.ops import gru_cell as gru_pallas
from repro.kernels.fused_gru.ref import gru_cell_ref
from repro.kernels.waterfill.ops import incidence, masked_rowmin, waterfill_tpu
from repro.kernels.waterfill.ref import masked_rowmin_ref, waterfill_jnp


# ------------------------------------------------------------- bipartite
@pytest.mark.parametrize("SF,SL,G,P", [
    (8, 16, 20, 4), (16, 48, 48, 8), (64, 128, 300, 8), (32, 64, 128, 6),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bipartite_kernel_matches_ref(SF, SL, G, P, dtype):
    rng = np.random.default_rng(SF * SL)
    E = SF * P
    f = jnp.asarray(rng.normal(size=(SF, G)), dtype)
    l = jnp.asarray(rng.normal(size=(SL, G)), dtype)
    edge_f = jnp.repeat(jnp.arange(SF), P)
    edge_l = jnp.asarray(rng.integers(0, SL, E), jnp.int32)
    edge_mask = jnp.asarray(rng.random(E) < 0.7, dtype)
    wf = jnp.asarray(rng.normal(size=(2 * G, G)) * 0.1, dtype)
    wl = jnp.asarray(rng.normal(size=(2 * G, G)) * 0.1, dtype)
    bf = jnp.asarray(rng.normal(size=(G,)) * 0.1, dtype)
    bl = jnp.zeros((G,), dtype)
    rf, rl = bipartite_round_ref(f, l, edge_f, edge_l, edge_mask, wf, wl, bf, bl)
    pf, plk = bipartite_round(f, l, edge_f, edge_l, edge_mask, wf, wl, bf, bl)
    tol = 1e-4 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(pf, np.float32),
                               np.asarray(rf, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(plk, np.float32),
                               np.asarray(rl, np.float32), atol=tol, rtol=tol)


# ------------------------------------------------------------- fused GRU
@pytest.mark.parametrize("B,Din,H", [
    (5, 7, 20), (16, 13, 64), (200, 13, 400), (64, 309, 400), (128, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gru_kernel_matches_ref(B, Din, H, dtype):
    rng = np.random.default_rng(B * H)
    x = jnp.asarray(rng.normal(size=(B, Din)), dtype)
    h = jnp.asarray(rng.normal(size=(B, H)), dtype)
    wi = jnp.asarray(rng.normal(size=(Din, 3 * H)) * 0.1, dtype)
    wh = jnp.asarray(rng.normal(size=(H, 3 * H)) * 0.1, dtype)
    bi = jnp.asarray(rng.normal(size=(3 * H,)) * 0.1, dtype)
    bh = jnp.asarray(rng.normal(size=(3 * H,)) * 0.1, dtype)
    r = gru_cell_ref(x, h, wi, wh, bi, bh)
    p = gru_pallas(x, h, wi, wh, bi, bh)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(p, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


# ------------------------------------------------------------- waterfill
@pytest.mark.parametrize("F,L", [(10, 8), (100, 40), (300, 64)])
def test_waterfill_matches_numpy(F, L):
    rng = np.random.default_rng(F)
    cap = rng.uniform(1e9, 10e9, L)
    paths = [rng.choice(L, size=rng.integers(1, 5), replace=False)
             for _ in range(F)]
    r_np = waterfill_np(cap, paths)
    a = incidence(paths, L)
    r_p = np.asarray(waterfill_tpu(a, jnp.asarray(cap)))
    np.testing.assert_allclose(r_p, r_np, rtol=1e-5)


def test_masked_rowmin_shapes():
    rng = np.random.default_rng(0)
    for F, L in [(7, 5), (128, 200), (129, 64)]:
        a = jnp.asarray((rng.random((F, L)) < 0.4).astype(np.float32))
        share = jnp.asarray(rng.uniform(1, 10, L), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(masked_rowmin(a, share)),
            np.asarray(masked_rowmin_ref(a, share)), rtol=1e-6)


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 40), st.integers(2, 16), st.integers(0, 10_000))
def test_waterfill_maxmin_properties(F, L, seed):
    """Max-min invariants: feasibility, non-negativity, work conservation
    (every flow is bottlenecked at some saturated link or its own share)."""
    rng = np.random.default_rng(seed)
    cap = rng.uniform(1e9, 10e9, L)
    paths = [rng.choice(L, size=rng.integers(1, min(5, L + 1)), replace=False)
             for _ in range(F)]
    rates = waterfill_np(cap, paths)
    assert (rates > 0).all()
    load = np.zeros(L)
    for p, r in zip(paths, rates):
        load[p] += r
    assert (load <= cap * (1 + 1e-6)).all(), "capacity violated"
    # each flow traverses at least one (near-)saturated link = its bottleneck
    for p, r in zip(paths, rates):
        sat = load[p] >= cap[p] * (1 - 1e-6)
        assert sat.any(), "flow not bottlenecked anywhere (not max-min)"


# ------------------------------------------------------------- dispatch
def test_gru_cell_pair_fused_matches_separate():
    """The block-structured fused flow+link GRU pair (dispatch "xla" hot
    path) must match two independent reference cells."""
    from repro.kernels.dispatch import gru_cell_pair
    from repro.nn.layers import gru_init
    rng = np.random.default_rng(7)
    key = jax.random.PRNGKey(7)
    for Bf, Df, Bl, Dl, H in [(8, 13, 24, 11, 32), (16, 74, 48, 74, 96)]:
        p_f = gru_init(jax.random.fold_in(key, 0), Df, H)
        p_l = gru_init(jax.random.fold_in(key, 1), Dl, H)
        x_f = jnp.asarray(rng.normal(size=(Bf, Df)), jnp.float32)
        x_l = jnp.asarray(rng.normal(size=(Bl, Dl)), jnp.float32)
        h_f = jnp.asarray(rng.normal(size=(Bf, H)), jnp.float32)
        h_l = jnp.asarray(rng.normal(size=(Bl, H)), jnp.float32)
        ff, ll = gru_cell_pair(p_f, p_l, x_f, h_f, x_l, h_l, mode="xla")
        rf = gru_cell_ref(x_f, h_f, p_f["wi"], p_f["wh"], p_f["bi"], p_f["bh"])
        rl = gru_cell_ref(x_l, h_l, p_l["wi"], p_l["wh"], p_l["bi"], p_l["bh"])
        np.testing.assert_allclose(np.asarray(ff), np.asarray(rf),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(ll), np.asarray(rl),
                                   rtol=1e-5, atol=1e-6)


def test_bipartite_matmul_formulation_matches_segment_sum():
    """dispatch's "xla" GNN path (incidence matmuls — the Pallas kernel's
    math) equals the seed's segment-sum rounds."""
    from repro.kernels.dispatch import gnn_rounds
    from repro.nn.layers import linear_init
    rng = np.random.default_rng(11)
    key = jax.random.PRNGKey(11)
    SF, SL, G, P, R = 16, 48, 24, 8, 3
    layers = [{"wf": linear_init(jax.random.fold_in(key, 2 * i), 2 * G, G),
               "wl": linear_init(jax.random.fold_in(key, 2 * i + 1), 2 * G, G)}
              for i in range(R)]
    f = jnp.asarray(rng.normal(size=(SF, G)), jnp.float32)
    l = jnp.asarray(rng.normal(size=(SL, G)), jnp.float32)
    edge_f = jnp.repeat(jnp.arange(SF), P)
    edge_l = jnp.asarray(rng.integers(0, SL, SF * P), jnp.int32)
    edge_mask = jnp.asarray(rng.random(SF * P) < 0.7, jnp.float32)
    gf, gl = gnn_rounds(layers, f, l, edge_f, edge_l, edge_mask, SL,
                        mode="xla")
    rf, rl = f, l
    for lay in layers:
        rf, rl = bipartite_round_ref(rf, rl, edge_f, edge_l, edge_mask,
                                     lay["wf"]["w"], lay["wl"]["w"],
                                     lay["wf"]["b"], lay["wl"]["b"])
    np.testing.assert_allclose(np.asarray(gf), np.asarray(rf),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gl), np.asarray(rl),
                               rtol=1e-4, atol=1e-5)


def test_dispatch_masked_rowmin_modes_agree():
    from repro.kernels.dispatch import masked_rowmin as rowmin_dispatch
    rng = np.random.default_rng(3)
    a = jnp.asarray((rng.random((60, 40)) < 0.4).astype(np.float32))
    share = jnp.asarray(rng.uniform(1, 10, 40), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(rowmin_dispatch(a, share, mode="xla")),
        np.asarray(rowmin_dispatch(a, share, mode="interpret")), rtol=1e-6)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 30), st.integers(0, 1000))
def test_waterfill_single_link_fair_share(n, seed):
    """n flows on one link -> everyone gets C/n exactly."""
    cap = np.array([7e9])
    paths = [np.array([0])] * n
    rates = waterfill_np(cap, paths)
    np.testing.assert_allclose(rates, 7e9 / n, rtol=1e-9)


# ------------------------------------------------------------- staging
@pytest.mark.parametrize("B,Din,H", [
    (5, 7, 20), (64, 13, 400), (64, 309, 400),
])
def test_staged_gru_matches_gru_cell(B, Din, H):
    """Weights staged once, in a program of their own, then handed to the
    per-call wrapper: the cell is bit-equal to `ops.gru_cell`, and the
    layout keeps each gate third at a multiple of the padded width."""
    from repro.kernels.fused_gru.ops import gru_cell_staged, stage_gru
    rng = np.random.default_rng(B + Din + H)
    x = jnp.asarray(rng.normal(size=(B, Din)), jnp.float32)
    h = jnp.asarray(rng.normal(size=(B, H)), jnp.float32)
    wi = jnp.asarray(rng.normal(size=(Din, 3 * H)) * 0.1, jnp.float32)
    wh = jnp.asarray(rng.normal(size=(H, 3 * H)) * 0.1, jnp.float32)
    bi = jnp.asarray(rng.normal(size=(3 * H,)) * 0.1, jnp.float32)
    bh = jnp.asarray(rng.normal(size=(3 * H,)) * 0.1, jnp.float32)
    w = jax.jit(stage_gru)(wi, wh, bi, bh)
    Dp, Hp = Din + (-Din) % 128, H + (-H) % 128
    assert {k: v.shape for k, v in w.items()} == {
        "wi": (Dp, 3 * Hp), "wh": (Hp, 3 * Hp),
        "bi": (1, 3 * Hp), "bh": (1, 3 * Hp)}
    for staged, orig in ((w["wi"], wi), (w["wh"], wh), (w["bi"], bi[None]),
                         (w["bh"], bh[None])):
        want = np.zeros(staged.shape, np.float32)
        for g in range(3):
            want[:orig.shape[0], g * Hp:g * Hp + H] = orig[:, g * H:(g + 1) * H]
        np.testing.assert_array_equal(np.asarray(staged), want)
    staged_out = jax.jit(gru_cell_staged, static_argnames="tile_b")(
        x, h, w, tile_b=8)
    np.testing.assert_array_equal(
        np.asarray(staged_out),
        np.asarray(gru_pallas(x, h, wi, wh, bi, bh, tile_b=8)))


@pytest.mark.parametrize("SF,SL,G,P", [(8, 16, 20, 4), (64, 128, 300, 8)])
def test_staged_gnn_round_matches_bipartite_round(SF, SL, G, P):
    """As above for one GNN round: bit-equal to `ops.bipartite_round`, with
    the [self; agg] halves of each weight at rows 0 and Gp."""
    from repro.kernels.bipartite.ops import bipartite_round_staged, stage_round
    rng = np.random.default_rng(SF + G)
    E = SF * P
    f = jnp.asarray(rng.normal(size=(SF, G)), jnp.float32)
    l = jnp.asarray(rng.normal(size=(SL, G)), jnp.float32)
    edge_f = jnp.repeat(jnp.arange(SF), P)
    edge_l = jnp.asarray(rng.integers(0, SL, E), jnp.int32)
    edge_mask = jnp.asarray(rng.random(E) < 0.7, jnp.float32)
    wf = jnp.asarray(rng.normal(size=(2 * G, G)) * 0.1, jnp.float32)
    wl = jnp.asarray(rng.normal(size=(2 * G, G)) * 0.1, jnp.float32)
    bf = jnp.asarray(rng.normal(size=(G,)) * 0.1, jnp.float32)
    bl = jnp.asarray(rng.normal(size=(G,)) * 0.1, jnp.float32)
    w = jax.jit(stage_round)(wf, wl, bf, bl)
    Gp = G + (-G) % 128
    for staged, orig in ((w["wf"], wf), (w["wl"], wl)):
        want = np.zeros((2 * Gp, Gp), np.float32)
        want[:G, :G], want[Gp:Gp + G, :G] = orig[:G], orig[G:]
        np.testing.assert_array_equal(np.asarray(staged), want)
    for staged, orig in ((w["bf"], bf), (w["bl"], bl)):
        want = np.zeros((1, Gp), np.float32)
        want[0, :G] = orig
        np.testing.assert_array_equal(np.asarray(staged), want)
    got = jax.jit(bipartite_round_staged)(f, l, edge_f, edge_l, edge_mask, w)
    ref = bipartite_round(f, l, edge_f, edge_l, edge_mask, wf, wl, bf, bl)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _incidence_case(case):
    """-> (SF, SL, edge_f, edge_l, edge_mask) as the event step lays an
    edge list out: flow slot f owns edges f·P .. f·P + P - 1."""
    name, SF, SL, P = case
    rng = np.random.default_rng(SF * SL + P)
    edge_f = np.repeat(np.arange(SF, dtype=np.int32), P)
    edge_l = rng.integers(0, SL, SF * P).astype(np.int32)
    edge_mask = (rng.random(SF * P) < 0.7).astype(np.float32)
    if name == "repeated-link":         # flow 0 lists link 3 twice
        edge_l[:2], edge_mask[:2] = 3, 1.0
    elif name == "all-masked":
        edge_mask[:] = 0.0
    elif name == "clamped":
        # more distinct links than SL slots: `_build_links` clamps the
        # edges of links ranked past the set onto slot SL - 1
        from repro.core.model import M4Config
        from repro.core.simulate import _build_links
        cfg = M4Config(snap_flows=SF, snap_links=SL, max_path=P)
        flow_links = rng.permutation(4 * SF * P)[:SF * P].reshape(SF, P)
        flow_links[-1, -1] = -1                         # a short path
        _, _, el, em = _build_links(cfg, jnp.asarray(flow_links, jnp.int32),
                                    jnp.arange(SF, dtype=jnp.int32),
                                    jnp.ones((SF,), jnp.float32), 4 * SF * P)
        edge_l, edge_mask = np.asarray(el), np.asarray(em)
        assert ((edge_l == SL - 1) & (edge_mask > 0)).sum() > 1
    return SF, SL, edge_f, edge_l, edge_mask


INCIDENCE_CASES = [("random", 8, 16, 4), ("random", 64, 128, 8),
                   ("repeated-link", 8, 16, 4), ("all-masked", 8, 16, 4),
                   ("clamped", 8, 16, 4)]


@pytest.mark.parametrize("mode", ["xla", "interpret"])
@pytest.mark.parametrize("case", INCIDENCE_CASES,
                         ids=lambda c: f"{c[0]}-{c[1]}x{c[2]}-P{c[3]}")
def test_incidence_builder_matches_scatter_oracle(case, mode):
    """The one incidence builder (a one-hot contraction) is bit-equal to a
    scatter-add of the unmasked edges, and the GNN round of `mode` reads
    exactly that matrix: with identity embeddings and weights [0; I] the
    round returns relu(M) = M for the flows and Mᵀ for the links, counts
    that no summation order can round."""
    from repro.kernels import dispatch
    from repro.kernels.bipartite.ops import stage_round
    from repro.kernels.bipartite.ref import incidence_from_edges
    SF, SL, edge_f, edge_l, edge_mask = _incidence_case(case)
    want = np.zeros((SF, SL), np.float32)
    np.add.at(want, (edge_f, edge_l), edge_mask)
    if case[0] == "repeated-link":
        assert want[0, 3] == 2.0
    got = jax.jit(incidence_from_edges, static_argnums=(3, 4))(
        edge_f, edge_l, edge_mask, SF, SL)
    np.testing.assert_array_equal(np.asarray(got), want)

    G = SL
    probe = jnp.concatenate([jnp.zeros((G, G)), jnp.eye(G)])    # [0; I]
    zero = jnp.zeros((G,))
    layers = ([{"wf": {"w": probe, "b": zero}, "wl": {"w": probe, "b": zero}}]
              if mode == "xla" else [stage_round(probe, probe, zero, zero)])
    run = jax.jit(dispatch.gnn_rounds, static_argnames=("num_links", "mode"))
    out_f, out_l = run(layers, jnp.eye(SF, G), jnp.eye(SL, G),
                       jnp.asarray(edge_f), jnp.asarray(edge_l),
                       jnp.asarray(edge_mask), num_links=SL, mode=mode)
    np.testing.assert_array_equal(np.asarray(out_f), want)
    np.testing.assert_array_equal(np.asarray(out_l)[:, :SF], want.T)
    np.testing.assert_array_equal(np.asarray(out_l)[:, SF:], 0.0)


SMALL = dict(hidden=16, gnn_dim=12, mlp_hidden=8, gnn_layers=2,
             snap_flows=8, snap_links=24)


def _staged_count(mode):
    from repro.obs.registry import get_registry, labeled
    return get_registry().snapshot()["counters"].get(
        labeled("kernels.staged", mode=mode), 0)


def test_stage_params_adds_kernel_layout():
    """The kernel modes add the staged tree beside the original entries;
    "xla" mode gets `params` back untouched and stages nothing."""
    from repro.core.model import M4Config, init_m4
    from repro.kernels import dispatch
    params = init_m4(jax.random.PRNGKey(0), M4Config(**SMALL))
    before = _staged_count("xla")
    assert dispatch.stage_params(params, "xla") is params
    assert dispatch.kernel_params(params, "xla") is params
    assert _staged_count("xla") == before
    before = _staged_count("interpret")
    staged = dispatch.stage_params(params, "interpret")
    assert _staged_count("interpret") == before + 1
    assert all(staged[k] is params[k] for k in params)
    w = dispatch.kernel_params(staged, "interpret")
    assert set(w) == set(dispatch.GRUS) | {"gnn"}
    assert w["gru1"]["wh"].shape == (128, 3 * 128)
    assert len(w["gnn"]) == SMALL["gnn_layers"]
    assert w["gnn"][0]["wf"].shape == (256, 128)
    with pytest.raises(ValueError, match="stage_params"):
        dispatch.kernel_params(params, "interpret")


def _scenario(seed=3):
    from repro.scenarios.spec import ScenarioSpec
    sc = ScenarioSpec(name=f"staging-{seed}", topo="ft-4x2x2",
                      workload="table2", size_dist="WebServer",
                      max_load=0.4, num_flows=24, seed=seed).to_scenario()
    return sc.topo, sc.config, sc.generate()


def test_open_loop_scan_stages_once_per_trace():
    """Tracing one event scan stages the weights exactly once, before the
    loop: the step inside reads the staged tree and cannot stage."""
    from repro.core import simulate as sim
    from repro.core.model import M4Config, init_m4
    cfg = M4Config(**SMALL, kernel_mode="interpret")
    params = init_m4(jax.random.PRNGKey(1), cfg)
    topo, net, flows = _scenario()
    static, L, _ = sim.make_static(topo, flows, net, cfg)
    order, times = sim._arrival_order(static)
    traces = sim.TRACE_COUNTS["open_loop"]
    before = _staged_count("interpret")
    # a length no other test compiles, so this call traces
    sim._open_loop_scan.lower(params, cfg, L, static, jnp.asarray(order),
                              jnp.asarray(times), num_events=7)
    assert sim.TRACE_COUNTS["open_loop"] == traces + 1
    assert _staged_count("interpret") == before + 1


def test_staged_paths_match_xla():
    """The open loop (`simulate_open_loop`) and the closed loop
    (`M4Simulator`, staged once per session) in "interpret" mode give the
    FCTs of the "xla" path."""
    import dataclasses

    from repro.core import simulate as sim
    from repro.core.model import M4Config, init_m4
    cfg = M4Config(**SMALL)
    params = init_m4(jax.random.PRNGKey(2), cfg)
    topo, net, flows = _scenario(5)
    fcts, closed = {}, {}
    for mode in ("xla", "interpret"):
        c = dataclasses.replace(cfg, kernel_mode=mode)
        fcts[mode] = sim.simulate_open_loop(params, c, topo, net, flows).fcts
        s = sim.M4Simulator(params, c, topo, net, flows[:4])
        for fid in range(4):
            s.inject_arrival(fid, float(flows[fid].t_arrival))
        t, fid = s.next_departure()
        s.commit_departure(fid, t)
        closed[mode] = (fid, t)
    np.testing.assert_allclose(fcts["interpret"], fcts["xla"], rtol=1e-4)
    assert closed["interpret"][0] == closed["xla"][0]
    np.testing.assert_allclose(closed["interpret"][1], closed["xla"][1],
                               rtol=1e-4)


def test_sharded_scan_stages_once_subprocess():
    """With two (forced host) devices the batch takes the pmap scan: in
    "interpret" mode it stages once for its one trace, and its FCTs are
    the "xla" path's."""
    code = """
import dataclasses, jax, numpy as np
assert jax.local_device_count() == 2, jax.devices()
from repro.core import simulate as sim
from repro.core.model import M4Config, init_m4
from repro.obs.registry import get_registry, labeled
from repro.scenarios import get_suite
cfg = M4Config(hidden=16, gnn_dim=12, mlp_hidden=8, gnn_layers=2,
               snap_flows=8, snap_links=24)
params = init_m4(jax.random.PRNGKey(0), cfg)
scens = []
for spec in get_suite("smoke16", num_flows=8).limit(4):
    sc = spec.to_scenario()
    scens.append((sc.topo, sc.config, sc.generate()))
fcts = {m: sim.simulate_open_loop_batch(
            params, dataclasses.replace(cfg, kernel_mode=m), scens)
        for m in ("xla", "interpret")}
assert sim.TRACE_COUNTS["open_loop_sharded"] == 2, dict(sim.TRACE_COUNTS)
counters = get_registry().snapshot()["counters"]
assert counters.get(labeled("kernels.staged", mode="interpret")) == 1
assert labeled("kernels.staged", mode="xla") not in counters
for a, b in zip(fcts["xla"], fcts["interpret"]):
    np.testing.assert_allclose(b.fcts, a.fcts, rtol=1e-4)
print("sharded-staged-ok")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "sharded-staged-ok" in out.stdout
