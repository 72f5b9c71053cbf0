"""m4 event-driven inference (§3.1, Figure 2/5).

The event manager races the next arrival (from the traffic generator)
against the earliest *predicted* departure (from querying MLP-sldn on the
hidden states). Each event triggers: snapshot construction (in-JAX, static
shapes) -> temporal GRU advance -> GNN spatial update -> departure-time
re-prediction for affected flows.

Per-event cost is O(path x link-degree), not O(N) (DESIGN.md §3):
`make_static` precomputes a link->flow membership table and the scan
carries a per-link active-flow occupancy bitmap, so the snapshot builder
gathers candidates from the event flow's <= P links instead of comparing
against all N flows.

`simulate_open_loop` runs the whole trace as one `lax.scan` (2N events).
`simulate_open_loop_batch` pads B scenarios to a shared arena shape and
`jax.vmap`s the scan across them — one compiled call instead of B retraces
(this is what `repro.sim.get_backend("m4").run_many` dispatches to) —
and `jax.pmap`-shards the vmapped batch across local devices when more
than one exists (params broadcast, arenas split devices x B/devices), or
across the devices a caller gives; given one, it runs the vmapped scan
there. Each batch sets `m4.batch.*` padding gauges (`repro.obs`).
`M4Simulator` exposes a single-event step for closed-loop applications that
inject flows dynamically (§5.4); its jitted step donates the state arenas
so the carry is updated in place instead of copied every event.

GRU advances and GNN rounds execute through `repro.kernels.dispatch`
(Pallas on TPU, jnp elsewhere, REPRO_KERNELS override); entry points pin
the resolved mode into `cfg.kernel_mode` so it is part of the jit key.
The kernels' padded weight layout (`dispatch.stage_params`) is built
once per call in `_open_loop_core`, before the scan (once per session in
`M4Simulator`), and the event step hands it to the kernels as it is.

Each layer of the event step is a `jax.named_scope` — `m4.departure`,
`m4.snapshot`, `m4.temporal`, `m4.spatial`, `m4.heads`, `m4.scatter` —
so a profiler trace attributes every device op to its layer (the scopes
change op metadata only, not the compiled program). The entry points
open `repro.obs` spans, `m4.run` (`m4.run_many`) > `m4.build`, `m4.scan`,
`m4.result`, which reach the profiler's trace while it collects.

Prefer the unified entry point `repro.sim.get_backend("m4")` over calling
these functions directly.
"""
from __future__ import annotations

import time
from collections import Counter
from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.dispatch import canonicalize_cfg, resolve_mode, stage_params
from ..nn import mlp
from ..obs.registry import get_registry
from ..obs.trace import get_tracer
from .model import (MATMUL_PRECISION, M4Config, predict_queue,
                    predict_size, predict_sldn, spatial_update,
                    temporal_update)
from .probes import (M4_CHANNELS, ProbeConfig, finalize as _probe_finalize,
                     init_buffers as _probe_init, normalize_probes,
                     record as _probe_record)

BIG = 1e30

# Number of XLA traces per entry point. Python side effects inside a jitted
# function run only while tracing, so these count *compiles*, not calls —
# the batched-path test asserts run_many(B scenarios) costs exactly one.
TRACE_COUNTS = Counter()


def _build_snapshot(cfg: M4Config, static, link_occ, fid):
    """Incremental snapshot builder: candidates come from the membership
    lists of the event flow's <= P links (O(P·K_max) work, independent of
    arena size N), filtered by the carried occupancy bitmap. Emits slot
    0 = event flow, then the lowest-index active flows that share a link
    with it, ascending, and dump index N beyond."""
    SF = cfg.snap_flows
    N = static["flow_links"].shape[0]
    rows = static["occ_rows"][fid]                           # (P,)
    cand = static["link_members"][rows]                      # (P, K)
    occ = link_occ[rows]                                     # (P, K)
    vals = jnp.where(occ & (cand != fid), cand, N).reshape(-1)
    uniq = _dedupe_ascending(vals, SF - 1, N)
    others_valid = uniq < N
    snap_f = jnp.concatenate([fid[None].astype(uniq.dtype), uniq])
    snap_mask = jnp.concatenate([jnp.ones((1,), jnp.float32),
                                 others_valid.astype(jnp.float32)])
    return snap_f, snap_mask


def _dedupe_ascending(vals, k, sentinel):
    """First k distinct values of `vals` in ascending order, padded with
    `sentinel` (which must upper-bound every real value). Equivalent to
    jnp.unique(size=k, fill_value=sentinel) with a much cheaper lowering —
    the event step is op-dispatch-bound on CPU, and unique's sort + cumsum
    + gather chain costs tens of microseconds per event. Two regimes:

    - small k: k rounds of (min, mask-out-all-copies), two vector ops each
    - larger k: one sort, then first-occurrence compaction via a cumsum-
      indexed scatter-min (duplicates share their first occurrence's slot
      and equal value; overflow past k slots clips onto slot k-1, where
      scatter-min keeps the smallest = the true k-th distinct value)
    """
    if k <= 16:
        picks = []
        for _ in range(k):
            m = jnp.min(vals)
            picks.append(m)
            vals = jnp.where(vals == m, sentinel, vals)
        return jnp.stack(picks)
    s = jnp.sort(vals)
    first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    slot = jnp.minimum(jnp.cumsum(first) - 1, k - 1)
    return jnp.full((k,), sentinel, s.dtype).at[slot].min(s)


def _build_links(cfg: M4Config, flow_links, snap_f, snap_f_mask, num_links):
    """Snapshot link set (deduped, padded) + edge list — all snapshot-sized
    (SF·P), no full-arena pass.

    An edge's slot is its link's rank in the sorted set, counted by one
    (SF·P, SL) comparison: a binary search lowers to a `while` loop of
    gathers, nested in the event scan on the chip."""
    SF, P, SL = cfg.snap_flows, cfg.max_path, cfg.snap_links
    gl = flow_links[snap_f]                                  # (SF, P)
    gl = jnp.where((gl >= 0) & (snap_f_mask[:, None] > 0), gl, num_links)
    uniq = _dedupe_ascending(gl.reshape(-1), SL, num_links)
    snap_l = uniq
    snap_l_mask = (uniq < num_links).astype(jnp.float32)
    el = (uniq[None, :] < gl.reshape(-1, 1)).sum(-1, dtype=jnp.int32)
    edge_mask = (gl.reshape(-1) < num_links).astype(jnp.float32)
    el = jnp.where(edge_mask > 0, jnp.minimum(el, SL - 1), 0)
    return snap_l, snap_l_mask, el, edge_mask


def make_event_step(cfg: M4Config, static, num_links: int):
    """static: dict of arena constant arrays (flow_links, flow_feat,
    link_feat, ideal_fct, t_arrival, cfg_vec, link_members, occ_rows,
    occ_slots); num_links is static.

    One event: an O(P·K) snapshot from the occupancy arenas, the GRU and
    GNN through the kernel dispatch, and a scatter-back that redirects
    masked slots to the dump rows.
    """
    SF, P = cfg.snap_flows, cfg.max_path
    edge_f = jnp.repeat(jnp.arange(SF, dtype=jnp.int32), P)

    def event_step(params, state, t_ev, fid, is_arrival):
        """Process one flow-level event; returns (state, sldn_pred, snap)."""
        with jax.default_matmul_precision(MATMUL_PRECISION):
            return _event_step(params, state, t_ev, fid, is_arrival)

    def _event_step(params, state, t_ev, fid, is_arrival):
        flow_links = static["flow_links"]
        cfg_vec = static["cfg_vec"]
        N = flow_links.shape[0]
        with jax.named_scope("m4.snapshot"):
            snap_f, sfm = _build_snapshot(cfg, static, state["link_occ"],
                                          fid)
            # occupancy arenas: the event flow enters (arrival) / leaves
            # (departure) the membership slots of its own links — O(P)
            state["link_occ"] = state["link_occ"].at[
                static["occ_rows"][fid],
                static["occ_slots"][fid]].set(is_arrival)
            fgather = jnp.minimum(snap_f, N - 1)  # clamped (masked out)
            snap_l, slm, edge_l, edge_mask = _build_links(
                cfg, flow_links, fgather, sfm, num_links)
            sl_safe = jnp.minimum(snap_l, num_links)  # dump row = num_links
            lgather = jnp.minimum(snap_l, num_links - 1)

            f_h = state["flow_h"][snap_f]
            l_h = state["link_h"][sl_safe]
            f_feat = static["flow_feat"][fgather]
            l_feat = static["link_feat"][lgather]

            dt_f = t_ev - state["flow_last"][snap_f]
            dt_f = dt_f.at[0].set(jnp.where(is_arrival, 0.0, dt_f[0]))
            dt_l = t_ev - state["link_last"][sl_safe]

        with jax.named_scope("m4.heads"):
            # arrival: init slot-0 hidden state from static features (§3.2.1)
            fin = jnp.concatenate([static["flow_feat"][fid], cfg_vec], -1)
            h_new = jnp.tanh(mlp(params["flow_init"], fin))
            f_h = f_h.at[0].set(jnp.where(is_arrival, h_new, f_h[0]))

        with jax.named_scope("m4.temporal"):
            f_h, l_h = temporal_update(params, cfg, f_h, l_h, dt_f, dt_l,
                                       f_feat, l_feat, cfg_vec)
        with jax.named_scope("m4.spatial"):
            f_h2, l_h2 = spatial_update(params, cfg, f_h, l_h, edge_f,
                                        edge_l, edge_mask, cfg_vec)
        with jax.named_scope("m4.heads"):
            sldn = predict_sldn(params, f_h2,
                                static["flow_feat"][fgather, 1] * 8.0,
                                cfg_vec)
            # departure-time re-prediction for snapshot flows
            t_dep_new = (state["t_arr"][snap_f]
                         + sldn * static["ideal_fct"][fgather])
            t_dep_new = jnp.maximum(t_dep_new, t_ev + 1e-9)

        with jax.named_scope("m4.scatter"):
            # scatter back with masked slots *redirected to the dump row*
            # (index N / num_links) instead of blending old values back in
            # — live rows receive exactly f_h2/l_h2, the dump row absorbs
            # the rest, and the arenas update without a read-modify-write
            # of the whole (N, H) buffer
            idx_f = jnp.where(sfm > 0, snap_f, N)
            idx_l = jnp.where(slm > 0, sl_safe, num_links)
            state["flow_h"] = state["flow_h"].at[idx_f].set(f_h2)
            state["link_h"] = state["link_h"].at[idx_l].set(l_h2)
            state["flow_last"] = state["flow_last"].at[idx_f].set(t_ev)
            state["link_last"] = state["link_last"].at[idx_l].set(t_ev)
            state["t_dep"] = state["t_dep"].at[idx_f].set(t_dep_new)
        return state, sldn, (snap_f, sfm)

    return event_step


def init_sim_state(params, cfg: M4Config, static, N, num_links: int):
    """Arenas carry one extra 'dump' row (index N / num_links) that absorbs
    scatters from masked snapshot slots. `link_occ` mirrors the static
    `link_members` table: occ[l, k] == flow link_members[l, k] is active."""
    H = params["gru1"]["wh"].shape[0]
    L = num_links
    K = static["link_members"].shape[1]
    cfg_vec = static["cfg_vec"]
    l_in = jnp.concatenate(
        [static["link_feat"][:L],
         jnp.broadcast_to(cfg_vec, (L, cfg_vec.shape[0]))], -1)
    with jax.default_matmul_precision(MATMUL_PRECISION):
        link_h = jnp.tanh(mlp(params["link_init"], l_in))
    link_h = jnp.concatenate([link_h, jnp.zeros((1, H), jnp.float32)], 0)
    return dict(
        flow_h=jnp.zeros((N + 1, H), jnp.float32),
        link_h=link_h,
        flow_last=jnp.zeros((N + 1,), jnp.float32),
        link_last=jnp.zeros((L + 1,), jnp.float32),
        arrived=jnp.zeros((N + 1,), bool), done=jnp.zeros((N + 1,), bool),
        link_occ=jnp.zeros((L + 1, K), bool),
        t_dep=jnp.full((N + 1,), BIG, jnp.float32),
        fct=jnp.zeros((N + 1,), jnp.float32),
        t_arr=jnp.concatenate([jnp.asarray(static["t_arrival"]),
                               jnp.zeros((1,), jnp.float32)]))


def _probe_values(params, static, state, N, num_links):
    """Channel read-out thunks over the post-event carry: the simulator's
    *belief* about intermediate network state (the quantities the paper
    densely supervises). Thunks only execute on stride-hit events."""

    def active():
        return (state["arrived"] & ~state["done"])[:N].astype(jnp.float32)

    def link_queue():
        # MLP-queue head over every live link hidden state (log1p(KB) scale;
        # the host-side finalize converts to bytes)
        return predict_queue(params, state["link_h"][:num_links])

    def link_active():
        # active-flow count per link via the static path->slot tables;
        # invalid path slots scatter onto the dump row
        rows = static["occ_rows"]                            # (N, P)
        cnt = jnp.zeros((num_links + 1,), jnp.float32).at[rows].add(
            jnp.broadcast_to(active()[:, None], rows.shape))
        return cnt[:num_links]

    def flow_remaining():
        # MLP-size head: remaining *fraction*; zeroed outside a flow's
        # lifetime so the series reads as size -> 0 over the flow's life
        return predict_size(params, state["flow_h"][:N]) * active()

    return {"link_queue": link_queue, "link_active": link_active,
            "flow_remaining": flow_remaining}


def _open_loop_core(params, cfg: M4Config, num_links: int, static, arr_order,
                    arr_times, num_events=None, probes=None):
    N = arr_times.shape[0]
    # the kernels' weight layout, built here once and carried through the
    # scan as loop-invariant values rather than rebuilt in every event
    params = stage_params(params, resolve_mode(cfg.kernel_mode))
    step = make_event_step(cfg, static, num_links)
    state = init_sim_state(params, cfg, static, N, num_links)

    def body(carry, _):
        state, ptr, t = carry
        with jax.named_scope("m4.departure"):
            next_arr = jnp.where(ptr < N, arr_times[jnp.minimum(ptr, N - 1)],
                                 BIG)
            # invariant: t_dep rows < N are finite exactly for flows that
            # are arrived-and-not-done (init BIG, arrival/snapshot updates
            # touch only active rows, departure resets to BIG), so the
            # departure race reads the carry directly — no mask gathers
            dep_t = state["t_dep"][:N]
            dep_i = jnp.argmin(dep_t)
            next_dep = dep_t[dep_i]
            is_arr = next_arr <= next_dep
            t_ev = jnp.where(is_arr, next_arr, next_dep)
            fid = jnp.where(is_arr, arr_order[jnp.minimum(ptr, N - 1)], dep_i)

        state, _, _ = step(params, state, t_ev, fid, is_arr)
        with jax.named_scope("m4.scatter"):
            # every event at fid implies "arrived"; "done" iff departure
            # — plain sets, no read-modify-write; arrival-event writes of
            # fct / t_dep redirect to the dump row instead of blending
            fid_or_dump = jnp.where(is_arr, N, fid)
            state["arrived"] = state["arrived"].at[fid].set(True)
            state["done"] = state["done"].at[fid].set(~is_arr)
            state["fct"] = state["fct"].at[fid_or_dump].set(
                t_ev - state["t_arr"][fid])
            state["t_dep"] = state["t_dep"].at[fid_or_dump].set(BIG)
            ptr = ptr + is_arr.astype(jnp.int32)
        return (state, ptr, t_ev), None

    length = 2 * N if num_events is None else num_events
    if probes is None:
        # probes-off IS the pre-probe program: same carry, same xs=None
        # scan, same jaxpr — asserted in tests/test_obs.py
        (state, _, _), _ = jax.lax.scan(body, (state, jnp.int32(0), 0.0),
                                        None, length=length)
        return state["fct"][:N], state["done"][:N]

    bufs0 = _probe_init(probes, num_flows=N, num_links=num_links)

    def body_probed(carry, ev_idx):
        inner, bufs = carry
        (state, ptr, t_ev), _ = body(inner, None)
        vals = _probe_values(params, static, state, N, num_links)
        bufs = _probe_record(probes, bufs, ev_idx, t_ev, vals)
        return ((state, ptr, t_ev), bufs), None

    ((state, _, _), bufs), _ = jax.lax.scan(
        body_probed, ((state, jnp.int32(0), 0.0), bufs0),
        jnp.arange(length, dtype=jnp.int32))
    return state["fct"][:N], state["done"][:N], bufs


@partial(jax.jit, static_argnums=(1, 2),
         static_argnames=("num_events", "probes"))
def _open_loop_scan(params, cfg: M4Config, num_links: int, static, arr_order,
                    arr_times, num_events=None, probes=None):
    TRACE_COUNTS["open_loop"] += 1
    return _open_loop_core(params, cfg, num_links, static, arr_order,
                           arr_times, num_events, probes)


@partial(jax.jit, static_argnums=(1, 2),
         static_argnames=("num_events", "probes"))
def _open_loop_scan_batched(params, cfg: M4Config, num_links: int, static,
                            arr_order, arr_times, num_events=None,
                            probes=None):
    """vmap of the open-loop scan over B scenarios padded to one arena shape.
    Scenario axes: every leaf of `static`, plus arr_order/arr_times."""
    TRACE_COUNTS["open_loop_batched"] += 1

    def one(s, o, t):
        return _open_loop_core(params, cfg, num_links, s, o, t,
                               num_events, probes)

    return jax.vmap(one)(static, arr_order, arr_times)


def _sharded_body(params, cfg: M4Config, num_links: int, static, arr_order,
                  arr_times):
    """pmap(vmap(scan)): params broadcast to every device, scenario arenas
    sharded (D, B/D, ...) across them — one compile per sweep chunk,
    N/devices scenarios of work per device."""
    TRACE_COUNTS["open_loop_sharded"] += 1

    def one(s, o, t):
        return _open_loop_core(params, cfg, num_links, s, o, t)

    return jax.vmap(one)(static, arr_order, arr_times)


@lru_cache(maxsize=None)
def _sharded_scan(devices=None):
    """The sharded scan over `devices` (a tuple), or over every local
    device (None)."""
    return jax.pmap(_sharded_body, static_broadcasted_argnums=(1, 2),
                    in_axes=(None, None, None, 0, 0, 0), devices=devices)



@dataclass
class M4Result:
    fcts: np.ndarray
    slowdowns: np.ndarray
    wallclock: float          # the scan's wall time, dispatch to ready
    # finalized `repro.obs.timeseries/1` dict when a ProbeConfig was passed
    probes: object = None


def _finalize_m4_series(probes, bufs, flows, *, num_flows, num_links,
                        trim_links=None):
    """Host-side unit conversion of the raw m4 probe ring: remaining
    fraction x flow size -> bytes, MLP-queue log1p(KB) head -> bytes."""
    series = _probe_finalize(probes, bufs, num_flows=num_flows,
                             num_links=num_links, trim_flows=len(flows),
                             trim_links=trim_links)
    ch = series["channels"]
    if "flow_remaining" in ch:
        sizes = np.array([f.size for f in flows], np.float64)
        ch["flow_remaining"] = ch["flow_remaining"] * sizes[None, :]
    if "link_queue" in ch:
        ch["link_queue"] = np.expm1(np.maximum(ch["link_queue"], 0.0)) * 1e3
    series["meta"] = {"backend": "m4",
                      "units": {"link_queue": "bytes",
                                "link_active": "flows",
                                "flow_remaining": "bytes"}}
    return series


def _membership_tables(flow_links: np.ndarray, num_links: int,
                       k_total=None):
    """link -> flow membership + each flow's slots in it (host-side).

    Returns (link_members (L+1, K): flow ids per link, padded with the dump
    flow id N; occ_rows/occ_slots (N, P): where flow f's path position p
    lives in the table — invalid positions point at the dump row L, slot 0,
    so O(P) occupancy scatters never need a branch). K is the max link
    degree (or `k_total`, to pad a batch to one shape)."""
    N, P = flow_links.shape
    L = num_links
    valid = flow_links >= 0
    counts = np.bincount(flow_links[valid].ravel(), minlength=L) \
        if valid.any() else np.zeros(L, np.int64)
    K = int(max(1, counts.max() if counts.size else 1))
    if k_total is not None:
        assert k_total >= K, (k_total, K)
        K = int(k_total)
    link_members = np.full((L + 1, K), N, np.int32)
    occ_rows = np.full((N, P), L, np.int32)
    occ_slots = np.zeros((N, P), np.int32)
    fill = np.zeros(L + 1, np.int64)
    for f in range(N):
        for p in range(P):
            l = flow_links[f, p]
            if l < 0:
                continue
            link_members[l, fill[l]] = f
            occ_rows[f, p] = l
            occ_slots[f, p] = fill[l]
            fill[l] += 1
    return link_members, occ_rows, occ_slots


def max_link_degree(flows, max_path: int) -> int:
    """Max number of flows traversing any one link (the K of the
    membership table); batch callers take the max across scenarios."""
    c = Counter()
    for f in flows:
        for l in f.path[:max_path]:
            c[l] += 1
    return max(c.values(), default=1)


def make_static(topo, flows, net_config, cfg: M4Config, n_total=None,
                l_total=None, k_total=None):
    """Arena constants for one scenario. `n_total`/`l_total`/`k_total` pad
    the flow, link and membership axes to a shared shape so scenarios can
    be stacked and vmapped: padded flows have no links and arrive at t=BIG
    (after every real event, so they only ever touch dump/own rows), padded
    links are on no path."""
    P = cfg.max_path
    n = len(flows)
    N = n if n_total is None else n_total
    L = topo.num_links if l_total is None else l_total
    assert N >= n and L >= topo.num_links
    flow_links = np.full((N, P), -1, np.int32)
    for f in flows:
        flow_links[f.fid, :len(f.path)] = f.path[:P]
    sizes = np.zeros(N, np.float32)
    sizes[:n] = [f.size for f in flows]
    nlinks = (flow_links >= 0).sum(1).astype(np.float32)
    ideal = np.full(N, 1e-9, np.float32)
    ideal[:n] = [topo.ideal_fct(f.size, f.path) for f in flows]
    t_arrival = np.full(N, BIG, np.float32)
    t_arrival[:n] = [f.t_arrival for f in flows]
    flow_feat = np.stack([np.log1p(sizes / 1e3) / 10.0, nlinks / 8.0,
                          np.log1p(ideal / 1e-6) / 10.0], -1)
    cap = np.full(L, topo.capacity.max(), np.float64)
    cap[:topo.num_links] = topo.capacity
    link_members, occ_rows, occ_slots = _membership_tables(
        flow_links, L, k_total)
    return {
        "flow_links": jnp.asarray(flow_links),
        "flow_feat": jnp.asarray(flow_feat, jnp.float32),
        "link_feat": jnp.asarray(np.log1p(cap / 1e9)[:, None] / 10.0,
                                 jnp.float32),
        "ideal_fct": jnp.asarray(ideal),
        "t_arrival": jnp.asarray(t_arrival),
        "cfg_vec": jnp.asarray(net_config.feature_vec()),
        "link_members": jnp.asarray(link_members),
        "occ_rows": jnp.asarray(occ_rows),
        "occ_slots": jnp.asarray(occ_slots),
    }, L, ideal


def _arrival_order(static):
    """Stable arrival order over the (possibly padded) arena; padded flows
    sit at t=BIG and therefore sort last."""
    t = np.asarray(static["t_arrival"])
    order = np.argsort(t, kind="stable").astype(np.int32)
    return order, t[order].astype(np.float32)


def simulate_open_loop(params, cfg: M4Config, topo, net_config, flows, *,
                       probes: ProbeConfig = None) -> M4Result:
    """One scenario through the open-loop scan.

    `probes` (a static `ProbeConfig`) additionally records intermediate-
    state time series into `M4Result.probes`; None compiles the identical
    probe-free program.
    The call is the `m4.run` span, with the children `m4.build`,
    `m4.scan` and `m4.result` (`repro.obs`)."""
    tracer = get_tracer()
    with tracer.span("m4.run"):
        cfg = canonicalize_cfg(cfg)
        probes = normalize_probes(probes, M4_CHANNELS)
        with tracer.span("m4.build"):
            static, num_links, ideal = make_static(topo, flows, net_config,
                                                   cfg)
            order, times = _arrival_order(static)
            args = (params, cfg, num_links, static, jnp.asarray(order),
                    jnp.asarray(times))
        with tracer.span("m4.scan"):
            t0 = time.perf_counter()
            out = _open_loop_scan(*args, probes=probes)
            out = jax.block_until_ready(out)
            wall = time.perf_counter() - t0
        with tracer.span("m4.result"):
            series = None
            if probes is None:
                fct, done = out
            else:
                fct, done, bufs = out
                series = _finalize_m4_series(probes, bufs, flows,
                                             num_flows=len(flows),
                                             num_links=num_links)
            fct = np.asarray(fct)
            return M4Result(fcts=fct, slowdowns=fct / ideal, wallclock=wall,
                            probes=series)


def batch_shape(cfg: M4Config, scenarios) -> list:
    """(flows, links, link degree K) of each (topo, net_config, flows)
    scenario: what `stack_scenarios` pads to the batch's largest."""
    return [(len(flows), topo.num_links, max_link_degree(flows, cfg.max_path))
            for topo, _, flows in scenarios]


def padding_shares(shape) -> dict:
    """The batch gauges of a `batch_shape`: its size, and the share of the
    padded membership slots (K), events (2·N) and links that is padding,
    1 − Σxᵢ / (B·max x)."""
    B = len(shape)
    n, l, k = (np.asarray(c, np.float64) for c in zip(*shape))
    return {"m4.batch.size": B,
            "m4.batch.k_pad_share": float(1 - k.sum() / (B * k.max())),
            "m4.batch.event_pad_share": float(1 - n.sum() / (B * n.max())),
            "m4.batch.link_pad_share": float(1 - l.sum() / (B * l.max()))}


def stack_scenarios(cfg: M4Config, scenarios, shape=None):
    """Pad (topo, net_config, flows) scenarios to one arena shape and
    stack them: returns (static, arrival order, arrival times, padded
    link count, per-scenario ideal FCTs) — the inputs of the batched
    scans, whose leading axis is the scenario. `shape` is the scenarios'
    `batch_shape`, when the caller has it."""
    shape = batch_shape(cfg, scenarios) if shape is None else shape
    n_max, l_max, k_max = (max(c) for c in zip(*shape))
    statics, orders, times, ideals = [], [], [], []
    for topo, net_config, flows in scenarios:
        static, _, ideal = make_static(topo, flows, net_config, cfg,
                                       n_total=n_max, l_total=l_max,
                                       k_total=k_max)
        order, t = _arrival_order(static)
        statics.append(static)
        orders.append(order)
        times.append(t)
        ideals.append(ideal)
    batched = {k: jnp.stack([s[k] for s in statics]) for k in statics[0]}
    return (batched, jnp.asarray(np.stack(orders)),
            jnp.asarray(np.stack(times)), l_max, ideals)


def simulate_open_loop_batch(params, cfg: M4Config, scenarios, *,
                             probes: ProbeConfig = None,
                             devices=None) -> list:
    """Run many scenarios in ONE compiled vmapped scan.

    scenarios: sequence of (topo, net_config, flows). Arenas are padded to
    the largest flow/link/degree count in the batch; padded work is dead
    weight in exchange for a single XLA program (no per-scenario retraces)
    and batch-parallel execution of the event steps. `probes` records
    per-scenario intermediate-state series (vmapped ring buffers, sliced
    and trimmed per scenario on the host); the multi-device sharded path
    is probe-free, so probed batches stay on the vmapped path.

    `devices` holds the batch to those devices: one runs the vmapped scan
    there, several shard the batch across them. None (the default) shards
    across every local device when there is more than one. A caller that
    owns one chip of a host passes that chip, so that what it runs, and
    what it measures, does not depend on how many chips the host exposes.

    The call is the `m4.run_many` span (children `m4.build`, `m4.scan`,
    `m4.result`); the batch's `padding_shares` are set as registry gauges
    and as attributes of that span.
    """
    scenarios = list(scenarios)
    if not scenarios:
        return []
    tracer = get_tracer()
    with tracer.span("m4.run_many") as span:
        cfg = canonicalize_cfg(cfg)
        probes = normalize_probes(probes, M4_CHANNELS)
        with tracer.span("m4.build"):
            shape = batch_shape(cfg, scenarios)
            batched, order_b, times_b, l_max, ideals = stack_scenarios(
                cfg, scenarios, shape)
        registry = get_registry()
        for name, value in padding_shares(shape).items():
            registry.set_gauge(name, value)
            span.attr(name, value)
        counts = [n for n, _, _ in shape]
        n_max = max(counts)
        D = jax.local_device_count() if devices is None else len(devices)
        sharded = D > 1 and len(scenarios) >= D and probes is None
        bufs = None
        with tracer.span("m4.scan"):
            t0 = time.perf_counter()
            if sharded:
                from .sharding import shard_leaves
                scan = _sharded_scan(None if devices is None
                                     else tuple(devices))
                res = scan(params, cfg, l_max, shard_leaves(batched, D),
                           shard_leaves(order_b, D), shard_leaves(times_b, D))
            else:
                args = (params, batched, order_b, times_b)
                if devices is not None:
                    args = jax.device_put(args, devices[0])
                res = _open_loop_scan_batched(
                    args[0], cfg, l_max, *args[1:], probes=probes)
            res = jax.block_until_ready(res)
            wall = time.perf_counter() - t0
        with tracer.span("m4.result"):
            if probes is None:
                fct, done = res
            else:
                fct, done, bufs = res
            fct = np.asarray(fct)
            if sharded:
                from .sharding import unshard
                fct = unshard(fct, len(scenarios))
            out = []
            for b, n in enumerate(counts):
                f = fct[b, :n]
                series = None
                if bufs is not None:
                    topo_b, _, flows_b = scenarios[b]
                    series = _finalize_m4_series(
                        probes, {k: v[b] for k, v in bufs.items()}, flows_b,
                        num_flows=n_max, num_links=l_max,
                        trim_links=topo_b.num_links)
                out.append(M4Result(fcts=f, slowdowns=f / ideals[b][:n],
                                    wallclock=wall / len(scenarios),
                                    probes=series))
            return out


@partial(jax.jit, static_argnums=(3,))
def _next_departure_scan(t_dep, arrived, done, N: int):
    """Device-side masked argmin over the active arena; returns two
    scalars (time, fid) so the closed-loop driver never pulls the full
    (N,) departure arena to host per step."""
    dep_t = jnp.where(arrived & ~done, t_dep, BIG)[:N]
    i = jnp.argmin(dep_t)
    return dep_t[i], i


class M4Simulator:
    """Single-event interface for closed-loop traffic generators (§5.4).

    The driver calls `peek_next_departure()` / `advance_to_arrival(flow)` —
    mirroring the paper's traffic-generator <-> backend protocol (Fig 5).
    Flow arena is pre-sized; closed-loop apps pass their full flow backlog
    and release arrivals dynamically. The jitted event step donates the
    state arenas (`donate_argnums`), so each step updates the carry in
    place instead of copying ~N·H floats per event; `next_departure` is a
    jitted masked argmin returning two scalars (no full-arena host sync).
    """

    def __init__(self, params, cfg: M4Config, topo, net_config, flows):
        cfg = canonicalize_cfg(cfg)
        # kernel-layout weights once per session, not once per event
        self.params = stage_params(params, cfg.kernel_mode)
        self.cfg = cfg
        self.static, self.num_links, self.ideal = make_static(
            topo, flows, net_config, cfg)
        self.N = len(flows)
        self.state = init_sim_state(params, cfg, self.static, self.N,
                                    self.num_links)
        self._step = jax.jit(make_event_step(cfg, self.static, self.num_links),
                             donate_argnums=(1,))
        self.t = 0.0
        self.fcts = np.full(self.N, np.nan, np.float64)
        # Host-side mirror of state["t_arr"]: arrival times only ever enter
        # the device arena from host floats (inject_arrival), so the mirror
        # lets commit_departure compute FCTs without a per-departure device
        # pull blocking the donated-arena event pipeline.
        self.t_arr_host = np.asarray(self.state["t_arr"],
                                     np.float64)[:self.N].copy()

    def next_departure(self):
        t, i = _next_departure_scan(self.state["t_dep"],
                                    self.state["arrived"],
                                    self.state["done"], self.N)
        t = float(t)
        return (None, None) if t >= BIG / 2 else (t, int(i))

    def inject_arrival(self, fid: int, t: float):
        self.t = t
        # float32 cast keeps the mirror bitwise-equal to the device value
        self.t_arr_host[fid] = np.float32(t)
        self.state["t_arr"] = self.state["t_arr"].at[fid].set(t)
        self.state, _, _ = self._step(self.params, self.state, jnp.float32(t),
                                      jnp.int32(fid), jnp.bool_(True))
        self.state["arrived"] = self.state["arrived"].at[fid].set(True)

    def commit_departure(self, fid: int, t: float):
        self.t = t
        self.state, _, _ = self._step(self.params, self.state, jnp.float32(t),
                                      jnp.int32(fid), jnp.bool_(False))
        self.state["done"] = self.state["done"].at[fid].set(True)
        self.state["t_dep"] = self.state["t_dep"].at[fid].set(BIG)
        self.fcts[fid] = t - self.t_arr_host[fid]

    def completion_times(self) -> np.ndarray:
        """Absolute completion time per flow (NaN while unfinished) — the
        `repro.sim` closed-loop session contract."""
        return np.where(np.isfinite(self.fcts),
                        self.t_arr_host + self.fcts, np.nan)
