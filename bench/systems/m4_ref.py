"""Plain reference of m4's open-loop simulation (arXiv:2503.01770 §3),
and the benchmark's own weights. Imports nothing of the program.

The simulation: events race the next arrival against the earliest
predicted departure among active flows (ties go to the arrival, then to
the lower flow id). Each event builds a snapshot of the event flow and
the active flows that share a link with it (the lowest ids first, at most
`snap_flows`), and the distinct links of those flows (ascending, at most
`snap_links`). The snapshot's hidden states advance through the temporal
GRUs (GRU-1 for flows, GRU-A for links), three GraphSAGE rounds with sum
aggregation on the flow-link graph, and the post-GNN GRUs (GRU-2, GRU-B);
the slowdown head then re-predicts the departure of every snapshot flow:
t_dep = t_arrival + slowdown · ideal FCT, never before t_event + 1 ns.

Written as dense, straightforward jax.numpy: an O(N·P²) search for the
flows that share a link, segment sums for the GNN, every matmul through
`mm`, which is float32 at `highest` precision or, for the control, the
three-pass bfloat16 product that `high` precision computes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic
from bench.systems.lowp import round_bf16

BIG = 1e30
FLOW_FEAT, LINK_FEAT = 3, 1


# ------------------------------------------------------------------ weights
def param_shapes(m: dict) -> dict:
    """The parameter tree m4 takes, as {path: shape}: linear layers
    {"w": (in, out), "b": (out,)}, GRU cells {"wi": (in, 3H), "wh": (H, 3H),
    "bi", "bh"} with gates in the order r, z, n."""
    H, G, M, C = m["hidden"], m["gnn_dim"], m["mlp_hidden"], m["cfg_dim"]

    def mlp(sizes):
        return {f"l{i}": {"w": (a, b), "b": (b,)}
                for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:]))}

    def gru(d_in):
        return {"wi": (d_in, 3 * H), "wh": (H, 3 * H), "bi": (3 * H,),
                "bh": (3 * H,)}
    return {
        "flow_init": mlp([FLOW_FEAT + C, M, H]),
        "link_init": mlp([LINK_FEAT + C, M, H]),
        "gru1": gru(1 + FLOW_FEAT + C), "gruA": gru(1 + LINK_FEAT + C),
        "proj_f": {"w": (H, G), "b": (G,)}, "proj_l": {"w": (H, G), "b": (G,)},
        "gnn": [{"wf": {"w": (2 * G, G), "b": (G,)},
                 "wl": {"w": (2 * G, G), "b": (G,)}}
                for _ in range(m["gnn_layers"])],
        "gru2": gru(G + C), "gruB": gru(G + C),
        "mlp_sldn": mlp([H + 1 + C, M, M, 1]),
        "mlp_size": mlp([H, M, M, 1]),
        "mlp_queue": mlp([H, M, M, 1]),
    }


def _is_shape(x):
    return isinstance(x, tuple) and all(isinstance(d, int) for d in x)


def make_params(seed: int, m: dict):
    """Random float32 weights from `seed`, made on the device in one
    jitted call: matrices N(0, 1/fan_in), GRU matrices U(±1/√H), every bias
    N(0, 0.05²) so that no bias path is a zero."""
    flat, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(m), is_leaf=_is_shape)
    bound = 1.0 / np.sqrt(m["hidden"])

    def one(key, path, shape):
        if len(shape) == 1:
            return 0.05 * jax.random.normal(key, shape, jnp.float32)
        if path[-1].key in ("wi", "wh"):
            return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[0])

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree.unflatten(tree, [one(k, p, s) for k, (p, s)
                                         in zip(keys, flat)])
    return build(jax.random.PRNGKey(seed))


# ------------------------------------------------------------------- inputs
def inputs(scen, m: dict) -> dict:
    """Host arrays of one scenario."""
    P = m["max_path"]
    flow_links = np.full((scen.num_flows, P), -1, np.int32)
    for i, p in enumerate(scen.paths):
        flow_links[i, :len(p[:P])] = p[:P]
    ideal = scen.ideal_fct().astype(np.float32)
    size = scen.size.astype(np.float32)
    nlinks = (flow_links >= 0).sum(1).astype(np.float32)
    flow_feat = np.stack([np.log1p(size / 1e3) / 10.0, nlinks / 8.0,
                          np.log1p(ideal / 1e-6) / 10.0], -1)
    t_arr = scen.t_arrival.astype(np.float32)
    cap = scen.capacity
    return {"flow_links": flow_links, "flow_feat": flow_feat.astype(np.float32),
            "link_feat": (np.log1p(cap / 1e9)[:, None] / 10.0).astype(
                np.float32),
            "ideal": ideal, "t_arr": t_arr,
            "order": np.argsort(t_arr, kind="stable").astype(np.int32),
            "cfg_vec": traffic.cfg_vec(scen.net)}


# --------------------------------------------------------------- arithmetic
def _mm_highest(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _mm_high(a, b):
    """bfloat16 three-pass product, as `high` precision computes it: each
    factor split into a bfloat16 head and tail, hi·hi + hi·lo + lo·hi."""
    def split(x):
        hi = round_bf16(x)
        return hi, round_bf16(x - hi)
    ah, al = split(a)
    bh, bl = split(b)
    return _mm_highest(ah, bh) + (_mm_highest(ah, bl) + _mm_highest(al, bh))


MATMULS = {"highest": _mm_highest, "high": _mm_high}


def _linear(p, x, mm):
    return mm(x, p["w"]) + p["b"]


def _mlp(p, x, mm):
    for i in range(len(p)):
        x = _linear(p[f"l{i}"], x, mm)
        if i < len(p) - 1:
            x = jax.nn.relu(x)
    return x


def _gru(p, x, h, mm):
    H = h.shape[-1]
    gi = mm(x, p["wi"]) + p["bi"]
    gh = mm(h, p["wh"]) + p["bh"]
    r = jax.nn.sigmoid(gi[:, :H] + gh[:, :H])
    z = jax.nn.sigmoid(gi[:, H:2 * H] + gh[:, H:2 * H])
    n = jnp.tanh(gi[:, 2 * H:] + r * gh[:, 2 * H:])
    return (1.0 - z) * n + z * h


def _time_feat(dt):
    return jnp.log1p(jnp.maximum(dt, 0.0) / 1e-6) / 10.0


def _first_distinct(vals, k, sentinel):
    """The first k distinct values of `vals` below `sentinel`, ascending,
    padded with `sentinel`; and how many distinct values there were."""
    s = jnp.sort(vals)
    first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    first &= s < sentinel
    rank = jnp.cumsum(first) - 1
    slot = jnp.where(first & (rank < k), rank, k)
    return (jnp.full((k + 1,), sentinel, s.dtype).at[slot].set(s)[:k],
            rank[-1] + 1)


# --------------------------------------------------------------- simulation
def _simulate(params, x, *, m, precision):
    mm = MATMULS[precision]
    SF, SL, P = m["snap_flows"], m["snap_links"], m["max_path"]
    flow_links, flow_feat = x["flow_links"], x["flow_feat"]
    ideal, t_arr, order, cfg = x["ideal"], x["t_arr"], x["order"], x["cfg_vec"]
    N, L = flow_links.shape[0], x["link_feat"].shape[0]
    H = params["gru1"]["wh"].shape[0]
    ids = jnp.arange(N, dtype=jnp.int32)
    edge_f = jnp.repeat(jnp.arange(SF, dtype=jnp.int32), P)
    cfg_f = jnp.broadcast_to(cfg, (SF, cfg.shape[0]))
    cfg_l = jnp.broadcast_to(cfg, (SL, cfg.shape[0]))

    link_h = jnp.tanh(_mlp(params["link_init"], jnp.concatenate(
        [x["link_feat"], jnp.broadcast_to(cfg, (L, cfg.shape[0]))], -1), mm))
    st = dict(flow_h=jnp.zeros((N, H), jnp.float32), link_h=link_h,
              flow_last=jnp.zeros((N,), jnp.float32),
              link_last=jnp.zeros((L,), jnp.float32),
              active=jnp.zeros((N,), bool),
              t_dep=jnp.full((N,), BIG, jnp.float32),
              fct=jnp.zeros((N,), jnp.float32))

    def event(carry, _):
        st, ptr, full = carry
        next_arr = jnp.where(ptr < N, t_arr[order[jnp.minimum(ptr, N - 1)]],
                             BIG)
        dep = jnp.where(st["active"], st["t_dep"], BIG)
        dep_i = jnp.argmin(dep)
        is_arr = next_arr <= dep[dep_i]
        t_ev = jnp.where(is_arr, next_arr, dep[dep_i])
        fid = jnp.where(is_arr, order[jnp.minimum(ptr, N - 1)], dep_i)

        # snapshot flows: the event flow, then active flows sharing a link
        ev = flow_links[fid]
        share = ((flow_links[:, :, None] == ev[None, None, :])
                 & (flow_links[:, :, None] >= 0)).any((1, 2))
        sel = share & st["active"] & (ids != fid)
        rank = jnp.cumsum(sel) - 1
        slot = jnp.where(sel & (rank < SF - 1), rank, SF - 1)
        others = jnp.full((SF,), N, jnp.int32).at[slot].set(ids)[:SF - 1]
        snap_f = jnp.concatenate([fid[None].astype(jnp.int32), others])
        fmask = snap_f < N
        fg = jnp.minimum(snap_f, N - 1)

        # snapshot links and the flow-link edges among them
        gl = jnp.where((flow_links[fg] >= 0) & fmask[:, None],
                       flow_links[fg], L).reshape(-1)
        snap_l, n_links = _first_distinct(gl, SL, L)
        lmask = snap_l < L
        lg = jnp.minimum(snap_l, L - 1)
        edge_l = jnp.minimum(jnp.searchsorted(snap_l, gl), SL - 1)
        emask = ((gl < L) & (snap_l[edge_l] == gl)).astype(jnp.float32)

        f_h = st["flow_h"][fg]
        h0 = jnp.tanh(_mlp(params["flow_init"], jnp.concatenate(
            [flow_feat[fid], cfg])[None], mm))[0]
        f_h = f_h.at[0].set(jnp.where(is_arr, h0, f_h[0]))
        dt_f = t_ev - st["flow_last"][fg]
        dt_f = dt_f.at[0].set(jnp.where(is_arr, 0.0, dt_f[0]))
        dt_l = t_ev - st["link_last"][lg]

        # temporal GRUs
        f_h = _gru(params["gru1"], jnp.concatenate(
            [_time_feat(dt_f)[:, None], flow_feat[fg], cfg_f], -1), f_h, mm)
        l_h = _gru(params["gruA"], jnp.concatenate(
            [_time_feat(dt_l)[:, None], x["link_feat"][lg], cfg_l], -1),
            st["link_h"][lg], mm)
        # GraphSAGE rounds, sum aggregation
        f = jax.nn.relu(_linear(params["proj_f"], f_h, mm))
        l = jax.nn.relu(_linear(params["proj_l"], l_h, mm))
        for layer in params["gnn"]:
            agg_f = jax.ops.segment_sum(l[edge_l] * emask[:, None], edge_f,
                                        num_segments=SF)
            agg_l = jax.ops.segment_sum(f[edge_f] * emask[:, None], edge_l,
                                        num_segments=SL)
            f, l = (jax.nn.relu(_linear(layer["wf"],
                                        jnp.concatenate([f, agg_f], -1), mm)),
                    jax.nn.relu(_linear(layer["wl"],
                                        jnp.concatenate([l, agg_l], -1), mm)))
        f_h2 = _gru(params["gru2"], jnp.concatenate([f, cfg_f], -1), f_h, mm)
        l_h2 = _gru(params["gruB"], jnp.concatenate([l, cfg_l], -1), l_h, mm)
        # slowdown head -> departure re-prediction
        sldn = 1.0 + jax.nn.softplus(_mlp(params["mlp_sldn"], jnp.concatenate(
            [f_h2, flow_feat[fg, 1:2], cfg_f], -1), mm)[:, 0])
        t_dep = jnp.maximum(t_arr[fg] + sldn * ideal[fg], t_ev + 1e-9)

        fi = jnp.where(fmask, snap_f, N)          # N, L: out of range, dropped
        li = jnp.where(lmask, snap_l, L)
        st = dict(st)
        st["flow_h"] = st["flow_h"].at[fi].set(f_h2, mode="drop")
        st["link_h"] = st["link_h"].at[li].set(l_h2, mode="drop")
        st["flow_last"] = st["flow_last"].at[fi].set(t_ev, mode="drop")
        st["link_last"] = st["link_last"].at[li].set(t_ev, mode="drop")
        st["t_dep"] = st["t_dep"].at[fi].set(t_dep, mode="drop")
        st["active"] = st["active"].at[fid].set(is_arr)
        st["fct"] = st["fct"].at[fid].set(
            jnp.where(is_arr, st["fct"][fid], t_ev - t_arr[fid]))
        st["t_dep"] = st["t_dep"].at[fid].set(
            jnp.where(is_arr, st["t_dep"][fid], BIG))
        # events whose snapshot had more sharing flows, or more links, than
        # it holds: the lowest ids are kept, edges to dropped links dropped
        full = full + jnp.stack([rank[-1] + 1 > SF - 1, n_links > SL])
        return (st, ptr + is_arr.astype(jnp.int32), full), None

    (st, _, full), _ = jax.lax.scan(
        event, (st, jnp.int32(0), jnp.zeros((2,), jnp.int32)), None,
        length=2 * N)
    return st["fct"], full


@partial(jax.jit, static_argnames=("m", "precision"))
def _simulate_jit(params, x, m, precision):
    return _simulate(params, x, m=dict(m), precision=precision)


def simulate(params, scen, m: dict, precision: str = "highest") -> tuple:
    """Per-flow FCTs of the scenario; and the number of events whose
    snapshot overflowed in flows and in links."""
    x = {k: jnp.asarray(v) for k, v in inputs(scen, m).items()}
    fct, full = _simulate_jit(params, x, tuple(sorted(m.items())), precision)
    full = np.asarray(full)
    return (np.asarray(fct), {"flows_overflow": int(full[0]),
                              "links_overflow": int(full[1])})
