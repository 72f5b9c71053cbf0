"""m4's neural architecture (§3.2, §4).

Four GRUs (GRU-1/GRU-A temporal for flows/links, GRU-2/GRU-B post-GNN),
a 3-layer GraphSAGE GNN (sum aggregator) on the bipartite flow-link
snapshot graph, and three query MLPs (FCT slowdown, remaining size, queue
length). Defaults follow the paper: 400-d hidden states, 300-d GNN
embeddings, 200-d 2-layer MLPs, 9-d network-config vector input.

TPU adaptation (DESIGN.md §3): snapshots are fixed-size padded index sets
(SNAP_F flows, SNAP_L links, max path P), so one event step is a single
static XLA program. The GRU cells and GNN rounds execute through
`repro.kernels.dispatch` — compiled Pallas kernels on TPU, the jnp
reference path elsewhere, overridable with REPRO_KERNELS
(`M4Config.kernel_mode` pins the resolved mode into the jit cache key).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ..nn import gru_init, linear, linear_init, mlp, mlp_init

# Precision of every f32 matmul of m4, Pallas kernels included: the event
# step (`core.simulate`) and the training scan (`core.training`) are
# traced under `jax.default_matmul_precision(MATMUL_PRECISION)`. The TPU's
# default rounds matmul inputs below f32: on the chip that put `run_many`
# up to 1e-3 from `run` on single flows, and the 4-chip training gradient
# 2e-3 from the one-chip one. "highest" keeps f32 in XLA and in Mosaic;
# on the CPU it changes nothing.
MATMUL_PRECISION = "highest"


@dataclass(frozen=True)
class M4Config:
    hidden: int = 400
    gnn_dim: int = 300
    mlp_hidden: int = 200
    gnn_layers: int = 3
    snap_flows: int = 64     # SNAP_F
    snap_links: int = 128    # SNAP_L
    max_path: int = 8        # P
    cfg_dim: int = 9
    dense_sldn: bool = True
    # Kernel execution mode for the GRU/GNN hot path: None = auto (TPU ->
    # compiled Pallas, else jnp), or one of repro.kernels.dispatch.MODES.
    # Entry points pin this to a concrete mode (dispatch.canonicalize_cfg)
    # so it lands in the jit cache key; REPRO_KERNELS overrides it.
    kernel_mode: str | None = None

    @property
    def use_pallas(self) -> bool:
        """True when the resolved mode runs the Pallas kernel code."""
        from ..kernels.dispatch import resolve_mode
        return resolve_mode(self.kernel_mode) != "xla"

    @property
    def flow_feat(self):
        return 3  # log size, n_links, log ideal_fct

    @property
    def link_feat(self):
        return 1  # log capacity


def init_m4(key, cfg: M4Config):
    H, G, M, C = cfg.hidden, cfg.gnn_dim, cfg.mlp_hidden, cfg.cfg_dim
    ks = jax.random.split(key, 16)
    p = {
        "flow_init": mlp_init(ks[0], [cfg.flow_feat + C, M, H]),
        "link_init": mlp_init(ks[1], [cfg.link_feat + C, M, H]),
        "gru1": gru_init(ks[2], 1 + cfg.flow_feat + C, H),   # flow temporal
        "gruA": gru_init(ks[3], 1 + cfg.link_feat + C, H),   # link temporal
        "proj_f": linear_init(ks[4], H, G),
        "proj_l": linear_init(ks[5], H, G),
        "gnn": [
            {"wf": linear_init(jax.random.fold_in(ks[6], i), 2 * G, G),
             "wl": linear_init(jax.random.fold_in(ks[7], i), 2 * G, G)}
            for i in range(cfg.gnn_layers)
        ],
        "gru2": gru_init(ks[8], G + C, H),                   # flow post-GNN
        "gruB": gru_init(ks[9], G + C, H),                   # link post-GNN
        "mlp_sldn": mlp_init(ks[10], [H + 1 + C, M, M, 1]),
        "mlp_size": mlp_init(ks[11], [H, M, M, 1]),
        "mlp_queue": mlp_init(ks[12], [H, M, M, 1]),
    }
    return p


# ---------------------------------------------------------------- features
def time_feat(dt):
    """dt seconds -> bounded feature."""
    return jnp.log1p(jnp.maximum(dt, 0.0) / 1e-6) / 10.0


def flow_static_feat(size_bytes, n_links, ideal_fct):
    return jnp.stack([
        jnp.log1p(size_bytes / 1e3) / 10.0,
        n_links / 8.0,
        jnp.log1p(ideal_fct / 1e-6) / 10.0,
    ], axis=-1)


def link_static_feat(capacity):
    return jnp.log1p(capacity / 1e9)[..., None] / 10.0


# ---------------------------------------------------------------- GNN
def _bipartite_round(layer, f_emb, l_emb, edge_f, edge_l, edge_mask, n_links):
    """One GraphSAGE round with sum aggregation.

    f_emb: (F, G), l_emb: (L, G); edges (E,) flow-slot / link-slot / mask.
    """
    ef = f_emb[edge_f] * edge_mask[:, None]
    agg_l = jax.ops.segment_sum(ef, edge_l, num_segments=n_links)
    el = l_emb[edge_l] * edge_mask[:, None]
    agg_f = jax.ops.segment_sum(el, edge_f, num_segments=f_emb.shape[0])
    f_new = jax.nn.relu(linear(layer["wf"], jnp.concatenate([f_emb, agg_f], -1)))
    l_new = jax.nn.relu(linear(layer["wl"], jnp.concatenate([l_emb, agg_l], -1)))
    return f_new, l_new


def gnn_forward(params, cfg: M4Config, f_h, l_h, edge_f, edge_l, edge_mask,
                ref_impl=False):
    """f_h: (SNAP_F, H), l_h: (SNAP_L, H) -> GNN embeddings (·, G).

    `ref_impl=True` forces the original segment-sum formulation (the seed
    program) regardless of kernel mode — kept as the oracle behind the
    legacy dense event step and the kernel parity tests; the production
    path goes through `repro.kernels.dispatch` (incidence matmuls on XLA,
    the fused Pallas kernel on TPU — same math, different execution)."""
    from ..kernels import dispatch
    f = jax.nn.relu(linear(params["proj_f"], f_h))
    l = jax.nn.relu(linear(params["proj_l"], l_h))
    if ref_impl:
        for layer in params["gnn"]:
            f, l = _bipartite_round(layer, f, l, edge_f, edge_l, edge_mask,
                                    cfg.snap_links)
        return f, l
    return dispatch.gnn_rounds(params["gnn"], f, l, edge_f, edge_l,
                               edge_mask, cfg.snap_links,
                               mode=dispatch.resolve_mode(cfg.kernel_mode))


# ---------------------------------------------------------------- queries
def predict_sldn(params, flow_h, n_links, cfg_vec):
    """-> FCT slowdown (>= 1)."""
    B = flow_h.shape[0]
    x = jnp.concatenate(
        [flow_h, n_links[:, None] / 8.0,
         jnp.broadcast_to(cfg_vec, (B, cfg_vec.shape[-1]))], axis=-1)
    return 1.0 + jax.nn.softplus(mlp(params["mlp_sldn"], x)[..., 0])


def predict_size(params, flow_h):
    """-> remaining fraction of flow size in [0, 1]."""
    return jax.nn.sigmoid(mlp(params["mlp_size"], flow_h)[..., 0])


def predict_queue(params, link_h):
    """-> queue length, log1p(bytes/1KB) scale (>= 0)."""
    return jax.nn.softplus(mlp(params["mlp_queue"], link_h)[..., 0])


# ---------------------------------------------------------------- one event
def temporal_update(params, cfg: M4Config, f_h, l_h, dt_f, dt_l,
                    f_feat, l_feat, cfg_vec, ref_impl=False):
    """GRU-1 / GRU-A temporal advance of snapshot states (`ref_impl=True`
    runs the seed program: two independent reference cells)."""
    from ..kernels import dispatch
    mode = "xla" if ref_impl else dispatch.resolve_mode(cfg.kernel_mode)
    Bf, Bl = f_h.shape[0], l_h.shape[0]
    cf = jnp.broadcast_to(cfg_vec, (Bf, cfg_vec.shape[-1]))
    cl = jnp.broadcast_to(cfg_vec, (Bl, cfg_vec.shape[-1]))
    xin_f = jnp.concatenate([time_feat(dt_f)[:, None], f_feat, cf], -1)
    xin_l = jnp.concatenate([time_feat(dt_l)[:, None], l_feat, cl], -1)
    if ref_impl:
        from ..nn.layers import gru_cell as gru_ref
        return (gru_ref(params["gru1"], xin_f, f_h),
                gru_ref(params["gruA"], xin_l, l_h))
    return dispatch.gru_cell_pair(params["gru1"], params["gruA"],
                                  xin_f, f_h, xin_l, l_h, mode=mode)


def spatial_update(params, cfg: M4Config, f_h, l_h, edge_f, edge_l, edge_mask,
                   cfg_vec, ref_impl=False):
    """GNN + GRU-2/GRU-B state refresh (`ref_impl` as in `gnn_forward`)."""
    from ..kernels import dispatch
    mode = "xla" if ref_impl else dispatch.resolve_mode(cfg.kernel_mode)
    gf, gl = gnn_forward(params, cfg, f_h, l_h, edge_f, edge_l, edge_mask,
                         ref_impl=ref_impl)
    Bf, Bl = f_h.shape[0], l_h.shape[0]
    cf = jnp.broadcast_to(cfg_vec, (Bf, cfg_vec.shape[-1]))
    cl = jnp.broadcast_to(cfg_vec, (Bl, cfg_vec.shape[-1]))
    if ref_impl:   # seed program: two independent reference cells
        from ..nn.layers import gru_cell as gru_ref
        f_new = gru_ref(params["gru2"], jnp.concatenate([gf, cf], -1), f_h)
        l_new = gru_ref(params["gruB"], jnp.concatenate([gl, cl], -1), l_h)
        return f_new, l_new
    return dispatch.gru_cell_pair(params["gru2"], params["gruB"],
                                  jnp.concatenate([gf, cf], -1), f_h,
                                  jnp.concatenate([gl, cl], -1), l_h,
                                  mode=mode)
