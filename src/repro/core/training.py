"""Dense-supervision training losses of m4 (§3.3).

Teacher-forced `lax.scan` over the ground-truth event sequence of each
simulation. Per event: temporal GRU advance -> query remaining size & queue
length (dense losses) -> GNN spatial update -> query FCT slowdown. Combined
L1 loss over the three heads.

This module owns the *math* (`event_scan_losses`, `combined_loss`); the
production training pipeline — cached dataset store, shape-bucketed
compilation, checkpoint/resume, schedules, eval — lives in `repro.train`
(docs/TRAINING.md). `train_m4` survives as a thin convenience wrapper
over `repro.train.fit` with the seed-faithful per-sim update schedule:
one optimizer update per sim per epoch, now compiled once per bucket
*shape* instead of once per sim shape (compiles counted in
`repro.train.TRACE_COUNTS`, the training mirror of
`core.simulate.TRACE_COUNTS`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..optim import adamw_update, clip_by_global_norm
from .model import (MATMUL_PRECISION, M4Config, predict_queue,
                    predict_size, predict_sldn, spatial_update,
                    temporal_update)


def _as_jnp(b):
    return {k: jnp.asarray(v) for k, v in b.__dict__.items()}


def event_scan_losses(params, cfg: M4Config, b):
    """Scan all K events of one sim; returns per-head mean L1 losses."""
    with jax.default_matmul_precision(MATMUL_PRECISION):
        return _event_scan_losses(params, cfg, b)


def _event_scan_losses(params, cfg: M4Config, b):
    import dataclasses
    # training differentiates through the event step: force the jnp kernel
    # path — the Pallas kernels (repro.kernels.*) define no VJP, so a cfg
    # or REPRO_KERNELS resolving to pallas/interpret would crash grad
    cfg = dataclasses.replace(cfg, kernel_mode="xla")
    N, L = b["flow_links"].shape[0], b["link_feat"].shape[0]
    H = params["gru1"]["wh"].shape[0]
    cfg_vec = b["cfg_vec"]

    # initial link states from bandwidth (paper: init from link bandwidth).
    # arenas carry a dump row (index N / L) absorbing masked-slot scatters.
    l_in = jnp.concatenate(
        [b["link_feat"], jnp.broadcast_to(cfg_vec, (L, cfg_vec.shape[0]))], -1)
    from ..nn import mlp
    link_h0 = jnp.concatenate(
        [jnp.tanh(mlp(params["link_init"], l_in)),
         jnp.zeros((1, H), jnp.float32)], 0)
    flow_h0 = jnp.zeros((N + 1, H), jnp.float32)

    carry0 = dict(flow_h=flow_h0, link_h=link_h0,
                  flow_last=jnp.zeros((N + 1,), jnp.float32),
                  link_last=jnp.zeros((L + 1,), jnp.float32))

    def step(carry, ev):
        t, etype, fid = ev["t"], ev["etype"], ev["fid"]
        sf, sl = ev["snap_f"], ev["snap_l"]            # (SF,), (SL,)
        sfm, slm = ev["snap_f_mask"], ev["snap_l_mask"]
        sf_safe = jnp.where(sf >= 0, sf, N)             # dump row for pads
        sl_safe = jnp.where(sl >= 0, sl, L)
        sf_g = jnp.minimum(sf_safe, N - 1)              # clamped gathers
        sl_g = jnp.minimum(sl_safe, L - 1)

        f_h = carry["flow_h"][sf_safe]                  # (SF, H)
        l_h = carry["link_h"][sl_safe]
        f_feat = b["flow_feat"][sf_g]
        l_feat = b["link_feat"][sl_g]

        # arrival: (re)initialize slot 0 (the event flow) from its features
        fin = jnp.concatenate([b["flow_feat"][fid], cfg_vec], -1)
        h_new = jnp.tanh(mlp(params["flow_init"], fin))
        is_arr = (etype == 0)
        f_h = f_h.at[0].set(jnp.where(is_arr, h_new, f_h[0]))

        dt_f = t - carry["flow_last"][sf_safe]
        dt_f = dt_f.at[0].set(jnp.where(is_arr, 0.0, dt_f[0]))
        dt_l = t - carry["link_last"][sl_safe]

        f_h, l_h = temporal_update(params, cfg, f_h, l_h, dt_f, dt_l,
                                   f_feat, l_feat, cfg_vec)

        # dense queries on the temporally-advanced states X~(t_i)
        rem_pred = predict_size(params, f_h)
        rem_loss = (jnp.abs(rem_pred - ev["gt_remaining"]) * ev["rem_mask"]).sum()
        rem_cnt = ev["rem_mask"].sum()
        q_pred = predict_queue(params, l_h)
        q_loss = (jnp.abs(q_pred - ev["gt_queue"]) * ev["queue_mask"]).sum()
        q_cnt = ev["queue_mask"].sum()

        # spatial update on the bipartite snapshot graph
        SF, P = cfg.snap_flows, cfg.max_path
        edge_f = jnp.repeat(jnp.arange(SF, dtype=jnp.int32), P)
        f_h2, l_h2 = spatial_update(params, cfg, f_h, l_h, edge_f,
                                    ev["edge_l"], ev["edge_mask"], cfg_vec)

        # FCT slowdown query on post-GNN states
        sldn_pred = predict_sldn(params, f_h2, b["flow_feat"][sf_g, 1] * 8.0,
                                 cfg_vec)
        sldn_tgt = b["gt_sldn"][sf_g]
        if cfg.dense_sldn:
            sldn_loss = (jnp.abs(sldn_pred - sldn_tgt) * sfm).sum()
            sldn_cnt = sfm.sum()
        else:
            sldn_loss = jnp.abs(sldn_pred[0] - sldn_tgt[0]) * (etype == 1)
            sldn_cnt = (etype == 1).astype(jnp.float32)

        # write back (masked scatter)
        wf = sfm[:, None]
        flow_h = carry["flow_h"].at[sf_safe].set(
            wf * f_h2 + (1 - wf) * carry["flow_h"][sf_safe])
        wl = slm[:, None]
        link_h = carry["link_h"].at[sl_safe].set(
            wl * l_h2 + (1 - wl) * carry["link_h"][sl_safe])
        flow_last = carry["flow_last"].at[sf_safe].set(
            jnp.where(sfm > 0, t, carry["flow_last"][sf_safe]))
        link_last = carry["link_last"].at[sl_safe].set(
            jnp.where(slm > 0, t, carry["link_last"][sl_safe]))

        out = jnp.stack([rem_loss, rem_cnt, q_loss, q_cnt, sldn_loss, sldn_cnt])
        return dict(flow_h=flow_h, link_h=link_h,
                    flow_last=flow_last, link_last=link_last), out

    ev_stream = {k: b[k] for k in
                 ("t", "etype", "fid", "snap_f", "snap_f_mask", "snap_l",
                  "snap_l_mask", "edge_l", "edge_mask", "gt_remaining",
                  "rem_mask", "gt_queue", "queue_mask")}
    _, outs = jax.lax.scan(step, carry0, ev_stream)
    s = outs.sum(0)
    return {"size": s[0] / jnp.maximum(s[1], 1),
            "queue": s[2] / jnp.maximum(s[3], 1),
            "sldn": s[4] / jnp.maximum(s[5], 1)}


def combined_loss(params, cfg: M4Config, b, *, w_size=1.0, w_queue=1.0,
                  w_sldn=1.0):
    l = event_scan_losses(params, cfg, b)
    total = w_sldn * l["sldn"] + w_size * l["size"] + w_queue * l["queue"]
    return total, l


def make_train_step(cfg: M4Config, *, lr=3e-4, ablate_size=False,
                    ablate_queue=False):
    """One-sim jitted AdamW step (legacy direct API).

    Prefer `repro.train.fit`: jit keys on the sim's tensor shapes, so
    calling this across a shape-diverse corpus silently retraces per
    shape — the bucketed pipeline pads shapes away. Traces are counted
    in `repro.train.TRACE_COUNTS` ("train_step_legacy") so the retrace
    is at least visible.
    """
    w_size = 0.0 if ablate_size else 1.0
    w_queue = 0.0 if ablate_queue else 1.0

    @jax.jit
    def train_step(params, opt, b):
        from ..train.loop import TRACE_COUNTS
        TRACE_COUNTS["train_step_legacy"] += 1
        (tot, parts), grads = jax.value_and_grad(
            combined_loss, has_aux=True)(params, cfg, b, w_size=w_size,
                                         w_queue=w_queue)
        grads, gn = clip_by_global_norm(grads, 1.0)
        params, opt = adamw_update(params, grads, opt, lr=lr, weight_decay=1e-4)
        return params, opt, tot, parts, gn
    return train_step


def train_m4(batches, cfg: M4Config, *, epochs=10, lr=3e-4, seed=0,
             log=print, ablate_size=False, ablate_queue=False,
             bucket_size=8, ckpt_dir=None):
    """Convenience wrapper over the `repro.train` pipeline.

    Seed-faithful semantics: constant LR, one AdamW update per sim per
    epoch (`step_mode="per_sim"`), shuffling off — but compiled once per
    bucket shape. Returns (TrainState, history) where history is the
    structured per-head/per-epoch record (`history[i]["loss"]` etc.).
    """
    from ..train import TrainConfig, fit
    tc = TrainConfig(epochs=epochs, lr=lr, schedule="const", seed=seed,
                     bucket_size=bucket_size, step_mode="per_sim",
                     shuffle=False, ckpt_dir=ckpt_dir,
                     w_size=0.0 if ablate_size else 1.0,
                     w_queue=0.0 if ablate_queue else 1.0)
    return fit(batches, cfg, tc, log=log)
