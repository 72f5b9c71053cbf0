"""Pallas TPU kernel: one fused GraphSAGE round on the bipartite flow-link
snapshot graph.

TPU adaptation (DESIGN.md §3): a GPU implementation scatters with atomics;
on TPU we reformulate the irregular gather/scatter as **incidence-matrix
matmuls** that run on the MXU:

    agg_f = M   @ l_emb        # link -> flow messages   (M: SF x SL, 0/1)
    agg_l = M^T @ f_emb        # flow -> link messages
    f_new = relu([f_emb ; agg_f] @ Wf + bf)
    l_new = relu([l_emb ; agg_l] @ Wl + bl)

M is an input, not built here: `ops.bipartite_rounds` builds it once per
event from the edge list (`ref.incidence_from_edges`, a one-hot
contraction) and passes the same M to all rounds.

Everything for one snapshot fits VMEM (SF=64, SL=128, G=304 padded:
~3 MB at f32), so the whole round is a single fused kernel; the grid tiles
the output feature dimension to keep per-program VMEM bounded and MXU
shapes 128-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _round_kernel(f_ref, l_ref, m_ref, wf_ref, wl_ref, bf_ref, bl_ref,
                  fo_ref, lo_ref):
    """One output-feature tile of the fused round.

    f_ref: (SF, G), l_ref: (SL, G), m_ref: (SF, SL),
    wf_ref/wl_ref: (2G, TG) tile, bf_ref/bl_ref: (1, TG),
    fo_ref: (SF, TG), lo_ref: (SL, TG).
    """
    f = f_ref[...]
    l = l_ref[...]
    m = m_ref[...]
    agg_f = jnp.dot(m, l, preferred_element_type=jnp.float32)       # (SF, G)
    agg_l = jnp.dot(m.T, f, preferred_element_type=jnp.float32)     # (SL, G)
    G = f.shape[1]
    wf, wl = wf_ref[...], wl_ref[...]
    fo = jnp.dot(f, wf[:G], preferred_element_type=jnp.float32) \
        + jnp.dot(agg_f, wf[G:], preferred_element_type=jnp.float32) \
        + bf_ref[...]
    lo = jnp.dot(l, wl[:G], preferred_element_type=jnp.float32) \
        + jnp.dot(agg_l, wl[G:], preferred_element_type=jnp.float32) \
        + bl_ref[...]
    fo_ref[...] = jnp.maximum(fo, 0.0).astype(fo_ref.dtype)
    lo_ref[...] = jnp.maximum(lo, 0.0).astype(lo_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_g", "interpret"))
def bipartite_round_pallas(f_emb, l_emb, m, wf, wl, bf, bl, *,
                           tile_g: int = 128, interpret: bool = True):
    """f_emb: (SF, G), l_emb: (SL, G), m: (SF, SL) incidence (float),
    wf/wl: (2G, G), bf/bl: (G,) or (1, G). G must be a multiple of tile_g
    (ops.py pads). Returns (f_new, l_new)."""
    SF, G = f_emb.shape
    SL = l_emb.shape[0]
    assert G % tile_g == 0, (G, tile_g)
    grid = (G // tile_g,)
    bf2, bl2 = bf.reshape(1, -1), bl.reshape(1, -1)

    return pl.pallas_call(
        _round_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((SF, G), lambda j: (0, 0)),      # f_emb (whole)
            pl.BlockSpec((SL, G), lambda j: (0, 0)),      # l_emb (whole)
            pl.BlockSpec((SF, SL), lambda j: (0, 0)),     # incidence
            pl.BlockSpec((2 * G, tile_g), lambda j: (0, j)),
            pl.BlockSpec((2 * G, tile_g), lambda j: (0, j)),
            pl.BlockSpec((1, tile_g), lambda j: (0, j)),
            pl.BlockSpec((1, tile_g), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((SF, tile_g), lambda j: (0, j)),
            pl.BlockSpec((SL, tile_g), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((SF, G), f_emb.dtype),
            jax.ShapeDtypeStruct((SL, G), l_emb.dtype),
        ],
        interpret=interpret,
    )(f_emb, l_emb, m, wf, wl, bf2, bl2)
