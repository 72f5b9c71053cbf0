"""Pure-jnp forms of the bipartite GraphSAGE round: the segment-sum
oracle (`bipartite_round_ref`) that the incidence-matmul and Pallas paths
are tested against, the incidence builder both paths share
(`incidence_from_edges`), and the incidence-matmul rounds of "xla" mode
(`bipartite_rounds_matmul`)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def incidence_from_edges(edge_f, edge_l, edge_mask, SF, SL):
    """Edge list -> dense incidence matrix M (SF, SL): M[f, l] counts the
    unmasked edges from flow slot f to link slot l.

    The one incidence builder of the GNN, built once per event and handed
    to every round, in "xla" mode (`dispatch.gnn_rounds`) and in the Pallas
    modes (`ops.bipartite_rounds`). It is a contraction of two one-hot
    matrices, (E, SF) and (E, SL)·mask, not a scatter-add: the TPU applies
    a scatter's updates one at a time. 0/1 inputs and f32 accumulation
    keep the small integer counts exact at any matmul precision. Written
    as (loᵀ·fo)ᵀ so that under `vmap`, where the event step's `edge_f` is
    one constant for every lane, the batch axis of M stays leading
    (fo.T @ lo puts it in the middle, which the kernel's block spec
    refuses)."""
    fo = (edge_f[:, None] == jnp.arange(SF, dtype=edge_f.dtype)[None, :]
          ).astype(jnp.float32)
    lo = (edge_l[:, None] == jnp.arange(SL, dtype=edge_l.dtype)[None, :]
          ).astype(jnp.float32) * edge_mask[:, None]
    return (lo.T @ fo).T


def bipartite_round_ref(f_emb, l_emb, edge_f, edge_l, edge_mask, wf, wl, bf, bl):
    """Segment-sum GraphSAGE round. wf/wl: (2G, G); bf/bl: (G,)."""
    SL = l_emb.shape[0]
    ef = f_emb[edge_f] * edge_mask[:, None]
    agg_l = jax.ops.segment_sum(ef, edge_l, num_segments=SL)
    el = l_emb[edge_l] * edge_mask[:, None]
    agg_f = jax.ops.segment_sum(el, edge_f, num_segments=f_emb.shape[0])
    G = f_emb.shape[1]
    f_new = jax.nn.relu(jnp.concatenate([f_emb, agg_f], -1) @ wf + bf)
    l_new = jax.nn.relu(jnp.concatenate([l_emb, agg_l], -1) @ wl + bl)
    return f_new, l_new


def bipartite_rounds_matmul(layers, f_emb, l_emb, m):
    """Multi-round GraphSAGE via the incidence-matmul formulation — the
    exact math the Pallas kernel runs (agg_f = M @ l, agg_l = Mᵀ @ f), as
    plain XLA matmuls. This is the jnp hot path on CPU: building M once
    and reusing it across rounds replaces 2·rounds segment-sum scatters
    (slow row-loops on CPU) with dense MXU/SIMD-friendly matmuls."""
    for layer in layers:
        agg_f = m @ l_emb
        agg_l = m.T @ f_emb
        wf, bf = layer["wf"]["w"], layer["wf"]["b"]
        wl, bl = layer["wl"]["w"], layer["wl"]["b"]
        G = f_emb.shape[1]
        f_emb = jax.nn.relu(f_emb @ wf[:G] + agg_f @ wf[G:] + bf)
        l_emb = jax.nn.relu(l_emb @ wl[:G] + agg_l @ wl[G:] + bl)
    return f_emb, l_emb
