"""Pure-jnp oracle for the bipartite GraphSAGE round: the segment-sum
form (`bipartite_round_ref`) that the incidence-matmul and Pallas paths
are tested against."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def incidence_from_edges(edge_f, edge_l, edge_mask, SF, SL):
    """Edge list -> dense 0/1 incidence matrix (SF, SL)."""
    m = jnp.zeros((SF, SL), jnp.float32)
    return m.at[edge_f, edge_l].add(edge_mask)


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
