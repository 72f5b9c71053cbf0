"""jit'd wrapper: padding to MXU-aligned shapes + multi-round driver used by
`repro.core.model.gnn_forward` when `repro.kernels.dispatch` resolves to a
Pallas mode ("pallas" on TPU, "interpret" elsewhere).

Two steps: `stage_round` lays one round's weights out as the kernel takes
them, and `bipartite_round_staged` pads the embeddings and calls the
kernel. A scan that runs the rounds every step stages once, outside the
loop (`repro.kernels.dispatch.stage_params`); `bipartite_round` does both
per call. The kernel takes the incidence M (SF, SL) as an input:
`bipartite_rounds` builds it once per event with `ref.incidence_from_edges`
(the builder "xla" mode uses too) and hands the same M to every round."""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import bipartite_round_pallas
from .ref import incidence_from_edges


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def stage_round(wf, wl, bf, bl):
    """Kernel layout of one round's weights: wf/wl (2G, G) -> (2Gp, Gp)
    with the [self; agg] halves at rows 0 and Gp, bf/bl (G,) -> (1, Gp),
    G rounded up to 128, zeros elsewhere."""
    G = wf.shape[1]
    Gp = G + ((-G) % 128)

    def halves(w):
        wp = jnp.zeros((2 * Gp, Gp), w.dtype)
        return wp.at[:G, :G].set(w[:G]).at[Gp:Gp + G, :G].set(w[G:])

    return {"wf": halves(wf), "wl": halves(wl),
            "bf": _pad_to(bf, 128, 0)[None], "bl": _pad_to(bl, 128, 0)[None]}


def _round_on_incidence(f_emb, l_emb, m, w, *, interpret):
    """One round on the incidence `m` and weights staged by `stage_round`:
    pads the embeddings (·, G) to the staged width, runs the kernel,
    slices back."""
    G = f_emb.shape[1]
    fp = _pad_to(f_emb, 128, 1)
    lp = _pad_to(l_emb, 128, 1)
    fo, lo = bipartite_round_pallas(fp, lp, m, w["wf"], w["wl"], w["bf"],
                                    w["bl"], interpret=interpret)
    return fo[:, :G], lo[:, :G]


def bipartite_round_staged(f_emb, l_emb, edge_f, edge_l, edge_mask, w, *,
                           interpret=True):
    """One round on weights staged by `stage_round`, from the edge list."""
    m = incidence_from_edges(edge_f, edge_l, edge_mask, f_emb.shape[0],
                             l_emb.shape[0])
    return _round_on_incidence(f_emb, l_emb, m, w, interpret=interpret)


def bipartite_round(f_emb, l_emb, edge_f, edge_l, edge_mask, wf, wl, bf, bl,
                    *, interpret=True):
    """Drop-in replacement for ref.bipartite_round_ref via the Pallas kernel."""
    return bipartite_round_staged(f_emb, l_emb, edge_f, edge_l, edge_mask,
                                  stage_round(wf, wl, bf, bl),
                                  interpret=interpret)


def bipartite_rounds(staged_layers, f, l, edge_f, edge_l, edge_mask, *,
                     interpret=True):
    """Multi-round GNN used by m4's spatial model, on `stage_round`s: the
    incidence is built once and every round reads it."""
    m = incidence_from_edges(edge_f, edge_l, edge_mask, f.shape[0],
                             l.shape[0])
    for w in staged_layers:
        f, l = _round_on_incidence(f, l, m, w, interpret=interpret)
    return f, l
