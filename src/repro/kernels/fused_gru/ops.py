"""jit'd wrapper: pads (B, Din, H) to MXU-aligned shapes, calls the kernel,
slices back. Gate-order-preserving padding of the 3H axis.

Two steps: `stage_gru` lays one GRU's weights out as the kernel takes
them, and `gru_cell_staged` pads the activations and calls the kernel.
A scan that runs the cell every step stages once, outside the loop
(`repro.kernels.dispatch.stage_params`); `gru_cell` does both per call.
"""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import gru_cell_pallas


def _pad_gates(w, H, Hp):
    """(D, 3H) -> (Dp?, 3Hp), keeping the r/z/n thirds aligned."""
    D = w.shape[0]
    out = jnp.zeros((D, 3 * Hp), w.dtype)
    for g in range(3):
        out = out.at[:, g * Hp:g * Hp + H].set(w[:, g * H:(g + 1) * H])
    return out


def stage_gru(wi, wh, bi, bh):
    """Kernel layout of one GRU's weights: wi (Din, 3H) -> (Dp, 3Hp), wh
    (H, 3H) -> (Hp, 3Hp), bi/bh (3H,) -> (1, 3Hp), Din and H rounded up
    to 128, each gate third at a multiple of Hp, zeros elsewhere."""
    Din, H = wi.shape[0], wh.shape[0]
    Dp, Hp = Din + ((-Din) % 128), H + ((-H) % 128)
    return {
        "wi": jnp.zeros((Dp, 3 * Hp), wi.dtype).at[:Din].set(
            _pad_gates(wi, H, Hp)),
        "wh": jnp.zeros((Hp, 3 * Hp), wh.dtype).at[:H].set(
            _pad_gates(wh, H, Hp)),
        "bi": _pad_gates(bi[None], H, Hp),
        "bh": _pad_gates(bh[None], H, Hp),
    }


def gru_cell_staged(x, h, w, *, tile_b=128, interpret=True):
    """GRU cell on weights staged by `stage_gru`: pads x (B, Din) and
    h (B, H) to the staged widths, runs the kernel, slices back."""
    B, Din = x.shape
    H = h.shape[1]
    Bp = B + ((-B) % tile_b)
    Dp, Hp = w["wi"].shape[0], w["wh"].shape[0]
    xp = jnp.zeros((Bp, Dp), x.dtype).at[:B, :Din].set(x)
    hp = jnp.zeros((Bp, Hp), h.dtype).at[:B, :H].set(h)
    out = gru_cell_pallas(xp, hp, w["wi"], w["wh"], w["bi"], w["bh"],
                          tile_b=tile_b, interpret=interpret)
    return out[:B, :H]


def gru_cell(x, h, wi, wh, bi, bh, *, tile_b=128, interpret=True):
    return gru_cell_staged(x, h, stage_gru(wi, wh, bi, bh), tile_b=tile_b,
                           interpret=interpret)
