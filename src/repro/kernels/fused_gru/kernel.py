"""Pallas TPU kernel: fused GRU cell.

The unfused formulation round-trips six (B, 3H) intermediates through HBM
(two matmuls, gate splits, sigmoid/tanh, blend). Here both matmuls and all
gate nonlinearities run in one kernel with the gate tensors living in VMEM
only. Grid tiles the batch (component) dimension; weights stay resident
(Din, 3H) + (H, 3H) — ~2.5 MB at the paper sizes (H=400 padded to 512),
well under VMEM.

Gate order follows torch.nn.GRUCell: r, z, n.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gru_kernel(x_ref, h_ref, wi_ref, wh_ref, bi_ref, bh_ref, o_ref, *, H):
    x = x_ref[...]
    h = h_ref[...]
    gi = jnp.dot(x, wi_ref[...], preferred_element_type=jnp.float32) + bi_ref[...]
    gh = jnp.dot(h, wh_ref[...], preferred_element_type=jnp.float32) + bh_ref[...]
    ir, iz, in_ = gi[:, :H], gi[:, H:2 * H], gi[:, 2 * H:]
    hr, hz, hn = gh[:, :H], gh[:, H:2 * H], gh[:, 2 * H:]
    r = jax.nn.sigmoid(ir + hr)
    z = jax.nn.sigmoid(iz + hz)
    n = jnp.tanh(in_ + r * hn)
    o_ref[...] = ((1.0 - z) * n + z * h).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def gru_cell_pallas(x, h, wi, wh, bi, bh, *, tile_b: int = 128,
                    interpret: bool = True):
    """x: (B, Din), h: (B, H), wi: (Din, 3H), wh: (H, 3H), bi/bh: (3H,)
    or (1, 3H). All dims must be pre-padded (ops.py): B % tile_b == 0,
    H % 128 == 0.
    """
    B, Din = x.shape
    H = h.shape[1]
    assert B % tile_b == 0 and H % 128 == 0, (B, H)
    grid = (B // tile_b,)
    return pl.pallas_call(
        functools.partial(_gru_kernel, H=H),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, Din), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, H), lambda i: (i, 0)),
            pl.BlockSpec((Din, 3 * H), lambda i: (0, 0)),
            pl.BlockSpec((H, 3 * H), lambda i: (0, 0)),
            pl.BlockSpec((1, 3 * H), lambda i: (0, 0)),
            pl.BlockSpec((1, 3 * H), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_b, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H), h.dtype),
        interpret=interpret,
    )(x, h, wi, wh, bi.reshape(1, -1), bh.reshape(1, -1))
