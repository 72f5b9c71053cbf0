"""Operations and bytes of m4's kernels and of its whole event step,
counted from the configuration's shapes (no tile padding). Float32
throughout: 4 bytes an element.

A matmul of (m, k) by (k, n) counts 2·m·k·n operations. Elementwise
work (gates, activations, bias adds) is counted too; it is small beside
the matmuls.

Bytes are those a call has to move that the previous call of the scan
did not: its activations in and out. Operands that stay the same through
the whole scan (weights and biases) need one
read per scan, not per call, and XLA keeps them in on-chip memory across
the calls (the traces show them prefetched into memory space 1 before
the loop), so they are not counted per call.
"""
from __future__ import annotations

F32 = 4


def _mm(m, k, n):
    return 2 * m * k * n


def gru_cell(rows: int, d_in: int, hidden: int) -> tuple:
    """(flops, bytes) of one fused GRU cell over `rows` rows: the input and
    hidden matmuls to the three gates, then the gate arithmetic (about 12
    operations per hidden unit). Bytes: x and h read, the new h written."""
    flops = (_mm(rows, d_in, 3 * hidden) + _mm(rows, hidden, 3 * hidden)
             + 12 * rows * hidden)
    return flops, (rows * d_in + 2 * rows * hidden) * F32


def bipartite_round(snap_flows: int, snap_links: int, dim: int) -> tuple:
    """(flops, bytes) of one GraphSAGE round on the snapshot's incidence
    matrix M (SF, SL): agg_f = M·l, agg_l = Mᵀ·f, then [x ; agg]·W (2G, G)
    + b and a relu on both sides. Bytes: f, l and M read, both outputs
    written."""
    SF, SL, G = snap_flows, snap_links, dim
    flops = (_mm(SF, SL, G) + _mm(SL, SF, G) + _mm(SF, 2 * G, G)
             + _mm(SL, 2 * G, G) + 2 * (SF + SL) * G)
    return flops, (2 * SF * G + 2 * SL * G + SF * SL) * F32


def _mlp(rows, sizes):
    return sum(_mm(rows, a, b) for a, b in zip(sizes[:-1], sizes[1:]))


def m4_event(m: dict) -> dict:
    """Operations of one m4 event step at the model sizes `m` (the keys of
    `M4Config`), by part. The snapshot holds SF flows and SL links; every
    part runs on all slots, as the step does."""
    H, G, M, C = m["hidden"], m["gnn_dim"], m["mlp_hidden"], m["cfg_dim"]
    SF, SL, R = m["snap_flows"], m["snap_links"], m["gnn_layers"]
    FF, LF = 3, 1          # static flow / link features
    gru = (gru_cell(SF, 1 + FF + C, H)[0] + gru_cell(SL, 1 + LF + C, H)[0]
           + gru_cell(SF, G + C, H)[0] + gru_cell(SL, G + C, H)[0])
    return {
        "flow_init": _mlp(1, [FF + C, M, H]),
        "gru": gru,
        "projections": _mm(SF, H, G) + _mm(SL, H, G),
        "gnn": R * bipartite_round(SF, SL, G)[0],
        "sldn_head": _mlp(SF, [H + 1 + C, M, M, 1]),
    }


def m4_event_flops(m: dict) -> int:
    return int(sum(m4_event(m).values()))


def m4_kernel_calls(m: dict) -> dict:
    """Kernel calls of one event step, and the (flops, bytes) of each."""
    H, G, C = m["hidden"], m["gnn_dim"], m["cfg_dim"]
    SF, SL = m["snap_flows"], m["snap_links"]
    return {
        "fused_gru": [gru_cell(SF, 1 + 3 + C, H), gru_cell(SL, 1 + 1 + C, H),
                      gru_cell(SF, G + C, H), gru_cell(SL, G + C, H)],
        "bipartite_round": [bipartite_round(SF, SL, G)] * m["gnn_layers"],
    }
