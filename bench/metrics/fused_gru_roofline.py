"""Roofline share of the fused GRU kernel (%); see `bench.roofline`."""
from bench.roofline import m4_kernel_share


def read(ctx):
    return m4_kernel_share(ctx, "fused_gru", "gru_cell_pallas")
