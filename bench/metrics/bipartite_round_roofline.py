"""Roofline share of the GraphSAGE round kernel (%); see `bench.roofline`."""
from bench.roofline import m4_kernel_share


def read(ctx):
    return m4_kernel_share(ctx, "bipartite_round", "bipartite_round_pallas")
