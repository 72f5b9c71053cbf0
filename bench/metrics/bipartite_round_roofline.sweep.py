"""Roofline share of the bipartite_round kernel in a batched sweep (%). Under
`vmap` each kernel call of a scan step does the work of every scenario
of the batch, so its least time is the batch times the per-scenario
operations and bytes of `bench.flops.m4_kernel_calls`; see
`bench.roofline`."""
from bench.roofline import m4_kernel_share


def read(ctx):
    share = m4_kernel_share(ctx, "bipartite_round", "bipartite_round_pallas")
    return None if share is None else share * ctx["config"]["batch"]
