"""Device time of m4's `m4.heads` scope per event (us): the MLP heads: the
arriving flow's initial state, the slowdown prediction and the new
departure times. The union of the ops whose name stack holds the scope,
per recorded iteration of the scan body; see `bench.layers`."""
from bench.layers import scope_us_per_step


def read(ctx):
    return scope_us_per_step(ctx, "m4.heads")
