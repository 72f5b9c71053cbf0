"""Device time of m4's `m4.spatial` scope per lane-event of a batched
sweep (us): the GraphSAGE rounds and the GRU pair after them. The
scope's time per recorded iteration of the vmapped scan body
(`bench.layers`), over the batch: each iteration advances every scenario
of the batch by one event."""
from bench.layers import scope_us_per_step


def read(ctx):
    per_step = scope_us_per_step(ctx, "m4.spatial")
    return None if per_step is None else per_step / ctx["config"]["batch"]
