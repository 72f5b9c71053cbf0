"""Device time of m4's `m4.spatial` scope per event (us): the GraphSAGE
rounds over the snapshot and the GRU pair after them. The union of the
ops whose name stack holds the scope, per recorded iteration of the scan
body; see `bench.layers`."""
from bench.layers import scope_us_per_step


def read(ctx):
    return scope_us_per_step(ctx, "m4.spatial")
