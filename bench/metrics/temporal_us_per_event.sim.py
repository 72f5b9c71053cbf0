"""Device time of m4's `m4.temporal` scope per event (us): the temporal GRU
advance of the snapshot's flows and links, its kernel calls and their
padding. The union of the ops whose name stack holds the scope, per
recorded iteration of the scan body; see `bench.layers`."""
from bench.layers import scope_us_per_step


def read(ctx):
    return scope_us_per_step(ctx, "m4.temporal")
