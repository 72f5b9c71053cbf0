"""Device time of m4's `m4.snapshot` scope per event (us): the snapshot
build: its flows and links from the occupancy arenas, and the gathers of
their hidden states, features and times. The union of the ops whose name
stack holds the scope, per recorded iteration of the scan body; see
`bench.layers`."""
from bench.layers import scope_us_per_step


def read(ctx):
    return scope_us_per_step(ctx, "m4.snapshot")
