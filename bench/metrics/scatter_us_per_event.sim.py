"""Device time of m4's `m4.scatter` scope per event (us): the scatter-back
of the snapshot's states and times into the arenas, and the event flow's
arrived, done and FCT writes. The union of the ops whose name stack
holds the scope, per recorded iteration of the scan body; see
`bench.layers`."""
from bench.layers import scope_us_per_step


def read(ctx):
    return scope_us_per_step(ctx, "m4.scatter")
