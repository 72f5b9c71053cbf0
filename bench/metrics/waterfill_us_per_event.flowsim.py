"""Device time of flowSim's `flowsim.waterfill` scope per event (us): the
whole water-filling loop, its link sums, row-min and rate updates. The
union of the ops whose name stack holds the scope, per recorded event;
see `bench.flowsim_layers`."""
from bench.flowsim_layers import scope_us_per_event


def read(ctx):
    return scope_us_per_event(ctx, "flowsim.waterfill")
