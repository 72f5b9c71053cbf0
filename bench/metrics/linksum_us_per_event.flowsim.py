"""Device time of flowSim's `flowsim.linksum` scope per event (us): the
two link sums of every water-filling round, the unfixed flows and the
fixed flows' rates over the incidence. The union of the ops whose name
stack holds the scope, per recorded event; see `bench.flowsim_layers`."""
from bench.flowsim_layers import scope_us_per_event


def read(ctx):
    return scope_us_per_event(ctx, "flowsim.linksum")
