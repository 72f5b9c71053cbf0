"""Device time of flowSim's `flowsim.rowmin` scope per event (us): the
masked row-min of every water-filling round (`masked_rowmin_pallas` on
the TPU). The union of the ops whose name stack holds the scope, per
recorded event; see `bench.flowsim_layers`."""
from bench.flowsim_layers import scope_us_per_event


def read(ctx):
    return scope_us_per_event(ctx, "flowsim.rowmin")
