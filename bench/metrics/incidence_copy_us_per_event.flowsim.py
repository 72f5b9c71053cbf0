"""Device time of the compiled incidence copies in flowSim's event scan
per event (us): the `copy-start` and `copy-done` ops of the
`_event_scan` programs, found by op name (the compiler's copy has no
name stack), per recorded event; see `bench.flowsim_layers`. Nothing
where the program names no `flowsim.advance` scope to count events by."""
from bench.flowsim_layers import copy_us_per_event


def read(ctx):
    return copy_us_per_event(ctx)
