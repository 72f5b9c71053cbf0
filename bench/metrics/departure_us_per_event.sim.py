"""Device time of m4's `m4.departure` scope per event (us): the departure
select: the next arrival, the argmin over the flows' predicted
departures and the event's choice. The union of the ops whose name stack
holds the scope, per recorded iteration of the scan body; see
`bench.layers`."""
from bench.layers import scope_us_per_step


def read(ctx):
    return scope_us_per_step(ctx, "m4.departure")
