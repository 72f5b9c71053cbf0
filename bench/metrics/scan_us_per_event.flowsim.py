"""Device time of flowSim's event-scan programs per simulated event (us):
the union of the time the chip spent inside the `_event_scan` programs,
over the events of the traced call."""
from bench import trace


def read(ctx):
    busy = trace.module_busy_s(ctx["trace"], lambda name: "event_scan" in name)
    if busy <= 0 or not ctx["events"]:
        return None
    return busy / ctx["events"] * 1e6
