"""Device time of the event-scan programs per lane-event of a batched
sweep (us): the union of the time the chip spent inside m4's
`_open_loop_scan*` programs, over the events of every scenario of the
traced call (padded events not counted)."""
from bench import trace


def read(ctx):
    busy = trace.module_busy_s(
        ctx["trace"], lambda name: "open_loop_scan" in name)
    if busy <= 0 or not ctx["events"]:
        return None
    return busy / ctx["events"] * 1e6
