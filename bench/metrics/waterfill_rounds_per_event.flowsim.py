"""Water-filling rounds per event of the last flowSim call (rounds): the
program's `flowsim.waterfill.rounds_per_event` gauge (`repro.obs`
registry, set by `run_flowsim_fast`); nothing where the program sets no
such gauge."""


def read(ctx):
    from repro.obs import get_registry
    gauges = get_registry().snapshot()["gauges"]
    return gauges.get("flowsim.waterfill.rounds_per_event")
