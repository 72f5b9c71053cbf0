"""Share of the traced sweep call's window (%) in which no operation ran
on the chip: 1 - the union of the device's operation intervals / the
window; the host's batch build is most of it."""


def read(ctx):
    if ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
