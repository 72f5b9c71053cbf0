"""Share of the batch's link-membership slots that is padding (%):
1 − ΣKᵢ / (B·K_max) over the link degrees Kᵢ of the batch's scenarios,
as the program's `m4.batch.k_pad_share` gauge (`repro.obs` registry,
set by `simulate_open_loop_batch`) holds it; nothing where the program
sets no such gauge."""


def read(ctx):
    from repro.obs import get_registry
    share = get_registry().snapshot()["gauges"].get("m4.batch.k_pad_share")
    return None if share is None else 100.0 * share
