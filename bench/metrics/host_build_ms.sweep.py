"""Host wall time of the traced call's batch build (ms): the `m4.build`
span of `run_many`, `stack_scenarios` (each scenario's `make_static`
padded to the batch's largest N, L and K, and the stacked uploads); see
`bench.layers`."""
from bench.layers import span_ms


def read(ctx):
    return span_ms(ctx, "m4.build")
