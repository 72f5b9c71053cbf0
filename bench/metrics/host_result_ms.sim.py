"""Host wall time of the traced call's result (ms): the `m4.result` span
(the FCTs to the host, the slowdowns and the result object); see
`bench.layers`."""
from bench.layers import span_ms


def read(ctx):
    return span_ms(ctx, "m4.result")
