"""Host wall time of the traced call's scenario build (ms): the
`m4.build` span (`make_static`, the arrival order and the uploads);
see `bench.layers`."""
from bench.layers import span_ms


def read(ctx):
    return span_ms(ctx, "m4.build")
