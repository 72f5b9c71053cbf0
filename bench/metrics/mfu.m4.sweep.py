"""Whole-step model FLOP/s utilization of m4 in a batched sweep (%): the
operations of one event step counted from the model sizes
(`bench.flops.m4_event_flops`), times the events of every scenario of
the traced call (padded events not counted), over its wall time and the
chip's bfloat16 peak."""


def read(ctx):
    if ctx["peak"] is None or ctx["window_s"] <= 0:
        return None
    flops = ctx["flops"].m4_event_flops(ctx["config"]["model"])
    return (100.0 * flops * ctx["events"] / ctx["window_s"]
            / ctx["peak"]["bf16_flops"])
