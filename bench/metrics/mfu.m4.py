"""Whole-step model FLOP/s utilization of m4 (%): the operations of one
event step counted from the model sizes (`bench.flops.m4_event_flops`),
times the events of the traced call, over its wall time and the chip's
bfloat16 peak. The step runs its float32 matmuls at `highest` precision,
several bfloat16 passes each, so this share cannot approach 100."""


def read(ctx):
    if ctx["peak"] is None or ctx["window_s"] <= 0:
        return None
    flops = ctx["flops"].m4_event_flops(ctx["config"]["model"])
    return (100.0 * flops * ctx["events"] / ctx["window_s"]
            / ctx["peak"]["bf16_flops"])
