"""Shared arithmetic of m4's kernel rooflines.

A kernel's share of its roofline is the least time the chip could take
for the work (the larger of operations over peak FLOP/s and bytes over
peak bandwidth, from `bench.flops` at the model sizes, with no tile
padding) over the kernel's device time in the trace.

Every event step calls each of the kernel's instructions once, so the
share is taken per step: the least time of one step's calls over the
sum, across the instructions, of each one's mean device time per
recorded call. A traced call of many thousand steps holds millions of
device events, and the TPU tracer does not record every one of them;
the mean per recorded call is the same whether or not some calls went
unrecorded, where a count of calls times the least time over all the
recorded time would read low by the share left out."""
from bench import trace


def m4_kernel_share(ctx, kernel, op_base):
    by_name = trace.op_time_by_name(
        ctx["trace"], lambda base, name, op: base == op_base)
    if ctx["peak"] is None or not by_name:
        return None
    per_step = ctx["flops"].m4_kernel_calls(ctx["config"]["model"])[kernel]
    if (len(by_name) != len(per_step)
            or any(n > ctx["events"] or s <= 0 for s, n in by_name.values())):
        return None          # calls the step does not account for
    peak = ctx["peak"]
    least = sum(max(f / peak["bf16_flops"], b / peak["hbm_bytes_per_s"])
                for f, b in per_step)
    return 100.0 * least / sum(s / n for s, n in by_name.values())
