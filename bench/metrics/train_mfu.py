"""The whole training step's share of the chip's peak: train-pass FLOPs
of the window's steps (work model, whatever implements them) over the
traced window over the bf16 peak."""
from chipbench import readers


def read(ctx):
    if "steps" not in ctx.stash or not ctx.trace_window_s:
        return None
    flops = ctx.stash["steps"] * readers.train_work(ctx).flops
    return 100.0 * flops / ctx.trace_window_s / ctx.peak.flops_per_s
