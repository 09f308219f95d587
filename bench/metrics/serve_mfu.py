"""The whole serve call's share of the chip's peak: serving FLOPs of the
real queries (work model) over the device's busy time over the bf16
peak."""
from chipbench import readers


def read(ctx):
    if "q_per_call" not in ctx.stash or not ctx.busy_s:
        return None
    flops = sum(w.flops for w in readers.serve_works(ctx))
    return 100.0 * flops / ctx.busy_s / ctx.peak.flops_per_s
