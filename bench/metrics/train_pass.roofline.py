"""Share of the fused train-pass kernel's roofline: the least time of the
window's steps by the work model (``chipbench.workmodel.train_pass``),
over the summed device time of the kernel's events in the trace."""
from chipbench import readers, workmodel
from chipbench.harness import log

# The fused Pallas train pass (``ops.kernel_dual_pass`` -> ``train_pass_pallas``)
# as the trace names it: ``%kernel_dual_pass.<n> = ... custom-call(...)``.
PATTERN = r"^%kernel_dual_pass[.\d]* = .*tpu_custom_call"


def read(ctx):
    if "steps" not in ctx.stash:
        return None
    t_k = readers.kernel_s(ctx, PATTERN)
    if not t_k:
        return None
    t_min, bound = workmodel.min_seconds(
        readers.train_work(ctx), ctx.peak.flops_per_s,
        ctx.peak.hbm_bytes_per_s)
    log(f"train_pass: {ctx.stash['kernel_events'][PATTERN]} kernel events "
        f"for {ctx.stash['steps']} steps, {t_k:.6f} s; {bound}-bound")
    return 100.0 * ctx.stash["steps"] * t_min / t_k
