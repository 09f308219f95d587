"""Share of the serving matvec kernel's roofline: the least time of the
real queries of every serve call against the real support rows by the
work model (``chipbench.workmodel.serve``), over the summed device time
of the kernel's events in the trace."""
from chipbench import readers, workmodel
from chipbench.harness import log

# The engine's Pallas matvec (``ops.kernel_matvec_tiled``) as the trace
# names it: ``%kernel_matvec_tiled.<n> = ... custom-call(...)``.
PATTERN = r"^%kernel_matvec_tiled[.\d]* = .*tpu_custom_call"


def read(ctx):
    if "q_per_call" not in ctx.stash:
        return None
    t_k = readers.kernel_s(ctx, PATTERN)
    if not t_k:
        return None
    t_min, bound = workmodel.min_seconds_sum(
        readers.serve_works(ctx), ctx.peak.flops_per_s,
        ctx.peak.hbm_bytes_per_s)
    log(f"serve_matvec: {ctx.stash['kernel_events'][PATTERN]} kernel events "
        f"for {ctx.stash['serve_calls']} serve calls, {t_k:.6f} s; "
        f"{bound}-bound")
    return 100.0 * t_min / t_k
