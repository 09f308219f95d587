"""Share of the roofline of the mesh step's two Pallas passes: the least
time of the window's shard-steps (steps x data shards) by the work model
of one n_grad x n_expand x D train pass (``workmodel.train_pass``, the
work ``train_pass.roofline`` counts), over the summed device time of the
matvec and vecmat kernel events on the cell's chips."""
from chipbench import meshtrace, readers, workmodel
from chipbench.harness import log


def read(ctx):
    if "steps" not in ctx.stash or "n_data" not in ctx.stash:
        return None
    t_f = readers.kernel_s(ctx, meshtrace.MATVEC)
    t_g = readers.kernel_s(ctx, meshtrace.VECMAT)
    if not t_f or not t_g:
        return None
    shard_steps = ctx.stash["steps"] * ctx.stash["n_data"]
    t_min, bound = workmodel.min_seconds(
        readers.train_work(ctx), ctx.peak.flops_per_s,
        ctx.peak.hbm_bytes_per_s)
    log(f"mesh_pass: {shard_steps} shard-steps; matvec {t_f:.6f} s, vecmat "
        f"{t_g:.6f} s; {bound}-bound")
    return 100.0 * shard_steps * t_min / (t_f + t_g)
