"""Device time of the mesh step's all-reduce events (matched by HLO
opcode, ``chipbench.meshtrace.ALL_REDUCE``) summed over the cell's
chips, as a % of their busy time summed over the same chips."""
from chipbench import meshtrace, readers


def read(ctx):
    if not ctx.busy_s:
        return None
    t = readers.kernel_s(ctx, meshtrace.ALL_REDUCE)
    if t is None:
        return None
    return 100.0 * t / (ctx.busy_s * ctx.chips)
