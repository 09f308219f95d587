"""1 minus the union of device operation intervals over the traced
training window."""
from chipbench import readers


def read(ctx):
    return readers.idle_share(ctx)
