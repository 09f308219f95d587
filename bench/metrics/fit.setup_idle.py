"""Device idle time in the traced training window while the host was
setting up a fit: the innermost ``dsekl.`` span over the gap is
``dsekl.fit.setup`` (``chipbench.spans``); % of the window."""
from chipbench import spans


def read(ctx):
    return spans.idle_share(ctx, "setup")
