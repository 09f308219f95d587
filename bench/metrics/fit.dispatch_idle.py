"""Device idle time in the traced training window while the host was
dispatching an epoch or waiting for it: the innermost ``dsekl.`` span over
the gap is ``dsekl.epoch.dispatch``, ``dsekl.epoch.wait`` or
``dsekl.epoch`` itself (``chipbench.spans``); % of the window."""
from chipbench import spans


def read(ctx):
    return spans.idle_share(ctx, "dispatch")
