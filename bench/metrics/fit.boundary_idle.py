"""Device idle time in the traced training window while the host was at
an epoch boundary of ``fit_loop``: the innermost ``dsekl.`` span over the
gap is ``dsekl.epoch.plan``, ``.host_delta``, ``.eval``, ``.hooks`` or
``.snapshot`` (``chipbench.spans``); % of the window."""
from chipbench import spans


def read(ctx):
    return spans.idle_share(ctx, "boundary")
