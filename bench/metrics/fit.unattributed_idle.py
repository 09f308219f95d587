"""Device idle time in the traced training window put down to no phase of
the program: the innermost ``dsekl.`` span over the gap is ``dsekl.fit``
itself, or there is none (between fits, the traffic's own code); % of the
window.  With the other three ``fit.*_idle`` it adds up to
``device_idle.train`` (``chipbench.spans``)."""
from chipbench import spans


def read(ctx):
    return spans.idle_share(ctx, spans.UNATTRIBUTED)
