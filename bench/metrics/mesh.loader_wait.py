"""Device idle time of the traced mesh window while the fit waited for the
prefetcher's blocks: the innermost ``dsekl.`` span of the fit's own
thread over the gap is ``dsekl.mesh.wait`` (``chipbench.meshtrace``); %
of the window.  Whether ``MeshPrefetcher`` hides the gather and the
host-to-device copies."""
from chipbench import meshtrace


def read(ctx):
    return meshtrace.loader_wait(ctx)
