"""Host-to-device GB/s of the factors in the window: the ``bytes`` of the
``repro.factor.h2d`` spans (the tile store, in the compute dtype) over
their seconds, both summed."""
from bench import spans


def read(ctx):
    return spans.link_gbs(ctx, "h2d")
