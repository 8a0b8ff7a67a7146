"""Device-to-host GB/s of the factors in the window: the ``bytes`` of the
``repro.factor.d2h`` spans (the factored store, in the compute dtype)
over their seconds, both summed."""
from bench import spans


def read(ctx):
    return spans.link_gbs(ctx, "d2h")
