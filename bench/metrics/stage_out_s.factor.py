"""Host seconds per factor after the device copy back: the
``repro.factor.widen`` span (the store widened to f64) inside each
``repro.factor`` span of the window, averaged over the factors."""
from bench import spans


def read(ctx):
    return spans.phase_s(ctx, ("widen",))
